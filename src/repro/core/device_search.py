"""Device-resident evolutionary generation engines (``engine="device"``
and the island-model ``engine="sharded"``).

The numpy engine in :mod:`repro.core.search` prices generations through the
stacked population backends, but its generation *loop* — tournament draws,
the per-offspring split/merge/swap mutation chain, phenotype dedup, elitist
survival — still runs as per-offspring Python over host NumPy rows, forcing
a host↔device round-trip every generation.  This module compiles the ENTIRE
generation step into one jitted program over the stacked ``(K, n_layers)``
core-count and ``(K, n_slots)`` permutation matrices:

1. **tournament selection** — a row-min over the draw matrix (survivors are
   kept (rank, time, energy)-sorted, so fitness order == index order);
2. **table-gated mutation** (:func:`mutate_rows_array`) — the bottleneck
   stage picks split/merge/swap per offspring, feasibility is a gather into
   the :class:`~repro.core.search.MoveTables` matrix, and the fallback chain
   is a deterministic masked cascade (split → merge → swap; a swap of two
   permutation genes is always valid and always changes the row);
3. **pricing** — :meth:`DevicePopulationPricer.price_row` vmapped over the
   offspring axis (segment boundaries and NoC flow structures are derived
   from the genome rows on device, no host-side batch assembly);
4. **survival** (:func:`survival_order_array` + :func:`pareto_ranks_array`)
   — nondomination ranking, ``(rank, time, energy, index)`` lexsort, and a
   sort-based phenotype dedup, keeping the ``population_size`` best unique
   rows.

Survivor batches (genomes, objectives, bottleneck stages, hot layers) stay
device-resident between generations; the only per-generation host traffic
is the 3-scalar :class:`~repro.core.search.GenStats` record and the
offspring (times, energies, genomes) fed to the epsilon-Pareto archive.

**The PRNG-key contract.**  All randomness in a run derives from
``jax.random.PRNGKey(seed)``: generation ``g`` consumes exactly the draws
of :func:`generation_draws` under ``fold_in(key, g)`` — fixed shapes,
fixed split order, explicit dtypes.  Because ``jax.random`` is
deterministic regardless of jit/eager and of backend, a host NumPy mirror
(``reference=True``) can consume the *identical* draw tensors and replay
the identical decisions: :func:`evolutionary_search_device` with
``reference=True`` runs the same algorithm with ``xp=numpy`` host ops and
the bit-exact numpy pricing backend.  ``tests/test_device_search.py``
asserts selection/mutation/survival parity exactly and the full fitness
trajectory to float64 roundoff.

Two deliberate, documented deviations from the numpy engine (same
*algorithm family*, different micro-policy — the numpy engine remains the
reference for its own path, not for this one):

* no ``tried``-set resampling of duplicate offspring (a host-side hash
  set); duplicates are simply removed at survival, and
* the population size is fixed at the seeded size: when fewer than
  ``population_size`` unique rows exist the best rows are duplicated
  rather than shrinking the batch (shapes must be static on device).

**The sharded island engine** (:class:`ShardedSearchEngine`,
``engine="sharded"``) scales this loop across a 1-D ``("island",)`` device
mesh: the population's K axis is sharded so every device runs the SAME
:func:`_generation_step` on its own subpopulation (an island), with elites
rotating one island around a ``ppermute`` ring every ``migrate_every``
generations and global stats assembled in-program via
``all_gather``/``psum``.  Its PRNG contract extends the device engine's:
island ``i`` of generation ``g`` draws under
``fold_in(key, g * n_islands + i)`` (:func:`island_keys`), which for a
single island reduces exactly to ``fold_in(key, g)`` — so a mesh of one
reproduces ``engine="device"`` trajectories bit-identically, and
:class:`_ShardedHostMirror` replays migration semantics on host NumPy
(``docs/distributed.md``; parity asserted by
``tests/test_sharded_search.py``).
"""

from __future__ import annotations

import dataclasses
import logging
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from repro import tracing
from repro.core.resilience import (Demotion, FaultPlan, RetryPolicy,
                                   SearchCheckpointer, finite_mean,
                                   quarantine_rows, validate_resume_meta)
from repro.distributed.collectives import gather_islands, ring_shift
from repro.core.search import (Candidate, EpsParetoArchive, GenStats,
                               MoveTables, Population, SearchResult,
                               _validate_search_args, decode, move_tables,
                               pareto_ranks, seeded_population)
from repro.neuromorphic.timestep import (device_pricer, precompute_pricing,
                                         price_candidate,
                                         simulate_population)

log = logging.getLogger("repro.resilience")

#: bottleneck-stage ids, in the (first-max-wins) vote order shared with
#: ``SimReport.bottleneck_stage`` / ``DevicePopulationPricer`` votes
STAGE_ID = {"memory": 0, "compute": 1, "traffic": 2, "barrier": 3}


# ----------------------------------------------------------- PRNG contract

def generation_draws(key, *, n_off: int, n_pop: int, n_layers: int,
                     n_slots: int, tournament_k: int) -> dict:
    """One generation's complete randomness, from one key.

    This function IS the PRNG-key contract: a fixed 8-way split consumed in
    a fixed order with explicit dtypes, so the jitted device step and the
    eager NumPy mirror draw identical tensors.  Keys: ``tourn`` (n_off, k)
    parent indices; ``explore_u`` / ``stage_r`` exploration coin and
    replacement stage; ``traffic_u`` the merge-vs-swap coin of the traffic
    move; ``split_pri`` / ``merge_pri`` (n_off, n_layers) random priorities
    that pick among feasible layers; ``swap_iu`` / ``swap_ju`` the swap
    gene positions.  Requires an enabled-x64 scope (float64 draws).
    """
    ks = jax.random.split(key, 8)
    kt = max(1, int(tournament_k))
    return dict(
        tourn=jax.random.randint(ks[0], (n_off, kt), 0, n_pop,
                                 dtype=jnp.int32),
        explore_u=jax.random.uniform(ks[1], (n_off,), dtype=jnp.float64),
        stage_r=jax.random.randint(ks[2], (n_off,), 0, 3, dtype=jnp.int32),
        traffic_u=jax.random.uniform(ks[3], (n_off,), dtype=jnp.float64),
        split_pri=jax.random.uniform(ks[4], (n_off, n_layers),
                                     dtype=jnp.float64),
        merge_pri=jax.random.uniform(ks[5], (n_off, n_layers),
                                     dtype=jnp.float64),
        swap_iu=jax.random.uniform(ks[6], (n_off,), dtype=jnp.float64),
        swap_ju=jax.random.uniform(ks[7], (n_off,), dtype=jnp.float64),
    )


def island_keys(base_key, gen: int, n_islands: int):
    """The sharded engine's per-island PRNG-key contract.

    Island ``i`` of generation ``g`` consumes :func:`generation_draws`
    under ``fold_in(base_key, g * n_islands + i)`` — the ``(gen, island)``
    pair packed into a single fold so that with ``n_islands == 1`` the
    stream reduces EXACTLY to the device engine's ``fold_in(base_key, g)``
    (the mesh-size-1 bit-parity contract).  Returns the stacked
    ``(n_islands, key_size)`` keys; the sharded step's ``in_specs`` shard
    them over the island axis, so each island reads row 0 of its block.
    Derivation stays on host — the jitted step never folds keys itself, so
    the host mirror consumes the identical key rows."""
    g, n = int(gen), int(n_islands)
    return jnp.stack([jax.random.fold_in(base_key, g * n + i)
                      for i in range(n)])


# ------------------------------------------------------- array-native moves

def mutate_rows_array(xp, pc, pp, pstage, phot_mem, phot_act, draws,
                      feasible, n_phys: int, explore_prob: float):
    """Stacked table-gated mutation: parent rows -> offspring rows.

    Pure array program over the offspring axis, written against the shared
    numpy/jax.numpy API surface: ``xp=jnp`` is the device path (traced into
    the jitted generation step), ``xp=numpy`` the host mirror — identical
    semantics op for op, which the parity suite asserts exactly.

    Per offspring: the parent's bottleneck stage (or, with probability
    ``explore_prob`` — and always on a "barrier" stage — a uniformly random
    stage) picks the move family.  memory/compute want a split of the hot
    layer (falling back to the feasible layer of max random priority);
    traffic flips a coin between merge and swap.  The fallback cascade is
    deterministic: an infeasible split falls to merge, an infeasible merge
    to swap.  A swap exchanges one *expressed* gene with any other gene —
    permutation entries are distinct, so it always changes the mapping and
    is always valid.
    """
    n_off, n_layers = pc.shape
    n_slots = pp.shape[1]
    lrange = xp.arange(n_layers)

    explore = (draws["explore_u"] < explore_prob) | (pstage >= 3)
    s_eff = xp.where(explore, draws["stage_r"], pstage)

    total = pc.sum(axis=1)
    split_feas = (feasible[lrange[None, :], pc + 1]
                  & ((total + 1) <= n_phys)[:, None])
    merge_feas = (pc > 1) & feasible[lrange[None, :], pc - 1]

    hot = xp.where(s_eff == 0, phot_mem, phot_act)
    hot_ok = xp.take_along_axis(split_feas, hot[:, None], axis=1)[:, 0]
    rand_split = xp.argmax(xp.where(split_feas, draws["split_pri"], -1.0),
                           axis=1).astype(xp.int32)
    split_l = xp.where(hot_ok, hot, rand_split)
    any_split = split_feas.any(axis=1)
    merge_l = xp.argmax(xp.where(merge_feas, draws["merge_pri"], -1.0),
                        axis=1).astype(xp.int32)
    any_merge = merge_feas.any(axis=1)

    want_split = s_eff <= 1
    traffic_merge = (s_eff == 2) & (draws["traffic_u"] < 0.5)
    do_split = want_split & any_split
    do_merge = ~do_split & any_merge & (traffic_merge | want_split)
    do_swap = ~(do_split | do_merge)

    oh_split = (lrange[None, :] == split_l[:, None]) & do_split[:, None]
    oh_merge = (lrange[None, :] == merge_l[:, None]) & do_merge[:, None]
    cores = pc + oh_split.astype(pc.dtype) - oh_merge.astype(pc.dtype)

    # swap: i an expressed gene, j any gene (i != j); clamps guard the
    # u -> index map against u*total rounding up to total
    i = xp.minimum((draws["swap_iu"] * total).astype(xp.int32), total - 1)
    j = xp.minimum((draws["swap_ju"] * n_slots).astype(xp.int32),
                   n_slots - 1)
    j = xp.where(i == j, (j + 1) % n_slots, j)
    pi = xp.take_along_axis(pp, i[:, None], axis=1)
    pj = xp.take_along_axis(pp, j[:, None], axis=1)
    srange = xp.arange(n_slots)
    swapped = xp.where(srange[None, :] == i[:, None], pj,
                       xp.where(srange[None, :] == j[:, None], pi, pp))
    perm = xp.where(do_swap[:, None], swapped, pp)
    return cores.astype(xp.int32), perm.astype(xp.int32)


def pareto_ranks_array(t, e, n_keep: int | None = None):
    """jnp nondomination ranks — the jittable (lax.while_loop) counterpart
    of :func:`repro.core.search.pareto_ranks`, same peeling algorithm.

    ``n_keep`` (a static Python int) caps the peeling for survival
    selection: the while_loop stops once at least ``n_keep`` rows are
    ranked — enough to fill every survivor slot — instead of running the
    O(K^2)-per-front peel over all K rows (the cost that dominated
    generations at population >= 1k).  Unpeeled rows carry the sentinel
    rank ``K``, which sorts after every real rank, so the
    ``(rank, time, energy, index)`` survival order is unchanged below the
    cutoff, and host and device agree rank-for-rank everywhere
    (``tests/test_device_search.py``).  Documented deviation from
    uncapped ranking: among the unpeeled (sentinel) rows the order falls
    back to (time, energy), so when phenotype dedup pushes survival past
    the cutoff — duplicate-heavy converged populations — the survivor
    tail may differ from the uncapped engine's; elitism is unaffected
    (rank 0 is always peeled first)."""
    dominated_by = ((t[None, :] <= t[:, None]) & (e[None, :] <= e[:, None])
                    & ((t[None, :] < t[:, None]) | (e[None, :] < e[:, None])))
    n = t.shape[0]
    cap = n if n_keep is None else min(int(n_keep), n)

    def body(state):
        ranks, remaining, r, peeled = state
        dom = (dominated_by & remaining[None, :]).sum(axis=1)
        frontier = remaining & (dom == 0)
        return (jnp.where(frontier, r, ranks), remaining & ~frontier,
                r + 1, peeled + frontier.sum().astype(jnp.int32))

    ranks, _, _, _ = jax.lax.while_loop(
        lambda s: s[1].any() & (s[3] < cap), body,
        (jnp.full(n, n, jnp.int32), jnp.ones(n, bool), jnp.int32(0),
         jnp.int32(0)))
    return ranks


def survival_order_array(xp, cores, perm, times, energies, ranks,
                         n_keep: int):
    """Elitist survival on stacked rows: indices of the ``n_keep`` best
    phenotype-unique rows under the total order (rank, time, energy,
    index).

    Dedup is sort-based (no O(K^2 * genes) equality tensor): rows are
    lexsorted by their genome columns with survival position as the final
    tie-break, so equal phenotypes are adjacent and ordered by fitness; a
    row equal to its sorted predecessor is a duplicate.  Unexpressed
    permutation genes are masked to -1 first — two genomes differing only
    in the dead tail are the same phenotype (the array analog of
    ``Population.row_key``).  If fewer than ``n_keep`` unique rows exist,
    the best duplicates pad the batch (static shapes).
    """
    n = cores.shape[0]
    idx = xp.arange(n)
    # total order is unique (index is the last key), so numpy and jax
    # agree independent of sort-stability implementation details
    order = xp.lexsort((idx, energies, times, ranks))
    oc, op = cores[order], perm[order]
    n_log = oc.sum(axis=1)
    pm = xp.where(xp.arange(perm.shape[1])[None, :] < n_log[:, None], op, -1)
    genome = xp.concatenate([oc, pm], axis=1)           # (n, L + S)
    gsort = xp.lexsort((idx,) + tuple(genome[:, c]
                                      for c in range(genome.shape[1])))
    gg = genome[gsort]
    eq_prev = xp.concatenate(
        [xp.zeros(1, bool), (gg[1:] == gg[:-1]).all(axis=1)])
    if xp is np:
        dup = np.zeros(n, bool)
        dup[gsort] = eq_prev
        sel = np.argsort(dup, kind="stable")
    else:
        dup = jnp.zeros(n, bool).at[gsort].set(eq_prev)
        sel = jnp.argsort(dup, stable=True)
    return order[sel[:n_keep]]


# ------------------------------------------------- shared step bookkeeping
#
# The generation-step skeleton is written ONCE, parameterized by the array
# namespace, the pricing function and the ranking function; the jitted
# device engine and the host mirror differ only in what they inject
# (jnp + vmapped device pricer + while_loop ranks vs numpy + the bit-exact
# numpy backend + host ranks).  What the parity suite then actually tests
# is the real divergence surface: XLA-vs-NumPy numerics of the same array
# program, and the two pricing paths.

def _sorted_state(xp, rank_fn, cores, perm, out, idx_n):
    """Price-output dict + genome rows -> survival-sorted state dict.
    Ranking is capped at the survivor count ``idx_n`` — rows beyond the
    cutoff only need a rank larger than every kept one.

    Objectives are quarantined first: NaN/inf rows take the sentinel
    ``(+inf, +inf)`` fitness, so they are dominated by every finite row
    and sort last, instead of poisoning the nondomination ranks (NaN
    comparisons are all False — an unscreened NaN row is never dominated
    and would rank 0).  Finite rows pass through bit-unchanged, on both
    the jitted and the mirror path (same ``where`` masking)."""
    t, e, _ = quarantine_rows(xp, out["times"], out["energies"])
    ranks = rank_fn(t, e, n_keep=idx_n)
    idx = survival_order_array(xp, cores, perm, t, e, ranks, idx_n)
    return dict(cores=cores[idx], perm=perm[idx], times=t[idx],
                energies=e[idx], stage=out["stage"][idx],
                hot_mem=out["hot_mem"][idx], hot_act=out["hot_act"][idx])


def _generation_step(xp, price_fn, rank_fn, feasible, n_phys, explore_prob,
                     state, draws):
    """One (mu + lambda) generation on stacked rows: select, mutate, price,
    concatenate with the survivors, rank, survive.  Returns (new state,
    offspring dict, stats dict)."""
    parents = draws["tourn"].min(axis=1)
    oc, op = mutate_rows_array(
        xp, state["cores"][parents], state["perm"][parents],
        state["stage"][parents], state["hot_mem"][parents],
        state["hot_act"][parents], draws, feasible, n_phys, explore_prob)
    out = price_fn(oc, op)
    all_c = xp.concatenate([state["cores"], oc])
    all_p = xp.concatenate([state["perm"], op])
    all_out = {k: xp.concatenate([state[k], out[k]])
               for k in ("times", "energies", "stage", "hot_mem", "hot_act")}
    new = _sorted_state(xp, rank_fn, all_c, all_p, all_out,
                        state["cores"].shape[0])
    off = dict(cores=oc, perm=op, times=out["times"],
               energies=out["energies"])
    n_quar = (~(xp.isfinite(out["times"])
                & xp.isfinite(out["energies"]))).sum()
    stats = dict(best_time=new["times"][0], best_energy=new["energies"][0],
                 mean_time=finite_mean(xp, new["times"]),
                 n_quarantined=n_quar)
    return new, off, stats


# ----------------------------------------------------------------- engine

class DeviceSearchEngine:
    """One workload's compiled generation machinery.

    Owns the jitted ``init`` (price + sort the seed population) and
    ``step`` (the full generation described in the module docstring)
    programs, both closed over the cache-bound
    :class:`~repro.neuromorphic.timestep.DevicePopulationPricer` and the
    feasibility table.  State is a dict of device arrays
    ``{cores, perm, times, energies, stage, hot_mem, hot_act}`` kept
    (rank, time, energy)-sorted; nothing in it touches the host between
    :meth:`step` calls.
    """

    def __init__(self, net, profile, cache, tables: MoveTables, *,
                 explore_prob: float, tournament_k: int):
        self.pricer = device_pricer(net, profile, cache)
        self.explore_prob = float(explore_prob)
        self.tournament_k = int(tournament_k)
        self.n_layers = len(cache.layers)
        self.n_slots = int(profile.n_cores)
        self.n_phys = int(tables.n_cores_phys)
        with jax.enable_x64(True):
            self.feasible = jnp.asarray(tables.feasible)
        self._init_fn = jax.jit(self._init_impl)
        self._step_fn = jax.jit(self._step_impl, static_argnames=("n_off",))

    def _price(self, cores, perm):
        """Vmapped device pricing, normalized to the step-skeleton keys
        (``times``/``energies`` are the per-candidate objectives)."""
        o = jax.vmap(self.pricer.price_row)(cores, perm)
        return dict(times=o["time_per_step"], energies=o["energy_per_step"],
                    stage=o["stage"], hot_mem=o["hot_mem"],
                    hot_act=o["hot_act"])

    def _init_impl(self, cores, perm):
        out = self._price(cores, perm)
        state = _sorted_state(jnp, pareto_ranks_array, cores, perm, out,
                              cores.shape[0])
        return state, dict(times=out["times"], energies=out["energies"])

    def _step_impl(self, state, key, n_off: int):
        draws = generation_draws(key, n_off=n_off,
                                 n_pop=state["cores"].shape[0],
                                 n_layers=self.n_layers,
                                 n_slots=self.n_slots,
                                 tournament_k=self.tournament_k)
        return _generation_step(jnp, self._price, pareto_ranks_array,
                                self.feasible, self.n_phys,
                                self.explore_prob, state, draws)

    def init(self, cores, perm):
        with jax.enable_x64(True):
            return self._init_fn(jnp.asarray(cores, jnp.int32),
                                 jnp.asarray(perm, jnp.int32))

    def step(self, state, key, n_off: int):
        with jax.enable_x64(True):
            return self._step_fn(state, key, n_off=n_off)


def _engine_for(net, profile, cache, tables, *, explore_prob,
                tournament_k) -> DeviceSearchEngine:
    """Engines (and their compiled programs) are cached on the workload's
    device pricer, keyed by the mutation hyper-parameters, so repeated
    searches over one cache never re-jit."""
    pricer = device_pricer(net, profile, cache)
    engines = pricer.__dict__.setdefault("_search_engines", {})
    key = (float(explore_prob), int(tournament_k))
    if key not in engines:
        engines[key] = DeviceSearchEngine(net, profile, cache, tables,
                                          explore_prob=explore_prob,
                                          tournament_k=tournament_k)
    return engines[key]


# ---------------------------------------------------------- sharded engine

class ShardedSearchEngine:
    """Island-model generation machinery over a 1-D ``("island",)`` mesh.

    The population's K axis is sharded over the mesh: each device owns one
    island's ``local_pop`` rows and runs the SAME :func:`_generation_step`
    as :class:`DeviceSearchEngine` on them inside a jitted
    ``shard_map`` program — selection, mutation and pricing never cross
    islands, so generation throughput scales with the mesh while
    per-island semantics stay identical to the single-device engine.
    Collectives appear at exactly two points of the step:

    * **migration** (the static ``migrate=True`` compile variant): each
      island's elite block (rows ``[0:n_migrants]`` — state is kept
      survival-sorted) is *rotated* one island forward around a
      ``ppermute`` ring and replaces the recipient's elite block, after
      which each island re-sorts locally.  A rotation moves rows — it
      never copies or drops them — so the global genome multiset is
      preserved exactly (property-tested in
      ``tests/test_sharded_search.py``).
    * **global stats**: the generation's best/mean objectives are reduced
      in-program (``all_gather`` of the per-island leaders + ``psum`` of
      the finite sums/counts, the :func:`finite_mean` formula) and
      emitted once per island as ``(1,)`` slices; the host reads island
      0's copy.  Per-generation host traffic therefore stays O(offspring)
      and mesh-independent.

    Host-side array layouts (checkpoints, the mirror, ``init`` inputs)
    use island-block order: global row ``i * local_pop + r`` is island
    ``i``'s row ``r``.  With one island every collective degenerates to
    the identity and no ``migrate`` variant is ever compiled, so the
    trajectory is bit-identical to :class:`DeviceSearchEngine` under the
    :func:`island_keys` contract.
    """

    def __init__(self, net, profile, cache, tables: MoveTables, *, mesh,
                 local_pop: int, n_migrants: int, explore_prob: float,
                 tournament_k: int):
        self.pricer = device_pricer(net, profile, cache)
        self.mesh = mesh
        self.n_islands = int(mesh.shape["island"])
        self.local_pop = int(local_pop)
        self.n_migrants = int(n_migrants)
        self.explore_prob = float(explore_prob)
        self.tournament_k = int(tournament_k)
        self.n_layers = len(cache.layers)
        self.n_slots = int(profile.n_cores)
        self.n_phys = int(tables.n_cores_phys)
        with jax.enable_x64(True):
            self.feasible = jnp.asarray(tables.feasible)
        spec = PartitionSpec("island")
        self._init_fn = self._wrap(self._init_impl, n_in=2,
                                   out_specs=(spec, spec))
        self._migrate_fn = self._wrap(self._migrate_impl, n_in=1,
                                      out_specs=spec)
        self._step_fns: dict = {}

    def _wrap(self, f, *, n_in: int, out_specs):
        """jit(shard_map(f)) with every input sharded over the island
        axis (a spec is a pytree *prefix*, so one P("island") covers a
        whole state dict)."""
        spec = PartitionSpec("island")
        return jax.jit(jax.shard_map(f, mesh=self.mesh,
                                     in_specs=(spec,) * n_in,
                                     out_specs=out_specs, check_vma=False))

    def _price(self, cores, perm):
        o = jax.vmap(self.pricer.price_row)(cores, perm)
        return dict(times=o["time_per_step"], energies=o["energy_per_step"],
                    stage=o["stage"], hot_mem=o["hot_mem"],
                    hot_act=o["hot_act"])

    def _init_impl(self, cores, perm):
        out = self._price(cores, perm)
        state = _sorted_state(jnp, pareto_ranks_array, cores, perm, out,
                              self.local_pop)
        return state, dict(times=out["times"], energies=out["energies"])

    def _migrate_impl(self, state):
        m = self.n_migrants
        inc = ring_shift({k: v[:m] for k, v in state.items()},
                         size=self.n_islands)
        merged = {k: state[k].at[:m].set(inc[k]) for k in state}
        return _sorted_state(jnp, pareto_ranks_array, merged["cores"],
                             merged["perm"], merged, self.local_pop)

    def _global_stats(self, new, n_quar):
        """Globally-reduced GenStats scalars, computed inside the sharded
        program.  Every op sequence mirrors the single-device stats
        (``new[...][0]`` leaders, the :func:`finite_mean` formula) with the
        cross-island reduction spliced in — at one island the ``psum`` /
        ``all_gather`` are identities, preserving bit parity."""
        lead = gather_islands(dict(t=new["times"][0], e=new["energies"][0]))
        tmin = lead["t"].min()
        emin = jnp.where(lead["t"] == tmin, lead["e"], jnp.inf).min()
        ok = jnp.isfinite(new["times"])
        n_ok = jax.lax.psum(ok.sum(), "island")
        total = jax.lax.psum(jnp.where(ok, new["times"], 0.0).sum(),
                             "island")
        mean = jnp.where(n_ok > 0, total / jnp.maximum(n_ok, 1),
                         jnp.asarray(np.inf, dtype=total.dtype))
        n_quar = jax.lax.psum(n_quar, "island")
        return dict(best_time=tmin[None], best_energy=emin[None],
                    mean_time=mean[None], n_quarantined=n_quar[None])

    def _step_for(self, n_off: int, migrate: bool):
        sig = (int(n_off), bool(migrate))
        fn = self._step_fns.get(sig)
        if fn is None:
            spec = PartitionSpec("island")

            def body(state, keys):
                draws = generation_draws(keys[0], n_off=sig[0],
                                         n_pop=self.local_pop,
                                         n_layers=self.n_layers,
                                         n_slots=self.n_slots,
                                         tournament_k=self.tournament_k)
                new, off, st = _generation_step(
                    jnp, self._price, pareto_ranks_array, self.feasible,
                    self.n_phys, self.explore_prob, state, draws)
                if sig[1]:
                    new = self._migrate_impl(new)
                return new, off, self._global_stats(new,
                                                    st["n_quarantined"])

            fn = self._wrap(body, n_in=2, out_specs=(spec, spec, spec))
            self._step_fns[sig] = fn
        return fn

    def init(self, cores, perm):
        with jax.enable_x64(True):
            return self._init_fn(jnp.asarray(cores, jnp.int32),
                                 jnp.asarray(perm, jnp.int32))

    def step(self, state, keys, n_off: int, migrate: bool = False):
        """One generation on every island from the stacked per-island
        ``keys`` (:func:`island_keys`); ``n_off`` is the per-island
        offspring count."""
        with jax.enable_x64(True):
            return self._step_for(n_off, migrate)(state, jnp.asarray(keys))

    def migrate(self, state):
        """The migration collective alone (jitted) — the unit the
        multiset-preservation property test drives directly."""
        with jax.enable_x64(True):
            return self._migrate_fn(state)


def _sharded_engine_for(net, profile, cache, tables, *, mesh, local_pop,
                        n_migrants, explore_prob,
                        tournament_k) -> ShardedSearchEngine:
    """Sharded engines are cached on the workload's device pricer like the
    single-device ones, additionally keyed by the island geometry and the
    exact device assignment (a different mesh must recompile)."""
    pricer = device_pricer(net, profile, cache)
    engines = pricer.__dict__.setdefault("_sharded_engines", {})
    key = (float(explore_prob), int(tournament_k), int(local_pop),
           int(n_migrants), tuple(d.id for d in mesh.devices.flat))
    if key not in engines:
        engines[key] = ShardedSearchEngine(net, profile, cache, tables,
                                           mesh=mesh, local_pop=local_pop,
                                           n_migrants=n_migrants,
                                           explore_prob=explore_prob,
                                           tournament_k=tournament_k)
    return engines[key]


# -------------------------------------------------------- reference mirror

class _NumpyMirror:
    """Host replay of the device engine under the shared PRNG-key contract.

    Prices with the bit-exact numpy population backend and runs
    selection/mutation/survival through the very same array programs with
    ``xp=numpy``.  This is the semantic specification the jitted engine is
    tested against — not a production path (use the numpy engine of
    :func:`repro.core.search.evolutionary_search` for host-only runs).
    """

    #: state handed to this engine must be fetched to host first
    host_state = True

    def __init__(self, net, xs, profile, cache, tables, *, explore_prob,
                 tournament_k, fault_plan: FaultPlan | None = None):
        self.net, self.xs, self.profile, self.cache = net, xs, profile, cache
        self.feasible = np.asarray(tables.feasible)
        self.n_phys = int(tables.n_cores_phys)
        self.n_layers = len(cache.layers)
        self.n_slots = int(profile.n_cores)
        self.explore_prob = float(explore_prob)
        self.tournament_k = int(tournament_k)
        #: fault-injection hook: scripted NaN pricing rows land here (the
        #: jitted engine's pricing cannot be corrupted per-call without a
        #: recompile, so the harness exercises quarantine via the mirror)
        self.fault_plan = fault_plan

    def _price(self, cores, perm):
        pairs = Population(cores, perm).pairs()
        reports = simulate_population(self.net, self.xs, self.profile,
                                      pairs, cache=self.cache)
        t = np.asarray([r.time_per_step for r in reports])
        e = np.asarray([r.energy_per_step for r in reports])
        if self.fault_plan is not None:
            t, e = self.fault_plan.corrupt_arrays(t, e)
        stage = np.asarray([STAGE_ID[r.bottleneck_stage] for r in reports],
                           np.int32)
        hot_mem = np.empty(len(reports), np.int32)
        hot_act = np.empty(len(reports), np.int32)
        for k, r in enumerate(reports):
            lids = np.repeat(np.arange(self.n_layers), cores[k])
            hot_mem[k] = lids[int(np.argmax(r.per_core_synops))]
            hot_act[k] = lids[int(np.argmax(r.per_core_acts))]
        return dict(times=t, energies=e, stage=stage, hot_mem=hot_mem,
                    hot_act=hot_act)

    def init(self, cores, perm):
        out = self._price(cores, perm)
        state = _sorted_state(np, pareto_ranks, cores, perm, out,
                              cores.shape[0])
        return state, dict(times=out["times"], energies=out["energies"])

    def step(self, state, key, n_off: int):
        with jax.enable_x64(True):
            draws = jax.device_get(generation_draws(
                key, n_off=n_off, n_pop=state["cores"].shape[0],
                n_layers=self.n_layers, n_slots=self.n_slots,
                tournament_k=self.tournament_k))
        return _generation_step(np, self._price, pareto_ranks,
                                self.feasible, self.n_phys,
                                self.explore_prob, state, draws)


class _ShardedHostMirror:
    """Host NumPy replay of the island engine — migration's semantic spec.

    Wraps one :class:`_NumpyMirror` for pricing and runs each island's
    generation sequentially over its block of the (island-block-ordered)
    global host state, consuming row ``i`` of the same :func:`island_keys`
    stack the sharded step shards.  Migration is the same elite-block
    rotation in list form: island ``i`` receives island ``i-1``'s elites
    (``ppermute`` ring direction), then re-sorts locally.  Doubles as the
    demotion target of the sharded :class:`_ResilientEngine` — a mid-run
    demotion continues the same trajectory to float64 roundoff.
    """

    host_state = True

    def __init__(self, net, xs, profile, cache, tables, *, n_islands,
                 local_pop, n_migrants, explore_prob, tournament_k,
                 fault_plan: FaultPlan | None = None):
        self.base = _NumpyMirror(net, xs, profile, cache, tables,
                                 explore_prob=explore_prob,
                                 tournament_k=tournament_k,
                                 fault_plan=fault_plan)
        self.n_islands = int(n_islands)
        self.local_pop = int(local_pop)
        self.n_migrants = int(n_migrants)

    def _blocks(self, state):
        L = self.local_pop
        return [{k: np.asarray(state[k])[i * L:(i + 1) * L] for k in state}
                for i in range(self.n_islands)]

    def _stats(self, blocks, n_quar):
        ts = np.asarray([b["times"][0] for b in blocks])
        es = np.asarray([b["energies"][0] for b in blocks])
        tmin = ts.min()
        emin = np.where(ts == tmin, es, np.inf).min()
        ok = [np.isfinite(b["times"]) for b in blocks]
        n_ok = np.sum([m.sum() for m in ok])
        total = np.sum([np.where(m, b["times"], 0.0).sum()
                        for b, m in zip(blocks, ok)])
        mean = total / max(n_ok, 1) if n_ok > 0 else np.inf
        n = self.n_islands
        return dict(best_time=np.full(n, tmin),
                    best_energy=np.full(n, emin),
                    mean_time=np.full(n, mean, np.float64),
                    n_quarantined=np.full(n, n_quar, np.int64))

    def _cat(self, blocks):
        return {k: np.concatenate([b[k] for b in blocks])
                for k in blocks[0]}

    def init(self, cores, perm):
        outs = []
        for blk in self._blocks(dict(cores=np.asarray(cores),
                                     perm=np.asarray(perm))):
            out = self.base._price(blk["cores"], blk["perm"])
            outs.append((blk, out))
        states = [_sorted_state(np, pareto_ranks, b["cores"], b["perm"],
                                o, self.local_pop) for b, o in outs]
        init_out = dict(
            times=np.concatenate([o["times"] for _, o in outs]),
            energies=np.concatenate([o["energies"] for _, o in outs]))
        return self._cat(states), init_out

    def migrate(self, state):
        blocks = self._migrate(self._blocks(state))
        return self._cat(blocks)

    def _migrate(self, blocks):
        m = self.n_migrants
        elites = [{k: b[k][:m] for k in b} for b in blocks]
        incoming = elites[-1:] + elites[:-1]
        out = []
        for b, e in zip(blocks, incoming):
            merged = {k: np.concatenate([e[k], b[k][m:]]) for k in b}
            out.append(_sorted_state(np, pareto_ranks, merged["cores"],
                                     merged["perm"], merged,
                                     self.local_pop))
        return out

    def step(self, state, keys, n_off: int, migrate: bool = False):
        keys = np.asarray(jax.device_get(keys))
        new_blocks, offs = [], []
        n_quar = 0
        for i, blk in enumerate(self._blocks(state)):
            with jax.enable_x64(True):
                draws = jax.device_get(generation_draws(
                    jnp.asarray(keys[i]), n_off=n_off,
                    n_pop=self.local_pop, n_layers=self.base.n_layers,
                    n_slots=self.base.n_slots,
                    tournament_k=self.base.tournament_k))
            nb, off, st = _generation_step(
                np, self.base._price, pareto_ranks, self.base.feasible,
                self.base.n_phys, self.base.explore_prob, blk, draws)
            new_blocks.append(nb)
            offs.append(off)
            n_quar += int(st["n_quarantined"])
        if migrate:
            new_blocks = self._migrate(new_blocks)
        return (self._cat(new_blocks), self._cat(offs),
                self._stats(new_blocks, n_quar))


# ------------------------------------------------------ degradation shell

class _ResilientEngine:
    """Graceful-degradation shell around a jitted generation engine.

    Without ``fallback`` a failed ``init``/``step`` propagates at once (the
    engine's :class:`FaultPlan` site, ``"device"`` or ``"sharded"``, still
    injects).  With it, a failed call (compile error, device OOM, runtime
    fault, or an injected one) is retried per the
    :class:`RetryPolicy`; when the retries are exhausted the engine
    demotes **permanently** to its host NumPy mirror (a failed compile
    fails again — flapping back is pointless).  The mirror consumes the
    identical :func:`generation_draws` under the same key contract
    (``fold_in(key, gen)``, or the :func:`island_keys` stack for the
    sharded engine), so a mid-run demotion continues the same trajectory
    to float64 roundoff; a mirror failure propagates."""

    def __init__(self, primary, mirror_factory, *,
                 retry: RetryPolicy | None = None,
                 fault_plan: FaultPlan | None = None,
                 backend: str = "device", fallback: bool = False):
        self.engine = primary
        self.fallback = fallback
        self._mirror_factory = mirror_factory
        self.retry = retry or RetryPolicy()
        self.fault_plan = fault_plan
        self._primary = str(backend)
        self.backend = self._primary
        self.demotions: list[Demotion] = []

    def _run(self, call, site: str):
        if not self.fallback:
            if self.fault_plan is not None:
                self.fault_plan.check(self.backend)
            return call(self.engine)
        while True:
            delay = self.retry.backoff_s
            last = None
            for a in range(self.retry.max_retries + 1):
                if a and delay > 0:
                    time.sleep(delay)
                    delay *= self.retry.multiplier
                try:
                    if self.fault_plan is not None:
                        self.fault_plan.check(self.backend)
                    return call(self.engine)
                except Exception as e:          # SimulatedCrash passes:
                    last = e                    # it is a BaseException
            if self.backend != self._primary:
                raise last                      # mirror failed: no net left
            d = Demotion(site=site, frm=self._primary, to="numpy-mirror",
                         error=repr(last), retries=self.retry.max_retries)
            self.demotions.append(d)
            log.warning("%s search engine failed %s after %d retries "
                        "(%s); demoting to the host numpy mirror",
                        self._primary, site, d.retries, d.error)
            self.engine = self._mirror_factory()
            self.backend = "numpy-mirror"

    def init(self, cores, perm):
        return self._run(lambda e: e.init(cores, perm), "init")

    def step(self, state, key, *args, **kw):
        def call(e):
            st = jax.device_get(state) if getattr(e, "host_state", False) \
                else state
            return e.step(st, key, *args, **kw)
        return self._run(call, "step")


# ----------------------------------------------------------------- driver

#: the engine's device-resident state dict, in checkpoint order
_STATE_KEYS = ("cores", "perm", "times", "energies", "stage", "hot_mem",
               "hot_act")


def evolutionary_search_device(
    net,
    profile,
    evaluator,
    *,
    population_size: int = 24,
    generations: int = 16,
    tournament_k: int = 3,
    explore_prob: float = 0.25,
    seed: int = 0,
    max_evaluations: int | None = None,
    seed_candidates=None,
    greedy=None,
    pareto_eps: float = 0.01,
    reference: bool = False,
    checkpoint_dir: str | None = None,
    checkpoint_every: int = 1,
    checkpoint_keep: int = 3,
    resume: bool = False,
    fault_plan: FaultPlan | None = None,
    retry: RetryPolicy | None = None,
) -> SearchResult:
    """Run the device-resident (mu + lambda) search (the ``engine="device"``
    path of :func:`repro.core.search.evolutionary_search`).

    ``evaluator`` must be :class:`~repro.core.partitioner.SimEvaluator`-like
    (expose ``net`` / ``xs`` / ``profile`` and ideally a ``cache``): the
    device engine prices inside its own jitted step, so the evaluator is
    the source of the pricing cache and the evaluation-count ledger
    (``n_evals`` is charged per generation to keep iso-budget comparisons
    with the other engines honest).  The final best-candidate
    ``SearchResult.report`` and the archive's ``front_reports`` are
    re-priced once at the end through the bit-exact numpy backend — a
    stats-only materialization that is *not* charged as search
    evaluations.  ``reference=True`` swaps the jitted step for the host
    NumPy mirror (the parity harness; same PRNG-key contract, same
    trajectory to float64 roundoff).

    Fault tolerance (``docs/robustness.md``): ``checkpoint_dir`` /
    ``checkpoint_every`` / ``checkpoint_keep`` / ``resume`` snapshot and
    restore the engine's device state dict — resume is bit-identical
    because each generation is a pure function of ``(key, gen,
    survivors)`` under the PRNG-key contract.  A failed jitted
    ``init``/``step`` raises, unless the evaluator was built with
    ``fallback=True``: then it is retried per ``retry`` and demoted
    permanently to the host mirror (logged; recorded in
    ``SearchResult.demotions``).  ``fault_plan`` scripts deterministic
    faults: ``fail={"device": n}`` makes the next ``n`` jitted calls
    raise, ``nan_rows`` corrupts mirror pricing rows, ``kill_after_gen``
    simulates a crash after that generation's checkpoint.
    """
    with tracing.span("search"):
        for attr in ("net", "xs", "profile"):
            if not hasattr(evaluator, attr):
                raise TypeError(
                    "engine='device' needs a SimEvaluator-like evaluator "
                    f"(missing .{attr}); plain callables can only drive "
                    "the numpy engine")
        _validate_search_args(net, profile,
                              population_size=population_size,
                              generations=generations,
                              seed_candidates=seed_candidates)
        xs = evaluator.xs
        cache = getattr(evaluator, "cache", None) \
            or precompute_pricing(net, xs, profile)

        ckpt = (SearchCheckpointer(checkpoint_dir, every=checkpoint_every,
                                   keep=checkpoint_keep)
                if checkpoint_dir else None)
        restored = ckpt.restore() if (ckpt is not None and resume) else None

        tables = move_tables(net, profile)
        n_layers = len(cache.layers)
        n_slots = int(profile.n_cores)

        def _mirror():
            return _NumpyMirror(net, xs, profile, cache, tables,
                                explore_prob=explore_prob,
                                tournament_k=tournament_k,
                                fault_plan=fault_plan)

        if reference:
            engine = _mirror()
        else:
            engine = _ResilientEngine(
                _engine_for(net, profile, cache, tables,
                            explore_prob=explore_prob,
                            tournament_k=tournament_k),
                _mirror, retry=retry, fault_plan=fault_plan,
                fallback=getattr(evaluator, "fallback", False))
        base_key = jax.random.PRNGKey(seed)
        archive = EpsParetoArchive(pareto_eps)

        if restored is not None:
            arrays, gen0, meta = restored
            validate_resume_meta(meta, engine="device",
                                 checkpoint_dir=checkpoint_dir)
            state = {k: np.asarray(arrays[k]) for k in _STATE_KEYS}
            archive.load_state(arrays)
            history = [GenStats(**h) for h in meta["history"]]
            evals_used = int(meta["evals_used"])
            seed_best_time = float(meta["seed_best_time"])
            n_pop = int(state["cores"].shape[0])
            start_gen = gen0 + 1
        else:
            with tracing.span("search.seed"):
                rng = np.random.default_rng(seed)
                cands = list(seed_candidates if seed_candidates is not None
                             else seeded_population(net, profile,
                                                    size=population_size,
                                                    rng=rng, greedy=greedy))
                if not cands:
                    raise ValueError("empty initial population")
                if max_evaluations is not None:
                    cands = cands[:max(1, max_evaluations)]
                pop = Population.from_candidates(cands)

                state, init_out = engine.init(pop.cores, pop.perm)
                evals_used = len(pop)
                _charge(evaluator, len(pop))
                init_host = jax.device_get(init_out)
                # screen the raw seed objectives before they reach host
                # stats or the archive (the archive rejects non-finite points
                # itself; the sentinel keeps the min() below NaN-safe)
                it, ie, _ = quarantine_rows(
                    np, np.asarray(init_host["times"], np.float64),
                    np.asarray(init_host["energies"], np.float64))
                seed_best_time = float(np.min(it))
                archive.update_batch(it, ie, pop.cores, pop.perm)

                first = jax.device_get({k: state[k]
                                        for k in ("times", "energies")})
                history = [GenStats(
                    generation=0, best_time=float(first["times"][0]),
                    best_energy=float(first["energies"][0]),
                    mean_time=float(finite_mean(np, first["times"])),
                    n_evals=evals_used, front_size=len(archive))]
                n_pop = len(pop)
                start_gen = 1

        def _snapshot(gen: int) -> None:
            host_state = jax.device_get(state)
            arrays = {k: np.asarray(host_state[k]) for k in _STATE_KEYS}
            arrays.update(archive.state_arrays(n_layers, n_slots))
            meta = dict(engine="device", evals_used=int(evals_used),
                        seed_best_time=float(seed_best_time),
                        history=[dataclasses.asdict(g) for g in history])
            ckpt.save(gen, arrays, meta)

        if restored is None:
            if ckpt is not None:
                _snapshot(0)
            if fault_plan is not None:
                fault_plan.after_generation(0)

        for gen in range(start_gen, generations + 1):
            n_off = n_pop
            if max_evaluations is not None:
                n_off = min(n_off, max_evaluations - evals_used)
            if n_off <= 0:
                break
            key = jax.random.fold_in(base_key, gen)
            with tracing.span("search.step"):
                state, off, stats = engine.step(state, key, n_off)
            evals_used += n_off
            _charge(evaluator, n_off)
            # the only per-generation host sync: tiny stats + the offspring
            # batch, absorbed by the epsilon-Pareto archive in ONE vectorized
            # update (no per-offspring host Python anywhere in this loop)
            with tracing.span("search.sync"):
                host = jax.device_get(dict(off=off, stats=stats))
            off_h, stats_h = host["off"], host["stats"]
            with tracing.span("search.archive"):
                archive.update_batch(off_h["times"], off_h["energies"],
                                     off_h["cores"], off_h["perm"])
                history.append(GenStats(
                    generation=gen,
                    best_time=float(stats_h["best_time"]),
                    best_energy=float(stats_h["best_energy"]),
                    mean_time=float(stats_h["mean_time"]),
                    n_evals=evals_used,
                    front_size=len(archive),
                    n_quarantined=int(stats_h.get("n_quarantined", 0))))
            if ckpt is not None and ckpt.due(gen, generations):
                _snapshot(gen)
            if fault_plan is not None:
                fault_plan.after_generation(gen)

        with tracing.span("search.finish"):
            final = jax.device_get({k: state[k] for k in ("cores", "perm")})
            best = Candidate(tuple(int(x) for x in final["cores"][0]),
                             tuple(int(x) for x in final["perm"][0]))
            part, mapping = decode(best)
            # stats-only materialization through the bit-exact path
            # (uncharged)
            best_report = price_candidate(net, profile, cache, part, mapping)
            front, _ = archive.front()
            front_reports = simulate_population(
                net, xs, profile, [decode(c) for c in front],
                cache=cache) if front else []
        return SearchResult(candidate=best, partition=part, mapping=mapping,
                            report=best_report, history=history,
                            n_evals=evals_used,
                            seed_best_time=seed_best_time,
                            front=front, front_reports=front_reports,
                            demotions=list(getattr(engine, "demotions",
                                                   ())))


def _charge(evaluator, n: int) -> None:
    """Record ``n`` candidate pricings on the evaluator's ledger (the
    iso-budget currency shared with the greedy walk and the numpy engine);
    evaluators without a counter are left alone."""
    if hasattr(evaluator, "n_evals"):
        evaluator.n_evals += int(n)


def evolutionary_search_sharded(
    net,
    profile,
    evaluator,
    *,
    population_size: int = 24,
    generations: int = 16,
    tournament_k: int = 3,
    explore_prob: float = 0.25,
    seed: int = 0,
    max_evaluations: int | None = None,
    seed_candidates=None,
    greedy=None,
    pareto_eps: float = 0.01,
    n_islands: int | None = None,
    migrate_every: int = 5,
    n_migrants: int | None = None,
    mesh=None,
    reference: bool = False,
    checkpoint_dir: str | None = None,
    checkpoint_every: int = 1,
    checkpoint_keep: int = 3,
    resume: bool = False,
    fault_plan: FaultPlan | None = None,
    retry: RetryPolicy | None = None,
) -> SearchResult:
    """Run the island-model sharded search (the ``engine="sharded"`` path
    of :func:`repro.core.search.evolutionary_search`).

    The population is split into ``n_islands`` equal islands (default: one
    per visible device; ``population_size`` must divide evenly and leave
    at least 2 rows per island), the K axis is sharded over the 1-D
    ``("island",)`` mesh, and every device runs the jitted device-engine
    generation on its own island.  Every ``migrate_every`` generations
    (0 disables) each island's top ``n_migrants`` rows (default
    ``local_pop // 8``, at least 1) rotate one island around the ring.
    Randomness follows :func:`island_keys`; with ``n_islands=1`` the run
    is bit-identical to :func:`evolutionary_search_device`.

    Checkpointing reuses the device engine's self-contained ``.npz``
    layout — island state is gathered to host in island-block order, and
    resume validates the island geometry via
    :func:`~repro.core.resilience.validate_resume_meta` (a checkpoint is
    only bit-identical under the configuration that wrote it).
    ``reference=True`` swaps the jitted program for
    :class:`_ShardedHostMirror`; under an evaluator with ``fallback=True``
    a failed jitted call demotes to the same mirror through
    :class:`_ResilientEngine` (``fail={"sharded": n}`` of a
    :class:`FaultPlan` injects such failures).  See
    ``docs/distributed.md``.
    """
    for attr in ("net", "xs", "profile"):
        if not hasattr(evaluator, attr):
            raise TypeError(
                "engine='sharded' needs a SimEvaluator-like evaluator "
                f"(missing .{attr}); plain callables can only drive the "
                "numpy engine")
    _validate_search_args(net, profile, population_size=population_size,
                          generations=generations,
                          seed_candidates=seed_candidates)
    if mesh is None:
        from repro.distributed.sharding import island_mesh
        mesh = island_mesh(n_islands)
    if "island" not in mesh.axis_names:
        raise ValueError(f"engine='sharded' needs a 1-D ('island',) mesh, "
                         f"got axes {mesh.axis_names}")
    n_islands = int(mesh.shape["island"])
    if population_size % n_islands:
        raise ValueError(
            f"population_size={population_size} does not divide evenly "
            f"over {n_islands} islands — pick a multiple of {n_islands} "
            "or pass n_islands explicitly")
    local_pop = population_size // n_islands
    if local_pop < 2:
        raise ValueError(
            f"population_size={population_size} over {n_islands} islands "
            f"leaves {local_pop} row(s) per island; tournament selection "
            "needs at least 2 — lower n_islands or grow the population")
    migrate_every = int(migrate_every)
    if n_migrants is None:
        n_migrants = max(1, local_pop // 8)
    n_migrants = int(n_migrants)
    if not 1 <= n_migrants <= local_pop:
        raise ValueError(f"n_migrants={n_migrants} must be in "
                         f"[1, {local_pop}] (the island size)")

    xs = evaluator.xs
    cache = getattr(evaluator, "cache", None) \
        or precompute_pricing(net, xs, profile)

    ckpt = (SearchCheckpointer(checkpoint_dir, every=checkpoint_every,
                               keep=checkpoint_keep)
            if checkpoint_dir else None)
    restored = ckpt.restore() if (ckpt is not None and resume) else None

    tables = move_tables(net, profile)
    n_layers = len(cache.layers)
    n_slots = int(profile.n_cores)

    def _mirror():
        return _ShardedHostMirror(net, xs, profile, cache, tables,
                                  n_islands=n_islands, local_pop=local_pop,
                                  n_migrants=n_migrants,
                                  explore_prob=explore_prob,
                                  tournament_k=tournament_k,
                                  fault_plan=fault_plan)

    if reference:
        engine = _mirror()
    else:
        engine = _ResilientEngine(
            _sharded_engine_for(net, profile, cache, tables, mesh=mesh,
                                local_pop=local_pop, n_migrants=n_migrants,
                                explore_prob=explore_prob,
                                tournament_k=tournament_k),
            _mirror, retry=retry, fault_plan=fault_plan, backend="sharded",
            fallback=getattr(evaluator, "fallback", False))
    base_key = jax.random.PRNGKey(seed)
    archive = EpsParetoArchive(pareto_eps)

    if restored is not None:
        arrays, gen0, meta = restored
        validate_resume_meta(meta, engine="sharded",
                             checkpoint_dir=checkpoint_dir,
                             expect=dict(population_size=population_size,
                                         n_islands=n_islands,
                                         migrate_every=migrate_every,
                                         n_migrants=n_migrants))
        state = {k: np.asarray(arrays[k]) for k in _STATE_KEYS}
        archive.load_state(arrays)
        history = [GenStats(**h) for h in meta["history"]]
        evals_used = int(meta["evals_used"])
        seed_best_time = float(meta["seed_best_time"])
        start_gen = gen0 + 1
    else:
        rng = np.random.default_rng(seed)
        cands = list(seed_candidates if seed_candidates is not None else
                     seeded_population(net, profile, size=population_size,
                                       rng=rng, greedy=greedy))
        if not cands:
            raise ValueError("empty initial population")
        if len(cands) != population_size:
            raise ValueError(
                f"{len(cands)} seed candidates do not fill "
                f"population_size={population_size} (the sharded engine "
                "needs full equal islands)")
        pop = Population.from_candidates(cands)

        state, init_out = engine.init(pop.cores, pop.perm)
        evals_used = len(pop)
        _charge(evaluator, len(pop))
        init_host = jax.device_get(init_out)
        it, ie, _ = quarantine_rows(
            np, np.asarray(init_host["times"], np.float64),
            np.asarray(init_host["energies"], np.float64))
        seed_best_time = float(np.min(it))
        archive.update_batch(it, ie, pop.cores, pop.perm)

        # gen-0 stats on host, with the same ops as the device driver at
        # one island (bit parity); islands contribute their sorted leaders
        first = jax.device_get({k: state[k] for k in ("times", "energies")})
        ft = np.asarray(first["times"]).reshape(n_islands, local_pop)
        fe = np.asarray(first["energies"]).reshape(n_islands, local_pop)
        tmin = float(np.min(ft[:, 0]))
        emin = float(np.min(np.where(ft[:, 0] == tmin, fe[:, 0], np.inf)))
        history = [GenStats(generation=0,
                            best_time=tmin,
                            best_energy=emin,
                            mean_time=float(finite_mean(
                                np, np.asarray(first["times"]))),
                            n_evals=evals_used,
                            front_size=len(archive))]
        start_gen = 1

    def _snapshot(gen: int) -> None:
        host_state = jax.device_get(state)
        arrays = {k: np.asarray(host_state[k]) for k in _STATE_KEYS}
        arrays.update(archive.state_arrays(n_layers, n_slots))
        meta = dict(engine="sharded", population_size=int(population_size),
                    n_islands=int(n_islands),
                    migrate_every=int(migrate_every),
                    n_migrants=int(n_migrants),
                    evals_used=int(evals_used),
                    seed_best_time=float(seed_best_time),
                    history=[dataclasses.asdict(g) for g in history])
        ckpt.save(gen, arrays, meta)

    if restored is None:
        if ckpt is not None:
            _snapshot(0)
        if fault_plan is not None:
            fault_plan.after_generation(0)

    for gen in range(start_gen, generations + 1):
        n_off_total = population_size
        if max_evaluations is not None:
            n_off_total = min(n_off_total, max_evaluations - evals_used)
        local_off = n_off_total // n_islands
        if local_off <= 0:
            break
        migrate = (n_islands > 1 and migrate_every > 0
                   and gen % migrate_every == 0)
        keys = island_keys(base_key, gen, n_islands)
        state, off, stats = engine.step(state, keys, n_off=local_off,
                                        migrate=migrate)
        evals_used += local_off * n_islands
        _charge(evaluator, local_off * n_islands)
        host = jax.device_get(dict(off=off, stats=stats))
        off_h, stats_h = host["off"], host["stats"]
        archive.update_batch(off_h["times"], off_h["energies"],
                             off_h["cores"], off_h["perm"])
        history.append(GenStats(
            generation=gen,
            best_time=float(np.asarray(stats_h["best_time"])[0]),
            best_energy=float(np.asarray(stats_h["best_energy"])[0]),
            mean_time=float(np.asarray(stats_h["mean_time"])[0]),
            n_evals=evals_used,
            front_size=len(archive),
            n_quarantined=int(np.asarray(stats_h["n_quarantined"])[0])))
        if ckpt is not None and ckpt.due(gen, generations):
            _snapshot(gen)
        if fault_plan is not None:
            fault_plan.after_generation(gen)

    final = jax.device_get({k: state[k] for k in
                            ("cores", "perm", "times", "energies")})
    ft = np.asarray(final["times"]).reshape(n_islands, local_pop)
    fe = np.asarray(final["energies"]).reshape(n_islands, local_pop)
    t0 = ft[:, 0]
    best_i = int(np.argmin(np.where(t0 == t0.min(), fe[:, 0], np.inf)))
    row = best_i * local_pop
    best = Candidate(tuple(int(x) for x in np.asarray(final["cores"])[row]),
                     tuple(int(x) for x in np.asarray(final["perm"])[row]))
    part, mapping = decode(best)
    best_report = price_candidate(net, profile, cache, part, mapping)
    front, _ = archive.front()
    front_reports = simulate_population(net, xs, profile,
                                        [decode(c) for c in front],
                                        cache=cache) if front else []
    return SearchResult(candidate=best, partition=part, mapping=mapping,
                        report=best_report, history=history,
                        n_evals=evals_used, seed_best_time=seed_best_time,
                        front=front, front_reports=front_reports,
                        demotions=list(getattr(engine, "demotions", ())))
