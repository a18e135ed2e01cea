"""Island-model sharded search tests (:mod:`repro.core.device_search`).

The guarantees layered on top of the single-device engine's:

* **mesh-1 bit parity** — ``engine="sharded"`` with one island replays
  ``engine="device"`` EXACTLY (same :func:`island_keys` stream, same jitted
  step, collectives degenerate to identities);
* **mirror parity** — the jitted multi-island step and
  :class:`_ShardedHostMirror` (host NumPy, per-island blocks, list-form
  ring migration) agree on the full trajectory to float64 roundoff and on
  the final candidate exactly;
* **migration conservation** — the elite-block ring rotation moves rows
  between islands without duplicating or dropping any: the global genome
  multiset is invariant (hypothesis, over island geometries);
* **front assembly** — a row nondominated globally is nondominated on its
  island, so the front of the gathered population equals the front of the
  pooled per-island fronts (the property that makes per-island ranking +
  host assembly correct);
* **launch plumbing** — ``force_host_device_count`` rejects a too-late
  call in-process and actually yields N devices in a fresh process;
* **degradation** — a permanently failing jitted sharded step demotes to
  the host mirror and completes the identical trajectory.

Run under ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` to
exercise real multi-island meshes (CI does); on one device the
multi-island tests degenerate to a single island but stay valid.
"""

import os
import subprocess
import sys

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

import jax

from repro.core.device_search import (_ShardedHostMirror, _sharded_engine_for,
                                      evolutionary_search_sharded,
                                      island_keys)
from repro.core.partitioner import SimEvaluator
from repro.core.resilience import ALWAYS, FaultPlan, RetryPolicy
from repro.core.search import (Population, evolutionary_search, move_tables,
                               pareto_ranks, seeded_population)
from repro.distributed.sharding import island_mesh
from repro.neuromorphic import loihi2_like, make_inputs, programmed_fc_network
from repro.neuromorphic.timestep import (precompute_pricing,
                                         price_population_device,
                                         price_population_sharded)

quick = pytest.mark.quick
pytestmark = pytest.mark.timeout(600)

N_DEV = len(jax.devices())


def fc_workload(sizes=(64, 96, 48), wd=0.6, ad=0.3, steps=2):
    net = programmed_fc_network(
        list(sizes), weight_densities=[wd] * (len(sizes) - 1),
        act_densities=[ad] * (len(sizes) - 1), seed=0,
        weight_format="sparse")
    return net, make_inputs(sizes[0], ad, steps, seed=1)


_WORKLOAD: dict = {}


def get_workload():
    """One shared (net, xs, prof, evaluator) so the sharded engine
    compiles once per (n_off, migrate) variant for the whole module."""
    if not _WORKLOAD:
        net, xs = fc_workload()
        prof = loihi2_like()
        _WORKLOAD["value"] = (net, xs, prof, SimEvaluator(net, xs, prof))
    return _WORKLOAD["value"]


def _traj(res):
    return [(g.generation, g.best_time, g.best_energy, g.mean_time,
             g.n_evals, g.front_size, g.n_quarantined) for g in res.history]


def _search(net, prof, ev, **kw):
    kw.setdefault("population_size", 16)
    kw.setdefault("generations", 4)
    kw.setdefault("seed", 3)
    return evolutionary_search(net, prof, ev, **kw)


def _rows_multiset(state):
    cores = np.asarray(state["cores"])
    perm = np.asarray(state["perm"])
    return sorted(map(tuple, np.concatenate([cores, perm], axis=1).tolist()))


# ---------------------------------------------------------- PRNG contract

class TestIslandKeys:
    @quick
    def test_single_island_reduces_to_device_contract(self):
        """With one island, generation g's key IS fold_in(key, g) — the
        fact that makes mesh-1 runs bit-identical to engine="device"."""
        base = jax.random.PRNGKey(11)
        for gen in (0, 1, 5):
            np.testing.assert_array_equal(
                np.asarray(island_keys(base, gen, 1))[0],
                np.asarray(jax.random.fold_in(base, gen)))

    @quick
    def test_gen_island_packing(self):
        """Island i of generation g folds in g * n_islands + i: distinct
        across both axes, and consecutive generations do not collide with
        neighbouring islands' streams."""
        base = jax.random.PRNGKey(0)
        n = 4
        seen = set()
        for gen in range(3):
            keys = np.asarray(island_keys(base, gen, n))
            for i in range(n):
                np.testing.assert_array_equal(
                    keys[i],
                    np.asarray(jax.random.fold_in(base, gen * n + i)))
                seen.add(keys[i].tobytes())
        assert len(seen) == 3 * n


# ------------------------------------------------------------- bit parity

class TestMeshOneParity:
    @quick
    def test_sharded_one_island_is_bit_identical_to_device(self):
        """The tentpole contract: n_islands=1 replays engine="device"
        EXACTLY — trajectory, front, final candidate (float equality, not
        tolerance)."""
        net, xs, prof, ev = get_workload()
        dev = _search(net, prof, SimEvaluator(net, xs, prof, cache=ev.cache),
                      engine="device")
        sh = _search(net, prof, SimEvaluator(net, xs, prof, cache=ev.cache),
                     engine="sharded", n_islands=1)
        assert _traj(sh) == _traj(dev)
        assert sh.candidate == dev.candidate
        assert sh.front == dev.front
        assert sh.report.time_per_step == dev.report.time_per_step
        assert sh.n_evals == dev.n_evals


class TestMirrorParity:
    @quick
    def test_multi_island_matches_host_mirror(self):
        """Jitted multi-island run vs reference=True host replay: same
        candidate, trajectory equal to float64 roundoff, same migration
        cadence (migrate_every=2 exercises the ring twice in 4 gens)."""
        net, xs, prof, ev = get_workload()
        kw = dict(engine="sharded", n_islands=N_DEV, migrate_every=2)
        jit = _search(net, prof,
                      SimEvaluator(net, xs, prof, cache=ev.cache), **kw)
        ref = evolutionary_search_sharded(
            net, prof, SimEvaluator(net, xs, prof, cache=ev.cache),
            population_size=16, generations=4, seed=3, n_islands=N_DEV,
            migrate_every=2, reference=True)
        assert jit.candidate == ref.candidate
        for a, b in zip(jit.history, ref.history):
            np.testing.assert_allclose(
                [a.best_time, a.best_energy, a.mean_time],
                [b.best_time, b.best_energy, b.mean_time], rtol=1e-9)
            assert (a.generation, a.n_evals, a.n_quarantined) \
                == (b.generation, b.n_evals, b.n_quarantined)


# -------------------------------------------------- migration conservation

def _engine_and_state(local_pop, n_migrants, seed):
    net, xs, prof, ev = get_workload()
    n_islands = N_DEV
    mesh = island_mesh(n_islands)
    eng = _sharded_engine_for(net, prof, ev.cache, move_tables(net, prof),
                              mesh=mesh, local_pop=local_pop,
                              n_migrants=n_migrants, explore_prob=0.25,
                              tournament_k=3)
    pop = Population.from_candidates(seeded_population(
        net, prof, size=local_pop * n_islands,
        rng=np.random.default_rng(seed)))
    state, _ = eng.init(pop.cores, pop.perm)
    return eng, state


class TestMigrationConservation:
    @quick
    @settings(max_examples=8, deadline=None)
    @given(st.integers(min_value=1, max_value=4),
           st.integers(min_value=0, max_value=5))
    def test_ring_rotation_preserves_global_genome_multiset(
            self, n_migrants, seed):
        """Migration is a rotation, not a copy: across any island count,
        elite size and population, the multiset of global genome rows is
        unchanged (no row duplicated, none lost) — and objectives still
        pair with their rows afterwards."""
        eng, state = _engine_and_state(local_pop=6, n_migrants=n_migrants,
                                       seed=seed)
        before = _rows_multiset(jax.device_get(state))
        after_state = jax.device_get(eng.migrate(state))
        assert _rows_multiset(after_state) == before
        # the host mirror's list-form rotation lands on the same blocks
        net, xs, prof, ev = get_workload()
        mirror = _ShardedHostMirror(
            net, xs, prof, ev.cache, move_tables(net, prof),
            n_islands=N_DEV, local_pop=6, n_migrants=n_migrants,
            explore_prob=0.25, tournament_k=3)
        mref = mirror.migrate({k: np.asarray(v)
                               for k, v in jax.device_get(state).items()})
        np.testing.assert_array_equal(after_state["cores"], mref["cores"])
        np.testing.assert_array_equal(after_state["perm"], mref["perm"])

    @quick
    def test_migrated_rows_keep_their_objectives(self):
        """Each (genome -> time, energy) pairing survives the rotation:
        sort both sides by genome bytes and compare objectives exactly."""
        eng, state = _engine_and_state(local_pop=6, n_migrants=2, seed=0)
        def by_genome(s):
            s = jax.device_get(s)
            g = np.concatenate([np.asarray(s["cores"]),
                                np.asarray(s["perm"])], axis=1)
            order = np.lexsort(tuple(g[:, c] for c in range(g.shape[1])))
            return (g[order], np.asarray(s["times"])[order],
                    np.asarray(s["energies"])[order])
        g0, t0, e0 = by_genome(state)
        g1, t1, e1 = by_genome(eng.migrate(state))
        np.testing.assert_array_equal(g0, g1)
        np.testing.assert_array_equal(t0, t1)
        np.testing.assert_array_equal(e0, e1)


# ----------------------------------------------------------- front assembly

class TestFrontAssembly:
    @quick
    @settings(max_examples=15, deadline=None)
    @given(st.integers(min_value=1, max_value=8),
           st.integers(min_value=2, max_value=12),
           st.integers(min_value=0, max_value=99))
    def test_front_of_gathered_equals_front_of_pooled_island_fronts(
            self, n_islands, local, seed):
        """A globally nondominated row is nondominated on its island, so
        rank-0 of the gathered population == rank-0 of the pooled
        per-island rank-0 sets.  This is why per-island survival sorting +
        host-side assembly loses no Pareto point."""
        rng = np.random.default_rng(seed)
        t = rng.integers(1, 20, size=(n_islands, local)).astype(float)
        e = rng.integers(1, 20, size=(n_islands, local)).astype(float)
        gt, ge = t.ravel(), e.ravel()
        global_front = {(a, b) for a, b, r in
                        zip(gt, ge, pareto_ranks(gt, ge)) if r == 0}
        pooled_t, pooled_e = [], []
        for i in range(n_islands):
            r = pareto_ranks(t[i], e[i])
            pooled_t.extend(t[i][r == 0])
            pooled_e.extend(e[i][r == 0])
        pt, pe = np.asarray(pooled_t), np.asarray(pooled_e)
        assembled = {(a, b) for a, b, r in
                     zip(pt, pe, pareto_ranks(pt, pe)) if r == 0}
        assert assembled == global_front

    @quick
    def test_history_best_is_global_lexmin_of_final_state(self):
        """The in-program all_gather stats report the true global
        (time, then energy) leader — cross-checked on host against the
        gathered final state of a real multi-island run."""
        net, xs, prof, ev = get_workload()
        eng, state = _engine_and_state(local_pop=6, n_migrants=1, seed=4)
        keys = island_keys(jax.random.PRNGKey(7), 1, eng.n_islands)
        state, _, stats = eng.step(state, keys, n_off=6)
        h = jax.device_get(dict(state=state, stats=stats))
        ts = np.asarray(h["state"]["times"]).reshape(eng.n_islands, -1)
        es = np.asarray(h["state"]["energies"]).reshape(eng.n_islands, -1)
        assert float(np.asarray(h["stats"]["best_time"])[0]) \
            == float(ts.min())
        lead_t, lead_e = ts[:, 0], es[:, 0]
        want_e = float(np.where(lead_t == lead_t.min(), lead_e,
                                np.inf).min())
        assert float(np.asarray(h["stats"]["best_energy"])[0]) == want_e
        # every island carries the same (replicated) global stats
        assert len(set(np.asarray(h["stats"]["best_time"]).tolist())) == 1


# ------------------------------------------------------------ sharded pricer

class TestShardedPricer:
    @quick
    def test_matches_device_pricer_incl_ragged_population(self):
        """price_population_sharded == price_population_device for K both
        divisible and NOT divisible by the island count (pad rows are
        priced and trimmed, never returned)."""
        net, xs, prof, ev = get_workload()
        cache = ev.cache or precompute_pricing(net, xs, prof)
        for k in (N_DEV * 3, N_DEV * 3 + 1, 5):
            pop = Population.from_candidates(seeded_population(
                net, prof, size=k, rng=np.random.default_rng(k)))
            want = price_population_device(net, prof, cache,
                                           pop.cores, pop.perm)
            got = price_population_sharded(net, prof, cache,
                                           pop.cores, pop.perm)
            assert len(got) == len(want) == len(pop)
            for a, b in zip(got, want):
                assert a.time_per_step == b.time_per_step
                assert a.energy_per_step == b.energy_per_step
                assert a.bottleneck_stage == b.bottleneck_stage


# ------------------------------------------------------------- launch flags

class TestLaunchFlags:
    @quick
    def test_force_after_jax_import_raises(self):
        """jax is long imported in this process: asking for a different
        forced count must fail loudly instead of silently not applying."""
        from repro.launch.mesh import (force_host_device_count,
                                       forced_host_device_count)
        with pytest.raises(RuntimeError, match="before jax"):
            force_host_device_count(N_DEV + 1)
        # idempotent path: the count already in force is a no-op
        if forced_host_device_count() is not None:
            force_host_device_count(forced_host_device_count())

    @quick
    def test_apply_devices_flag_parses_and_rejects(self):
        from repro.launch.mesh import apply_devices_flag
        assert apply_devices_flag(["--quick"]) is None
        with pytest.raises(SystemExit):
            apply_devices_flag(["--devices", "eight"])

    @quick
    def test_forced_count_yields_devices_in_fresh_process(self):
        """End-to-end: force 3 host devices before jax in a clean process
        and observe exactly 3, sharded search included."""
        code = (
            "from repro.launch.mesh import force_host_device_count\n"
            "force_host_device_count(3)\n"
            "import jax\n"
            "assert len(jax.devices()) == 3, jax.devices()\n"
            "from repro.distributed.sharding import island_mesh\n"
            "assert island_mesh().shape['island'] == 3\n"
            "print('OK')\n")
        env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
        env["PYTHONPATH"] = os.pathsep.join(
            [os.path.abspath("src")] + sys.path)
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, out.stderr
        assert "OK" in out.stdout

    @quick
    def test_island_mesh_rejects_oversubscription(self):
        with pytest.raises(RuntimeError, match="devices"):
            island_mesh(N_DEV + 1)


# ------------------------------------------------------------- degradation

class TestDegradation:
    def test_sharded_fault_demotes_to_mirror_and_matches(self):
        """A permanently failing jitted sharded step demotes to the host
        mirror and completes the reference trajectory (same island-keys
        contract on both sides)."""
        net, xs, prof, ev = get_workload()
        kw = dict(population_size=16, generations=3, seed=5,
                  n_islands=N_DEV, migrate_every=2)
        ref = evolutionary_search_sharded(
            net, prof, SimEvaluator(net, xs, prof, cache=ev.cache),
            reference=True, **kw)
        res = evolutionary_search_sharded(
            net, prof, SimEvaluator(net, xs, prof, cache=ev.cache,
                                    fallback=True),
            fault_plan=FaultPlan(fail={"sharded": ALWAYS}),
            retry=RetryPolicy(max_retries=1, backoff_s=0.0), **kw)
        assert [d.frm for d in res.demotions] == ["sharded"]
        assert res.demotions[0].to == "numpy-mirror"
        assert _traj(res) == _traj(ref)
        assert res.candidate == ref.candidate


# ------------------------------------------------------------- validation

class TestValidation:
    @quick
    def test_population_must_divide_into_islands(self):
        net, xs, prof, ev = get_workload()
        if N_DEV == 1:
            pytest.skip("needs >= 2 devices for a non-divisible split")
        with pytest.raises(ValueError, match="divide"):
            _search(net, prof, SimEvaluator(net, xs, prof, cache=ev.cache),
                    engine="sharded", population_size=N_DEV * 4 + 1)

    @quick
    def test_islands_need_two_rows_each(self):
        net, xs, prof, ev = get_workload()
        if N_DEV == 1:
            pytest.skip("a single island cannot go below 2 rows without "
                        "tripping the population_size >= 2 check first")
        with pytest.raises(ValueError, match="at least 2"):
            _search(net, prof, SimEvaluator(net, xs, prof, cache=ev.cache),
                    engine="sharded", population_size=N_DEV,
                    n_islands=N_DEV)

    @quick
    def test_unknown_engine_still_rejected(self):
        net, xs, prof, ev = get_workload()
        with pytest.raises(ValueError, match="unknown search engine"):
            _search(net, prof, SimEvaluator(net, xs, prof, cache=ev.cache),
                    engine="tpu")
