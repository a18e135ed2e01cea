"""Device-resident evolutionary engine tests (:mod:`repro.core.device_search`).

Two layers of guarantees:

* **decision parity** — selection, mutation, and survival are the same
  array program under ``xp=numpy`` and ``xp=jax.numpy``; given the shared
  PRNG-key draws they must agree EXACTLY (integer genome ops);
* **trajectory parity** — a full ``engine="device"`` run and the host
  NumPy mirror (``reference=True``, bit-exact numpy pricing) replay the
  same fitness trajectory to float64 roundoff and land on the same final
  candidate, under the shared PRNG-key contract.

Plus the mutation edge cases of the array path: single-layer networks,
populations where no row has a feasible split/merge, and duplicate
phenotypes after mutation.
"""

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

import jax
import jax.numpy as jnp

from repro.core.device_search import (STAGE_ID, _NumpyMirror,
                                      evolutionary_search_device,
                                      generation_draws, mutate_rows_array,
                                      pareto_ranks_array,
                                      survival_order_array)
from repro.core.partitioner import SimEvaluator
from repro.core.search import (Population, decode, encode,
                               evolutionary_search, move_tables, pareto_ranks,
                               seeded_population)
from repro.neuromorphic import (loihi2_like, make_inputs, minimal_partition,
                                programmed_fc_network, random_mapping,
                                strided_mapping)
from repro.neuromorphic.partition import validate_partition

quick = pytest.mark.quick


def fc_workload(sizes=(96, 128, 64), wd=0.6, ad=0.3, steps=2):
    net = programmed_fc_network(
        list(sizes), weight_densities=[wd] * (len(sizes) - 1),
        act_densities=[ad] * (len(sizes) - 1), seed=0,
        weight_format="sparse")
    return net, make_inputs(sizes[0], ad, steps, seed=1)


_WORKLOAD: dict = {}


def get_workload():
    """One shared (net, xs, prof, evaluator) so the device pricer/engine
    compile once for the whole module (also usable outside fixtures:
    hypothesis tests do not take function-scoped fixtures)."""
    if not _WORKLOAD:
        net, xs = fc_workload()
        prof = loihi2_like()
        _WORKLOAD["value"] = (net, xs, prof, SimEvaluator(net, xs, prof))
    return _WORKLOAD["value"]


@pytest.fixture(scope="module")
def workload():
    return get_workload()


def _seed_rows(net, prof, n, seed=0):
    rng = np.random.default_rng(seed)
    pop = Population.from_candidates(
        seeded_population(net, prof, size=n, rng=rng))
    return pop.cores, pop.perm


class TestDecisionParity:
    """The same array program under numpy and jax.numpy: exact agreement."""

    def _draws(self, key, **kw):
        with jax.enable_x64(True):
            return jax.device_get(generation_draws(key, **kw))

    @quick
    def test_mutation_parity_np_vs_jnp(self, workload):
        net, xs, prof, _ = workload
        tables = move_tables(net, prof)
        cores, perm = _seed_rows(net, prof, 16, seed=1)
        rng = np.random.default_rng(2)
        n = cores.shape[0]
        stage = rng.integers(0, 4, n).astype(np.int32)
        hot_mem = rng.integers(0, cores.shape[1], n).astype(np.int32)
        hot_act = rng.integers(0, cores.shape[1], n).astype(np.int32)
        for s in range(3):
            draws = self._draws(jax.random.PRNGKey(s), n_off=n, n_pop=n,
                                n_layers=cores.shape[1],
                                n_slots=perm.shape[1], tournament_k=3)
            parents = draws["tourn"].min(axis=1)
            args = (cores[parents], perm[parents], stage[parents],
                    hot_mem[parents], hot_act[parents], draws)
            c_np, p_np = mutate_rows_array(
                np, *args, np.asarray(tables.feasible),
                tables.n_cores_phys, 0.25)
            with jax.enable_x64(True):
                c_j, p_j = mutate_rows_array(
                    jnp, *[jnp.asarray(a) if not isinstance(a, dict) else
                           {k: jnp.asarray(v) for k, v in a.items()}
                           for a in args],
                    jnp.asarray(tables.feasible), tables.n_cores_phys, 0.25)
            assert np.array_equal(c_np, np.asarray(c_j))
            assert np.array_equal(p_np, np.asarray(p_j))
            # every offspring row is a valid, changed genome
            for k in range(n):
                i = int(parents[k])
                changed = (not np.array_equal(c_np[k], cores[i])
                           or not np.array_equal(p_np[k], perm[i]))
                assert changed
                assert tables.valid_rows(c_np[k][None, :])[0]
                assert sorted(p_np[k]) == list(range(prof.n_cores))

    @quick
    def test_survival_parity_and_dedup(self, workload):
        net, xs, prof, _ = workload
        cores, perm = _seed_rows(net, prof, 10, seed=4)
        # inject duplicate phenotypes: rows 3/7 copy rows 0/1 (with a
        # shuffled dead tail on one of them — same phenotype, different
        # genome bytes)
        cores[3], perm[3] = cores[0], perm[0]
        cores[7] = cores[1]
        perm[7] = perm[1].copy()
        n_expr = int(cores[7].sum())
        perm[7, n_expr:] = perm[7, n_expr:][::-1]
        rng = np.random.default_rng(5)
        t = rng.uniform(1, 10, len(cores))
        e = rng.uniform(1, 10, len(cores))
        # duplicates must carry identical objectives (same phenotype)
        t[3], e[3] = t[0], e[0]
        t[7], e[7] = t[1], e[1]
        ranks = pareto_ranks(t, e)
        idx_np = survival_order_array(np, cores, perm, t, e, ranks, 6)
        with jax.enable_x64(True):
            ranks_j = pareto_ranks_array(jnp.asarray(t), jnp.asarray(e))
            assert np.array_equal(np.asarray(ranks_j), ranks)
            idx_j = survival_order_array(
                jnp, jnp.asarray(cores), jnp.asarray(perm), jnp.asarray(t),
                jnp.asarray(e), ranks_j, 6)
        assert np.array_equal(idx_np, np.asarray(idx_j))
        # survivors are phenotype-unique (dup rows sorted behind)
        keys = {Population.row_key(cores[i], perm[i]) for i in idx_np}
        assert len(keys) == len(idx_np)

    @quick
    def test_pareto_ranks_device_known_points(self):
        t = np.array([1.0, 2.0, 3.0, 2.0])
        e = np.array([3.0, 1.0, 2.0, 2.0])
        with jax.enable_x64(True):
            r = np.asarray(pareto_ranks_array(jnp.asarray(t),
                                              jnp.asarray(e)))
        assert list(r) == [0, 0, 2, 1]

    @given(seed=st.integers(0, 300), cap=st.integers(1, 24))
    @settings(max_examples=50, deadline=None)
    def test_pareto_ranks_tie_and_cap_parity(self, seed, cap):
        """Host and device ranks agree bit for bit on duplicate (time,
        energy) rows, and on everything below the survivor cutoff after
        rank-capped peeling (unpeeled rows carry the sentinel rank K on
        both sides)."""
        rng = np.random.default_rng(seed)
        k = int(rng.integers(2, 24))
        # tiny integer grid -> many exact duplicates and dominance ties
        t = rng.integers(0, 4, k).astype(np.float64)
        e = rng.integers(0, 4, k).astype(np.float64)
        full_h = pareto_ranks(t, e)
        cap_h = pareto_ranks(t, e, n_keep=cap)
        with jax.enable_x64(True):
            full_d = np.asarray(pareto_ranks_array(jnp.asarray(t),
                                                   jnp.asarray(e)))
            cap_d = np.asarray(pareto_ranks_array(jnp.asarray(t),
                                                  jnp.asarray(e),
                                                  n_keep=cap))
        assert np.array_equal(full_h, full_d)
        assert np.array_equal(cap_h, cap_d)
        # duplicate rows always share a rank
        for i in range(k):
            same = (t == t[i]) & (e == e[i])
            assert (full_h[same] == full_h[i]).all()
            assert (cap_h[same] == cap_h[i]).all()
        # capped == full below the cutoff; sentinel only above it
        peeled = cap_h < k
        assert int(peeled.sum()) >= min(cap, k)
        assert np.array_equal(cap_h[peeled], full_h[peeled])
        if peeled.any() and (~peeled).any():
            assert full_h[~peeled].min() > full_h[peeled].max()


class TestDeviceEngine:
    def test_trajectory_parity_device_vs_numpy_mirror(self, workload):
        """The headline contract: same PRNG keys -> same fitness
        trajectory (float64 roundoff) and same final candidate, device
        (jitted, XLA pricing) vs host mirror (numpy pricing)."""
        net, xs, prof, ev = workload
        res = evolutionary_search(net, prof, ev, population_size=8,
                                  generations=4, seed=7, engine="device")
        ev2 = SimEvaluator(net, xs, prof, cache=ev.cache)
        ref = evolutionary_search_device(net, prof, ev2, population_size=8,
                                         generations=4, seed=7,
                                         reference=True)
        assert len(res.history) == len(ref.history)
        for a, b in zip(res.history, ref.history):
            assert np.isclose(a.best_time, b.best_time, rtol=1e-9)
            assert np.isclose(a.best_energy, b.best_energy, rtol=1e-9)
            assert np.isclose(a.mean_time, b.mean_time, rtol=1e-9)
            assert a.n_evals == b.n_evals
        assert res.candidate == ref.candidate

    @quick
    def test_never_worse_than_seed_and_valid(self, workload):
        net, xs, prof, ev = workload
        rng = np.random.default_rng(5)
        seeds = seeded_population(net, prof, size=8, rng=rng)
        seed_reports = ev.evaluate_population([decode(c) for c in seeds])
        best_seed = min(r.time_per_step for r in seed_reports)
        res = evolutionary_search(net, prof, ev, population_size=8,
                                  generations=4, seed=3,
                                  seed_candidates=seeds, engine="device")
        assert res.report.time_per_step <= best_seed * (1 + 1e-9)
        assert np.isclose(res.seed_best_time, best_seed, rtol=1e-9)
        assert validate_partition(net, res.partition, prof)
        # history is monotone; front exists and knee() resolves
        best = [g.best_time for g in res.history]
        assert all(t2 <= t1 * (1 + 1e-12) for t1, t2 in zip(best, best[1:]))
        assert res.front and res.knee() is not None

    @quick
    def test_determinism_and_budget(self, workload):
        net, xs, prof, ev = workload
        runs = []
        for _ in range(2):
            ev_i = SimEvaluator(net, xs, prof, cache=ev.cache)
            runs.append((evolutionary_search(net, prof, ev_i,
                                             population_size=6,
                                             generations=3, seed=11,
                                             max_evaluations=20,
                                             engine="device"), ev_i))
        (a, ev_a), (b, ev_b) = runs
        assert a.candidate == b.candidate
        assert [g.best_time for g in a.history] == \
            [g.best_time for g in b.history]
        assert a.n_evals == b.n_evals <= 20
        # the device engine charges the evaluator's ledger per generation
        assert ev_a.n_evals == a.n_evals

    @quick
    def test_requires_sim_evaluator_like(self, workload):
        net, xs, prof, ev = workload
        with pytest.raises(TypeError, match="SimEvaluator-like"):
            evolutionary_search(net, prof, lambda p, m: ev(p, m),
                                population_size=4, generations=2,
                                engine="device")

    @quick
    def test_unknown_engine_raises(self, workload):
        net, xs, prof, ev = workload
        with pytest.raises(ValueError, match="engine"):
            evolutionary_search(net, prof, ev, engine="tpu")


class TestMutationEdgeCases:
    @quick
    def test_single_layer_network(self):
        """One-layer genomes: no next layer (all traffic exits at the I/O
        router), hot layer is always 0, and the search still runs device-
        resident end to end."""
        net, xs = fc_workload(sizes=(64, 32))
        prof = loihi2_like()
        assert len(net.layers) == 1
        ev = SimEvaluator(net, xs, prof)
        res = evolutionary_search(net, prof, ev, population_size=6,
                                  generations=3, seed=2, engine="device")
        assert validate_partition(net, res.partition, prof)
        ev2 = SimEvaluator(net, xs, prof, cache=ev.cache)
        ref = evolutionary_search_device(net, prof, ev2, population_size=6,
                                         generations=3, seed=2,
                                         reference=True)
        assert res.candidate == ref.candidate

    @quick
    def test_all_moves_infeasible_falls_back_to_swap(self):
        """allow_partitioning=False masks every split AND every merge
        (all rows pinned at one core per layer): every mutation must fall
        through the cascade to a gene swap, and core counts never move."""
        import dataclasses
        net, xs = fc_workload(sizes=(48, 32, 16))
        prof = dataclasses.replace(loihi2_like(), allow_partitioning=False)
        tables = move_tables(net, prof)
        # the feasibility table really is all-false beyond one core
        assert not tables.feasible[:, 2:].any()
        ev = SimEvaluator(net, xs, prof)
        res = evolutionary_search(net, prof, ev, population_size=4,
                                  generations=3, seed=1, engine="device")
        assert tuple(res.partition.cores) == tuple(1 for _ in net.layers)
        assert validate_partition(net, res.partition, prof)

    @quick
    def test_duplicate_phenotypes_after_mutation_are_deduped(self, workload):
        """Force a degenerate population (every row the same phenotype):
        survivors stay that phenotype or improve, and the engine neither
        crashes nor double-counts the duplicate rows on the front."""
        net, xs, prof, ev = workload
        p0 = minimal_partition(net, prof)
        cand = encode(p0, strided_mapping(p0, prof), prof.n_cores)
        res = evolutionary_search(net, prof, ev, population_size=6,
                                  generations=2, seed=9,
                                  seed_candidates=[cand] * 6,
                                  engine="device")
        assert validate_partition(net, res.partition, prof)
        front_keys = {c for c in map(lambda c: (c.cores, c.perm), res.front)}
        assert len(front_keys) == len(res.front)

    @quick
    @settings(max_examples=4, deadline=None)
    @given(st.integers(min_value=0, max_value=2**31 - 1))
    def test_hypothesis_generation_step_parity(self, seed):
        """Property: for ANY key seed, the generation steps — the same
        survivors state, the same fold_in keys — produce identical
        offspring genomes on device and in the numpy mirror, and survival
        picks the same rows (objectives compared to float64 roundoff)."""
        net, xs, prof, ev = get_workload()
        ev_d = SimEvaluator(net, xs, prof, cache=ev.cache)
        res_d = evolutionary_search(net, prof, ev_d, population_size=6,
                                    generations=2, seed=seed,
                                    engine="device")
        ev_r = SimEvaluator(net, xs, prof, cache=ev.cache)
        res_r = evolutionary_search_device(net, prof, ev_r,
                                           population_size=6, generations=2,
                                           seed=seed, reference=True)
        for a, b in zip(res_d.history, res_r.history):
            assert np.isclose(a.best_time, b.best_time, rtol=1e-9)
        assert res_d.candidate == res_r.candidate
