"""Tests for the fault-tolerant search runtime
(:mod:`repro.core.resilience` wired through ``core/search.py``,
``core/device_search.py`` and the pricing stack).

Covers the PR-6 acceptance criteria:

* resume determinism — kill a checkpointed run after generation ``g``,
  resume, and the fitness trajectory, eps-Pareto front and knee match the
  uninterrupted run exactly, on both engines and both workload kinds;
* graceful degradation — injected backend failures demote down the
  ``device -> numpy`` chain (logged), and the completed run matches
  a numpy-only run at rtol=1e-9;
* non-finite quarantine — an injected NaN pricing row never reaches the
  survivors, the eps-archive or ``SearchResult.front``, and the ordering of
  the finite rows is unperturbed.
"""

import numpy as np
import pytest

from repro.core.partitioner import SimEvaluator
from repro.core.resilience import (ALWAYS, FallbackChain, FaultPlan,
                                   InjectedFault, RetryPolicy,
                                   SimulatedCrash, finite_mean,
                                   quarantine_rows)
from repro.core.search import (Candidate, evolutionary_search, pareto_ranks,
                               seeded_population)
from repro.neuromorphic import (SimLayer, SimNetwork, loihi2_like,
                                make_inputs, programmed_fc_network,
                                simulate_population)
from repro.neuromorphic.network import _exact_density_mask

quick = pytest.mark.quick
pytestmark = pytest.mark.timeout(300)


def fc_workload(sizes=(48, 64, 32), wd=0.6, ad=0.3, steps=2):
    net = programmed_fc_network(
        list(sizes), weight_densities=[wd] * (len(sizes) - 1),
        act_densities=[ad] * (len(sizes) - 1), seed=0,
        weight_format="sparse")
    xs = make_inputs(sizes[0], ad, steps, seed=1)
    return net, xs


def conv_workload(steps=2):
    rng = np.random.default_rng(2)
    layers = []
    h = w = 8
    c_prev = 2
    for i, c in enumerate((4, 8)):
        wgt = rng.normal(0, 1 / 3.0, (3, 3, c_prev, c)).astype(np.float32)
        wgt *= _exact_density_mask(wgt.shape, 0.6, rng)
        layers.append(SimLayer(name=f"conv{i}", kind="conv", weights=wgt,
                               stride=2, in_hw=(h, w)))
        h, w, c_prev = h // 2, w // 2, c
    wfc = rng.normal(0, 0.3, (h * w * c_prev, 10)).astype(np.float32)
    layers.append(SimLayer(name="fc", kind="fc", weights=wfc))
    net = SimNetwork(layers=layers, in_size=8 * 8 * 2)
    return net, make_inputs(net.in_size, 0.4, steps, seed=3)


_WORKLOADS: dict = {}


def get_workload(kind: str):
    """(net, xs, prof, shared evaluator) per workload kind, module-cached so
    every test prices from one warm flow/jit cache."""
    if kind not in _WORKLOADS:
        net, xs = fc_workload() if kind == "fc" else conv_workload()
        prof = loihi2_like()
        _WORKLOADS[kind] = (net, xs, prof, SimEvaluator(net, xs, prof))
    return _WORKLOADS[kind]


def _traj(res):
    return [(g.generation, g.best_time, g.best_energy, g.mean_time,
             g.n_evals, g.front_size, g.n_quarantined) for g in res.history]


# ------------------------------------------------------- resume determinism

class TestResumeDeterminism:
    @pytest.mark.parametrize("engine", ["numpy", "device"])
    @pytest.mark.parametrize("kind", ["fc", "conv"])
    def test_kill_and_resume_matches_uninterrupted(self, tmp_path, engine,
                                                   kind):
        """Kill after generation 2 of 4 (checkpoint already on disk), resume
        from the directory: fitness trajectory, front and knee are identical
        to the run that never crashed."""
        net, xs, prof, ev = get_workload(kind)
        kw = dict(population_size=6, generations=4, seed=3, engine=engine)
        full = evolutionary_search(
            net, prof, SimEvaluator(net, xs, prof, cache=ev.cache), **kw)
        d = str(tmp_path / "ck")
        with pytest.raises(SimulatedCrash):
            evolutionary_search(
                net, prof, SimEvaluator(net, xs, prof, cache=ev.cache),
                checkpoint_dir=d, fault_plan=FaultPlan(kill_after_gen=2),
                **kw)
        res = evolutionary_search(
            net, prof, SimEvaluator(net, xs, prof, cache=ev.cache),
            checkpoint_dir=d, resume=True, **kw)
        assert _traj(res) == _traj(full)
        assert res.front == full.front
        assert [r.time_per_step for r in res.front_reports] == \
            [r.time_per_step for r in full.front_reports]
        assert res.knee()[0] == full.knee()[0]
        assert res.candidate == full.candidate

    @quick
    def test_resume_without_checkpoint_starts_fresh(self, tmp_path):
        """``resume=True`` on an empty directory is a cold start, not an
        error — the idiom is 'always pass --resume' in restart loops."""
        net, xs, prof, ev = get_workload("fc")
        res = evolutionary_search(
            net, prof, SimEvaluator(net, xs, prof, cache=ev.cache),
            population_size=4, generations=2, seed=0,
            checkpoint_dir=str(tmp_path / "empty"), resume=True)
        assert res.history[-1].generation == 2

    @quick
    def test_resume_rejects_engine_mismatch(self, tmp_path):
        """A numpy-engine snapshot must not silently seed a device-engine
        run (different RNG contracts): loud error instead."""
        net, xs, prof, ev = get_workload("fc")
        d = str(tmp_path / "ck")
        evolutionary_search(
            net, prof, SimEvaluator(net, xs, prof, cache=ev.cache),
            population_size=4, generations=2, seed=0, checkpoint_dir=d)
        with pytest.raises(ValueError, match="engine"):
            evolutionary_search(
                net, prof, SimEvaluator(net, xs, prof, cache=ev.cache),
                population_size=4, generations=3, seed=0,
                checkpoint_dir=d, resume=True, engine="device")

    @quick
    def test_checkpoint_every_still_resumes(self, tmp_path):
        """Sparse cadence (every=2) + kill at an unsnapshotted generation:
        resume replays from the newest snapshot and still converges to the
        uninterrupted trajectory (same per-generation RNG contract)."""
        net, xs, prof, ev = get_workload("fc")
        kw = dict(population_size=5, generations=4, seed=9)
        full = evolutionary_search(
            net, prof, SimEvaluator(net, xs, prof, cache=ev.cache), **kw)
        d = str(tmp_path / "ck")
        with pytest.raises(SimulatedCrash):
            evolutionary_search(
                net, prof, SimEvaluator(net, xs, prof, cache=ev.cache),
                checkpoint_dir=d, checkpoint_every=2,
                fault_plan=FaultPlan(kill_after_gen=3), **kw)
        res = evolutionary_search(
            net, prof, SimEvaluator(net, xs, prof, cache=ev.cache),
            checkpoint_dir=d, checkpoint_every=2, resume=True, **kw)
        assert _traj(res)[-1] == _traj(full)[-1]
        assert res.front == full.front


# ---------------------------------------------------- graceful degradation

class TestDegradation:
    def test_chain_demotes_to_numpy_and_matches(self):
        """Permanent device outage: the run completes on the numpy
        backend with one logged demotion, and the trajectory/front match a
        numpy-only run at rtol=1e-9 (criterion; the final link is the
        bit-exact reference backend, so equality is in fact exact)."""
        net, xs, prof, ev = get_workload("fc")
        kw = dict(population_size=6, generations=3, seed=3)
        faulty = SimEvaluator(
            net, xs, prof, cache=ev.cache, population_backend="device",
            fault_plan=FaultPlan(fail={"device": ALWAYS}),
            retry=RetryPolicy(max_retries=1), fallback=True)
        deg = evolutionary_search(net, prof, faulty, **kw)
        ref = evolutionary_search(
            net, prof, SimEvaluator(net, xs, prof, cache=ev.cache), **kw)
        assert [(x.frm, x.to) for x in deg.demotions] == \
            [("device", "numpy")]
        assert faulty.active_backend == "numpy"
        np.testing.assert_allclose(
            [g.best_time for g in deg.history],
            [g.best_time for g in ref.history], rtol=1e-9)
        np.testing.assert_allclose(
            [g.best_energy for g in deg.history],
            [g.best_energy for g in ref.history], rtol=1e-9)
        assert deg.front == ref.front

    @quick
    def test_retry_absorbs_transient_fault(self):
        """One transient device fault, default one-retry policy: no
        demotion, result identical to the fault-free run on the same
        backend."""
        net, xs, prof, ev = get_workload("fc")
        kw = dict(population_size=5, generations=2, seed=1)
        faulty = SimEvaluator(net, xs, prof, cache=ev.cache,
                              population_backend="device",
                              fault_plan=FaultPlan(fail={"device": 1}),
                              fallback=True)
        res = evolutionary_search(net, prof, faulty, **kw)
        clean = evolutionary_search(
            net, prof, SimEvaluator(net, xs, prof, cache=ev.cache,
                                    population_backend="device"), **kw)
        assert res.demotions == []
        assert faulty.active_backend == "device"
        assert _traj(res) == _traj(clean)

    def test_device_engine_demotes_to_mirror(self):
        """Device-engine outage at init: the run completes on the host
        numpy mirror under the same per-generation PRNG contract — exactly
        equal to the ``reference=True`` mirror run, and within 1e-9 of the
        fault-free device run."""
        from repro.core.device_search import evolutionary_search_device
        net, xs, prof, ev = get_workload("fc")
        kw = dict(population_size=6, generations=3, seed=3)
        full = evolutionary_search_device(
            net, prof, SimEvaluator(net, xs, prof, cache=ev.cache), **kw)
        mir = evolutionary_search_device(
            net, prof, SimEvaluator(net, xs, prof, cache=ev.cache),
            reference=True, **kw)
        deg = evolutionary_search_device(
            net, prof, SimEvaluator(net, xs, prof, cache=ev.cache,
                                    fallback=True),
            fault_plan=FaultPlan(fail={"device": ALWAYS}),
            retry=RetryPolicy(max_retries=0), **kw)
        assert [(x.frm, x.to) for x in deg.demotions] == \
            [("device", "numpy-mirror")]
        assert _traj(deg) == _traj(mir)
        np.testing.assert_allclose(
            [g.best_time for g in deg.history],
            [g.best_time for g in full.history], rtol=1e-9)

    @quick
    @pytest.mark.parametrize("site", ["pricing", "engine"])
    def test_default_is_fail_fast(self, site):
        """Without ``fallback=True`` an injected device failure propagates:
        no retry, no demotion, no silent switch to a host backend."""
        from repro.core.device_search import evolutionary_search_device
        net, xs, prof, ev = get_workload("fc")
        plan = FaultPlan(fail={"device": 1})
        kw = dict(population_size=4, generations=2, seed=0)
        with pytest.raises(InjectedFault):
            if site == "pricing":
                evolutionary_search(net, prof, SimEvaluator(
                    net, xs, prof, cache=ev.cache,
                    population_backend="device", fault_plan=plan), **kw)
            else:
                evolutionary_search_device(
                    net, prof, SimEvaluator(net, xs, prof, cache=ev.cache),
                    fault_plan=plan, **kw)

    @quick
    def test_exhausted_chain_raises_last_error(self):
        """The numpy reference backend is the last link: its failure
        propagates instead of looping."""
        chain = FallbackChain("numpy", retry=RetryPolicy(max_retries=0))

        def attempt(backend):
            raise InjectedFault(f"down: {backend}")
        with pytest.raises(InjectedFault, match="down: numpy"):
            chain.run(attempt)
        assert chain.demotions == []

    @quick
    def test_chain_never_absorbs_simulated_crash(self):
        """:class:`SimulatedCrash` models ``kill -9``: no retry or fallback
        handler may catch it."""
        chain = FallbackChain("device")
        with pytest.raises(SimulatedCrash):
            chain.run(lambda backend: (_ for _ in ()).throw(
                SimulatedCrash("kill")))
        assert chain.backend == "device" and chain.demotions == []


# ------------------------------------------------------ NaN/inf quarantine

class TestQuarantine:
    def test_nan_row_never_reaches_front_or_archive(self):
        """End-to-end: two scripted NaN pricing rows in generation 1.
        Every survivor statistic, archive point and front report stays
        finite, and the quarantine counter records exactly the injected
        rows."""
        net, xs, prof, ev = get_workload("fc")
        res = evolutionary_search(
            net, prof,
            SimEvaluator(net, xs, prof, cache=ev.cache,
                         fault_plan=FaultPlan(nan_rows={1: (0, 2)})),
            population_size=6, generations=3, seed=3)
        assert all(np.isfinite(g.best_time) for g in res.history)
        assert all(np.isfinite(g.best_energy) for g in res.history)
        assert all(np.isfinite(g.mean_time) for g in res.history)
        assert sum(g.n_quarantined for g in res.history) == 2
        # the eps-archive's items ARE the returned front: all finite
        assert len(res.front_reports) == res.history[-1].front_size
        for r in res.front_reports:
            assert np.isfinite(r.time_per_step)
            assert np.isfinite(r.energy_per_step)

    @quick
    def test_finite_ordering_unperturbed(self):
        """The survival sort of the finite rows is exactly the sort of the
        finite subset alone — quarantined rows behave as if never priced
        (they sort last, after every finite row)."""
        rng = np.random.default_rng(5)
        t = rng.uniform(10, 100, size=12)
        e = rng.uniform(10, 100, size=12)
        corrupt = np.array([1, 4, 7])
        tc, ec = t.copy(), e.copy()
        tc[corrupt] = np.nan
        ec[corrupt[0]] = np.inf          # mixed NaN/inf corruption
        qt, qe, bad = quarantine_rows(np, tc, ec)
        assert set(np.flatnonzero(bad)) == set(corrupt)
        order = np.lexsort((qe, qt, pareto_ranks(qt, qe)))
        # quarantined rows occupy exactly the tail
        assert set(order[-len(corrupt):]) == set(corrupt)
        finite = np.setdiff1d(np.arange(12), corrupt)
        ref = np.lexsort((e[finite], t[finite],
                          pareto_ranks(t[finite], e[finite])))
        np.testing.assert_array_equal(order[:-len(corrupt)], finite[ref])
        # finite rows pass through bit-unchanged
        np.testing.assert_array_equal(qt[finite], t[finite])
        np.testing.assert_array_equal(qe[finite], e[finite])

    @quick
    def test_unscreened_nan_would_rank_zero(self):
        """The failure mode quarantine exists for: NaN comparisons are all
        False, so an unscreened NaN row is never dominated and ranks 0."""
        t = np.array([1.0, np.nan, 3.0])
        e = np.array([3.0, np.nan, 1.0])
        assert pareto_ranks(t, e)[1] == 0          # poisoned
        qt, qe, _ = quarantine_rows(np, t, e)
        ranks = pareto_ranks(qt, qe)
        assert ranks[1] > max(ranks[0], ranks[2])  # quarantined: sorts last

    @quick
    def test_sorted_state_quarantines_under_jit(self):
        """The shared ``_sorted_state`` skeleton quarantines on the jnp
        path too (it is traced into the jitted init/step programs)."""
        import jax
        import jax.numpy as jnp
        from repro.core.device_search import (_sorted_state,
                                              pareto_ranks_array)
        K = 6
        t = np.array([30.0, np.nan, 10.0, np.inf, 20.0, 40.0])
        e = np.array([1.0, 2.0, 3.0, 4.0, 5.0, 6.0])
        with jax.enable_x64(True):
            out = dict(times=jnp.asarray(t), energies=jnp.asarray(e),
                       stage=jnp.zeros(K, jnp.int32),
                       hot_mem=jnp.zeros(K, jnp.int32),
                       hot_act=jnp.zeros(K, jnp.int32))
            cores = jnp.arange(K, dtype=jnp.int32)[:, None]
            perm = jnp.tile(jnp.arange(3, dtype=jnp.int32), (K, 1))
            state = jax.jit(
                lambda c, p, o: _sorted_state(jnp, pareto_ranks_array,
                                              c, p, o, K)
            )(cores, perm, out)
        times = np.asarray(state["times"])
        assert np.all(np.isinf(times[-2:]))        # rows 1 and 3, sentineled
        assert set(np.asarray(state["cores"])[:, 0][-2:].tolist()) == {1, 3}
        np.testing.assert_array_equal(np.sort(times[:4]),
                                      np.array([10.0, 20.0, 30.0, 40.0]))

    @quick
    def test_finite_mean_matches_mean_when_all_finite(self):
        rng = np.random.default_rng(0)
        v = rng.uniform(1, 9, size=17)
        assert finite_mean(np, v) == v.mean()      # bit-equal, same sum
        v2 = v.copy()
        v2[3] = np.nan
        keep = np.delete(v2, 3)
        assert finite_mean(np, v2) == keep.sum() / keep.size
        assert finite_mean(np, np.full(4, np.nan)) == np.inf


# ------------------------------------------------------- input validation

class TestValidation:
    @quick
    @pytest.mark.parametrize("engine", ["numpy", "device"])
    def test_population_size_too_small(self, engine):
        net, xs, prof, ev = get_workload("fc")
        with pytest.raises(ValueError, match="population_size"):
            evolutionary_search(net, prof, ev, population_size=1,
                                generations=2, engine=engine)

    @quick
    @pytest.mark.parametrize("engine", ["numpy", "device"])
    def test_generations_too_small(self, engine):
        net, xs, prof, ev = get_workload("fc")
        with pytest.raises(ValueError, match="generations"):
            evolutionary_search(net, prof, ev, population_size=4,
                                generations=0, engine=engine)

    @quick
    def test_seed_candidate_shape_mismatch(self):
        net, xs, prof, ev = get_workload("fc")
        bad = Candidate(cores=(1,) * (len(net.layers) + 1),
                        perm=tuple(range(prof.n_cores)))
        with pytest.raises(ValueError, match="seed candidate 0"):
            evolutionary_search(net, prof, ev, population_size=4,
                                generations=2, seed_candidates=[bad])

    @quick
    def test_simulate_population_rejects_disagreeing_pair(self):
        """A (partition, mapping) pair whose widths disagree fails loudly
        up front, naming the candidate, instead of a cryptic gather error
        deep in the flow build."""
        from repro.core.search import decode
        net, xs, prof, ev = get_workload("fc")
        rng = np.random.default_rng(0)
        good = [decode(c) for c in
                seeded_population(net, prof, size=3, rng=rng)]
        part0, _ = good[0]
        short = good[1][1]
        # graft a mapping truncated to fewer cores than the partition has
        short = type(short)(phys=short.phys[:part0.total_cores - 1])
        with pytest.raises(ValueError, match="candidate 0"):
            simulate_population(net, xs, prof, [(part0, short)] + good[1:],
                                cache=ev.cache)

    @quick
    def test_price_population_device_rejects_bad_shapes(self):
        from repro.neuromorphic.timestep import price_population_device
        net, xs, prof, ev = get_workload("fc")
        cores = np.ones((3, len(net.layers)), np.int32)
        perm = np.tile(np.arange(prof.n_cores, dtype=np.int32), (4, 1))
        with pytest.raises(ValueError):
            price_population_device(net, prof, ev.cache, cores, perm)


# ------------------------------------------------------- fault-plan basics

class TestFaultPlan:
    @quick
    def test_fail_budget_decrements(self):
        plan = FaultPlan(fail={"device": 2})
        for _ in range(2):
            with pytest.raises(InjectedFault):
                plan.check("device")
        plan.check("device")                       # budget spent: clean
        plan.check("numpy")                        # other sites untouched

    @quick
    def test_kill_fires_once(self):
        plan = FaultPlan(kill_after_gen=2)
        plan.after_generation(0)
        plan.after_generation(1)
        with pytest.raises(SimulatedCrash):
            plan.after_generation(2)
        plan.after_generation(3)                   # resumed run: no re-kill

    @quick
    def test_corrupt_schedule_is_per_call(self):
        plan = FaultPlan(nan_rows={1: (0,)})
        t0, e0 = plan.corrupt_arrays(np.ones(3), np.ones(3))
        assert np.isfinite(t0).all()               # call 0: clean
        t1, e1 = plan.corrupt_arrays(np.ones(3), np.ones(3))
        assert np.isnan(t1[0]) and np.isnan(e1[0])
        assert np.isfinite(t1[1:]).all()
