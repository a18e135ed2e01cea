"""Population-pricing tests: the device pricer's padding and masking
contract (:meth:`repro.neuromorphic.timestep.DevicePopulationPricer.structures_row`)
and the two jitted population backends, ``device``
(:func:`repro.neuromorphic.timestep.price_population_device`) and
``sharded`` (:func:`repro.neuromorphic.timestep.price_population_sharded`).

Parity contract (``docs/simulator.md``): the NumPy population path is
bit-identical to per-candidate ``simulate``; the jitted backends run the
same float64 formulas under XLA (which may reassociate/fuse), so they are
asserted to ``rtol=1e-9`` instead.
"""

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.partitioner import SimEvaluator
from repro.core.search import (Population, decode, decode_population,
                               encode, encode_population, move_tables,
                               seeded_population)
from repro.neuromorphic import (Partition, SimLayer, SimNetwork, fc_network,
                                loihi2_like, make_inputs, minimal_partition,
                                ordered_mapping, programmed_fc_network,
                                random_mapping, simulate, simulate_population,
                                speck_like, strided_mapping)
from repro.neuromorphic.network import _exact_density_mask
from repro.neuromorphic.timestep import (device_pricer, population_pad_width,
                                         precompute_pricing,
                                         price_population_device)

quick = pytest.mark.quick

RTOL = 1e-9


def fc_workload(sizes=(96, 128, 128, 64), wd=0.6, ad=0.3, steps=3):
    net = programmed_fc_network(
        list(sizes), weight_densities=[wd] * (len(sizes) - 1),
        act_densities=[ad] * (len(sizes) - 1), seed=0,
        weight_format="sparse")
    return net, make_inputs(sizes[0], ad, steps, seed=1)


def conv_workload(steps=3):
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


class TestStructuresRow:
    @quick
    def test_padding_and_masking_contract(self):
        import jax
        import jax.numpy as jnp

        net, xs = fc_workload()
        prof = loihi2_like()
        cache = precompute_pricing(net, xs, prof)
        p0 = minimal_partition(net, prof)
        pairs = [(p0, ordered_mapping(p0, prof)),
                 (p0.split(0).split(1), strided_mapping(p0.split(0).split(1),
                                                        prof))]
        pop = Population.from_candidates(
            [encode(p, m, prof.n_cores) for p, m in pairs])
        pricer = device_pricer(net, prof, cache)
        n_pad = population_pad_width(net, prof)
        for k, (part, _) in enumerate(pairs):
            with jax.enable_x64(True):
                mask, lid, seg_lo, seg_hi, neurons, PL, ph, dup = (
                    np.asarray(a) for a in pricer.structures_row(
                        jnp.asarray(pop.cores[k]), jnp.asarray(pop.perm[k])))
            assert mask.shape == (n_pad,)
            n = part.total_cores
            assert mask[:n].all() and not mask[n:].any()
            # padded cores gather empty segments: lo == hi == 0
            assert not seg_lo[n:].any()
            assert not seg_hi[n:].any()
            assert not neurons[n:].any()
            assert not PL[n:].any()
            # live cores cover each layer's neuron range exactly
            assert neurons[:n].sum() == sum(l.n_neurons for l in net.layers)


def _assert_reports_close(a, b):
    for f in ("times", "energies", "per_core_synops", "per_core_acts",
              "per_core_msgs_out"):
        va, vb = getattr(a, f), getattr(b, f)
        assert va.shape == vb.shape, f
        assert np.allclose(va, vb, rtol=RTOL, atol=RTOL), f
    for f in ("time_per_step", "energy_per_step", "max_synops", "max_acts",
              "max_link_load"):
        assert np.isclose(getattr(a, f), getattr(b, f), rtol=RTOL), f
    assert a.bottleneck_stage == b.bottleneck_stage
    assert a.n_cores_active == b.n_cores_active
    ma, mb = a.metrics, b.metrics
    assert np.isclose(ma.msgs_total, mb.msgs_total, rtol=RTOL)
    assert np.isclose(ma.weight_density, mb.weight_density, rtol=RTOL)
    assert np.isclose(ma.act_density, mb.act_density, rtol=RTOL)
    for s in ("synops", "acts", "traffic"):
        sa, sb = getattr(ma, s), getattr(mb, s)
        assert (sa.n_units, sa.n_active) == (sb.n_units, sb.n_active), s
        assert np.isclose(sa.total, sb.total, rtol=RTOL), s
        assert np.isclose(sa.max, sb.max, rtol=RTOL), s
        assert np.isclose(sa.imbalance, sb.imbalance, rtol=RTOL), s


POPULATION_BACKENDS = ("device", "sharded")


@pytest.mark.parametrize("backend", POPULATION_BACKENDS)
class TestJittedBackends:
    """The jitted population backends: genome arrays in, the padded
    per-core structures derived on device, float64-roundoff parity with the
    NumPy path.  ``sharded`` runs the ``device`` program with the
    population axis sharded over every visible device."""

    @quick
    def test_fc_parity_with_simulate(self, backend):
        net, xs = fc_workload()
        prof = loihi2_like()
        rng = np.random.default_rng(4)
        p0 = minimal_partition(net, prof)
        pairs = [(p0, ordered_mapping(p0, prof)),
                 (p0.split(0), strided_mapping(p0.split(0), prof)),
                 (p0.split(1).split(1),
                  random_mapping(p0.split(1).split(1), prof, rng))]
        reports = simulate_population(net, xs, prof, pairs, backend=backend)
        for (p, m), rp in zip(pairs, reports):
            _assert_reports_close(
                rp, simulate(net, xs, prof, p, m, engine="batched"))

    def test_conv_parity_with_numpy_backend(self, backend):
        net, xs = conv_workload()
        prof = loihi2_like()
        parts = [Partition((1, 1, 1)), Partition((2, 4, 2)),
                 Partition((4, 8, 1))]
        pairs = [(p, strided_mapping(p, prof)) for p in parts]
        r_np = simulate_population(net, xs, prof, pairs)
        r_jit = simulate_population(net, xs, prof, pairs, backend=backend)
        for a, b in zip(r_np, r_jit):
            _assert_reports_close(a, b)

    @quick
    def test_empty_core_segments(self, backend):
        net = fc_network([16, 6, 8], weight_density=1.0, seed=19)
        xs = make_inputs(16, 0.8, 3, seed=20)
        prof = loihi2_like()
        pairs = [(Partition((1, 1)), ordered_mapping(Partition((1, 1)),
                                                     prof)),
                 (Partition((7, 2)), strided_mapping(Partition((7, 2)),
                                                     prof))]
        for (p, m), rp in zip(pairs, simulate_population(net, xs, prof,
                                                         pairs,
                                                         backend=backend)):
            _assert_reports_close(rp, simulate(net, xs, prof, p, m))

    def test_async_platform_parity(self, backend):
        """Speck-style chips take the pipeline-latency branch of the jitted
        program (per-layer segment maxima instead of the barrier max)."""
        prof = speck_like()
        rng = np.random.default_rng(7)
        layers = []
        h = w = 8
        c_prev = 2
        for i, c in enumerate((4, 4)):
            wgt = rng.normal(0, 1 / 3.0,
                             (3, 3, c_prev, c)).astype(np.float32)
            layers.append(SimLayer(name=f"c{i}", kind="conv", weights=wgt,
                                   stride=2, in_hw=(h, w), neuron_model="if",
                                   threshold=1.0))
            h, w, c_prev = h // 2, w // 2, c
        net = SimNetwork(layers=layers, in_size=8 * 8 * 2)
        xs = make_inputs(net.in_size, 0.4, 3, seed=8)
        p = minimal_partition(net, prof)
        pairs = [(p, ordered_mapping(p, prof))]
        for (pp, m), rp in zip(pairs, simulate_population(net, xs, prof,
                                                          pairs,
                                                          backend=backend)):
            _assert_reports_close(rp, simulate(net, xs, prof, pp, m))

    @quick
    def test_large_population_parity_spot_checks(self, backend):
        """A seeded 32-candidate population prices to the same results as
        the NumPy path (spot-checked pointwise over the whole batch)."""
        net, xs = fc_workload(steps=2)
        prof = loihi2_like()
        rng = np.random.default_rng(9)
        pairs = [decode(c) for c in seeded_population(net, prof, size=32,
                                                      rng=rng)]
        r_np = simulate_population(net, xs, prof, pairs)
        r_jit = simulate_population(net, xs, prof, pairs, backend=backend)
        assert len(r_np) == len(r_jit) == 32
        for a, b in zip(r_np, r_jit):
            _assert_reports_close(a, b)

    @quick
    def test_evaluator_counts_and_matches(self, backend):
        net, xs = fc_workload()
        prof = loihi2_like()
        ev_np = SimEvaluator(net, xs, prof)
        ev_jit = SimEvaluator(net, xs, prof, cache=ev_np.cache,
                              population_backend=backend)
        p0 = minimal_partition(net, prof)
        pairs = [(p0, strided_mapping(p0, prof)),
                 (p0.split(2), ordered_mapping(p0.split(2), prof))]
        a = ev_np.evaluate_population(pairs)
        b = ev_jit.evaluate_population(pairs)
        assert ev_jit.n_evals == 2
        for ra, rb in zip(a, b):
            _assert_reports_close(ra, rb)


@quick
@pytest.mark.parametrize("backend", ["tpu", "vmap"])
def test_unknown_backend_raises(backend):
    net, xs = fc_workload(steps=2)
    prof = loihi2_like()
    p0 = minimal_partition(net, prof)
    with pytest.raises(ValueError, match="backend"):
        simulate_population(net, xs, prof,
                            [(p0, ordered_mapping(p0, prof))],
                            backend=backend)


class TestDeviceBackend:
    @quick
    def test_accepts_on_device_genome_arrays(self):
        """price_population_device is the re-pricing entry point for
        populations that already live on the accelerator: jnp inputs, no
        pre-built batch."""
        import jax.numpy as jnp

        net, xs = fc_workload(steps=2)
        prof = loihi2_like()
        cache = precompute_pricing(net, xs, prof)
        rng = np.random.default_rng(23)
        cands = seeded_population(net, prof, size=6, rng=rng)
        pop = Population.from_candidates(cands)
        reports = price_population_device(net, prof, cache,
                                          jnp.asarray(pop.cores),
                                          jnp.asarray(pop.perm))
        r_np = simulate_population(net, xs, prof,
                                   [decode(c) for c in cands], cache=cache)
        for a, b in zip(r_np, reports):
            _assert_reports_close(a, b)


class TestTensorFirstRoundTrip:
    @quick
    @settings(max_examples=15, deadline=None)
    @given(st.integers(min_value=0, max_value=2**31 - 1))
    def test_population_round_trip(self, seed):
        """Hypothesis round-trip: random valid genomes survive
        encode_population -> decode_population and the Population view
        unchanged."""
        prof = loihi2_like()
        net, _ = fc_workload(steps=2)
        rng = np.random.default_rng(seed)
        tables = move_tables(net, prof)
        cands = []
        for _ in range(int(rng.integers(1, 7))):
            cores = np.ones(len(net.layers), np.int32)
            for _ in range(int(rng.integers(0, 8))):
                l = int(rng.integers(len(net.layers)))
                if tables.feasible[l, cores[l] + 1] \
                        and cores.sum() + 1 <= prof.n_cores:
                    cores[l] += 1
            part = Partition(tuple(int(x) for x in cores))
            cands.append(encode(part, random_mapping(part, prof, rng),
                                prof.n_cores))
        cores_mat, perm_mat = encode_population(cands)
        assert cores_mat.shape == (len(cands), len(net.layers))
        assert perm_mat.shape == (len(cands), prof.n_cores)
        assert decode_population(cores_mat, perm_mat) == cands
        pop = Population(cores_mat, perm_mat)
        assert pop.candidates() == cands
        for k, c in enumerate(cands):
            p, m = decode(c)
            pp, pm = pop.pairs()[k]
            assert pp == p
            assert tuple(pm.phys) == tuple(m.phys)
            # every row is a permutation of all physical slots
            assert sorted(pop.perm[k]) == list(range(prof.n_cores))


class TestTrainedProfileParity:
    """Acceptance contract for trained sparsity profiles: a profile-applied
    workload prices bit-identically on the numpy population backend (vs
    per-candidate ``simulate``) and to float64-roundoff parity on device —
    profile injection only rewrites the NETWORK (gates + masked
    weights), never the pricing math, so every backend guarantee holds."""

    def _profiled_workload(self, steps=3):
        from repro.sparsity import SparsityProfile
        rng = np.random.default_rng(31)
        net = fc_network([48, 64, 64, 32], weight_density=1.0, seed=30)
        masks = tuple(
            _exact_density_mask(l.weights.shape, d, rng).astype(np.float32)
            for l, d in zip(net.layers, (0.7, 0.5, 0.8)))
        profile = SparsityProfile(
            layer_names=tuple(l.name for l in net.layers),
            act_density=np.array([0.35, 0.5, 0.2]),
            weight_density=np.array([0.7, 0.5, 0.8]),
            weight_masks=masks, input_density=0.4)
        return net, profile, make_inputs(48, 0.4, steps, seed=32)

    @quick
    def test_three_way_backend_parity_under_profile(self):
        net, profile, xs = self._profiled_workload()
        prof = loihi2_like()
        applied = profile.apply(net)
        rng = np.random.default_rng(33)
        pairs = [decode(c) for c in seeded_population(applied, prof,
                                                      size=8, rng=rng)]
        r_np = simulate_population(net, xs, prof, pairs,
                                   sparsity_profile=profile)
        r_dev = simulate_population(applied, xs, prof, pairs,
                                    backend="device")
        for (p, m), a, b in zip(pairs, r_np, r_dev):
            ref = simulate(net, xs, prof, p, m, sparsity_profile=profile)
            # numpy population path is BIT-identical to simulate
            assert a.time_per_step == ref.time_per_step
            assert a.energy_per_step == ref.energy_per_step
            _assert_reports_close(a, b)

    @quick
    def test_profile_injection_equals_pre_applied_net(self):
        net, profile, xs = self._profiled_workload()
        prof = loihi2_like()
        r1 = simulate(net, xs, prof, sparsity_profile=profile)
        r2 = simulate(profile.apply(net), xs, prof)
        assert r1.time_per_step == r2.time_per_step
        assert r1.energy_per_step == r2.energy_per_step

    def test_evaluator_profile_matches_applied_net(self):
        net, profile, xs = self._profiled_workload()
        prof = loihi2_like()
        e1 = SimEvaluator(net, xs, prof, sparsity_profile=profile)
        e2 = SimEvaluator(profile.apply(net), xs, prof)
        p0 = minimal_partition(profile.apply(net), prof)
        m0 = ordered_mapping(p0, prof)
        a, b = e1(p0, m0), e2(p0, m0)
        assert a.time_per_step == b.time_per_step
        assert a.energy_per_step == b.energy_per_step
