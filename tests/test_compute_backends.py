"""Dense vs event LayerCompute backend parity.

The contract (``repro.neuromorphic.compute``): every backend produces the
SAME exact integer event counters — so every pricing product (SimReports,
caches, populations) is bit-identical across backends — while float
outputs may differ by contraction reassociation only (rtol <= 1e-6 with a
small atol floor for near-zero entries).  The event backend is exercised
in both kernel modes: ``gather`` (the CPU fast path) and ``pallas`` (the
real kernel body, interpret-auto-selected on CPU so CI executes it).
"""

import numpy as np
import pytest

from repro.neuromorphic import (EventCompute, SimLayer, SimNetwork,
                                fc_network, get_compute, loihi2_like,
                                make_inputs, programmed_fc_network,
                                register_compute, simulate,
                                simulate_population)
from repro.neuromorphic.compute import DenseCompute, LayerCompute, _im2col
from repro.neuromorphic.network import _exact_density_mask

quick = pytest.mark.quick

FLOAT_TOL = dict(rtol=1e-6, atol=1e-6)


def conv_stack(*, neuron_model="relu", sends_deltas=False, threshold=0.0,
               weight_density=0.6, seed=0):
    """conv -> conv -> fc stack (channel-major flat boundaries)."""
    rng = np.random.default_rng(seed)
    layers = []
    h = w = 8
    c_prev = 2
    for i, c in enumerate((4, 8)):
        wgt = rng.normal(0, 1 / 3.0, (3, 3, c_prev, c)).astype(np.float32)
        wgt *= _exact_density_mask(wgt.shape, weight_density, rng)
        layers.append(SimLayer(
            name=f"conv{i}", kind="conv", weights=wgt, stride=2,
            in_hw=(h, w), neuron_model=neuron_model, threshold=threshold,
            sends_deltas=sends_deltas))
        h, w, c_prev = h // 2, w // 2, c
    wfc = rng.normal(0, 0.3, (h * w * c_prev, 10)).astype(np.float32)
    layers.append(SimLayer(name="fc", kind="fc", weights=wfc,
                           neuron_model="relu"))
    return SimNetwork(layers=layers, in_size=8 * 8 * 2)


def assert_backends_match(net, xs, event="event"):
    """run_batch parity: exact counters, roundoff-equal outputs."""
    out_d, cnt_d = net.run_batch(xs, compute="dense")
    out_e, cnt_e = net.run_batch(xs, compute=event)
    np.testing.assert_allclose(out_e, out_d, **FLOAT_TOL)
    for l, (a, b) in enumerate(zip(cnt_d, cnt_e)):
        for field in ("msgs_in", "macs", "fetches_dense", "msgs_out",
                      "acts_evented"):
            assert np.array_equal(getattr(a, field), getattr(b, field)), \
                f"layer {l}: {field} diverged"
    return out_d, out_e


class TestFunctionalParity:
    @quick
    @pytest.mark.parametrize("density", [0.05, 0.3, 0.8])
    def test_fc_relu(self, density):
        net = fc_network([48, 64, 32], weight_density=0.6, seed=0)
        xs = make_inputs(48, density, 12, seed=1)
        assert_backends_match(net, xs)

    @quick
    def test_fc_programmed_gates(self):
        net = programmed_fc_network([40, 64, 48],
                                    weight_densities=[0.7, 0.7],
                                    act_densities=[0.1, 0.2], seed=2)
        xs = make_inputs(40, 0.2, 10, seed=3)
        assert_backends_match(net, xs)

    @quick
    def test_conv_stack(self):
        net = conv_stack(seed=0)
        xs = make_inputs(net.in_size, 0.3, 8, seed=4)
        assert_backends_match(net, xs)

    def test_sigma_delta_chain(self):
        """Delta reconstruction makes x_eff dense while the wire mask stays
        sparse — the two event compactions must diverge correctly."""
        net = fc_network([32, 48, 24], weight_density=0.8,
                         neuron_model="sd_relu", seed=5)
        for l in net.layers:
            l.threshold = 0.05
            l.sends_deltas = True
        xs = make_inputs(32, 0.4, 10, seed=6)
        assert_backends_match(net, xs)

    def test_if_spiking(self):
        net = fc_network([32, 40, 16], weight_density=0.7,
                         neuron_model="if", seed=7)
        for l in net.layers:
            l.threshold = 0.5
        xs = make_inputs(32, 0.5, 10, seed=8)
        assert_backends_match(net, xs)

    @quick
    def test_all_zero_inputs(self):
        """Event-free input: the event path must not fetch, and both
        backends must count zero everywhere."""
        net = fc_network([16, 24, 8], seed=0)
        xs = np.zeros((4, 16), np.float32)
        out_d, out_e = assert_backends_match(net, xs)
        assert np.array_equal(out_d, out_e)   # relu(0) exactly everywhere


class TestSimReportParity:
    @quick
    @pytest.mark.parametrize("workload", ["fc", "conv"])
    def test_counter_derived_reports_identical(self, workload):
        """``simulate(compute="event")`` prices from identical counters, so
        times/energies/per-core aggregates are bit-identical to dense."""
        if workload == "fc":
            net = fc_network([48, 96, 64, 32], weight_density=0.5, seed=1)
            xs = make_inputs(48, 0.25, 12, seed=2)
        else:
            net = conv_stack(seed=1)
            xs = make_inputs(net.in_size, 0.3, 6, seed=3)
        prof = loihi2_like()
        r_d = simulate(net, xs, prof, compute="dense")
        r_e = simulate(net, xs, prof, compute="event")
        np.testing.assert_allclose(r_e.outputs, r_d.outputs, **FLOAT_TOL)
        for field in ("times", "energies", "per_core_synops",
                      "per_core_acts", "per_core_msgs_out"):
            assert np.array_equal(getattr(r_e, field), getattr(r_d, field)), \
                f"{field} diverged"
        assert r_e.max_synops == r_d.max_synops
        assert r_e.max_acts == r_d.max_acts
        assert r_e.max_link_load == r_d.max_link_load
        assert r_e.bottleneck_stage == r_d.bottleneck_stage
        assert r_e.metrics == r_d.metrics

    @quick
    def test_reference_engine_honors_compute(self):
        net = fc_network([32, 48, 24], weight_density=0.6, seed=3)
        xs = make_inputs(32, 0.3, 6, seed=4)
        prof = loihi2_like()
        r_d = simulate(net, xs, prof, engine="reference", compute="dense")
        r_e = simulate(net, xs, prof, engine="reference", compute="event")
        np.testing.assert_allclose(r_e.outputs, r_d.outputs, **FLOAT_TOL)
        assert np.array_equal(r_e.times, r_d.times)
        assert np.array_equal(r_e.energies, r_d.energies)

    def test_population_pricing_identical(self):
        """A population priced from an event-compute cache matches the
        dense cache bit for bit (counters are the only cache contents)."""
        from repro.neuromorphic import minimal_partition, strided_mapping
        from repro.neuromorphic.noc import ordered_mapping
        net = fc_network([32, 64, 48], weight_density=0.6, seed=4)
        xs = make_inputs(32, 0.3, 6, seed=5)
        prof = loihi2_like()
        p0 = minimal_partition(net, prof)
        cands = [(p0, ordered_mapping(p0, prof)),
                 (p0, strided_mapping(p0, prof))]
        r_d = simulate_population(net, xs, prof, cands, compute="dense")
        r_e = simulate_population(net, xs, prof, cands, compute="event")
        for a, b in zip(r_d, r_e):
            assert np.array_equal(a.times, b.times)
            assert np.array_equal(a.energies, b.energies)


class TestPallasMode:
    """The real kernel body (interpret mode on CPU) behind the same seam."""

    @quick
    def test_fc_pallas(self):
        net = fc_network([48, 64, 32], weight_density=0.6, seed=0)
        xs = make_inputs(48, 0.3, 12, seed=1)
        assert_backends_match(net, xs, event=EventCompute(mode="pallas"))

    def test_conv_pallas(self):
        net = conv_stack(seed=2)
        xs = make_inputs(net.in_size, 0.3, 4, seed=2)
        assert_backends_match(net, xs, event=EventCompute(mode="pallas"))

    def test_pallas_gather_agree(self):
        """The two kernel modes express one semantic contract."""
        net = fc_network([32, 48, 24], weight_density=0.7, seed=6)
        xs = make_inputs(32, 0.2, 8, seed=7)
        out_g, cnt_g = net.run_batch(xs, compute=EventCompute(mode="gather"))
        out_p, cnt_p = net.run_batch(xs, compute=EventCompute(mode="pallas"))
        np.testing.assert_allclose(out_p, out_g, **FLOAT_TOL)
        for a, b in zip(cnt_g, cnt_p):
            assert np.array_equal(a.macs, b.macs)


class TestSeamPlumbing:
    @quick
    def test_registry_round_trip(self):
        assert isinstance(get_compute("dense"), DenseCompute)
        assert isinstance(get_compute("event"), EventCompute)
        assert get_compute("dense") is get_compute("dense")  # shared instance
        ev = EventCompute(mode="gather")
        assert get_compute(ev) is ev
        with pytest.raises(ValueError):
            get_compute("nope")
        with pytest.raises(ValueError):
            EventCompute(mode="bogus")

    @quick
    def test_register_custom_backend(self):
        class Tagged(DenseCompute):
            name = "tagged"
        register_compute("tagged", Tagged)
        try:
            assert isinstance(get_compute("tagged"), Tagged)
        finally:
            from repro.neuromorphic import compute as C
            C._REGISTRY.pop("tagged", None)
            C._INSTANCES.pop("tagged", None)

    @quick
    def test_default_compute_flip(self):
        """The process-wide default (benchmarks/run.py --compute) reroutes
        calls that omit compute=."""
        from repro.neuromorphic import compute as C
        net = fc_network([24, 32, 16], weight_density=0.6, seed=8)
        xs = make_inputs(24, 0.3, 5, seed=9)
        out_d, _ = net.run_batch(xs)
        old = C.DEFAULT_COMPUTE
        C.DEFAULT_COMPUTE = "event"
        try:
            out_e, _ = net.run_batch(xs)
        finally:
            C.DEFAULT_COMPUTE = old
        np.testing.assert_allclose(out_e, out_d, **FLOAT_TOL)

    @quick
    def test_evaluator_threads_compute(self):
        from repro.core.partitioner import SimEvaluator
        net = fc_network([24, 32, 16], weight_density=0.6, seed=8)
        xs = make_inputs(24, 0.3, 5, seed=9)
        prof = loihi2_like()
        ev_d = SimEvaluator(net, xs, prof)
        ev_e = SimEvaluator(net, xs, prof, compute="event")
        from repro.neuromorphic import minimal_partition
        from repro.neuromorphic.noc import ordered_mapping
        p0 = minimal_partition(net, prof)
        m0 = ordered_mapping(p0, prof)
        assert np.array_equal(ev_d(p0, m0).times, ev_e(p0, m0).times)


class TestIm2col:
    @quick
    @pytest.mark.parametrize("h,w,stride", [(8, 8, 2), (9, 7, 1), (6, 10, 2)])
    def test_matches_dense_conv_counters(self, h, w, stride):
        """The im2col receptive fields must be exactly the dense conv's —
        integer mask counts are the bit-level witness."""
        rng = np.random.default_rng(h * 10 + w + stride)
        cin, cout = 3, 5
        wgt = rng.normal(0, 0.3, (3, 3, cin, cout)).astype(np.float32)
        lay = SimLayer(name="c", kind="conv", weights=wgt, stride=stride,
                       in_hw=(h, w))
        net = SimNetwork(layers=[lay], in_size=h * w * cin)
        xs = make_inputs(net.in_size, 0.4, 3, seed=0)
        assert_backends_match(net, xs)

    @quick
    def test_patch_order_is_cin_kh_kw(self):
        """_im2col feature order must match _patch_weights' flattening."""
        x = np.arange(2 * 4 * 4, dtype=np.float32).reshape(1, 2, 4, 4)
        pat = _im2col(x, 3, 3, 1, 4, 4)
        # center tap of window (1,1): features [c*9 + 4] must be x[:, c, 1, 1]
        row = pat[1 * 4 + 1]
        assert row[0 * 9 + 4] == x[0, 0, 1, 1]
        assert row[1 * 9 + 4] == x[0, 1, 1, 1]


# ------------------------------------------------- the dense conv program

def _eager_conv_layer(layer, x_eff, act_mask):
    """The dense conv as three eager convs over host NHWC copies, each
    fetched before the next: the formula the device program replaces."""
    import jax
    import jax.numpy as jnp
    T = x_eff.shape[0]
    h, w = layer.in_hw
    cin = layer.weights.shape[2]
    to_nhwc = lambda a: np.transpose(a.reshape(T, cin, h, w), (0, 2, 3, 1))
    wj = jnp.asarray(layer.weights)
    conv = lambda lhs, rhs, precision=None: np.asarray(
        jax.lax.conv_general_dilated(
            jnp.asarray(to_nhwc(lhs)), rhs,
            window_strides=(layer.stride, layer.stride), padding="SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision))
    pre = conv(x_eff, wj, jax.lax.Precision.HIGHEST)
    macs = conv(act_mask, (wj != 0).astype(jnp.float32))
    fetches = conv(act_mask, jnp.ones_like(wj))
    to_flat = lambda a: np.transpose(a, (0, 3, 1, 2)).reshape(T, -1)
    return to_flat(pre), to_flat(macs), to_flat(fetches)


def _eager_delta_layer(layer, x_in, in_acc):
    """The host time cumsum in front of :func:`_eager_conv_layer`."""
    x_eff = np.cumsum(x_in, axis=0)
    if np.any(in_acc):
        x_eff = in_acc[None, :] + x_eff
    act_mask = (x_in != 0).astype(np.float32)
    return (*_eager_conv_layer(layer, x_eff, act_mask), x_eff[-1].copy())


@quick
@pytest.mark.parametrize("path", ["plain", "delta", "delta_acc"])
@pytest.mark.parametrize("T", [1, 64])
@pytest.mark.parametrize("cin", [2, 16])
@pytest.mark.parametrize("stride", [1, 2])
def test_dense_conv_program_is_bit_identical(stride, cin, T, path):
    """One device program a conv layer gives the bits of the eager convs
    on host layouts: ``pre``, ``macs``, ``fetches`` and, on a delta
    input, the new accumulator."""
    rng = np.random.default_rng(1000 * stride + 10 * cin + T)
    h, w, cout = 10, 12, 6
    wgt = rng.normal(0, 0.3, (3, 3, cin, cout)).astype(np.float32)
    wgt *= _exact_density_mask(wgt.shape, 0.6, rng)
    layer = SimLayer(name="c", kind="conv", weights=wgt, stride=stride,
                     in_hw=(h, w))
    n = cin * h * w
    # a sparse wire stream of quantized deltas, as sigma-delta layers send
    wire = (np.round(rng.normal(0, 4, (T, n))) * 0.05
            * (rng.random((T, n)) < 0.2)).astype(np.float32)
    dense = DenseCompute()
    if path == "plain":
        # the step-major engine's input to a delta layer: the rebuilt
        # values beside the wire's own event mask
        x_eff = np.cumsum(wire, axis=0)
        act_mask = (wire != 0).astype(np.float32)
        got = dense.conv_forward(layer, x_eff, act_mask, act_mask.sum(1))
        want = _eager_conv_layer(layer, x_eff, act_mask)
    else:
        in_acc = (rng.normal(0, 1, n).astype(np.float32)
                  if path == "delta_acc" else np.zeros(n, np.float32))
        act_mask = (wire != 0).astype(np.float32)
        got = dense.delta_forward(layer, wire, in_acc, act_mask,
                                  act_mask.sum(1))
        want = _eager_delta_layer(layer, wire, in_acc)
    assert len(got) == len(want)
    for name, a, b in zip(("pre", "macs", "fetches", "new_acc"), got, want):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        assert np.array_equal(a, b), name


@quick
def test_dense_conv_fetch_map_is_the_window_sum_of_the_mask():
    """An sdconv64-shaped layer (32x32x16 -> 16x16x32, 3x3 stride 2): the
    fetch map is one map repeated in every output channel, the SAME-padded
    3x3 window sum over all input channels of the event mask."""
    rng = np.random.default_rng(7)
    T, cin, cout, h, stride = 4, 16, 32, 32, 2
    wgt = rng.normal(0, 0.3, (3, 3, cin, cout)).astype(np.float32)
    layer = SimLayer(name="c", kind="conv", weights=wgt, stride=stride,
                     in_hw=(h, h))
    mask = (rng.random((T, cin * h * h)) < 0.1).astype(np.float32)
    x_eff = mask * rng.normal(0, 1, mask.shape).astype(np.float32)
    _, _, fetches = DenseCompute().conv_forward(layer, x_eff, mask,
                                                mask.sum(1))
    oh = h // stride
    per_channel = fetches.reshape(T, cout, oh, oh)
    assert (per_channel == per_channel[:, :1]).all()
    # XLA's SAME split for a 3x3 window at stride 2 over 32: one row of
    # padding at the end, none at the start
    summed = np.pad(mask.reshape(T, cin, h, h).sum(axis=1),
                    ((0, 0), (0, 1), (0, 1)))
    win = np.lib.stride_tricks.sliding_window_view(summed, (3, 3),
                                                   axis=(1, 2))
    want = win[:, ::stride, ::stride].sum(axis=(3, 4))
    assert np.array_equal(per_channel[:, 0], want)
