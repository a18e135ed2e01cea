"""The in-program tracer (``repro.tracing``): off it records nothing; on,
under ``enable()`` or a profiler trace, its spans nest, share request ids,
carry counters and compile counts, and leave every simulated statistic
bit-identical."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.core.partitioner import SimEvaluator
from repro.core.search import evolutionary_search
from repro.neuromorphic import (SimLayer, SimNetwork, fc_network,
                                loihi2_like, make_inputs, simulate)
from repro.neuromorphic.compute import EventCompute

SIM_SPANS = {"sim.simulate", "sim.run_batch", "sim.synaptic", "sim.neuron",
             "kernel.put", "kernel.fetch", "price.cumsum", "price.candidate",
             "price.segments", "price.cores", "price.route"}
SEARCH_SPANS = {"search", "search.seed", "search.step", "search.sync",
                "search.archive", "search.finish"}


@pytest.fixture(autouse=True)
def empty_buffer():
    tracing.drain()
    yield
    tracing.drain()


def _by_index(spans):
    return {s.index: s for s in spans}


def test_off_records_nothing_and_returns_the_shared_no_op():
    assert tracing.span("a") is tracing.span("b")
    with tracing.span("a"):
        tracing.count("n", 1)
        with tracing.span("b"):
            pass
    assert tracing.drain() == ([], 0)


def test_enable_nests_spans_under_one_request_and_counts_innermost():
    with tracing.enable():
        with tracing.span("root"):
            tracing.count("n", 2)
            with tracing.span("child"):
                tracing.count("n", 3)
                tracing.count("n", 4)
                with tracing.span("grandchild"):
                    pass
            tracing.count("m", 1.5)
        with tracing.span("second"):
            pass
    tracing.count("n", 1)                 # no span open: nothing to add to
    spans, dropped = tracing.drain()
    assert dropped == 0
    assert [s.name for s in spans] == ["grandchild", "child", "root",
                                       "second"]
    grand, child, root, second = spans
    assert root.parent == -1 and second.parent == -1
    assert child.parent == root.index and grand.parent == child.index
    assert root.request == child.request == grand.request
    assert second.request != root.request
    assert root.counts == {"n": 2, "m": 1.5}
    assert child.counts == {"n": 7} and grand.counts == {}
    assert root.start <= child.start <= grand.start <= grand.end \
        <= child.end <= root.end <= second.start
    assert tracing.span("off again") is tracing.span("x")


def test_a_full_buffer_counts_the_spans_it_drops(monkeypatch):
    monkeypatch.setattr(tracing, "MAX_SPANS", 3)
    with tracing.enable():
        for _ in range(5):
            with tracing.span("s"):
                pass
    spans, dropped = tracing.drain()
    assert len(spans) == 3 and dropped == 2
    assert tracing.drain() == ([], 0)


def test_a_fresh_compile_lands_on_its_span():
    f = jax.jit(lambda x: x * 3.0 + 1.0)
    with tracing.enable():
        with tracing.span("outer"):
            with tracing.span("compiles"):
                f(jnp.arange(7.0)).block_until_ready()
            with tracing.span("warm"):
                f(jnp.arange(7.0)).block_until_ready()
    spans = {s.name: s for s in tracing.drain().spans}
    assert spans["compiles"].counts["compiles"] >= 1
    assert spans["compiles"].counts["compile_s"] > 0
    assert "compiles" not in spans["warm"].counts
    assert "compiles" not in spans["outer"].counts


def test_a_profiler_trace_turns_recording_on(tmp_path):
    from bench import trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracing.span("traced.outer"):
            with tracing.span("traced.inner"):
                jnp.ones(3).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    with tracing.span("after"):
        pass
    assert [s.name for s in tracing.drain().spans] == ["traced.inner",
                                                       "traced.outer"]
    host = trace.load(trace.find_xplane(str(tmp_path)))["host"]
    on_one_thread = [{n for n, _, _ in line} for line in host]
    assert any({"traced.outer", "traced.inner"} <= names
               for names in on_one_thread)


# ------------------------------------------------------ the program's spans

def _conv_net(neuron_model="relu", seed=0):
    """conv -> conv -> fc, 8x8x2 input."""
    rng = np.random.default_rng(seed)
    layers, h, c_prev = [], 8, 2
    for i, c in enumerate((4, 8)):
        w = rng.normal(0, 1 / 3.0, (3, 3, c_prev, c)).astype(np.float32)
        layers.append(SimLayer(name=f"conv{i}", kind="conv", weights=w,
                               stride=2, in_hw=(h, h),
                               neuron_model=neuron_model, threshold=0.05))
        h, c_prev = h // 2, c
    w = rng.normal(0, 0.3, (h * h * c_prev, 3)).astype(np.float32)
    layers.append(SimLayer(name="fc", kind="fc", weights=w))
    return SimNetwork(layers=layers, in_size=8 * 8 * 2)


def _report_fields(rep) -> dict:
    out = dataclasses.asdict(rep)
    out["metrics"] = dataclasses.asdict(rep.metrics)
    return out


def _assert_identical(a, b):
    fa, fb = _report_fields(a), _report_fields(b)
    assert fa.keys() == fb.keys()
    for k in fa:
        np.testing.assert_equal(fa[k], fb[k], err_msg=k)


def _traced(fn):
    tracing.drain()
    with tracing.enable():
        out = fn()
    spans, dropped = tracing.drain()
    assert dropped == 0
    return out, spans


CASES = {
    # fc through the event backend's Pallas kernel (interpret mode here)
    "fc_event": (lambda: fc_network([24, 40, 16], seed=0,
                                    neuron_model="ssm"),
                 24, lambda: EventCompute(mode="pallas")),
    # sigma-delta convs through the default backend
    "conv_default": (lambda: _conv_net("sd_relu"), 128, lambda: None),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_simulate_is_bit_identical_with_tracing_and_names_every_span(case):
    build, n_in, compute = CASES[case]
    net, prof = build(), loihi2_like()
    xs = make_inputs(n_in, 0.3, 6, seed=3)
    off = simulate(net, xs, prof, compute=compute())
    assert tracing.drain() == ([], 0)
    on, spans = _traced(lambda: simulate(net, xs, prof, compute=compute()))
    _assert_identical(off, on)
    assert {s.name for s in spans} == SIM_SPANS
    roots = [s for s in spans if s.parent == -1]
    assert [s.name for s in roots] == ["sim.simulate"]
    assert {s.request for s in spans} == {roots[0].request}
    idx = _by_index(spans)
    for s in spans:
        if s.name in ("sim.synaptic", "sim.neuron"):
            assert idx[s.parent].name == "sim.run_batch"
        if s.name in ("price.segments", "price.cores", "price.route"):
            assert idx[s.parent].name == "price.candidate"
    per_layer = sum(s.name == "sim.synaptic" for s in spans)
    assert per_layer == sum(s.name == "sim.neuron" for s in spans) \
        == len(net.layers)


def test_price_cumsum_counts_the_maps_in_closed_form_and_summed():
    # an s5.sim-style request: an fc SSM stack on the event backend, on a
    # synchronous chip, so each layer's fetch and activity maps are closed
    sizes = [32, 48, 48, 16]
    net = fc_network(sizes, seed=0, neuron_model="ssm")
    xs = make_inputs(sizes[0], 0.1, 8, seed=3)
    _, spans = _traced(lambda: simulate(net, xs, loihi2_like(),
                                        compute="event"))
    (cumsum,) = [s for s in spans if s.name == "price.cumsum"]
    closed, summed = (cumsum.counts["csum_closed"],
                      cumsum.counts["csum_summed"])
    assert closed + summed == 4 * len(net.layers)
    assert closed == summed == 2 * len(net.layers)


def test_device_search_is_identical_with_tracing_and_names_every_span():
    net = fc_network([24, 40, 16], seed=0, neuron_model="relu")
    prof = loihi2_like()
    xs = make_inputs(24, 0.3, 4, seed=1)
    ev = SimEvaluator(net, xs, prof)
    kw = dict(population_size=8, generations=2, seed=5, engine="device")
    off = evolutionary_search(net, prof, ev, **kw)
    on, spans = _traced(lambda: evolutionary_search(net, prof, ev, **kw))
    assert on.candidate == off.candidate and on.front == off.front
    assert on.history == off.history and on.n_evals == off.n_evals
    _assert_identical(on.report, off.report)
    names = [s.name for s in spans]
    assert SEARCH_SPANS <= set(names)
    for per_gen in ("search.step", "search.sync", "search.archive"):
        assert names.count(per_gen) == kw["generations"]
    idx = _by_index(spans)
    root = next(s for s in spans if s.name == "search")
    assert root.parent == -1
    assert all(s.request == root.request for s in spans)
    for s in spans:
        if s.name.startswith("search."):
            assert s.parent == root.index
        if s.name == "price.candidate":
            assert idx[s.parent].name == "search.finish"


# --------------------------------------------------------------- h2d_bytes

def _h2d(spans) -> int:
    return sum(s.counts.get("h2d_bytes", 0) for s in spans
               if s.name == "kernel.put")


def test_h2d_bytes_of_the_event_kernel_are_its_operands():
    sizes, T = [24, 40, 16], 6
    net = fc_network(sizes, seed=0, neuron_model="ssm")
    xs = make_inputs(sizes[0], 0.3, T, seed=3)
    _, spans = _traced(lambda: net.run_batch(
        xs, compute=EventCompute(mode="pallas")))
    # per layer: activations and their mask, weights and their mask
    want = sum(4 * (2 * T * k + 2 * k * n)
               for k, n in zip(sizes[:-1], sizes[1:]))
    assert _h2d(spans) == want


def _conv_inputs(layer) -> int:
    return layer.weights.shape[2] * layer.in_hw[0] * layer.in_hw[1]


def test_h2d_bytes_of_the_dense_conv_are_its_inputs_and_masks():
    net, T = _conv_net("sd_relu"), 6
    xs = make_inputs(net.in_size, 0.3, T, seed=3)
    net.run_batch(xs)                         # kernels cached on the device
    _, spans = _traced(lambda: net.run_batch(xs))
    conv0, conv1 = [l for l in net.layers if l.kind == "conv"]
    # layer 0 takes the raw stream: its values as float32 and its wire mask
    # at one byte an element; layer 1 takes layer 0's deltas alone and
    # derives the mask on the device
    want = (4 + 1) * T * _conv_inputs(conv0) + 4 * T * _conv_inputs(conv1)
    assert _h2d(spans) == want


def test_a_dense_conv_layer_fetches_once_and_counts_its_bytes():
    net, T = _conv_net("sd_relu"), 6
    xs = make_inputs(net.in_size, 0.3, T, seed=3)
    net.run_batch(xs)
    _, spans = _traced(lambda: net.run_batch(xs))
    idx = _by_index(spans)
    fetches = [s for s in spans if s.name == "kernel.fetch"]
    synaptic = [s for s in spans if s.name == "sim.synaptic"]
    convs = [l for l in net.layers if l.kind == "conv"]
    # one fetch inside each conv layer's synaptic span; the fc layer runs
    # on the host
    assert sorted(idx[s.parent].index for s in fetches) == \
        [s.index for s in synaptic[:len(convs)]]
    for span, layer, delta in zip(fetches, convs, (False, True)):
        oh, ow = layer.out_hw
        # pre and macs of every output channel, the fetch map of one, and
        # the new accumulator of a delta input
        want = 4 * (T * (2 * layer.weights.shape[3] + 1) * oh * ow
                    + delta * _conv_inputs(layer))
        assert span.counts == {"d2h_bytes": want}


def test_h2d_bytes_of_the_windowed_delta_path():
    sizes, T, window = [16, 24, 8], 24, 8
    net = fc_network(sizes, seed=0, neuron_model="sd_relu")
    for layer in net.layers:
        layer.threshold = 0.05
    xs = make_inputs(sizes[0], 0.3, T, seed=3)
    cc = EventCompute(mode="pallas", delta_window=window)
    _, spans = _traced(lambda: net.run_batch(xs, compute=cc))
    k0, k1, n1 = sizes
    layer0 = 4 * (2 * T * k0 + 2 * k0 * k1)
    # the delta stream and its accumulator, then one kernel pass over the
    # within-window sums and one over the window bases; the second pass
    # reuses the weights the first one left on the device
    nwin = T // window
    layer1 = 4 * ((T * k1 + k1) + (2 * T * k1 + 2 * k1 * n1)
                  + 2 * nwin * k1)
    assert _h2d(spans) == layer0 + layer1


def _reused(spans) -> int:
    return sum(s.counts.get("h2d_reused_bytes", 0) for s in spans
               if s.name == "kernel.put")


def _same_counters(a, b):
    for ca, cb in zip(a[1], b[1], strict=True):
        for f in dataclasses.fields(ca):
            np.testing.assert_array_equal(getattr(ca, f.name),
                                          getattr(cb, f.name), f.name)


def _same_run(a, b):
    np.testing.assert_array_equal(a[0], b[0])
    _same_counters(a, b)


def test_a_second_event_run_copies_only_activations():
    sizes, T = [24, 40, 16], 6
    net = fc_network(sizes, seed=0, neuron_model="ssm")
    xs = make_inputs(sizes[0], 0.3, T, seed=3)
    cc = EventCompute(mode="pallas")
    first, _ = _traced(lambda: net.run_batch(xs, compute=cc))
    second, spans = _traced(lambda: net.run_batch(xs, compute=cc))
    pairs = list(zip(sizes[:-1], sizes[1:]))
    assert _h2d(spans) == sum(4 * 2 * T * k for k, _ in pairs)
    assert _reused(spans) == sum(4 * 2 * k * n for k, n in pairs)
    _same_run(first, second)
    dense = net.run_batch(xs, compute="dense")
    _same_counters(second, dense)
    np.testing.assert_array_equal(second[0], dense[0])


def test_rebound_weights_are_copied_again_and_used():
    sizes, T = [24, 40, 16], 6
    net = fc_network(sizes, seed=0, neuron_model="ssm")
    xs = make_inputs(sizes[0], 0.3, T, seed=3)
    cc = EventCompute(mode="pallas")
    net.run_batch(xs, compute=cc)             # weights resident
    net.layers[0].weights = -net.layers[0].weights
    out, spans = _traced(lambda: net.run_batch(xs, compute=cc))
    k0, k1, n1 = sizes
    assert _h2d(spans) == 4 * (2 * T * k0 + 2 * k0 * k1 + 2 * T * k1)
    fresh = fc_network(sizes, seed=0, neuron_model="ssm")
    fresh.layers[0].weights = -fresh.layers[0].weights
    _same_run(out, fresh.run_batch(xs, compute=cc))
