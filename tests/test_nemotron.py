"""NVIDIA Nemotron 3 Nano 30B-A3B: the configuration's arithmetic, its
routed-expert lowering and one partition's share of a period.

The router of an MoE up-projection (``repro.neuromorphic.network.Router``)
picks the top 6 experts from its own neurons' pre-activations at every
step; these tests hold it to a plain NumPy top-6, across both engines and
every backend.  A share (``compile_network(..., share=(i, n))``) holds
partition i's Mamba heads, query heads with their KV head and routed
experts; the shares of each layer, with what every partition holds alike
counted once, add up to the uncut layer.
"""

import os
import sys

import numpy as np
import pytest

from repro.configs import registry
from repro.neuromorphic import (EventCompute, compile_network,
                                excluded_params, loihi2_like, lowering_spec,
                                simulate)
from repro.neuromorphic.compute import get_compute
from test_compute_backends import assert_backends_match

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ARCH = "nemotron-3-nano-30b-a3b"

quick = pytest.mark.quick


def _cfg(smoke=False):
    entry = registry.get(ARCH)
    return entry.smoke() if smoke else entry.config


# ------------------------------------------------------------- arithmetic

@quick
def test_published_parameter_counts():
    """31.6B parameters, 3.2B active with the input embedding left out."""
    cfg = _cfg()
    assert abs(cfg.param_count() - 31.6e9) <= 0.005 * 31.6e9
    active = cfg.active_param_count() - cfg.vocab_size * cfg.d_model
    assert round(active / 1e8) == 32, active


@quick
def test_the_layer_string_maps_onto_blocks():
    from repro.configs import nemotron3_nano_30b_a3b as nem
    blocks = _cfg().all_blocks()
    kinds = [(b.kind, b.moe is not None) for b in blocks]
    assert kinds.count(("ssd", True)) + kinds.count(("ssd", False)) == 23
    assert sum(b.moe is not None for b in blocks) == 23
    assert kinds.count(("attn", True)) == 6
    layers = "".join(("M" if b.kind == "ssd" else "*")
                     + ("E" if b.moe else "") for b in blocks)
    assert layers == nem.LAYERS and len(layers) == 52


@quick
def test_the_frontend_identity_at_published_size():
    """Arithmetic only: no weights are built."""
    cfg = _cfg()
    specs, _ = lowering_spec(cfg, seq_len=8192)
    assert (sum(s.param_nnz for s in specs) + excluded_params(cfg)
            == cfg.param_count())


@quick
def test_a_share_matches_the_benchmark_builder_at_published_widths():
    """``lowering_spec(share=(0, 16))`` and the benchmark's own generator
    give the same layer shapes and synapse counts."""
    import json
    sys.path.insert(0, ROOT)
    from bench.builders import nemotron_h_share
    with open(os.path.join(
            ROOT, "bench/configs/nemotron3nano_p16_loihi2x48.json")) as f:
        config = json.load(f)
    specs, _ = lowering_spec(_cfg(), share=(0, 16), seq_len=8192)
    plan = nemotron_h_share.plan(config)
    assert ([(s.fanin, s.width, s.nnz, s.neuron_model) for s in specs]
            == [(p["fanin"], p["width"], p["nnz"], p["neuron_model"])
                for p in plan])
    routers = [(s.router.n_experts, s.router.top_k, s.router.width,
                s.router.held, s.router.n_shared, s.router.scale)
               for s in specs if s.router]
    assert routers == [tuple(p["router"].values()) for p in plan
                       if p["router"]]
    assert sum(s.fanin * s.width for s in specs) == 324_930_048


# ---------------------------------------------------------------- routing

def _layer_inputs(net, xs, compute="dense"):
    """Each layer's (input, output) over the stream, batched engine."""
    cc = get_compute(compute)
    cur, out = np.asarray(xs, np.float32), []
    for layer in net.layers:
        y, _, _, _ = layer.step_batch(cur, layer.init_state(), None,
                                      compute=cc)
        out.append((cur, y))
        cur = y
    return out


def _plain_top6(pre_router, scale):
    """The published router, written out: sigmoid scores, the 6 largest
    renormalised and scaled."""
    s = 1.0 / (1.0 + np.exp(-pre_router.astype(np.float64)))
    w = np.zeros_like(s)
    for t in range(s.shape[0]):
        top = np.argsort(-s[t], kind="stable")[:6]
        w[t, top] = s[t, top] / s[t, top].sum() * scale
    return w


@quick
@pytest.mark.parametrize("share", [None, (1, 4)])
def test_routed_experts_are_the_plain_top6(share):
    cn = compile_network(ARCH, share=share, seed=3)
    xs = cn.inputs(6, seed=4)
    for layer, (x, y) in zip(cn.net.layers, _layer_inputs(cn.net, xs)):
        r = layer.router
        if r is None:
            continue
        pre = x @ layer.weights
        want = _plain_top6(pre[:, -r.n_experts:], r.scale)
        live = list(r.held)
        got_live = np.stack([np.abs(y[:, i * r.width:(i + 1) * r.width])
                             .max(axis=1) > 0 for i in range(len(live))], 1)
        assert np.array_equal(got_live, want[:, live] > 0)
        assert (want > 0).sum(axis=1).tolist() == [6] * xs.shape[0]
        # forced-active neurons message |pre| + 1, scaled by the weight
        n = len(live) * r.width
        gate = np.repeat(want[:, live], r.width, axis=1)
        np.testing.assert_allclose(y[:, :n], (np.abs(pre[:, :n]) + 1) * gate,
                                   rtol=2e-6, atol=1e-6)
        assert np.all(y[:, -r.n_experts:] == 0)      # the router is silent


@quick
def test_routed_counters_are_bit_identical_across_engines_and_backends():
    cn = compile_network(ARCH, seed=5)
    xs = cn.inputs(5, seed=6)
    assert_backends_match(cn.net, xs)
    assert_backends_match(cn.net, xs, event=EventCompute(mode="pallas"))
    prof = loihi2_like()
    runs = [simulate(cn.net, xs, prof, engine=e, compute=c)
            for e in ("batched", "reference") for c in ("dense", "event")]
    for r in runs[1:]:
        for k in ("times", "energies", "per_core_synops",
                  "per_core_msgs_out"):
            assert np.array_equal(getattr(r, k), getattr(runs[0], k)), k
        np.testing.assert_allclose(r.outputs, runs[0].outputs, rtol=1e-5)


@quick
def test_the_gate_counts_the_expert_messages_it_lets_through():
    from repro import tracing
    cn = compile_network(ARCH, seed=7)
    xs = cn.inputs(4, seed=8)
    with tracing.enable():
        _, counters = cn.net.run_batch(xs)
    spans, dropped = tracing.drain()
    gates = [s for s in spans if s.name == "sim.moe.gate"]
    routed = [(l, c) for l, c in zip(cn.net.layers, counters) if l.router]
    assert not dropped and len(gates) == len(routed) == 3
    for g, (layer, c) in zip(gates, routed):
        n = len(layer.router.held) * layer.router.width
        assert g.counts["expert_msgs"] == int(c.msgs_out[:, :n].sum())


# ------------------------------------------------------------------ shares

def _step_major(layer, x):
    """(messages, pre-activations) of one layer over a stream, one step at
    a time (the reference engine)."""
    cc = get_compute("dense")
    st, ys, pres = layer.init_state(), [], []
    for t in range(x.shape[0]):
        m = (x[t] != 0).astype(np.float32)
        pre = cc.forward(layer, x[t][None], m[None],
                         np.asarray([m.sum()], np.float32))[0][0]
        y, st, _, _ = layer.step(x[t], st, None, compute=cc)
        ys.append(y)
        pres.append(pre)
    return np.stack(ys), np.stack(pres)


def _ranges(ranges):
    return np.concatenate([np.arange(a, b) for a, b in ranges])


@quick
def test_four_shares_of_a_period_add_up_to_the_uncut_layers():
    """Layers that hold some neurons (in-projections, states, ups, scores)
    give the uncut layer's messages there, and together all of them;
    layers that hold some fan-in (out- and down-projections) give partial
    sums whose total, with the rows several partitions hold (the shared
    experts, the router) counted once, is the uncut pre-activation."""
    cfg = _cfg(smoke=True)
    uncut = compile_network(cfg, share=(0, 1), seed=9)
    shares = [compile_network(cfg, share=(i, 4), seed=9) for i in range(4)]
    assert len({len(s.net.layers) for s in shares}) == 1
    x = uncut.inputs(4, seed=10)
    for l, layer in enumerate(uncut.net.layers):
        want_y, want_pre = _step_major(layer, x)
        partial = all(len(_ranges(s.specs[l].cols)) == layer.n_neurons
                      for s in shares)
        held_rows = np.zeros(layer.fanin, bool)
        held_cols = np.zeros(layer.n_neurons, bool)
        total = np.zeros_like(want_pre)
        for s in shares:
            spec, part = s.specs[l], s.net.layers[l]
            rows, cols = _ranges(spec.rows), _ranges(spec.cols)
            assert np.array_equal(part.weights,
                                  layer.weights[np.ix_(rows, cols)])
            xin = x[:, rows]
            if partial:
                xin = np.where(held_rows[rows], 0.0, xin).astype(np.float32)
                total += _step_major(part, xin)[1]
            else:
                y, _ = _step_major(part, xin)
                np.testing.assert_allclose(y, want_y[:, cols], rtol=1e-5,
                                           atol=1e-6, err_msg=spec.name)
            held_rows[rows] = True
            held_cols[cols] = True
        assert held_cols.all() and held_rows.all(), layer.name
        if partial:
            np.testing.assert_allclose(total, want_pre, rtol=1e-5,
                                       atol=1e-5, err_msg=layer.name)
        x = want_y


@quick
def test_the_published_share_holds_its_heads_and_experts():
    """Arithmetic of the 16-way share of the published period: 4 Mamba
    heads (half a group), 2 query heads over 1 KV head, experts 0-7."""
    specs, attn = lowering_spec(_cfg(), share=(0, 16), seq_len=8192)
    assert [a.heads for a in attn] == [2] and attn[0].kv_heads == 1
    state = [s for s in specs if s.name.endswith(".state")]
    assert all(s.fanin == 772 and s.width == 256 for s in state)
    ups = [s for s in specs if s.router]
    assert all(s.router.held == tuple(range(8)) for s in ups)
    assert [s.macs_per_token for s in specs
            if s.name.endswith("experts_down")] == [None] * 3
