"""Cell ``nemotron3nano.sim`` cut to a size a test run can hold: its
configuration at tiny widths with the same structure (Mamba heads half a
group, two query heads over one KV head, top-6 of 16 experts with 4 held
and 2 shared) on a (3, 4) router grid."""

import copy
import io
import json

import numpy as np

from bench import harness, reference, reference_lm

SEED = 2**31 + 17
CELL = "nemotron3nano.sim"


def tiny() -> harness.Cell:
    cell = copy.copy(harness.resolve(CELL))
    c = copy.deepcopy(cell.config)
    c.update(hidden_size=48, mamba_head_dim=8, ssm_state_size=8,
             head_dim=8, moe_intermediate_size=8,
             moe_shared_expert_intermediate_size=16, n_groups=2,
             mamba_num_heads=2, num_attention_heads=2,
             num_key_value_heads=1, n_routed_experts=4)
    c["published"].update(mamba_num_heads=8, n_routed_experts=16)
    c["network"].update(context=16)
    c["chip"].update(n_cores=48, grid=[3, 4])
    cell.config = c
    cell.traffic = dict(cell.traffic, steps=8, check_sample=2)
    return cell


def _run(cell, traced=False, seconds=0.3) -> dict:
    out, err = io.StringIO(), io.StringIO()
    assert harness.run_cell(cell, SEED, seconds, traced, require_chip=False,
                            out=out, err=err) == 0, err.getvalue()
    return json.loads(out.getvalue().splitlines()[-1])


def test_a_tiny_cut_runs_a_window_and_is_correct():
    cell = tiny()
    res = _run(cell)
    assert res["correct"], res
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert set(res["checks"]) == set(cell.limits)


def test_a_traced_run_reads_the_gate_and_the_routing(monkeypatch):
    from bench import flops
    monkeypatch.setattr(flops, "peak", lambda kind: 1e12)   # no CPU peak
    res = _run(tiny(), traced=True)
    assert res["correct"], res
    assert {"gate_ms.sim", "noc_ms.sim", "functional_ms.sim",
            "pricing_ms.sim", "cumsum_ms.sim", "mfu.sim"} <= \
        set(res["metrics"])
    assert res["metrics"]["gate_ms.sim"]["value"] > 0


def test_control_fails_a_limit():
    cell = tiny()
    state = cell.kind.setup(cell, SEED)
    spans = harness.Spans(False)
    answers = [cell.kind.request(state, cell.kind.payload(state, i), spans,
                                 False)[0] for i in range(2)]
    program = cell.kind.check(state, answers, SEED)
    control = cell.kind.control(state, answers, SEED)
    assert all(program[k] <= v for k, v in cell.limits.items()), program
    assert any(control[k] > v for k, v in cell.limits.items()), control


def _patch_router(monkeypatch, target, step, swap):
    """The program routes ``step`` of the layer whose router is ``target``
    with experts ``swap = (chosen, unchosen)`` exchanged."""
    from repro.neuromorphic.network import Router
    orig = Router.expert_weights

    def routed(self, pre):
        w = orig(self, pre)
        if self is target and step < w.shape[0]:
            a, b = swap
            top = [e for e in np.flatnonzero(w[step]) if e != a] + [b]
            r = pre[step, -self.n_experts:].astype(np.float64)
            s = (1.0 / (1.0 + np.exp(-r))).astype(np.float32)[top]
            w = w.copy()
            w[step] = 0.0
            w[step, top] = s / s.sum() * np.float32(self.scale)
        return w
    monkeypatch.setattr(Router, "expert_weights", routed)


def test_a_misrouted_step_is_not_correct_unless_the_reference_ties_it(
        monkeypatch):
    cell = tiny()
    state = cell.kind.setup(cell, SEED)
    xs = cell.kind.payload(state, 0)
    # the first routed layer's step 3: its lowest chosen expert, held
    # here, against its highest unchosen one
    up = next(i for i, s in enumerate(state["layers"]) if s["router"])
    r = state["layers"][up]["router"]
    x_up = reference_lm.forward(state["layers"][:up], xs)[0]
    pre = x_up @ state["layers"][up]["weights"]
    logits = pre[3, -r["n_experts"]:]
    order = np.argsort(-logits, kind="stable")
    chosen = [e for e in order[:r["top_k"]] if e in r["held"]]
    assert chosen, "no held expert routed at step 3"
    a, b = int(chosen[-1]), int(order[r["top_k"]])
    _patch_router(monkeypatch, state["net"].layers[up].router, 3, (a, b))
    got = cell.kind.sim.program_answer(cell.kind.request(
        state, xs, harness.Spans(False), False)[0])
    assert cell.kind.closest_gaps(state, xs, got)["count_gap"] > 0

    orig = reference_lm._route_ties
    monkeypatch.setattr(
        reference_lm, "_route_ties",
        lambda router, pre, pre64, top: (
            [(3, a, b)] if router is r else orig(router, pre, pre64, top)))
    g = cell.kind.closest_gaps(state, xs, got)
    assert g["count_gap"] == 0.0 and g["price_gap"] < 1e-12, g
    assert g["out_gap"] < cell.limits["out_gap"], g


def test_a_dropped_message_is_not_correct(monkeypatch):
    from repro.neuromorphic.network import SimLayer
    orig = SimLayer._neuron_batch

    def dropping(self, pre, state):
        y, state = orig(self, pre, state)
        y = np.array(y, copy=True)
        t = y.shape[0] // 2
        y[t, np.argmax(np.abs(y[t]))] = 0.0
        return y, state
    monkeypatch.setattr(SimLayer, "_neuron_batch", dropping)
    assert _run(tiny())["correct"] is False


def test_the_reference_walks_the_x_then_y_path():
    chip = dict(grid=[4, 5], n_cores=80)
    cores = (3, 5, 2)
    phys = list(np.random.default_rng(1).permutation(80)[:10])
    router = np.asarray(phys) // 4
    for l, (gather, touch, hops, n_dst) in enumerate(
            reference_lm.routes(cores, phys, chip)):
        start = sum(cores[:l])
        dst = (router[start + cores[l]:start + cores[l] + cores[l + 1]]
               if l + 1 < len(cores) else [0])
        assert n_dst == len(dst)
        for g in range(cores[l]):
            s = router[start + g]
            want = np.zeros(20)
            for d in dst:
                want[reference._route_nodes(int(s), int(d), 5)] += 1
            i = int(np.argmax(gather[g]))
            assert np.array_equal(touch[i], want)
            assert hops[i] == sum(abs(s // 5 - d // 5) + abs(s % 5 - d % 5)
                                  for d in dst)


def test_the_configuration_states_its_cut():
    cell = harness.resolve(CELL)
    c = cell.config
    for k in c["reduced"]:
        assert c[k] == c["network"][k] != c["published"][k]
    assert (c["chip"]["n_cores"], c["chip"]["grid"]) == (5760, [30, 48])
