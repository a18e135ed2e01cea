"""``correct`` comes out false for the control and for a broken timed
path, at a size a test run can hold.

The control is the plain reference put in the program's place at the
next lower precision (bfloat16 contractions, float32 pricing); each fault
breaks the program underneath a whole run of the harness, whose look for a
chip is skipped.
"""

import dataclasses
import io
import json

import numpy as np
import pytest

from bench import harness
from bench.conftest import tiny

SEED = 2**31 + 5


def _run(cell) -> dict:
    out, err = io.StringIO(), io.StringIO()
    assert harness.run_cell(cell, SEED, 0.3, False, require_chip=False,
                            out=out, err=err) == 0, err.getvalue()
    return json.loads(out.getvalue().splitlines()[-1])


@pytest.mark.parametrize("name", ["s5.sim", "sdconv64.sim", "s5.search"])
def test_control_fails_a_limit(name):
    cell = tiny(name)
    state = cell.kind.setup(cell, SEED)
    spans = harness.Spans(False)
    answers = [cell.kind.request(state, cell.kind.payload(state, i), spans,
                                 False)[0] for i in range(2)]
    program = cell.kind.check(state, answers, SEED)
    control = cell.kind.control(state, answers, SEED)
    assert all(program[k] <= v for k, v in cell.limits.items()), program
    assert any(control[k] > v for k, v in cell.limits.items()), control


# ---------------------------------------------------------------- faults

def _state_unchanged(monkeypatch, kind):
    """A step that returns its state unchanged: neurons forget their state
    between timesteps; the search's generation step keeps its survivors."""
    if kind == "simulate":
        from repro.neuromorphic.network import SimLayer
        orig = SimLayer._neuron_batch

        def forgetful(self, pre, state):
            ys = [orig(self, pre[t:t + 1], state)[0]
                  for t in range(pre.shape[0])]
            return np.concatenate(ys), state
        monkeypatch.setattr(SimLayer, "_neuron_batch", forgetful)
    else:
        from repro.core.device_search import DeviceSearchEngine
        orig = DeviceSearchEngine.step

        def stuck(self, state, key, n_off):
            _, off, stats = orig(self, state, key, n_off)
            return state, off, stats
        monkeypatch.setattr(DeviceSearchEngine, "step", stuck)


def _half_batch(monkeypatch, kind):
    """Half of the batch left out: the second half of each stream's
    timesteps never reaches the network."""
    from repro.neuromorphic import timestep
    from repro.neuromorphic.network import SimNetwork

    def halve(xs):
        xs = np.array(xs, copy=True)
        xs[xs.shape[0] // 2:] = 0.0
        return xs
    if kind == "simulate":
        orig = SimNetwork.run_batch
        monkeypatch.setattr(SimNetwork, "run_batch",
                            lambda self, xs, **kw: orig(self, halve(xs),
                                                        **kw))
    else:
        orig = timestep.precompute_pricing
        monkeypatch.setattr(timestep, "precompute_pricing",
                            lambda net, xs, prof, **kw: orig(
                                net, halve(xs), prof, **kw))
        import repro.neuromorphic as nm
        monkeypatch.setattr(nm, "precompute_pricing",
                            timestep.precompute_pricing)


def _answer_altered(monkeypatch, kind):
    """An answer altered where it is produced: the last step's output and
    its time in a priced report."""
    from repro.neuromorphic import timestep
    orig = timestep._finish_report

    def altered(*args, **kw):
        rep = orig(*args, **kw)
        times, outputs = rep.times.copy(), rep.outputs.copy()
        times[-1] *= 1.5
        outputs[-1] = 1.5 * outputs[-1] + 1.0
        return dataclasses.replace(rep, times=times, outputs=outputs,
                                   time_per_step=float(times.mean()))
    monkeypatch.setattr(timestep, "_finish_report", altered)


def _message_dropped(monkeypatch, kind):
    """A counter altered where it is produced: in every layer, the largest
    message of the middle step is never sent."""
    from repro.neuromorphic.network import SimLayer
    orig = SimLayer._neuron_batch

    def dropping(self, pre, state):
        y, state = orig(self, pre, state)
        y = np.array(y, copy=True)
        t = y.shape[0] // 2
        y[t, np.argmax(np.abs(y[t]))] = 0.0
        return y, state
    monkeypatch.setattr(SimLayer, "_neuron_batch", dropping)


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "answer_altered": _answer_altered,
          "message_dropped": _message_dropped}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", ["s5.sim", "sdconv64.sim", "s5.search"])
def test_a_broken_timed_path_is_not_correct(name, fault, monkeypatch):
    cell = tiny(name)
    FAULTS[fault](monkeypatch, cell.traffic["kind"])
    assert _run(cell)["correct"] is False


# ------------------------------------------------------------------- ties

def test_an_ssm_message_within_rounding_of_zero_may_go_either_way(
        monkeypatch):
    """The program leaves out the one message of the second layer whose
    state lies nearest zero.  That is a fault while the message lies
    outside the reference's rounding band, and no fault once the band
    holds it."""
    from bench import reference
    from repro.neuromorphic.network import SimLayer
    cell = tiny("s5.sim")
    state = cell.kind.setup(cell, SEED)
    xs = cell.kind.payload(state, 0)
    layer = state["net"].layers[1]
    orig = SimLayer._neuron_batch
    where = {}

    def dropping(self, pre, st):
        y, st = orig(self, pre, st)
        if self is layer:
            y = np.array(y, copy=True)
            where["tn"] = np.unravel_index(np.argmin(np.abs(y)), y.shape)
            y[where["tn"]] = 0.0
        return y, st
    monkeypatch.setattr(SimLayer, "_neuron_batch", dropping)
    got = cell.kind.program_answer(cell.kind.request(
        state, xs, harness.Spans(False), False)[0])
    want = cell.kind.reference_answer(state, xs)
    assert "low" not in want
    assert cell.kind.gaps(got, want)["count_gap"] > 0

    # the margin that just takes that message into the band
    t, n = where["tn"]
    ys = {}
    orig_neurons = reference._neurons

    def keep(spec, pre):
        y = orig_neurons(spec, pre)
        ys.setdefault((spec["name"], y.dtype.name), y)
        return y
    monkeypatch.setattr(reference, "_neurons", keep)
    reference.forward(state["layers"], xs, ties=True)
    name = state["layers"][1]["name"]
    y32, y64 = ys[(name, "float32")], ys[(name, "float64")]
    band = max(np.abs(y32[t] - y64[t]).max(),
               np.finfo(np.float32).eps * np.abs(y64[t]).max())
    monkeypatch.setattr(reference, "TIE_MARGIN",
                        1.0001 * abs(y64[t, n]) / band)
    want = cell.kind.reference_answer(state, xs)
    assert "low" in want
    g = cell.kind.gaps(got, want)
    assert g["count_gap"] == 0.0 and g["price_gap"] < 1e-12, g
