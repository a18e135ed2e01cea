"""The trace reduction, on hand-made events and on a small trace recorded
on a TPU v5e (``testdata/small.xplane.pb``: three rounds of two jitted
programs inside ``bench.window``, with host sleeps between them)."""

import os

import numpy as np
import pytest

from bench import trace

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "testdata", "small.xplane.pb")


def test_union_merges_overlaps():
    iv = np.array([[5, 7], [0, 2], [1, 3], [3, 4], [8, 9]], float)
    assert trace.union(iv).tolist() == [[0, 4], [5, 7], [8, 9]]
    assert trace.union(np.zeros((0, 2))).shape == (0, 2)


def test_names():
    assert trace.op_name("%fusion.12 = f32[8]{0} fusion(%p.1)") == "fusion.12"
    assert trace.module_name("jit__step_impl(4349841366091887482)") == \
        "jit__step_impl"


def _hand_made():
    ms = 1e6
    dev0 = [("fusion.1", 10 * ms, 20 * ms), ("fusion.2", 15 * ms, 30 * ms),
            ("all-gather.3", 50 * ms, 60 * ms),
            ("fusion.9", 200 * ms, 210 * ms)]          # after the window
    dev1 = [("collective-permute-start.1", 10 * ms, 40 * ms)]
    host = [("bench.window", 0.0, 100 * ms),
            ("bench.client", 0.0, 10 * ms),
            ("bench.search", 10 * ms, 100 * ms),
            ("PjitFunction(step)", 62 * ms, 99 * ms)]
    return dict(devices={"/device:TPU:0": dev0, "/device:TPU:1": dev1,
                         "/device:TPU:2": []},
                modules={"/device:TPU:0": [("jit_step", 10 * ms, 60 * ms)],
                         "/device:TPU:1": [("jit_step", 10 * ms, 40 * ms)]},
                host=[[("python", 0.0, 1.0)], host])


def test_reduce_hand_made():
    r = trace.reduce(_hand_made())
    assert r["window_s"] == pytest.approx(0.1)
    assert r["n_devices"] == 2
    # device 0 is busy 10-30 and 50-60 ms, device 1 10-40 ms
    assert r["busy_s"] == pytest.approx((0.030 + 0.030) / 2)
    assert r["collective_s"] == pytest.approx((0.010 + 0.030) / 2)
    assert r["device_ops"] == [["jit_step", pytest.approx(0.040)]]
    # device 0 idles 60-100 (host in the step), 0-10 (client), 30-50
    names = [g[0] for g in r["idle_gaps"]]
    assert names == ["bench.search/PjitFunction(step)", "bench.search",
                     "bench.client"]
    assert [g[1] for g in r["idle_gaps"]] == pytest.approx([0.04, 0.02,
                                                            0.01])


def test_reduce_needs_the_window_span():
    tr = _hand_made()
    tr["host"] = [[("bench.search", 0.0, 1.0)]]
    with pytest.raises(ValueError, match="bench.window"):
        trace.reduce(tr)


def _busy_by_sweep(events, lo, hi):
    """Busy time by a sweep over start and end points, clipped to the
    window: a second way to the union's length."""
    points = sorted([(max(s, lo), 1) for _, s, e in events if e > lo and
                     s < hi] + [(min(e, hi), -1) for _, s, e in events
                                if e > lo and s < hi])
    busy, depth, since = 0.0, 0, None
    for t, d in points:
        if depth == 0 and d == 1:
            since = t
        depth += d
        if depth == 0:
            busy += t - since
    return busy


def test_recorded_tpu_trace():
    tr = trace.load(FIXTURE)
    assert list(tr["devices"]) == ["/device:TPU:0"]
    r = trace.reduce(tr)
    lo, hi, _ = trace.window_bounds(tr)
    ops = tr["devices"]["/device:TPU:0"]
    assert r["n_devices"] == 1
    assert r["busy_s"] == pytest.approx(_busy_by_sweep(ops, lo, hi) * 1e-9)
    assert 0 < r["busy_s"] < r["window_s"]
    assert [n for n, _ in r["device_ops"]] == ["jit__lambda"]
    assert all(name.startswith("bench.") for name, _ in r["idle_gaps"])
    assert any(name.startswith("bench.run_batch") for name, _ in
               r["idle_gaps"])
