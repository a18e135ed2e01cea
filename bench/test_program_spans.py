"""The per-layer metrics that read the program's own spans
(``bench/program_spans.py``), on hand-made runs and on traced runs of
each cell at a tiny size."""

import io
import json
import sys
import time

import pytest

from bench import harness
from bench.conftest import tiny
from repro import tracing

SIM = ("synaptic_ms.sim", "neuron_ms.sim", "h2d_mb.sim", "cumsum_ms.sim",
       "candidate_ms.sim")
SEARCH = ("sync_ms.search", "archive_ms.search", "edges_ms.search")


def _span(name, start, end, counts=None, index=0):
    return tracing.Span(name, start, end, -1, 0, counts or {}, index)


def _run(name, spans, *, dropped=0, traced=True, work=None, n=2,
         monkeypatch=None):
    """A run of cell ``name`` with ``n`` completed requests, request i
    from 10 i to 10 i + 10 s, whose program recorded ``spans``."""
    drains = []

    def drain():
        drains.append(1)
        return tracing.Drained(list(spans), dropped)
    monkeypatch.setattr(tracing, "drain", drain)
    requests = [harness.Request(i, 10.0 * i, 10.0 * i + 10,
                                dict(work or {}), True) for i in range(n)]
    run = harness.Run(harness.resolve(name), 0, traced, {}, 0.0, 10.0 * n,
                      requests, [], None, {})
    return run, drains


def _read(run, metric):
    return run.cell.reader(metric)(run)


def test_sim_readers_divide_by_the_requests_completed(monkeypatch):
    spans = [_span("sim.synaptic", 1.0, 1.004),
             _span("sim.synaptic", 2.0, 2.002),
             _span("sim.neuron", 3.0, 3.001),
             _span("price.cumsum", 4.0, 4.008),
             _span("price.candidate", 5.0, 5.010),
             _span("price.route", 5.001, 5.002),
             _span("kernel.put", 6, 6.1, {"h2d_bytes": 3_000_000}),
             _span("kernel.put", 12, 12.1, {"h2d_bytes": 1_000_000}),
             _span("kernel.fetch", 13, 13.1)]
    run, drains = _run("s5.sim", spans, work=dict(steps=8),
                       monkeypatch=monkeypatch)
    got = {m: _read(run, m) for m in SIM}
    assert got == pytest.approx({"synaptic_ms.sim": 3.0,
                                 "neuron_ms.sim": 0.5, "h2d_mb.sim": 2.0,
                                 "cumsum_ms.sim": 4.0,
                                 "candidate_ms.sim": 5.0})
    assert len(drains) == 1                # the readers of one run share it


def test_a_failed_request_is_not_counted(monkeypatch):
    run, _ = _run("s5.sim", [_span("sim.neuron", 1.0, 1.006)], n=3,
                  monkeypatch=monkeypatch)
    run.requests[1].ok = False
    assert _read(run, "neuron_ms.sim") == pytest.approx(3.0)


def test_search_readers_divide_by_generations_and_searches(monkeypatch):
    spans = [_span("search.seed", 1.0, 1.010),
             _span("search.sync", 2.0, 2.002),
             _span("search.sync", 3.0, 3.006),
             _span("search.archive", 4.0, 4.008),
             _span("search.finish", 15.0, 15.030), _span("search", 0.5, 16)]
    run, _ = _run("s5.search", spans, work=dict(generations=4),
                  monkeypatch=monkeypatch)
    got = {m: _read(run, m) for m in SEARCH}
    assert got == pytest.approx({"sync_ms.search": 1.0,
                                 "archive_ms.search": 1.0,
                                 "edges_ms.search": 20.0})


def test_spans_outside_the_window_are_left_out(monkeypatch):
    spans = [_span("sim.neuron", -1.0, 0.5),     # starts before the window
             _span("sim.neuron", 1.0, 1.004),
             _span("sim.neuron", 19.9, 20.1),    # ends after it
             _span("sim.neuron", 25.0, 26.0)]
    run, _ = _run("s5.sim", spans, monkeypatch=monkeypatch)
    assert _read(run, "neuron_ms.sim") == pytest.approx(2.0)


@pytest.mark.parametrize("case", ["untraced", "missing", "dropped",
                                  "no_tracer", "none_done"])
def test_nothing_to_read_reads_none(case, monkeypatch):
    spans = [_span(n, 1.0, 1.001, {"h2d_bytes": 8}) for n in
             ("sim.synaptic", "sim.neuron", "price.cumsum",
              "price.candidate", "kernel.put", "search.seed",
              "search.sync", "search.archive", "search.finish")]
    kw = dict(work=dict(steps=8, generations=4), monkeypatch=monkeypatch)
    if case == "untraced":
        kw["traced"] = False
    elif case == "missing":
        spans = [_span("sim.run_batch", 1.0, 2.0)]
    elif case == "dropped":
        kw["dropped"] = 1
    elif case == "none_done":
        kw["n"] = 1
    for cell, metrics in (("s5.sim", SIM), ("s5.search", SEARCH)):
        run, _ = _run(cell, spans, **kw)
        if case == "no_tracer":
            import repro
            monkeypatch.delattr(repro, "tracing")
            monkeypatch.setitem(sys.modules, "repro.tracing", None)
        if case == "none_done":
            run.requests[0].ok = False
        assert {m: _read(run, m) for m in metrics} == dict.fromkeys(metrics)
        monkeypatch.undo()


@pytest.mark.parametrize("name", ["s5.sim", "sdconv64.sim", "s5.search"])
def test_a_traced_run_reports_the_program_spans(name, monkeypatch):
    from bench import flops
    monkeypatch.setattr(flops, "peak", lambda kind: 1e12)   # no CPU peak
    cell = tiny(name)
    out, err = io.StringIO(), io.StringIO()
    assert harness.run_cell(cell, 2**31 + 17, 0.3, True, require_chip=False,
                            out=out, err=err) == 0, err.getvalue()
    res = json.loads(out.getvalue().splitlines()[-1])
    assert res["correct"], err.getvalue()
    want = set(SEARCH if name == "s5.search" else SIM)
    if name == "s5.sim":
        # on a CPU the event backend takes its NumPy path: no device copy
        want.discard("h2d_mb.sim")
    assert want <= set(res["metrics"])
    assert all(res["metrics"][m]["value"] > 0 for m in want)
    assert tracing.drain() == ([], 0)


# --------------------------------------------------- what each span covers
#
# The metrics above are only as steady as the program's span boundaries.
# These tests pin the work inside each span a metric reads: each named
# function of the program runs inside its span, and in the sim cells the
# spans hold most of the benchmark's own halves.  Moving a boundary moves
# the yardstick.

def _spy(monkeypatch, calls, owner, attr, label=None):
    """Record ``(label, start, end)`` of every call of ``owner.attr``."""
    real = getattr(owner, attr)
    label = label or attr

    def spy(*args, **kw):
        t0 = time.perf_counter()
        try:
            return real(*args, **kw)
        finally:
            calls.append((label, t0, time.perf_counter()))
    monkeypatch.setattr(owner, attr, spy)


def _inside(spans, t0, t1, *names) -> bool:
    return any(s.name in names and s.start <= t0 and t1 <= s.end
               for s in spans)


def _seconds(spans, *names) -> float:
    return sum(s.end - s.start for s in spans if s.name in names)


def _requests(cell, traced, n=3, calls=None):
    """``n`` requests of ``cell`` after its set-up and warm-up, recorded
    under ``tracing.enable()``: the program's spans and the benchmark's.
    ``calls`` is emptied before the requests."""
    state = cell.kind.setup(cell, 2**31 + 29)
    cell.kind.warmup(state, harness.Spans(False))
    bench = harness.Spans(False)
    if calls is not None:
        calls.clear()
    tracing.drain()
    with tracing.enable():
        for i in range(n):
            cell.kind.request(state, cell.kind.payload(state, i), bench,
                              traced)
    spans, dropped = tracing.drain()
    assert dropped == 0
    return spans, [tracing.Span(n, a, b, -1, 0, {}, -1)
                   for n, a, b in bench.spans]


@pytest.mark.parametrize("name", ["s5.sim", "sdconv64.sim"])
def test_sim_spans_hold_their_work(name, monkeypatch):
    from repro.neuromorphic import compute, network, timestep
    calls = []
    for cls in (compute.LayerCompute, compute.DenseCompute,
                compute.EventCompute):
        for attr in ("forward", "delta_forward"):
            if attr in vars(cls):
                _spy(monkeypatch, calls, cls, attr, "synaptic")
    _spy(monkeypatch, calls, network.SimLayer, "_neuron_batch", "neuron")
    _spy(monkeypatch, calls, timestep, "_neuron_csum", "cumsum")
    for attr in ("_cached_layer_counters", "core_times", "route_batch"):
        _spy(monkeypatch, calls, timestep, attr, "candidate")
    spans, _ = _requests(tiny(name), traced=True, calls=calls)
    where = {"synaptic": "sim.synaptic", "neuron": "sim.neuron",
             "cumsum": "price.cumsum", "candidate": "price.candidate"}
    assert {label for label, _, _ in calls} == set(where)
    for label, t0, t1 in calls:
        assert _inside(spans, t0, t1, where[label]), label


@pytest.mark.parametrize("name", ["s5.sim", "sdconv64.sim"])
def test_price_cumsum_leaves_out_the_functional_run(name):
    spans, _ = _requests(tiny(name), traced=False)  # simulate runs run_batch
    runs = [s for s in spans if s.name == "sim.run_batch"]
    assert runs
    assert not any(_inside(spans, s.start, s.end, "price.cumsum")
                   for s in runs)


@pytest.mark.parametrize("name", ["s5.sim", "sdconv64.sim"])
def test_sim_spans_hold_most_of_each_half(name):
    cell = tiny(name)
    if name == "s5.sim":
        # at the tiny widths each layer's bookkeeping (masks, counter
        # copies) weighs as much as its 64-wide contraction; at a quarter
        # of the cell's widths the spans hold about 90% of each half
        cell.config["network"]["sizes"] = [256, 768, 768, 256]
        cell.traffic["steps"] = 64
    spans, bench = _requests(cell, traced=True)
    functional = _seconds(bench, "bench.run_batch")
    pricing = _seconds(bench, "bench.pricing")
    assert _seconds(spans, "sim.synaptic", "sim.neuron") >= 0.8 * functional
    assert (_seconds(spans, "price.cumsum", "price.candidate")
            >= 0.8 * pricing)


def test_search_spans_hold_their_work(monkeypatch):
    import jax
    from repro.core import device_search, search
    calls = []
    _spy(monkeypatch, calls, jax, "device_get")
    _spy(monkeypatch, calls, search.EpsParetoArchive, "update_batch")
    _spy(monkeypatch, calls, device_search._ResilientEngine, "init")
    _spy(monkeypatch, calls, device_search._ResilientEngine, "step")
    for attr in ("seeded_population", "price_candidate",
                 "simulate_population"):
        _spy(monkeypatch, calls, device_search, attr)
    spans, _ = _requests(tiny("s5.search"), traced=True, n=2, calls=calls)
    gens = 2 * tiny("s5.search").traffic["generations"]
    where = {"device_get": ("search.seed", "search.sync", "search.finish"),
             "update_batch": ("search.seed", "search.archive"),
             "init": ("search.seed",), "step": ("search.step",),
             "seeded_population": ("search.seed",),
             "price_candidate": ("search.finish",),
             "simulate_population": ("search.finish",)}
    assert {label for label, _, _ in calls} == set(where)
    for label, t0, t1 in calls:
        assert _inside(spans, t0, t1, *where[label]), label
    inside = lambda label, span: sum(
        _inside(spans, t0, t1, span) for l, t0, t1 in calls if l == label)
    assert inside("device_get", "search.sync") == gens
    assert inside("update_batch", "search.archive") == gens
