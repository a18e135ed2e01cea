"""Kind ``simulate``: one client sends ``simulate(net, xs, chip,
compute=<traffic's compute>)`` (null: the program's default backend), each
request on a stream that no earlier request of the run used, the next one
when the last returns (a closed loop).

In a traced run a request is split into ``net.run_batch(xs)`` and
``simulate(net, xs, chip, precomputed=run)``: the same work as the plain
call, with a span around each half.

``correct`` compares a sample of the window's answers, drawn from the seed,
with the plain reference (``bench/reference.py``): the functional run at
float32 and its pricing at float64 on the minimal partition under the
ordered placement, which is what ``simulate`` prices by default.  An SSM
message whose state lies within rounding of zero may go either way.
"""

from __future__ import annotations

import numpy as np

from bench import reference, workload

REPORT_ARRAYS = ("times", "energies")
REPORT_SCALARS = ("time_per_step", "energy_per_step", "max_synops",
                  "max_acts", "max_link_load")
COUNTER_ARRAYS = ("per_core_synops", "per_core_acts", "per_core_msgs_out")
#: the warm-up's stream, apart from every request's
WARMUP = 2**40


def setup(cell, seed: int) -> dict:
    layers, in_size = workload.build_layers(cell.config, seed, cell.root)
    return dict(cell=cell, seed=seed, layers=layers, in_size=in_size,
                net=workload.program_network(layers, in_size),
                chip=workload.program_chip(cell.config))


def payload(state: dict, index: int) -> np.ndarray:
    return workload.stream(state["in_size"], state["cell"].traffic,
                           state["seed"], index)


def request(state: dict, xs: np.ndarray, span, traced: bool):
    from repro.neuromorphic import simulate
    net, chip = state["net"], state["chip"]
    compute = state["cell"].traffic.get("compute")
    if traced:
        with span("bench.run_batch"):
            run = net.run_batch(xs, compute=compute)
        with span("bench.pricing"):
            rep = simulate(net, xs, chip, precomputed=run)
    else:
        rep = simulate(net, xs, chip, compute=compute)
    return rep, dict(steps=int(xs.shape[0]))


def warmup(state: dict, span) -> None:
    """Run one request of the window's shapes on a stream of its own."""
    xs = payload(state, WARMUP)
    request(state, xs, span, traced=False)
    request(state, xs, span, traced=True)


# ------------------------------------------------------------- correctness

def program_answer(rep) -> dict:
    out = {k: getattr(rep, k) for k in REPORT_ARRAYS + REPORT_SCALARS
           + COUNTER_ARRAYS + ("n_cores_active", "bottleneck_stage")}
    out["outputs"] = np.asarray(rep.outputs)
    out["msgs_total"] = rep.metrics.msgs_total
    return out


def reference_answer(state: dict, xs: np.ndarray, *,
                     contract: str = "float32", dtype=np.float64) -> dict:
    """The reference's report of ``xs``.  At float32 it also holds, under
    ``low`` and ``high``, the reports of its counters with every SSM
    message tie left out and with every one sent."""
    layers, chip = state["layers"], state["cell"].config["chip"]
    ties = contract == "float32"
    outputs, counters = reference.forward(layers, xs, contract=contract,
                                          ties=ties)
    cores = reference.minimal_cores(layers, chip)
    phys = list(range(sum(cores)))
    ans = reference.price(layers, counters, chip, cores, phys, dtype=dtype)
    ans["outputs"] = outputs
    if ties and any(c["tie"].any() for c in counters):
        low, high = reference.counter_bounds(layers, counters)
        ans["low"], ans["high"] = (
            reference.price(layers, c, chip, cores, phys, dtype=dtype)
            for c in (low, high))
    return ans


def _rel(a, b, lo=None, hi=None) -> float:
    """How far ``a`` lies outside [lo, hi] (default: ``b`` alone), over
    max |b|; a shape mismatch is infinite."""
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    lo = b if lo is None else np.minimum(np.asarray(lo, np.float64), b)
    hi = b if hi is None else np.maximum(np.asarray(hi, np.float64), b)
    if a.shape != b.shape:
        return float("inf")
    scale = max(float(np.max(np.abs(b), initial=0.0)), 1e-300)
    out = np.maximum(np.maximum(lo - a, a - hi), 0.0)
    return float(np.max(out, initial=0.0)) / scale


def gaps(got: dict, want: dict) -> dict:
    """The numbers compared: ``out_gap`` (functional outputs),
    ``count_gap`` (per-core event counters and messages per step) and
    ``price_gap`` (step times, energies and the M0 maxima; a different
    core count or bottleneck stage is infinite).  Counters and prices are
    held to the span between the reference's reports with every SSM
    message tie left out and sent, where it has them."""
    lo, hi = want.get("low", want), want.get("high", want)
    rel = lambda k: _rel(got[k], want[k], lo[k], hi[k])
    price = max(rel(k) for k in REPORT_ARRAYS + REPORT_SCALARS)
    if (got["n_cores_active"] != want["n_cores_active"]
            or got["bottleneck_stage"] not in {
                w["bottleneck_stage"] for w in (want, lo, hi)}):
        price = float("inf")
    return dict(
        out_gap=_rel(got["outputs"], want["outputs"]),
        count_gap=max(rel(k) for k in COUNTER_ARRAYS + ("msgs_total",)),
        price_gap=price)


def _worst(rows: list[dict]) -> dict:
    return {k: max(r[k] for r in rows) for k in rows[0]}


def check(state: dict, answers: list, seed: int) -> dict:
    rows = []
    for i in workload.sample(state["cell"].traffic, len(answers), seed):
        want = reference_answer(state, payload(state, i))
        rows.append(gaps(program_answer(answers[i]), want))
    return _worst(rows)


def control(state: dict, answers: list, seed: int) -> dict:
    """The same numbers with the reference at bfloat16 contractions and
    float32 pricing in the program's place."""
    rows = []
    for i in workload.sample(state["cell"].traffic, len(answers), seed):
        xs = payload(state, i)
        rows.append(gaps(reference_answer(state, xs, contract="bfloat16",
                                          dtype=np.float32),
                         reference_answer(state, xs)))
    return _worst(rows)
