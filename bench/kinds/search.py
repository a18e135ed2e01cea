"""Kind ``search``: one client repeats ``evolutionary_search`` on one
workload fixed in set-up (network, chip, input stream and pricing cache),
each request with a search seed of its own, the next one when the last
returns (a closed loop).

``correct`` takes a sample of the window's searches, drawn from the seed,
and holds each answer to what it claims.  The plain reference
(``bench/reference.py``) runs the workload's stream once and prices, at
float64, the final candidate and every candidate of the front; these are
compared with the times and energies the search reports for them (the
device-priced best of the last generation, and the host-priced reports),
where an SSM message whose state lies within rounding of zero may go
either way.
Each search must also keep its shape: one record per generation, every
evaluation charged, a best time that never rises, a survivors' mean that
fell, and candidates that respect the chip's capacities.
"""

from __future__ import annotations

import numpy as np

from bench import reference, workload

#: the warm-up's search seed index, apart from every request's
WARMUP = 2**40


def setup(cell, seed: int) -> dict:
    from repro.neuromorphic import precompute_pricing
    layers, in_size = workload.build_layers(cell.config, seed, cell.root)
    net = workload.program_network(layers, in_size)
    chip = workload.program_chip(cell.config)
    xs = workload.stream(in_size, cell.traffic, seed, 0)
    return dict(cell=cell, seed=seed, layers=layers, in_size=in_size,
                net=net, chip=chip, xs=xs,
                cache=precompute_pricing(net, xs, chip))


def payload(state: dict, index: int) -> int:
    return int(workload.rng(state["seed"], workload.SEARCH, index)
               .integers(0, 2**31 - 1))


def _search_kwargs(traffic: dict) -> dict:
    kw = dict(engine=traffic["engine"],
              population_size=int(traffic["population_size"]),
              generations=int(traffic["generations"]),
              tournament_k=int(traffic["tournament_k"]),
              explore_prob=float(traffic["explore_prob"]))
    if "n_islands" in traffic:
        kw["n_islands"] = int(traffic["n_islands"])
        kw["migrate_every"] = int(traffic["migrate_every"])
    return kw


def request(state: dict, search_seed: int, span, traced: bool):
    from repro.core.partitioner import SimEvaluator
    from repro.core.search import evolutionary_search
    net, chip = state["net"], state["chip"]
    ev = SimEvaluator(net, state["xs"], chip, cache=state["cache"])
    with span("bench.search"):
        res = evolutionary_search(net, chip, ev, seed=search_seed,
                                  **_search_kwargs(state["cell"].traffic))
    return res, dict(generations=len(res.history) - 1)


def warmup(state: dict, span) -> None:
    """One whole search: compiles the engine's init and every generation
    variant the window uses."""
    request(state, payload(state, WARMUP), span, traced=False)


# ------------------------------------------------------------- correctness

def _counters(state: dict, contract: str = "float32"):
    """The reference's counters of the workload's stream; at float32 also
    those with every SSM message tie left out and sent."""
    key = f"ref_counters_{contract}"
    if key not in state:
        ties = contract == "float32"
        counters = reference.forward(state["layers"], state["xs"],
                                     contract=contract, ties=ties)[1]
        state[key] = (counters,)
        if ties and any(c["tie"].any() for c in counters):
            state[key] += reference.counter_bounds(state["layers"], counters)
    return state[key]


def _price(state: dict, cores, perm, dtype=np.float64,
           contract: str = "float32") -> list[tuple[float, float]]:
    """(time, energy) per step of a candidate: the reference's, then those
    with the SSM message ties left out and sent, where it has them."""
    cores = [int(c) for c in cores]
    out = []
    for counters in _counters(state, contract):
        rep = reference.price(state["layers"], counters,
                              state["cell"].config["chip"], cores,
                              [int(p) for p in perm[:sum(cores)]],
                              dtype=dtype)
        out.append((rep["time_per_step"], rep["energy_per_step"]))
    return out


def _rel(a: float, ref: list[float]) -> float:
    """How far ``a`` lies outside the span of ``ref``, over |ref[0]|."""
    out = max(min(ref) - a, a - max(ref), 0.0)
    return out / max(abs(ref[0]), 1e-300)


def faults(state: dict, res) -> int:
    """How many of the search's structural promises it breaks."""
    tr = state["cell"].traffic
    chip = state["cell"].config["chip"]
    gens, pop = int(tr["generations"]), int(tr["population_size"])
    h = res.history
    cand = res.candidate
    bad = [len(h) != gens + 1,
           res.n_evals != pop * (gens + 1),
           any(b.best_time > a.best_time for a, b in zip(h, h[1:])),
           not h[-1].mean_time < h[0].mean_time,
           not reference.feasible(state["layers"], cand.cores, chip),
           sorted(cand.perm) != list(range(chip["n_cores"])),
           not res.front,
           len(res.front) != len(res.front_reports)]
    bad += [not reference.feasible(state["layers"], c.cores, chip)
            for c in res.front]
    return int(sum(bad))


def claims(res) -> list[tuple]:
    """(cores, perm, claimed time, claimed energy) of every priced answer
    the search returns."""
    c = res.candidate
    out = [(c.cores, c.perm, res.history[-1].best_time,
            res.history[-1].best_energy),
           (c.cores, c.perm, res.report.time_per_step,
            res.report.energy_per_step)]
    out += [(f.cores, f.perm, r.time_per_step, r.energy_per_step)
            for f, r in zip(res.front, res.front_reports)]
    return out


def check(state: dict, answers: list, seed: int) -> dict:
    gap, bad = 0.0, 0
    for i in workload.sample(state["cell"].traffic, len(answers), seed):
        res = answers[i]
        bad += faults(state, res)
        for cores, perm, t, e in claims(res):
            ref = _price(state, cores, perm)
            gap = max(gap, _rel(t, [r[0] for r in ref]),
                      _rel(e, [r[1] for r in ref]))
    return dict(price_gap=gap, bad_answers=float(bad))


def control(state: dict, answers: list, seed: int) -> dict:
    """The same numbers with the reference at bfloat16 contractions and
    float32 pricing in the program's place."""
    gap, bad = 0.0, 0
    for i in workload.sample(state["cell"].traffic, len(answers), seed):
        res = answers[i]
        bad += faults(state, res)
        for cores, perm, _, _ in claims(res):
            ct, ce = _price(state, cores, perm, np.float32, "bfloat16")[0]
            ref = _price(state, cores, perm)
            gap = max(gap, _rel(ct, [r[0] for r in ref]),
                      _rel(ce, [r[1] for r in ref]))
    return dict(price_gap=gap, bad_answers=float(bad))
