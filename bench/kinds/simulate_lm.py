"""Kind ``simulate_lm``: one client sends ``simulate(net, xs, chip,
compute=<traffic's compute>)`` on one partition's share of a routed-expert
LM (``bench/builders/nemotron_h_share.py``), each request a stream of
decoded tokens that no earlier request of the run used, the next one when
the last returns (a closed loop).

A request's tokens are ``x_t = topic_weight * c_k + noise_weight * e_t``
over ``steps`` steps, with ``c_k`` one of ``topics`` directions drawn once
from the seed, ``k`` drawn per request with probability proportional to
``1 / (k + 1) ** zipf``, and fresh ``e_t``; ``c_k`` and ``e_t`` are N(0, I).
Skewed topics make the routing uneven, as in chat traffic.  Every input
lane messages at every step.

The network's up-projections carry the program's ``Router``; in a traced
run a request is split into ``net.run_batch(xs)`` and ``simulate(net, xs,
chip, precomputed=run)``, as in kind ``simulate``.

``correct`` compares a sample of the window's answers, drawn from the
seed, with ``bench/reference_lm.py``: the functional run at float32 with
its counters and the pricing at float64 on the minimal partition under the
ordered placement.  A message whose deciding value lies within rounding of
zero may go either way (counters and prices are held to the span between
every such tie left out and sent).  A routing step whose top-k boundary
lies within rounding may go either way too: where the reference finds
such steps (at most ``reference_lm.MAX_ROUTE_TIES`` of them), it also
runs the request with each combination of them routed the other way, and
the answer is held to the closest of those references.
"""

from __future__ import annotations

import itertools

import numpy as np

from bench import reference, reference_lm, workload
from bench.kinds import simulate as sim

#: stream tag of the topic directions, apart from ``workload``'s
TOPICS = 5
WARMUP = sim.WARMUP


def setup(cell, seed: int) -> dict:
    from repro.neuromorphic.network import Router, SimLayer, SimNetwork
    from bench.harness import load_module
    config = cell.config
    builder = load_module(cell.root, "bench/builders/"
                          f"{config['network']['builder']}.py")
    layers, in_size = builder.build(config, workload.rng(seed,
                                                         workload.WEIGHTS))
    net = SimNetwork(
        layers=[SimLayer(name=s["name"], kind=s["kind"], weights=s["weights"],
                         neuron_model=s["neuron_model"],
                         threshold=s["threshold"], decay=s["decay"],
                         router=(Router(**s["router"]) if s["router"]
                                 else None))
                for s in layers],
        in_size=in_size)
    tr = cell.traffic
    topics = workload.rng(seed, TOPICS).standard_normal(
        (int(tr["topics"]), in_size))
    return dict(cell=cell, seed=seed, layers=layers, in_size=in_size,
                net=net, chip=workload.program_chip(config), topics=topics,
                cores=reference.minimal_cores(layers, config["chip"]))


def payload(state: dict, index: int) -> np.ndarray:
    """(steps, in_size) float32 tokens of request ``index``."""
    tr, topics = state["cell"].traffic, state["topics"]
    g = workload.rng(state["seed"], workload.STREAM, index)
    p = 1.0 / np.arange(1, len(topics) + 1) ** float(tr["zipf"])
    k = g.choice(len(topics), p=p / p.sum())
    noise = g.standard_normal((int(tr["steps"]), state["in_size"]))
    return (float(tr["topic_weight"]) * topics[k]
            + float(tr["noise_weight"]) * noise).astype(np.float32)


request = sim.request


def warmup(state: dict, span) -> None:
    """Run one request of the window's shapes on a stream of its own."""
    xs = payload(state, WARMUP)
    request(state, xs, span, traced=False)
    request(state, xs, span, traced=True)


# ------------------------------------------------------------- correctness

def reference_answer(state: dict, xs: np.ndarray, *,
                     contract: str = "float32", dtype=np.float64,
                     force: dict | None = None) -> dict:
    """The reference's report of ``xs``; at float32 also, under ``low``
    and ``high``, those with every message tie left out and sent, and
    under ``route_ties`` the routing ties."""
    layers, chip = state["layers"], state["cell"].config["chip"]
    ties = contract == "float32"
    outputs, counters, route_ties = reference_lm.forward(
        layers, xs, contract=contract, ties=ties, force=force)
    cores = state["cores"]
    phys = list(range(sum(cores)))
    ans = reference_lm.price(layers, counters, chip, cores, phys,
                             dtype=dtype)
    ans["outputs"] = outputs
    ans["route_ties"] = route_ties
    if ties and any(c["tie"].any() or c["tie_in"].any() for c in counters):
        low, high = reference_lm.counter_bounds(layers, counters)
        ans["low"], ans["high"] = (
            reference_lm.price(layers, c, chip, cores, phys, dtype=dtype)
            for c in (low, high))
    return ans


def closest_gaps(state: dict, xs: np.ndarray, got: dict) -> dict:
    """The gaps of ``got`` from the reference, or from the reference with
    some of its routing ties routed the other way, whichever is closest."""
    want = reference_answer(state, xs)
    best = sim.gaps(got, want)
    ties = want["route_ties"][:reference_lm.MAX_ROUTE_TIES]
    key = lambda g: (g["count_gap"], g["price_gap"], g["out_gap"])
    for n in range(1, len(ties) + 1):
        for combo in itertools.combinations(ties, n):
            force = {(l, t): (a, b) for l, t, a, b in combo}
            g = sim.gaps(got, reference_answer(state, xs, force=force))
            best = min(best, g, key=key)
    return best


def check(state: dict, answers: list, seed: int) -> dict:
    rows = [closest_gaps(state, payload(state, i),
                         sim.program_answer(answers[i]))
            for i in workload.sample(state["cell"].traffic, len(answers),
                                     seed)]
    return sim._worst(rows)


def control(state: dict, answers: list, seed: int) -> dict:
    """The same numbers with the reference at bfloat16 contractions and
    float32 pricing in the program's place."""
    rows = []
    for i in workload.sample(state["cell"].traffic, len(answers), seed):
        xs = payload(state, i)
        got = reference_answer(state, xs, contract="bfloat16",
                               dtype=np.float32)
        rows.append(closest_gaps(state, xs, got))
    return sim._worst(rows)
