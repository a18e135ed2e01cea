"""A configuration's network and chip, built from ``--seed``, and the
message streams of its requests.

The network is built by the benchmark's own builder named in the
configuration (``bench/builders/<builder>.py``) as plain layer specs, which
the reference reads; :func:`program_network` hands the same arrays to the
program's public ``SimLayer`` / ``SimNetwork`` types.
"""

from __future__ import annotations

import os

import numpy as np

#: stream tags of :func:`rng`, so weights, request streams and the sample
#: of checked answers never share random numbers
WEIGHTS, STREAM, SAMPLE, SEARCH = 1, 2, 3, 4


def rng(seed: int, tag: int, index: int = 0) -> np.random.Generator:
    """Generator for one purpose of one run; any whole ``seed`` works."""
    return np.random.default_rng([abs(int(seed)), int(seed < 0), tag,
                                  int(index)])


def build_layers(config: dict, seed: int, root: str) -> tuple[list[dict], int]:
    """The configuration's layer specs and input width, from the seed, by
    the builder file its ``network.builder`` names."""
    from bench.harness import load_module
    net_cfg = config["network"]
    builder = load_module(root, os.path.join(
        "bench", "builders", net_cfg["builder"] + ".py"))
    return builder.build(net_cfg, rng(seed, WEIGHTS))


def stream(in_size: int, traffic: dict, seed: int, index: int) -> np.ndarray:
    """(steps, in_size) input messages of request ``index``: exactly
    ``round(density * in_size)`` events per step, uniformly placed, with
    magnitudes |N(value_mean, value_std)|."""
    g = rng(seed, STREAM, index)
    steps = int(traffic["steps"])
    vals = np.abs(g.normal(float(traffic["value_mean"]),
                           float(traffic["value_std"]),
                           (steps, in_size))).astype(np.float32)
    k = int(round(float(traffic["density"]) * in_size))
    mask = np.zeros((steps, in_size), np.float32)
    if k > 0:
        keys = g.random((steps, in_size))
        idx = np.argpartition(keys, k - 1, axis=1)[:, :k]
        np.put_along_axis(mask, idx, 1.0, axis=1)
    return vals * mask


def sample(traffic: dict, n_answers: int, seed: int) -> list[int]:
    """Indices of the window's answers that ``correct`` checks: the
    traffic's ``check_sample`` of them, drawn from the seed."""
    k = min(int(traffic["check_sample"]), n_answers)
    g = rng(seed, SAMPLE)
    return sorted(int(i) for i in g.choice(n_answers, size=k, replace=False))


def program_network(layers: list[dict], in_size: int):
    """The layer specs as the program's ``SimNetwork``."""
    from repro.neuromorphic.network import SimLayer, SimNetwork
    return SimNetwork(
        layers=[SimLayer(name=s["name"], kind=s["kind"], weights=s["weights"],
                         neuron_model=s["neuron_model"],
                         threshold=s["threshold"], decay=s["decay"],
                         stride=s["stride"], in_hw=s["in_hw"],
                         sends_deltas=s["sends_deltas"])
                for s in layers],
        in_size=in_size)


def program_chip(config: dict):
    """The configuration's chip as the program's ``ChipProfile``."""
    from repro.neuromorphic.platform import ChipProfile
    chip = dict(config["chip"])
    chip["grid"] = tuple(chip["grid"])
    return ChipProfile(**chip)
