"""Fully connected stack ``sizes[0] -> ... -> sizes[-1]`` with weights
N(0, 1/sqrt(fanin)) at an exact per-layer density."""

from __future__ import annotations

import numpy as np

from bench.builders import exact_density_mask, layer


def build(net_cfg: dict, rng: np.random.Generator) -> tuple[list[dict], int]:
    sizes = [int(s) for s in net_cfg["sizes"]]
    density = float(net_cfg.get("weight_density", 1.0))
    layers = []
    for i in range(len(sizes) - 1):
        w = rng.normal(0, 1.0 / np.sqrt(sizes[i]),
                       (sizes[i], sizes[i + 1])).astype(np.float32)
        w *= exact_density_mask(w.shape, density, rng)
        layers.append(layer(f"fc{i}", "fc", w, net_cfg))
    return layers, sizes[0]
