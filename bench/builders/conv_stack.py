"""Conv stack in the PilotNet mold: one SAME-padded conv per entry of
``conv`` (its ``channels``, square ``kernel`` and ``stride``), then one fc
layer per entry of ``fc``.  Flat activations are channel-major ((c, h, w))
on both sides of every layer."""

from __future__ import annotations

import numpy as np

from bench.builders import exact_density_mask, layer


def build(net_cfg: dict, rng: np.random.Generator) -> tuple[list[dict], int]:
    h, w = (int(x) for x in net_cfg["in_hw"])
    cin = int(net_cfg["in_channels"])
    density = float(net_cfg.get("weight_density", 1.0))
    if net_cfg.get("padding", "same") != "same":
        raise ValueError("the program's conv layers are SAME-padded only")
    in_size = h * w * cin
    layers = []
    c_prev = cin
    for i, conv in enumerate(net_cfg["conv"]):
        c, k, stride = (int(conv[key]) for key in ("channels", "kernel",
                                                   "stride"))
        if h % stride or w % stride:
            raise ValueError(f"conv{i}: a {h}x{w} map does not divide by "
                             f"stride {stride}")
        wgt = rng.normal(0, 1.0 / np.sqrt(k * k * c_prev),
                         (k, k, c_prev, c)).astype(np.float32)
        wgt *= exact_density_mask(wgt.shape, density, rng)
        layers.append(layer(f"conv{i}", "conv", wgt, net_cfg, stride=stride,
                            in_hw=(h, w)))
        h, w, c_prev = h // stride, w // stride, c
    fanin = h * w * c_prev
    for i, nout in enumerate(int(x) for x in net_cfg["fc"]):
        wfc = rng.normal(0, 1.0 / np.sqrt(fanin),
                         (fanin, nout)).astype(np.float32)
        wfc *= exact_density_mask(wfc.shape, density, rng)
        layers.append(layer(f"fc{i}", "fc", wfc, net_cfg))
        fanin = nout
    return layers, in_size
