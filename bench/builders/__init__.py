"""Network builders, one file per ``network.builder`` name of a
configuration.  Each module's ``build(net_cfg, rng)`` returns the plain
layer specs and the input width; the benchmark owns these generators so a
change to the program's own builders cannot move the yardstick.

A layer spec is a dict: ``name``, ``kind`` ('fc' | 'conv'), ``weights``
(fc: (fanin, nout); conv: (kh, kw, cin, cout), float32), ``stride``,
``in_hw`` (conv only), ``neuron_model``, ``threshold``, ``decay`` and
``sends_deltas``.
"""

from __future__ import annotations

import numpy as np


def exact_density_mask(shape, density: float,
                       rng: np.random.Generator) -> np.ndarray:
    """0/1 float32 mask with exactly ``round(density * size)`` ones,
    uniformly placed."""
    n = int(np.prod(shape))
    k = int(round(density * n))
    flat = np.zeros(n, np.float32)
    if k > 0:
        flat[rng.choice(n, size=k, replace=False)] = 1.0
    return flat.reshape(shape)


def layer(name: str, kind: str, weights: np.ndarray, net_cfg: dict, *,
          stride: int = 1, in_hw=None) -> dict:
    return dict(name=name, kind=kind, weights=weights, stride=stride,
                in_hw=None if in_hw is None else tuple(in_hw),
                neuron_model=net_cfg["neuron_model"],
                threshold=float(net_cfg.get("threshold", 0.0)),
                decay=float(net_cfg.get("decay", 0.9)),
                sends_deltas=bool(net_cfg.get("sends_deltas", False)))
