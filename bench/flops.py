"""Dense-equivalent operations of a request, from its shapes, and the
chip's peaks.

Only the value contraction of each layer counts: 2*T*K*N for an fc layer
of fanin K and N neurons, and 2*T*oh*ow*cout*kh*kw*cin for a conv layer.
Neither the counter contractions nor the work an event path skips count,
so the number is the same whatever computes the layer.
"""

from __future__ import annotations

import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


def layer_flops(spec: dict, steps: int) -> int:
    w = spec["weights"]
    if spec["kind"] == "fc":
        k, n = w.shape
        return 2 * steps * int(k) * int(n)
    kh, kw, cin, cout = (int(x) for x in w.shape)
    h, wd = spec["in_hw"]
    oh, ow = h // spec["stride"], wd // spec["stride"]
    return 2 * steps * oh * ow * cout * kh * kw * cin


def request_flops(layers: list[dict], steps: int) -> int:
    return sum(layer_flops(s, steps) for s in layers)


def peak(device_kind: str, key: str = "bf16_flops_per_s") -> float:
    """A peak of one chip of ``device_kind``; a kind not in the table is
    an error."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS}; known: {sorted(table)}")
    return float(table[device_kind][key])
