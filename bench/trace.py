"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
benchmark reports.

The trace is read with ``jax.profiler.ProfileData``.  Devices are the
planes named ``/device:<PLATFORM>:<n>``; their operations are the events
of the line ``XLA Ops`` (named by the HLO instruction, ``%fusion.12 =
...``), and their programs those of ``XLA Modules`` (``jit_step(<hash>)``).
The benchmark's own host spans (named
``bench.*``, written with ``jax.profiler.TraceAnnotation``) sit on a host
thread line of ``/host:CPU``; the span ``bench.window`` bounds the measured
window, and everything is clipped to it.

- busy time: the union of a device's operation intervals, averaged over
  the devices that ran any operation;
- device time per program, and of the collective operations;
- the longest idle gaps of the first device, each named by the benchmark
  span that encloses it and the innermost host event under that span.
"""

from __future__ import annotations

import glob
import os
import re

import numpy as np

WINDOW = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
COLLECTIVE = re.compile(r"all-gather|all-reduce|collective-permute|"
                        r"all-to-all|reduce-scatter|collective-broadcast")


def find_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {files}")
    return files[0]


def _events(line, name=lambda n: n) -> list[tuple[str, float, float]]:
    return [(name(e.name), float(e.start_ns),
             float(e.start_ns + e.duration_ns)) for e in line.events]


def op_name(hlo: str) -> str:
    """``%fusion.12 = f32[8]{0} fusion(...)`` -> ``fusion.12``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def module_name(name: str) -> str:
    """``jit_step(1234)`` -> ``jit_step``."""
    return name.split("(", 1)[0]


def load(path: str) -> dict:
    """The parts of a trace the reduction reads: per device its operation
    and program intervals, and the host threads' events."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, modules, host = {}, {}, []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            devices[plane.name], modules[plane.name] = [], []
            for line in plane.lines:
                if line.name == OP_LINE:
                    devices[plane.name] += _events(line, op_name)
                elif line.name == MODULE_LINE:
                    modules[plane.name] += _events(line, module_name)
        elif plane.name == "/host:CPU":
            host += [_events(line) for line in plane.lines]
    return dict(devices=devices, modules=modules, host=host)


def union(intervals: np.ndarray) -> np.ndarray:
    """Sorted, disjoint union of (n, 2) [start, end) intervals."""
    if len(intervals) == 0:
        return np.zeros((0, 2))
    iv = intervals[np.argsort(intervals[:, 0], kind="stable")]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out)


def _clip(events, lo: float, hi: float):
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def window_bounds(tr: dict) -> tuple[float, float, list]:
    """(start, end) of the ``bench.window`` span and the host thread line
    that holds it."""
    for events in tr["host"]:
        for name, s, e in events:
            if name == WINDOW:
                return s, e, events
    raise ValueError(f"the trace holds no {WINDOW!r} span")


def reduce(tr: dict, top: int = 10) -> dict:
    """Busy and idle time, device time per program and of collectives,
    and the breakdown, all inside the window.  Times are in seconds."""
    lo, hi, thread = window_bounds(tr)
    per_dev = {name: _clip(ops, lo, hi) for name, ops in tr["devices"].items()}
    per_dev = {k: v for k, v in per_dev.items() if v}
    busy, coll = [], []
    for name in sorted(per_dev):
        ops = per_dev[name]
        u = union(np.asarray([(s, e) for _, s, e in ops]))
        busy.append(float((u[:, 1] - u[:, 0]).sum()))
        coll.append(sum(e - s for op, s, e in ops if COLLECTIVE.search(op)))
    prog_time: dict = {}
    for name in per_dev:
        for prog, s, e in _clip(tr["modules"].get(name, []), lo, hi):
            prog_time[prog] = prog_time.get(prog, 0.0) + (e - s)
    n_dev = max(len(per_dev), 1)
    window_s = (hi - lo) * 1e-9
    return dict(
        window_s=window_s,
        n_devices=len(per_dev),
        busy_s=sum(busy) * 1e-9 / n_dev,
        collective_s=sum(coll) * 1e-9 / n_dev,
        device_ops=[[n, t * 1e-9 / n_dev] for n, t in
                    sorted(prog_time.items(), key=lambda kv: -kv[1])[:top]],
        idle_gaps=idle_gaps(per_dev, thread, lo, hi, top),
    )


def idle_gaps(per_dev: dict, thread: list, lo: float, hi: float,
              top: int) -> list:
    """The ``top`` longest gaps between the first device's operations,
    named ``<bench span>/<innermost host event>`` at their midpoints."""
    if per_dev:
        first = per_dev[sorted(per_dev)[0]]
        u = union(np.asarray([(s, e) for _, s, e in first]))
        edges = np.concatenate([[lo], u.ravel(), [hi]]).reshape(-1, 2)
    else:
        edges = np.asarray([[lo, hi]])
    gaps = [(s, e) for s, e in edges if e > s]
    gaps.sort(key=lambda g: g[0] - g[1])
    host = sorted(((n, s, e) for n, s, e in thread if e > s),
                  key=lambda ev: ev[1])
    out = []
    for s, e in gaps[:top]:
        mid = 0.5 * (s + e)
        around = [ev for ev in host if ev[1] <= mid < ev[2]]
        spans = [ev for ev in around if ev[0].startswith("bench.")
                 and ev[0] != WINDOW]
        outer = spans[0][0] if spans else WINDOW
        inner = around[-1][0] if around else ""
        name = outer if inner in ("", outer, WINDOW) else f"{outer}/{inner}"
        out.append([name, float(e - s) * 1e-9])
    return out
