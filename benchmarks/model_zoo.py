"""Model-zoo arm: compiled real-model workloads through the full pricing
stack — floorline fit, compute-backend parity/speed, and a short
evolutionary mapping search per compiled arch.

The fc/conv microbenchmarks characterize the simulator; this arm prices the
*workloads the paper argues about*: real architecture configs compiled by
:mod:`repro.neuromorphic.frontend` (attention / SSD / MoE blocks with exact
per-token counter maps).  Appends a ``model_zoo`` section to
``BENCH_sim.json``:

* per-arch rows — layer/param/MAC arithmetic of the compiled network,
  floorline fit over programmed activation densities, best time/step from a
  short evolutionary search, and the dense/event counter-parity witness;
* smoke mode (``REPRO_BENCH_SMOKE=1``) prices one arch with a 2-generation
  search so the CI suite stays fast; the full run covers one arch per
  family and adds the device-engine search + three-backend pricing parity.
"""

from __future__ import annotations

import os
import time

import numpy as np

from benchmarks import workloads as W
from repro.core.floorline import WorkloadPoint, fit_floorline
from repro.core.partitioner import SimEvaluator
from repro.core.search import evolutionary_search
from repro.neuromorphic import minimal_partition, simulate, simulate_population
from repro.neuromorphic.noc import ordered_mapping, strided_mapping

BENCH_PATH = "BENCH_sim.json"

FLOOR_DENSITIES = (1.0, 0.5, 0.2, 0.05)


def _smoke() -> bool:
    return os.environ.get("REPRO_BENCH_SMOKE") == "1"


def _floorline_points(arch_id: str, steps: int) -> list[WorkloadPoint]:
    pts = []
    for dens in FLOOR_DENSITIES:
        compiled, prof = W.model_zoo(arch_id, act_density=dens, seed=1)
        xs = compiled.inputs(steps, seed=2)
        r = simulate(compiled.net, xs, prof)
        pts.append(WorkloadPoint(max_synops=r.max_synops, max_acts=r.max_acts,
                                 time=r.time_per_step,
                                 energy=r.energy_per_step,
                                 label=f"{arch_id}/{dens}"))
    return pts


def _backend_parity(compiled, prof, xs) -> dict:
    """Exact-counter witness: dense vs event totals must be identical."""
    _, cnt_d = compiled.net.run_batch(xs, compute="dense")
    _, cnt_e = compiled.net.run_batch(xs, compute="event")
    tot_d = sum(float(c.macs.sum()) for c in cnt_d)
    tot_e = sum(float(c.macs.sum()) for c in cnt_e)
    return {"macs_dense": tot_d, "macs_event": tot_e,
            "identical": tot_d == tot_e and all(
                np.array_equal(a.macs, b.macs)
                for a, b in zip(cnt_d, cnt_e))}


def _pricing_parity(compiled, prof, xs) -> dict:
    """numpy/device population backends price identically."""
    p0 = minimal_partition(compiled.net, prof)
    cands = [(p0, ordered_mapping(p0, prof)), (p0, strided_mapping(p0, prof))]
    rows = {}
    for backend in ("numpy", "device"):
        t0 = time.perf_counter()
        reps = simulate_population(compiled.net, xs, prof, cands,
                                   backend=backend)
        rows[backend] = {"secs": time.perf_counter() - t0,
                         "time_per_step": [float(r.time_per_step)
                                           for r in reps]}
    base = rows["numpy"]["time_per_step"]
    rows["max_rel_err"] = max(
        abs(a - b) / abs(b)
        for a, b in zip(rows["device"]["time_per_step"], base))
    return rows


def _one_arch(arch_id: str, *, steps: int, generations: int,
              pop: int, full: bool) -> dict:
    compiled, prof = W.model_zoo(arch_id)
    xs = compiled.inputs(steps, seed=3)
    row: dict = {
        "arch": arch_id,
        "family": compiled.family,
        "n_layers": len(compiled.net.layers),
        "param_nnz": compiled.param_layer_nnz(),
        "macs_per_token": compiled.macs_per_token(),
        "n_attn_sites": len(compiled.attn_specs),
    }
    pts = _floorline_points(arch_id, steps)
    model = fit_floorline(pts)
    row["floorline"] = {"mem_latency": model.mem_latency,
                        "act_latency": model.act_latency, "t0": model.t0,
                        "n_points": len(pts)}
    row["backend_parity"] = _backend_parity(compiled, prof, xs)

    ev = SimEvaluator(compiled.net, xs, prof, population_backend="device")
    t0 = time.perf_counter()
    res = evolutionary_search(compiled.net, prof, ev,
                              population_size=pop, generations=generations,
                              seed=0)
    row["search"] = {"engine": "numpy", "generations": generations,
                     "population": pop, "secs": time.perf_counter() - t0,
                     "n_evals": res.n_evals,
                     "seed_best_time": res.seed_best_time,
                     "best_time_per_step": res.report.time_per_step,
                     "bottleneck": res.report.bottleneck_stage}
    if full:
        row["pricing_parity"] = _pricing_parity(compiled, prof, xs)
        ev_d = SimEvaluator(compiled.net, xs, prof)
        t0 = time.perf_counter()
        res_d = evolutionary_search(compiled.net, prof, ev_d,
                                    population_size=pop,
                                    generations=generations, seed=0,
                                    engine="device")
        row["search_device"] = {
            "secs": time.perf_counter() - t0,
            "best_time_per_step": res_d.report.time_per_step}
    return row


def run(quick: bool = False, arch: str | None = None) -> dict:
    smoke = _smoke()
    archs = ([arch] if arch else
             list(W.MODEL_ZOO_ARCHS[:1] if smoke else W.MODEL_ZOO_ARCHS))
    steps = 4 if quick else 8
    generations = 2 if (quick or smoke) else 6
    pop = 8 if (quick or smoke) else 16
    rows = [_one_arch(a, steps=steps, generations=generations, pop=pop,
                      full=not smoke) for a in archs]
    res = {"rows": rows, "smoke": smoke}

    from benchmarks._bench_io import merge_write_json
    merge_write_json(BENCH_PATH, {"model_zoo": res})
    return res


def report(res: dict) -> str:
    lines = ["## model zoo — compiled real-model workloads"]
    for r in res["rows"]:
        s = r["search"]
        gain = r["search"].get("seed_best_time", 0.0)
        gain = (gain / s["best_time_per_step"]) if s["best_time_per_step"] else 1.0
        lines.append(
            f"  {r['arch']:16s} [{r['family']}] {r['n_layers']} layers, "
            f"{r['macs_per_token']} MACs/token: search {s['generations']}g -> "
            f"time/step {s['best_time_per_step']:.0f} "
            f"({gain:.2f}x vs seed pop), counters "
            f"{'identical' if r['backend_parity']['identical'] else 'DIVERGED'}"
            f" across compute backends")
    return "\n".join(lines)
