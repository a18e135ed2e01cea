"""Bring-up smoke run of the simulate -> price -> search path on a TPU.

    python chip_smoke.py              # phases (a)-(e) on one chip
    python chip_smoke.py --chips 4    # sharded island search on four chips

Every workload is generated from ``--seed``; nothing is read from disk.
Each phase prints one line with its wall time, its backend compile time
(``compile_s``) and its tracing and lowering time (``trace_lower_s``).
The last line of standard output is one JSON object naming the device;
it is printed only when every phase passed.  The script exits non-zero
when JAX finds no TPU, when a phase fails, and when a pricing or search
backend fails: the evaluators are fail-fast, so nothing demotes to a
host fallback.

One chip:

(a) the device runs a jitted program;
(b) ``simulate`` of an S5-style stack holding 80% of a Loihi 2-class
    chip's synapses, with SSM and with ReLU neurons, through the dense and
    the event (Pallas) backends: the integer counters are identical to
    each other and to the step-major reference engine, and the outputs
    agree to f32 roundoff;
(c) the same on a PilotNet-style sigma-delta conv net with the dense,
    event-cumsum and event-window backends.  Their divergence is printed,
    not asserted: float reassociation flips sigma-delta threshold
    crossings, as it does on the CPU;
(d) device population pricing of 64 candidates against NumPy pricing;
(e) ``evolutionary_search(engine="device")`` against its host mirror.

Four chips: ``engine="sharded"`` over a four-island mesh against its host
mirror, with ``engine="device"`` at the same total population beside it.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(HERE, "src"), HERE]

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmarks.workloads import conv_net, s5_sim  # noqa: E402
from repro.core.device_search import (_sharded_engine_for,  # noqa: E402
                                      evolutionary_search_device,
                                      evolutionary_search_sharded)
from repro.core.partitioner import SimEvaluator  # noqa: E402
from repro.core.search import (Population, decode,  # noqa: E402
                               evolutionary_search, move_tables,
                               seeded_population)
from repro.distributed.sharding import island_mesh  # noqa: E402
from repro.kernels.event_matmul.ops import event_matmul_pair  # noqa: E402
from repro.launch.mesh import enable_compile_cache  # noqa: E402
from repro.neuromorphic import (EventCompute, get_compute,  # noqa: E402
                                loihi2_like, make_inputs,
                                precompute_pricing, simulate)

S5_SIZES = (512, 1536, 1536, 1536, 512)
CONV_HW = (64, 64)
CONV_CHANNELS = (16, 32, 64)
STEPS = 64
DENSITY = 0.1
POPULATION = 64
GENERATIONS = 4
ISLANDS = 4
#: parity of the jitted float64 pricer and search with their NumPy
#: references, as tests/test_population_pricing.py and
#: tests/test_device_search.py assert it
F64_RTOL = 1e-9
#: event outputs against the dense host-f32 outputs, relative to the
#: largest dense output: f32 reassociation stays near 1e-7, while one bf16
#: MXU pass per product gave 3e-3 on a v5e
OUTPUT_RTOL = 1e-5

COUNTERS = ("msgs_in", "macs", "fetches_dense", "msgs_out", "acts_evented")
REPORT_ARRAYS = ("times", "energies", "per_core_synops", "per_core_acts",
                 "per_core_msgs_out")
REPORT_SCALARS = ("time_per_step", "energy_per_step", "max_synops",
                  "max_acts", "max_link_load")


# ---------------------------------------------------------------- workloads

def s5_workload(sizes=S5_SIZES, steps=STEPS, density=DENSITY, seed=0,
                neuron_model="ssm"):
    """S5-style fc stack on the Loihi 2-class profile."""
    net, prof = s5_sim(sizes=tuple(sizes), seed=seed,
                       neuron_model=neuron_model)
    return net, make_inputs(net.in_size, density, steps, seed=seed + 1), prof


def sigma_delta_workload(in_hw=CONV_HW, channels=CONV_CHANNELS, steps=STEPS,
                         density=DENSITY, seed=0):
    """PilotNet-style sigma-delta conv net on the Loihi 2-class profile."""
    net = conv_net(in_hw=tuple(in_hw), cin=2, channels=tuple(channels),
                   fc_out=1, neuron_model="sd_relu", sends_deltas=True,
                   seed=seed)
    xs = make_inputs(int(net.in_size), density, steps, seed=seed + 1)
    return net, xs, loihi2_like()


# ----------------------------------------------------------------- checks

def _rel_err(a, b) -> float:
    """max |a - b| / (1 + |b|): the tests' ``rtol`` with ``atol = rtol``."""
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    if a.shape != b.shape:
        return float("inf")
    return float(np.max(np.abs(a - b) / (1.0 + np.abs(b)), initial=0.0))


def _report_mismatches(a, b) -> list[str]:
    """Fields of two SimReports that are not bit-identical."""
    bad = [f for f in REPORT_ARRAYS
           if not np.array_equal(getattr(a, f), getattr(b, f))]
    bad += [f for f in REPORT_SCALARS + ("n_cores_active",
                                         "bottleneck_stage", "metrics")
            if getattr(a, f) != getattr(b, f)]
    return bad


def _reports_rel_err(got, want) -> float:
    """Largest relative error of a priced population against a reference;
    a categorical mismatch counts as infinite."""
    err = 0.0
    for a, b in zip(got, want, strict=True):
        if (a.bottleneck_stage != b.bottleneck_stage
                or a.n_cores_active != b.n_cores_active):
            return float("inf")
        for f in REPORT_ARRAYS + REPORT_SCALARS:
            err = max(err, _rel_err(getattr(a, f), getattr(b, f)))
    return err


def _trajectory_rel_err(a, b) -> float:
    """Largest relative error between two search histories; a differing
    length, evaluation count or final candidate counts as infinite."""
    if (len(a.history) != len(b.history) or a.candidate != b.candidate
            or [g.n_evals for g in a.history]
            != [g.n_evals for g in b.history]):
        return float("inf")
    return max(_rel_err([g.best_time, g.best_energy, g.mean_time],
                        [h.best_time, h.best_energy, h.mean_time])
               for g, h in zip(a.history, b.history))


def _no_demotions(*results) -> None:
    for r in results:
        if r.demotions:
            raise AssertionError(f"backend demoted: {r.demotions}")


def check_event_kernels_compiled(net, xs) -> dict:
    """The event backend resolves to the Pallas kernels compiled for the
    device, not to the host gather path or interpret mode: lower the first
    layer's ``event_matmul_pair`` at its real shape, as the backend calls
    it, and look for the Mosaic custom call."""
    ev = get_compute("event")
    mode = ev._kernel_mode()
    if mode != "pallas":
        raise AssertionError(f"event backend resolved to {mode!r}, "
                             "not the Pallas kernels")
    w = net.layers[0].weights
    f32 = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    occ = jax.ShapeDtypeStruct((-(-w.shape[0] // ev.bk),
                                -(-w.shape[1] // ev.bn)), jnp.bool_)
    text = event_matmul_pair.lower(
        f32(xs.shape), f32(xs.shape), f32(w.shape), f32(w.shape), occ,
        threshold=ev.threshold, bm=ev.bm, bk=ev.bk, bn=ev.bn).as_text()
    if "tpu_custom_call" not in text:
        raise AssertionError("event_matmul_pair lowered without a Mosaic "
                             "kernel: it runs in interpret mode")
    return dict(event_mode=mode)


# ------------------------------------------------------------------ phases

def phase_device() -> dict:
    """(a) The default device runs a jitted program."""
    y = jax.jit(lambda x: x * 2.0 + 1.0)(jnp.arange(8, dtype=jnp.float32))
    if not np.array_equal(np.asarray(y), np.arange(8) * 2.0 + 1.0):
        raise AssertionError(f"device returned {y}")
    return dict(platform=y.devices().pop().platform)


def phase_functional(net, xs, prof) -> dict:
    """(b) Dense and event functional runs, then the step-major reference
    engine: integer counters and every counter-derived report field must
    be identical, and the outputs agree to f32 roundoff."""
    runs = {c: net.run_batch(xs, compute=c) for c in ("dense", "event")}
    for i, (a, b) in enumerate(zip(runs["dense"][1], runs["event"][1])):
        for f in COUNTERS:
            if not np.array_equal(getattr(a, f), getattr(b, f)):
                raise AssertionError(f"layer {i}: event {f} != dense {f}")
    reports = {c: simulate(net, xs, prof, compute=c, precomputed=runs[c])
               for c in runs}
    reports["reference"] = simulate(net, xs, prof, engine="reference",
                                    compute="dense")
    for c in ("event", "reference"):
        bad = _report_mismatches(reports[c], reports["dense"])
        if bad:
            raise AssertionError(f"{c} report differs from dense in {bad}")
    out_d, out_e = runs["dense"][0], runs["event"][0]
    out_err = float(np.max(np.abs(out_e - out_d))
                    / max(float(np.max(np.abs(out_d))), 1e-30))
    if not out_err <= OUTPUT_RTOL:
        raise AssertionError(f"event outputs off dense by {out_err!r} "
                             f"relative > {OUTPUT_RTOL!r}")
    dense = reports["dense"]
    return dict(time_per_step=dense.time_per_step,
                msgs_per_step=dense.metrics.msgs_total,
                output_rel_err=out_err)


def phase_sigma_delta(net, xs, prof, *, window: int = 32) -> dict:
    """(c) Dense, event-cumsum and event-window runs of a sigma-delta net;
    the relative differences of time per step and messages against dense
    are reported, not asserted."""
    computes = dict(dense="dense",
                    event_cumsum=EventCompute(delta_mode="cumsum"),
                    event_window=EventCompute(delta_window=window))
    reps = {k: simulate(net, xs, prof, compute=c)
            for k, c in computes.items()}
    base = reps["dense"]
    info = dict(time_per_step=base.time_per_step,
                msgs_per_step=base.metrics.msgs_total)
    for k in ("event_cumsum", "event_window"):
        r = reps[k]
        if not np.isfinite(r.time_per_step):
            raise AssertionError(f"{k}: time_per_step {r.time_per_step}")
        info[f"{k}_time_rel"] = r.time_per_step / base.time_per_step - 1.0
        info[f"{k}_msgs_rel"] = (r.metrics.msgs_total
                                 / base.metrics.msgs_total - 1.0)
    return info


def phase_pricing(net, xs, prof, cache, *, population=POPULATION, seed=0,
                  rtol=F64_RTOL) -> dict:
    """(d) Device population pricing against the NumPy reference."""
    cands = [decode(c) for c in seeded_population(
        net, prof, size=population, rng=np.random.default_rng(seed))]
    dev = SimEvaluator(net, xs, prof, cache=cache,
                       population_backend="device")
    got = dev.evaluate_population(cands)
    want = SimEvaluator(net, xs, prof, cache=cache,
                        population_backend="numpy").evaluate_population(cands)
    if dev.demotions or dev.active_backend != "device":
        raise AssertionError(f"pricing demoted: {dev.demotions}")
    err = _reports_rel_err(got, want)
    if not err <= rtol:
        raise AssertionError(f"device pricing rel err {err!r} > {rtol!r}")
    return dict(candidates=len(cands), max_rel_err=err)


def phase_search(net, xs, prof, cache, *, population=POPULATION,
                 generations=GENERATIONS, seed=0, rtol=F64_RTOL) -> dict:
    """(e) The jitted device search against its host NumPy mirror."""
    kw = dict(population_size=population, generations=generations, seed=seed)
    res = evolutionary_search(net, prof, SimEvaluator(net, xs, prof,
                                                      cache=cache),
                              engine="device", **kw)
    ref = evolutionary_search_device(
        net, prof, SimEvaluator(net, xs, prof, cache=cache),
        reference=True, **kw)
    _no_demotions(res)
    err = _trajectory_rel_err(res, ref)
    if not err <= rtol:
        raise AssertionError(f"device search vs mirror rel err {err!r} > "
                             f"{rtol!r}")
    return dict(generations=len(res.history) - 1,
                seed_best_time=res.seed_best_time,
                best_time=res.history[-1].best_time, max_rel_err=err)


def phase_sharded(net, xs, prof, cache, *, n_islands=ISLANDS,
                  population=POPULATION, generations=GENERATIONS, seed=0,
                  migrate_every=2, rtol=F64_RTOL) -> dict:
    """Sharded island search against its host mirror, with the device
    engine at the same total population beside it.  The island mesh must
    span ``n_islands`` distinct devices and the population's sharding must
    put one island on each of them."""
    mesh = island_mesh(n_islands)
    devs = list(mesh.devices.flat)
    if len({d.id for d in devs}) != n_islands:
        raise AssertionError(f"island mesh devices {devs}")
    kw = dict(population_size=population, generations=generations, seed=seed)
    isl = dict(n_islands=n_islands, migrate_every=migrate_every)
    ev = lambda: SimEvaluator(net, xs, prof, cache=cache)
    wall = {}
    t0 = time.perf_counter()
    sh = evolutionary_search(net, prof, ev(), engine="sharded", **isl, **kw)
    wall["sharded"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    mirror = evolutionary_search_sharded(net, prof, ev(), reference=True,
                                         **isl, **kw)
    wall["mirror"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    dev = evolutionary_search(net, prof, ev(), engine="device", **kw)
    wall["device"] = time.perf_counter() - t0
    _no_demotions(sh, dev)
    err = _trajectory_rel_err(sh, mirror)
    if not err <= rtol:
        raise AssertionError(f"sharded search vs mirror rel err {err!r} > "
                             f"{rtol!r}")
    # the population as the sharded engine holds it: one island per device
    local_pop = population // n_islands
    eng = _sharded_engine_for(net, prof, cache, move_tables(net, prof),
                              mesh=mesh, local_pop=local_pop,
                              n_migrants=max(1, local_pop // 8),
                              explore_prob=0.25, tournament_k=3)
    pop = Population.from_candidates(seeded_population(
        net, prof, size=population, rng=np.random.default_rng(seed)))
    state, _ = eng.init(pop.cores, pop.perm)
    shards = state["cores"].addressable_shards
    if ({s.device.id for s in shards} != {d.id for d in devs}
            or sorted(s.data.shape[0] for s in shards)
            != [local_pop] * n_islands):
        raise AssertionError(
            "population sharding does not put one island per device: "
            f"{[(s.device, s.data.shape) for s in shards]}")
    return dict(islands=n_islands,
                platforms=",".join(sorted({d.platform for d in devs})),
                max_rel_err=err, sharded_best_time=sh.history[-1].best_time,
                device_best_time=dev.history[-1].best_time,
                sharded_wall_s=wall["sharded"], device_wall_s=wall["device"],
                mirror_wall_s=wall["mirror"])


# --------------------------------------------------------------------- main

class _CompileClock:
    """Sums JAX's compile-event durations: backend compile, and tracing
    plus lowering, separately."""

    BACKEND = "/jax/core/compile/backend_compile_duration"
    FRONT = ("/jax/core/compile/jaxpr_trace_duration",
             "/jax/core/compile/jaxpr_to_mlir_module_duration")

    def __init__(self):
        self.backend = 0.0
        self.front = 0.0

    def __call__(self, event: str, duration: float, **_kw) -> None:
        if event == self.BACKEND:
            self.backend += duration
        elif event in self.FRONT:
            self.front += duration


def _run_phase(clock: _CompileClock, failed: list, name: str, fn, *args,
               **kw) -> None:
    """Run one phase, print its line, and record a failure by name."""
    b0, f0, t0 = clock.backend, clock.front, time.perf_counter()
    try:
        info, status = fn(*args, **kw), "ok"
    except Exception:                   # noqa: BLE001 - report, run the rest
        traceback.print_exc()
        info, status = {}, "FAILED"
        failed.append(name)
    wall = time.perf_counter() - t0
    fields = "".join(f" {k}={v!r}" for k, v in info.items())
    print(f"phase {name}: {status} wall_s={wall!r} "
          f"compile_s={clock.backend - b0!r} "
          f"trace_lower_s={clock.front - f0!r}{fields}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded island search")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    device = dict(platform=devices[0].platform,
                  kind=devices[0].device_kind, count=len(devices))
    print(f"device: platform={device['platform']} kind={device['kind']} "
          f"count={device['count']}", flush=True)
    if device["platform"] != "tpu":
        print("chip_smoke: JAX found no TPU", file=sys.stderr)
        return 1
    if device["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{device['count']} devices", file=sys.stderr)
        return 1
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    clock = _CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    failed: list[str] = []
    run = functools.partial(_run_phase, clock, failed)

    t0 = time.perf_counter()
    net, xs, prof = s5_workload(seed=args.seed)
    cache = precompute_pricing(net, xs, prof)
    print(f"setup: s5 sizes={S5_SIZES} "
          f"synapses={sum(layer.w_nnz for layer in net.layers)} "
          f"steps={STEPS} density={DENSITY} "
          f"wall_s={time.perf_counter() - t0!r}",
          flush=True)
    if args.chips == 4:
        run("sharded", phase_sharded, net, xs, prof, cache, seed=args.seed)
    else:
        run("a_device", phase_device)
        run("b_functional", lambda: {**check_event_kernels_compiled(net, xs),
                                     **phase_functional(net, xs, prof)})
        run("b_functional_relu", phase_functional,
            *s5_workload(seed=args.seed, neuron_model="relu"))
        run("c_sigma_delta", phase_sigma_delta,
            *sigma_delta_workload(seed=args.seed))
        run("d_pricing", phase_pricing, net, xs, prof, cache, seed=args.seed)
        run("e_search", phase_search, net, xs, prof, cache, seed=args.seed)
    if failed:
        print(f"chip_smoke: failed phases {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
