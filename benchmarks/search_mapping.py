"""Greedy §VI-B walk vs evolutionary mapping search, head to head.

Both optimizers price candidates through one :class:`SimEvaluator` kind
(same pricing cache, same evaluation counting), so the comparison is
iso-evaluation: with a total budget of B candidate pricings, the greedy
walk converges after its own ``greedy_evals`` (it cannot spend more — that
is its failure mode), while the evolutionary pipeline spends the same
``greedy_evals`` producing its floorline-informed seeds and the remaining
``B - greedy_evals`` on population generations.  A cold-start evolutionary
run (no greedy seeds) gets the full budget B for reference.

Writes ``BENCH_search.json`` at the repo root: best time/energy per
optimizer at iso-evaluations, the evolutionary front's knee point, plus two
throughput microbenchmarks:

* ``pricing`` — evals/s of the two population-pricing backends
  (``numpy`` / ``device``) repricing one fixed population;
* ``generation`` — FULL-generation throughput of the two search engines
  at population >= 256: the host loop pricing through numpy, and the
  device-resident engine whose whole generation step is one jitted program
  (``repro.core.search``, ``engine="device"``).  The headline number is
  ``device_speedup_vs_numpy``.

Plus the multi-device section (``sharded``): the island-model
``engine="sharded"`` vs the single-device engine at EQUAL total
population, across every visible device.  On CPU, run with ``--devices N``
(applied before jax initializes — see ``repro.launch.mesh``) to shard over
``N`` forced host devices; the headline is ``sharded_speedup_vs_device``.

Sections merge-update ``BENCH_search.json`` (other sections survive).
"""

from __future__ import annotations

import os
import sys
import time

if __name__ == "__main__":
    # --devices must rewrite XLA_FLAGS before anything imports jax; the
    # argparse pass below keeps the flag for --help and validation
    from repro.launch.mesh import apply_devices_flag
    apply_devices_flag(sys.argv[1:])

from benchmarks import workloads as W
from benchmarks._bench_io import merge_write_json
from repro.core.partitioner import SimEvaluator, optimize_partitioning
from repro.core.search import decode, evolutionary_search, seeded_population
from repro.neuromorphic.noc import ordered_mapping
from repro.neuromorphic.partition import minimal_partition
from repro.neuromorphic.timestep import precompute_pricing, simulate_population

BENCH_PATH = "BENCH_search.json"


def _pricing_throughput(net, xs, prof, *, pop: int, repeats: int,
                        seed: int = 0) -> dict:
    """evals/s of the two population-pricing backends on one fixed
    population (>= 64 candidates unless the workload cannot seed that many),
    measured over ``repeats`` full repricings from a warm cache."""
    import numpy as np
    cache = precompute_pricing(net, xs, prof)
    rng = np.random.default_rng(seed)
    pairs = [decode(c) for c in seeded_population(net, prof, size=pop,
                                                  rng=rng)]
    out = {"pop_size": len(pairs)}
    backends = ("numpy", "device")
    # warm every path (device: jit compile; numpy: routing caches)
    for backend in backends:
        simulate_population(net, xs, prof, pairs, cache=cache,
                            backend=backend)
    for backend in backends:
        t0 = time.perf_counter()
        for _ in range(repeats):
            simulate_population(net, xs, prof, pairs, cache=cache,
                                backend=backend)
        dt = time.perf_counter() - t0
        out[f"{backend}_evals_per_sec"] = repeats * len(pairs) / max(dt, 1e-9)
    out["device_speedup"] = (out["device_evals_per_sec"]
                             / out["numpy_evals_per_sec"])
    return out


def _generation_throughput(net, xs, prof, *, pop: int, gens: int,
                           seed: int = 0,
                           device_pops: tuple = ()) -> dict:
    """Full-generation throughput of the two search engines on one seeded
    population: numpy engine + numpy pricing, and the device-resident
    engine.  Each arm runs once to warm jit/routing caches, then is timed
    over a ``gens``-
    generation search; throughput counts generations (and offspring
    pricings) per second.

    ``device_pops`` adds device-engine-only points at larger populations
    (the host engines would dominate the wall clock there) — the scaling
    regime the rank-capped Pareto peeling and the batched archive update
    unlock; recorded as ``device_pop{K}_gens_per_sec``."""
    import numpy as np
    shared = SimEvaluator(net, xs, prof)
    rng = np.random.default_rng(seed)
    seeds = seeded_population(net, prof, size=pop, rng=rng)
    out = {"pop_size": len(seeds), "generations": gens}
    arms = (("numpy", "numpy", "numpy"),
            ("device", "device", "device"))
    for name, engine, backend in arms:
        def run_once(n_gens):
            ev = SimEvaluator(net, xs, prof, cache=shared.cache,
                              population_backend=backend)
            return evolutionary_search(
                net, prof, ev, population_size=len(seeds), generations=n_gens,
                seed=seed, seed_candidates=list(seeds), engine=engine)
        run_once(1)                       # warm jit / routing caches
        t0 = time.perf_counter()
        res = run_once(gens)
        dt = time.perf_counter() - t0
        out[f"{name}_gens_per_sec"] = gens / max(dt, 1e-9)
        out[f"{name}_evals_per_sec"] = res.n_evals / max(dt, 1e-9)
        out[f"{name}_best_time"] = res.report.time_per_step
    out["device_speedup_vs_numpy"] = (out["device_gens_per_sec"]
                                      / out["numpy_gens_per_sec"])
    for big in device_pops:
        big_seeds = seeded_population(net, prof, size=big,
                                      rng=np.random.default_rng(seed + 1))
        def run_big(n_gens):
            ev = SimEvaluator(net, xs, prof, cache=shared.cache,
                              population_backend="device")
            return evolutionary_search(
                net, prof, ev, population_size=len(big_seeds),
                generations=n_gens, seed=seed,
                seed_candidates=list(big_seeds), engine="device")
        run_big(1)                        # warm jit at this population
        t0 = time.perf_counter()
        res = run_big(gens)
        dt = time.perf_counter() - t0
        out[f"device_pop{big}_size"] = len(big_seeds)
        out[f"device_pop{big}_gens_per_sec"] = gens / max(dt, 1e-9)
        out[f"device_pop{big}_evals_per_sec"] = res.n_evals / max(dt, 1e-9)
    return out


def _sharded_throughput(net, xs, prof, *, pop: int, gens: int,
                        seed: int = 0) -> dict:
    """Equal-total-population head-to-head of the single-device engine vs
    the island-model sharded engine over every visible device.  Both arms
    run the same jitted generation step; the sharded arm splits the
    population into one island per device (``migrate_every=2`` so the run
    exercises the ring collective), warms its compile, then is timed over
    ``gens`` generations."""
    import numpy as np
    import jax
    n_dev = len(jax.devices())
    shared = SimEvaluator(net, xs, prof)
    seeds = seeded_population(net, prof, size=pop,
                              rng=np.random.default_rng(seed))
    seeds = seeds[:len(seeds) - len(seeds) % n_dev]     # equal islands
    out = {"pop_size": len(seeds), "generations": gens, "n_devices": n_dev}
    arms = (("device", "device", {}),
            ("sharded", "sharded", dict(n_islands=n_dev, migrate_every=2)))
    for name, engine, kw in arms:
        def run_once(n_gens, _engine=engine, _kw=kw):
            ev = SimEvaluator(net, xs, prof, cache=shared.cache,
                              population_backend="device")
            return evolutionary_search(
                net, prof, ev, population_size=len(seeds),
                generations=n_gens, seed=seed, seed_candidates=list(seeds),
                engine=_engine, **_kw)
        run_once(1)                       # warm jit at this population
        t0 = time.perf_counter()
        res = run_once(gens)
        dt = time.perf_counter() - t0
        out[f"{name}_gens_per_sec"] = gens / max(dt, 1e-9)
        out[f"{name}_evals_per_sec"] = res.n_evals / max(dt, 1e-9)
        out[f"{name}_best_time"] = res.report.time_per_step
    out["sharded_speedup_vs_device"] = (out["sharded_gens_per_sec"]
                                        / out["device_gens_per_sec"])
    return out


def _head_to_head(net, xs, prof, *, population_size: int, generations: int,
                  seed: int = 0, checkpoint_dir: str | None = None,
                  resume: bool = False) -> dict:
    # one pricing cache for every arm; each arm gets its own eval counter
    shared = SimEvaluator(net, xs, prof)

    # paper baseline: minimal partition + ordered mapping
    p0 = minimal_partition(net, prof)
    base = shared(p0, ordered_mapping(p0, prof))

    # greedy §VI-B walk (converges; cannot use more evaluations)
    ev_g = SimEvaluator(net, xs, prof, cache=shared.cache)
    t0 = time.perf_counter()
    greedy = optimize_partitioning(net, prof, ev_g)
    t_greedy = time.perf_counter() - t0
    budget = max(2 * ev_g.n_evals, population_size * (generations + 1))

    # evolutionary pipeline: charged for the greedy evals behind its seeds
    ev_e = SimEvaluator(net, xs, prof, cache=shared.cache)
    t0 = time.perf_counter()
    evo = evolutionary_search(
        net, prof, ev_e, population_size=population_size,
        generations=generations, seed=seed, greedy=greedy,
        max_evaluations=budget - ev_g.n_evals,
        checkpoint_dir=checkpoint_dir, resume=resume)
    t_evo = time.perf_counter() - t0

    # cold start (no greedy seeds), full budget, for reference
    ev_c = SimEvaluator(net, xs, prof, cache=shared.cache)
    t0 = time.perf_counter()
    cold = evolutionary_search(
        net, prof, ev_c, population_size=population_size,
        generations=generations, seed=seed, max_evaluations=budget)
    t_cold = time.perf_counter() - t0

    knee = evo.knee()
    return {
        "budget_evals": budget,
        "front_size": len(evo.front),
        "knee_time": knee[1].time_per_step if knee else None,
        "knee_energy": knee[1].energy_per_step if knee else None,
        "baseline_time": base.time_per_step,
        "greedy_time": greedy.report.time_per_step,
        "greedy_energy": greedy.report.energy_per_step,
        "greedy_evals": ev_g.n_evals,
        "greedy_evals_per_sec": ev_g.n_evals / max(t_greedy, 1e-9),
        "evo_time": evo.report.time_per_step,
        "evo_energy": evo.report.energy_per_step,
        "evo_evals": ev_g.n_evals + evo.n_evals,    # pipeline total
        "evo_evals_per_sec": evo.n_evals / max(t_evo, 1e-9),
        "evo_generations": evo.history[-1].generation,
        "cold_time": cold.report.time_per_step,
        "cold_evals": cold.n_evals,
        "cold_evals_per_sec": cold.n_evals / max(t_cold, 1e-9),
        "speedup_vs_greedy": greedy.report.time_per_step /
        evo.report.time_per_step,
        "speedup_vs_baseline": base.time_per_step / evo.report.time_per_step,
        "energy_vs_greedy": greedy.report.energy_per_step /
        evo.report.energy_per_step,
    }


def run(quick: bool = False, *, checkpoint_dir: str | None = None,
        resume: bool = False) -> dict:
    """``checkpoint_dir`` makes the evolutionary arm of each head-to-head
    crash-safe: per-generation snapshots land under
    ``<checkpoint_dir>/<workload>/`` and ``resume=True`` continues a killed
    run from its newest snapshot (bit-identical to the uninterrupted run —
    see docs/robustness.md)."""
    smoke = os.environ.get("REPRO_BENCH_SMOKE") == "1"
    steps = 2 if smoke else (3 if quick else 6)
    pop = 8 if smoke else (12 if quick else 24)
    gens = 2 if smoke else (5 if quick else 12)
    price_reps = 2 if smoke else (5 if quick else 10)
    # the generation head-to-head: the device engine's advantage is the
    # amortized per-offspring host work, so it is measured at a large
    # population (>= 256 outside the CI smoke path); the device-only
    # pop=1024 point probes the rank-capped-peeling scaling regime
    gen_pop = 64 if smoke else 256
    gen_gens = 2 if smoke else 3
    device_pops = () if smoke else (1024,)

    def ckpt_for(workload: str) -> str | None:
        if checkpoint_dir is None:
            return None
        return os.path.join(checkpoint_dir, workload)

    out = {}
    s5, prof = W.s5_sim(weight_density=0.5, seed=0, weight_format="sparse")
    xs = W.sim_inputs(s5, 0.3, steps, seed=2)
    out["s5"] = _head_to_head(s5, xs, prof, population_size=pop,
                              generations=gens, seed=0,
                              checkpoint_dir=ckpt_for("s5"), resume=resume)
    out["s5"]["pricing"] = _pricing_throughput(s5, xs, prof, pop=64,
                                               repeats=price_reps)
    out["s5"]["generation"] = _generation_throughput(s5, xs, prof,
                                                     pop=gen_pop,
                                                     gens=gen_gens,
                                                     device_pops=device_pops)

    pnet, pprof = W.pilotnet_sim(weight_density=0.6, seed=1)
    pxs = W.sim_inputs(pnet, 0.3, max(steps - 1, 2), seed=3)
    out["pilotnet"] = _head_to_head(pnet, pxs, pprof, population_size=pop,
                                    generations=gens, seed=0,
                                    checkpoint_dir=ckpt_for("pilotnet"),
                                    resume=resume)
    out["pilotnet"]["pricing"] = _pricing_throughput(pnet, pxs, pprof,
                                                     pop=64,
                                                     repeats=price_reps)
    out["pilotnet"]["generation"] = _generation_throughput(pnet, pxs, pprof,
                                                           pop=gen_pop,
                                                           gens=gen_gens)

    # island-model scaling at equal TOTAL population (one island per
    # visible device; meaningful speedups need --devices N on CPU)
    sh_pop = 64 if smoke else (512 if quick else 8192)
    sh_gens = 2 if smoke else (2 if quick else 3)
    out["sharded"] = _sharded_throughput(s5, xs, prof, pop=sh_pop,
                                         gens=sh_gens)

    merge_write_json(BENCH_PATH, out)
    return out


def report(res: dict) -> str:
    lines = ["## search_mapping — greedy §VI-B vs evolutionary "
             "(iso-evaluation budget)"]
    for name in ("s5", "pilotnet"):
        r = res[name]
        lines.append(
            f"  {name:8s} B={r['budget_evals']:<4d} "
            f"greedy={r['greedy_time']:8.1f} ({r['greedy_evals']} evals)  "
            f"evo={r['evo_time']:8.1f} ({r['evo_evals']} evals) "
            f"-> {r['speedup_vs_greedy']:.3f}x vs greedy, "
            f"{r['speedup_vs_baseline']:.2f}x vs baseline")
        lines.append(
            f"  {'':8s} pricing rate: greedy "
            f"{r['greedy_evals_per_sec']:7.1f} evals/s, population "
            f"{r['evo_evals_per_sec']:7.1f} evals/s "
            f"(cold-start evo: {r['cold_time']:.1f})")
        if r.get("knee_time") is not None:
            lines.append(
                f"  {'':8s} front: {r['front_size']} pts, knee "
                f"(time={r['knee_time']:.1f}, "
                f"energy={r['knee_energy']:.1f})")
        pr = r.get("pricing")
        if pr:
            lines.append(
                f"  {'':8s} population pricing @ pop={pr['pop_size']}: "
                f"numpy {pr['numpy_evals_per_sec']:8.1f} evals/s, "
                f"device {pr['device_evals_per_sec']:8.1f} evals/s "
                f"-> {pr['device_speedup']:.2f}x")
        ge = r.get("generation")
        if ge:
            lines.append(
                f"  {'':8s} full generations @ pop={ge['pop_size']}: "
                f"numpy {ge['numpy_gens_per_sec']:6.2f} gen/s, "
                f"device {ge['device_gens_per_sec']:6.2f} gen/s "
                f"-> device {ge['device_speedup_vs_numpy']:.2f}x vs numpy")
            for key in ge:
                if key.startswith("device_pop") and key.endswith(
                        "_gens_per_sec"):
                    pop_k = key[len("device_pop"):-len("_gens_per_sec")]
                    lines.append(
                        f"  {'':8s} device engine @ pop={pop_k}: "
                        f"{ge[key]:6.2f} gen/s "
                        f"({ge[f'device_pop{pop_k}_evals_per_sec']:8.1f} "
                        f"evals/s)")
    sh = res.get("sharded")
    if sh:
        lines.append(
            f"  sharded islands @ pop={sh['pop_size']} on "
            f"{sh['n_devices']} device(s): "
            f"device {sh['device_gens_per_sec']:6.2f} gen/s, "
            f"sharded {sh['sharded_gens_per_sec']:6.2f} gen/s "
            f"-> {sh['sharded_speedup_vs_device']:.2f}x")
    lines.append(f"  wrote {BENCH_PATH}")
    return "\n".join(lines)


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        description="greedy vs evolutionary mapping-search head-to-head")
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="extra-small sizes for CI (implies --quick)")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="snapshot the evolutionary arms per generation "
                         "under <dir>/<workload>/ (crash-safe)")
    ap.add_argument("--resume", action="store_true",
                    help="continue each evolutionary arm from its newest "
                         "snapshot in --checkpoint-dir")
    ap.add_argument("--devices", type=int, default=None,
                    help="force N CPU host devices for the sharded-engine "
                         "section (applied before jax initializes)")
    args = ap.parse_args(argv)
    if args.resume and not args.checkpoint_dir:
        ap.error("--resume requires --checkpoint-dir")
    os.environ["REPRO_BENCH_SMOKE"] = "1" if args.smoke else "0"
    res = run(quick=args.quick or args.smoke,
              checkpoint_dir=args.checkpoint_dir, resume=args.resume)
    print(report(res))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
