"""Runs one experiment per paper table/figure + the
engine/search microbenchmarks.

``python -m benchmarks.run [--quick] [--smoke] [--only NAME] [--engine E]
[--compute C]``

``--quick`` shrinks every experiment; ``--smoke`` (implies ``--quick``)
shrinks the expensive ones further so the WHOLE suite — including the
mapping-search head-to-head — finishes in a couple of minutes, as a CI
smoke path.  ``--engine`` flips ``repro.neuromorphic.timestep.DEFAULT_ENGINE``
and ``--compute`` flips ``repro.neuromorphic.compute.DEFAULT_COMPUTE`` for
every experiment in the process.  ``--devices N`` forces ``N`` CPU host
devices (via ``repro.launch.mesh.force_host_device_count``, applied
before any benchmark module imports jax) so the sharded-search section
exercises a real multi-device mesh on CPU CI.
"""

from __future__ import annotations

import argparse
import json
import os
import time


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="extra-small sizes for CI (implies --quick)")
    ap.add_argument("--only", default=None)
    ap.add_argument("--engine", default=None,
                    choices=("batched", "reference"),
                    help="simulator engine for every experiment "
                         "(default: layer-major batched)")
    ap.add_argument("--compute", default=None,
                    choices=("dense", "event"),
                    help="per-layer synaptic compute backend for every "
                         "experiment (default: dense)")
    ap.add_argument("--arch", default=None,
                    help="registry arch id for the model_zoo experiment "
                         "(default: one smoke arch per family)")
    ap.add_argument("--profile", default=None, metavar="NPZ",
                    help="saved SparsityProfile npz: the sim_speed compute "
                         "sweep adds rows priced under its trained "
                         "densities/masks (an unreadable file is an error)")
    ap.add_argument("--devices", type=int, default=None,
                    help="force N CPU host devices for the sharded-search "
                         "section (must run before jax initializes)")
    args = ap.parse_args(argv)
    if args.smoke:
        args.quick = True
    # authoritative per-invocation: a stale/inherited value must not flip
    # benchmark sizes without the flag
    os.environ["REPRO_BENCH_SMOKE"] = "1" if args.smoke else "0"

    if args.devices is not None:
        # before the repro/benchmark imports below pull in jax
        from repro.launch.mesh import force_host_device_count
        force_host_device_count(args.devices)

    if args.engine:
        from repro.neuromorphic import timestep
        timestep.DEFAULT_ENGINE = args.engine
    if args.compute:
        from repro.neuromorphic import compute
        compute.DEFAULT_COMPUTE = args.compute

    from repro.launch.mesh import enable_compile_cache
    enable_compile_cache()
    profile = None
    if args.profile:
        from repro.sparsity import SparsityProfile
        try:
            profile = SparsityProfile.load(args.profile)
        except (OSError, KeyError, ValueError) as e:
            ap.error(f"--profile {args.profile} unreadable: {e}")

    from benchmarks import (act_schedules, compute_floor, iso_accuracy,
                            max_synops, model_zoo, search_mapping,
                            sim_speed, stage1_sparsity,
                            stage2_partitioning, traffic_mapping,
                            weight_format, weight_sparsity)

    mods = [
        ("sim_speed", sim_speed),
        ("model_zoo", model_zoo),
        ("fig2_3_weight_sparsity", weight_sparsity),
        ("fig4_weight_format", weight_format),
        ("fig5_act_schedules", act_schedules),
        ("fig6_max_synops", max_synops),
        ("fig7_compute_floor", compute_floor),
        ("fig8_traffic_mapping", traffic_mapping),
        ("fig10_11_stage1", stage1_sparsity),
        ("fig12_stage2", stage2_partitioning),
        ("iso_accuracy", iso_accuracy),
        ("search_mapping", search_mapping),
    ]
    results = {}
    stage1_res = None
    for name, mod in mods:
        if args.only and args.only not in name:
            continue
        t0 = time.time()
        if mod is stage2_partitioning:
            res = mod.run(args.quick, stage1=stage1_res)
        elif mod is model_zoo:
            res = mod.run(args.quick, arch=args.arch)
        elif mod is sim_speed:
            res = mod.run(args.quick, profile=profile)
        else:
            res = mod.run(args.quick)
        if mod is stage1_sparsity:
            stage1_res = res
            res = {k: v for k, v in res.items() if not k.startswith("_")}
        dt = time.time() - t0
        print(mod.report(res))
        print(f"   [{name} done in {dt:.1f}s]\n")
        results[name] = res

    if args.only:
        # partial runs refresh their experiments in place instead of
        # truncating everything else previously recorded
        try:
            with open("benchmarks/results.json") as f:
                merged = json.load(f)
        except (OSError, json.JSONDecodeError):
            merged = {}
        merged.update(results)
        results = merged
    with open("benchmarks/results.json", "w") as f:
        json.dump(results, f, indent=1, default=float)
    print("wrote benchmarks/results.json")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
