"""Simulator-engine microbenchmark: step-major reference vs layer-major
batched execution, plus dense vs event-driven compute backends, on fixed
fc and conv workloads.

Writes ``BENCH_sim.json`` at the repo root with two sections:

* engine rows (``fc`` / ``conv``) — steps/sec per engine + speedup.  The
  fc workload is the acceptance gate for the layer-major engine (>= 10x
  steps/sec); the equivalence suite (``tests/test_sim_equivalence.py``)
  proves the two engines agree exactly, so the speedup is free.
* ``compute`` — dense vs event :class:`~repro.neuromorphic.compute.
  LayerCompute` backends over a 2-D ``(act_density, weight_density)`` grid
  on characterization-mode fc and conv workloads (§V-A message gates; the
  conv workload programs *channel-structured* activity, the granularity
  event execution exploits on convs).  Weight sparsity is *structured* —
  whole (128, 128) weight tiles dead on fc, whole input channels dead on
  conv — because that is what the block-CSR skip machinery converts into
  skipped fetches (the paper's CNN weight-format finding; unstructured
  masks leave tile occupancy near 1 and win nothing).  The headline is the
  event backend's advantage growing along BOTH axes — work now scales with
  ``act_density x weight_density`` — while ``tests/test_weight_sparse.py``
  proves both backends price identically.  ``--profile <npz>`` adds rows
  priced under a trained :class:`~repro.sparsity.SparsityProfile` (real
  unstructured masks, honestly recorded next to the synthetic grid), and
  ``sd_window`` rows compare windowed vs dense-cumsum delta reconstruction
  on bursty sigma-delta workloads.

Rerun just the compute sweep (the sections produced are merged into
``BENCH_sim.json`` atomically, leaving the rest in place)::

    PYTHONPATH=src python -m benchmarks.sim_speed --compute [--quick]
    [--profile experiments/profile.npz]
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks import workloads as W
from repro.neuromorphic import (fc_network, loihi2_like, make_inputs,
                                programmed_fc_network)
from repro.neuromorphic.compute import EventCompute
from repro.neuromorphic.network import _exact_density_mask
from repro.neuromorphic.timestep import simulate

BENCH_PATH = "BENCH_sim.json"

#: programmed activation densities of the compute-backend sweep
COMPUTE_DENSITIES = (0.01, 0.05, 0.1, 0.2, 0.5)
#: structured weight densities of the 2-D sweep (1.0 = the old 1-D sweep)
COMPUTE_WEIGHT_DENSITIES = (1.0, 0.5, 0.1)
#: fraction of 32-step windows carrying events in the sd_window sweep
SD_DUTY_FRACTIONS = (0.0625, 0.25, 1.0)


def _time_engine(net, xs, prof, engine: str, repeats: int = 3) -> float:
    """Best-of-N wall-clock for one simulate() call, in seconds."""
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        simulate(net, xs, prof, engine=engine)
        best = min(best, time.perf_counter() - t0)
    return best


def _bench(name: str, net, xs, prof, repeats: int) -> dict:
    simulate(net, xs, prof, engine="batched")      # warm jit/caches
    T = xs.shape[0]
    t_ref = _time_engine(net, xs, prof, "reference", repeats)
    t_bat = _time_engine(net, xs, prof, "batched", repeats)
    row = {
        "workload": name,
        "steps": T,
        "ref_steps_per_sec": T / t_ref,
        "batched_steps_per_sec": T / t_bat,
        "speedup": t_ref / t_bat,
    }
    return row


def _time_run_batch_pair(net, xs, repeats: int) -> tuple[float, float]:
    """Best-of-N wall-clock of the functional layer-major run — the seam
    the compute backends plug into — for the dense and event backends,
    interleaved so host-load drift biases neither arm."""
    best = {"dense": float("inf"), "event": float("inf")}
    for backend in best:
        net.run_batch(xs, compute=backend)       # warm jit / weight caches
    for _ in range(repeats):
        for backend in best:
            t0 = time.perf_counter()
            net.run_batch(xs, compute=backend)
            best[backend] = min(best[backend], time.perf_counter() - t0)
    return best["dense"], best["event"]


def _tile_mask_fc_weights(net, weight_density: float, *, bk: int = 128,
                          bn: int = 128, seed: int = 3) -> None:
    """Kill whole (bk, bn) weight tiles to an exact tile density on every
    fc layer: the structured weight sparsity the block-CSR occupancy map
    converts into skipped DMAs (unstructured masks leave nearly every tile
    occupied — the paper's CNN structure finding)."""
    if weight_density >= 1.0:
        return
    rng = np.random.default_rng(seed)
    for l in net.layers:
        if l.kind != "fc":
            continue
        K, N = l.weights.shape
        kb, nb = -(-K // bk), -(-N // bn)
        tm = _exact_density_mask((kb, nb), weight_density, rng)
        l.weights = l.weights * np.repeat(np.repeat(tm, bk, axis=0), bn,
                                          axis=1)[:K, :N]


def _channel_mask_conv_weights(net, weight_density: float, *,
                               seed: int = 5) -> None:
    """Kill all taps of whole input channels on every conv layer: the
    channel-structured weight sparsity whose dead patch-weight rows the
    gather path's CSR row skipping never fetches."""
    if weight_density >= 1.0:
        return
    rng = np.random.default_rng(seed)
    for l in net.layers:
        if l.kind != "conv":
            continue
        cin = l.weights.shape[2]
        chm = np.zeros(cin, np.float32)
        chm[rng.choice(cin, max(1, round(weight_density * cin)),
                       replace=False)] = 1.0
        l.weights = l.weights * chm[None, None, :, None]


def _compute_fc_workload(density: float, steps: int, quick: bool,
                         weight_density: float = 1.0):
    """Characterization-mode fc stack: per-layer message gates program the
    activation density exactly (paper §V-A); the input layer is kept small
    so the gated layers carry the compute.  ``weight_density`` kills whole
    128x128 weight tiles (structured)."""
    sizes = ([128, 384, 384, 256] if quick
             else [256, 1024, 1024, 1024, 512])
    net = programmed_fc_network(sizes, weight_densities=[1.0] * (len(sizes) - 1),
                                act_densities=[density] * (len(sizes) - 1),
                                seed=0)
    _tile_mask_fc_weights(net, weight_density)
    xs = make_inputs(sizes[0], density, steps, seed=1)
    return net, xs


def _compute_conv_workload(density: float, steps: int, quick: bool,
                           weight_density: float = 1.0):
    """Channel-structured characterization conv: whole feature maps are
    gated on/off (the structure event-driven conv execution exploits —
    quiet channels fetch no weight taps), and the input programs the same
    per-channel activity.  ``weight_density`` kills whole input channels'
    taps (structured weight sparsity)."""
    hw = (16, 16) if quick else (32, 32)
    cin = 4 if quick else 8
    channels = (16, 32) if quick else (32, 64, 64)
    net = W.conv_net(in_hw=hw, cin=cin, channels=channels, fc_out=16,
                     force_active=True, seed=0)
    rng = np.random.default_rng(7)
    for l in net.layers:
        if l.kind != "conv":
            continue
        cout = l.weights.shape[3]
        chm = np.zeros(cout, np.float32)
        chm[rng.choice(cout, max(1, round(density * cout)),
                       replace=False)] = 1.0
        l.msg_gate = np.repeat(chm, l.out_hw[0] * l.out_hw[1])
    _channel_mask_conv_weights(net, weight_density)
    xs = make_inputs(net.in_size, 1.0, steps, seed=1)
    in_chm = np.zeros(cin, np.float32)
    in_chm[rng.choice(cin, max(1, round(density * cin)), replace=False)] = 1.0
    xs = (xs.reshape(steps, cin, -1) * in_chm[None, :, None]).reshape(
        steps, -1)
    return net, xs


def _bench_compute(quick: bool, repeats: int, profile=None) -> dict:
    """Dense vs event backend steps/sec over the 2-D
    (act_density, weight_density) grid, plus trained-profile rows."""
    out = {}
    for name, make, steps in (
            ("fc", _compute_fc_workload, 32 if quick else 128),
            ("conv", _compute_conv_workload, 8 if quick else 32)):
        rows = []
        for d in COMPUTE_DENSITIES:
            for wd in COMPUTE_WEIGHT_DENSITIES:
                net, xs = make(d, steps, quick, wd)
                t_dense, t_event = _time_run_batch_pair(net, xs, repeats)
                rows.append({
                    "act_density": d,
                    "weight_density": wd,
                    "weight_structure": "tile" if name == "fc" else "channel",
                    "steps": steps,
                    "dense_steps_per_sec": steps / t_dense,
                    "event_steps_per_sec": steps / t_event,
                    "event_speedup": t_dense / t_event,
                })
        out[name] = rows
    if profile is not None:
        out["trained_profile"] = _bench_profile_rows(profile, quick, repeats)
    out["sd_window"] = _bench_sd_window(quick, repeats)
    return out


def _bench_profile_rows(profile, quick: bool, repeats: int) -> list[dict]:
    """Rows priced under a trained SparsityProfile artifact: the exact
    masks a sparse-training run produced (typically *unstructured* —
    recorded honestly next to the synthetic structured grid, where the
    tile-skip machinery has little to grab onto)."""
    sizes = [int(profile.weight_masks[0].shape[0])] + [
        int(m.shape[1]) for m in profile.weight_masks] \
        if profile.weight_masks else [128, 384, 256]
    steps = 32 if quick else 128
    net = programmed_fc_network(
        sizes, weight_densities=[1.0] * (len(sizes) - 1),
        act_densities=[float(d) for d in
                       profile.densities_for(len(sizes) - 1)], seed=0)
    net = profile.apply(net, seed=17)
    xs = make_inputs(sizes[0], float(profile.input_density), steps, seed=1)
    t_dense, t_event = _time_run_batch_pair(net, xs, repeats)
    return [{
        "source": "trained_profile",
        "act_density": float(np.mean(profile.act_density)),
        "weight_density": float(np.mean(profile.weight_density)),
        "weight_structure": "unstructured",
        "steps": steps,
        "dense_steps_per_sec": steps / t_dense,
        "event_steps_per_sec": steps / t_event,
        "event_speedup": t_dense / t_event,
    }]


def _bench_sd_window(quick: bool, repeats: int) -> list[dict]:
    """Temporal-tile sigma-delta: windowed delta reconstruction vs the
    dense time-cumsum event path on bursty workloads — inputs carry events
    only in the first ``duty`` fraction of each 128-step burst period, and
    the 32-step reconstruction window divides the period, so low-duty
    workloads have whole windows with zero deltas: exactly what the
    windowed path compacts away (window == period would put the burst in
    every window and skip nothing)."""
    sizes = [128, 384, 384, 256] if quick else [256, 1024, 1024, 512]
    steps = 256 if quick else 512
    period, win = 128, 32
    rows = []
    for duty in SD_DUTY_FRACTIONS:
        net = fc_network(sizes, weight_density=1.0, seed=0,
                         neuron_model="sd_relu")
        for l in net.layers:
            l.threshold = 0.05
            l.sends_deltas = True
        xs = make_inputs(sizes[0], 0.5, steps, seed=1)
        keep = max(1, round(duty * period))
        xs[np.arange(steps) % period >= keep] = 0.0   # bursty: quiet windows
        window = EventCompute(mode="gather", delta_mode="window",
                              delta_window=win)
        cumsum = EventCompute(mode="gather", delta_mode="cumsum")
        best = {"window": float("inf"), "cumsum": float("inf")}
        for cc in (window, cumsum):
            net.run_batch(xs, compute=cc)              # warm caches
        for _ in range(repeats):
            for key, cc in (("window", window), ("cumsum", cumsum)):
                t0 = time.perf_counter()
                net.run_batch(xs, compute=cc)
                best[key] = min(best[key], time.perf_counter() - t0)
        rows.append({
            "duty": duty,
            "steps": steps,
            "window": win,
            "period": period,
            "cumsum_steps_per_sec": steps / best["cumsum"],
            "window_steps_per_sec": steps / best["window"],
            "window_speedup": best["cumsum"] / best["window"],
        })
    return rows


def run(quick: bool = False, *, profile=None, only: str | None = None) -> dict:
    """``only=None`` runs everything; ``only="compute"`` reruns just the
    compute sweep (its sections merge into ``BENCH_sim.json`` atomically,
    leaving the engine rows in place — and vice versa)."""
    steps = 64 if quick else 256
    repeats = 2 if quick else 3

    out = {}
    if only in (None, "engine"):
        fc = fc_network([128, 256, 256, 256, 128, 64], weight_density=0.5,
                        seed=0)
        fc_xs = make_inputs(128, 0.5, steps, seed=1)

        conv, conv_prof = W.akidanet_sim(weight_density=0.6, seed=0)
        conv_xs = W.sim_inputs(conv, 0.5, max(steps // 4, 16), seed=1)
        out["fc"] = _bench("fc", fc, fc_xs, loihi2_like(), repeats)
        out["conv"] = _bench("conv", conv, conv_xs, conv_prof, repeats)
    if only in (None, "compute"):
        # full runs average harder (noisy shared hosts); quick/smoke keeps
        # its reduced repeat count
        out["compute"] = _bench_compute(quick, repeats if quick
                                        else max(repeats, 5),
                                        profile=profile)
    from benchmarks._bench_io import merge_write_json
    merge_write_json(BENCH_PATH, out)
    return out


def report(res: dict) -> str:
    lines = ["## sim_speed — step-major vs layer-major engine"]
    for name in ("fc", "conv"):
        r = res.get(name)
        if r is None:
            continue
        lines.append(
            f"  {name:5s} T={r['steps']:<4d} "
            f"ref={r['ref_steps_per_sec']:8.1f} steps/s  "
            f"batched={r['batched_steps_per_sec']:10.1f} steps/s  "
            f"-> {r['speedup']:.1f}x")
    comp = res.get("compute")
    if comp:
        lines.append("  compute backends — dense vs event "
                     "(act density x structured weight density)")
        for name in ("fc", "conv", "trained_profile"):
            for r in comp.get(name, ()):
                lines.append(
                    f"    {name:15s} d={r['act_density']:<5g} "
                    f"wd={r['weight_density']:<5g} "
                    f"dense={r['dense_steps_per_sec']:9.1f} steps/s  "
                    f"event={r['event_steps_per_sec']:9.1f} steps/s  "
                    f"-> {r['event_speedup']:.2f}x")
        for r in comp.get("sd_window", ()):
            lines.append(
                f"    sd_window duty={r['duty']:<7g} "
                f"cumsum={r['cumsum_steps_per_sec']:9.1f} steps/s  "
                f"window={r['window_steps_per_sec']:9.1f} steps/s  "
                f"-> {r['window_speedup']:.2f}x")
    lines.append(f"  wrote {BENCH_PATH}")
    return "\n".join(lines)


def main(argv=None) -> int:
    import argparse

    from repro.sparsity import SparsityProfile

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--compute", action="store_true",
                    help="rerun only the compute-backend sweep (merged "
                         "into BENCH_sim.json; engine rows untouched)")
    ap.add_argument("--engine", action="store_true",
                    help="rerun only the engine rows")
    ap.add_argument("--profile", default=None, metavar="NPZ",
                    help="price extra compute rows under a saved "
                         "SparsityProfile (an unreadable file is an error)")
    args = ap.parse_args(argv)
    profile = None
    if args.profile:
        try:
            profile = SparsityProfile.load(args.profile)
        except (OSError, KeyError, ValueError) as e:
            ap.error(f"--profile {args.profile} unreadable: {e}")
    only = None
    if args.compute and not args.engine:
        only = "compute"
    elif args.engine and not args.compute:
        only = "engine"
    print(report(run(args.quick, profile=profile, only=only)))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
