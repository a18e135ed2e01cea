"""Dense-equivalent operations of the value contractions of the requests
completed in the window (``bench/flops.py``), over the window, over the
bf16 peak of the cell's chips."""

from bench import flops


def read(run):
    done = run.done
    if not done:
        return None
    ops = sum(flops.request_flops(run.state["layers"], r.work["steps"])
              for r in done)
    peak = flops.peak(run.device["kind"]) * run.cell.chips
    return ops / run.window_s / peak
