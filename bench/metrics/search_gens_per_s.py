"""Generations of every search completed in the window, over the
window."""


def read(run):
    gens = sum(r.work["generations"] for r in run.done)
    return gens / run.window_s if run.done else None
