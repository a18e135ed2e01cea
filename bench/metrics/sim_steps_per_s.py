"""Simulated timesteps of every ``simulate`` request completed in the
window, over the window."""


def read(run):
    steps = sum(r.work["steps"] for r in run.done)
    return steps / run.window_s if run.done else None
