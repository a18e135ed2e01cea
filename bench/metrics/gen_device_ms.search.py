"""Device busy time of the traced window per search generation, in
milliseconds (busy time averaged over the cell's devices)."""


def read(run):
    gens = sum(r.work["generations"] for r in run.done)
    t = run.trace
    if t is None or t["n_devices"] == 0 or gens == 0:
        return None
    return 1e3 * t["busy_s"] / gens
