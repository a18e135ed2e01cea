"""95th percentile of the latency of every ``simulate`` request in the
window, in milliseconds."""

import numpy as np


def read(run):
    lat = [r.end - r.start for r in run.requests]
    return float(np.percentile(lat, 95)) * 1e3 if lat else None
