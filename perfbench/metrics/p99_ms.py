"""99th percentile latency over every request sent in the window, from
its submit to the time the front end's loop handed its answer back."""

import numpy as np


def read(run):
    lat = run.latency_s
    return float(np.percentile(lat, 99)) * 1e3 if lat.size else None
