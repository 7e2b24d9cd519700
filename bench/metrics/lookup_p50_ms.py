"""lookup_p50_ms: the median latency of every lookup scheduled in the
window, from its scheduled arrival to its answer, in ms (host clock). A
lookup that never came back counts as infinitely late."""

import math

import numpy as np


def read(run):
    lat = [(lk.t_done - lk.t_sched) * 1e3 if not math.isnan(lk.t_done)
           else math.inf for lk in run["lookups"]]
    return float(np.percentile(lat, 50)) if lat else None
