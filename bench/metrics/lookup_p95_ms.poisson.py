"""lookup_p95_ms.poisson: the 95th percentile of the latency of every
lookup scheduled in the window, from its scheduled arrival to its answer,
in ms (host clock), in the open-loop cells. A lookup that never came back
counts as infinitely late. A window of the poisson mix holds too few
lookups for this tail to be steady from run to run, so it is read beside
the median, which is the end-to-end metric there."""

import math

import numpy as np


def read(run):
    lat = [(lk.t_done - lk.t_sched) * 1e3 if not math.isnan(lk.t_done)
           else math.inf for lk in run["lookups"]]
    return float(np.percentile(lat, 95)) if lat else None
