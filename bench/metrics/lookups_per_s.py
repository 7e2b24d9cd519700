"""lookups_per_s: correct lookups completed per second of the window.

The span runs from the first batch that lands after the window opens to
the last one that lands before it closes, so neither edge cuts a batch in
part and a stall inside counts in full. The lookups of the first batch
mark the start and are not counted; those of every later batch up to the
last are (host clock)."""

import math


def read(run):
    lo, hi = run["t_open"], run["t_close"]
    landed = {}
    for lk, ok in zip(run["lookups"], run["ok"]):
        if not math.isnan(lk.t_done) and lo <= lk.t_done <= hi:
            t, n = landed.get(lk.tag, (lk.t_done, 0))
            landed[lk.tag] = (min(t, lk.t_done), n + ok)
    if len(landed) < 2:
        return None
    batches = sorted(landed.values())
    span = batches[-1][0] - batches[0][0]
    return sum(n for _, n in batches[1:]) / span if span > 0 else None
