"""Find an open-loop cell's knee: one set-up, one window per offered rate.

    python3 bench/sweep.py --workload ct_chor.poisson --seed <n> \
        --seconds 40 --rates 2,2.5,3,3.5,4

Needs a TPU. The cell's own configuration and mix are used with only the
Poisson rate changed; the rates run in the order given, each after the
last one's lookups have all come back. For each rate it prints the
offered and completed rates, p50/p95 latency from scheduled arrival, the
backlog (lookups sent and not yet answered) at each quarter of the window,
and the backlog's growth over the window: the least-squares slope of the
backlog, sampled every half second, times the window's length.

The rule: a rate holds when its backlog grows by fewer than
``GROWTH_LIMIT`` lookups over the window; the knee is the highest rate
that holds with every lower rate tried holding too. The last line gives the knee and four fifths of
it, the rate an open-loop cell below the knee is run at. Used once, to
fix the rate the cell's mix file holds; the benchmark's runs never call it.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


#: seconds between two readings of the backlog
SAMPLE_S = 0.5

#: backlog growth over a window, in lookups, at which a rate no longer
#: holds: rates well below the knee read within one lookup of 0, and a
#: queue that grows by a few lookups through the window is not sustained
GROWTH_LIMIT = 2.0


def backlog(lookups, t) -> int:
    return sum(lk.t_submit <= t for lk in lookups) - sum(
        (not math.isnan(lk.t_done)) and lk.t_done <= t for lk in lookups)


def growth(lookups, t_open: float, seconds: float) -> float:
    """How much the backlog grows over the window, by a straight line
    fitted to it every ``SAMPLE_S`` seconds."""
    t = [t_open + SAMPLE_S * (i + 1) for i in range(int(seconds / SAMPLE_S))]
    y = [backlog(lookups, x) for x in t]
    tm, ym = sum(t) / len(t), sum(y) / len(y)
    slope = sum((a - tm) * (b - ym) for a, b in zip(t, y)) / sum(
        (a - tm) ** 2 for a in t)
    return slope * seconds


def knee(rows) -> float:
    """The highest rate that holds, every lower rate holding too."""
    best = None
    for row in sorted(rows, key=lambda r: r["rate_qps"]):
        if row["backlog_growth"] >= GROWTH_LIMIT:
            break
        best = row["rate_qps"]
    return best


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    sys.path.insert(0, str(HERE))
    import harness

    try:
        compiles, _, _ = harness.start(1)
    except harness.NoChip as e:
        print(f"sweep.py: {e}", file=sys.stderr)
        return 3
    import numpy as np

    with open(ROOT / "BENCHMARK.json") as f:
        spec = harness.cell_spec(json.load(f), args.workload)
    sess = harness.Session(spec, seed=args.seed, traced=False)
    sess.warm(spec["mix"])
    print(f"setup_s {time.perf_counter() - T_START}", flush=True)
    rows = []
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        mix = copy.deepcopy(spec["mix"])
        mix["arrivals"]["rate_qps"] = rate
        win = sess.window(mix, args.seconds, args.seed + k, compiles)
        drv = win["driver"]
        lat = [(lk.t_done - lk.t_sched) * 1e3 for lk in drv.lookups]
        done_in = sum(drv.t_open <= lk.t_done <= drv.t_close
                      for lk in drv.lookups)
        quarters = [backlog(drv.lookups, drv.t_open + q * args.seconds / 4)
                    for q in (1, 2, 3, 4)]
        c0, c1 = win["counters_open"], win["counters_close"]
        row = {
            "rate_qps": rate,
            "sent": len(drv.lookups),
            "completed_per_s": done_in / args.seconds,
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": float(np.percentile(lat, 95)),
            "backlog_quarters": quarters,
            "backlog_growth": growth(drv.lookups, drv.t_open, args.seconds),
            "batches": c1["batches"] - c0["batches"],
            "compiles": win["compiles"],
            "unanswered_at_drain": win["unanswered_at_drain"],
        }
        print("sweep " + json.dumps(row), flush=True)
        rows.append(row)
    sess.close()
    top = knee(rows)
    print("knee " + json.dumps({
        "batch": sess.cap, "knee_qps": top,
        "cell_rate_qps": None if top is None else round(0.8 * top, 2)}),
        flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
