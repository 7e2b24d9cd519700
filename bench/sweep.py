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

The rule: a rate holds when its backlog grows over the window by less
than :func:`growth_limit` of the lookups the window sent, the larger of
``GROWTH_FLOOR`` lookups and ``GROWTH_SHARE`` of them; the knee is the
highest rate that holds with every lower rate tried holding too. The
last line gives the knee and four fifths of it, the rate an open-loop
cell below the knee is run at. Used once, to fix the rate the cell's mix
file holds; the benchmark's runs never call it.
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

import numpy as np  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


#: seconds between two readings of the backlog
SAMPLE_S = 0.5

#: the least backlog growth over a window, in lookups, at which a rate no
#: longer holds: at a few lookups a second, rates well below the knee read
#: within one lookup of 0, and a queue that grows by a few lookups through
#: the window is not sustained
GROWTH_FLOOR = 2.0
#: above the floor, the limit as a share of the lookups the window sent.
#: The fitted growth of a queue that holds is noise on the scale of the
#: backlog's swing, one batch: a batch of ``rate x period`` lookups, cut
#: every ``period`` seconds. Fitted from ``seconds / SAMPLE_S`` readings,
#: its sd is about ``0.29 x batch x sqrt(12 SAMPLE_S / seconds)`` lookups:
#: 0.08% of what was sent at a 0.3 s period over 40 s, so 1% stands over
#: ten sds off. A queue that outgrows 1% of its load completes less than
#: 99% of what it is offered: the knee so found lies within about 1% of
#: the rate the system completes. Below 200 lookups a window the floor
#: rules, as at ``ct_chor.poisson``'s sweep.
GROWTH_SHARE = 0.01


def growth_limit(sent: int) -> float:
    """The backlog growth, in lookups, at which a window that sent
    ``sent`` lookups no longer holds."""
    return max(GROWTH_FLOOR, GROWTH_SHARE * sent)


def backlog(lookups, t):
    """Lookups sent and not yet answered at each time of ``t``."""
    sent = np.sort([lk.t_submit for lk in lookups])
    done = np.sort([lk.t_done for lk in lookups if not math.isnan(lk.t_done)])
    return (np.searchsorted(sent, t, side="right")
            - np.searchsorted(done, t, side="right"))


def growth(lookups, t_open: float, seconds: float) -> float:
    """How much the backlog grows over the window, by a straight line
    fitted to it every ``SAMPLE_S`` seconds."""
    t = t_open + SAMPLE_S * np.arange(1, int(seconds / SAMPLE_S) + 1)
    y = backlog(lookups, t)
    tc = t - t.mean()
    return float(np.dot(tc, y - y.mean()) / np.dot(tc, tc) * seconds)


def knee(rows) -> float:
    """The highest rate that holds, every lower rate holding too."""
    best = None
    for row in sorted(rows, key=lambda r: r["rate_qps"]):
        if row["backlog_growth"] >= growth_limit(row["sent"]):
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
        quarters = backlog(drv.lookups, drv.t_open + np.arange(1, 5)
                           * args.seconds / 4).tolist()
        c0, c1 = win["counters_open"], win["counters_close"]
        row = {
            "rate_qps": rate,
            "sent": len(drv.lookups),
            "completed_per_s": done_in / args.seconds,
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": float(np.percentile(lat, 95)),
            "backlog_quarters": quarters,
            "backlog_growth": growth(drv.lookups, drv.t_open, args.seconds),
            "growth_limit": growth_limit(len(drv.lookups)),
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
