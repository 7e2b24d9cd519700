"""Readings that set a limit: the program's, or a fault's, over many seeds.

    python3 bench/control.py --workload <name> --seconds <s> \
        --seeds <a,b,c> [--fault <one of faults.FAULTS>]

Needs a TPU. Runs the cell once per seed in one process, exactly as
``run.py`` does, with the answer stage broken as ``faults.py`` says when
``--fault`` is given (``drop_server`` is the control: a lookup rebuilt
from d - 1 of the configuration's d servers). Prints, per seed, every
number compared (``checks``) and how many lookups it covered. The
benchmark's own runs never call it.
"""

from __future__ import annotations

import time

import argparse
import json
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--fault", default=None)
    args = ap.parse_args()
    sys.path.insert(0, str(HERE))
    import harness

    try:
        compiles, _, _ = harness.start(1)
    except harness.NoChip as e:
        print(f"control.py: {e}", file=sys.stderr)
        return 3
    with open(ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        out = harness.run_cell(
            bench, args.workload, seed=seed, seconds=args.seconds,
            trace=False, t_start=t0, compiles=compiles, fault=args.fault,
            log=lambda msg: None,
        )
        print("reading " + json.dumps({
            "workload": args.workload, "fault": args.fault, "seed": seed,
            "checks": {k: c["value"] for k, c in out["checks"].items()},
            "attempted": out["attempted"], "correct": out["correct"],
            "metrics": {k: v["value"] for k, v in out["metrics"].items()},
        }), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
