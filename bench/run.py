"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Needs the TPU chips the cell asks for, and exits with code 3 and no result
where JAX finds fewer or another platform. Earlier lines on standard
output give the set-up, the plans the answer stage executed, what JAX
compiled inside the window, by name (it should be nothing), the
generator's lateness, the program's counters (its wait counters among
them), the batches and padding, and the device's peak bytes. The last
line is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer ones), ``device`` and ``checks``, the number compared with its
limit, which the last line on standard error repeats.

JAX's persistent compilation cache lives in ``.jax_cache/`` at the root
of the checkout, whatever the environment says, so only a checkout's
first run of a cell compiles (``harness.start``).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    with open(ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    chips = {w["name"]: int(w["chips"]) for w in bench["workloads"]}
    if args.workload not in chips:
        print(f"run.py: no workload {args.workload!r}", file=sys.stderr)
        return 2

    sys.path.insert(0, str(HERE))
    import harness

    try:
        compiles, cache, devices = harness.start(chips[args.workload])
    except harness.NoChip as e:
        print(f"run.py: {args.workload} {e}", file=sys.stderr)
        return 3
    print(f"compile cache: {cache}", flush=True)
    print(f"device: {devices[0].device_kind} x{len(devices)}", flush=True)
    result = harness.run_cell(
        bench, args.workload, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), t_start=T_START, compiles=compiles,
        log=lambda msg: print(msg, flush=True),
    )
    print(json.dumps(result), flush=True)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except Exception:
        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    # a frontend worker stuck on the device must not hold the exit
    os._exit(code)
