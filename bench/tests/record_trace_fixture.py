"""Record the small device trace that ``test_trace.py`` reduces.

    python bench/tests/record_trace_fixture.py --out <dir>

Needs a TPU. It runs a few jitted programs inside the spans the harness
writes (``bench.window`` around everything, ``bench.answer_batch`` with a
``least_bytes`` stat, ``bench.query_gen``) and leaves known host gaps
between them: 30 ms of sleep inside ``bench.query_gen`` and 20 ms inside
``bench.answer_batch``. It copies the ``.xplane.pb`` to
``<dir>/fixture.xplane.pb`` (kept as ``data/v5e_fixture.xplane.pb``,
recorded on a TPU v5 lite) and prints the planes, lines and first
events, so the reduction can be checked against a real chip's layout.
"""

from __future__ import annotations

import argparse
import glob
import os
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp
from jax.profiler import ProfileData, TraceAnnotation


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_trace_fixture: needs a TPU")
    os.makedirs(args.out, exist_ok=True)

    big = jax.jit(lambda x: jnp.bitwise_xor(x, x >> 3).sum(axis=0))
    mat = jax.jit(lambda a: (a @ a).astype(jnp.float32).sum())
    x = jnp.arange(64 * 1024 * 1024, dtype=jnp.uint32).reshape(-1, 1024)
    a = jnp.ones((2048, 2048), jnp.bfloat16)
    big(x).block_until_ready()
    mat(a).block_until_ready()

    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with TraceAnnotation("bench.window"):
        for _ in range(2):
            with TraceAnnotation("bench.query_gen"):
                mat(a).block_until_ready()
                time.sleep(0.03)
            with TraceAnnotation("bench.answer_batch",
                                 least_bytes=int(x.nbytes)):
                big(x).block_until_ready()
                time.sleep(0.02)
                big(x).block_until_ready()
    jax.profiler.stop_trace()
    path = sorted(glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True))[-1]
    shutil.copy(path, os.path.join(args.out, "fixture.xplane.pb"))
    shutil.rmtree(tmp)

    pd = ProfileData.from_file(os.path.join(args.out, "fixture.xplane.pb"))
    for plane in pd.planes:
        lines = list(plane.lines)
        print("plane", plane.name, [ln.name for ln in lines])
        for ln in lines:
            evs = list(ln.events)
            for e in evs[:4]:
                print("  ", ln.name, "|", e.name, e.start_ns, e.duration_ns,
                      dict(e.stats) if plane.name.startswith("/host") else "")
            if len(evs) > 4:
                print("   ...", len(evs), "events")
    print("kind", jax.devices()[0].device_kind)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
