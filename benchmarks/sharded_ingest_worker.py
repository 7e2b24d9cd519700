"""Subprocess worker for the ``sharded_ingest`` benchmark row: touched-
shard-only distributed invalidation vs a full re-shard, on an 8-device
mesh (DESIGN.md §13).

Runs in its own process because the forced device count must be set
before jax imports (the parent harness keeps seeing 1 device). One live
``VersionedStore`` takes a sequence of update bursts confined to ≤ 25%
of its logical shards (and to the first device block); after each
ingest, the SAME snapshot is swapped into two identically-warmed
``ShardedBackend``\\ s — one with ``touched_rows`` (the incremental
path), one with ``reshard="full"`` (the old whole-store re-shard, kept
as the baseline) — and each then answers a batch. Timed per burst:
ingest-to-first-answer wall. Asserted here, not in the parent: the two
modes' answers are bit-identical every burst (zero torn), and the
touched mode never drops a cached ExecutionPlan.

It is a simulation on 8 forced CPU devices (the parent pins
``JAX_PLATFORMS=cpu`` too), and its JSON says so under ``platform``.

Prints one JSON object on the last stdout line for the parent to parse.

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        PYTHONPATH=src python benchmarks/sharded_ingest_worker.py [--smoke]
"""

import argparse
import json
import os
import time

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.core import make_scheme
from repro.db import Delta, VersionedStore, make_synthetic_store
from repro.dist import mesh_rules
from repro.dist.sharding import DEFAULT_RULES
from repro.serve import SchemeRouter, ShardedBackend


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()

    assert len(jax.devices()) == 8, jax.devices()
    mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "model"))
    rules = dict(DEFAULT_RULES, records=("data", "model"), queries=None)

    n, rb = (4096, 32) if args.smoke else (16384, 32)
    bursts = 3 if args.smoke else 6
    burst_rows = 64
    shards = 16  # logical (VersionedStore) shards
    rng = np.random.default_rng(5)

    live = VersionedStore(
        make_synthetic_store(n, rb, seed=7), shards=shards
    )
    sch = make_scheme("chor", d=3, d_a=1)
    router = SchemeRouter(sch)
    inc = ShardedBackend(live.snapshot())
    full = ShardedBackend(live.snapshot())

    q = jnp.asarray(rng.integers(0, n, size=32), jnp.int32)

    def answer(backend, key_i, nq):
        routed = router.plan(jax.random.key(key_i), nq, jnp.clip(q, 0, nq - 1))
        return np.asarray(
            router.finalize(routed, backend.answer_batch(routed))
        )

    def residency_ready(backend):
        """Force the sharded residency (db + bitplanes) to exist and
        block until its device buffers are real — the point at which the
        backend can serve the new version at full speed. For the touched
        mode this is a wait on the in-place refresh; for the full mode
        it pays the whole-store re-shard the swap deferred."""
        st = backend._mesh_state()
        jax.block_until_ready((st["db"], backend._mesh_planes(st)))

    with mesh_rules(mesh, rules):
        # warm both backends identically: mesh residency (db + planes)
        # and banked plans
        np.testing.assert_array_equal(answer(inc, 0, n), answer(full, 0, n))
        residency_ready(inc)
        residency_ready(full)

        # bursts confined to logical shards {0..3} (<= 25% of 16) AND to
        # the first contiguous device block (n/8 rows), so BOTH the
        # store_shards_touched counter and the device refresh stay small
        block = n // 8
        pool = np.array(
            [r for r in range(block) if r % shards < 4], np.int64
        )
        wall_inc = wall_full = 0.0
        last = {}
        for step in range(bursts + 1):
            rows = np.sort(rng.choice(pool, size=burst_rows, replace=False))
            delta = Delta.update(
                rows,
                rng.integers(0, 256, size=(burst_rows, rb), dtype=np.uint8),
            )
            touched = live.touched_rows(delta, n_before=live.n)
            live.ingest(delta)
            snap = live.snapshot()

            if step == 0:
                # untimed warm burst: pays the one-time scatter-kernel
                # jit + autotune cells so the timed loop measures the
                # steady-state write path (same policy as pir_ingest_p99)
                inc.swap_store(snap, touched_rows=touched, live=live)
                residency_ready(inc)
                full.swap_store(snap, reshard="full")
                residency_ready(full)
                continue

            # timed: ingest wall — swap to the new version until the
            # sharded residency is ready to serve it
            t0 = time.perf_counter()
            last = inc.swap_store(snap, touched_rows=touched, live=live)
            residency_ready(inc)
            wall_inc += time.perf_counter() - t0

            t0 = time.perf_counter()
            full.swap_store(snap, reshard="full")
            residency_ready(full)
            wall_full += time.perf_counter() - t0

            # untimed: zero torn answers — both modes serve the same bits
            np.testing.assert_array_equal(
                answer(inc, 1 + step, snap.n),
                answer(full, 1 + step, snap.n),
            )

    pm = inc.planner.metrics
    out = {
        "platform": jax.devices()[0].platform,
        "n": n,
        "bursts": bursts,
        "burst_rows": burst_rows,
        "wall_full_s": wall_full,
        "wall_touched_s": wall_inc,
        "ratio": wall_full / max(wall_inc, 1e-9),
        "store_shards_touched": last.get("store_shards_touched", -1),
        "store_shards_total": last.get("store_shards_total", -1),
        "mesh_shards_kept": last.get("mesh_shards_kept", -1),
        "mesh_shards_updated": last.get("mesh_shards_updated", -1),
        "plans_kept": pm["plans_kept"],
        "plans_dropped": pm["plans_dropped"],
        "match": True,
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
