"""On-chip smoke test: the private-lookup server at the paper's CT scale.

    python chip_smoke.py             # one chip: the served main path
    python chip_smoke.py --chips 4   # four chips: the record-sharded path

One chip drives ``configs.pir_ct.make_async_frontend`` with the Pallas
kernels over ``make_synthetic_store(10**6, 1536, seed)``: the paper's
Certificate Transparency deployment (n = 10^6 records of 1,536 B, d = 100
servers, d_a = 50, Sparse-PIR θ = 0.25). It runs five phases through the
normal entry points: Sparse-PIR, Chor, Direct, one k = 4 ``submit_many``
request, and one append plus one update delta through ``fe.ingest``
followed by lookups of the touched rows. Every answer is compared with
``record_bytes`` of the snapshot it was served from.

``--chips 4`` shards the same store's records over four chips through
``mesh_rules``, answers one Chor and one Sparse batch on the mesh and
compares every server's answer bit for bit with the one-device jnp
oracle, then refreshes one update delta through ``swap_store(...,
touched_rows=...)`` and reads the touched row back.

Each phase prints one ``phase`` line: wall and compile seconds, the
batch, the backend's path counts, the plans it executed and the device's
peak bytes. The last line is one JSON object, ``{"ok": true, "device":
{...}}``, printed only when every phase passed on a TPU. Anything else
exits non-zero without it.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

SEED = 0
# the scheduler's largest batch: Sparse-PIR query generation draws
# [B, n, d] uniforms and argsorts them twice, which a compile for v5e
# prices at 6.7 GB of temporaries for B = 4 and 13.3 GB for B = 8 beside
# the 1.5 GB store on a 16 GB chip
BATCH = 4
# every future must resolve, and every phase end, inside this many
# seconds of the start (compiles included)
DEADLINE_S = 1080.0


def device_bytes(stat: str) -> list:
    """One ``memory_stats()`` figure per device (None where unreported)."""
    return [(d.memory_stats() or {}).get(stat) for d in jax.devices()]


class Smoke:
    """Phase bookkeeping: failures, compile time, compile-cache events."""

    def __init__(self) -> None:
        self.t0 = time.perf_counter()
        self.errors: list = []
        self.compile_s = 0.0
        self.cache = {"hits": 0, "misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.compile_s += secs

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache["misses"] += 1

    def remaining(self) -> float:
        return max(1.0, DEADLINE_S - (time.perf_counter() - self.t0))

    def fail(self, phase: str, msg: str) -> None:
        self.errors.append(f"{phase}: {msg}")
        print(f"FAIL {phase}: {msg}", flush=True)

    @contextlib.contextmanager
    def phase(self, name: str):
        """Time one phase and print its line; an exception fails it."""
        info: dict = {}
        t, c = time.perf_counter(), self.compile_s
        try:
            yield info
        except Exception as exc:  # the phase fails; the run goes on
            self.fail(name, f"{type(exc).__name__}: {exc}")
        info["wall_s"] = time.perf_counter() - t
        info["compile_s"] = self.compile_s - c
        info["peak_bytes"] = device_bytes("peak_bytes_in_use")
        print(f"phase {name} {json.dumps(info, default=str)}", flush=True)


def check_plans(smoke: Smoke, phase: str, backend, info: dict) -> None:
    """Every plan the backend executed must be a compiled Pallas plan."""
    plans = list(backend.plans_executed.values())
    info["path_counts"] = dict(backend.path_counts)
    info["plans"] = [
        f"{p.describe()} impl={p.impl} interpret={p.interpret}"
        for p in plans
    ]
    if not plans:
        smoke.fail(phase, "no plan executed")
    for p in plans:
        if p.impl != "pallas" or p.interpret:
            smoke.fail(phase, f"plan {p.describe()} ran impl={p.impl} "
                              f"interpret={p.interpret}")


def serve(smoke: Smoke, phase: str, fe, requests, info: dict) -> None:
    """Submit ``requests`` ((client, index or index tuple) pairs), drain,
    and compare every answer with the snapshot the frontend serves."""
    futs = [
        fe.submit_many(c, ix) if isinstance(ix, tuple) else fe.submit(c, ix)
        for c, ix in requests
    ]
    if not fe.drain(timeout=smoke.remaining()):
        smoke.fail(phase, "drain timed out")
    snap = fe.pipeline.store
    for (client, ix), fut in zip(requests, futs):
        want = (np.stack([snap.record_bytes(i) for i in ix])
                if isinstance(ix, tuple) else snap.record_bytes(ix))
        try:
            got = fut.result(timeout=smoke.remaining())
        except Exception as exc:
            smoke.fail(phase, f"{client} {ix}: {type(exc).__name__}: {exc}")
            continue
        if not np.array_equal(np.asarray(got), want):
            smoke.fail(phase, f"{client} {ix}: answer differs from the store")
    m = fe.metrics
    info["batch"] = fe.pipeline.scheduler.max_batch
    info["served"] = m["served"]
    info["failed"] = m["failed"]
    if m["failed"]:
        smoke.fail(phase, f"{m['failed']} failed futures")


def close(smoke: Smoke, phase: str, fe) -> None:
    """Close a frontend (its idle jobs finish first); a failed idle job
    or a worker still running at the deadline fails the phase."""
    try:
        fe.close(timeout=smoke.remaining())
    except Exception as exc:
        smoke.fail(phase, f"{type(exc).__name__}: {exc}")


def one_chip(smoke: Smoke, cfg) -> None:
    from repro.configs import pir_ct
    from repro.db import Delta, VersionedStore, make_synthetic_store

    cfg = dataclasses.replace(cfg, backend="pallas", query_batch=BATCH)
    n, rb = cfg.n_records, cfg.record_bytes
    rng = np.random.default_rng(SEED + 1)
    picks = [int(i) for i in rng.choice(n, size=8, replace=False)]

    with smoke.phase("setup") as info:
        store = make_synthetic_store(n, rb, seed=SEED)
        store.packed.block_until_ready()
        info.update(n=n, record_bytes=rb, d=cfg.d, d_a=cfg.d_a,
                    theta=cfg.theta, store_bytes=store.nbytes)

    fe = pir_ct.make_async_frontend(cfg, store=store)
    with smoke.phase("sparse") as info:
        serve(smoke, "sparse", fe, [("s0", picks[0]), ("s1", picks[1])],
              info)
        check_plans(smoke, "sparse", fe.pipeline.backend, info)
    with smoke.phase("multi_k4") as info:
        fe.pipeline.backend.plans_executed.clear()
        serve(smoke, "multi_k4", fe, [("m0", tuple(picks[2:6]))], info)
        check_plans(smoke, "multi_k4", fe.pipeline.backend, info)
        close(smoke, "multi_k4", fe)

    for scheme in ("chor", "direct"):
        fe = pir_ct.make_async_frontend(
            dataclasses.replace(cfg, scheme=scheme), store=store
        )
        with smoke.phase(scheme) as info:
            serve(smoke, scheme, fe, [("c0", picks[6]), ("c1", picks[7])],
                  info)
            check_plans(smoke, scheme, fe.pipeline.backend, info)
            close(smoke, scheme, fe)

    live = VersionedStore(store, backend="pallas")
    fe = pir_ct.make_async_frontend(
        dataclasses.replace(cfg, scheme="chor"), store=live
    )
    with smoke.phase("ingest") as info:
        fresh = rng.integers(0, 256, size=(2, rb), dtype=np.uint8)
        fe.ingest(Delta.append(fresh[:1]))
        fe.ingest(Delta.update([picks[0]], fresh[1:]))
        # both deltas land in the idle slot before the lookups go out
        if not fe.drain(timeout=smoke.remaining()):
            smoke.fail("ingest", "deltas not applied in time")
        serve(smoke, "ingest", fe, [("i0", n), ("i1", picks[0])], info)
        snap = fe.pipeline.store
        for i, row in ((n, fresh[0]), (picks[0], fresh[1])):
            if not np.array_equal(snap.record_bytes(i), row):
                smoke.fail("ingest", f"row {i} does not hold its delta")
        info.update(version=live.version, n_after=snap.n,
                    ingested=fe.metrics["ingested"])
        check_plans(smoke, "ingest", fe.pipeline.backend, info)
        close(smoke, "ingest", fe)


def four_chips(smoke: Smoke, cfg) -> None:
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core import make_scheme
    from repro.db import RecordStore, make_synthetic_store
    from repro.dist import mesh_rules
    from repro.dist.sharding import DEFAULT_RULES
    from repro.serve import SchemeRouter, ShardedBackend

    n, rb = cfg.n_records, cfg.record_bytes
    devices = jax.devices()
    mesh = Mesh(np.array(devices).reshape(2, 2), ("data", "model"))
    rules = dict(DEFAULT_RULES, records=("data", "model"), queries=None)
    rows = P(("data", "model"), None)
    rng = np.random.default_rng(SEED + 1)
    picks = jnp.asarray(rng.choice(n, size=BATCH, replace=False), jnp.int32)

    with smoke.phase("sharded_setup") as info:
        one = make_synthetic_store(n, rb, seed=SEED)
        # the records go straight to their shards: the one-device copy is
        # freed before anything else lands
        store = RecordStore(
            packed=jax.device_put(one.packed, NamedSharding(mesh, rows)),
            record_bits=one.record_bits,
        )
        host = np.array(one.packed)  # a writable host copy
        del one
        backend = ShardedBackend(store, backend="pallas")
        info["bytes_in_use"] = device_bytes("bytes_in_use")

    oracle_store = None
    for name, kw in (("chor", {}), ("sparse", {"theta": cfg.theta})):
        with smoke.phase(f"sharded_{name}") as info:
            sch = make_scheme(name, d=cfg.d, d_a=cfg.d_a, **kw).staged
            router = SchemeRouter(sch)
            routed = router.plan(jax.random.key(SEED), n, picks)
            backend.plans_executed.clear()
            with mesh_rules(mesh, rules):
                got = np.asarray(backend.answer_batch(routed, scheme=sch))
            check_plans(smoke, f"sharded_{name}", backend, info)
            info["bytes_in_use"] = device_bytes("bytes_in_use")
            if oracle_store is None:
                oracle_store = RecordStore(
                    packed=jax.device_put(host, devices[0]),
                    record_bits=store.record_bits,
                )
            oracle = ShardedBackend(oracle_store, backend="ref")
            want = np.asarray(oracle.answer_batch(routed, scheme=sch))
            if not np.array_equal(got, want):
                smoke.fail(f"sharded_{name}",
                           "mesh answers differ from the one-device oracle")
            out = np.asarray(router.finalize(routed, jnp.asarray(got)))
            if not np.array_equal(out, host[np.asarray(picks)]):
                smoke.fail(f"sharded_{name}", "records differ from the store")
    del oracle_store

    with smoke.phase("sharded_swap") as info:
        row = int(picks[0])
        fresh = rng.integers(0, 2**32, size=(1, host.shape[1]),
                             dtype=np.uint32)
        host[row] = fresh[0]
        new = RecordStore(
            packed=store.packed.at[row].set(jnp.asarray(fresh[0])),
            record_bits=store.record_bits,
        )
        with mesh_rules(mesh, rules):
            backend.swap_store(new, touched_rows=np.asarray([row]))
            info["last_swap"] = dict(backend.last_swap)
            sch = make_scheme("chor", d=cfg.d, d_a=cfg.d_a).staged
            router = SchemeRouter(sch)
            routed = router.plan(jax.random.key(SEED + 1), n, picks)
            out = np.asarray(router.finalize(
                routed, backend.answer_batch(routed, scheme=sch)
            ))
        if not np.array_equal(out, host[np.asarray(picks)]):
            smoke.fail("sharded_swap", "records differ after the swap")
        if backend.last_swap.get("mesh_shards_updated") != 1:
            smoke.fail("sharded_swap", f"touched-shard refresh updated "
                       f"{backend.last_swap.get('mesh_shards_updated')} "
                       f"shards, not 1")
        info["bytes_in_use"] = device_bytes("bytes_in_use")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(jax.devices())}", file=sys.stderr)
        return 2

    from repro.compile_cache import use_compile_cache
    from repro.configs import pir_ct

    smoke = Smoke()
    print(f"compile cache: {use_compile_cache()}", flush=True)
    print(f"device: {dev.device_kind} x{len(jax.devices())}", flush=True)
    (four_chips if args.chips == 4 else one_chip)(smoke, pir_ct.CONFIG)
    print(f"compile cache events: {smoke.cache}; compile_s="
          f"{smoke.compile_s:.1f}; wall_s="
          f"{time.perf_counter() - smoke.t0:.1f}", flush=True)
    if smoke.errors:
        print(f"chip_smoke: {len(smoke.errors)} failure(s)", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except Exception:  # a failure outside any phase
        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    # a failed phase may leave a frontend thread busy on the device:
    # leave without waiting for it
    os._exit(code)
