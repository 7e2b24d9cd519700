"""PIR serving driver — run the engine against a synthetic database.

    PYTHONPATH=src python -m repro.launch.serve --scheme sparse --theta 0.25 \
        --n 8192 --record-bytes 256 --d 10 --da 5 --queries 256

    # concurrent ingest + cross-batch cache (DESIGN.md §Async front):
    PYTHONPATH=src python -m repro.launch.serve --frontend async \
        --ingest-workers 4 --cache-entries 4096 --submitters 8

    # serve a LIVE store under concurrent appends (DESIGN.md §13):
    PYTHONPATH=src python -m repro.launch.serve --frontend async \
        --ingest-every 32 --ingest-rows 64

Prints per-batch latency, throughput, the (ε, δ) price per query, and the
engine's cumulative cost metrics (records touched vs the Table-1 model).
The async path submits from ``--submitters`` concurrent threads through
the bounded ingest queue and reports end-to-end future-resolution
throughput plus cache/frontend counters.
"""

from __future__ import annotations

import argparse
import threading
import time

import numpy as np

from repro.compile_cache import use_compile_cache
from repro.core import SCHEMES, make_scheme
from repro.core.accounting import PrivacyBudget
from repro.data.pipeline import pir_delta_batch
from repro.db import VersionedStore, make_synthetic_store
from repro.kernels import registered_backends
from repro.serve import (
    AsyncFrontend,
    BatchScheduler,
    QueryCache,
    ServingPipeline,
    ShardedBackend,
)


def build_args() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scheme", default="sparse", choices=sorted(SCHEMES))
    ap.add_argument("--n", type=int, default=8192)
    ap.add_argument("--record-bytes", type=int, default=256)
    ap.add_argument("--d", type=int, default=10)
    ap.add_argument("--da", type=int, default=5)
    ap.add_argument("--theta", type=float, default=0.25)
    ap.add_argument("--p", type=int, default=100)
    ap.add_argument("--t", type=int, default=4)
    ap.add_argument("--u", type=int, default=1000)
    ap.add_argument("--queries", type=int, default=256)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--max-wait-ms", type=float, default=0.0)
    ap.add_argument("--eps-budget", type=float, default=float("inf"))
    ap.add_argument("--frontend", choices=["sync", "async"], default="sync",
                    help="sync: submit+flush loop; async: AsyncFrontend "
                         "ingest queue with per-request futures")
    ap.add_argument("--ingest-workers", type=int, default=2)
    ap.add_argument("--queue-limit", type=int, default=8192)
    ap.add_argument("--submitters", type=int, default=4,
                    help="concurrent submitter threads (async frontend)")
    ap.add_argument("--cache-entries", type=int, default=0,
                    help="cross-batch cache slots; 0 disables the cache")
    ap.add_argument("--ingest-every", type=int, default=0,
                    help="serve a live VersionedStore and append one "
                         "delta every N queries (sync: per N served; "
                         "async: per N submitted, through the flush "
                         "worker's idle slot); 0 = frozen store")
    ap.add_argument("--ingest-rows", type=int, default=64,
                    help="records appended per ingest delta")
    ap.add_argument("--compact-depth", type=int, default=0,
                    help="rebase the live store's delta log onto a new "
                         "frozen base in the flush worker's idle slot "
                         "once it passes this depth (async frontend, "
                         "DESIGN.md §13); 0 = compaction off")
    ap.add_argument("--backend", default="auto",
                    choices=sorted(registered_backends()),
                    help="execution backend (repro.kernels.backend "
                         "registry; DESIGN.md §Execution backends)")
    ap.add_argument("--autotune-file", default="",
                    help="JSON autotune table: loaded at startup when it "
                         "exists (entries measured on other devices are "
                         "dropped), written back — with this run's search "
                         "results, pending cells tuned at exit — so the "
                         "next run starts warm")
    return ap


def make_engine(args) -> ServingPipeline:
    # the whole flag union goes through; the registry drops what the
    # chosen scheme does not declare (DESIGN.md §Scheme protocol)
    scheme = make_scheme(
        args.scheme,
        d=args.d,
        d_a=args.da,
        theta=args.theta,
        p=args.p - (args.p % args.d) or args.d,
        t=args.t,
        u=args.u,
    )
    store = make_synthetic_store(args.n, args.record_bytes, seed=0)
    cache = (
        QueryCache(scheme, store.n, max_entries=args.cache_entries)
        if args.cache_entries > 0 else None
    )
    # a live store serves through its frozen head; the sharded backend
    # below is handed the base snapshot (serve never sees the writer)
    served = (
        VersionedStore(store, backend=args.backend)
        if args.ingest_every > 0 else store
    )
    return ServingPipeline(
        served, scheme,
        scheduler=BatchScheduler(
            max_batch=args.batch, max_wait_s=args.max_wait_ms / 1e3
        ),
        cache=cache,
        backend=ShardedBackend(
            store,
            backend=args.backend,
            autotune_file=args.autotune_file or None,
        ),
        default_budget=lambda: PrivacyBudget(
            epsilon_limit=args.eps_budget, delta_limit=1.0
        ),
    )


def _feed_delta(args, engine: ServingPipeline, step: int, *,
                direct: bool, frontend=None) -> None:
    """One append delta of write traffic against the live store
    (deterministic in step, like the query stream)."""
    for delta in pir_delta_batch(
        engine.store.n, args.record_bytes,
        appends=args.ingest_rows, seed=2, step=step,
    ):
        if direct:
            engine.ingest(delta)
        else:
            frontend.ingest(delta)


def run_sync(args, engine: ServingPipeline) -> None:
    rng = np.random.default_rng(1)
    served = 0
    ingest_step = 0
    t_start = time.perf_counter()
    while served < args.queries:
        if args.ingest_every and served >= ingest_step * args.ingest_every:
            _feed_delta(args, engine, ingest_step, direct=True)
            ingest_step += 1
        nq = min(args.batch, args.queries - served)
        idx = rng.integers(0, args.n, size=nq)
        # each client's last accepted query: flush returns its answer
        last = {}
        for i, q in enumerate(idx):
            client = f"client-{i % 32}"
            if not engine.submit(client, int(q)):
                print("budget refused a query; stopping")
                served = args.queries
                break
            last[client] = int(q)
        t0 = time.perf_counter()
        out = engine.flush()
        dt = time.perf_counter() - t0
        # verify every client's answer (deltas here only append, so old
        # rows never move)
        for client, answer in out.items():
            expect = engine.store.record_bytes(last[client])
            assert (answer == expect).all(), (client, last[client])
        served += nq
        print(f"batch of {nq:4d} served in {dt*1e3:7.1f} ms "
              f"({nq/dt:8.0f} qps)")
    wall = time.perf_counter() - t_start
    if args.ingest_every:
        print(f"live store: v{engine.store_version}, n={engine.store.n} "
              f"({engine.metrics['records_ingested']} records ingested "
              f"mid-traffic)")
    print(f"\n{served} queries in {wall:.2f}s; engine metrics: {engine.metrics}")


def run_async(args, engine: ServingPipeline) -> None:
    rng = np.random.default_rng(1)
    per = -(-args.queries // args.submitters)
    indices = [rng.integers(0, args.n, size=per) for _ in range(args.submitters)]
    futures = [[] for _ in range(args.submitters)]

    with AsyncFrontend(
        engine, ingest_workers=args.ingest_workers,
        queue_limit=args.queue_limit, shed_policy="block",
        compact_log_depth=args.compact_depth or None,
    ) as fe:
        t_start = time.perf_counter()

        def feed(s: int) -> None:
            for j, q in enumerate(indices[s]):
                # submitter 0 doubles as the writer: one append delta per
                # --ingest-every submits, applied in the flush worker's
                # idle slot (appends only, so every queried index keeps
                # its bytes and the futures below verify exact)
                if args.ingest_every and s == 0 and j % args.ingest_every == 0:
                    _feed_delta(args, engine, j, direct=False, frontend=fe)
                futures[s].append(
                    fe.submit(f"client-{s}-{j % 32}", int(q))
                )

        threads = [
            threading.Thread(target=feed, args=(s,))
            for s in range(args.submitters)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        fe.drain()
        wall = time.perf_counter() - t_start

        refused = served = 0
        for s, futs in enumerate(futures):
            for j, fut in enumerate(futs):
                try:
                    answer = fut.result(timeout=5.0)
                    expect = engine.store.record_bytes(int(indices[s][j]))
                    assert (answer == expect).all()
                    served += 1
                except PermissionError:
                    refused += 1
        print(f"{served} served (+{refused} budget-refused) from "
              f"{args.submitters} concurrent submitters in {wall:.2f}s "
              f"({served/wall:8.0f} qps end-to-end, futures verified exact)")
        if args.ingest_every:
            print(f"live store: v{engine.store_version}, n={engine.store.n} "
                  f"({fe.metrics['ingested']} idle-slot ingests)")
            if args.compact_depth:
                live = engine.live
                print(f"compaction: {fe.metrics['compacted']} idle-slot "
                      f"rebases ({live.metrics['compacted_deltas']} deltas "
                      f"compacted, log depth now {live.log_depth}, base at "
                      f"v{live.base_version})")
            print(f"touched-shard invalidation: "
                  f"{engine.backend.mesh_metrics}")
        print(f"frontend metrics: {fe.metrics}")


def main() -> None:
    args = build_args().parse_args()
    use_compile_cache()
    engine = make_engine(args)
    scheme = engine.scheme

    eps, delta = scheme.privacy(args.n)
    print(f"scheme={args.scheme} n={args.n} d={args.d} d_a={args.da} "
          f"frontend={args.frontend}")
    print(f"eps/query={eps:.4g} delta/query={delta:.4g} "
          f"costs={scheme.costs(args.n)}")

    if args.frontend == "async":
        run_async(args, engine)
    else:
        run_sync(args, engine)
    print(f"scheduler target batch: {engine.scheduler.target_batch}; "
          f"backend={engine.backend.backend_name} "
          f"paths: {engine.backend.path_counts}")
    if args.autotune_file:
        if engine.backend.autotune_dropped:
            print(f"autotune load dropped {engine.backend.autotune_dropped} "
                  f"entries measured on a different device")
        # finish the search for any still-cold cells so the dumped table
        # carries measured winners, not priors
        tuned = engine.backend.tune_pending()
        print(f"autotune table -> {engine.backend.save_autotune()} "
              f"({len(engine.backend.planner.table)} entries, "
              f"{tuned} tuned at exit)")


if __name__ == "__main__":
    main()
