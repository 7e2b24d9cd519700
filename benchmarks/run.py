"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (brief contract) and writes the
full curve data to results/benchmarks/*.csv so EXPERIMENTS.md can quote
any point. Analytic figures time the accountant; system rows time the
actual jitted server paths on this host. These timings are CPU-host
numbers (jnp oracles and interpret-mode Pallas), not device numbers: no
row here measures a TPU. ``python chip_smoke.py`` is the on-chip check.

Run: PYTHONPATH=src python -m benchmarks.run [--smoke] [--only NAME,...]

``--smoke`` shrinks every system row to tiny shapes with 1 timing rep —
a seconds-long CI guard that the whole harness still runs end to end.
``--only`` regenerates just the named figures/rows (function names, e.g.
``--only fig3_sparse,serve_async_vs_sync``); results/README.md maps each
CSV to its regenerating invocation.
"""

from __future__ import annotations

import argparse
import csv
import json
import math
import os
import time
from typing import Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.core import accounting as acc
from repro.core import make_scheme
from repro.db import make_synthetic_store
from repro.kernels import ref
from repro.serve import AsyncFrontend, BatchScheduler, QueryCache, ServingPipeline

# abspath: CSVs must land in results/benchmarks/ regardless of the cwd the
# harness is launched from
OUT_DIR = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "results", "benchmarks")
)
# the cross-PR perf trajectory file (schema: row -> {batch, wall_s,
# speedup}), written at the repo root by every harness run; seeded from
# the previous PR's artifact so the trajectory never loses rows
BENCH_JSON = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "BENCH_PR10.json")
)
PREV_BENCH_JSON = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "BENCH_PR9.json")
)

# perf-floor gate (EXPERIMENTS.md §Autotune): in every measured exec_*
# cell the auto backend must be no slower than ref beyond timing noise.
# Best-of-N timings on a shared CPU host still jitter ~±15%; the floor
# is a regression tripwire, not a microbenchmark.
PERF_FLOOR_TOL = 0.20

SMOKE = False  # set by main(); system rows shrink to tiny shapes, 1 rep

Row = Tuple[str, float, str]

# rows the run registers for BENCH_PR10.json (machine-readable trajectory)
BENCH: Dict[str, Dict[str, float]] = {}


def _bench(name: str, batch: int, wall_s: float, speedup: float) -> None:
    BENCH[name] = {
        "batch": int(batch),
        "wall_s": float(wall_s),
        "speedup": float(speedup),
    }


def _reps(full: int) -> int:
    return 1 if SMOKE else full


def _time_us(fn: Callable, *args, reps: int = 5, warmup: int = 2) -> float:
    reps = _reps(reps)
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps * 1e6


def _write_csv(name: str, header: List[str], rows: List) -> None:
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, f"{name}.csv"), "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(header)
        w.writerows(rows)


# --------------------------------------------------------------- Figure 1
def fig1_direct() -> List[Row]:
    """Direct Requests: ε vs p for d=100, n=1e6, d_a ∈ {d−1, d/2, d/10}."""
    n, d = 10**6, 100
    rows, t0 = [], time.perf_counter()
    for d_a in (99, 50, 10):
        for p in np.unique(np.logspace(math.log10(d), 6, 60).astype(int)):
            p = int(p - (p % d)) or d
            if p <= 1:
                continue
            rows.append((d_a, p, acc.epsilon_direct(n, d, d_a, min(p, n))))
    us = (time.perf_counter() - t0) * 1e6 / len(rows)
    _write_csv("fig1_direct", ["d_a", "p", "epsilon"], rows)
    ref_pt = acc.epsilon_direct(n, d, 99, 1000)
    return [("fig1_direct_eps_vs_p", us, f"eps(d_a=99;p=1000)={ref_pt:.2f}")]


# --------------------------------------------------------------- Figure 2
def fig2_as_direct() -> List[Row]:
    """AS-Bundled Direct: ε vs p, u=1e3."""
    n, d, u = 10**6, 100, 1000
    rows, t0 = [], time.perf_counter()
    for d_a in (99, 50, 10):
        for p in np.unique(np.logspace(math.log10(d), 6, 60).astype(int)):
            p = int(p - (p % d)) or d
            if p <= 1:
                continue
            rows.append((d_a, p, acc.epsilon_as_direct(n, d, d_a, min(p, n), u)))
    us = (time.perf_counter() - t0) * 1e6 / len(rows)
    _write_csv("fig2_as_direct", ["d_a", "p", "epsilon"], rows)
    ref_pt = acc.epsilon_as_direct(n, d, 99, 1000, u)
    return [("fig2_as_direct_eps_vs_p", us, f"eps(d_a=99;p=1000;u=1e3)={ref_pt:.2f}")]


# --------------------------------------------------------------- Figure 3
def fig3_sparse() -> List[Row]:
    """Sparse-PIR: ε vs θ for d=100."""
    d = 100
    rows, t0 = [], time.perf_counter()
    for d_a in (99, 90, 50):
        for theta in np.linspace(0.005, 0.5, 100):
            rows.append((d_a, theta, acc.epsilon_sparse(theta, d, d_a)))
    us = (time.perf_counter() - t0) * 1e6 / len(rows)
    _write_csv("fig3_sparse", ["d_a", "theta", "epsilon"], rows)
    ref_pt = acc.epsilon_sparse(0.25, d, 99)
    return [("fig3_sparse_eps_vs_theta", us, f"eps(d_a=99;th=.25)={ref_pt:.2f}")]


# --------------------------------------------------------------- Figure 4
def fig4_as_sparse() -> List[Row]:
    """AS-Sparse-PIR: ε vs θ for d=100, u=1e3."""
    d, u = 100, 1000
    rows, t0 = [], time.perf_counter()
    for d_a in (99, 90, 50):
        for theta in np.linspace(0.005, 0.5, 100):
            rows.append((d_a, theta, acc.epsilon_as_sparse(theta, d, d_a, u)))
    us = (time.perf_counter() - t0) * 1e6 / len(rows)
    _write_csv("fig4_as_sparse", ["d_a", "theta", "epsilon"], rows)
    ref_pt = acc.epsilon_as_sparse(0.25, d, 99, u)
    return [("fig4_as_sparse_eps_vs_theta", us,
             f"eps(d_a=99;th=.25;u=1e3)={ref_pt:.3f}")]


# --------------------------------------------------------------- Figure 5
def fig5_subset() -> List[Row]:
    """Subset-PIR: δ vs t for d=100."""
    d = 100
    rows, t0 = [], time.perf_counter()
    for d_a in (99, 50, 10):
        for t in range(1, d + 1):
            rows.append((d_a, t, acc.delta_subset(d, d_a, t)))
    us = (time.perf_counter() - t0) * 1e6 / len(rows)
    _write_csv("fig5_subset", ["d_a", "t", "delta"], rows)
    return [("fig5_subset_delta_vs_t", us,
             f"delta(d_a=50;t=10)={acc.delta_subset(d, 50, 10):.2e}")]


# --------------------------------------------------------------- Figure 6
def fig6_frontier() -> List[Row]:
    """Cost-privacy frontier: ε vs C_p and ε vs C_m for DR/SP/AS-DR/AS-SP
    (d=100, d_a=50, n=1e6, u=1e3) — the paper's comparative evaluation."""
    n, d, d_a, u = 10**6, 100, 50, 1000
    rows, t0 = [], time.perf_counter()
    for p in np.unique(np.logspace(2, 6, 50).astype(int)):
        p = int(p - (p % d)) or d
        if p <= 1:
            continue
        p = min(p, n)
        c = acc.scheme_costs("direct", n=n, d=d, p=p)
        rows.append(("direct", p, None, c["C_p"], c["C_m"],
                     acc.epsilon_direct(n, d, d_a, p)))
        rows.append(("as-direct", p, None, c["C_p"], c["C_m"],
                     acc.epsilon_as_direct(n, d, d_a, p, u)))
    for theta in np.linspace(0.005, 0.5, 50):
        c = acc.scheme_costs("sparse", n=n, d=d, theta=theta)
        rows.append(("sparse", None, theta, c["C_p"], c["C_m"],
                     acc.epsilon_sparse(theta, d, d_a)))
        rows.append(("as-sparse", None, theta, c["C_p"], c["C_m"],
                     acc.epsilon_as_sparse(theta, d, d_a, u)))
    us = (time.perf_counter() - t0) * 1e6 / len(rows)
    _write_csv("fig6_frontier",
               ["scheme", "p", "theta", "C_p", "C_m", "epsilon"], rows)
    return [("fig6_cost_privacy_frontier", us, f"{len(rows)}pts")]


# ---------------------------------------------------------------- Table 1
def table1() -> List[Row]:
    """Security & cost summary — analytic columns PLUS measured record
    touches from actual query matrices (validates C_p empirically)."""
    n, d, d_a, u = (256, 8, 4, 1000) if SMOKE else (4096, 8, 4, 1000)
    store = make_synthetic_store(n=n, record_bytes=64, seed=0)
    key = jax.random.key(0)
    q = jnp.arange(16)

    rows = []
    out: List[Row] = []

    for name, kw in (
        ("chor", {}),
        ("sparse", dict(theta=0.25)),
    ):
        sch = make_scheme(name, d=d, d_a=d_a, **kw)
        # the staged protocol's query stage (DESIGN.md §Scheme protocol):
        # the payload is exactly the [d, B, n] masks the servers see
        staged = sch.staged
        masks = staged.query(staged.precompute(key, n, len(q)), q).payload
        touched = float(jnp.sum(masks)) / len(q)
        analytic = sch.costs(n)["C_p"] / 2.0  # records touched (c_acc+c_prc=2)
        us = _time_us(
            jax.jit(lambda m: jax.vmap(
                lambda mm: ref.xor_fold_ref(store.packed, mm))(m)),
            masks,
        )
        rows.append((name, sch.epsilon(n), sch.delta(n), sch.costs(n)["C_m"],
                     analytic, touched))
        out.append((f"table1_{name}_server", us,
                    f"touched={touched:.0f};analytic={analytic:.0f}"))

    for name, kw in (
        ("direct", dict(p=64)),
        ("as-direct", dict(p=64, u=u)),
        ("as-sparse", dict(theta=0.25, u=u)),
        ("subset", dict(t=4)),
    ):
        sch = make_scheme(name, d=d, d_a=d_a, **kw)
        c = sch.costs(n)
        rows.append((name, sch.epsilon(n), sch.delta(n), c["C_m"],
                     c["C_p"] / 2.0, None))

    _write_csv(
        "table1",
        ["scheme", "epsilon", "delta", "C_m",
         "records_touched_analytic", "records_touched_measured"],
        rows,
    )
    return out


# --------------------------------------------- server kernel throughput
def server_paths() -> List[Row]:
    """The three TPU server paths, timed on host XLA (correctness-scale);
    derived column reports throughput. TPU projections: §Roofline."""
    n, rb, qn = (512, 16, 8) if SMOKE else (8192, 128, 64)
    store = make_synthetic_store(n=n, record_bytes=rb, seed=1)
    masks = (jax.random.uniform(jax.random.key(2), (qn, n)) < 0.25).astype(jnp.uint8)
    planes = store.bitplanes()

    out: List[Row] = []
    fold = jax.jit(lambda m: ref.xor_fold_ref(store.packed, m))
    us = _time_us(fold, masks)
    out.append(("server_xor_fold", us,
                f"Mrec/s={n * qn / us:.1f}"))

    par = jax.jit(lambda m: ref.parity_matmul_ref(m, planes))
    us = _time_us(par, masks)
    gf = 2.0 * qn * n * rb * 8 / (us * 1e-6) / 1e9
    out.append(("server_parity_matmul", us, f"GFLOPs={gf:.1f}"))

    from repro.kernels import indices_from_mask

    idx = indices_from_mask(masks, 192 if SMOKE else 3072)
    gat = jax.jit(lambda i: ref.gather_xor_ref(store.packed, i))
    us = _time_us(gat, idx)
    out.append(("server_gather_xor", us,
                f"touched/q={float((idx >= 0).sum()) / qn:.0f}"))
    return out


# -------------------------------------------- execution-backend matrix
def _best_us(fn, *args, reps: int = 5, warmup: int = 2) -> float:
    """Min-of-reps timing (noise-robust; always multi-rep, even in smoke
    — the perf-floor gate below is asserted, not just reported)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    best = math.inf
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def exec_backend_matrix() -> List[Row]:
    """The execution-backend layer's decision matrix (EXPERIMENTS.md
    §Autotune): for each registered backend × scheme family × bucket,
    what the planner chose (path/impl/source) and what one server answer
    costs. Fresh isolated autotune tables per backend, so the decisions
    shown are exactly what a cold process would make.

    For ``auto`` the row is the POST-SEARCH decision: cold cells queued
    by the first plan are tuned inline here (the idle-slot search run to
    completion), the cell is re-planned from the table, and the plan is
    asserted to match the table's recorded winner. The ``exec_perf_floor``
    row is the never-regress gate: the worst auto-vs-ref ratio over every
    measured cell, asserted >= 1 - PERF_FLOOR_TOL so CI fails when an
    `auto` decision loses to the ref backend beyond timing noise."""
    from repro.kernels import AutotuneTable, KernelPlanner, registered_backends
    from repro.serve import SchemeRouter

    n, rb = (256, 16) if SMOKE else (2048, 32)
    buckets = (8, 64) if SMOKE else (8, 256)
    store = make_synthetic_store(n, rb, seed=6)
    key = jax.random.key(0)

    cells = []
    for name, kw in (("chor", {}), ("sparse", dict(theta=0.25))):
        sch = make_scheme(name, d=2, d_a=1, **kw).staged
        router = SchemeRouter(sch)
        for b in buckets:
            cells.append((name, b, sch, router.plan(key, n, jnp.arange(b) % n)))

    timings: Dict[Tuple[str, int, str], Tuple[float, object]] = {}
    rows, out = [], []
    for backend in registered_backends():
        planner = KernelPlanner(store, backend=backend, table=AutotuneTable())
        for name, b, sch, routed in cells:
            plan = planner.plan(routed, b, None, scheme=sch)
            if backend == "auto" and planner.pending():
                # finish the search the serve layer would run in idle
                # slots, then re-plan: the row must show the winner
                planner.tune_pending()
                plan = planner.plan(routed, b, None, scheme=sch)
            if backend == "auto":
                by_cell = {
                    (k[0], k[1]): e for k, e in planner.table.items()
                }
                entry = by_cell.get((name, b))
                if entry is not None:  # measured cell: plan == table winner
                    assert (plan.path, plan.impl) == (
                        entry["path"], entry["impl"],
                    ), f"auto plan diverges from table winner for {name}/b{b}"
                    assert plan.source == entry["source"]
            us = _best_us(plan, routed.payload[0])
            timings[(name, b, backend)] = (us, plan)
            rows.append((backend, name, b, plan.path, plan.impl,
                         plan.source, us))

    floor, floor_cell, floor_wall = math.inf, "", 0.0
    for (name, b, backend), (us, plan) in timings.items():
        ref_us = timings[(name, b, "ref")][0]
        _bench(f"exec_{backend}_{name}_b{b}", b, us * 1e-6, ref_us / us)
        out.append((
            f"exec_{backend}_{name}_b{b}", us,
            f"path={plan.path};impl={plan.impl};source={plan.source};"
            f"vs_ref={ref_us / us:.2f}x",
        ))
        if backend == "auto" and ref_us / us < floor:
            floor, floor_cell = ref_us / us, f"{name}_b{b}"
            floor_wall = us * 1e-6
    # the never-regress gate: auto >= ref (within noise) in EVERY cell
    assert floor >= 1.0 - PERF_FLOOR_TOL, (
        f"auto regressed vs ref: {floor:.2f}x at {floor_cell} "
        f"(floor {1.0 - PERF_FLOOR_TOL:.2f})"
    )
    _bench("exec_perf_floor", 0, floor_wall, floor)
    out.append((
        "exec_perf_floor", floor_wall * 1e6,
        f"worst_cell={floor_cell};vs_ref={floor:.2f}x;"
        f"tol={PERF_FLOOR_TOL:.2f}",
    ))
    _write_csv(
        "exec_backend_matrix",
        ["backend", "scheme", "bucket", "path", "impl", "source", "us"],
        rows,
    )
    return out


# ---------------------------------------------------- pipeline end-to-end
def engine_throughput() -> List[Row]:
    n, d, d_a = (512, 6, 3) if SMOKE else (4096, 6, 3)
    b = 16 if SMOKE else 64
    store = make_synthetic_store(n=n, record_bytes=64, seed=3)
    out: List[Row] = []
    for name, kw in (
        ("sparse", dict(theta=0.25)),
        ("chor", {}),
        ("subset", dict(t=3)),
        ("direct", dict(p=24)),
    ):
        pipe = ServingPipeline(
            store, make_scheme(name, d=d, d_a=d_a, **kw),
            scheduler=BatchScheduler(max_batch=1024),
        )
        rng = np.random.default_rng(0)
        for i in range(b):
            pipe.submit(f"c{i}", int(rng.integers(0, n)))
        pipe.flush()  # pays jit
        for i in range(b):
            pipe.submit(f"c{i}", int(rng.integers(0, n)))
        t0 = time.perf_counter()
        pipe.flush()
        dt = time.perf_counter() - t0
        out.append((f"engine_{name}", dt * 1e6 / b, f"qps={b / dt:.0f}"))
    return out


def serve_batched_vs_loop() -> List[Row]:
    """The tentpole number: one scheduled batch of B queries vs B
    per-request round-trips through the same pipeline (batch 1). Batching
    is what makes the MXU parity path and dispatch amortisation pay."""
    n, b, loop_n = (512, 128, 8) if SMOKE else (4096, 1024, 64)
    store = make_synthetic_store(n=n, record_bytes=64, seed=4)
    sch = make_scheme("chor", d=2, d_a=1)

    def make_pipe(max_batch):
        return ServingPipeline(
            store, sch, scheduler=BatchScheduler(max_batch=max_batch)
        )

    # batched: B queries served as one scheduled batch
    pipe = make_pipe(b)
    for rep in range(2):  # first rep pays jit
        for i in range(b):
            pipe.submit(f"c{i}", (i * 37) % n)
        t0 = time.perf_counter()
        pipe.flush()
        dt_batched = time.perf_counter() - t0
    qps_batched = b / dt_batched

    # per-request loop: batch-1 round trips (same scheme, same store)
    pipe1 = make_pipe(1)
    pipe1.submit("w", 0)
    pipe1.flush()  # pays jit for the [1, n] shapes
    t0 = time.perf_counter()
    for i in range(loop_n):
        pipe1.submit("c", (i * 37) % n)
        pipe1.flush()
    dt_loop = time.perf_counter() - t0
    qps_loop = loop_n / dt_loop

    speedup = qps_batched / qps_loop
    _write_csv(
        "serve_batched_vs_loop",
        ["mode", "batch", "qps"],
        [("batched", b, qps_batched), ("loop", 1, qps_loop)],
    )
    _bench("serve_batched_vs_loop", b, dt_batched, speedup)
    return [(
        f"serve_batched_b{b}", dt_batched * 1e6 / b,
        f"batched_qps={qps_batched:.0f};loop_qps={qps_loop:.0f};"
        f"speedup={speedup:.1f}x",
    )]


def serve_async_vs_sync() -> List[Row]:
    """The tentpole row: the async serving front (concurrent ingest +
    cross-batch QueryCache) vs the plain synchronous submit+flush loop it
    replaces, same scheme/store/batch. Workload: 32 client sessions, each
    re-polling its own hot record for 1 query in 5 (the paper's §2.2
    correlated-query pattern) over a scan of distinct indices. The async
    front overlaps admission with serving, banks precomputed query
    randomness while idle, and answers per-(client, index) repeats from
    the memo — every hit still spends ε, but steady-state batches shrink
    to the next pow2 bucket down, halving the per-server record touches.

    Also measures the **double-buffered flush** (plan batch k+1 while
    batch k's ExecutionPlan runs, DESIGN.md §Execution backends) against
    the single-threaded flush worker it replaces, on a cache-free
    back-to-back-batch stream at bucket 256 — the steady-state regime
    the overlap targets (one big batch at a time leaves nothing to
    overlap; the planner's query generation amortizes across the
    stream). Same frontend, ``double_buffer`` flipped."""
    n, b, batches = (1024, 256, 2) if SMOKE else (4096, 1024, 3)
    total = b * batches
    store = make_synthetic_store(n=n, record_bytes=64, seed=5)
    # the paper's reference scheme: Sparse-PIR, where query generation
    # (parity-conditioned weights + slot ranking) is the dominant plan
    # cost — exactly what the frontend's idle prefill takes off the
    # critical path
    sch = make_scheme("sparse", d=4, d_a=2, theta=0.25)

    hot = [(131 * j) % n for j in range(32)]

    def client(i: int) -> str:
        return f"c{i % 32}"

    def q_index(i: int) -> int:
        # every other query: this client re-polls its own hot record (a
        # CT monitor watching its certificate — §2.2 correlated queries)
        return hot[i % 32] if i % 2 == 0 else (i * 7) % n

    def make_pipe(cached: bool):
        # target_latency_s pinned high so both modes cut at exactly b
        return ServingPipeline(
            store, sch,
            scheduler=BatchScheduler(max_batch=b, target_latency_s=10.0),
            cache=QueryCache(sch, store.n) if cached else None,
        )

    def warm(pipe):
        # distinct warm clients per phase: the per-(client, index) memo
        # must not absorb a later warm flush, or its bucket never compiles
        for i in range(b):
            pipe.submit("w1", (i * 5) % n)
        pipe.flush()  # pays jit for the inline-plan [b, n] shapes
        if pipe.cache is not None:
            pipe.prefill_cache(b)
            for i in range(b):
                pipe.submit("w2", (i * 3) % n)
            pipe.flush()  # pays jit for the assemble-from-pre path
            for i in range(b // 2):
                pipe.submit("w3", (i * 9) % n)
            pipe.flush()  # the bucket hit-shrunk batches land on

    def run_sync() -> float:
        pipe = make_pipe(cached=False)
        warm(pipe)
        t0 = time.perf_counter()
        for i in range(total):
            pipe.submit(client(i), q_index(i))
            if (i + 1) % b == 0:
                pipe.flush()
        return time.perf_counter() - t0

    def run_async() -> Tuple[float, int, int]:
        # the frontend banks its precompute pool itself during the idle
        # window before traffic arrives — that idle work is the design
        pipe = make_pipe(cached=True)
        warm(pipe)
        with AsyncFrontend(
            pipe, ingest_workers=2, queue_limit=total, shed_policy="block"
        ) as fe:
            fe.start()
            deadline = time.perf_counter() + 0.25
            while (
                pipe.cache.pre_depth(b) < pipe.cache.max_pre_batches
                and time.perf_counter() < deadline
            ):
                time.sleep(0.002)  # let the flush worker fill the pool
            t0 = time.perf_counter()
            futures = [fe.submit(client(i), q_index(i)) for i in range(total)]
            fe.drain()
            dt = time.perf_counter() - t0
            assert all(f.done() for f in futures)
            m = fe.metrics
            return dt, m["prefilled"], m["cache_hits"]

    # the flush-path comparison: bucket-256 back-to-back batches, no
    # cache, only double_buffer flipped — isolates plan/execute overlap
    db_b = 256
    db_batches = 3 if SMOKE else 8
    db_total = db_b * db_batches

    def run_flush(double_buffer: bool) -> float:
        pipe = ServingPipeline(
            store, sch,
            scheduler=BatchScheduler(max_batch=db_b, target_latency_s=10.0),
        )
        for i in range(db_b):
            pipe.submit("w", (i * 5) % n)
        pipe.flush()  # pays jit for the [db_b, n] shapes
        with AsyncFrontend(
            pipe, ingest_workers=2, queue_limit=db_total,
            shed_policy="block", double_buffer=double_buffer,
        ) as fe:
            t0 = time.perf_counter()
            futs = [
                fe.submit(client(i), (i * 7) % n) for i in range(db_total)
            ]
            fe.drain()
            dt = time.perf_counter() - t0
            assert all(f.done() for f in futs)
        return dt

    # interleave the modes, best-of-2 each: the set samples the same
    # noise window, so the ratios are stable even on a shared host
    dt_sync = dt_async = dt_single = dt_dbuf = math.inf
    prefilled = hits = 0
    for _ in range(2):
        dt_sync = min(dt_sync, run_sync())
        dt, pf, h = run_async()
        dt_async, prefilled, hits = min(dt_async, dt), max(prefilled, pf), h
        dt_single = min(dt_single, run_flush(double_buffer=False))
        dt_dbuf = min(dt_dbuf, run_flush(double_buffer=True))
    qps_sync = total / dt_sync
    qps_async = total / dt_async
    qps_single = db_total / dt_single
    qps_dbuf = db_total / dt_dbuf

    ratio = qps_async / qps_sync
    dbuf_ratio = qps_dbuf / qps_single
    _write_csv(
        "serve_async_vs_sync",
        ["mode", "batch", "qps"],
        [("async", b, qps_async), ("sync", b, qps_sync),
         ("dbuf", db_b, qps_dbuf), ("single_flush", db_b, qps_single)],
    )
    _bench("serve_async_vs_sync", b, dt_async, ratio)
    _bench("serve_dbuf_vs_single_flush", db_b, dt_dbuf, dbuf_ratio)
    return [
        (
            f"serve_async_vs_sync_b{b}", dt_async * 1e6 / total,
            f"async_qps={qps_async:.0f};sync_qps={qps_sync:.0f};"
            f"ratio={ratio:.2f}x;hits={hits};prefilled={prefilled}",
        ),
        (
            f"serve_dbuf_vs_single_b{db_b}", dt_dbuf * 1e6 / db_total,
            f"dbuf_qps={qps_dbuf:.0f};single_flush_qps={qps_single:.0f};"
            f"ratio={dbuf_ratio:.2f}x",
        ),
    ]


# ----------------------------------------------- private-DLRM end-to-end
def dlrm_serving() -> List[Row]:
    """The PR-8 tentpole row: end-to-end private-DLRM inference
    (DESIGN.md §Multi-index wire format). Each example's embedding-bag
    is ONE jagged multi-index request (k = 8 ids) through
    ``ServingPipeline.submit_many`` — flattened into one padded wire
    batch, answered by the multi-lookup execution path, then fed to the
    DLRM dot interaction on-device — versus the per-index request loop
    it replaces: each of a request's k indices issued as its own
    single-index round trip (batch-1 flushes, the same loop baseline
    ``serve_batched_vs_loop`` pins). A third mode, ``singles`` (a
    request's k ids as k single-index requests sharing one scheduler
    cut), is reported in the CSV for context but not gated. Outputs are
    asserted bit-identical across modes and the headline
    ``dlrm_lookups_per_sec`` trajectory row carries the multi-vs-loop
    speedup, asserted >= 2x at k = 8 (one plan + one wire round-trip +
    one kernel dispatch amortized over k, instead of k of each)."""
    from repro.db.store import RecordStore

    n, dim, reqs = (512, 16, 8) if SMOKE else (2048, 32, 16)
    k = 8
    table = (
        jax.random.normal(jax.random.key(9), (n, dim)) * 0.02
    ).astype(jnp.float32)
    store = RecordStore.from_float_table(table)
    sch = make_scheme("sparse", d=2, d_a=1, theta=0.25)
    rng = np.random.default_rng(12)
    ids = rng.integers(0, n, size=(reqs, k))

    iu, ju = jnp.triu_indices(k, k=1)

    @jax.jit
    def interact(z):  # [reqs, k, dim] embedding bags -> dot-pair logits
        inter = jnp.einsum("bfd,bgd->bfg", z, z)
        return inter[:, iu, ju].sum(axis=1)

    def to_f32(raw: np.ndarray) -> jnp.ndarray:
        return jnp.asarray(raw.view(np.float32).reshape(reqs, k, dim))

    def run_multi() -> Tuple[float, np.ndarray]:
        pipe = ServingPipeline(
            store, sch, scheduler=BatchScheduler(max_batch=reqs * k)
        )
        for j, row in enumerate(ids):  # warm pass pays jit
            pipe.submit_many(f"w{j}", row.tolist())
        pipe.flush()
        t0 = time.perf_counter()
        for j, row in enumerate(ids):
            pipe.submit_many(f"c{j}", row.tolist())
        out = pipe.flush()
        raw = np.stack([out[f"c{j}"] for j in range(reqs)])  # [reqs, k, nb]
        scores = interact(to_f32(raw))
        jax.block_until_ready(scores)
        return time.perf_counter() - t0, np.asarray(scores)

    # the loop side subsamples requests (full scale) — batch-1 round
    # trips are slow by design, and the per-lookup rate is what's compared
    loop_reqs = reqs if SMOKE else 4

    def run_loop() -> Tuple[float, np.ndarray]:
        pipe = ServingPipeline(
            store, sch, scheduler=BatchScheduler(max_batch=1)
        )
        pipe.submit("w", int(ids[0, 0]))
        pipe.flush()  # pays jit for the batch-1 shapes
        raw = np.empty((loop_reqs, k), dtype=object)
        t0 = time.perf_counter()
        for j in range(loop_reqs):
            for pos in range(k):  # the per-index loop: k round trips
                pipe.submit(f"c{j}", int(ids[j, pos]))
                raw[j, pos] = pipe.flush()[f"c{j}"]
        stacked = np.stack([np.stack(list(r)) for r in raw])
        scores = interact_loop(
            jnp.asarray(stacked.view(np.float32).reshape(loop_reqs, k, dim))
        )
        jax.block_until_ready(scores)
        return time.perf_counter() - t0, np.asarray(scores)

    def run_singles() -> float:
        # context row: a request's k ids as k single-index requests
        # sharing one scheduler cut (batched singles, no multi wire)
        pipe = ServingPipeline(
            store, sch, scheduler=BatchScheduler(max_batch=reqs * k)
        )
        for rep, tag in (("w", "w"), ("t", "t")):  # first rep pays jit
            t0 = time.perf_counter()
            for j in range(reqs):
                for pos in range(k):
                    pipe.submit(f"{tag}{j}_{pos}", int(ids[j, pos]))
            pipe.flush()
            dt = time.perf_counter() - t0
        return dt

    iu_l, ju_l = jnp.triu_indices(k, k=1)

    @jax.jit
    def interact_loop(z):
        inter = jnp.einsum("bfd,bgd->bfg", z, z)
        return inter[:, iu_l, ju_l].sum(axis=1)

    # interleaved best-of-2: both modes sample the same noise window
    dt_multi = dt_loop = dt_singles = math.inf
    s_multi = s_loop = None
    for _ in range(_reps(2)):
        dt, s = run_multi()
        if dt < dt_multi:
            dt_multi, s_multi = dt, s
        dt, s = run_loop()
        if dt < dt_loop:
            dt_loop, s_loop = dt, s
        dt_singles = min(dt_singles, run_singles())
    # PIR transports raw bits: the modes must score bit-identically
    assert (s_multi[:loop_reqs] == s_loop).all(), (
        "multi-index scores != per-index-loop scores"
    )

    flat = reqs * k
    lps_multi = flat / dt_multi
    lps_loop = loop_reqs * k / dt_loop
    lps_singles = flat / dt_singles
    speedup = lps_multi / lps_loop
    assert speedup >= 2.0, (
        f"multi-index path only {speedup:.2f}x the per-index "
        f"request loop at k={k} (need >= 2x)"
    )
    _write_csv(
        "dlrm_serving",
        ["mode", "requests", "k", "lookups_per_sec"],
        [("multi", reqs, k, lps_multi), ("loop", loop_reqs, k, lps_loop),
         ("singles", reqs, k, lps_singles)],
    )
    _bench("dlrm_lookups_per_sec", flat, dt_multi, speedup)
    return [(
        "dlrm_lookups_per_sec", dt_multi * 1e6 / flat,
        f"multi_lps={lps_multi:.0f};loop_lps={lps_loop:.0f};"
        f"singles_lps={lps_singles:.0f};speedup={speedup:.1f}x;k={k}",
    )]


# ------------------------------------------------- fleet scenario matrix
def _fleet_pipe(
    n: int, rb: int, max_batch: int, *, live: bool = False
) -> ServingPipeline:
    """A cache-equipped serving pipeline with every pow2 bucket shape the
    scheduler can cut pre-compiled — the timed runs then measure queueing
    and serving, not XLA compiles. Post-degrade shapes (d' < d) are left
    cold on purpose: that compile storm is part of the honest disruption
    cost a replica loss inflicts, and it lands in the loss scenario's p99.
    ``live=True`` serves through a :class:`~repro.db.live.VersionedStore`
    (DESIGN.md §13) for the write-heavy rows."""
    from repro.db import VersionedStore

    store = make_synthetic_store(n, rb, seed=7)
    sch = make_scheme("sparse", d=4, d_a=2, theta=0.25)
    pipe = ServingPipeline(
        VersionedStore(store, shards=16) if live else store, sch,
        scheduler=BatchScheduler(
            max_batch=max_batch, max_wait_s=0.005, target_latency_s=10.0
        ),
        cache=QueryCache(sch, store.n, max_entries=4096),
    )
    b, w = 1, 0
    while b <= max_batch:
        for i in range(b):
            pipe.submit(f"warm{w}", (i * 11) % n)
        pipe.flush()
        w, b = w + 1, b * 2
    return pipe


def fleet_scenarios() -> List[Row]:
    """The PR-6 tentpole row: the fleet harness (DESIGN.md §Fleet harness)
    drives open-loop Poisson / bursty / diurnal traffic through the live
    AsyncFrontend → scheduler → router → sharded-backend path, and the
    1-loss scenario kills a replica's heartbeats mid-traffic. Asserted
    here, not just reported: the loss run remeshes at least once, its
    final per-query ε equals the ``pir_degraded_privacy`` Security-Theorem
    bound for 1 failed replica, and *zero* in-flight futures are dropped
    in any scenario. The trajectory row tracks p99 under 1-replica-loss
    (speedup column = healthy p99 / loss p99 — the disruption ratio)."""
    from repro.dist.fault import pir_degraded_privacy
    from repro.fleet import (
        BurstyArrivals,
        ClientPopulation,
        DiurnalArrivals,
        FaultEvent,
        FleetScenario,
        PoissonArrivals,
        run_scenario,
    )

    n, rb = (512, 64) if SMOKE else (2048, 64)
    rate = 150.0 if SMOKE else 400.0
    dur = 0.6 if SMOKE else 2.0
    hb = 0.05 if SMOKE else 0.1
    max_batch = 64 if SMOKE else 256
    d, d_a, theta = 4, 2, 0.25

    matrix = [
        ("poisson_healthy", PoissonArrivals(rate), ()),
        ("poisson_1loss", PoissonArrivals(rate),
         (FaultEvent(0.4 * dur, d - 1),)),
        ("bursty", BurstyArrivals(
            base_qps=rate / 2, burst_qps=2 * rate,
            period_s=max(0.2, dur / 3), duty=0.3,
        ), ()),
        ("diurnal", DiurnalArrivals(mean_qps=rate, period_s=dur), ()),
    ]
    reports, rows = {}, []
    for name, arrivals, faults in matrix:
        pipe = _fleet_pipe(n, rb, max_batch)
        pop = ClientPopulation(
            n_clients=64 if SMOKE else 1024, n_records=n, seed=0
        )
        rep = run_scenario(
            FleetScenario(
                name=name, arrivals=arrivals, duration_s=dur,
                faults=faults, heartbeat_timeout_s=hb, seed=11,
            ),
            pipe, pop,
        )
        assert rep.slo["failed"] == 0, (
            f"{name}: {rep.slo['failed']:.0f} in-flight futures dropped"
        )
        reports[name] = rep
        s = rep.slo
        rows.append((
            name, rep.arrivals, f"{rep.wall_s:.3f}",
            f"{s['p50_ms']:.2f}", f"{s['p95_ms']:.2f}", f"{s['p99_ms']:.2f}",
            f"{s['goodput_qps']:.1f}", f"{s['refusal_rate']:.4f}",
            f"{s['shed_rate']:.4f}", f"{s['max_queue_depth']:.0f}",
            rep.remeshes, f"{rep.price[0]:.6g}",
        ))

    loss, healthy = reports["poisson_1loss"], reports["poisson_healthy"]
    assert loss.remeshes >= 1, "1-loss scenario never remeshed"
    bound = pir_degraded_privacy(
        d=d, d_a=d_a, failed=1, scheme="sparse", n=n, theta=theta
    )
    # the accounted ε after the mid-traffic loss IS the Security-Theorem
    # bound for d' = d-1 — degradation is priced, not waved through
    assert math.isclose(loss.price[0], bound["epsilon"], rel_tol=1e-9), (
        f"degraded eps {loss.price[0]} != bound {bound['epsilon']}"
    )
    assert loss.price[0] <= bound["epsilon"] + 1e-12

    _write_csv(
        "fleet_scenarios",
        ["scenario", "arrivals", "wall_s", "p50_ms", "p95_ms", "p99_ms",
         "goodput_qps", "refusal_rate", "shed_rate", "max_queue_depth",
         "remeshes", "eps_per_query"],
        rows,
    )
    _write_csv(
        "fleet_1loss_timeline",
        sorted({k for pt in loss.timeline for k in pt}),
        [
            [pt.get(k, "") for k in sorted({k2 for p2 in loss.timeline
                                            for k2 in p2})]
            for pt in loss.timeline
        ],
    )
    p99_h, p99_l = healthy.slo["p99_ms"], loss.slo["p99_ms"]
    _bench("fleet_p99_1loss", loss.arrivals, p99_l / 1e3, p99_h / p99_l)
    return [
        (
            f"fleet_{name}", rep.slo["p99_ms"] * 1e3,
            f"p50={rep.slo['p50_ms']:.1f}ms;p99={rep.slo['p99_ms']:.1f}ms;"
            f"goodput={rep.slo['goodput_qps']:.0f}qps;"
            f"remesh={rep.remeshes};eps={rep.price[0]:.3g}",
        )
        for name, rep in reports.items()
    ]


# ------------------------------------------------- streaming-ingest row
def pir_ingest_p99() -> List[Row]:
    """The PR-9 tentpole row: serve p99 under a write-heavy fleet
    scenario — Poisson reads with an update delta touching > 1% of the
    records landing every eighth of the run through the flush worker's
    idle slot (DESIGN.md §13) — versus the identical read-only scenario
    on a frozen store. Asserted, not just reported: zero dropped
    futures in both runs; every delta actually applied; same-shape
    ingest kept every cached ExecutionPlan (``plans_dropped == 0`` —
    incremental invalidation, not re-planning); and the headline gate,
    **write-heavy p99 ≤ 1.5× frozen p99**. A separate explicit-futures
    pass asserts zero *torn* answers: each answer is bit-identical to
    its index's bytes in SOME store version — a batch that mixed two
    snapshots would produce bytes no version ever held."""
    from repro.data.pipeline import pir_delta_batch
    from repro.fleet import (
        ClientPopulation,
        FleetScenario,
        PoissonArrivals,
        run_scenario,
    )

    n, rb = (512, 64) if SMOKE else (2048, 64)
    rate = 150.0 if SMOKE else 400.0
    dur = 0.6 if SMOKE else 2.0
    max_batch = 64 if SMOKE else 256
    upd = max(8, n // 64)  # > 1% of records per delta
    bursts = 8

    def scenario(name: str, write_heavy: bool) -> FleetScenario:
        return FleetScenario(
            name=name, arrivals=PoissonArrivals(rate), duration_s=dur,
            seed=11,
            ingest_every_s=dur / bursts if write_heavy else 0.0,
            ingest_updates=upd if write_heavy else 0,
            # PR-10: idle-slot log compaction runs DURING the timed
            # write-heavy window — the 1.5x p99 gate below now also
            # proves rebasing never blocks a flush
            compact_log_depth=4 if write_heavy else 0,
        )

    pop = ClientPopulation(
        n_clients=64 if SMOKE else 1024, n_records=n, seed=0
    )

    pipe_f = _fleet_pipe(n, rb, max_batch)
    rep_f = run_scenario(scenario("ingest_frozen", False), pipe_f, pop)

    pipe_w = _fleet_pipe(n, rb, max_batch, live=True)
    # pay the scatter kernel's jit before the timed run, same shapes as
    # the scheduled deltas — the steady-state write path is what's timed
    for d0 in pir_delta_batch(n, rb, updates=upd, seed=99, step=0):
        pipe_w.ingest(d0)
    planner0 = dict(pipe_w.backend.planner.metrics)
    rep_w = run_scenario(scenario("ingest_write_heavy", True), pipe_w, pop)

    for name, rep in (("frozen", rep_f), ("write_heavy", rep_w)):
        assert rep.slo["failed"] == 0, (
            f"{name}: {rep.slo['failed']:.0f} in-flight futures dropped"
        )
    ingests = int(rep_w.frontend_metrics["ingested"])
    assert ingests >= bursts // 2, (
        f"write-heavy run only applied {ingests} of ~{bursts} deltas"
    )
    # the delta log passed the threshold mid-run, so at least one
    # idle-slot rebase must have landed without tripping the p99 gate.
    # Read the store's own counter, not the report snapshot: the report
    # is taken at drain (all futures resolved), which can race the
    # flush worker's final idle tick; run_scenario has closed the
    # frontend by now, so the store counters are settled.
    compacted = int(pipe_w.live.metrics["compactions"])
    assert compacted >= 1, (
        f"compact_log_depth=4 with {ingests} ingests never compacted"
    )
    pm = pipe_w.backend.planner.metrics
    # same-shape updates must never re-plan: incremental invalidation
    # keeps every cached ExecutionPlan and refreshes only touched rows
    assert pm["plans_dropped"] == planner0["plans_dropped"], (
        f"update-only ingest dropped plans: {pm}"
    )
    assert pm["plans_kept"] > planner0["plans_kept"]

    # zero-torn-answers pass: explicit futures, checked by snapshot
    # membership against the live store's whole version history
    tn = 256 if SMOKE else 512
    pipe_t = _fleet_pipe(tn, rb, 32, live=True)
    live = pipe_t.live
    with AsyncFrontend(pipe_t, queue_limit=1024, shed_policy="block") as fe:
        futs = []
        for step in range(6):
            for d in pir_delta_batch(
                tn, rb, updates=max(8, tn // 32), seed=13, step=step
            ):
                fe.ingest(d)
            for j in range(16):
                idx = (step * 31 + j * 7) % tn
                futs.append((idx, fe.submit(f"t{step}_{j}", idx)))
        assert fe.drain(30.0)
        history = [live.snapshot(v) for v in range(live.version + 1)]
        for idx, fut in futs:
            a = bytes(fut.result(5.0))
            assert any(
                a == bytes(s.record_bytes(idx)) for s in history
            ), f"torn answer for index {idx}: matches no store version"

    p99_f, p99_w = rep_f.slo["p99_ms"], rep_w.slo["p99_ms"]
    ratio = p99_w / max(p99_f, 1e-9)
    # the headline gate: writes ride the idle slot, reads keep their
    # plans — serving a churning store must cost ≤ 1.5x the frozen p99
    assert ratio <= 1.5, (
        f"write-heavy p99 {p99_w:.1f}ms is {ratio:.2f}x the frozen "
        f"{p99_f:.1f}ms (gate 1.5x)"
    )
    _write_csv(
        "pir_ingest_p99",
        ["mode", "arrivals", "p50_ms", "p99_ms", "goodput_qps", "ingests",
         "records_ingested"],
        [
            ("frozen", rep_f.arrivals, rep_f.slo["p50_ms"],
             p99_f, rep_f.slo["goodput_qps"], 0, 0),
            ("write_heavy", rep_w.arrivals, rep_w.slo["p50_ms"],
             p99_w, rep_w.slo["goodput_qps"], ingests,
             int(rep_w.frontend_metrics["records_ingested"])),
        ],
    )
    _bench("pir_ingest_p99", rep_w.arrivals, p99_w / 1e3, p99_f / p99_w)
    return [(
        "pir_ingest_p99", p99_w * 1e3,
        f"write_p99={p99_w:.1f}ms;frozen_p99={p99_f:.1f}ms;"
        f"ratio={ratio:.2f}x;ingests={ingests};compacted={compacted};"
        f"plans_kept={pm['plans_kept']};torn=0",
    )]


# --------------------------------------------- touched-shard ingest row
def sharded_ingest() -> List[Row]:
    """The PR-10 tentpole row: per-ingest cost on the 8-device sharded
    path, touched-shard-only invalidation vs the old full re-shard.
    Runs benchmarks/sharded_ingest_worker.py in a subprocess (the forced
    8-device count must be set before jax imports; this process keeps
    seeing 1). The worker asserts zero torn answers and zero dropped
    plans internally; here we gate the counters — an update burst
    confined to ≤ 25% of the logical shards must report exactly that,
    with most device shards kept by identity — and, at full scale, the
    headline **full re-shard ≥ 2× touched-shard wall** ratio."""
    import subprocess
    import sys

    worker = os.path.join(os.path.dirname(__file__),
                          "sharded_ingest_worker.py")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src")
    )
    env.pop("XLA_FLAGS", None)  # the worker sets its own
    # a forced-CPU simulation: this parent may hold the chip, and a child
    # that reached for it would fail or hang
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, worker] + (["--smoke"] if SMOKE else []),
        capture_output=True, text=True, env=env, timeout=540,
    )
    assert proc.returncode == 0, (
        f"worker failed\nSTDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}"
    )
    r = json.loads(proc.stdout.strip().splitlines()[-1])

    assert r["match"], "modes diverged"
    # invalidation stayed proportional to the burst, not the store
    assert 0 < r["store_shards_touched"] <= r["store_shards_total"] // 4, r
    assert r["mesh_shards_kept"] > 0, r
    assert r["mesh_shards_updated"] < 8, r
    # same-shape bursts: every cached ExecutionPlan survived every swap
    assert r["plans_dropped"] == 0, r
    assert r["plans_kept"] > 0, r
    ratio = r["ratio"]
    if not SMOKE:
        # the acceptance gate: per-burst cost O(touched), not O(n)
        assert ratio >= 2.0, (
            f"touched-shard ingest only {ratio:.2f}x faster than the "
            f"full re-shard (gate 2.0x): {r}"
        )
    _write_csv(
        "sharded_ingest",
        ["mode", "bursts", "wall_s", "shards_touched", "shards_total",
         "mesh_shards_kept", "mesh_shards_updated", "plans_dropped"],
        [
            ("full_reshard", r["bursts"], r["wall_full_s"],
             r["store_shards_total"], r["store_shards_total"], 0, 8, 0),
            ("touched_only", r["bursts"], r["wall_touched_s"],
             r["store_shards_touched"], r["store_shards_total"],
             r["mesh_shards_kept"], r["mesh_shards_updated"],
             r["plans_dropped"]),
        ],
    )
    per_burst = r["wall_touched_s"] / r["bursts"]
    _bench("sharded_ingest", r["burst_rows"], per_burst, ratio)
    return [(
        "sharded_ingest", per_burst * 1e6,
        f"platform={r['platform']};full/touched={ratio:.2f}x;touched_shards="
        f"{r['store_shards_touched']}/{r['store_shards_total']};"
        f"mesh_kept={r['mesh_shards_kept']};plans_dropped=0;torn=0",
    )]


ALL = [
    fig1_direct, fig2_as_direct, fig3_sparse, fig4_as_sparse, fig5_subset,
    fig6_frontier, table1, server_paths, exec_backend_matrix,
    engine_throughput, serve_batched_vs_loop, serve_async_vs_sync,
    dlrm_serving, fleet_scenarios, pir_ingest_p99, sharded_ingest,
]


def main(argv=None) -> None:
    global SMOKE
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes, 1 timing rep (CI guard)")
    ap.add_argument("--only", default="",
                    help="comma-separated figure/row names to regenerate "
                         "(default: all); see results/README.md")
    args = ap.parse_args(argv)
    SMOKE = args.smoke
    use_compile_cache()
    fns = ALL
    if args.only:
        by_name = {fn.__name__: fn for fn in ALL}
        unknown = [n for n in args.only.split(",") if n not in by_name]
        if unknown:
            ap.error(f"unknown --only names {unknown}; "
                     f"choose from {sorted(by_name)}")
        fns = [by_name[n] for n in args.only.split(",")]
    print("name,us_per_call,derived")
    for fn in fns:
        for name, us, derived in fn():
            print(f"{name},{us:.2f},{derived}")
    # machine-readable perf trajectory (schema: row -> {batch, wall_s,
    # speedup}); every row in it is a FULL-scale measurement. Partial
    # (--only) runs MERGE into the existing artifact; smoke runs never
    # write — their tiny-shape 1-rep numbers are not comparable and
    # would be indistinguishable from real rows.
    if SMOKE:
        print(f"# smoke run: {BENCH_JSON} not written "
              f"(smoke rows are not trajectory-comparable)")
    else:
        merged = {}
        # seed from the previous PR's artifact, then let this PR's own
        # rows (older runs first, this run last) override name-by-name
        for path in (PREV_BENCH_JSON, BENCH_JSON):
            if os.path.exists(path):
                with open(path) as f:
                    merged.update(json.load(f))
        merged.update(BENCH)
        with open(BENCH_JSON, "w") as f:
            json.dump(merged, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"# wrote {BENCH_JSON} ({len(merged)} rows)")


if __name__ == "__main__":
    main()
