"""AsyncFrontend behavior: race-freedom under concurrent submitters
(every future resolves to the exact record the sync path would return),
backpressure shedding at the bounded queue, budget refusal surfacing as
PermissionError on the future, deadline-timer cuts, graceful drain and
close semantics, and the asyncio adapter. The privacy side of the front
(admission pricing, cache rules) is tests/test_serve_cache.py and
tests/test_statistical_privacy.py."""

import asyncio
import threading
import time
from concurrent.futures import CancelledError

import numpy as np
import pytest

from repro.core import make_scheme
from repro.core.accounting import PrivacyBudget
from repro.db import make_synthetic_store
from repro.kernels.backend import AutotuneTable
from repro.serve import (
    AsyncFrontend,
    BackpressureError,
    BatchScheduler,
    QueryCache,
    ServingPipeline,
    ShardedBackend,
)


def make_pipe(n=256, cached=False, max_batch=64, max_wait_s=0.0, **kw):
    store = make_synthetic_store(n, 16, seed=7)
    sch = make_scheme("chor", d=2, d_a=1)
    return ServingPipeline(
        store, sch,
        scheduler=BatchScheduler(
            max_batch=max_batch, max_wait_s=max_wait_s, target_latency_s=10.0
        ),
        cache=QueryCache(sch, store.n) if cached else None,
        **kw,
    )


# --------------------------------------------------------- double buffering
@pytest.mark.parametrize("double_buffer", [True, False])
def test_double_buffered_flush_exact_over_many_batches(double_buffer):
    """The double-buffered flush (plan batch k+1 while batch k's
    ExecutionPlan runs) must stay bit-exact across a long run of
    back-to-back batches — same records as the single-threaded flush it
    replaces, every future resolved."""
    pipe = make_pipe(n=512, max_batch=16)
    queries = [(i * 13) % 512 for i in range(160)]
    with AsyncFrontend(
        pipe, ingest_workers=2, queue_limit=1024, shed_policy="block",
        double_buffer=double_buffer,
    ) as fe:
        futs = [fe.submit(f"c{i % 6}", q) for i, q in enumerate(queries)]
        assert fe.drain(timeout=60.0)
        for q, fut in zip(queries, futs):
            np.testing.assert_array_equal(
                fut.result(timeout=5.0), pipe.store.record_bytes(q)
            )
    assert fe.metrics["served"] == len(queries)
    assert fe.metrics["failed"] == 0
    # the engine really cut multiple batches (the overlap was exercised)
    assert pipe.metrics["batches"] >= len(queries) // 16


def test_double_buffer_executor_lifecycle():
    """The one-slot execute stage spins up on start and is torn down by
    close (drain included), with the in-flight batch settled."""
    pipe = make_pipe(n=128, max_batch=8)
    fe = AsyncFrontend(pipe, double_buffer=True).start()
    assert fe._executor is not None
    fut = fe.submit("a", 17)
    fe.close(drain=True)
    np.testing.assert_array_equal(
        fut.result(timeout=5.0), pipe.store.record_bytes(17)
    )
    assert fe._executor is None
    # single-threaded mode never creates the executor
    pipe2 = make_pipe(n=128, max_batch=8)
    fe2 = AsyncFrontend(pipe2, double_buffer=False).start()
    assert fe2._executor is None
    fe2.close()


def test_double_buffer_serve_error_fails_only_that_batch(monkeypatch):
    """An execute-stage failure fails exactly the in-flight batch's
    futures; the flush worker keeps planning and serving later batches."""
    pipe = make_pipe(n=64, max_batch=4)
    boom = {"armed": True}
    real = pipe.execute_planned

    def flaky(planned):
        if boom.pop("armed", False):
            raise RuntimeError("kernel exploded")
        return real(planned)

    monkeypatch.setattr(pipe, "execute_planned", flaky)
    with AsyncFrontend(
        pipe, queue_limit=64, shed_policy="block", double_buffer=True
    ) as fe:
        first = [fe.submit(f"a{i}", i) for i in range(4)]
        assert fe.drain(timeout=30.0)
        second = [fe.submit(f"b{i}", i) for i in range(4)]
        assert fe.drain(timeout=30.0)
    failed = sum(1 for f in first if f.exception() is not None)
    assert failed == 4  # the armed batch failed as a unit
    for i, f in enumerate(second):
        np.testing.assert_array_equal(
            f.result(timeout=5.0), pipe.store.record_bytes(i)
        )
    assert fe.metrics["failed"] == 4


# ------------------------------------------------------------- concurrency
@pytest.mark.parametrize("cached", [False, True])
def test_concurrent_submitters_get_exact_records(cached):
    """Race-freedom and determinism vs the sync path: 8 threads submit
    interleaved queries; every future must resolve to precisely the
    record bytes `store.record_bytes(idx)` — the same answer the
    synchronous submit+flush loop returns (PIR retrieval is exact, so
    equality of answers is the determinism contract; arrival order may
    differ, results may not)."""
    pipe = make_pipe(cached=cached)
    n_threads, per = 8, 24
    results = [[None] * per for _ in range(n_threads)]

    with AsyncFrontend(pipe, ingest_workers=3, queue_limit=1024,
                       shed_policy="block") as fe:
        def feed(s):
            futs = [fe.submit(f"s{s}-c{j % 4}", (s * 37 + j * 11) % pipe.store.n)
                    for j in range(per)]
            for j, f in enumerate(futs):
                results[s][j] = f.result(timeout=30.0)

        threads = [threading.Thread(target=feed, args=(s,))
                   for s in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60.0)
        assert not any(t.is_alive() for t in threads)

    for s in range(n_threads):
        for j in range(per):
            idx = (s * 37 + j * 11) % pipe.store.n
            np.testing.assert_array_equal(
                results[s][j], pipe.store.record_bytes(idx)
            )
    m = fe.metrics
    assert m["served"] == n_threads * per
    assert m["shed"] == 0 and m["failed"] == 0


def test_drain_forces_partial_batches_and_keeps_accepting():
    pipe = make_pipe()  # no deadline: only fullness or drain cuts
    with AsyncFrontend(pipe, ingest_workers=1) as fe:
        futs = [fe.submit("c", i) for i in range(5)]  # far below target
        assert fe.drain(timeout=30.0)
        assert all(f.done() for f in futs)
        for i, f in enumerate(futs):
            np.testing.assert_array_equal(
                f.result(), pipe.store.record_bytes(i)
            )
        # still open for business after a drain (no deadline is set, so
        # a lone request again waits for the next drain to cut it)
        late = fe.submit("c", 9)
        assert fe.drain(timeout=30.0)
        np.testing.assert_array_equal(
            late.result(), pipe.store.record_bytes(9)
        )


def test_deadline_timer_cuts_partial_batch_without_drain():
    """With max_wait_s set, the flush worker's deadline timer serves a
    lone request by itself — no drain, no fullness."""
    pipe = make_pipe(max_wait_s=0.05)
    with AsyncFrontend(pipe, ingest_workers=1) as fe:
        fut = fe.submit("c", 3)
        np.testing.assert_array_equal(
            fut.result(timeout=30.0), pipe.store.record_bytes(3)
        )


# ------------------------------------------------------------ backpressure
def _parked_frontend(monkeypatch, queue_limit, shed_policy):
    """Frontend whose workers are parked (start patched to a no-op), so
    the bounded ingest queue fills deterministically. Call
    ``monkeypatch.undo()`` then ``fe.start()`` to let it run for real."""
    monkeypatch.setattr(AsyncFrontend, "start", lambda self: self)
    pipe = make_pipe()
    return AsyncFrontend(pipe, ingest_workers=1, queue_limit=queue_limit,
                         shed_policy=shed_policy)


def test_reject_policy_sheds_when_queue_full(monkeypatch):
    fe = _parked_frontend(monkeypatch, 2, "reject")
    queued = [fe.submit("c", i) for i in (0, 1)]  # fills the queue
    with pytest.raises(BackpressureError):
        fe.submit("c", 2)
    assert fe.metrics["shed"] == 1
    assert fe.metrics["accepted"] == 2  # the shed submit was never counted
    monkeypatch.undo()  # un-park: real workers drain the backlog
    fe.start()
    try:
        assert fe.drain(timeout=30.0)
        for i, f in enumerate(queued):
            np.testing.assert_array_equal(
                f.result(), fe.pipeline.store.record_bytes(i)
            )
    finally:
        fe.close()


def test_block_policy_waits_for_room(monkeypatch):
    fe = _parked_frontend(monkeypatch, 1, "block")
    fe.submit("c", 0)  # queue now full
    blocked_done = threading.Event()

    def blocked_submit():
        fe.submit("c", 1)  # must wait for room, not raise
        blocked_done.set()

    t = threading.Thread(target=blocked_submit, daemon=True)
    t.start()
    time.sleep(0.05)
    assert not blocked_done.is_set()  # genuinely blocked on the queue
    monkeypatch.undo()  # un-park: the workers make room
    fe.start()
    try:
        assert blocked_done.wait(timeout=30.0)
        t.join(timeout=10.0)
        assert fe.drain(timeout=30.0)
        assert fe.metrics["shed"] == 0 and fe.metrics["served"] == 2
    finally:
        fe.close()


# ---------------------------------------------------------------- refusals
def test_budget_refusal_resolves_future_with_permission_error():
    # sparse, not chor: chor spends (0, 0) so its budget never exhausts
    store = make_synthetic_store(128, 16, seed=8)
    sch = make_scheme("sparse", d=4, d_a=2, theta=0.25)
    pipe = ServingPipeline(
        store, sch,
        scheduler=BatchScheduler(
            max_batch=16, max_wait_s=0.02, target_latency_s=10.0
        ),
        default_budget=lambda: PrivacyBudget(
            epsilon_limit=1.5 * sch.epsilon(store.n)
        ),
    )
    with AsyncFrontend(pipe, ingest_workers=1) as fe:
        ok, refused = fe.submit("c", 5), fe.submit("c", 6)
        assert fe.drain(timeout=30.0)
        np.testing.assert_array_equal(ok.result(), store.record_bytes(5))
        with pytest.raises(PermissionError):
            refused.result()
        # an unrelated client is unaffected
        np.testing.assert_array_equal(
            fe.submit("d", 6).result(timeout=30.0), store.record_bytes(6)
        )
    assert pipe.metrics["refused"] == 1


def test_serve_error_fails_batch_but_front_survives(monkeypatch):
    pipe = make_pipe(max_wait_s=0.02)
    boom = {"armed": True}
    orig = pipe.serve_requests

    def flaky(batch):
        if boom["armed"]:
            boom["armed"] = False
            raise RuntimeError("replica fire")
        return orig(batch)

    monkeypatch.setattr(pipe, "serve_requests", flaky)
    # single-threaded flush is the path that calls serve_requests inline;
    # the double-buffered equivalent is
    # test_double_buffer_serve_error_fails_only_that_batch
    with AsyncFrontend(pipe, ingest_workers=1, double_buffer=False) as fe:
        bad = fe.submit("c", 1)
        assert fe.drain(timeout=30.0)
        with pytest.raises(RuntimeError, match="replica fire"):
            bad.result()
        good = fe.submit("c", 2)
        np.testing.assert_array_equal(
            good.result(timeout=30.0), pipe.store.record_bytes(2)
        )
    assert fe.metrics["failed"] == 1 and fe.metrics["served"] == 1


# ------------------------------------------------------------------- close
def test_close_without_drain_cancels_unserved(monkeypatch):
    fe = _parked_frontend(monkeypatch, 8, "reject")
    stranded = [fe.submit("c", i) for i in (1, 2, 3)]
    monkeypatch.undo()
    fe.close(drain=False)
    for f in stranded:
        assert f.done()
        with pytest.raises(CancelledError):
            f.result()
    with pytest.raises(RuntimeError, match="closed"):
        fe.submit("c", 4)


def test_context_manager_drains_on_clean_exit():
    pipe = make_pipe()
    with AsyncFrontend(pipe, ingest_workers=2) as fe:
        futs = [fe.submit(f"c{i}", i) for i in range(7)]
    # __exit__ drained: every accepted future is resolved, exactly
    for i, f in enumerate(futs):
        np.testing.assert_array_equal(f.result(), pipe.store.record_bytes(i))


# ----------------------------------------------------------------- asyncio
def test_asubmit_from_event_loop():
    pipe = make_pipe(max_wait_s=0.02)

    async def drive(fe):
        answers = await asyncio.gather(
            *(fe.asubmit(f"c{i % 3}", i * 5) for i in range(6))
        )
        return answers

    with AsyncFrontend(pipe, ingest_workers=2) as fe:
        answers = asyncio.run(drive(fe))
    for i, a in enumerate(answers):
        np.testing.assert_array_equal(a, pipe.store.record_bytes(i * 5))


# ----------------------------------------------------- close deadline clock
def test_close_deadline_runs_on_scheduler_clock(monkeypatch):
    """close(drain=False)'s bounded wait for stuck block-policy
    submitters must run on the scheduler's injected clock, scaled by
    drain_timeout_s — not a hardcoded wall-clock second. Regression: a
    fake clock that jumps past the deadline must let close return
    immediately even while a submitter is permanently unsettled."""
    ticks = {"n": 0}

    def fake_clock():
        ticks["n"] += 1
        return float(ticks["n"])  # each read advances a full second

    pipe = make_pipe()
    pipe.scheduler.clock = fake_clock
    monkeypatch.setattr(AsyncFrontend, "start", lambda self: self)
    fe = AsyncFrontend(pipe, ingest_workers=1, queue_limit=4,
                       shed_policy="block", drain_timeout_s=2.0)
    with fe._cv:
        fe._unadmitted += 1  # a submitter that will never settle
    t0 = time.monotonic()
    fe.close(drain=False)
    wall = time.monotonic() - t0
    # the fake clock blows through the 2.0s budget in a couple of reads;
    # the old hardcoded `time.monotonic() + 1.0` made this take >= 1s
    assert wall < 0.5
    assert ticks["n"] >= 2  # the deadline really consulted the injected clock


def test_drain_timeout_must_be_positive():
    pipe = make_pipe()
    with pytest.raises(ValueError, match="drain_timeout_s"):
        AsyncFrontend(pipe, drain_timeout_s=0.0)


# ----------------------------------------------------- idle-slot autotune
def _fresh_autotune_pipe(n=256):
    store = make_synthetic_store(n, 16, seed=7)
    sch = make_scheme("chor", d=2, d_a=1)
    return ServingPipeline(
        store, sch, backend=ShardedBackend(store, autotune=AutotuneTable())
    )


def test_cold_cell_serve_never_microbenchmarks_on_request_path():
    """The first request to hit a cold autotune cell must be planned from
    the analytic prior alone — zero microbenchmark calls on the
    ingest/flush threads. The cell is queued for the idle slot instead
    (DESIGN.md §Execution backends)."""
    pipe = _fresh_autotune_pipe()
    calls = []
    real = pipe.backend.planner._measure

    def counting(fn, *args, **kw):
        calls.append(kw.get("candidate"))
        return real(fn, *args, **kw)

    pipe.backend.planner._measure = counting
    with AsyncFrontend(pipe, autotune=False) as fe:
        fut = fe.submit("a", 5)
        assert fe.drain(timeout=30.0)
        np.testing.assert_array_equal(
            fut.result(timeout=5.0), pipe.store.record_bytes(5)
        )
        assert calls == []  # the serve path consulted only the prior
    assert len(pipe.backend.planner.pending()) == 1  # queued for idle slot


def test_idle_slot_compacts_live_store_past_depth():
    """With ``compact_log_depth`` set, the flush worker's idle slot
    rebases the live store's delta log onto a new frozen base once it
    passes the threshold — counted in the "compacted" metric, serving
    answers unchanged, and never rebased below the threshold."""
    from repro.db import Delta, VersionedStore

    store = make_synthetic_store(128, 16, seed=9)
    live = VersionedStore(store, backend="ref")
    sch = make_scheme("chor", d=2, d_a=1)
    pipe = ServingPipeline(live, sch)
    rng = np.random.default_rng(1)
    with AsyncFrontend(
        pipe, idle_tick_s=0.001, compact_log_depth=3
    ) as fe:
        for _ in range(4):
            fe.ingest(Delta.append(
                rng.integers(0, 256, size=(8, 16), dtype=np.uint8)
            ))
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            if fe.metrics["compacted"] >= 1:
                break
            time.sleep(0.01)
        assert fe.metrics["compacted"] >= 1
        assert live.base_version >= 3 and live.log_depth < 3
        assert live.metrics["compacted_deltas"] >= 3
        # serving against the rebased store stays exact
        fut = fe.submit("a", 140)
        assert fe.drain(timeout=30.0)
        np.testing.assert_array_equal(
            fut.result(timeout=5.0), live.snapshot().record_bytes(140)
        )


def test_compact_log_depth_validates_and_defaults_off():
    pipe = make_pipe()
    with pytest.raises(ValueError, match="compact_log_depth"):
        AsyncFrontend(pipe, compact_log_depth=0)
    with AsyncFrontend(pipe) as fe:
        assert fe.compact_log_depth is None
        time.sleep(0.05)
        assert fe.metrics["compacted"] == 0  # frozen store: never fires


def test_idle_slot_runs_autotune_step_and_counts():
    """Between flushes the worker spends lulls on the autotune search:
    the cold cell left by the first serve gets its measured winner off
    the serving path, and the "autotuned" counter records the step."""
    pipe = _fresh_autotune_pipe()
    with AsyncFrontend(pipe, idle_tick_s=0.001) as fe:
        fut = fe.submit("a", 5)
        assert fe.drain(timeout=30.0)
        np.testing.assert_array_equal(
            fut.result(timeout=5.0), pipe.store.record_bytes(5)
        )
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            if fe.metrics["autotuned"] and not pipe.backend.planner.pending():
                break
            time.sleep(0.01)
        assert fe.metrics["autotuned"] >= 1
    assert not pipe.backend.planner.pending()
    assert any(
        entry["source"] == "measured"
        for _, entry in pipe.backend.planner.table.items()
    )


def test_idle_slot_error_is_recorded_and_raised_on_close(monkeypatch):
    """An idle-slot job that raises (here: an autotune candidate the
    compiler refuses) must not take the flush worker down: later
    submits still resolve, the error and the counter say what failed,
    and close() re-raises it instead of exiting cleanly."""
    pipe = make_pipe(n=128, max_batch=8)

    def refused(*_a, **_kw):
        raise ValueError("candidate refused")

    monkeypatch.setattr(pipe, "autotune_step", refused)
    fe = AsyncFrontend(pipe, idle_tick_s=0.001).start()
    deadline = time.monotonic() + 30.0
    while fe.idle_error is None and time.monotonic() < deadline:
        time.sleep(0.01)
    assert isinstance(fe.idle_error, ValueError)
    assert fe.metrics["idle_failed"] == 1
    fut = fe.submit("a", 17)
    assert fe.drain(timeout=30.0)
    np.testing.assert_array_equal(
        fut.result(timeout=5.0), pipe.store.record_bytes(17)
    )
    with pytest.raises(RuntimeError, match="idle-slot job failed"):
        fe.close(drain=True)


def _live_pipe(n=128):
    from repro.db import VersionedStore

    live = VersionedStore(make_synthetic_store(n, 16, seed=9), backend="ref")
    return ServingPipeline(live, make_scheme("chor", d=2, d_a=1)), live


def _wait_for_idle_error(fe, timeout_s=30.0):
    deadline = time.monotonic() + timeout_s
    while fe.idle_error is None and time.monotonic() < deadline:
        time.sleep(0.01)
    assert fe.idle_error is not None


def test_failed_autotune_step_keeps_ingest_running(monkeypatch):
    """A failing performance job stops only itself: a delta ingested
    after an autotune step raised is still applied, drain() waits for
    it, and the next read serves the new row."""
    from repro.db import Delta

    pipe, live = _live_pipe()

    def refused(*_a, **_kw):
        raise ValueError("candidate refused")

    monkeypatch.setattr(pipe, "autotune_step", refused)
    fe = AsyncFrontend(pipe, idle_tick_s=0.001).start()
    _wait_for_idle_error(fe)
    row = np.arange(16, dtype=np.uint8)[None, :] + 100
    fe.ingest(Delta.update([5], row))
    assert fe.drain(timeout=30.0)
    assert pipe.pending_deltas == 0 and fe.metrics["ingested"] == 1
    fut = fe.submit("a", 5)
    assert fe.drain(timeout=30.0)
    np.testing.assert_array_equal(fut.result(timeout=5.0), row[0])
    assert fe.metrics["idle_failed"] == 1
    with pytest.raises(RuntimeError, match="autotune"):
        fe.close(drain=True)


def test_failed_ingest_is_raised_not_acknowledged(monkeypatch):
    """A delta that fails to apply is never reported as drained: drain()
    and every later ingest() raise, while reads keep being served."""
    from repro.db import Delta

    pipe, live = _live_pipe()

    def broken(*_a, **_kw):
        raise ValueError("delta rejected")

    monkeypatch.setattr(pipe, "ingest", broken)
    fe = AsyncFrontend(pipe, idle_tick_s=0.001, autotune=False).start()
    fe.ingest(Delta.update([5], np.zeros((1, 16), np.uint8)))
    _wait_for_idle_error(fe)
    with pytest.raises(RuntimeError, match="delta failed to apply"):
        fe.drain(timeout=30.0)
    with pytest.raises(RuntimeError, match="ingest is stopped"):
        fe.ingest(Delta.update([6], np.zeros((1, 16), np.uint8)))
    fut = fe.submit("a", 5)
    with pytest.raises(RuntimeError, match="delta failed to apply"):
        fe.drain(timeout=30.0)
    np.testing.assert_array_equal(
        fut.result(timeout=5.0), live.snapshot().record_bytes(5)
    )
    with pytest.raises(RuntimeError, match="ingest"):
        fe.close(drain=True)


def test_close_reports_a_stuck_worker_within_its_timeout(monkeypatch):
    """A worker stuck in a job (a hung device call) makes close() raise
    at its timeout instead of waiting forever."""
    pipe = make_pipe(n=128, max_batch=8)
    entered, release = threading.Event(), threading.Event()

    def hung(*_a, **_kw):
        entered.set()
        release.wait(30.0)
        return 0

    monkeypatch.setattr(pipe, "autotune_step", hung)
    fe = AsyncFrontend(pipe, idle_tick_s=0.001).start()
    assert entered.wait(30.0)
    t0 = time.monotonic()
    try:
        with pytest.raises(RuntimeError, match="still running"):
            fe.close(timeout=0.5)
        assert time.monotonic() - t0 < 10.0
    finally:
        release.set()
