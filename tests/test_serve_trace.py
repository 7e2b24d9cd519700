"""The served path's spans and wait counters (``repro.serve.trace``).

A profiler trace of a small ``AsyncFrontend`` run holds every span the
path opens, nested as the code nests them and linked by batch id; the
wait counters split a request's life exactly on a fake clock; and
recording a trace changes no answer and no wire bit."""

import glob
import itertools
import time

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import make_scheme
from repro.core.accounting import PrivacyBudget
from repro.db import Delta, VersionedStore, make_synthetic_store
from repro.serve import (
    AsyncFrontend,
    BatchScheduler,
    QueryCache,
    ServingPipeline,
)
from repro.serve.frontend import _SENTINEL
from repro.serve.trace import SPANS, span

SCHEMES = [("chor", dict(d=3, d_a=1)),
           ("sparse", dict(d=4, d_a=2, theta=0.3))]


def record(tmp_path, fn):
    """Run ``fn`` under the profiler; the trace's ``ProfileData``."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    return ProfileData.from_file(path)


def pir_events(profile):
    """Every ``pir.*`` host event: (thread line, name, start, end, stats)."""
    out = []
    for p, plane in enumerate(profile.planes):
        if not plane.name.startswith("/host:"):
            continue
        for li, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("pir."):
                    out.append(((p, li), e.name, e.start_ns,
                                 e.start_ns + e.duration_ns, dict(e.stats)))
    return out


def test_span_names():
    assert len(set(SPANS)) == len(SPANS)
    assert all(name.startswith("pir.") for name in SPANS)
    with span("plan", batch=1) as sp:  # no profiler running: a no-op
        sp.set_metadata(misses=0)


@pytest.mark.parametrize("name,kw", SCHEMES)
def test_a_traced_frontend_run_opens_every_span(tmp_path, name, kw):
    store = make_synthetic_store(128, 16, seed=4)
    live = VersionedStore(store, backend="ref")
    sch = make_scheme(name, **kw)
    pipe = ServingPipeline(
        live, sch,
        scheduler=BatchScheduler(max_batch=4, max_wait_s=0.005,
                                 target_latency_s=10.0),
        cache=QueryCache(sch, store.n),
    )
    queries = [(i * 7) % 128 for i in range(12)]
    tuned = []
    autotune = pipe.autotune_step

    def autotune_step():
        tuned.append(1)
        return autotune()

    # the idle slot's last job: two calls have a sleep of the flush
    # worker between them
    pipe.autotune_step = autotune_step

    def serve():
        with AsyncFrontend(pipe, idle_tick_s=0.001, compact_log_depth=1,
                           double_buffer=True) as fe:
            futs = [fe.submit(f"c{i % 5}", q) for i, q in enumerate(queries)]
            assert fe.drain(timeout=60.0)
            for q, fut in zip(queries, futs):
                np.testing.assert_array_equal(fut.result(timeout=5.0),
                                              store.record_bytes(q))
            fe.ingest(Delta.update(
                [3], np.full((1, 16), 9, dtype=np.uint8)))
            assert fe.drain(timeout=60.0)
            deadline = time.monotonic() + 30.0
            while fe.metrics["compacted"] < 1:
                assert time.monotonic() < deadline, "never compacted"
                time.sleep(0.01)
            del tuned[:]
            while len(tuned) < 2:
                assert time.monotonic() < deadline, "idle slot stuck"
                time.sleep(0.01)

    events = pir_events(record(tmp_path, serve))
    names = {e[1] for e in events}
    assert set(SPANS) <= names, set(SPANS) - names

    answers = [e for e in events if e[1] == "pir.answer"]
    assert answers
    for line, _, lo, hi, stats in answers:
        assert stats["servers"] == sch.d and stats["kind"] == "mask"
        assert stats["n"] == store.n and stats["words"] == store.words
        inside = [e for e in events if e[1] == "pir.answer.server"
                  and e[0] == line and lo <= e[2] and e[3] <= hi]
        assert sorted(e[4]["server"] for e in inside) == list(range(sch.d))

    def ids(span_name):
        return sorted(e[4]["batch"] for e in events if e[1] == span_name)

    assert ids("pir.plan") == ids("pir.execute")
    assert len(set(ids("pir.plan"))) == len(ids("pir.plan"))
    assert set(ids("pir.wait.inflight")) <= set(ids("pir.plan"))
    assert len(ids("pir.finalize")) == len(answers)
    plans = [e[4] for e in events if e[1] == "pir.plan"]
    assert sum(p["requests"] for p in plans) == len(queries)
    assert all(p["bucket"] >= p["misses"] for p in plans)
    admitted = sum(e[4]["items"] for e in events if e[1] == "pir.admit")
    assert admitted == len(queries)


def fake_clock():
    now = [0.0]
    return now, (lambda: now[0])


@pytest.mark.parametrize("multi", [False, True])
def test_queue_dispatch_and_execute_waits_on_a_fake_clock(monkeypatch, multi):
    now, clock = fake_clock()
    store = make_synthetic_store(64, 8, seed=5)
    pipe = ServingPipeline(store, make_scheme("chor", d=2, d_a=1),
                           scheduler=BatchScheduler(max_batch=8, clock=clock))
    real = pipe.backend.answer_batch

    def answer(routed, **kw):
        now[0] += 3.0  # the answer takes 3 s
        return real(routed, **kw)

    monkeypatch.setattr(pipe.backend, "answer_batch", answer)
    submit = (lambda c, i: pipe.submit_many(c, [i, i + 1])) if multi \
        else pipe.submit
    assert submit("a", 1)           # enqueued at 0
    now[0] = 2.0
    assert submit("b", 2)           # enqueued at 2
    now[0] = 5.0
    batch = pipe.take_batch()       # cut at 5: waits 5 + 3
    assert pipe.metrics["queue_wait_s"] == 8.0
    assert pipe.metrics["dequeued"] == 2
    now[0] = 6.0
    planned = pipe.plan_requests(batch)   # planning starts at 6
    now[0] = 10.0
    results = pipe.execute_planned(planned)  # 10 -> 13
    assert pipe.metrics["dispatch_wait_s"] == 2 * 4.0
    assert pipe.metrics["execute_s"] == 2 * 3.0
    assert len(results) == 2
    # the next batch's ids follow
    assert submit("c", 3)
    assert pipe.plan_requests(pipe.take_batch()).batch_id \
        == planned.batch_id + 1


def test_admission_wait_counts_refusals_on_a_fake_clock(monkeypatch):
    # sparse, not chor: chor spends (0, 0) so its budget never exhausts
    now, clock = fake_clock()
    store = make_synthetic_store(64, 8, seed=6)
    sch = make_scheme("sparse", d=4, d_a=2, theta=0.25)
    pipe = ServingPipeline(
        store, sch, scheduler=BatchScheduler(max_batch=8, clock=clock),
        default_budget=lambda: PrivacyBudget(
            epsilon_limit=1.5 * sch.epsilon(store.n)),
    )
    # no worker threads: the test runs the ingest loop itself
    monkeypatch.setattr(AsyncFrontend, "start", lambda self: self)
    fe = AsyncFrontend(pipe, ingest_workers=1)
    now[0] = 1.0
    ok = fe.submit("c", 5)
    now[0] = 2.5
    refused = fe.submit("c", 6)     # over budget: refused at admission
    now[0] = 4.0
    fe._ingest.put(_SENTINEL)
    fe._ingest_loop()               # admits both at 4
    assert fe.metrics["admit_count"] == 2
    assert fe.metrics["admit_wait_s"] == (4.0 - 1.0) + (4.0 - 2.5)
    assert pipe.metrics["refused"] == 1
    with pytest.raises(PermissionError):
        refused.result(timeout=5.0)
    assert not ok.done()            # admitted, waiting for a cut
    fe.close(drain=False)


def test_tracing_changes_no_answer_and_no_wire_bit(tmp_path):
    def serve():
        store = make_synthetic_store(128, 16, seed=7)
        sch = make_scheme("sparse", d=4, d_a=2, theta=0.3)
        pipe = ServingPipeline(store, sch, cache=QueryCache(sch, store.n),
                               seed=11)
        wire = []
        real = pipe.backend.answer_batch

        def answer(routed, **kw):
            wire.append(np.asarray(routed.payload))
            return real(routed, **kw)

        pipe.backend.answer_batch = answer
        answers = []
        for rnd, i in itertools.product(range(2), range(6)):
            pipe.submit(f"c{i}", (i * 11 + rnd) % 128)
            if i % 3 == 2:
                answers += [a for _, a in sorted(pipe.flush().items())]
        return answers, wire

    plain = serve()
    out = {}
    record(tmp_path, lambda: out.setdefault("traced", serve()))
    traced = out["traced"]
    for a, b in zip(plain[0] + plain[1], traced[0] + traced[1]):
        np.testing.assert_array_equal(a, b)
    assert len(plain[0]) == len(traced[0]) == 12
    assert len(plain[1]) == len(traced[1]) > 0
