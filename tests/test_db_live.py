"""The live-store subsystem (DESIGN.md §13): ``Delta`` semantics, the
``VersionedStore`` MVCC contract (``snapshot(v)`` bit-identical to a
store rebuilt from scratch at ``v``), the on-device scatter ingest path
vs the host oracle, incremental invalidation (only shards a delta
touched re-plan; everything else keeps its plans), snapshot-consistent
serving (in-flight batches reconstruct against their pinned snapshot),
the version-keyed cache across an ingest boundary, and the empirical
§2.2 distinguishability game on the post-ingest wire.

Registry-parameterized where the contract is per-scheme: the snapshot
conformance sweep runs every registered scheme × {bare, Anonymized}.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import adversary as adv
from repro.core import make_scheme
from repro.core.accounting import PrivacyBudget
from repro.core.protocol import (
    Anonymized,
    build_scheme,
    registered_schemes,
    staged_retrieve,
)
from repro.db import Delta, VersionedStore, make_synthetic_store, rebuild
from repro.db.live import apply_delta_np
from repro.db.store import RecordStore
from repro.kernels import registered_backends, scatter_update
from repro.serve import (
    AsyncFrontend,
    QueryCache,
    SchemeRouter,
    ServingPipeline,
    scheme_signature,
)

D, D_A = 4, 2
PARAMS = {
    "chor": {},
    "sparse": dict(theta=0.3),
    "direct": dict(p=8),
    "subset": dict(t=3),
}

RNG = np.random.default_rng(20260808)


def _raw(m: int, nbytes: int) -> np.ndarray:
    return RNG.integers(0, 256, size=(m, nbytes), dtype=np.uint8)


def _sparse_pipe(live, *, cache=None, budget=None):
    sch = make_scheme("sparse", d=D, d_a=D_A, theta=0.3)
    kw = {}
    if budget is not None:
        kw["default_budget"] = budget
    return ServingPipeline(live, sch, cache=cache, **kw)


# --------------------------------------------------------------------------
# Delta semantics
# --------------------------------------------------------------------------
def test_delta_constructors_validate():
    with pytest.raises(ValueError, match="unknown delta kind"):
        Delta(kind="upsert")
    with pytest.raises(ValueError, match="payload"):
        Delta(kind="append")  # no raw
    with pytest.raises(ValueError, match="target indices"):
        Delta(kind="update", raw=_raw(1, 8))
    with pytest.raises(ValueError, match="rows != index count"):
        Delta.update([1, 2, 3], _raw(2, 8))


def test_delta_update_dedups_last_write_wins():
    """Duplicate targets keep the final payload — numpy assignment
    semantics, so every backend impl and the replay oracle agree."""
    raw = _raw(4, 8)
    d = Delta.update([5, 9, 5, 9], raw)
    assert d.count == 2
    np.testing.assert_array_equal(d.indices, [5, 9])
    np.testing.assert_array_equal(d.raw, raw[[2, 3]])  # last writes


def test_delta_delete_dedups_and_counts():
    d = Delta.delete([7, 3, 7, 3, 1])
    np.testing.assert_array_equal(d.indices, [1, 3, 7])
    assert d.count == 3
    assert Delta.append(_raw(6, 4)).count == 6


def test_apply_delta_np_oracle_semantics():
    base = _raw(10, 8)
    packed = np.asarray(RecordStore.from_bytes(base).packed)
    bits = 64
    up = apply_delta_np(packed, bits, Delta.update([3], _raw(1, 8)))
    assert (up[3] != packed[3]).any() and (np.delete(up, 3, 0)
                                           == np.delete(packed, 3, 0)).all()
    ap = apply_delta_np(packed, bits, Delta.append(_raw(2, 8)))
    assert ap.shape[0] == 12 and (ap[:10] == packed).all()
    de = apply_delta_np(packed, bits, Delta.delete([0, 9]))
    assert (de[0] == 0).all() and (de[9] == 0).all()
    assert (de[1:9] == packed[1:9]).all()
    with pytest.raises(IndexError, match="out of range"):
        apply_delta_np(packed, bits, Delta.delete([10]))


# --------------------------------------------------------------------------
# VersionedStore: the MVCC contract
# --------------------------------------------------------------------------
def test_snapshot_bit_identical_to_rebuild_at_every_version():
    """The tentpole contract: ``snapshot(v)`` == a store built from
    scratch at ``v``, for EVERY v — retained heads and host-replayed
    evicted ones alike."""
    base = make_synthetic_store(64, 16, seed=3)
    live = VersionedStore(base, shards=8, retain=2, backend="ref")
    deltas = [
        Delta.append(_raw(8, 16)),
        Delta.update([5, 60, 5], _raw(3, 16)),
        Delta.delete([0, 71]),
        Delta.append(_raw(4, 16)),
        Delta.update([70], _raw(1, 16)),
    ]
    for d in deltas:
        live.ingest(d)
    assert live.version == len(deltas) and live.n == 76
    for v in range(live.version + 1):
        want = rebuild(base, deltas[:v])
        got = live.snapshot(v)
        np.testing.assert_array_equal(
            np.asarray(got.packed), np.asarray(want.packed)
        )
        assert got.record_bits == want.record_bits
    # retain=2 evicted the early heads: those came back via host replay
    assert live.metrics["snapshot_rebuilds"] >= 1
    with pytest.raises(ValueError, match="out of range"):
        live.snapshot(live.version + 1)


def test_snapshots_are_frozen_values():
    """Pinning a snapshot is just holding the object: later ingests
    never mutate it (jnp immutability + the frozen RecordStore)."""
    base = make_synthetic_store(32, 8, seed=4)
    live = VersionedStore(base, backend="ref")
    pin = live.snapshot()
    before = np.array(np.asarray(pin.packed), copy=True)
    live.ingest(Delta.update(np.arange(32), _raw(32, 8)))
    live.ingest(Delta.append(_raw(16, 8)))
    np.testing.assert_array_equal(np.asarray(pin.packed), before)
    assert pin.n == 32 and live.n == 48


def test_shard_touch_tracking_is_minimal():
    """Only the shards a delta actually wrote advance their version —
    the invalidation key the serving stack keys re-planning on."""
    live = VersionedStore(
        make_synthetic_store(64, 8, seed=5), shards=8, backend="ref"
    )
    v0 = live.version
    live.ingest(Delta.update([2, 10], _raw(2, 8)))  # shards {2}: 2, 10≡2
    assert live.shards_touched_since(v0) == (2,)
    live.ingest(Delta.delete([5]))
    assert set(live.shards_touched_since(v0)) == {2, 5}
    # appends touch exactly the tail's shards
    v2 = live.version
    live.ingest(Delta.append(_raw(3, 8)))  # rows 64..66 → shards 0,1,2
    assert set(live.shards_touched_since(v2)) == {0, 1, 2}
    assert live.shard_of(64) == 0 and live.shard_of(66) == 2


def test_snapshot_replays_from_nearest_retained_head():
    """Replay cost pin: an evicted ``snapshot(v)`` seeds from the
    nearest retained head below ``v`` and replays exactly the gap —
    after a compaction that head is the rebased base, so the count
    drops to ``v - base_version``, never the full-from-v0 prefix."""
    base = make_synthetic_store(32, 8, seed=7)
    live = VersionedStore(base, shards=4, retain=2, backend="ref")
    deltas = [Delta.update([i], _raw(1, 8)) for i in range(8)]
    for d in deltas[:5]:
        live.ingest(d)
    # heads {0, 4, 5}: v3 is evicted, nearest head below is the v0 base
    got = live.snapshot(3)
    np.testing.assert_array_equal(
        np.asarray(got.packed), np.asarray(rebuild(base, deltas[:3]).packed)
    )
    assert live.metrics["deltas_replayed"] == 3
    # rebase at v5, ingest to v8 (heads {5, 7, 8}): v6 is evicted and
    # its nearest retained head is now the v5 base — ONE delta replays,
    # not six from the original v0 base
    assert live.compact() == 5
    assert live.base_version == 5 and live.log_depth == 0
    for d in deltas[5:]:
        live.ingest(d)
    got = live.snapshot(6)
    np.testing.assert_array_equal(
        np.asarray(got.packed), np.asarray(rebuild(base, deltas[:6]).packed)
    )
    assert live.metrics["deltas_replayed"] == 3 + 1
    assert live.metrics["snapshot_rebuilds"] == 2


def test_compaction_rebases_log_and_preserves_mvcc_contract():
    """``compact()`` == ``rebuild(base, log)`` (oracle-checked inside),
    resets the replay log, keeps absolute shard versions, keeps pinned
    snapshot objects, and makes pre-base versions unreachable by number."""
    base = make_synthetic_store(48, 8, seed=8)
    live = VersionedStore(base, shards=8, backend="ref")
    deltas = [
        Delta.append(_raw(4, 8)),
        Delta.update([5, 50], _raw(2, 8)),
        Delta.delete([0]),
    ]
    for d in deltas:
        live.ingest(d)
    touched_pre = set(live.shards_touched_since(0))
    pin = live.snapshot(2)
    pin_bytes = np.array(np.asarray(pin.packed), copy=True)

    assert live.compact() == 3
    assert live.metrics["compactions"] == 1
    assert live.metrics["compacted_deltas"] == 3
    assert live.version == 3 and live.base_version == 3
    assert live.log_depth == 0
    np.testing.assert_array_equal(
        np.asarray(live.snapshot().packed),
        np.asarray(rebuild(base, deltas).packed),
    )
    # shard versions are absolute: distributed invalidation keyed on
    # shards_touched_since keeps working across the rebase
    assert set(live.shards_touched_since(0)) == touched_pre
    # v2 is unreachable by number, but the pinned object is untouched
    with pytest.raises(ValueError, match="predates the compaction base"):
        live.snapshot(2)
    np.testing.assert_array_equal(np.asarray(pin.packed), pin_bytes)
    # writes keep flowing with absolute version numbering post-rebase
    live.ingest(Delta.update([1], _raw(1, 8)))
    assert live.version == 4 and live.log_depth == 1
    np.testing.assert_array_equal(
        np.asarray(live.snapshot(4).packed),
        np.asarray(rebuild(base, deltas + [live._log[0]]).packed),
    )
    assert live.compact() == 1
    assert live.compact() == 0  # empty log: no-op


@pytest.mark.parametrize("backend", sorted(registered_backends()))
def test_scatter_ingest_matches_host_oracle(backend):
    """Every registered write backend produces bit-identical packed
    words to the numpy replay, for update and delete."""
    base = make_synthetic_store(48, 12, seed=6)
    bits = base.record_bits
    for delta in (
        Delta.update([0, 17, 47], _raw(3, 12)),
        Delta.delete([1, 46]),
    ):
        live = VersionedStore(base, backend=backend)
        live.ingest(delta)
        want = apply_delta_np(np.asarray(base.packed), bits, delta)
        np.testing.assert_array_equal(
            np.asarray(live.snapshot().packed), want
        )


# --------------------------------------------------------------------------
# Snapshot conformance: every scheme × {bare, Anonymized}
# --------------------------------------------------------------------------
def test_conformance_covers_the_whole_registry():
    assert set(PARAMS) == set(registered_schemes())


@pytest.mark.parametrize("name", sorted(PARAMS))
@pytest.mark.parametrize("anon", [False, True])
def test_snapshot_retrieval_conformance(name, anon):
    """For every registered scheme (and its Anonymized wrap): the full
    staged wire against ``snapshot(v)`` is bit-identical to the same
    wire against a store rebuilt from scratch at ``v`` — same key, same
    query, every version."""
    sch = build_scheme(name, d=D, d_a=D_A, **PARAMS[name])
    if anon:
        sch = Anonymized(sch, u=64)
    base = make_synthetic_store(96, 20, seed=7)
    live = VersionedStore(base, shards=8, backend="ref")
    deltas = [
        Delta.update([17, 95], _raw(2, 20)),
        Delta.append(_raw(8, 20)),
        Delta.delete([40]),
    ]
    for d in deltas:
        live.ingest(d)
    key = jax.random.key(11)
    for v in range(live.version + 1):
        snap, scratch = live.snapshot(v), rebuild(base, deltas[:v])
        q = jnp.array([0, 17, 40, snap.n - 1])
        out = np.asarray(staged_retrieve(sch, key, snap, q))
        want = np.asarray(staged_retrieve(sch, key, scratch, q))
        np.testing.assert_array_equal(out, want)
        np.testing.assert_array_equal(
            out, np.asarray(scratch.packed)[np.asarray(q)]
        )


# --------------------------------------------------------------------------
# Incremental invalidation: only touched shards re-plan
# --------------------------------------------------------------------------
def test_update_ingest_keeps_plans_and_refreshes_rows():
    """Mid-traffic ingest of >= 1% of records re-plans only what it
    touched: a same-shape update keeps every banked plan (refreshing the
    touched rows in place); an append drops them. Asserted via the
    planner's plan/precompute call counts."""
    n = 256
    live = VersionedStore(make_synthetic_store(n, 16, seed=8), shards=8)
    pipe = _sparse_pipe(live)
    for c in range(4):
        assert pipe.submit(f"c{c}", 7 * c)
    pipe.flush()  # builds the plans the ingest must preserve
    pm0 = dict(pipe.backend.planner.metrics)
    assert pm0["plans_built"] >= 1

    touched = np.arange(0, n, 64)  # 4 records: >= 1% of n
    pipe.ingest(Delta.update(touched, _raw(len(touched), 16)))
    pm1 = dict(pipe.backend.planner.metrics)
    assert pm1["rebinds"] == pm0["rebinds"] + 1
    assert pm1["plans_kept"] > pm0["plans_kept"]
    assert pm1["plans_dropped"] == pm0["plans_dropped"]  # nothing re-plans
    assert pm1["precompute_full_builds"] == pm0["precompute_full_builds"]
    assert (
        pm1["precompute_rows_refreshed"]
        >= pm0["precompute_rows_refreshed"]
    )
    # the served bits reflect the write
    assert pipe.submit("r", int(touched[1]))
    np.testing.assert_array_equal(
        pipe.flush()["r"], live.snapshot().record_bytes(int(touched[1]))
    )

    # an append changes the operand SHAPE: plans cannot survive
    pipe.ingest(Delta.append(_raw(8, 16)))
    pm2 = dict(pipe.backend.planner.metrics)
    assert pm2["plans_dropped"] > pm1["plans_dropped"]
    assert pipe.submit("t", n + 7)
    np.testing.assert_array_equal(
        pipe.flush()["t"], live.snapshot().record_bytes(n + 7)
    )


def test_append_reprices_privacy():
    """Growing n moves the per-query (ε, δ) for n-dependent schemes
    (Direct-Requests: p draws from n); the pipeline re-prices on the
    shape change so admission charges the post-append price."""
    live = VersionedStore(make_synthetic_store(128, 8, seed=9))
    sch = make_scheme("direct", d=D, d_a=D_A, p=8)
    pipe = ServingPipeline(live, sch)
    eps_before = pipe.price[0]
    pipe.ingest(Delta.append(_raw(64, 8)))
    eps_after = pipe.price[0]
    assert eps_after == pytest.approx(pipe.staged.privacy(192)[0])
    assert eps_after != eps_before


# --------------------------------------------------------------------------
# Snapshot-consistent serving: pinned batches never tear
# --------------------------------------------------------------------------
def test_in_flight_batch_answers_from_its_pinned_snapshot():
    """A batch planned at version v reconstructs against v even when an
    ingest lands between plan and execute — the answer is the pinned
    snapshot's bytes, bit-exact, never a torn mix."""
    live = VersionedStore(make_synthetic_store(64, 8, seed=10))
    pipe = _sparse_pipe(live)
    idx = 5
    pinned_bytes = np.array(live.snapshot().record_bytes(idx), copy=True)
    assert pipe.submit("c", idx)
    planned = pipe.plan_requests(pipe.take_batch())
    assert planned.store_version == 0

    pipe.ingest(Delta.update([idx], _raw(1, 8)))  # lands mid-flight
    new_bytes = live.snapshot().record_bytes(idx)
    assert (np.asarray(new_bytes) != pinned_bytes).any()

    out = {r.client: a for r, a in pipe.execute_planned(planned)}
    np.testing.assert_array_equal(out["c"], pinned_bytes)
    # the NEXT batch plans against the new head and sees the write
    assert pipe.submit("c2", idx)
    np.testing.assert_array_equal(pipe.flush()["c2"], new_bytes)
    assert pipe.store_version == 1


def test_engine_ingest_requires_live_store():
    pipe = _sparse_pipe(make_synthetic_store(32, 8, seed=12))
    assert pipe.live is None
    with pytest.raises(RuntimeError, match="frozen"):
        pipe.ingest(Delta.append(_raw(1, 8)))
    with pytest.raises(RuntimeError, match="frozen"):
        pipe.queue_delta(Delta.append(_raw(1, 8)))


def test_drain_waits_for_the_delta_being_applied():
    """ingest_step takes a delta off the backlog before applying it:
    drain() must not report the backlog done until the delta has landed."""
    import threading

    live = VersionedStore(make_synthetic_store(64, 8, seed=13), shards=8)
    pipe = _sparse_pipe(live)
    started, release = threading.Event(), threading.Event()
    ingest = pipe.ingest

    def slow_ingest(delta):
        started.set()
        assert release.wait(30.0)
        return ingest(delta)

    pipe.ingest = slow_ingest
    with AsyncFrontend(pipe) as fe:
        fe.ingest(Delta.update([5], _raw(1, 8)))
        assert started.wait(30.0)
        assert pipe.pending_deltas == 0
        assert not fe.drain(0.2)
        release.set()
        assert fe.drain(30.0)
        assert fe.metrics["ingested"] == 1
        assert live.version == 1


def test_frontend_applies_deltas_in_idle_slot():
    """Writes ride the flush worker's idle slot: submits and ingests
    interleave through AsyncFrontend, drain() waits out the delta
    backlog, and every future resolves against SOME store version
    (snapshot membership = no torn answers)."""
    live = VersionedStore(make_synthetic_store(64, 8, seed=13), shards=8)
    pipe = _sparse_pipe(live)
    futures = {}
    with AsyncFrontend(pipe) as fe:
        for step in range(3):
            fe.ingest(Delta.update([step, 32 + step], _raw(2, 8)))
            for c in range(4):
                i = int(RNG.integers(0, 64))
                futures[f"s{step}c{c}"] = (i, fe.submit(f"s{step}c{c}", i))
        fe.drain(30.0)
        assert pipe.pending_deltas == 0
        assert fe.metrics["ingested"] == 3
    assert live.version == 3
    history = [
        np.asarray(live.snapshot(v).packed) for v in range(live.version + 1)
    ]
    for name, (i, fut) in futures.items():
        got = np.asarray(fut.result(5.0))
        packed_rows = [h[i] for h in history]
        assert any(
            (np.asarray(live.snapshot(v).record_bytes(i)) == got).all()
            for v in range(live.version + 1)
        ), (name, i, packed_rows)
    assert pipe.metrics["ingests"] == 3
    assert pipe.metrics["records_ingested"] == 6


# --------------------------------------------------------------------------
# Version-keyed cache across the ingest boundary
# --------------------------------------------------------------------------
def test_cache_version_keying_unit():
    """advance_version evicts exactly the touched entries; lookup
    structurally refuses anything older than its index's last write."""
    sch = make_scheme("sparse", d=D, d_a=D_A, theta=0.3)
    cache = QueryCache(sch, 64)
    cache.insert("a", 3, answer=np.ones(4, np.uint8), version=0)
    cache.insert("b", 9, answer=np.ones(4, np.uint8), version=0)
    evicted = cache.advance_version(1, [3])
    assert evicted == 1 and cache.version == 1
    assert cache.lookup("a", 3) is None          # touched: gone
    assert cache.lookup("b", 9) is not None      # untouched: survives
    # an entry stamped with a pinned PRE-write version is refused even
    # if inserted after the advance (in-flight batch insert)
    cache.insert("c", 3, answer=np.ones(4, np.uint8), version=0)
    assert cache.lookup("c", 3) is None
    assert cache.metrics["stale_evictions"] == 2
    # same-shape advance keeps the signature; a new-n signature re-signs
    sig2 = scheme_signature(sch, 96)
    cache.advance_version(2, [], signature=sig2)
    assert cache.signature == sig2


def test_cache_across_ingest_boundary_spends_and_never_serves_stale():
    """The accounting contract survives the boundary: a hit on an
    untouched index spends (ε, δ) exactly like a miss and emits no new
    wire; a query for a touched index can never hit — stale answers are
    structurally impossible."""
    live = VersionedStore(make_synthetic_store(128, 16, seed=14))
    sch = make_scheme("sparse", d=D, d_a=D_A, theta=0.3)
    eps = sch.epsilon(128)
    pipe = ServingPipeline(
        live, sch, cache=QueryCache(sch, 128),
        default_budget=lambda: PrivacyBudget(epsilon_limit=10 * eps),
    )
    assert pipe.submit("c", 7) and pipe.submit("c", 40)
    pipe.flush()
    assert pipe.budget("c").spent_epsilon == pytest.approx(2 * eps)

    pipe.ingest(Delta.update([40], _raw(1, 16)))  # touches 40, not 7

    # untouched index: cache hit, full spend, zero new server work
    batches_before = pipe.metrics["batches"]
    assert pipe.submit("c", 7)
    out = pipe.flush()
    np.testing.assert_array_equal(out["c"], live.snapshot().record_bytes(7))
    assert pipe.metrics["cache_hits"] == 1
    assert pipe.metrics["batches"] == batches_before
    assert pipe.budget("c").spent_epsilon == pytest.approx(3 * eps)

    # touched index: the hit is refused, the fresh answer is the new bytes
    assert pipe.submit("c", 40)
    out = pipe.flush()
    np.testing.assert_array_equal(
        out["c"], live.snapshot().record_bytes(40)
    )
    assert pipe.metrics["cache_hits"] == 1  # unchanged: it missed
    assert pipe.cache.metrics["stale_evictions"] >= 1
    assert pipe.budget("c").spent_epsilon == pytest.approx(4 * eps)


def test_version_stamp_is_index_independent():
    """The wire's ``store_version`` stamp is bookkeeping, not a secret
    channel: every batch planned at the same serving version carries the
    same stamp whatever was asked."""
    live = VersionedStore(make_synthetic_store(64, 8, seed=15))
    pipe = _sparse_pipe(live)
    pipe.ingest(Delta.update([1], _raw(1, 8)))
    stamps = set()
    for i in (0, 1, 63):
        assert pipe.submit(f"c{i}", i)
        planned = pipe.plan_requests(pipe.take_batch())
        stamps.add(planned.routed.store_version)
        pipe.execute_planned(planned)
    assert stamps == {1}


def test_post_ingest_wire_meets_repriced_epsilon_bound():
    """The §2.2 distinguishability game on the wire a *post-append*
    batch actually sends: the empirical ε at the d_a corrupted servers
    must meet the analytic bound at the NEW n — the version-keyed
    serving path re-prices, and the mechanism it ships matches the
    price. (Statistical-privacy check across the ingest boundary.)"""
    n0, grow, theta = 12, 4, 0.3
    live = VersionedStore(make_synthetic_store(n0, 8, seed=16))
    live.ingest(Delta.append(_raw(grow, 8)))
    n = live.n
    sch = make_scheme("sparse", d=D, d_a=D_A, theta=theta)
    router = SchemeRouter(sch)
    q_i, q_j = 2, n - 1  # one pre-existing record, one appended

    def observe(keys, hyp):
        q = q_i if hyp == 0 else q_j

        def one(k):
            routed = router.plan(k, n, jnp.full((1,), q, jnp.int32))
            obs = routed.payload[:D_A, 0, :]
            pi = jnp.sum(obs[:, q_i]) % 2
            pj = jnp.sum(obs[:, q_j]) % 2
            return (2 * pi + pj).astype(jnp.int32)

        return jax.vmap(one)(keys)

    res = adv.run_game(observe, jax.random.key(20260808), trials=4000)
    lr = max(
        res.max_lr(min_count=40),
        adv.GameResult(res.counts_j, res.counts_i, res.trials).max_lr(40),
    )
    emp = math.log(lr) if lr > 0 else 0.0
    assert emp <= sch.epsilon(n) + 0.3, (emp, sch.epsilon(n))
