"""Cross-batch cache behavior: the precompute/assemble split is
bit-identical to inline planning, the per-(client, index) memo enforces
its structural privacy rule (no reuse across distinct client queries),
and — the accounting contract — a cache hit spends (ε, δ) exactly like a
miss, so exhausted clients are refused even when their answer is cached.
The statistical side (replayed query vectors leak no more than the one
query they priced) lives in tests/test_statistical_privacy.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import make_scheme
from repro.core.accounting import PrivacyBudget
from repro.db import make_synthetic_store
from repro.serve import (
    BatchScheduler,
    QueryCache,
    SchemeRouter,
    ServingPipeline,
    scheme_signature,
)


# ------------------------------------------------ precompute/assemble split
@pytest.mark.parametrize("name,kw", [
    ("chor", {}),
    ("sparse", dict(theta=0.3)),
    ("as-sparse", dict(theta=0.3, u=16)),
    ("subset", dict(t=3)),
])
def test_plan_from_pre_bit_identical(name, kw):
    """plan(key) == plan(key, pre=precompute(key)) — the banked-randomness
    serving path changes zero wire bits, so every Security-Theorem proof
    about the inline path transfers verbatim."""
    router = SchemeRouter(make_scheme(name, d=4, d_a=2, **kw))
    key = jax.random.key(11)
    q = jnp.array([3, 9, 1, 7])
    inline = router.plan(key, 64, q)
    from_pre = router.plan(key, 64, q, pre=router.precompute(key, 64, 4))
    np.testing.assert_array_equal(
        np.asarray(inline.payload), np.asarray(from_pre.payload)
    )
    assert inline.servers == from_pre.servers


def test_direct_has_no_precompute_half():
    router = SchemeRouter(make_scheme("direct", d=4, d_a=2, p=8))
    key = jax.random.key(0)
    assert router.precompute(key, 64, 4) is None
    with pytest.raises(ValueError, match="no precompute"):
        router.plan(key, 64, jnp.array([1]), pre=object())


def test_pre_wrong_store_size_rejected():
    router = SchemeRouter(make_scheme("chor", d=3, d_a=1))
    key = jax.random.key(1)
    pre = router.precompute(key, 64, 2)
    with pytest.raises(ValueError, match="pre built for n=64"):
        router.plan(key, 128, jnp.array([1, 2]), pre=pre)


# ---------------------------------------------------------- the memo (L1)
def test_memo_key_is_client_and_index():
    """The structural privacy rule: cached randomness is only ever
    returned for exactly the (client, index) that created it."""
    sch = make_scheme("sparse", d=4, d_a=2, theta=0.25)
    cache = QueryCache(sch, 128)
    cols = np.ones((4, 128), np.uint8)
    cache.insert("alice", 7, answer=np.arange(4, dtype=np.uint8),
                 query_cols=cols)
    hit = cache.lookup("alice", 7)
    assert hit is not None and hit.query_cols is cols  # bit-identical replay
    assert cache.lookup("bob", 7) is None        # cross-client: never
    assert cache.lookup("alice", 8) is None      # cross-index: never
    assert cache.metrics == {**cache.metrics, "hits": 1, "misses": 2}


def test_memo_lru_eviction_and_query_vector_cap():
    sch = make_scheme("chor", d=2, d_a=1)
    cache = QueryCache(sch, 64, max_entries=2, max_query_vector_bytes=8)
    big = np.zeros((2, 64), np.uint8)  # 128 B > cap -> dropped
    cache.insert("a", 1, answer=np.zeros(4, np.uint8), query_cols=big)
    assert cache.lookup("a", 1).query_cols is None
    cache.insert("b", 2, answer=np.zeros(4, np.uint8))
    cache.lookup("a", 1)  # touch: "a" is now most recent
    cache.insert("c", 3, answer=np.zeros(4, np.uint8))  # evicts "b"
    assert cache.lookup("b", 2) is None
    assert cache.lookup("a", 1) is not None
    assert cache.metrics["evictions"] == 1
    assert len(cache) == 2


def test_pre_pool_is_single_use_and_bounded():
    sch = make_scheme("chor", d=2, d_a=1)
    cache = QueryCache(sch, 64, max_pre_batches=2)
    assert cache.take_pre(8) is None
    assert cache.put_pre(8, "pre0") and cache.put_pre(8, "pre1")
    assert not cache.put_pre(8, "pre2")  # over cap: dropped, not queued
    assert cache.pre_depth(8) == 2
    assert cache.take_pre(8) == "pre0"  # FIFO, and popped for good
    assert cache.take_pre(8) == "pre1"
    assert cache.take_pre(8) is None    # single-use: nothing comes back
    assert cache.metrics["pre_dropped"] == 1
    cache.put_pre(8, "pre3")
    cache.invalidate()
    assert cache.pre_depth(8) == 0 and len(cache) == 0


def test_pipeline_rejects_mismatched_cache():
    store = make_synthetic_store(64, 8, seed=0)
    sch = make_scheme("chor", d=2, d_a=1)
    other = QueryCache(make_scheme("chor", d=3, d_a=1), store.n)
    with pytest.raises(ValueError, match="cache built for"):
        ServingPipeline(store, sch, cache=other)
    assert scheme_signature(sch, store.n) != other.signature


# --------------------------------------------- budget-aware serving (ε, δ)
def test_cache_hit_spends_budget_identically_to_miss():
    """Admission charges before the cache is consulted: two identical
    queries cost 2ε even though the second never touches a server, and
    the third is refused despite its answer sitting in cache."""
    store = make_synthetic_store(128, 16, seed=1)
    sch = make_scheme("sparse", d=4, d_a=2, theta=0.25)
    eps = sch.epsilon(store.n)
    pipe = ServingPipeline(
        store, sch, cache=QueryCache(sch, store.n),
        default_budget=lambda: PrivacyBudget(epsilon_limit=2.5 * eps),
    )
    assert pipe.submit("c", 7)
    out1 = pipe.flush()
    spent_after_miss = pipe.budget("c").spent_epsilon
    assert spent_after_miss == pytest.approx(eps)

    assert pipe.submit("c", 7)  # same (client, index): will hit
    out2 = pipe.flush()
    assert pipe.budget("c").spent_epsilon == pytest.approx(2 * eps)
    assert pipe.metrics["cache_hits"] == 1
    np.testing.assert_array_equal(out1["c"], out2["c"])
    np.testing.assert_array_equal(out2["c"], store.record_bytes(7))

    # exhausted: refused even though the answer is cached
    assert not pipe.submit("c", 7)
    assert pipe.metrics["refused"] == 1
    # other clients are unaffected (and get their own fresh randomness)
    assert pipe.submit("other", 7)


def test_cache_hit_touches_no_server():
    store = make_synthetic_store(128, 16, seed=2)
    sch = make_scheme("sparse", d=4, d_a=2, theta=0.3)
    pipe = ServingPipeline(store, sch, cache=QueryCache(sch, store.n))
    pipe.submit("c", 42)
    pipe.flush()
    served_batches = pipe.metrics["batches"]
    paths = dict(pipe.backend.path_counts)

    pipe.submit("c", 42)
    out = pipe.flush()  # pure hit: no routing, no backend, no padding
    np.testing.assert_array_equal(out["c"], store.record_bytes(42))
    assert pipe.metrics["batches"] == served_batches
    assert pipe.backend.path_counts == paths
    assert pipe.metrics["cache_hits"] == 1


def test_memoized_query_cols_match_wire_payload():
    """The memo stores the exact per-server columns that went on the wire
    — a replay is provably bit-identical, not just distributionally so."""
    store = make_synthetic_store(64, 8, seed=3)
    sch = make_scheme("chor", d=3, d_a=1)
    cache = QueryCache(sch, store.n)
    pipe = ServingPipeline(store, sch, cache=cache, seed=9)
    pipe.submit("u", 13)
    pipe.flush()
    entry = cache.lookup("u", 13)
    assert entry is not None and entry.query_cols is not None
    cols = entry.query_cols  # [d, n] mask bits for this query's slot
    assert cols.shape == (3, store.n)
    # the masks XOR to one-hot(13): that is the Chor correctness invariant
    folded = np.bitwise_xor.reduce(cols % 2, axis=0)
    expect = np.zeros(store.n, np.uint8)
    expect[13] = 1
    np.testing.assert_array_equal(folded, expect)


def test_prefill_then_serve_consumes_pre_and_is_exact():
    store = make_synthetic_store(256, 16, seed=4)
    sch = make_scheme("sparse", d=4, d_a=2, theta=0.25)
    cache = QueryCache(sch, store.n)
    pipe = ServingPipeline(
        store, sch, cache=cache, scheduler=BatchScheduler(max_batch=8)
    )
    assert pipe.prefill_cache(4) == 1
    assert cache.pre_depth(4) == 1
    for i, q in enumerate((3, 99, 200)):
        pipe.submit(f"c{i}", q)
    out = pipe.flush()  # 3 misses pad to bucket 4 -> consumes the pre
    assert cache.metrics["pre_used"] == 1 and cache.pre_depth(4) == 0
    for i, q in enumerate((3, 99, 200)):
        np.testing.assert_array_equal(out[f"c{i}"], store.record_bytes(q))


# ------------------------------------------- refusal memo (negative L1)
def _counting_budget(budget):
    """Wrap can_spend to count accountant consultations."""
    calls = {"n": 0}
    orig = budget.can_spend

    def counted(*a, **kw):
        calls["n"] += 1
        return orig(*a, **kw)

    budget.can_spend = counted
    return calls


def test_refusal_memo_skips_accountant_and_never_spends():
    """Once a client's budget refuses, repeated over-budget polls are
    refused from the memo without re-consulting the accountant — and no
    refusal, memoized or not, ever spends budget."""
    store = make_synthetic_store(64, 8, seed=7)
    sch = make_scheme("sparse", d=4, d_a=2, theta=0.25)
    eps = sch.epsilon(store.n)
    pipe = ServingPipeline(
        store, sch, cache=QueryCache(sch, store.n),
        default_budget=lambda: PrivacyBudget(epsilon_limit=1.5 * eps),
    )
    assert pipe.submit("c", 1)  # the one affordable query
    calls = _counting_budget(pipe.budget("c"))

    assert not pipe.submit("c", 2)  # consults the accountant, memoizes
    assert calls["n"] == 1
    for i in range(5):
        assert not pipe.submit("c", 3 + i)  # memo: accountant untouched
    assert calls["n"] == 1
    assert pipe.metrics["refused"] == 6
    assert pipe.cache.metrics["refusal_hits"] == 5
    assert pipe.cache.metrics["refusals_noted"] == 1
    # refusals — first or memoized — never spend budget
    assert pipe.budget("c").spent_epsilon == pytest.approx(eps)
    # the memo is per client
    assert pipe.submit("other", 1)
    # invalidate clears the memo: the accountant is consulted again (and
    # still refuses — budgets are monotone)
    pipe.cache.invalidate()
    assert not pipe.submit("c", 9)
    assert calls["n"] == 2
    assert pipe.budget("c").spent_epsilon == pytest.approx(eps)


def test_refusals_without_cache_recheck_every_time():
    """No cache, no memo: the legacy behavior — every refused submit
    re-consults the accountant (and still never spends)."""
    store = make_synthetic_store(64, 8, seed=8)
    sch = make_scheme("chor", d=2, d_a=1)
    pipe = ServingPipeline(
        store, sch,
        default_budget=lambda: PrivacyBudget(
            epsilon_limit=0.0, delta_limit=0.0
        ),
    )
    # chor is free (ε=0, δ=0): force refusals with a spent-out budget
    pipe.budget("c").spent_epsilon = 1.0
    pipe._eps_per_query = 0.5
    calls = _counting_budget(pipe.budget("c"))
    for _ in range(3):
        assert not pipe.submit("c", 1)
    assert calls["n"] == 3
    assert pipe.metrics["refused"] == 3


def test_refusal_memo_bounded():
    sch = make_scheme("chor", d=2, d_a=1)
    cache = QueryCache(sch, 64, max_refusal_entries=2)
    tok = (1.0, 0.0, 1.0, 0.0)
    for c in ("a", "b", "c"):
        cache.note_refusal(c, tok)
    assert not cache.refused("a", tok)  # LRU-evicted, memo stays bounded
    assert cache.refused("b", tok) and cache.refused("c", tok)
    assert not cache.refused("b", (2.0, 0.0, 1.0, 0.0))  # changed state: miss


def test_refusal_memo_never_stale_on_topup_or_cache_reuse():
    """The memo is keyed on the budget-state token, so it cannot wrongly
    refuse after the budget side changes: an in-place top-up re-consults
    the accountant and admits, and a fresh pipeline reusing the same
    cache never inherits another budget's refusals."""
    store = make_synthetic_store(64, 8, seed=9)
    sch = make_scheme("sparse", d=4, d_a=2, theta=0.25)
    eps = sch.epsilon(store.n)
    cache = QueryCache(sch, store.n)
    pipe = ServingPipeline(
        store, sch, cache=cache,
        default_budget=lambda: PrivacyBudget(epsilon_limit=0.5 * eps),
    )
    assert not pipe.submit("c", 1)  # refused and memoized immediately
    assert not pipe.submit("c", 1)
    assert cache.metrics["refusal_hits"] == 1

    # in-place top-up (PrivacyBudget is mutable): must admit, not memo-hit
    pipe.budget("c").epsilon_limit = 1.5 * eps
    assert pipe.submit("c", 1)
    assert pipe.budget("c").spent_epsilon == pytest.approx(eps)

    # a new pipeline reusing the cache: fresh budgets, no inherited refusals
    pipe2 = ServingPipeline(store, sch, cache=cache)  # infinite default
    assert not pipe.submit("c", 2)  # re-exhausted on pipe, memoized again
    assert pipe2.submit("c", 2)  # same cache, fresh budget: admitted


def test_prefill_respects_pool_cap_and_direct_fallback():
    store = make_synthetic_store(64, 8, seed=5)
    sch = make_scheme("chor", d=2, d_a=1)
    pipe = ServingPipeline(
        store, sch, cache=QueryCache(sch, store.n, max_pre_batches=1)
    )
    assert pipe.prefill_cache(4) == 1
    assert pipe.prefill_cache(4) == 0  # pool at cap
    # the direct family has no query-independent half: prefill is a no-op
    sch_d = make_scheme("direct", d=2, d_a=1, p=8)
    pipe_d = ServingPipeline(
        store, sch_d, cache=QueryCache(sch_d, store.n)
    )
    assert pipe_d.prefill_cache(4) == 0
    pipe_d.submit("c", 5)
    np.testing.assert_array_equal(
        pipe_d.flush()["c"], store.record_bytes(5)
    )


# ------------------------------------------------- metrics under contention
def test_metrics_exact_under_threaded_hammer():
    """Every counter bump happens under the cache lock — T threads each
    driving I hits, I misses, I notes and I memoized refusals must land
    on exactly T*I per counter. Plain dict increments (read-modify-write
    outside the lock) lose updates under this hammer."""
    import threading

    sch = make_scheme("chor", d=2, d_a=1)
    cache = QueryCache(
        sch, 64, max_entries=100_000, max_refusal_entries=100_000
    )
    T, I = 8, 300
    start = threading.Barrier(T)

    def hammer(t):
        start.wait()
        for i in range(I):
            client = f"t{t}-{i}"
            cache.insert(client, 0, answer=np.zeros(4, np.uint8))
            assert cache.lookup(client, 0) is not None       # hit
            assert cache.lookup(client, 1) is None           # miss
            tok = (1.0, 0.0, 1.0, 0.0)
            cache.note_refusal(client, tok)
            assert cache.refused(client, tok)                # refusal hit

    threads = [threading.Thread(target=hammer, args=(t,)) for t in range(T)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=60.0)
    assert not any(th.is_alive() for th in threads)
    m = cache.metrics
    assert m["hits"] == T * I
    assert m["misses"] == T * I
    assert m["insertions"] == T * I
    assert m["refusals_noted"] == T * I
    assert m["refusal_hits"] == T * I
    assert m["evictions"] == 0


# --------------------------------------------- refusal memo LRU order pin
def test_refusal_memo_eviction_order_is_lru():
    """Pin the memo's LRU discipline: a refusal *hit* refreshes its
    client, so eviction always takes the least-recently-consulted entry
    — not insertion (FIFO) order."""
    sch = make_scheme("chor", d=2, d_a=1)
    cache = QueryCache(sch, 64, max_refusal_entries=3)
    tok = (1.0, 0.0, 1.0, 0.0)
    for c in ("a", "b", "c"):
        cache.note_refusal(c, tok)
    assert cache.refused("a", tok)      # touch: order is now b, c, a
    cache.note_refusal("d", tok)        # evicts b (LRU), NOT a (FIFO)
    assert not cache.refused("b", tok)
    assert cache.refused("a", tok) and cache.refused("c", tok)
    assert cache.refused("d", tok)      # order: a, c, d (b's miss is no touch)
    cache.note_refusal("e", tok)        # evicts a — consulted least recently
    assert not cache.refused("a", tok)
    assert all(cache.refused(c, tok) for c in ("c", "d", "e"))


def test_invalidate_clears_refusal_memo_under_churn():
    """invalidate() empties the refusal memo along with entries and
    pres, even while the memo is churning at its bound — no client stays
    memo-refused across a remesh/re-sign."""
    sch = make_scheme("chor", d=2, d_a=1)
    cache = QueryCache(sch, 64, max_entries=8, max_refusal_entries=8)
    tok = (1.0, 0.0, 1.0, 0.0)
    clients = [f"c{i}" for i in range(40)]  # 5x the bound: constant churn
    for i, c in enumerate(clients):
        cache.note_refusal(c, tok)
        cache.insert(c, i % 64, answer=np.zeros(4, np.uint8))
    assert sum(cache.refused(c, tok) for c in clients) == 8  # at the bound
    cache.invalidate()
    assert len(cache) == 0
    assert not any(cache.refused(c, tok) for c in clients)
    # and the memo still works (and stays bounded) after the wipe
    cache.note_refusal("fresh", tok)
    assert cache.refused("fresh", tok)
