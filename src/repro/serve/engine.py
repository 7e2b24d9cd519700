"""The serving pipeline: queue → router → execution backend.

This is the production face of the paper: clients submit (client_id, index)
requests; the :class:`~repro.serve.scheduler.BatchScheduler` batches them
(batched queries are what make the MXU parity path profitable, DESIGN.md
§Hardware adaptation) and pads to power-of-two buckets; the
:class:`~repro.serve.router.SchemeRouter` drives the configured scheme's
staged protocol (DESIGN.md §Scheme protocol) to turn each batch into
per-server payloads; the
:class:`~repro.serve.sharded.ShardedBackend` answers them — on the
single-host kernels off-mesh, or with record stores partitioned across the
active mesh (``repro.dist``) when one is in scope.

Privacy is enforced at admission: every accepted query spends its scheme's
(ε, δ) from the client's :class:`~repro.core.accounting.PrivacyBudget`
(sequential composition, §2.2) and exhausted clients are refused.
Straggler mitigation = Subset-PIR (paper §5.1): the backend's per-replica
latency EMAs rank the databases and the router contacts only the fastest
``t`` — the paper's own optimization *is* the straggler policy, with its
privacy price δ accounted per query.

With a :class:`~repro.serve.cache.QueryCache` attached, the pipeline
memoizes per-(client, index) answers across flushes and consumes
pre-generated batch randomness banked by :meth:`ServingPipeline.
prefill_cache`. Admission spends the budget *before* the cache is ever
consulted, so a hit is priced exactly like a miss and exhausted clients
are refused even when their answer sits in cache (DESIGN.md §Cross-batch
cache). The pipeline itself stays single-threaded; the thread-safe
concurrent ingest front over it is
:class:`~repro.serve.frontend.AsyncFrontend` (DESIGN.md §Async front).

:class:`PIRServingEngine` is the back-compat facade over the pipeline —
the pre-refactor one-file engine's constructor and methods, unchanged.
"""

from __future__ import annotations

import dataclasses
import itertools
import threading
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.accounting import PrivacyBudget
from repro.core.protocol import (
    Queries,
    SchemeProtocol,
    as_protocol,
    multi_bucket,
)
from repro.db import packing
from repro.db.live import Delta, VersionedStore
from repro.db.store import RecordStore
from repro.dist.fault import (
    RemeshPlan,
    plan_elastic_remesh,
    scheme_degradation,
)
from repro.kernels.backend import ExecutionPlan
from repro.serve.cache import QueryCache, block_pre_ready, scheme_signature
from repro.serve.router import SchemeRouter
from repro.serve.scheduler import BatchScheduler, Request
from repro.serve.sharded import ServerStats, ShardedBackend
from repro.serve.trace import span

__all__ = ["ServerStats", "PlannedBatch", "ServingPipeline", "PIRServingEngine"]


@dataclasses.dataclass
class PlannedBatch:
    """One cut batch, planned but not yet executed (the unit the
    double-buffered flush worker overlaps, DESIGN.md §Execution
    backends): cache hits already resolved into ``results``, misses
    routed into wire-level ``routed`` payloads with the batch's
    :class:`~repro.kernels.backend.ExecutionPlan` pre-resolved."""

    batch: List[Request]
    results: List[Optional[Tuple[Request, np.ndarray]]]
    misses: List[Request]
    miss_pos: List[int]
    padded: int
    routed: Optional[Queries]  # or a MultiQueries for a jagged batch
    exec_plan: Optional[ExecutionPlan]
    plan_s: float  # wall time the plan phase itself took
    # multi-index plumbing (None on the classic single-index path):
    # per-miss-request jagged index lists that actually went to wire, and
    # per-miss-request [k_r] slots holding cached answers (None = fresh)
    miss_lists: Optional[List[List[int]]] = None
    partial: Optional[List[List[Optional[np.ndarray]]]] = None
    # snapshot pinning (DESIGN.md §13): the frozen store this batch
    # answers against and its version. Writes landing mid-batch produce
    # a *new* head; this batch keeps answering — and memoizing, under
    # this version — against the store it was planned on, so an answer
    # can never tear across an ingest.
    store: Optional[RecordStore] = None
    store_version: int = 0
    # the pipeline's id of this batch (the ``batch`` stat of its plan and
    # execute spans) and the scheduler-clock time its planning began
    batch_id: int = 0
    t_plan: float = 0.0


class ServingPipeline:
    """Batch-scheduled, scheme-routed, mesh-shardable PIR serving."""

    def __init__(
        self,
        store: RecordStore,
        scheme,
        *,
        scheduler: Optional[BatchScheduler] = None,
        backend: Optional[ShardedBackend] = None,
        cache: Optional[QueryCache] = None,
        default_budget: Optional[Callable[[], PrivacyBudget]] = None,
        simulate_latency: Optional[Callable[[int], float]] = None,
        seed: int = 0,
    ):
        # `store` may be a frozen RecordStore or a live VersionedStore
        # (duck-typed: anything with snapshot()/ingest()). Live stores
        # serve through their current frozen head; `self.store` is
        # ALWAYS a frozen snapshot — the rest of the pipeline never
        # learns whether writes exist.
        self.live: Optional[VersionedStore] = None
        if hasattr(store, "snapshot") and hasattr(store, "ingest"):
            self.live = store
            store = store.snapshot()
        self.store = store
        self.store_version = self.live.version if self.live is not None else 0
        self._pending_deltas: List[Delta] = []
        # `scheme` may be a staged SchemeProtocol instance (incl. Anonymized
        # wrappers) or the back-compat Scheme facade; `self.scheme` keeps
        # whatever the caller handed over, `self.staged` is the normalized
        # protocol object every stage below drives
        self.scheme = scheme
        self.staged: SchemeProtocol = as_protocol(scheme)
        # explicit None checks: an empty BatchScheduler is falsy (__len__)
        self.scheduler = scheduler if scheduler is not None else BatchScheduler()
        self.backend = backend if backend is not None else ShardedBackend(
            store, simulate_latency=simulate_latency
        )
        self.backend.ensure_replicas(self.staged.d)
        # the straggler policy rides along unconditionally; only schemes
        # whose query() consumes pick_servers (Subset-PIR) ever look at it
        self.router = SchemeRouter(
            self.staged, pick_servers=self.backend.fastest
        )
        if cache is not None and cache.signature != scheme_signature(
            scheme, store.n
        ):
            raise ValueError(
                f"cache built for {cache.signature}, pipeline serves "
                f"{scheme_signature(scheme, store.n)}"
            )
        self.cache = cache
        self._budgets: Dict[str, PrivacyBudget] = {}
        self._default_budget = default_budget or (
            lambda: PrivacyBudget(epsilon_limit=float("inf"), delta_limit=1.0)
        )
        self._key = jax.random.key(seed)
        # guards cache/metrics/scheduler-feedback mutations so the
        # frontend may run plan_requests(batch k+1) concurrently with
        # execute_planned(batch k) — the double-buffered flush. The heavy
        # device work in execute runs outside the lock; the sync path
        # takes it uncontended.
        self._phase_lock = threading.Lock()
        self._batch_ids = itertools.count()
        # the per-query (ε, δ) price is constant between remeshes (fixed
        # scheme, fixed n): compute once so admission is O(1) float math;
        # degrade_replicas re-prices it when survivors shrink the scheme
        self._eps_per_query, self._delta_per_query = self.staged.privacy(
            store.n
        )
        # replica-loss state (DESIGN.md §Fleet harness): the healthy
        # scheme is kept so cumulative failures always degrade from the
        # original d, not from an already-degraded intermediate
        self._base_staged: SchemeProtocol = self.staged
        self._failed_replicas: set = set()
        self._serviceable = True
        self.last_remesh: Optional[RemeshPlan] = None
        self.degraded: Optional[Dict[str, float]] = None
        # queue_wait_s, dispatch_wait_s and execute_s are summed over
        # requests, on the scheduler's clock: enqueue -> cut (take_batch),
        # start of planning -> start of execute, execute's start -> its
        # results ready; dequeued counts the requests cut
        self.metrics = {
            "queries": 0, "batches": 0, "refused": 0, "padded": 0,
            "truncated": 0, "cache_hits": 0, "remeshes": 0,
            "queue_wait_s": 0.0, "dequeued": 0,
            "dispatch_wait_s": 0.0, "execute_s": 0.0,
            "d_effective": float(self.staged.d),
            "epsilon_per_query": self._eps_per_query,
            "delta_per_query": self._delta_per_query,
            "unserviceable": 0,
            "ingests": 0, "records_ingested": 0,
        }

    # ------------------------------------------------------------ clients
    def budget(self, client: str) -> PrivacyBudget:
        if client not in self._budgets:
            self._budgets[client] = self._default_budget()
        return self._budgets[client]

    def set_budget(self, client: str, budget: PrivacyBudget) -> None:
        """Install a per-client budget ahead of traffic. The fleet
        harness gives each simulated client its own (ε, δ) allowance this
        way; clients never installed fall back to ``default_budget`` on
        first contact."""
        self._budgets[client] = budget

    @property
    def price(self) -> Tuple[float, float]:
        """The per-query (ε, δ) admission price currently charged.
        Constant between remeshes; replica loss re-prices it through
        :meth:`degrade_replicas` ((∞, δ) once unserviceable)."""
        return self._eps_per_query, self._delta_per_query

    def _budget_token(self, client: str) -> tuple:
        """Hashable snapshot of the client's budget state. ``can_spend``
        is a pure function of this state and the pipeline's fixed price,
        so the cache's refusal memo keyed on it can never go stale."""
        b = self.budget(client)
        return (b.epsilon_limit, b.delta_limit, b.spent_epsilon, b.spent_delta)

    def submit_request(self, client: str, index: int) -> Optional[Request]:
        """Queue one query; None if the client's privacy budget refuses.

        Spending happens here, at admission — before the cache is ever
        consulted — so a cache hit is priced exactly like a miss. The
        cache's refusal memo short-circuits repeated over-budget polls:
        it is keyed on the exact budget state the refusal was computed
        from, so any budget change (top-up, shared-budget spend, a fresh
        budget behind a reused cache) re-consults the accountant — and
        (as always) a refusal spends nothing.

        An unserviceable pipeline (replica loss left d' ≤ d_a: privacy
        would rest entirely on corrupt servers) refuses everyone
        unconditionally — an explicit flag, not an ∞ price, because the
        default budget's ∞ limit would happily "afford" ∞.
        """
        if not self._serviceable:
            self.metrics["refused"] += 1
            return None
        if self.cache is not None and self.cache.refused(
            client, self._budget_token(client)
        ):
            self.metrics["refused"] += 1
            return None
        eps, delta = self._eps_per_query, self._delta_per_query
        if not self.budget(client).can_spend(eps, delta):
            if self.cache is not None:
                self.cache.note_refusal(client, self._budget_token(client))
            self.metrics["refused"] += 1
            return None
        self.budget(client).spend(eps, delta)
        return self.scheduler.submit(client, index)

    def submit(self, client: str, index: int) -> bool:
        """Queue one query; False if the client's privacy budget refuses."""
        return self.submit_request(client, index) is not None

    def submit_request_many(
        self, client: str, indices
    ) -> Optional[Request]:
        """Queue one jagged multi-index request; None if refused.

        Admission charges the Composition-Lemma price up front: a
        k-index request is k sequential lookups to the accountant
        (DESIGN.md §Multi-index wire format), so it spends k·(ε, δ) —
        before the cache is consulted, exactly like :meth:`submit_request`,
        and hits on any of its indices never refund it. The cache's
        refusal memo is keyed on the *fixed* per-query price, so a
        variable-k request consults the accountant directly instead.
        """
        k = len(indices)
        if k == 0:
            raise ValueError("submit_request_many needs at least one index")
        if not self._serviceable:
            self.metrics["refused"] += 1
            return None
        eps, delta = self._eps_per_query, self._delta_per_query
        if not self.budget(client).can_spend(k * eps, k * delta):
            self.metrics["refused"] += 1
            return None
        self.budget(client).spend(k * eps, k * delta)
        return self.scheduler.submit_many(client, indices)

    def submit_many(self, client: str, indices) -> bool:
        """Queue one multi-index request; False if the budget refuses."""
        return self.submit_request_many(client, indices) is not None

    # ------------------------------------------------------------ serving
    def fastest_servers(self, t: int) -> List[int]:
        return self.backend.fastest(t)

    @property
    def stats(self) -> Dict[int, ServerStats]:
        return self.backend.stats

    # ------------------------------------------------------- replica loss
    def degrade_replicas(self, failed: List[int]) -> Dict[str, float]:
        """Replica-loss hook (DESIGN.md §Fleet harness): degrade, don't
        outage. Wired to :class:`~repro.dist.fault.HeartbeatMonitor`'s
        failure edge by the fleet harness; callable directly by ops.

        ``failed`` are replica ids of the *original* d-server deployment
        (cumulative: ids union with prior losses; repeats are no-ops).
        The pipeline (1) accounts the degradation —
        :func:`~repro.dist.fault.scheme_degradation` re-fits the scheme
        to the d' survivors and prices it with ``pir_degraded_privacy``;
        (2) swaps in the degraded scheme, re-pricing admission at the new
        (ε, δ); (3) relabels the backend's survivors and rebuilds the
        router; (4) invalidates + re-signs the cache (old-d randomness is
        unreplayable on the survivor wire); (5) records the
        :func:`~repro.dist.fault.plan_elastic_remesh` plan. Once d' ≤
        d_a the pipeline flips unserviceable and refuses all admission
        (the paper's mandate: refuse, never serve at ε = ∞).

        Batches planned before the swap still execute and resolve —
        their wire bits went out under the old scheme, which was honestly
        priced when their clients were admitted; degradation never drops
        an in-flight future. Returns the degraded-privacy dict.
        """
        with self._phase_lock:
            fresh = {int(f) for f in failed} - self._failed_replicas
            if not fresh:
                if self.degraded is not None:
                    return dict(self.degraded)
                return {
                    "d_effective": float(self.staged.d), "serviceable": 1.0,
                    "epsilon": self._eps_per_query,
                    "delta": self._delta_per_query,
                }
            self._failed_replicas |= fresh
            d0 = self._base_staged.d
            survivors = [
                r for r in range(d0) if r not in self._failed_replicas
            ]
            degraded_scheme, info = scheme_degradation(
                self._base_staged, self.store.n, len(self._failed_replicas)
            )
            self.degraded = info
            self.metrics["remeshes"] += 1
            self.metrics["d_effective"] = info["d_effective"]
            self.last_remesh = (
                plan_elastic_remesh(survivors) if survivors else None
            )
            if degraded_scheme is None:
                self._serviceable = False
                self.metrics["unserviceable"] = 1
                self._eps_per_query = float("inf")
                self._delta_per_query = info["delta"]
                self.metrics["epsilon_per_query"] = float("inf")
                self.metrics["delta_per_query"] = info["delta"]
                return dict(info)
            self.scheme = self.staged = degraded_scheme
            self._eps_per_query = info["epsilon"]
            self._delta_per_query = info["delta"]
            self.metrics["epsilon_per_query"] = self._eps_per_query
            self.metrics["delta_per_query"] = self._delta_per_query
            self.backend.relabel_replicas(survivors)
            self.router = SchemeRouter(
                self.staged, pick_servers=self.backend.fastest
            )
            if self.cache is not None:
                # banked pres and memod columns were drawn for the old d
                # and cannot be replayed on the survivor wire; the
                # refusal memo goes too (budget tokens survive, but the
                # price rose — re-consulting the accountant is the only
                # safe direction)
                self.cache.invalidate()
                self.cache.signature = scheme_signature(
                    degraded_scheme, self.store.n
                )
            return dict(info)

    def plan_requests(self, batch: List[Request]) -> Optional[PlannedBatch]:
        """Plan one cut batch without executing it: resolve cache hits,
        route the misses into per-server wire payloads (consuming banked
        precomputed randomness for the bucket when available) and
        pre-resolve the batch's :class:`~repro.kernels.backend.
        ExecutionPlan`. Client/planning work only — the server compute
        happens in :meth:`execute_planned`. The async frontend's
        double-buffered flush runs this for batch k+1 while batch k
        executes; `serve_requests` composes the two phases inline.
        """
        if not batch:
            return None
        t_plan = self.scheduler.clock()
        batch_id = next(self._batch_ids)
        with span("plan", batch=batch_id, requests=len(batch)) as sp:
            if any(r.indices for r in batch):
                planned = self._plan_requests_multi(batch)
            else:
                planned = self._plan_requests_single(batch)
            sp.set_metadata(misses=len(planned.misses), bucket=planned.padded)
        planned.batch_id, planned.t_plan = batch_id, t_plan
        return planned

    def _plan_requests_single(self, batch: List[Request]) -> PlannedBatch:
        """The single-index half of :meth:`plan_requests`."""
        results: List[Optional[Tuple[Request, np.ndarray]]] = [None] * len(batch)
        with self._phase_lock:
            # pin the batch's snapshot under the lock: everything below —
            # routing shape (n), execution, reconstruction, cache stamps —
            # reads the pinned frozen store, never the (possibly newer)
            # live head
            store, ver = self.store, self.store_version
            if self.cache is not None:
                misses, miss_pos = [], []
                for i, r in enumerate(batch):
                    entry = self.cache.lookup(r.client, r.index)
                    if entry is not None:
                        results[i] = (r, entry.answer)
                    else:
                        misses.append(r)
                        miss_pos.append(i)
            else:
                misses, miss_pos = list(batch), list(range(len(batch)))
            self.metrics["queries"] += len(batch)
            self.metrics["cache_hits"] += len(batch) - len(misses)

        routed = exec_plan = None
        padded = 0
        plan_s = 0.0
        clock = self.scheduler.clock
        if misses:
            b = len(misses)
            padded = self.scheduler.padded_size(b)
            q_idx = jnp.asarray(
                [r.index for r in misses] + [0] * (padded - b), jnp.int32
            )
            with self._phase_lock:
                # the plan timer starts only once the phase lock is held:
                # under the double-buffered flush, waiting here for the
                # concurrent execute's bookkeeping is queue contention,
                # not plan cost — billing it as plan time inflated the
                # scheduler's service EMA and shrank the adaptive target
                t0 = clock()
                self._key, sub = jax.random.split(self._key)
                pre = (
                    self.cache.take_pre(padded)
                    if self.cache is not None else None
                )
            with span("query_gen", bucket=padded):
                routed = self.router.plan(sub, store.n, q_idx, pre=pre)
            if self.live is not None:
                routed.store_version = ver
            exec_plan = self.backend.prepare(routed, scheme=self.staged)
            plan_s = clock() - t0
        return PlannedBatch(
            batch=list(batch), results=results, misses=misses,
            miss_pos=miss_pos, padded=padded, routed=routed,
            exec_plan=exec_plan, plan_s=plan_s,
            store=store, store_version=ver,
        )

    @staticmethod
    def _assemble(r: Request, rows: List[np.ndarray]) -> np.ndarray:
        """A request's final answer from its per-index record bytes:
        [k, nbytes] for a multi-index request, flat [nbytes] for a
        classic single-index one (back-compat shape)."""
        if r.indices:
            return np.stack([np.asarray(a) for a in rows])
        return np.asarray(rows[0])

    def _plan_requests_multi(self, batch: List[Request]) -> PlannedBatch:
        """The multi-index half of :meth:`plan_requests` (DESIGN.md
        §Multi-index wire format): cache hits resolve *per (client,
        index)* — a request whose indices all hit never touches a wire,
        and partially-hit requests send only their missing indices — the
        remaining jagged lists flatten into one padded
        :class:`~repro.core.protocol.MultiQueries` wire batch via
        :meth:`~repro.serve.router.SchemeRouter.plan_many`. ``queries``
        and ``cache_hits`` metrics count *flattened indices* here: each
        index is a priced lookup under the Composition Lemma."""
        results: List[Optional[Tuple[Request, np.ndarray]]] = [None] * len(batch)
        misses: List[Request] = []
        miss_pos: List[int] = []
        miss_lists: List[List[int]] = []
        partial: List[List[Optional[np.ndarray]]] = []
        with self._phase_lock:
            store, ver = self.store, self.store_version  # pin (see above)
            for i, r in enumerate(batch):
                idxs = r.index_list
                rows: List[Optional[np.ndarray]] = [None] * len(idxs)
                if self.cache is not None:
                    for j, ix in enumerate(idxs):
                        entry = self.cache.lookup(r.client, ix)
                        if entry is not None:
                            rows[j] = entry.answer
                if all(a is not None for a in rows):
                    results[i] = (r, self._assemble(r, rows))
                else:
                    misses.append(r)
                    miss_pos.append(i)
                    miss_lists.append(
                        [ix for j, ix in enumerate(idxs) if rows[j] is None]
                    )
                    partial.append(rows)
            flat_total = sum(r.k for r in batch)
            self.metrics["queries"] += flat_total
            self.metrics["cache_hits"] += flat_total - sum(
                len(lst) for lst in miss_lists
            )

        routed = exec_plan = None
        padded = 0
        plan_s = 0.0
        clock = self.scheduler.clock
        if misses:
            padded = multi_bucket(miss_lists)
            with self._phase_lock:
                t0 = clock()
                self._key, sub = jax.random.split(self._key)
                pre = (
                    self.cache.take_pre(padded)
                    if self.cache is not None else None
                )
            with span("query_gen", bucket=padded):
                routed = self.router.plan_many(
                    sub, store.n, miss_lists, pre=pre
                )
            if self.live is not None:
                routed.queries.store_version = ver  # flat wire carries it
            exec_plan = self.backend.prepare(routed, scheme=self.staged)
            plan_s = clock() - t0
        return PlannedBatch(
            batch=list(batch), results=results, misses=misses,
            miss_pos=miss_pos, padded=padded, routed=routed,
            exec_plan=exec_plan, plan_s=plan_s,
            miss_lists=miss_lists, partial=partial,
            store=store, store_version=ver,
        )

    def _execute_planned_multi(
        self, planned: PlannedBatch, t1: float
    ) -> List[Tuple[Request, np.ndarray]]:
        """Execute a multi-index planned batch: one backend answer for
        the whole flattened wire batch, ONE flat reconstruction + one
        device->host transfer (request r's i-th wire index is flat row
        r·k_max + i — the padded layout, so the per-request split is
        numpy slicing, not per-request device ops), fresh rows merged
        back into each request's cached slots in index order, and every
        fresh (client, index) answer memoized.
        ``SchemeRouter.finalize_many`` is the same split as a protocol-
        level API; the serving path inlines it to keep the hot path at
        one transfer per batch."""
        results = planned.results
        if planned.routed is None:
            return results  # type: ignore[return-value]
        misses = planned.misses
        routed = planned.routed
        pinned = planned.store if planned.store is not None else self.store
        responses = self.backend.answer_batch(
            routed, plan=planned.exec_plan, scheme=self.staged,
            store=planned.store,
        )
        with span("finalize", batch=planned.batch_id):
            with span("reconstruct"):
                # reconstruct the whole padded [B, W] batch in one shot —
                # MultiQueries delegates its wire view, so the scheme's
                # flat reconstruct applies; padding rows are sliced away
                flat_out = self.router.finalize(routed, responses)
                flat_out.block_until_ready()
            dt = planned.plan_s + (self.scheduler.clock() - t1)

            with span("unpack"):
                nbytes = -(-pinned.record_bits // 8)
                raw_all = packing.unpack_bytes_np(np.asarray(flat_out), nbytes)
                k_max = routed.k_max
                raw = np.concatenate([
                    raw_all[j * k_max: j * k_max + len(lst)]
                    for j, lst in enumerate(planned.miss_lists)
                ]) if planned.miss_lists else raw_all[:0]
            flat_total = sum(len(lst) for lst in planned.miss_lists)
            with span("cache_insert"):
                cols = None
                if self.cache is not None:
                    col_bytes = (
                        routed.payload.nbytes // routed.payload.shape[1]
                    )
                    if col_bytes <= self.cache.max_query_vector_bytes:
                        cols = np.asarray(routed.payload)

                with self._phase_lock:
                    self.scheduler.observe_service(planned.padded, dt)
                    self.metrics["batches"] += 1
                    self.metrics["padded"] += planned.padded - flat_total
                    start = 0
                    for j, r in enumerate(misses):
                        fresh = raw[start:start + len(planned.miss_lists[j])]
                        start += len(planned.miss_lists[j])
                        rows = list(planned.partial[j])
                        f = 0
                        for pos in range(len(rows)):
                            if rows[pos] is not None:
                                continue
                            answer = np.array(fresh[f])
                            rows[pos] = answer
                            if self.cache is not None:
                                # request j's f-th wire index sits at flat
                                # column j·k_max + f (the padded layout)
                                flat_col = j * routed.k_max + f
                                self.cache.insert(
                                    r.client, planned.miss_lists[j][f],
                                    answer=answer,
                                    query_cols=(
                                        None if cols is None
                                        else cols[:, flat_col]
                                    ),
                                    version=planned.store_version,
                                )
                            f += 1
                        results[planned.miss_pos[j]] = (
                            r, self._assemble(r, rows)
                        )
        return results  # type: ignore[return-value]

    def _execute_planned_single(
        self, planned: PlannedBatch, t1: float
    ) -> List[Tuple[Request, np.ndarray]]:
        """The single-index half of :meth:`execute_planned`."""
        results = planned.results
        if planned.routed is None:
            return results  # type: ignore[return-value]
        misses, miss_pos = planned.misses, planned.miss_pos
        b = len(misses)
        routed = planned.routed
        pinned = planned.store if planned.store is not None else self.store
        responses = self.backend.answer_batch(
            routed, plan=planned.exec_plan, scheme=self.staged,
            store=planned.store,
        )
        with span("finalize", batch=planned.batch_id):
            with span("reconstruct"):
                out = self.router.finalize(routed, responses)
                out.block_until_ready()
            dt = planned.plan_s + (self.scheduler.clock() - t1)

            with span("unpack"):
                nbytes = -(-pinned.record_bits // 8)
                raw = packing.unpack_bytes_np(np.asarray(out[:b]), nbytes)
            with span("cache_insert"):
                cols = None
                if self.cache is not None:
                    # one device->host transfer for the whole payload,
                    # skipped when a single column would blow the cache's
                    # byte cap
                    col_bytes = (
                        routed.payload.nbytes // routed.payload.shape[1]
                    )
                    if col_bytes <= self.cache.max_query_vector_bytes:
                        cols = np.asarray(routed.payload[:, :b])

                with self._phase_lock:
                    self.scheduler.observe_service(planned.padded, dt)
                    self.metrics["batches"] += 1
                    self.metrics["padded"] += planned.padded - b
                    for j, r in enumerate(misses):
                        answer = np.array(raw[j])
                        results[miss_pos[j]] = (r, answer)
                        if self.cache is not None:
                            self.cache.insert(
                                r.client, r.index, answer=answer,
                                query_cols=(
                                    None if cols is None else cols[:, j]
                                ),
                                version=planned.store_version,
                            )
        return results  # type: ignore[return-value]

    def execute_planned(
        self, planned: Optional[PlannedBatch]
    ) -> List[Tuple[Request, np.ndarray]]:
        """Execute a planned batch's misses on the backend and finalize:
        [(Request, record bytes)] in the planned batch's order. The
        device compute runs outside the pipeline's phase lock so a
        concurrent :meth:`plan_requests` never waits on it."""
        if planned is None:
            return []
        # service time = this batch's own plan + execute wall time;
        # timing from execute's start (not the plan's t0) keeps the
        # scheduler's EMA honest when the double buffer queues this
        # execute behind the previous batch's — queue wait is not
        # per-batch cost and would otherwise shrink the target. Both
        # phases read the scheduler's own clock so fake-clock tests can
        # pin exactly what the EMA and the wait counters are fed.
        clock = self.scheduler.clock
        t_exec = clock()
        with span("execute", batch=planned.batch_id):
            if planned.miss_lists is not None:  # a jagged multi-index batch
                results = self._execute_planned_multi(planned, t_exec)
            else:
                results = self._execute_planned_single(planned, t_exec)
        t_done = clock()
        n = len(planned.batch)
        with self._phase_lock:
            self.metrics["dispatch_wait_s"] += (t_exec - planned.t_plan) * n
            self.metrics["execute_s"] += (t_done - t_exec) * n
        return results

    def serve_requests(
        self, batch: List[Request]
    ) -> List[Tuple[Request, np.ndarray]]:
        """Serve one cut batch, per request: [(Request, record bytes)].

        Cache hits are answered from the per-client memo without touching
        any server (their budget was already spent at admission); misses
        are routed as one padded batch and memoized on the way out.
        ``serve_requests = execute_planned ∘ plan_requests`` — the async
        frontend drives the phases separately to double-buffer flushes.
        """
        return self.execute_planned(self.plan_requests(batch))

    def take_batch(self) -> List[Request]:
        """Pop the next batch off the scheduler (≤ max_batch; truncation
        leaves the rest queued)."""
        if not len(self.scheduler):
            return []
        now = self.scheduler.clock()
        batch = self.scheduler.next_batch()
        if len(self.scheduler):
            self.metrics["truncated"] += 1
        self.metrics["queue_wait_s"] += sum(now - r.t_enqueue for r in batch)
        self.metrics["dequeued"] += len(batch)
        return batch

    def prefill_cache(self, bucket: Optional[int] = None) -> int:
        """Bank one batch of precomputed query randomness for ``bucket``
        (default: the adaptive target's bucket — the shape full cuts land
        on). The async frontend calls this from its flush worker while
        idle, moving query generation off the serve critical path. Returns
        1 if banked. Deliberately NOT the transient queue-length bucket:
        precomputing odd buckets would trigger compiles for shapes that
        are never served, stalling the flush worker.
        """
        if self.cache is None:
            return 0
        if bucket is None:
            bucket = self.scheduler.padded_size(self.scheduler.target_batch)
        if bucket <= 0:
            return 0
        if self.cache.pre_depth(bucket) >= self.cache.max_pre_batches:
            return 0
        with self._phase_lock:
            self._key, sub = jax.random.split(self._key)
        pre = self.router.precompute(sub, self.store.n, bucket)
        if pre is None:  # scheme has no query-independent half
            return 0
        # materialize here, on the producer: banking pending randomness
        # would just move the wait into the next flush
        return int(self.cache.put_pre(bucket, block_pre_ready(pre)))

    def autotune_step(self, max_cells: int = 1) -> int:
        """Run the execution backend's autotune search for up to
        ``max_cells`` pending plan cells (DESIGN.md §Execution backends).
        The async frontend calls this from its flush worker while idle —
        the second idle-slot job next to :meth:`prefill_cache` — so cold
        cells planned from the analytic prior get their measured winner
        during lulls, never on a request thread. Returns cells tuned."""
        return self.backend.autotune_step(max_cells)

    # ------------------------------------------------------------- ingest
    def ingest(self, delta: Delta) -> int:
        """Apply one delta to the live store and roll the serve path
        forward; returns the new store version (DESIGN.md §13).

        Under the phase lock, in order: (1) the
        :class:`~repro.db.live.VersionedStore` applies the delta on
        device and becomes a new frozen head; (2) the execution backend
        rebinds — same-shape deltas keep every cached
        :class:`~repro.kernels.backend.ExecutionPlan` and refresh only
        the touched bitplane rows, appends re-plan (the shape changed, so
        every plan is for the wrong store); (3) the cache advances its
        version — entries for touched indices evict, untouched indices
        keep their lines, and the per-index last-written map makes a
        stale hit structurally impossible even for entries inserted
        later by in-flight batches pinned to older snapshots; (4)
        admission re-prices (ε, δ) when ``n`` changed. Batches planned
        before this call still answer bit-identically — they hold their
        pinned snapshot.
        """
        if self.live is None:
            raise RuntimeError(
                "pipeline serves a frozen RecordStore; construct it over "
                "a VersionedStore to ingest deltas"
            )
        with self._phase_lock:
            n_before = self.live.n
            touched = self.live.touched_rows(delta, n_before=n_before)
            ver = self.live.ingest(delta)
            snap = self.live.snapshot()
            same_shape = (
                snap.n == self.store.n and snap.words == self.store.words
            )
            # touched_rows always flows through: the planner's rebind
            # keeps plans only on a same-shape swap (it drops them
            # itself when n changed), while the backend's mesh residency
            # can absorb even a pad-fitting append as a touched-shard-
            # only device refresh (DESIGN.md §13); `live` threads the
            # shard-version vector into the swap counters
            self.backend.swap_store(
                snap, touched_rows=touched, live=self.live
            )
            self.store = snap
            self.store_version = ver
            if self.cache is not None:
                self.cache.advance_version(
                    ver, [int(i) for i in touched],
                    signature=scheme_signature(self.scheme, snap.n),
                )
            if not same_shape and self._serviceable:
                # append grew n: the admission price is a function of n
                self._eps_per_query, self._delta_per_query = (
                    self.staged.privacy(snap.n)
                )
                self.metrics["epsilon_per_query"] = self._eps_per_query
                self.metrics["delta_per_query"] = self._delta_per_query
            self.metrics["ingests"] += 1
            self.metrics["records_ingested"] += delta.count
            return ver

    def queue_delta(self, delta: Delta) -> None:
        """Enqueue a delta for the flush worker's idle slot: the async
        frontend applies pending deltas via :meth:`ingest_step` next to
        cache prefill and autotune, so writes ride the same idle
        machinery as the other background jobs and never preempt a
        cut batch."""
        if self.live is None:
            raise RuntimeError(
                "pipeline serves a frozen RecordStore; construct it over "
                "a VersionedStore to ingest deltas"
            )
        with self._phase_lock:
            self._pending_deltas.append(delta)

    @property
    def pending_deltas(self) -> int:
        """Deltas queued but not yet applied."""
        return len(self._pending_deltas)

    def ingest_step(self, max_deltas: int = 1) -> int:
        """Apply up to ``max_deltas`` queued deltas (the idle-slot job).
        Returns how many were applied."""
        done = 0
        while done < max_deltas:
            with self._phase_lock:
                if not self._pending_deltas:
                    break
                delta = self._pending_deltas.pop(0)
            self.ingest(delta)
            done += 1
        return done

    def compact_step(self, *, min_log_depth: int = 1) -> int:
        """Rebase the live store's delta log onto its current head when
        the log is at least ``min_log_depth`` deep (the idle-slot
        compaction job, DESIGN.md §13). Returns how many deltas were
        compacted away (0: frozen store, shallow log, or a write raced
        the oracle check and the compaction deferred to the next idle
        tick).

        No phase lock: compaction changes neither the head snapshot nor
        the version number, so served answers cannot observe it; the
        single flush worker serializes it against :meth:`ingest_step`,
        and the store's own lock + oracle-recheck make even an external
        concurrent writer safe (the rebase simply aborts)."""
        if self.live is None or self.live.log_depth < max(1, min_log_depth):
            return 0
        return self.live.compact()

    def step(self) -> Dict[str, np.ndarray]:
        """Serve at most one scheduled batch (≤ max_batch; the rest of the
        queue stays). Returns client → record bytes for the served batch."""
        return {r.client: a for r, a in self.serve_requests(self.take_batch())}

    def poll(self) -> Dict[str, np.ndarray]:
        """The async-style entry point: serve one batch only if the
        scheduler says it's time (adaptive target reached, or the oldest
        request hit the max_wait deadline); {} otherwise. An ingest loop
        calls this between submits instead of forcing flushes."""
        return self.step() if self.scheduler.ready() else {}

    def flush(self) -> Dict[str, np.ndarray]:
        """Drain the whole queue in max_batch-sized steps."""
        out: Dict[str, np.ndarray] = {}
        while len(self.scheduler):
            out.update(self.step())
        return out


class PIRServingEngine(ServingPipeline):
    """Back-compat facade: the pre-refactor engine's exact surface."""

    def __init__(
        self,
        store: RecordStore,
        scheme,
        *,
        max_batch: int = 1024,
        default_budget: Optional[Callable[[], PrivacyBudget]] = None,
        simulate_latency: Optional[Callable[[int], float]] = None,
        seed: int = 0,
    ):
        super().__init__(
            store,
            scheme,
            scheduler=BatchScheduler(max_batch=max_batch),
            default_budget=default_budget,
            simulate_latency=simulate_latency,
            seed=seed,
        )
        self.max_batch = max_batch

    def flush(self) -> Dict[str, np.ndarray]:
        """Old contract: serve ONE batch of at most max_batch; anything
        beyond max_batch stays queued for the next flush() call."""
        return self.step()
