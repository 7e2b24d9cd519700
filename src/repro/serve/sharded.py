"""Execution backends: where a routed batch actually touches records.

``ShardedBackend`` is the production *answer stage* of the staged
scheme protocol (DESIGN.md §Scheme protocol): it consumes the wire-level
:class:`~repro.core.protocol.Queries` a scheme's ``query()`` emitted and
answers per-server payloads against the record store — dispatching on
the wire *kind* (mask vs index) and θ, never on scheme names. The
scheme's ``reconstruct`` then runs on the stacked responses
(``SchemeRouter.finalize``).

Every implementation decision — which kernel, which backend impl, fused
vs streaming sparse, fold vs parity, block sizes, index budgets — flows
through the execution-backend layer (``repro.kernels.backend``, DESIGN.md
§Execution backends): :meth:`ShardedBackend.prepare` asks the
:class:`~repro.kernels.backend.KernelPlanner` for an
:class:`~repro.kernels.backend.ExecutionPlan` and
:meth:`ShardedBackend.answer_batch` executes it. This module holds **no
kernel choice of its own** — no impl strings, no crossover constants —
and imports no kernel module (``tools/check_api.py`` fences the kernel
internals behind ``repro.kernels``). The serving pipeline calls
``prepare`` for batch k+1 while batch k's plan is still executing, so
even the planner's one-shot autotune microbenchmarks hide in the
double-buffer overlap.

With no active mesh, the plan carries a ready jitted executor (exactly
what the old one-file engine did, with the kernel choice now measured
instead of hardcoded). Under ``repro.dist.mesh_rules`` with a rule
mapping the "records" logical axis, every server's database is
partitioned across the mesh and each device answers only its record
shard:

  * XOR-family batches run the plan's per-shard answer function
    (``repro.kernels.backend.shard_answer_fn``) under ``shard_map`` and
    the partial answers combine with
    :func:`repro.dist.collectives.xor_psum` (GF(2) butterfly; XOR is the
    reduction the PIR algebra wants, and fold, parity and sparse gather
    are all XOR-additive across record shards, so the result is
    bit-exact vs the single-host path).
  * Direct-Requests batches gather through
    :func:`repro.dist.collectives.sharded_record_lookup`.

Records are zero-padded up to the shard product — zero records are
XOR-neutral and query masks never select them, so padding cannot change
any answer.

``backend=`` names a registered execution backend ("pallas" | "ref" |
"auto"); the old ``kernel_impl=`` keyword survives as a deprecated alias
onto the same registry (README §Execution backends has the migration
table). ``autotune_file=`` loads a dumped autotune table at construction
(missing file = cold start) and :meth:`save_autotune` writes the
process-local measurements back out.

The backend also owns **straggler tracking**: a latency EMA per database
replica (the paper's d databases stay *logical* replicas — sharding is
within one replica's answer). Observation is **scheme-agnostic**: every
server answered by :meth:`answer_batch` feeds its replica's EMA,
whatever the scheme — so the ranking is warm before any subset traffic
arrives. The *consumer* is subset-only by design: only Subset-PIR's
``query()`` takes a ``pick_servers`` policy, so only it ever reads
:meth:`fastest` (paper §5.1, priced at δ); other schemes contact all d
replicas regardless of the EMAs. tests/test_serving_pipeline.py pins
both halves of this contract.
"""

from __future__ import annotations

import dataclasses
import math
import time
import warnings
from functools import partial
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.db import packing
from repro.db.store import RecordStore
from repro.dist.collectives import sharded_record_lookup, xor_psum
from repro.dist.sharding import (
    current_mesh,
    mesh_axis_names,
    touched_record_blocks,
)
from repro.kernels.backend import (
    AutotuneTable,
    ExecutionPlan,
    KernelPlanner,
    dump_autotune,
    resolve_kernel_impl_alias,
    scatter_update,
    shard_answer_fn,
)
from repro.core.protocol import MultiQueries, Queries

__all__ = ["ServerStats", "ShardedBackend"]


@dataclasses.dataclass
class ServerStats:
    """Latency EMA per database replica (straggler tracking)."""

    ema_s: float = 0.0
    n: int = 0

    def observe(self, dt: float, alpha: float = 0.2) -> None:
        self.ema_s = dt if self.n == 0 else (1 - alpha) * self.ema_s + alpha * dt
        self.n += 1


class ShardedBackend:
    """Mesh-aware batch executor with per-replica latency tracking."""

    def __init__(
        self,
        store: RecordStore,
        *,
        simulate_latency: Optional[Callable[[int], float]] = None,
        backend: str = "auto",
        autotune: Optional[AutotuneTable] = None,
        autotune_file: Optional[str] = None,
        parity_min_batch: Optional[int] = None,
        vmem_budget_bytes: Optional[int] = None,
        kernel_impl: Optional[str] = None,
    ):
        if kernel_impl is not None:
            warnings.warn(
                "kernel_impl= is deprecated; use backend= (the execution-"
                "backend registry, README §Execution backends)",
                DeprecationWarning,
                stacklevel=2,
            )
            backend = resolve_kernel_impl_alias(kernel_impl, backend)
        self.store = store
        self.planner = KernelPlanner(
            store,
            backend=backend,
            table=autotune,
            parity_min_batch=parity_min_batch,
            vmem_budget_bytes=vmem_budget_bytes,
        )
        self.autotune_file = autotune_file
        #: autotune entries refused at load because they were measured on
        #: a different device (see AutotuneTable.update)
        self.autotune_dropped = 0
        if autotune_file is not None:
            try:
                # entries stamped for a different store shape are dropped
                # like foreign devices: a live store that changed shape
                # since the dump must not warm-start from stale timings
                self.autotune_dropped = self.planner.table.update(
                    AutotuneTable.load(autotune_file),
                    store_shape=(store.n, store.words),
                )
            except FileNotFoundError:
                pass  # cold start; save_autotune() creates it
        self.stats: Dict[int, ServerStats] = {}
        self._sim = simulate_latency
        # per-mesh sharded copies of the db/planes + jitted shard_map fns
        self._mesh_db: Dict[int, dict] = {}
        self._mesh_fns: Dict[tuple, Callable] = {}
        # the live-store version the mesh residency was last synced to
        # (swap_store(live=...) advances it) + cumulative counters for
        # the touched-shard invalidation contract (DESIGN.md §13)
        self._live_version = 0
        self.mesh_metrics: Dict[str, int] = {
            "mesh_states_dropped": 0,
            "mesh_states_refreshed": 0,
            "mesh_shards_kept": 0,
            "mesh_shards_updated": 0,
        }
        #: the full counter dict of the most recent swap_store call —
        #: the public observability surface for per-ingest invalidation
        #: cost (consumers read this, never the store's shard-version
        #: vector; tools/check_api.py enforces the fence)
        self.last_swap: Dict[str, int] = {}
        # (id(store), planes) memo for snapshot-pinned parity answers:
        # a batch that pinned a pre-ingest snapshot may still need that
        # version's bitplanes after the planner moved on
        self._pinned_planes: Optional[Tuple[int, jnp.ndarray]] = None
        self.path_counts = {"fold": 0, "parity": 0, "sparse": 0, "direct": 0}
        #: every plan :meth:`answer_batch` executed, by ``describe()`` —
        #: what an on-chip check reads to prove which kernels ran
        self.plans_executed: Dict[str, ExecutionPlan] = {}

    @property
    def backend_name(self) -> str:
        """The registered execution backend this instance plans with."""
        return self.planner.backend_name

    @property
    def kernel_impl(self) -> str:
        """Deprecated alias for :attr:`backend_name` (old introspection
        surface; the constructor keyword maps the same way)."""
        return self.planner.backend_name

    def save_autotune(self, path: Optional[str] = None) -> str:
        """Dump the planner's autotune table as JSON (default: the
        ``autotune_file`` this backend was constructed with)."""
        path = path or self.autotune_file
        if path is None:
            raise ValueError("no autotune_file configured and no path given")
        dump_autotune(path, self.planner.table)
        return path

    # ---------------------------------------------------------- store swaps
    def swap_store(
        self,
        store: RecordStore,
        *,
        touched_rows=None,
        live=None,
        reshard: str = "auto",
    ) -> Dict[str, int]:
        """Move the backend onto a new store version (DESIGN.md §13).

        The single-host incremental contract rides on
        :meth:`KernelPlanner.rebind`: a same-shape content swap with a
        known touched-row set keeps every cached :class:`ExecutionPlan`
        and refreshes only the touched bitplane rows; a shape change
        drops plans and planes.

        Mesh residency is where the distributed contract lives. With
        ``touched_rows`` known and ``reshard="auto"`` (the default),
        each cached sharded db (and its bitplanes, if materialized) is
        **refreshed in place, touched device shards only**: untouched
        shards keep their exact device buffers (asserted by identity in
        tests/_multidevice_checks.py), their banked plans, their jitted
        shard_map executors, and the straggler EMAs — the ingest cost
        becomes O(touched), not O(n). An append that still fits the
        residency's row padding updates only the tail shards it lands
        in; a residency it no longer fits (or a words change) is dropped
        and rebuilds lazily, exactly like ``reshard="full"`` /
        ``touched_rows=None`` (the old whole-store re-shard, kept as the
        explicit fallback and the benchmark baseline).

        ``live`` (the :class:`~repro.db.live.VersionedStore` the
        snapshot came from) is observability only: the counters gain
        ``store_shards_touched`` / ``store_shards_total`` from its
        shard-version vector since the last swap — what CI asserts stays
        below the shard count on a burst.

        Sharded arrays are values, so a batch already holding the old
        residency keeps answering against it — the refresh builds a new
        sharded array and in-flight batches stay torn-free. Returns the
        planner's counter deltas plus the mesh refresh counters (also
        accumulated in :attr:`mesh_metrics`)."""
        if reshard not in ("auto", "full"):
            raise ValueError(f"reshard must be auto|full, got {reshard!r}")
        counters = self.planner.rebind(store, touched_rows=touched_rows)
        self.store = store
        counters.update(
            mesh_states_dropped=0, mesh_states_refreshed=0,
            mesh_shards_kept=0, mesh_shards_updated=0,
        )
        if live is not None:
            counters["store_shards_touched"] = len(
                live.shards_touched_since(self._live_version)
            )
            counters["store_shards_total"] = live.shards
            self._live_version = live.version
        incremental = reshard == "auto" and touched_rows is not None
        if incremental and self._mesh_db:
            rows_np = np.asarray(touched_rows, np.int64).ravel()
            vals = (
                jnp.take(store.packed, jnp.asarray(rows_np), axis=0)
                if rows_np.size else None
            )
            for key in list(self._mesh_db):
                st = self._refresh_mesh_state(
                    self._mesh_db[key], store, rows_np, vals
                )
                if st is None:
                    del self._mesh_db[key]
                    counters["mesh_states_dropped"] += 1
                else:
                    counters["mesh_states_refreshed"] += 1
                    counters["mesh_shards_kept"] += st["kept"]
                    counters["mesh_shards_updated"] += st["updated"]
        elif not incremental:
            counters["mesh_states_dropped"] = len(self._mesh_db)
            self._mesh_db.clear()
        for k in self.mesh_metrics:
            self.mesh_metrics[k] += counters[k]
        self.last_swap = dict(counters)
        return counters

    def _refresh_mesh_state(
        self,
        state: dict,
        store: RecordStore,
        rows_np: np.ndarray,
        vals: Optional[jnp.ndarray],
    ) -> Optional[Dict[str, int]]:
        """Rewrite only the touched device shards of one mesh residency.

        Returns ``{"kept", "updated"}`` shard counts, or None when the
        residency cannot absorb the delta in place (words changed, the
        store outgrew the row padding, or shards are not all process-
        addressable) — the caller drops it and the next on-mesh batch
        re-shards from scratch.

        Mechanics: the sharded db is decomposed into its per-device
        blocks (``addressable_shards``); a block none of the touched
        rows fall in contributes its existing device buffer *by
        identity*, a touched block gets the delta's rows scattered into
        a fresh buffer on its own device (``scatter_update`` under the
        ``_ingest``/``scatter_shard`` autotune family), and
        ``jax.make_array_from_single_device_arrays`` reassembles the
        sharded value without any cross-device reshuffle. Bitplanes, if
        this residency materialized them, refresh the same way with the
        touched rows' fresh planes."""
        db = state["db"]
        n_pad, rshards = state["n_pad"], state["rshards"]
        if int(db.shape[1]) != store.words or store.n > n_pad:
            return None
        shards = list(db.addressable_shards)
        if len(shards) != rshards:
            return None  # multi-process residency: refresh is per-host
        block = n_pad // rshards
        touched = set(touched_record_blocks(rows_np, n_pad, rshards))

        def rebuilt(arr, fresh_rows):
            datas, kept, updated = [], 0, 0
            for sh in arr.addressable_shards:
                start = sh.index[0].start or 0
                if start // block not in touched:
                    datas.append(sh.data)  # byte-identical device buffer
                    kept += 1
                    continue
                sel = (rows_np >= start) & (rows_np < start + block)
                local = jnp.asarray(rows_np[sel] - start, jnp.int32)
                # the rows go to the shard's own device: a store that is
                # itself sharded hands them over spread across the mesh
                datas.append(
                    scatter_update(
                        jnp.asarray(sh.data), local,
                        jax.device_put(fresh_rows[sel], sh.device),
                        backend=self.backend_name, family="scatter_shard",
                    )
                )
                updated += 1
            return (
                jax.make_array_from_single_device_arrays(
                    arr.shape, arr.sharding, datas
                ),
                kept,
                updated,
            )

        if vals is None or not touched:
            return {"kept": rshards, "updated": 0}
        state["db"], kept, updated = rebuilt(db, vals)
        if state["planes"] is not None:
            fresh = packing.bitplanes_from_packed(
                vals, dtype=state["planes"].dtype
            )
            state["planes"], _, _ = rebuilt(state["planes"], fresh)
        return {"kept": kept, "updated": updated}

    # -------------------------------------------------------------- autotune
    def autotune_step(self, max_cells: int = 1) -> int:
        """Run the planner's autotune search for up to ``max_cells``
        pending cells (the async front's idle-slot job); returns cells
        tuned. Request threads never call this — they plan from the
        table or the analytic prior only."""
        return self.planner.tune_step(max_cells)

    def tune_pending(self) -> int:
        """Drain the planner's pending-cell queue (benchmarks and
        shutdown dumps); returns cells tuned."""
        return self.planner.tune_pending()

    # ------------------------------------------------------------ stragglers
    def ensure_replicas(self, d: int) -> None:
        for i in range(d):
            self.stats.setdefault(i, ServerStats())

    def relabel_replicas(self, survivors: List[int]) -> None:
        """Compact the replica id space after loss: survivor ``s`` (old
        id) becomes logical replica ``i`` (its rank in ``survivors`` —
        mirroring :func:`~repro.dist.fault.plan_elastic_remesh`'s sorted
        survivor tuple). Latency EMAs carry over under the new labels so
        the straggler ranking stays warm across a remesh; dead replicas'
        stats retire. The simulated-latency hook keeps seeing *physical*
        ids — a simulated-slow machine stays slow whatever logical slot
        the remesh parks it in."""
        order = [int(s) for s in survivors]
        self.stats = {
            i: self.stats.get(s, ServerStats()) for i, s in enumerate(order)
        }
        if self._sim is not None:
            phys = self._sim
            m = tuple(order)
            self._sim = lambda i: phys(m[i]) if 0 <= i < len(m) else phys(i)

    def observe_latency(self, server: int, dt: float) -> None:
        self.stats.setdefault(server, ServerStats()).observe(dt)

    def fastest(self, t: int) -> List[int]:
        """Rank replicas by latency EMA; unobserved rank first (explore)."""
        order = sorted(
            self.stats,
            key=lambda i: (self.stats[i].n > 0, self.stats[i].ema_s),
        )
        return order[:t]

    # ------------------------------------------------------- mesh residency
    def _mesh_state(self) -> Optional[dict]:
        """Sharded db residency for the active mesh (None off-mesh)."""
        mesh = current_mesh()
        if mesh is None:
            return None
        raxes = mesh_axis_names("records")
        if not raxes:
            return None
        rshards = math.prod(mesh.shape[a] for a in raxes)
        if rshards <= 1:
            return None
        state = self._mesh_db.get(id(mesh))
        if state is None or state["raxes"] != raxes:
            # single-mesh residency: switching meshes (elastic remesh) evicts
            # the previous mesh's device-resident db/planes and jitted fns
            # instead of pinning one sharded copy per mesh generation
            self._mesh_db.clear()
            self._mesh_fns.clear()
            self.planner.invalidate()
            n = self.store.n
            n_pad = -(-n // rshards) * rshards
            db = jnp.pad(self.store.packed, ((0, n_pad - n), (0, 0)))
            state = {
                "mesh": mesh,
                "raxes": raxes,
                "rshards": rshards,
                "n_pad": n_pad,
                "db": jax.device_put(db, NamedSharding(mesh, P(raxes, None))),
                "planes": None,
            }
            self._mesh_db[id(mesh)] = state
        return state

    def _mesh_planes(self, state: dict) -> jnp.ndarray:
        if state["planes"] is None:
            planes = jnp.pad(
                self.planner.planes(),
                ((0, state["n_pad"] - self.store.n), (0, 0)),
            )
            state["planes"] = jax.device_put(
                planes, NamedSharding(state["mesh"], P(state["raxes"], None))
            )
        return state["planes"]

    def _query_axes(self, state: dict, b: int) -> Tuple[str, ...]:
        """Mesh axes for the batch dim: "queries" rule minus record axes,
        dropped when the batch doesn't divide."""
        qaxes = tuple(
            a for a in mesh_axis_names("queries") if a not in state["raxes"]
        )
        if not qaxes:
            return ()
        qshards = math.prod(state["mesh"].shape[a] for a in qaxes)
        return qaxes if qshards > 1 and b % qshards == 0 else ()

    def _mask_fn(
        self, state: dict, qaxes: Tuple[str, ...], plan: ExecutionPlan
    ) -> Callable:
        """Build (and cache) the shard_map'd per-server answer function
        from a mesh plan's decision fields."""
        key = (
            id(state["mesh"]), state["raxes"], qaxes,
            plan.path, plan.impl, plan.m_budget, plan.blocks,
        )
        fn = self._mesh_fns.get(key)
        if fn is not None:
            return fn

        mesh, raxes = state["mesh"], state["raxes"]
        answer_shard = shard_answer_fn(plan)

        @partial(
            shard_map,
            mesh=mesh,
            in_specs=(P(raxes, None), P(qaxes or None, raxes)),
            out_specs=P(qaxes or None, None),
            check_vma=False,
        )
        def _answer(operand_loc, m_loc):
            return xor_psum(answer_shard(operand_loc, m_loc), raxes)

        fn = jax.jit(_answer)
        self._mesh_fns[key] = fn
        return fn

    # ------------------------------------------------------------- planning
    def prepare(
        self, routed: Queries, *, scheme: Optional[object] = None
    ) -> ExecutionPlan:
        """Resolve one batch's :class:`ExecutionPlan` (cached in the
        planner). The serving pipeline calls this for batch k+1 while
        batch k executes; calling it is optional — :meth:`answer_batch`
        plans on demand when no plan is handed in. A
        :class:`~repro.core.protocol.MultiQueries` batch threads its
        padded per-request column count into the planner so the fused
        multi-lookup path joins the candidate race (DESIGN.md
        §Multi-index wire format)."""
        bucket = int(routed.payload.shape[1])
        k_max = routed.k_max if isinstance(routed, MultiQueries) else None
        if routed.kind != "mask":
            return self.planner.plan(
                routed, bucket, None, scheme=scheme, k_max=k_max
            )
        return self.planner.plan(
            routed, bucket, self._mesh_state(), scheme=scheme, k_max=k_max
        )

    def _plan_matches(
        self,
        plan: Optional[ExecutionPlan],
        state: Optional[dict],
        routed: Queries,
        n_host: Optional[int] = None,
    ) -> bool:
        """A handed-in plan is only reusable if the mesh residency it was
        built for still holds (plans carry no executor on-mesh) AND it
        was planned for this batch's wire parameters — a sparse plan's
        index budget is sized from θ, so executing it against a
        different-θ batch would truncate indices and corrupt bits."""
        if plan is None:
            return False
        on_mesh = state is not None
        if (plan.run is None) != on_mesh:
            return False
        if plan.theta != getattr(routed, "theta", None):
            return False
        # a multi plan's kernel asserts bucket % k_max == 0 — a handed-in
        # plan whose padded column count doesn't divide this batch must
        # be replanned, not executed
        k_plan = dict(plan.blocks).get("k_max")
        if k_plan and int(routed.payload.shape[1]) % int(k_plan):
            return False
        n_eff = (
            state["n_pad"] // state["rshards"] if on_mesh
            else (n_host if n_host is not None else self.store.n)
        )
        return plan.n == n_eff

    # ------------------------------------------------------------ execution
    def _pinned_operand(
        self, plan: ExecutionPlan, store: RecordStore
    ) -> jnp.ndarray:
        """The kernel operand for a *pinned* snapshot (DESIGN.md §13):
        its packed words, or its bitplanes for the parity path (memoized
        per snapshot object — the double buffer has at most one stale
        snapshot in flight)."""
        if plan.path != "parity":
            return store.packed
        hit = self._pinned_planes
        if hit is None or hit[0] != id(store):
            self._pinned_planes = (id(store), store.bitplanes())
        return self._pinned_planes[1]

    def _answer_mask_server(
        self,
        masks_s: jnp.ndarray,
        routed: Queries,
        plan: Optional[ExecutionPlan],
        scheme: Optional[object],
        store: Optional[RecordStore] = None,
    ) -> Tuple[jnp.ndarray, ExecutionPlan]:
        """One server's [B, n] masks -> [B, W] packed partial answer.

        ``store`` pins the snapshot the answer must be computed against
        (None: the backend's current store)."""
        state = self._mesh_state()
        n_host = store.n if store is not None else None
        if not self._plan_matches(plan, state, routed, n_host):
            plan = self.planner.plan(
                routed, int(masks_s.shape[0]), state, scheme=scheme,
                k_max=getattr(routed, "k_max", None),
            )
        self.path_counts[plan.family] += 1

        if state is None:  # single host: the plan carries the executor
            if store is not None and store is not self.planner.store:
                # snapshot-pinned: a delta landed after this batch
                # planned; answer against the pinned version's operand,
                # not the planner's current one
                return plan(
                    masks_s, operand=self._pinned_operand(plan, store)
                ), plan
            return plan(masks_s), plan

        pad = state["n_pad"] - self.store.n
        if pad:
            masks_s = jnp.pad(masks_s, ((0, 0), (0, pad)))
        qaxes = self._query_axes(state, masks_s.shape[0])
        operand = (
            self._mesh_planes(state) if plan.path == "parity" else state["db"]
        )
        return self._mask_fn(state, qaxes, plan)(operand, masks_s), plan

    def _answer_index_server(
        self, reqs_s: jnp.ndarray, store: Optional[RecordStore] = None
    ) -> jnp.ndarray:
        """One server's [B, k] index requests -> [B, k, W] records."""
        self.path_counts["direct"] += 1
        state = self._mesh_state()
        if state is None:
            pinned = store if store is not None else self.store
            return jnp.take(pinned.packed, reqs_s, axis=0)
        # clamp to the REAL record range: the db is zero-padded to n_pad and
        # the lookup's own clamp is against n_pad, which would make an
        # out-of-range id return the zero pad record on-mesh only
        reqs_s = jnp.clip(reqs_s, 0, self.store.n - 1)
        key = (id(state["mesh"]), state["raxes"], "index")
        fn = self._mesh_fns.get(key)
        if fn is None:
            # a fresh jit wrapper per mesh: jit's cache keys on shapes, not
            # on the mesh the traced shard_map baked in
            fn = jax.jit(sharded_record_lookup)
            self._mesh_fns[key] = fn
        return fn(state["db"], reqs_s)

    def answer_batch(
        self,
        routed: Queries,
        *,
        plan: Optional[ExecutionPlan] = None,
        scheme: Optional[object] = None,
        store: Optional[RecordStore] = None,
    ) -> jnp.ndarray:
        """Answer every contacted server, tracking per-replica latency.

        ``plan`` (from :meth:`prepare`) skips planning on the hot path —
        the double-buffered pipeline prepares batch k+1 while batch k
        runs here. ``store`` pins the snapshot version the batch must be
        answered against (DESIGN.md §13): when an ingest swapped the
        backend's store between this batch's plan and its execution, the
        answer still comes from the pinned snapshot, bit-identically —
        single-host; on-mesh the residency swap is the consistency
        boundary instead. The latency EMA is fed for **every** scheme's
        servers (see the module docstring: observation is
        scheme-agnostic, only Subset-PIR consumes the ranking).

        Returns stacked responses: [d_eff, B, W] (mask) or
        [d_eff, B, k, W] (index), ordered like ``routed.servers``.
        """
        responses = []
        for pos, sid in enumerate(routed.servers):
            t0 = time.perf_counter()
            if routed.kind == "mask":
                r, plan = self._answer_mask_server(
                    routed.payload[pos], routed, plan, scheme, store
                )
            else:
                r = self._answer_index_server(routed.payload[pos], store)
            r.block_until_ready()
            self.observe_latency(
                sid,
                (self._sim(sid) if self._sim else 0.0)
                + time.perf_counter() - t0,
            )
            responses.append(r)
        if plan is not None:
            self.plans_executed[plan.describe()] = plan
        return jnp.stack(responses)
