"""The execution-backend layer: every kernel decision, in one place.

Before this layer existed, "which kernel answers this batch" was smeared
across the stack: ``kernel_impl="auto|pallas|ref"`` strings in the serve
backend, ``on_cpu()`` checks and a hardcoded parity-crossover constant in
``kernels/ops.py``, and per-scheme cost formulas that nothing downstream
read. Now the serve layer asks this module to **plan** and then executes
the returned :class:`ExecutionPlan` — it never names a kernel again
(DESIGN.md §Execution backends has the plan lifecycle).

Three pieces:

* **Backend registry** (:func:`register_backend`): ``pallas`` (the TPU
  kernels, interpret mode off-TPU), ``ref`` (the pure-jnp oracles —
  bit-identical, and the faster choice in a CPU serving hot path), and
  ``auto`` (kernels on accelerators, oracles on CPU hosts). A backend
  resolves to a concrete *impl* and the planner builds executors from it.
* **Autotune table** (:class:`AutotuneTable`): a memo of *measured*
  search results, keyed ``(scheme, bucket, backend, n, words, family)``.
  Each entry records the winning candidate (path + impl + block shape),
  the microbenchmark microseconds of **every** candidate it beat —
  including the ref-oracle baseline, which is how the never-regress
  guarantee is auditable after the fact — and the fingerprint of the
  device it was measured on (:func:`device_fingerprint`). ``load`` /
  ``update`` refuse to merge entries fingerprinted for a different
  device: a table dumped on a v4 must not pin plans on a v5e host, so
  mismatched entries are dropped and counted instead of merged. The
  table dumps/loads as JSON (:func:`dump_autotune` /
  :func:`load_autotune`; format in DESIGN.md §Execution backends) so a
  deployment can ship warmed decisions. EXPERIMENTS.md §Autotune
  describes the methodology.
* **Planner** (:class:`KernelPlanner`): ``plan(scheme_plan, bucket,
  mesh_state)`` maps one batch's wire plan (the scheme's
  :class:`~repro.core.protocol.Queries` — its ``kind`` and θ are the
  only scheme-side facts execution needs) to an :class:`ExecutionPlan`
  carrying the chosen path, impl, block sizes, sparse index budget and
  (single-host) a ready jitted executor.

``plan()`` **never measures**. On a request thread the planner answers
from the autotune table when a measured entry exists, and from the
analytic cost-model prior (``SchemeProtocol.costs(n)`` → the C_p
crossover) when it does not — a cold cell costs zero microbenchmarks and
zero extra jit compiles on the serving path. Cold cells are queued as
*pending*, and :meth:`KernelPlanner.tune_step` runs the actual search in
the ``AsyncFrontend``'s idle slot (where cache prefill already lives):
it enumerates every candidate for the cell — path ∈ {fold, parity} for
the dense-mask family, {fused, streaming-pair} × ``block_w`` ×
``grid_order`` for the sparse family — measures each at the cell's true
(bucket, n, W) shape, and records the winner.

**Never-regress guarantee:** when the backend is ``auto`` and resolves
to a non-ref impl, the candidate set *always includes the ref-oracle
baseline* for the same cell, so the recorded winner can be "run the
oracle" — ``auto`` keeps whichever side actually wins on this device,
and BENCH's ``exec_perf_floor`` row asserts ``auto ≥ ref`` (within noise
tolerance) in every measured cell.

The serve layer's ``parity_min_batch`` knob survives as a *forced*
decision (``ExecutionPlan.source == "forced"``) — useful in tests and
benchmarks — but the default is measured-or-model.
"""

from __future__ import annotations

import dataclasses
import json
import threading
import time
import zlib
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.db import packing
from repro.db.store import RecordStore
from repro.kernels import ops, ref
from repro.kernels.fused import (
    LANES,
    fused_block_w,
    fused_gather_fold,
    fused_multi_gather_fold,
)
from repro.kernels.gather_xor import gather_xor, indices_from_mask
from repro.kernels.parity_matmul import parity_matmul
from repro.kernels.xor_fold import xor_fold

__all__ = [
    "ExecutionPlan",
    "ExecutionBackend",
    "register_backend",
    "get_backend",
    "registered_backends",
    "resolve_kernel_impl_alias",
    "AutotuneTable",
    "autotune_table",
    "device_fingerprint",
    "device_memory_limit",
    "load_autotune",
    "dump_autotune",
    "PlanCandidate",
    "TuneCell",
    "KernelPlanner",
    "shard_answer_fn",
    "scatter_update",
]


# --------------------------------------------------------------------------
# The plan
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """One batch's resolved execution decision (DESIGN.md §Execution
    backends: the plan lifecycle).

    ``path`` is the physical kernel form (``fold`` / ``parity`` /
    ``sparse_fused`` / ``sparse_pair`` / ``sparse_ref`` / ``direct``),
    ``impl`` the impl the executor is built from — normally the resolved
    backend (never "auto"), but under the never-regress guarantee a
    measured winner may be ``ref`` even when the backend resolved to
    pallas. ``blocks`` carries the chosen kernel block shape
    (``block_w``, ``grid_order``), ``m_budget`` the sparse index budget
    (None off the sparse family), and ``source`` where the decision came
    from: ``measured`` (autotune search winner), ``model`` (analytic
    cost-model prior — the cold-cell answer while the search is still
    pending), ``forced`` (caller override) or ``only`` (single
    candidate). ``run`` is the jitted single-host executor (payload ->
    [B, W]); it is None for decision-only plans — mesh plans, where the
    sharded serve layer builds the shard_map executor *from the plan's
    decision fields*, and the direct family, whose gather the serve
    layer's index path owns — the decision itself still lives here.
    """

    path: str
    impl: str
    bucket: int
    n: int
    blocks: Tuple[Tuple[str, Any], ...] = ()
    m_budget: Optional[int] = None
    theta: Optional[float] = None
    interpret: bool = False
    source: str = "only"
    run: Optional[Callable[[jnp.ndarray], jnp.ndarray]] = dataclasses.field(
        default=None, compare=False, repr=False
    )
    # the jitted raw executor ``(operand, payload) -> [B, W]`` behind
    # ``run`` (same nullability). ``run`` resolves the operand from the
    # planner's *current* store at call time — which is what lets a plan
    # survive a same-shape store swap (DESIGN.md §13) — while the serve
    # layer passes an explicit operand here to answer against a batch's
    # *pinned* snapshot even after later deltas landed.
    kernel: Optional[
        Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray]
    ] = dataclasses.field(default=None, compare=False, repr=False)

    @property
    def family(self) -> str:
        """The coarse path family (the serve layer's path_counts key)."""
        if self.path.startswith("sparse"):
            return "sparse"
        return self.path

    def __call__(
        self, payload: jnp.ndarray, operand: Optional[jnp.ndarray] = None
    ) -> jnp.ndarray:
        if self.run is None:
            raise RuntimeError(
                "this ExecutionPlan carries the decision only (mesh plans "
                "and the direct family); the sharded serve layer owns the "
                "executor"
            )
        if operand is not None:
            # snapshot-pinned execution: answer against the caller's
            # operand (a pinned store version's packed words / planes),
            # not whatever the planner's store points at right now
            return self.kernel(operand, payload)
        return self.run(payload)

    def describe(self) -> str:
        return (
            f"{self.path}/{self.impl} b={self.bucket} n={self.n} "
            f"source={self.source}"
        )


# --------------------------------------------------------------------------
# Backend registry
# --------------------------------------------------------------------------
_BACKENDS: Dict[str, "ExecutionBackend"] = {}


def register_backend(name: str) -> Callable[[type], type]:
    """Class decorator: register an execution backend under its config
    name (the string ``backend=`` flags and configs carry)."""

    def deco(cls: type) -> type:
        key = name.lower()
        if key in _BACKENDS:
            raise ValueError(f"backend {key!r} already registered")
        cls.name = key
        _BACKENDS[key] = cls()
        return cls

    return deco


def get_backend(name: str) -> "ExecutionBackend":
    try:
        return _BACKENDS[name.lower()]
    except KeyError:
        raise ValueError(
            f"unknown backend {name!r}; registered: {registered_backends()}"
        ) from None


def registered_backends() -> Tuple[str, ...]:
    return tuple(sorted(_BACKENDS))


def resolve_kernel_impl_alias(
    kernel_impl: Optional[str], backend: str
) -> str:
    """Map the deprecated ``kernel_impl="auto|pallas|ref"`` knob onto the
    backend registry (README §Execution backends has the migration
    table). ``kernel_impl`` strings were exactly the registered backend
    names, so the alias is the identity — this helper exists so callers
    keep one validated seam instead of string-matching."""
    if kernel_impl is None:
        return backend
    get_backend(kernel_impl)  # same validation the old constructor did
    return kernel_impl


class ExecutionBackend:
    """One registered execution backend; ``resolve()`` returns the
    concrete impl ("pallas" or "ref") the planner builds executors for."""

    name = "?"

    def resolve(self) -> str:
        return self.name


@register_backend("pallas")
class PallasBackend(ExecutionBackend):
    """The TPU kernels (Mosaic on TPU, interpret mode elsewhere)."""


@register_backend("ref")
class RefBackend(ExecutionBackend):
    """The pure-jnp oracles — bit-identical to the kernels by the
    tests/test_kernels.py equality sweeps, and the faster choice on CPU
    hosts (emulating a TPU interpreter in a serving hot path costs ~50×
    for identical bits)."""


@register_backend("auto")
class AutoBackend(ExecutionBackend):
    """Kernels on accelerators, oracles on CPU hosts — and, per measured
    cell, whichever of the two the autotune search proves faster (the
    never-regress guarantee; the resolved impl is only the prior)."""

    def resolve(self) -> str:
        return "ref" if ops.on_cpu() else "pallas"


# --------------------------------------------------------------------------
# Autotune table
# --------------------------------------------------------------------------
# (scheme, bucket, backend-impl, n, words, family): the conceptual key
# is (scheme, bucket, backend); n/words qualify it so two stores of
# different shape never share a measurement, and family ("mask" or
# "sparse@<theta>", with a "+multi@<k_max>" suffix for jagged
# multi-index buckets whose candidate set adds the fused multi kernel)
# keeps decisions with disjoint candidate sets from ever colliding
# under one key (a sparse scheme can take either route depending on
# whether gathering pays)
Key = Tuple[str, int, str, int, int, str]


def _family(theta: Optional[float], k_max: Optional[int] = None) -> str:
    base = "mask" if theta is None else f"sparse@{float(theta):g}"
    return base if not k_max else f"{base}+multi@{int(k_max)}"


def device_fingerprint() -> Dict[str, str]:
    """Identity of the device measurements on this host are valid for:
    ``{"platform", "device_kind"}`` of ``jax.devices()[0]``. Autotune
    entries are stamped with it at :meth:`AutotuneTable.put` time, and
    merges drop entries whose fingerprint is not the local one — a
    microsecond measured on one accelerator generation says nothing
    about another."""
    dev = jax.devices()[0]
    return {
        "platform": str(dev.platform),
        "device_kind": str(getattr(dev, "device_kind", "") or dev.platform),
    }


def device_memory_limit() -> Optional[int]:
    """Bytes one device of this host can hold (``memory_stats()
    ["bytes_limit"]`` on TPU), or None where the backend does not say —
    the planner keeps a candidate whose operand cannot fit out of every
    race."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return None
    return int(dev.memory_stats()["bytes_limit"])


class AutotuneTable:
    """Memo of measured autotune-search results.

    Entry: ``(scheme, bucket, backend, n, words, family) -> {"path",
    "impl", "blocks", "source", "us", "device"}`` where ``path`` /
    ``impl`` / ``blocks`` describe the winning candidate, ``us`` maps
    every measured candidate label to its microbenchmark microseconds
    (the ref baseline's timing is in here too — the never-regress
    decision stays auditable), and ``device`` is the fingerprint of the
    host that measured it. JSON round-trip via :meth:`to_json` /
    :meth:`from_json`; the on-disk format is the documented
    autotune-file format (DESIGN.md §Execution backends).

    :meth:`update` (and therefore :func:`load_autotune`) drops entries
    fingerprinted for a different device instead of merging them; the
    running count lands in :attr:`dropped` and is returned per call.

    Entries measured against a concrete store additionally carry a
    ``store_shape`` stamp (``[n, words]`` at measurement time): a dumped
    ``--autotune-file`` table survives a same-shape restart of a live
    store, while entries stamped for a *different* shape are dropped and
    counted by :meth:`update` exactly like foreign devices — a live
    store that appended past its dump would otherwise warm-start from
    cells whose timings describe a database it no longer is.
    """

    VERSION = 2

    def __init__(self) -> None:
        self._entries: Dict[Key, Dict[str, Any]] = {}
        #: cumulative count of entries refused by :meth:`update` because
        #: their device fingerprint did not match this host
        self.dropped = 0

    def __len__(self) -> int:
        return len(self._entries)

    def get(self, key: Key) -> Optional[Dict[str, Any]]:
        return self._entries.get(key)

    def put(
        self,
        key: Key,
        path: str,
        *,
        impl: str,
        source: str,
        blocks: Optional[Dict[str, Any]] = None,
        us: Optional[Dict[str, float]] = None,
        device: Optional[Dict[str, str]] = None,
        store_shape: Optional[Sequence[int]] = None,
    ) -> None:
        """Record a decision. ``device=None`` stamps the local
        fingerprint (the normal path for fresh measurements);
        deserialization passes the dumped fingerprint through.
        ``store_shape`` is the ``(n, words)`` the measurement ran
        against (None for shape-agnostic entries, e.g. hand-built
        tables)."""
        self._entries[key] = {
            "path": path,
            "impl": impl,
            "blocks": dict(blocks or {}),
            "source": source,
            "us": dict(us or {}),
            "device": dict(device) if device is not None
            else device_fingerprint(),
            "store_shape": (
                [int(x) for x in store_shape]
                if store_shape is not None else None
            ),
        }

    def items(self):
        return self._entries.items()

    def clear(self) -> None:
        self._entries.clear()

    # ------------------------------------------------------------ JSON io
    def to_json(self) -> str:
        entries = [
            {
                "scheme": k[0], "bucket": k[1], "backend": k[2],
                "n": k[3], "words": k[4], "family": k[5], **v,
            }
            for k, v in sorted(self._entries.items())
        ]
        return json.dumps(
            {"version": self.VERSION, "entries": entries}, indent=2
        )

    @classmethod
    def from_json(cls, text: str) -> "AutotuneTable":
        blob = json.loads(text)
        if blob.get("version") != cls.VERSION:
            raise ValueError(
                f"autotune table version {blob.get('version')!r} != "
                f"{cls.VERSION}"
            )
        table = cls()
        for e in blob["entries"]:
            table.put(
                (
                    str(e["scheme"]), int(e["bucket"]), str(e["backend"]),
                    int(e["n"]), int(e["words"]), str(e["family"]),
                ),
                str(e["path"]),
                impl=str(e["impl"]),
                source=str(e["source"]),
                blocks=dict(e.get("blocks", {})),
                us={k: float(v) for k, v in e.get("us", {}).items()},
                device={
                    k: str(v) for k, v in (e.get("device") or {}).items()
                },
                store_shape=e.get("store_shape"),
            )
        return table

    def dump(self, path: str) -> None:
        with open(path, "w") as f:
            f.write(self.to_json() + "\n")

    @classmethod
    def load(cls, path: str) -> "AutotuneTable":
        """Read a dumped table verbatim (entries keep whatever
        fingerprint they were measured with). Merging into a live table
        — :meth:`update` / :func:`load_autotune` — is where the
        device-mismatch filter applies."""
        with open(path) as f:
            return cls.from_json(f.read())

    def update(
        self,
        other: "AutotuneTable",
        *,
        store_shape: Optional[Sequence[int]] = None,
    ) -> int:
        """Merge ``other``'s entries measured on *this* device; drop the
        rest. With ``store_shape=(n, words)``, entries stamped for a
        *different* shape are dropped too (unstamped entries pass on the
        device check alone — old dumps stay loadable). Returns the
        number dropped by this call (also accumulated in
        :attr:`dropped`)."""
        local = device_fingerprint()
        want = (
            [int(x) for x in store_shape]
            if store_shape is not None else None
        )
        dropped = 0
        for key, entry in other._entries.items():
            stamp = entry.get("store_shape")
            if entry.get("device") != local or (
                want is not None and stamp is not None and stamp != want
            ):
                dropped += 1
                continue
            self._entries[key] = entry
        self.dropped += dropped
        return dropped


_PROCESS_TABLE = AutotuneTable()


def autotune_table() -> AutotuneTable:
    """The process-local autotune table every default planner shares."""
    return _PROCESS_TABLE


def load_autotune(
    path: str,
    table: Optional[AutotuneTable] = None,
    *,
    store_shape: Optional[Sequence[int]] = None,
) -> AutotuneTable:
    """Merge a dumped JSON table into ``table`` (default: the process
    table); returns the merged table. Entries fingerprinted for a
    different device — or, when ``store_shape`` is given, stamped for a
    different store shape — are dropped and counted
    (``table.dropped``)."""
    table = table if table is not None else _PROCESS_TABLE
    table.update(AutotuneTable.load(path), store_shape=store_shape)
    return table


def dump_autotune(path: str, table: Optional[AutotuneTable] = None) -> None:
    (table if table is not None else _PROCESS_TABLE).dump(path)


# --------------------------------------------------------------------------
# The search space
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class PlanCandidate:
    """One point in the autotune search space: a kernel path, the impl
    it runs on, and its block shape. ``label`` is the stable string the
    table's ``us`` timing map keys on."""

    path: str
    impl: str
    blocks: Tuple[Tuple[str, Any], ...] = ()

    @property
    def label(self) -> str:
        tail = "".join(f"+{k}={v}" for k, v in sorted(self.blocks))
        return f"{self.path}/{self.impl}{tail}"


@dataclasses.dataclass(frozen=True)
class TuneCell:
    """One pending autotune cell: everything :meth:`KernelPlanner.tune_step`
    needs to rebuild the candidate set and a representative payload
    off the request path."""

    scheme: str
    bucket: int
    impl: str  # the backend-resolved impl (candidate sets key off it)
    theta: Optional[float]
    n_eff: int
    m_budget: Optional[int]
    # jagged multi-index buckets: padded per-request column count (None
    # for plain single-index batches) — widens the sparse candidate set
    # with the fused multi kernel
    k_max: Optional[int] = None

    @property
    def family(self) -> str:
        return _family(self.theta, self.k_max)


# --------------------------------------------------------------------------
# Planner
# --------------------------------------------------------------------------
def _bench_mask(key: jax.Array, bucket: int, n: int, p: float) -> jnp.ndarray:
    """[bucket, n] {0,1} uint8 mask of density ≈ p for the microbench.
    Built from uint8 draws so the transient stays bucket·n bytes — a
    float32 uniform would be 4× that, mid-serving, at CT scale."""
    draws = jax.random.randint(key, (bucket, n), 0, 256, dtype=jnp.uint8)
    return (draws < max(1, round(p * 256))).astype(jnp.uint8)


def _measure_us(
    fn: Callable, *args, reps: int = 3,
    candidate: Optional["PlanCandidate"] = None,
) -> float:
    """One candidate's microbenchmark: one warmup call (pays jit), then
    best-of-``reps`` — the min is the right statistic for an ordering
    decision (a stall inflates a sample, nothing deflates one).
    ``candidate`` identifies what is being timed; the real timer ignores
    it, injected fakes (tests, simulators) key on it."""
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


class KernelPlanner:
    """Maps (wire plan, bucket, mesh residency) -> :class:`ExecutionPlan`.

    Owns the decisions the serve layer used to hardcode: which backend
    impl runs (registry), fold vs parity, fused vs streaming sparse,
    block shape and grid order, interpret mode and the sparse index
    budget. ``plan()`` is **measurement-free**: it answers from the
    autotune table or the analytic prior and queues cold cells; the
    search itself runs through :meth:`tune_step` /
    :meth:`tune_pending` in the async front's idle slot (DESIGN.md
    §Execution backends).

    ``seed`` fixes the bench-payload PRNG so a search over the same
    cells is reproducible; ``vmem_budget_bytes`` overrides the
    device-derived fused VMEM gate (``PIRConfig.fused_vmem_budget_bytes``
    threads through here); ``memory_limit_bytes`` overrides the
    device's memory limit (:func:`device_memory_limit`, read once here),
    which keeps candidates that cannot fit out of every race;
    ``measure`` swaps the microbenchmark function (tests inject
    deterministic timers).
    """

    # the sparse gather forms only pay while the index budget stays
    # meaningfully below the record count; at θ·n ≈ n streaming the whole
    # store (fold/parity) beats chasing nearly-all of it record by record
    GATHER_DENSE_CUTOFF = 0.75

    def __init__(
        self,
        store: RecordStore,
        *,
        backend: str = "auto",
        table: Optional[AutotuneTable] = None,
        parity_min_batch: Optional[int] = None,
        seed: int = 0,
        vmem_budget_bytes: Optional[int] = None,
        memory_limit_bytes: Optional[int] = None,
        measure: Optional[Callable[..., float]] = None,
    ):
        self.backend = get_backend(backend)
        self.store = store
        self.table = table if table is not None else autotune_table()
        self._parity_min_batch = parity_min_batch
        self._seed = int(seed)
        self._vmem_budget = vmem_budget_bytes
        self._memory_limit = (
            memory_limit_bytes if memory_limit_bytes is not None
            else device_memory_limit()
        )
        self._measure = measure if measure is not None else _measure_us
        self._planes: Optional[jnp.ndarray] = None
        self._plans: Dict[Tuple, ExecutionPlan] = {}
        self._pending: Dict[Key, TuneCell] = {}
        self._lock = threading.Lock()
        #: observability for the incremental-invalidation contract
        #: (DESIGN.md §13): how many cached plans a store swap kept vs
        #: dropped, and how much precompute (bitplane) work re-ran —
        #: tests assert a small delta touches only its own rows here.
        self.metrics: Dict[str, int] = {
            "rebinds": 0,
            "plans_built": 0,
            "plans_kept": 0,
            "plans_dropped": 0,
            "precompute_full_builds": 0,
            "precompute_rows_refreshed": 0,
        }

    # ------------------------------------------------------------- helpers
    @property
    def backend_name(self) -> str:
        return self.backend.name

    def planes(self) -> jnp.ndarray:
        if self._planes is None:
            self._planes = self.store.bitplanes()
            self.metrics["precompute_full_builds"] += 1
        return self._planes

    def _table_key(
        self, scheme_name: str, bucket: int, impl: str,
        theta: Optional[float] = None, k_max: Optional[int] = None,
    ) -> Key:
        return (
            scheme_name, int(bucket), impl, self.store.n, self.store.words,
            _family(theta, k_max),
        )

    def _table_hit(self, key: Key) -> Optional[Dict[str, Any]]:
        """A table entry is only trusted when its fingerprint matches
        this host (a hand-constructed table may carry foreign entries;
        :meth:`AutotuneTable.update` filters, ``table=`` does not)."""
        hit = self.table.get(key)
        if hit is None:
            return None
        dev = hit.get("device")
        if dev is not None and dev != device_fingerprint():
            return None
        return hit

    def _model_crossover(self) -> int:
        """The analytic fold/parity crossover batch (the prior the
        search refines; the constant that used to *be* the decision)."""
        return ops.parity_crossover_batch(
            self.store.n, self.store.record_bits
        )

    def _fused_bw(self, n_eff: int) -> int:
        return fused_block_w(
            n_eff, self.store.words, budget_bytes=self._vmem_budget
        )

    def _fits(self, extra_bytes: int) -> bool:
        """Whether a candidate that needs ``extra_bytes`` beside the
        store fits one device (always, where the device does not say)."""
        limit = self._memory_limit
        return limit is None or self.store.nbytes + extra_bytes <= limit

    def _parity_fits(self, n_eff: int) -> bool:
        """The parity path's operand: float32 bitplanes plus the bfloat16
        copy the kernel feeds the MXU, 6 bytes per record bit — about
        74 GB at CT scale, so a 16 GB chip never races it."""
        return self._fits(n_eff * self.store.record_bits * 6)

    # ------------------------------------------------------------ executors
    def _operand(self, path: str) -> jnp.ndarray:
        """The kernel operand for a path, from the *current* store — read
        per call, never baked into a jit trace, so a same-shape store
        swap (:meth:`rebind`) flows into every cached plan for free."""
        return self.planes() if path == "parity" else self.store.packed

    def _build_kernel(
        self, path: str, impl: str, m_budget: Optional[int],
        interpret: bool, blocks: Dict[str, Any],
    ) -> Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray]:
        """Jitted raw executor ``(operand, payload)`` for a resolved
        (path, impl). The operand stays an *argument* (jit retraces on
        shape change only), which is what makes plans swap- and
        snapshot-safe."""
        return jax.jit(_path_answer_fn(path, impl, m_budget, interpret,
                                       blocks))

    def _build_run(
        self, path: str, impl: str, m_budget: Optional[int],
        interpret: bool, blocks: Dict[str, Any],
        kernel: Optional[Callable] = None,
    ) -> Callable[[jnp.ndarray], jnp.ndarray]:
        """Single-host executor for a resolved (path, impl): the shared
        path→kernel dispatch, resolving this planner's operand at call
        time."""
        fn = kernel if kernel is not None else self._build_kernel(
            path, impl, m_budget, interpret, blocks
        )
        return lambda payload: fn(self._operand(path), payload)

    # ------------------------------------------------------- the search space
    def _impl_candidates(self, impl: str) -> List[str]:
        """Impls the search races. Under ``auto`` resolving to a kernel
        impl, the ref oracle is always in the race — that baseline IS
        the never-regress guarantee: the winner may be "run the oracle"
        and auto keeps it."""
        impls = [impl]
        if self.backend.name == "auto" and impl != "ref":
            impls.append("ref")
        return impls

    def _candidates(self, cell: TuneCell) -> List[PlanCandidate]:
        """Enumerate the cell's search space: path × block shape × grid
        layout, plus the ref baseline under ``auto``."""
        out: List[PlanCandidate] = []
        if cell.theta is None:  # dense-mask family: fold vs parity
            parity = self._parity_fits(cell.n_eff)
            for impl in self._impl_candidates(cell.impl):
                out.append(PlanCandidate("fold", impl))
                if parity:
                    out.append(PlanCandidate("parity", impl))
            return out
        # sparse family
        for impl in self._impl_candidates(cell.impl):
            if impl == "ref":
                # the oracle gathers [bucket, m, W] rows before folding
                if self._fits(
                    cell.bucket * (cell.m_budget or 0) * self.store.words * 4
                ):
                    out.append(PlanCandidate("sparse_ref", "ref"))
                continue
            bw = self._fused_bw(cell.n_eff)
            if bw:
                for go in ("qw", "wq"):
                    out.append(PlanCandidate(
                        "sparse_fused", impl,
                        (("block_w", bw), ("grid_order", go)),
                    ))
                # jagged multi-index buckets race the fused multi kernel
                # too: one grid step per (request, word-block), every
                # index of the request folded against the resident block.
                # The streaming pair and the ref oracle above stay in the
                # set as its bit-identical fallbacks.
                if cell.k_max:
                    for go in ("rw", "wr"):
                        out.append(PlanCandidate(
                            "sparse_multi_fused", impl,
                            (("block_w", bw), ("grid_order", go),
                             ("k_max", cell.k_max)),
                        ))
            # word-blocks the TPU compiler accepts: the whole record, and
            # one 128-lane vreg when the record is wider
            w = self.store.words
            for bw in ([w, LANES] if w > LANES else [w]):
                for go in ("qwm", "wqm"):
                    out.append(PlanCandidate(
                        "sparse_pair", impl,
                        (("block_w", bw), ("grid_order", go)),
                    ))
        return out

    def _prior(
        self, cell: TuneCell
    ) -> Tuple[str, str, Dict[str, Any]]:
        """The analytic cost-model prior: the measurement-free answer a
        request thread gets for a cold cell (and the seed ordering of
        the search). Returns (path, impl, blocks)."""
        if cell.theta is None:
            qstar = self._model_crossover()
            parity = cell.bucket >= qstar and self._parity_fits(cell.n_eff)
            return ("parity" if parity else "fold"), cell.impl, {}
        if cell.impl == "ref":
            return "sparse_ref", "ref", {}
        bw = self._fused_bw(cell.n_eff)
        if bw:
            # C_p says the work is m·BW either way; residency is the
            # model's tiebreak — fit VMEM, walk queries outer. A jagged
            # bucket amortizes the db fetch across the request's whole
            # index list, so the multi form is its prior.
            if cell.k_max:
                return "sparse_multi_fused", cell.impl, {
                    "block_w": bw, "grid_order": "rw", "k_max": cell.k_max,
                }
            return "sparse_fused", cell.impl, {
                "block_w": bw, "grid_order": "qw",
            }
        return "sparse_pair", cell.impl, {}

    # ------------------------------------------------------------ the search
    def pending(self) -> Tuple[Key, ...]:
        """Cells planned from the prior and still awaiting their search
        (the idle-slot work queue)."""
        with self._lock:
            return tuple(self._pending)

    def _note_pending(self, key: Key, cell: TuneCell) -> None:
        with self._lock:
            if key not in self._pending and self._table_hit(key) is None:
                self._pending[key] = cell

    def tune_step(self, max_cells: int = 1) -> int:
        """Run the autotune search for up to ``max_cells`` pending cells
        (FIFO). Returns how many were tuned. This is the idle-slot
        entry point: the async front calls it when the ingest queue is
        quiet, so the table fills during lulls instead of stalling
        requests."""
        tuned = 0
        while tuned < max_cells:
            with self._lock:
                if not self._pending:
                    break
                key = next(iter(self._pending))
                cell = self._pending.pop(key)
            self._tune_cell(key, cell)
            tuned += 1
        return tuned

    def tune_pending(self) -> int:
        """Drain the pending queue completely (benchmarks and shutdown
        dumps call this; serving uses :meth:`tune_step`)."""
        return self.tune_step(max_cells=len(self._pending) + 1_000_000)

    def _bench_payload(self, key: Key, cell: TuneCell) -> jnp.ndarray:
        """A representative payload for the cell, deterministic in
        (planner seed, cell key) — fixed seed ⇒ reproducible search."""
        if cell.theta is None:
            density = 0.5
        else:
            density = min(
                0.5, max(0.01, (cell.m_budget or 1) / max(cell.n_eff, 1))
            )
        prng = jax.random.fold_in(
            jax.random.key(self._seed),
            zlib.crc32(repr(key).encode()) & 0x7FFFFFFF,
        )
        return _bench_mask(prng, cell.bucket, self.store.n, density)

    def _tune_cell(self, key: Key, cell: TuneCell) -> None:
        """Measure every candidate for one cell and record the winner
        (plus all timings + the device fingerprint) in the table."""
        cands = self._candidates(cell)
        if not cands:
            return
        shape = (self.store.n, self.store.words)
        if len(cands) == 1:
            c = cands[0]
            self.table.put(
                key, c.path, impl=c.impl, blocks=dict(c.blocks),
                source="only", store_shape=shape,
            )
        else:
            payload = self._bench_payload(key, cell)
            interp = ops.on_cpu()
            us: Dict[str, float] = {}
            by_label: Dict[str, PlanCandidate] = {}
            for c in cands:
                fn = self._build_run(
                    c.path, c.impl, cell.m_budget, interp, dict(c.blocks)
                )
                us[c.label] = float(self._measure(fn, payload, candidate=c))
                by_label[c.label] = c
            winner = by_label[min(us, key=us.get)]
            self.table.put(
                key, winner.path, impl=winner.impl,
                blocks=dict(winner.blocks), source="measured", us=us,
                store_shape=shape,
            )
        with self._lock:
            # cached model-prior plans for this cell are stale now
            self._plans.clear()

    # ---------------------------------------------------------------- plan
    def plan(
        self,
        scheme_plan: Any,
        bucket: int,
        mesh_state: Optional[dict] = None,
        *,
        scheme: Any = None,
        k_max: Optional[int] = None,
    ) -> ExecutionPlan:
        """One batch's wire plan -> its execution decision.

        ``scheme_plan`` is the scheme's wire-level
        :class:`~repro.core.protocol.Queries` (its ``kind`` and ``theta``
        are the scheme-side facts execution depends on); ``bucket`` the
        padded batch size; ``mesh_state`` the serve layer's mesh
        residency dict (None off-mesh). ``scheme`` (a staged
        SchemeProtocol) keys the autotune table and supplies ``costs(n)``
        as the analytic prior; without it the plan keys on the wire kind
        alone. ``k_max`` marks a jagged multi-index bucket (the padded
        per-request column count, ``bucket % k_max == 0``): the sparse
        candidate set gains the fused multi kernel and the cell keys
        under the ``+multi@<k_max>`` family so single-index decisions are
        never clobbered.

        Never measures: a table hit returns the recorded search winner,
        a miss returns the analytic prior and queues the cell for the
        idle-slot search (single-host cells only — shard_map executors
        are not safely microbenchmarkable mid-serving, so mesh plans
        stay on the prior).
        """
        kind = scheme_plan.kind
        theta = getattr(scheme_plan, "theta", None)
        scheme_name = getattr(scheme, "name", None) or f"kind:{kind}"
        costs = scheme.costs(self.store.n) if scheme is not None else None
        on_mesh = mesh_state is not None
        mesh_key = (
            (id(mesh_state["mesh"]), mesh_state["raxes"]) if on_mesh else None
        )
        impl = self.backend.resolve()
        interpret = ops.on_cpu()
        if k_max is not None and (k_max < 1 or bucket % k_max):
            raise ValueError(
                f"multi bucket {bucket} not a multiple of k_max={k_max}"
            )

        cache_key = (
            scheme_name, kind, theta, int(bucket), impl, mesh_key, k_max
        )
        cached = self._plans.get(cache_key)
        if cached is not None:
            return cached

        n_eff = (
            mesh_state["n_pad"] // mesh_state["rshards"]
            if on_mesh else self.store.n
        )
        blocks: Dict[str, Any] = {}
        m_budget = None
        chosen_impl = impl
        if kind == "index":
            path, source = "direct", "only"
        else:
            sparse = (
                theta is not None and theta < 0.5
                and self._gather_pays(theta, costs, scheme)
            )
            cell_theta = theta if sparse else None
            # the mask family's dense forms (fold/parity) already answer
            # the whole flat bucket in one launch — only the sparse
            # gather forms have a multi variant to race
            cell_k = k_max if sparse else None
            if sparse:
                m_budget = ops.sparse_index_budget(n_eff, theta)
            cell = TuneCell(
                scheme=scheme_name, bucket=int(bucket), impl=impl,
                theta=cell_theta, n_eff=n_eff, m_budget=m_budget,
                k_max=cell_k,
            )
            if not sparse and self._parity_min_batch is not None:
                path = (
                    "parity" if bucket >= self._parity_min_batch else "fold"
                )
                source = "forced"
            else:
                key = self._table_key(
                    scheme_name, bucket, impl, cell_theta, cell_k
                )
                hit = self._table_hit(key)
                if hit is not None:
                    path = hit["path"]
                    chosen_impl = hit.get("impl", impl)
                    blocks = dict(hit.get("blocks", {}))
                    source = hit["source"]
                else:
                    path, chosen_impl, blocks = self._prior(cell)
                    source = (
                        "only" if sparse and impl == "ref" else "model"
                    )
                    if not on_mesh and source == "model":
                        self._note_pending(key, cell)

        # the direct family's lookup has exactly one physical form per
        # residency (a gather, owned by the serve layer's index path) —
        # its plan is decision-only, like every mesh plan
        run = None
        kernel = None
        if not on_mesh and path != "direct":
            kernel = self._build_kernel(
                path, chosen_impl, m_budget, interpret, blocks
            )
            run = self._build_run(
                path, chosen_impl, m_budget, interpret, blocks,
                kernel=kernel,
            )
        self.metrics["plans_built"] += 1
        plan = ExecutionPlan(
            path=path,
            impl=chosen_impl,
            bucket=int(bucket),
            n=n_eff,
            blocks=tuple(sorted(blocks.items())),
            m_budget=m_budget,
            theta=theta,
            interpret=interpret,
            source=source,
            run=run,
            kernel=kernel,
        )
        self._plans[cache_key] = plan
        return plan

    def _gather_pays(
        self, theta: float, costs: Optional[Dict[str, float]], scheme: Any
    ) -> bool:
        """Whether the sparse gather forms beat the dense mask forms at
        all — the scheme's own cost model decides. ``costs(n)`` prices
        C_p = θ·d·n·(c_acc + c_prc) (Table 1), so C_p/(2d) is the
        records a query touches per server; the static gather budget
        adds the 6σ Chernoff slack on top. Once that budget stops being
        meaningfully below the record count (θ·n ≈ n, or tiny stores
        where the slack dominates), streaming the whole store wins and
        the dense fold/parity decision takes over — Sparse-PIR's
        *privacy* accounting is untouched; only the physical form
        changes, bit-identically."""
        n = self.store.n
        d = getattr(scheme, "d", 0)
        touched = (
            costs["C_p"] / (2.0 * d)
            if costs is not None and d and "C_p" in costs
            else theta * n
        )
        budget = ops.sparse_index_budget(n, min(max(touched / n, 1e-9), 0.5))
        return budget < self.GATHER_DENSE_CUTOFF * n

    def invalidate(self) -> None:
        """Drop cached plans (mesh changed or store swapped); the
        autotune table survives — measurements key on shapes, not
        residency."""
        with self._lock:
            self.metrics["plans_dropped"] += len(self._plans)
            self._plans.clear()

    def rebind(
        self,
        store: RecordStore,
        *,
        touched_rows: Optional[Any] = None,
    ) -> Dict[str, int]:
        """Swap the planner onto a new store version (DESIGN.md §13).

        A same-shape content swap with a known touched-row set is the
        incremental-invalidation fast path: every cached
        :class:`ExecutionPlan` is **kept** (executors resolve their
        operand from ``self.store`` per call, so the new packed buffer
        flows in with zero replans and zero retraces), and the
        precompute (bitplanes) refreshes only the touched rows. A shape
        change (append/delete changed ``n``) or an unknown touch set
        drops plans and planes wholesale — those plans' shapes went
        stale, not just their bytes. Autotune entries survive either
        way: measurements key on (n, words), so a content swap keeps
        them and a shape change misses to a *different* key instead of
        hitting a stale one. Returns the per-call counter deltas (also
        accumulated in :attr:`metrics`)."""
        with self._lock:
            self.metrics["rebinds"] += 1
            same_shape = (
                store.n == self.store.n
                and store.words == self.store.words
                and store.record_bits == self.store.record_bits
            )
            if same_shape and touched_rows is not None:
                self.store = store
                rows = jnp.asarray(touched_rows, jnp.int32)
                refreshed = 0
                if self._planes is not None and int(rows.shape[0]):
                    fresh = packing.bitplanes_from_packed(
                        jnp.take(store.packed, rows, axis=0),
                        dtype=self._planes.dtype,
                    )
                    self._planes = self._planes.at[rows].set(fresh)
                    refreshed = int(rows.shape[0])
                kept = len(self._plans)
                self.metrics["plans_kept"] += kept
                self.metrics["precompute_rows_refreshed"] += refreshed
                return {
                    "plans_kept": kept, "plans_dropped": 0,
                    "precompute_rows_refreshed": refreshed,
                }
            self.store = store
            self._planes = None
            dropped = len(self._plans)
            self._plans.clear()
            self.metrics["plans_dropped"] += dropped
            return {
                "plans_kept": 0, "plans_dropped": dropped,
                "precompute_rows_refreshed": 0,
            }


def _path_answer_fn(
    path: str, impl: str, m_budget: Optional[int], interp: bool,
    blocks: Dict[str, Any],
) -> Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray]:
    """THE path→kernel dispatch: ``(operand, payload) -> [B, W]`` where
    ``operand`` is the packed db ([n, W] uint32) — or the bitplanes for
    the parity path. Single source of truth for both executor shapes:
    the planner binds the operand for single-host ``run`` closures, and
    :func:`shard_answer_fn` hands the same function to ``shard_map``
    with the local shard as operand. The ``ref`` impl routes to the jnp
    oracles — bit-identical to the kernels, asserted exactly in
    tests/test_kernels.py. ``blocks`` carries the search's block shape
    (``block_w``, ``grid_order``) for the sparse kernel forms."""
    if path == "fold":
        if impl == "ref":
            return ref.xor_fold_ref
        return lambda db, m: xor_fold(db, m, interpret=interp)
    if path == "parity":
        if impl == "ref":
            return lambda planes, m: packing.pack_bits(
                ref.parity_matmul_ref(m, planes)
            )
        return lambda planes, m: packing.pack_bits(
            parity_matmul(m, planes, interpret=interp)
        )
    if path == "sparse_ref":
        return lambda db, m: ref.gather_xor_ref(
            db, indices_from_mask(m, m_budget)
        )
    if path == "sparse_pair":
        bw = blocks.get("block_w", 128)
        go = blocks.get("grid_order", "qwm")
        return lambda db, m: gather_xor(
            db, indices_from_mask(m, m_budget),
            block_w=bw, grid_order=go, interpret=interp,
        )
    if path == "sparse_fused":
        bw = blocks["block_w"]
        go = blocks.get("grid_order", "qw")
        return lambda db, m: fused_gather_fold(
            db, indices_from_mask(m, m_budget),
            block_w=bw, grid_order=go, interpret=interp,
        )
    if path == "sparse_multi_fused":
        bw = blocks["block_w"]
        go = blocks.get("grid_order", "rw")
        k_max = int(blocks["k_max"])

        def _multi(db, m):
            idx = indices_from_mask(m, m_budget)
            # the serving layout keeps every flat column live (padding
            # columns are real dummy queries whose responses the client
            # discards), so the canonical all-live offsets make this
            # bit-identical to the flat forms on the same payload
            off = jnp.arange(
                idx.shape[0] // k_max + 1, dtype=jnp.int32
            ) * k_max
            return fused_multi_gather_fold(
                db, idx, off, k_max=k_max,
                block_w=bw, grid_order=go, interpret=interp,
            )

        return _multi
    raise ValueError(f"no kernel form for path {path!r}")


# --------------------------------------------------------------------------
# The write path: batched delta application (repro.db.live's ingest)
# --------------------------------------------------------------------------
# the pseudo-scheme the write path's autotune cells key under — ingest is
# scheme-agnostic, but it shares the table so dumped files carry the
# write-side decisions too
_INGEST_SCHEME = "_ingest"


def scatter_update(
    db: jnp.ndarray,
    rows: Any,
    vals: Any,
    *,
    backend: str = "auto",
    table: Optional[AutotuneTable] = None,
    measure: Optional[Callable[..., float]] = None,
    family: str = "scatter",
) -> jnp.ndarray:
    """Apply a batch of packed-row updates on device: the delta-ingest
    write primitive behind :meth:`repro.db.live.VersionedStore.ingest`.

    db: [n, W]; rows: [m] int (unique — ``Delta`` dedups); vals: [m, W]
    (cast to ``db.dtype``) -> a new [n, W] buffer with
    ``out[rows[i]] = vals[i]``.

    Kernel choice is raced through the execution-backend registry like
    the read paths: under ``auto`` resolving to a kernel impl, the Pallas
    scatter kernel races the jnp ``.at[].set`` oracle once per
    (update-bucket, n, W) cell and the winner lands in the autotune table
    (pseudo-scheme ``"_ingest"``, family ``family`` — ``"scatter"`` for
    whole-store ingest, ``"scatter_shard"`` for the sharded serve layer's
    per-shard device refreshes, which run against shard-sized buffers and
    must not clobber the whole-store cells; same JSON dump, same
    device-fingerprint trust rule). Unlike ``plan()`` this *does*
    measure inline on a cold cell: ingest is the write path, not the
    request path, so a one-off microbenchmark stalls no reader. The
    update count is padded to its power-of-two bucket by duplicating the
    last update (identical writes commute, so the dedup contract holds)
    to keep jit retraces bounded."""
    m = int(rows.shape[0])
    if m == 0:
        return db
    impl = get_backend(backend).resolve()
    interp = ops.on_cpu()
    n, w = int(db.shape[0]), int(db.shape[1])
    bucket = 1 << max(0, int(m - 1).bit_length())
    rows_j = jnp.asarray(rows, jnp.int32)
    vals_j = jnp.asarray(vals, db.dtype)
    pad = bucket - m
    if pad:
        rows_j = jnp.concatenate(
            [rows_j, jnp.broadcast_to(rows_j[-1:], (pad,))]
        )
        vals_j = jnp.concatenate(
            [vals_j, jnp.broadcast_to(vals_j[-1:], (pad, w))]
        )

    from repro.kernels.scatter import scatter_rows

    candidates: Dict[str, Callable] = {
        "scatter/ref": jax.jit(ref.scatter_rows_ref),
    }
    if impl != "ref":
        candidates["scatter/pallas"] = (
            lambda d, r, v: scatter_rows(d, r, v, interpret=interp)
        )
        if backend != "auto":
            # a hard backend pin skips the race entirely, like plan()
            candidates.pop("scatter/ref")

    if len(candidates) == 1:
        return next(iter(candidates.values()))(db, rows_j, vals_j)

    table = table if table is not None else autotune_table()
    measure = measure if measure is not None else _measure_us
    key: Key = (_INGEST_SCHEME, bucket, impl, n, w, family)
    hit = table.get(key)
    if hit is not None and (
        hit.get("device") not in (None, device_fingerprint())
        or f"scatter/{hit.get('impl')}" not in candidates
    ):
        hit = None
    if hit is None:
        us = {
            label: float(measure(fn, db, rows_j, vals_j))
            for label, fn in candidates.items()
        }
        winner = min(us, key=us.get)
        table.put(
            key, "scatter", impl=winner.split("/", 1)[1],
            source="measured", us=us, store_shape=(n, w),
        )
        hit = table.get(key)
    return candidates[f"scatter/{hit['impl']}"](db, rows_j, vals_j)


def shard_answer_fn(
    plan: ExecutionPlan,
) -> Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray]:
    """Per-shard answer function for a mesh :class:`ExecutionPlan`.

    Returns ``(operand_loc, payload_loc) -> partial answer [B, W]`` where
    ``operand_loc`` is the local db shard ([n_loc, W] packed words) — or
    the local bitplane shard for the parity path. The sharded serve layer
    wraps this in ``shard_map`` and XOR-combines the partials; the kernel
    choice stays here, behind the ``repro.kernels`` fence (the serve
    layer never imports a kernel module)."""
    return _path_answer_fn(
        plan.path, plan.impl, plan.m_budget, plan.interpret,
        dict(plan.blocks),
    )
