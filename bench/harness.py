"""One run of one cell: set-up, a measured window, the check, the metrics.

Everything a cell needs is found by name from ``BENCHMARK.json``: the
configuration file (sizes, the program's entry, the reference), the mix
under ``bench/traffic/`` and one reader per metric under
``bench/metrics/``. The harness itself knows none of them.

Set-up, in order: make the store on the device from the seed, build the
frontend through the configuration's entry, serve one batch of every pow2
bucket the mix can produce through the pipeline's plan and execute stages,
run the idle-slot jobs to the end (autotune, prefill), serve every bucket
once more so the tuned plans are the ones compiled, start the frontend
and wait until its idle slot finds nothing to do. Between the warm passes
and the start, the first warm batch is asked again by the same clients,
so the L1 memo answers it. The window follows. After it closes, every
lookup is waited for (a minute at most), the device's peak bytes are
read, the frontend is closed and its store freed, and only then does the
reference rebuild each asked-for record from the seed and compare it with
the answer.

``correct`` also holds the run to the guarantee its configuration states
(:func:`guarantee_checks`): every answered batch, warm-up and window, went
to all d servers on the stated wire; on a mask wire each server's mask
for each query has the share of ones the scheme states; on an index wire
(Direct Requests) each lookup asks p distinct records holding the real
one once, and the dummies and the real index's slot spread as Algorithm
4.1 says; and the program prices a lookup at the stated (epsilon, delta).

The calls into each layer are wrapped from here (spans from the
benchmark's own files; the program has none): the answer stage always, to
see which buckets ran; in a traced run also query generation,
reconstruction, planning, execution and the idle-slot jobs, each in a
``bench.<layer>`` ``TraceAnnotation``.
"""

from __future__ import annotations

import functools
import gc
import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import threading
import time
import zlib
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))

import faults  # noqa: E402
import traffic  # noqa: E402
import xplane  # noqa: E402

#: how long after the window closes the run waits for its last answers
DRAIN_S = 60.0
#: the longest the idle slot may take to settle after a warm pass
SETTLE_S = 900.0
COUNTERS = ("queries", "cache_hits", "batches", "padded", "truncated")


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def cell_spec(bench: dict, name: str) -> dict:
    """The cell, its configuration entry and file, its mix and its
    metrics, all found by name."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]

    def mine(m):
        return "workloads" not in m or name in m["workloads"]

    return {
        "cell": cell,
        "config": load_json(ROOT / entry["file"]),
        "mix": traffic.load(cell["traffic"]),
        "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
        "per_layer": [m for m in bench["per_layer"] if mine(m)],
    }


def load_reader(name: str):
    """``bench/metrics/<name>.py``, whose ``read(run)`` returns the
    metric's value or None where it finds nothing to read."""
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", HERE / "metrics" / f"{name}.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_reference(name: str):
    return importlib.import_module(f"references.{name}")


class NoChip(RuntimeError):
    """JAX found another platform, or fewer chips than the cell asks for."""


def start(chips: int):
    """Process set-up for the scripts that drive a cell (``run.py``,
    ``sweep.py``, ``control.py``), before anything else touches JAX: JAX's
    persistent compilation cache in ``.jax_cache/`` of the checkout,
    libtpu's logs under the temporary directory, and at least ``chips``
    TPU chips, or :class:`NoChip`. Returns the :class:`CompileLog`, the
    cache directory and the devices."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR",
                          str(Path(tempfile.gettempdir()) / "bench-tpu-logs"))
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise NoChip(f"needs {chips} TPU chip(s); JAX found {len(devices)} "
                     f"{devices[0].platform} device(s)")
    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import use_compile_cache

    compiles = CompileLog()
    return compiles, use_compile_cache(), devices


class CompileLog:
    """Counts what JAX compiles or loads from its cache, by phase."""

    def __init__(self) -> None:
        import jax

        self.counts = {"traces": 0, "compiles": 0, "cache_hits": 0,
                       "compile_s": 0.0}
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/jaxpr_trace_duration":
            self.counts["traces"] += 1
        elif event == "/jax/core/compile/backend_compile_duration":
            self.counts["compiles"] += 1
            self.counts["compile_s"] += secs

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.counts["cache_hits"] += 1

    def snapshot(self) -> dict:
        return dict(self.counts)


class Probe:
    """The benchmark's wrappers around the frontend's layers.

    Always: the answer stage records the buckets it ran, and the idle-slot
    jobs record whether they are running and what they last returned (how
    set-up knows the slot has settled). In a traced run every wrapped call
    also opens a ``bench.<layer>`` span; the answer span carries the shape
    facts that ``answer_roofline`` turns into least bytes, and query
    generation waits for its payload inside its span. ``fault`` (tests and
    the control only) breaks the answers as ``faults.py`` says."""

    def __init__(self, fe, *, traced: bool, fault: Optional[str] = None):
        import jax
        import jax.numpy as jnp

        self.jax = jax
        self.fe = fe
        self.traced = traced
        self.buckets: Dict[int, int] = {}
        self.idle_running = 0
        self.idle_last: Dict[str, Optional[int]] = {}
        self._mu = threading.Lock()
        pipe = fe.pipeline
        backend = pipe.backend
        self._union = jax.jit(
            lambda p: jnp.sum(jnp.any(p != 0, axis=1), axis=-1, dtype=jnp.int32)
        )
        self._ones = jax.jit(
            lambda p: jnp.sum(p != 0, axis=-1, dtype=jnp.int32)
        )
        self._requests = jax.jit(functools.partial(
            request_counts, n=int(pipe.store.n),
            bins=load_reference("requests").BINS))
        #: per answered batch: the servers it went to, its wire, and what
        #: the guarantee checks read from it: each server's count of ones
        #: per query (mask), or the request counts of
        #: ``references/requests.py`` (index); device arrays until the run
        #: is checked
        self.asked: List[tuple] = []
        ask, ret = faults.breaker(fault, n=int(pipe.store.n))

        answer = backend.answer_batch

        def answer_batch(sent, **kw):
            routed = ask(sent)
            bucket = int(routed.payload.shape[1])
            with self._mu:
                self.buckets[bucket] = self.buckets.get(bucket, 0) + 1
            if not self.traced:
                out = answer(routed, **kw)
            else:
                stats = self._answer_stats(routed)
                with jax.profiler.TraceAnnotation("bench.answer_batch", **stats):
                    out = answer(routed, **kw)
            # after the span, and not waited for: the reduction runs behind
            # the answer on the device and is read once the run is over
            if routed.kind == "mask":
                reading = self._ones(routed.payload)
            else:
                reading = dict(self._requests(routed.payload, routed.q_idx),
                               k=int(routed.payload.shape[2]))
            with self._mu:
                self.asked.append((tuple(routed.servers), routed.kind,
                                   reading))
            return ret(out, sent)

        backend.answer_batch = answer_batch

        for job in ("prefill_cache", "autotune_step"):
            setattr(pipe, job, self._idle(job, getattr(pipe, job)))
        if traced:
            router = pipe.router
            router.plan = self._span("bench.query_gen", router.plan,
                                     sync=lambda q: q.payload)
            router.finalize = self._span("bench.reconstruct", router.finalize)
            pipe.plan_requests = self._span("bench.plan", pipe.plan_requests)
            pipe.execute_planned = self._span("bench.execute",
                                              pipe.execute_planned)

    def _answer_stats(self, routed) -> dict:
        """Shape facts of one answer call: servers, bucket, records and
        words; for a sparse wire also the records any of the bucket's
        masks select, per server, summed; for an index wire the slots
        each server is asked per query (k)."""
        store = self.fe.pipeline.store
        stats = {
            "servers": len(routed.servers),
            "bucket": int(routed.payload.shape[1]),
            "n": int(store.n),
            "words": int(store.words),
            "kind": routed.kind,
        }
        if routed.kind == "mask" and getattr(routed, "theta", None) is not None:
            stats["union_records"] = int(self._union(routed.payload).sum())
        if routed.kind == "index":
            stats["k"] = int(routed.payload.shape[2])
        return stats

    def _span(self, name: str, fn: Callable, sync: Optional[Callable] = None):
        jax = self.jax

        def wrapped(*a, **kw):
            with jax.profiler.TraceAnnotation(name):
                out = fn(*a, **kw)
                if sync is not None:
                    jax.block_until_ready(sync(out))
            return out

        return wrapped

    def _idle(self, job: str, fn: Callable):
        self.idle_last[job] = None
        span = f"bench.{job}"

        def wrapped(*a, **kw):
            with self._mu:
                self.idle_running += 1
            try:
                if self.traced:
                    with self.jax.profiler.TraceAnnotation(span):
                        out = fn(*a, **kw)
                else:
                    out = fn(*a, **kw)
            finally:
                with self._mu:
                    self.idle_running -= 1
            with self._mu:
                self.idle_last[job] = int(out)
            return out

        return wrapped

    def settled(self, jobs) -> bool:
        with self._mu:
            quiet = self.idle_running == 0 and all(
                self.idle_last[j] == 0 for j in jobs
            )
        return quiet and not self.fe.pipeline.backend.planner.pending()


def pow2_buckets(cap: int) -> List[int]:
    out, b = [], 1
    while b < cap:
        out.append(b)
        b *= 2
    return out + [cap]


class Session:
    """A frontend over a store made from the seed, warmed for one mix."""

    def __init__(self, spec: dict, *, seed: int, traced: bool,
                 fault: Optional[str] = None):
        import jax
        from repro.db.store import RecordStore

        self.spec, self.seed = spec, seed
        cfg = spec["config"]
        prog = cfg["program"]
        self.n = int(prog["n_records"])
        stated_wire(cfg["guarantee"], cfg["limits"], self.n)
        self.nbytes = int(prog["record_bytes"])
        if self.nbytes % 4:
            raise ValueError("record_bytes must be a whole number of words")
        self.words = self.nbytes // 4
        self.reference = load_reference(cfg["reference"])
        packed = self.reference.device_store(seed, self.n, self.words)
        packed.block_until_ready()
        store = RecordStore(packed=packed, record_bits=self.nbytes * 8)
        self.fe = build_frontend(cfg, store, seed)
        self.probe = Probe(self.fe, traced=traced, fault=fault)
        self.idle_jobs = ["autotune_step"] + (
            ["prefill_cache"] if self.fe.pipeline.cache is not None else []
        )
        self.cap = int(prog["query_batch"])
        self.warm_lookups: List[traffic.Lookup] = []
        self.jax = jax

    def counters(self) -> dict:
        m = self.fe.metrics
        return {k: m[k] for k in COUNTERS}

    def _warm_bucket(self, mix: dict, b: int, tag: str) -> None:
        """Serve one batch of exactly ``b`` requests through the pipeline's
        own plan and execute stages (the ones the frontend's flush worker
        drives), before the frontend's threads start: a burst sent through
        the running frontend may be cut into smaller batches."""
        pipe = self.fe.pipeline
        reqs = traffic.draw(mix, self.n, b, seed=self.seed + zlib.crc32(
            f"{tag}-{b}".encode()))
        lookups = []
        for i, (_, idx) in enumerate(reqs):
            lk = traffic.Lookup(f"warm-{tag}-{b}-{i}", idx, time.perf_counter())
            if len(idx) == 1:
                pipe.submit_request(lk.client, idx[0])
            else:
                pipe.submit_request_many(lk.client, idx)
            lookups.append(lk)
        batch = pipe.take_batch()
        if len(batch) != b:
            raise RuntimeError(f"warm-up cut {len(batch)} requests, not {b}")
        for lk, (_, answer) in zip(lookups, pipe.serve_requests(batch)):
            lk.answer, lk.t_done = np.asarray(answer), time.perf_counter()
        self.warm_lookups += lookups

    def _warm_memo(self, b: int) -> None:
        """Ask again, as the same clients, for the records of the first
        warm batch of ``b``: the L1 memo answers them without a server,
        and their bytes are checked with every other lookup's. The
        window's clients seldom repeat, so this is where the memo is
        compared."""
        pipe = self.fe.pipeline
        if pipe.cache is None:
            self.memo_hits = None
            return
        first = [lk for lk in self.warm_lookups
                 if lk.client.startswith(f"warm-r0-{b}-")]
        hits0 = self.fe.metrics["cache_hits"]
        lookups = []
        for lk in first:
            again = traffic.Lookup(lk.client, lk.indices, time.perf_counter())
            if len(lk.indices) == 1:
                pipe.submit_request(lk.client, lk.indices[0])
            else:
                pipe.submit_request_many(lk.client, lk.indices)
            lookups.append(again)
        batch = pipe.take_batch()
        if len(batch) != len(lookups):
            raise RuntimeError(f"memo pass cut {len(batch)} of {len(lookups)}")
        for lk, (_, answer) in zip(lookups, pipe.serve_requests(batch)):
            lk.answer, lk.t_done = np.asarray(answer), time.perf_counter()
        self.memo_hits = (self.fe.metrics["cache_hits"] - hits0, len(lookups))
        self.warm_lookups += lookups

    def _settle(self) -> None:
        """Wait until the running frontend's idle slot has nothing to do:
        both idle jobs last returned 0 and no autotune cell is queued."""
        for job in self.idle_jobs:
            self.probe.idle_last[job] = None
        end = time.perf_counter() + SETTLE_S
        while not self.probe.settled(self.idle_jobs):
            if time.perf_counter() > end:
                raise RuntimeError("the idle slot did not settle")
            time.sleep(0.05)

    def warm(self, mix: dict) -> List[int]:
        """Two passes over every bucket the mix can produce. After each,
        the idle-slot jobs run to the end (autotune for every cell the
        pass queued, prefill until the bank is full), as the frontend's
        idle slot would; the second pass compiles the tuned plans. Then
        the frontend starts and its idle slot must find nothing to do.
        Returns the buckets."""
        pipe = self.fe.pipeline
        top = self.cap if mix["loop"] == "open" else min(
            self.cap, int(mix["outstanding"]) * int(mix.get("k", 1)))
        buckets = pow2_buckets(top)
        for rnd in range(2):
            for b in buckets:
                self._warm_bucket(mix, b, f"r{rnd}")
            while pipe.autotune_step():
                pass
            while pipe.cache is not None and pipe.prefill_cache():
                pass
        self._warm_memo(top)
        self.fe.start()
        self._settle()
        # a batch of b lookups in a bucket B > b takes its b answers out of
        # the [B, W] reconstruction with one eager slice, a program of its
        # own for each b; run those slices here instead of a whole batch of
        # every size through all d servers
        jnp = self.jax.numpy
        for b in range(1, top + 1):
            size = next(x for x in buckets if x >= b)
            if size != b:
                jnp.zeros((size, self.words), jnp.uint32)[:b].block_until_ready()
        return buckets

    def _submit(self, client: str, indices: tuple):
        if len(indices) == 1:
            return self.fe.submit(client, indices[0])
        return self.fe.submit_many(client, indices)

    def _tag(self):
        m = self.fe.metrics
        return (m["served"], m["failed"])

    def window(self, mix: dict, seconds: float, seed: int,
               compiles: CompileLog, trace_dir: Optional[str] = None) -> dict:
        """Send the mix for ``seconds``; returns what the window saw. With
        ``trace_dir`` the profiler records the window there."""
        jax = self.jax
        reqs = traffic.draw(mix, self.n, traffic.request_budget(mix, seconds),
                            seed)
        drv = traffic.Driver(self._submit, self._tag)
        if trace_dir is not None:
            # no Python function tracing: it would slow every host call of
            # the window; the bench.* spans and device ops are kept
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        c0, k0, b0 = self.counters(), compiles.snapshot(), dict(self.probe.buckets)
        idle0 = self.fe.metrics
        if trace_dir is not None:
            with jax.profiler.TraceAnnotation("bench.window"):
                drv.run(mix, reqs, seconds, seed)
            jax.profiler.stop_trace()
        else:
            drv.run(mix, reqs, seconds, seed)
        c1, k1 = self.counters(), compiles.snapshot()
        idle1 = self.fe.metrics
        b1 = dict(self.probe.buckets)
        unanswered = drv.wait_all(DRAIN_S)
        return {
            "driver": drv,
            "counters_open": c0,
            "counters_close": c1,
            "compiles": {k: k1[k] - k0[k] for k in k0},
            "buckets": {b: b1.get(b, 0) - b0.get(b, 0) for b in b1
                        if b1.get(b, 0) - b0.get(b, 0)},
            "idle_jobs": {k: idle1[k] - idle0[k] for k in ("prefilled",
                                                           "autotuned")},
            "unanswered_at_drain": unanswered,
        }

    def plans(self) -> List[str]:
        return [f"{p.describe()} impl={p.impl} blocks={dict(p.blocks)} "
                f"interpret={p.interpret}" for p in self.fe.pipeline.backend.plans_executed.values()]

    def plans_ok(self) -> bool:
        plans = list(self.fe.pipeline.backend.plans_executed.values())
        return bool(plans) and all(p.impl == "pallas" and not p.interpret
                                   for p in plans)

    def close(self) -> None:
        self.fe.close(timeout=120.0)
        self.fe = None
        self.probe = None
        gc.collect()


def build_frontend(cfg: dict, store, seed: int):
    """The configuration's entry, given the store and the pipeline's seed
    (the program's key is 31 bits)."""
    from repro.configs.base import PIRConfig

    modname, fn = cfg["entry"].split(":")
    entry = getattr(importlib.import_module(modname), fn)
    pir = PIRConfig(**cfg["program"])
    return entry(pir, store=store, seed=seed & 0x7FFFFFFF)


def check(reference, seed: int, words: int, nbytes: int,
          lookups: List[traffic.Lookup]) -> List[bool]:
    """Whether each lookup came back with the record bytes the reference
    rebuilds from the seed (never answered, failed or refused: False)."""
    ok = [False] * len(lookups)
    idx = sorted({i for lk in lookups for i in lk.indices})
    if not idx:
        return ok
    rows = dict(zip(idx, reference.expected_bytes(seed, idx, words, nbytes)))
    for j, lk in enumerate(lookups):
        if lk.answer is None or lk.error is not None:
            continue
        want = np.stack([rows[i] for i in lk.indices])
        got = np.asarray(lk.answer).reshape(want.shape) \
            if lk.answer.size == want.size else None
        ok[j] = got is not None and got.dtype == np.uint8 and \
            np.array_equal(got, want)
    return ok


def request_counts(payload, q_idx, *, n: int, bins: int) -> dict:
    """On the device, what ``references/requests.py``'s ``reduce_batch``
    counts for one index-wire batch (``payload [d, B, k]``, ``q_idx [B]``),
    with the number of lookups that break Algorithm 4.1's rule in place
    of their list."""
    import jax.numpy as jnp

    d, b, k = payload.shape
    x = jnp.transpose(payload, (1, 0, 2)).reshape(b, d * k)
    q = q_idx.astype(x.dtype)[:, None]
    real = x == q
    inside = (x >= 0) & (x < n)
    srt = jnp.sort(x, axis=1)
    distinct = jnp.all(srt[:, 1:] != srt[:, :-1], axis=1)
    sound = jnp.all(inside, axis=1) & distinct & (jnp.sum(real, axis=1) == 1)
    dummy = inside & ~real
    w_store = -(-n // bins)
    w_offset = -(-(n - 1) // bins)
    offset = jnp.where(x >= q, x - q, x - q + n)

    def hist(at, weight):
        onehot = at[..., None] == jnp.arange(bins, dtype=at.dtype)
        return jnp.sum(onehot & weight[..., None],
                       axis=tuple(range(at.ndim)), dtype=jnp.int32)

    return {
        "violations": jnp.sum(~sound, dtype=jnp.int32),
        "lookups": jnp.int32(b),
        "dummies": hist(jnp.clip(x // w_store, 0, bins - 1), dummy),
        "reals": hist(jnp.clip(q_idx // w_store, 0, bins - 1),
                      jnp.ones((b,), bool)),
        "offsets": hist(jnp.clip((offset - 1) // w_offset, 0, bins - 1),
                        dummy),
        "servers": jnp.sum(real.reshape(b, d, k), axis=(0, 2),
                           dtype=jnp.int32),
    }


def stated_wire(guarantee: dict, limits: dict, n: int) -> str:
    """The wire the guarantee states, ``mask`` (``guarantee.masks``: Chor
    or Sparse masks) or ``index`` (``guarantee.requests``: Direct
    Requests, p indices a lookup); refuses a guarantee that states
    neither or both, an unknown kind, a p that Algorithm 4.1 cannot run,
    or a limit it needs and lacks."""
    stated = [key for key in ("masks", "requests") if key in guarantee]
    if len(stated) != 1:
        raise ValueError("the guarantee must state exactly one of 'masks' "
                         f"and 'requests'; it states {stated}")
    d = int(guarantee["d"])
    if stated == ["masks"]:
        spec = guarantee["masks"]
        load_reference("masks").density(spec["kind"], d,
                                        float(spec.get("theta", 0.5)))
        need = "mask_density_z"
    else:
        spec = guarantee["requests"]
        if spec.get("kind") != "direct":
            raise ValueError(f"no request guarantee of kind "
                             f"{spec.get('kind')!r}; have 'direct'")
        load_reference("requests").check_stated(int(spec["p"]), d, n)
        need = "request_spread_z"
    if need not in limits:
        raise ValueError(f"the configuration's limits lack {need!r}")
    return "mask" if stated == ["masks"] else "index"


def guarantee_checks(guarantee: dict, asked: List[tuple], price: tuple,
                     n: int, limits: dict) -> dict:
    """The numbers that hold a run to the guarantee its configuration
    states, each with its limit. Always: batches that did not go to all d
    servers on the stated wire (``short_batches``), and whether the
    program's price per lookup differs from the stated (epsilon, delta).
    On a mask wire, how far any server's mask for any query lies from the
    stated density, in standard deviations of its Binomial(n, p) count
    (``references/masks.py``). On an index wire, the lookups whose p
    indices break Algorithm 4.1's rule, and the largest |z| of the pooled
    request counts (``references/requests.py``)."""
    wire = stated_wire(guarantee, limits, n)
    d = int(guarantee["d"])
    mine = [(servers, reading) for servers, kind, reading in asked
            if kind == wire]
    short = len(asked) - len(mine) + sum(len(servers) != d for servers, _ in mine)
    checks = {}
    if wire == "mask":
        masks = load_reference("masks")
        spec = guarantee["masks"]
        p = masks.density(spec["kind"], d, float(spec.get("theta", 0.5)))
        short += sum(ones.shape[0] != d for _, ones in mine)
        z = [np.abs(masks.row_z(ones, n, p)).max() for _, ones in mine]
        checks["short_batches"] = {"value": int(short), "limit": 0}
        checks["mask_density_z"] = {"value": float(max(z, default=0.0)),
                                    "limit": float(limits["mask_density_z"])}
    else:
        requests = load_reference("requests")
        p = int(guarantee["requests"]["p"])
        short += sum(len(c["servers"]) != d or c["k"] != p // d
                     for _, c in mine)
        counts = [{k: v for k, v in c.items() if k not in ("violations", "k")}
                  for _, c in mine]
        spread = requests.spread_z(requests.pool(counts), n, p) \
            if counts else 0.0
        checks["short_batches"] = {"value": int(short), "limit": 0}
        checks["request_violations"] = {
            "value": int(sum(int(c["violations"]) for _, c in mine)),
            "limit": 0}
        checks["request_spread_z"] = {
            "value": spread, "limit": float(limits["request_spread_z"])}
    stated = (float(guarantee["epsilon_per_lookup"]),
              float(guarantee["delta_per_lookup"]))
    checks["price_mismatch"] = {"value": int(tuple(map(float, price)) != stated),
                                "limit": 0}
    return checks


def device_info(jax, chips: int) -> dict:
    devs = jax.devices()[:chips]
    peak = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(jax.devices()),
        "memory_peak_bytes": max((p for p in peak if p is not None), default=None),
    }


def run_cell(bench: dict, name: str, *, seed: int, seconds: float,
             trace: bool, t_start: float, compiles: CompileLog,
             fault: Optional[str] = None, spec: Optional[dict] = None,
             log: Callable = print) -> dict:
    """One run of one cell on the devices :func:`start` found; returns
    the result line's object. Tests pass ``spec`` (see :func:`cell_spec`)
    to run a tiny cell on the CPU."""
    import jax

    spec = spec or cell_spec(bench, name)
    cell, mix = spec["cell"], spec["mix"]
    sess = Session(spec, seed=seed, traced=trace, fault=fault)
    buckets = sess.warm(mix)
    k_setup = compiles.snapshot()
    setup_s = time.perf_counter() - t_start
    log(f"setup_s {setup_s} buckets {buckets} compiles {k_setup}")

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        win = sess.window(mix, seconds, seed, compiles, trace_dir)
        drv = win["driver"]
        log(f"window: compiles {win['compiles']} buckets {win['buckets']} "
            f"idle jobs {win['idle_jobs']} unanswered after drain "
            f"{win['unanswered_at_drain']}")
        lateness = [lk.t_submit - lk.t_sched for lk in drv.lookups]
        log(f"generator lateness: max {max(lateness, default=0.0)} s, "
            f"mean {float(np.mean(lateness)) if lateness else 0.0} s over "
            f"{len(lateness)} sends")
        c0, c1 = win["counters_open"], win["counters_close"]
        log("window counters: " + json.dumps({k: c1[k] - c0[k] for k in c0}))
        plans, plans_ok = sess.plans(), sess.plans_ok()
        log(f"plans executed ({'all compiled pallas' if plans_ok else 'NOT all compiled pallas'}): "
            + "; ".join(plans))
        price = (sess.fe.metrics["epsilon_per_query"],
                 sess.fe.metrics["delta_per_query"])
        log(f"price per lookup (program): eps {price[0]} delta {price[1]}; "
            f"stated " + json.dumps(spec["config"]["guarantee"]))
        if sess.memo_hits is not None:
            log(f"memo: {sess.memo_hits[0]} of {sess.memo_hits[1]} repeated "
                f"warm-up lookups served from the L1 memo")
        asked = [(servers, kind, jax.tree.map(np.asarray, reading))
                 for servers, kind, reading in sess.probe.asked]
        device = device_info(jax, int(cell["chips"]))
        log(f"memory_peak_bytes {device['memory_peak_bytes']}")
        sess.close()
        warm = sess.warm_lookups
        reduction = None
        if trace:
            import glob

            paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb",
                                     recursive=True))
            reduction = xplane.load(paths[-1])
            log(f"trace: window {reduction.window_s} s, busy {reduction.busy_s} "
                f"s, clock offset {reduction.clock_offsets_ns} ns; idle by "
                f"span " + json.dumps(xplane.idle_by_label(reduction)))
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)

    ok = check(sess.reference, seed, sess.words, sess.nbytes, drv.lookups)
    ok_warm = check(sess.reference, seed, sess.words, sess.nbytes, warm)
    bad = ok.count(False) + ok_warm.count(False)
    log(f"checked {len(ok) + len(ok_warm)} lookups ({len(ok)} in the window, "
        f"{len(ok_warm)} in warm-up): {bad} bad")
    checks = {"bad_lookups": {"value": bad, "limit": 0}}
    checks.update(guarantee_checks(spec["config"]["guarantee"], asked, price,
                                   sess.n, spec["config"]["limits"]))
    log(f"checked {len(asked)} answered batches against the stated "
        f"guarantee")

    run = {
        "cell": cell, "config": spec["config"], "mix": mix,
        "seconds": seconds, "setup_s": setup_s,
        "lookups": drv.lookups, "ok": ok,
        "t_open": drv.t_open, "t_close": drv.t_close,
        "counters_open": c0, "counters_close": c1,
        "trace": reduction, "device": device,
        "peaks": load_json(HERE / "peaks.json"),
    }
    metrics = {}
    for m in (spec["per_layer"] if trace else spec["end_to_end"]):
        value = load_reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    if trace:
        device["busy_s"] = reduction.busy_s
        device["window_s"] = reduction.window_s
    out = {
        "correct": all(c["value"] <= c["limit"] for c in checks.values()),
        "attempted": len(drv.lookups),
        "failed": ok.count(False),
        "metrics": metrics,
        "device": device,
    }
    if trace:
        out["breakdown"] = xplane.breakdown(reduction)
    out["checks"] = checks
    return out
