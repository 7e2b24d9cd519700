"""The execution-backend layer (repro.kernels.backend, DESIGN.md
§Execution backends): registry contents, the deprecated kernel_impl
alias, autotune-table decisions + JSON round-trip, ExecutionPlan
resolution (sparse family, VMEM gate, forced crossover), and — the
acceptance bar — registry-parameterized bit-identity: every backend's
planned execution answers every wire kind exactly like the jnp oracle."""

import json
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import make_scheme
from repro.db import make_synthetic_store
from repro.kernels import ref
from repro.kernels.backend import (
    AutotuneTable,
    ExecutionPlan,
    KernelPlanner,
    TuneCell,
    device_fingerprint,
    get_backend,
    register_backend,
    registered_backends,
    resolve_kernel_impl_alias,
)
from repro.serve import SchemeRouter, ShardedBackend


def _counting_measure(calls=None):
    """An injected microbenchmark that never touches the clock: records
    each measured candidate and returns a deterministic figure (later
    candidates slower, so the first candidate always wins)."""
    calls = calls if calls is not None else []

    def measure(fn, *args, candidate=None):
        calls.append(candidate)
        return float(100 + len(calls))

    return measure, calls


# ---------------------------------------------------------------- registry
def test_registry_contents_and_resolution():
    assert set(registered_backends()) >= {"auto", "pallas", "ref"}
    assert get_backend("pallas").resolve() == "pallas"
    assert get_backend("ref").resolve() == "ref"
    # this container is a CPU host: auto resolves to the oracle impl
    assert get_backend("auto").resolve() == "ref"
    with pytest.raises(ValueError, match="unknown backend"):
        get_backend("mosaic")


def test_registry_rejects_duplicates():
    with pytest.raises(ValueError, match="already registered"):
        register_backend("ref")(type("Dup", (), {}))


def test_kernel_impl_alias_maps_and_validates():
    assert resolve_kernel_impl_alias(None, "auto") == "auto"
    assert resolve_kernel_impl_alias("pallas", "auto") == "pallas"
    with pytest.raises(ValueError, match="unknown backend"):
        resolve_kernel_impl_alias("jnp", "auto")


def test_sharded_backend_kernel_impl_deprecated_alias():
    store = make_synthetic_store(64, 8, seed=0)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        backend = ShardedBackend(store, kernel_impl="pallas")
    assert any(
        issubclass(w.category, DeprecationWarning) for w in caught
    )
    assert backend.backend_name == "pallas"
    assert backend.kernel_impl == "pallas"  # old introspection surface
    with pytest.raises(ValueError, match="unknown backend"):
        ShardedBackend(store, kernel_impl="jnp")


# ----------------------------------------------------------- autotune table
def test_autotune_table_json_roundtrip(tmp_path):
    table = AutotuneTable()
    table.put(("chor", 64, "ref", 512, 6, "mask"), "parity", impl="ref",
              source="measured",
              us={"fold/ref": 10.5, "parity/ref": 3.25})
    table.put(("sparse", 8, "pallas", 512, 6, "sparse@0.25"),
              "sparse_fused", impl="pallas",
              blocks={"block_w": 64, "grid_order": "wq"}, source="measured")
    path = tmp_path / "autotune.json"
    table.dump(str(path))
    blob = json.loads(path.read_text())
    assert blob["version"] == AutotuneTable.VERSION
    assert {e["scheme"] for e in blob["entries"]} == {"chor", "sparse"}
    # every dumped entry carries the measuring device's fingerprint
    assert all(e["device"] == device_fingerprint() for e in blob["entries"])
    back = AutotuneTable.load(str(path))
    assert len(back) == 2
    hit = back.get(("chor", 64, "ref", 512, 6, "mask"))
    assert hit["path"] == "parity" and hit["us"]["parity/ref"] == 3.25
    sp = back.get(("sparse", 8, "pallas", 512, 6, "sparse@0.25"))
    assert sp["impl"] == "pallas"
    assert sp["blocks"] == {"block_w": 64, "grid_order": "wq"}


def test_autotune_table_version_guard():
    with pytest.raises(ValueError, match="version"):
        AutotuneTable.from_json('{"version": 99, "entries": []}')


def test_autotune_merge_drops_and_counts_foreign_devices():
    """Satellite bugfix: a table dumped on a different host/accelerator
    must not silently pin wrong plans here — update() merges only
    entries fingerprinted for this device and counts the rest."""
    local = AutotuneTable()
    k_here = ("chor", 64, "ref", 512, 6, "mask")
    k_there = ("chor", 128, "ref", 512, 6, "mask")
    incoming = AutotuneTable()
    incoming.put(k_here, "fold", impl="ref", source="measured")
    incoming.put(
        k_there, "parity", impl="pallas", source="measured",
        device={"platform": "tpu", "device_kind": "TPU v9000"},
    )
    dropped = local.update(incoming)
    assert dropped == 1 and local.dropped == 1
    assert local.get(k_here) is not None and local.get(k_there) is None
    # roundtrip keeps foreign entries verbatim; only the *merge* filters
    back = AutotuneTable.from_json(incoming.to_json())
    assert back.get(k_there)["device"]["device_kind"] == "TPU v9000"
    assert back.update(AutotuneTable()) == 0  # filter is one-directional


def test_sharded_backend_autotune_file_cold_start_and_save(tmp_path):
    store = make_synthetic_store(128, 8, seed=1)
    path = str(tmp_path / "at.json")
    backend = ShardedBackend(store, autotune_file=path)  # missing: cold
    assert backend.autotune_dropped == 0
    backend.planner.table.put(
        ("chor", 64, "ref", 128, 2, "mask"), "fold", impl="ref",
        source="measured", us={"fold/ref": 1.0, "parity/ref": 2.0},
    )
    assert backend.save_autotune() == path
    # a second backend warm-starts from the dumped decisions
    warm = ShardedBackend(store, autotune_file=path)
    assert warm.planner.table.get(
        ("chor", 64, "ref", 128, 2, "mask")
    )["path"] == "fold"


def test_sharded_backend_autotune_file_foreign_entries_dropped(tmp_path):
    """Loading a file dumped on another device is a counted no-op, not a
    silent plan pin."""
    store = make_synthetic_store(128, 8, seed=1)
    path = str(tmp_path / "foreign.json")
    foreign = AutotuneTable()
    foreign.put(
        ("chor", 64, "ref", 128, 2, "mask"), "parity", impl="pallas",
        source="measured",
        device={"platform": "tpu", "device_kind": "TPU v9000"},
    )
    foreign.dump(path)
    backend = ShardedBackend(
        store, autotune=AutotuneTable(), autotune_file=path
    )
    assert backend.autotune_dropped == 1
    assert len(backend.planner.table) == 0


def test_autotune_merge_drops_and_counts_foreign_shapes():
    """Entries are stamped with the (n, words) they were measured
    against; update() with a wanted shape keeps same-shape entries,
    drops-and-counts resized ones exactly like foreign devices, and
    lets unstamped (pre-stamp) entries pass on the device check alone."""
    incoming = AutotuneTable()
    k_same = ("chor", 64, "ref", 128, 2, "mask")
    k_resized = ("chor", 64, "ref", 256, 2, "mask")
    k_legacy = ("chor", 32, "ref", 128, 2, "mask")
    incoming.put(k_same, "fold", impl="ref", source="measured",
                 store_shape=(128, 2))
    incoming.put(k_resized, "parity", impl="ref", source="measured",
                 store_shape=(256, 2))
    incoming.put(k_legacy, "fold", impl="ref", source="measured")
    local = AutotuneTable()
    dropped = local.update(incoming, store_shape=(128, 2))
    assert dropped == 1 and local.dropped == 1
    assert local.get(k_same) is not None
    assert local.get(k_legacy) is not None  # unstamped: back-compat
    assert local.get(k_resized) is None
    # the stamp survives the JSON round-trip verbatim
    back = AutotuneTable.from_json(incoming.to_json())
    assert back.get(k_same)["store_shape"] == [128, 2]
    # no wanted shape: device fingerprint alone filters (old behavior)
    relaxed = AutotuneTable()
    assert relaxed.update(incoming) == 0


def test_sharded_backend_autotune_file_survives_same_shape_restart(tmp_path):
    """--autotune-file tables survive a same-shape restart verbatim;
    pointing the same file at a resized store drops the stale entries
    (their measured winners were shaped by the old store geometry)."""
    store = make_synthetic_store(128, 8, seed=1)
    path = str(tmp_path / "stamped.json")
    backend = ShardedBackend(store, autotune=AutotuneTable(),
                             autotune_file=path)
    key = ("chor", 64, "ref", store.n, store.words, "mask")
    backend.planner.table.put(
        key, "fold", impl="ref", source="measured",
        store_shape=(store.n, store.words),
    )
    backend.save_autotune()
    same = ShardedBackend(store, autotune=AutotuneTable(),
                          autotune_file=path)
    assert same.autotune_dropped == 0
    assert same.planner.table.get(key)["path"] == "fold"
    resized = ShardedBackend(
        make_synthetic_store(256, 8, seed=1), autotune=AutotuneTable(),
        autotune_file=path,
    )
    assert resized.autotune_dropped == 1
    assert len(resized.planner.table) == 0


# ------------------------------------------------------------ plan decisions
def _routed(scheme, n, b, key=0):
    router = SchemeRouter(scheme)
    return router.plan(jax.random.key(key), n, jnp.arange(b) % n)


def test_plan_sparse_family_and_vmem_gate():
    store = make_synthetic_store(256, 16, seed=2)
    sch = make_scheme("sparse", d=2, d_a=1, theta=0.25).staged
    routed = _routed(sch, store.n, 4)
    for backend, paths in (
        ("ref", {"sparse_ref"}),
        ("pallas", {"sparse_fused", "sparse_pair"}),
    ):
        plan = KernelPlanner(
            store, backend=backend, table=AutotuneTable()
        ).plan(routed, 4, None, scheme=sch)
        assert plan.path in paths
        assert plan.family == "sparse"
        assert plan.m_budget is not None and plan.m_budget > 0
        assert plan.run is not None  # single host: executor attached


def test_plan_sparse_dense_fallback_consults_cost_model():
    """The scheme's costs(n) decide whether gathering pays at all: on a
    tiny store the θ·n + 6σ budget is no longer meaningfully below n, so
    the planner hands the (still sparse-masked) batch to the dense
    fold/parity decision — same bits, different physical form."""
    small = make_synthetic_store(64, 8, seed=7)
    sch = make_scheme("sparse", d=4, d_a=2, theta=0.3).staged
    plan = KernelPlanner(small, table=AutotuneTable()).plan(
        _routed(sch, small.n, 2), 2, None, scheme=sch
    )
    assert plan.path in ("fold", "parity")
    assert plan.m_budget is None
    # and the answers stay exact through the serving backend
    backend = ShardedBackend(small)
    router = SchemeRouter(sch)
    routed = router.plan(jax.random.key(3), small.n, jnp.asarray([5, 63]))
    out = router.finalize(routed, backend.answer_batch(routed, scheme=sch))
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(small.packed)[np.asarray([5, 63])]
    )
    # a CT-sized store keeps the gather family for the same θ
    big = make_synthetic_store(4096, 8, seed=7)
    plan_big = KernelPlanner(big, table=AutotuneTable()).plan(
        _routed(sch, big.n, 2, key=1), 2, None, scheme=sch
    )
    assert plan_big.family == "sparse"


def test_autotune_families_never_collide():
    """Regression: a sparse decision cached in the table must never be
    handed back as a dense fold/parity decision (or vice versa) for the
    same (scheme, bucket, n, words) — the key's family component keeps
    the two candidate sets apart. θ=0.25 gathers on this store; θ=0.49's
    budget crosses the dense cutoff, so the SAME scheme name takes both
    routes through one shared table."""
    store = make_synthetic_store(128, 8, seed=11)
    table = AutotuneTable()
    planner = KernelPlanner(store, backend="pallas", table=table)

    gathery = make_scheme("sparse", d=4, d_a=2, theta=0.25).staged
    plan_a = planner.plan(_routed(gathery, store.n, 2), 2, None,
                          scheme=gathery)
    assert plan_a.family == "sparse"

    densy = make_scheme("sparse", d=4, d_a=2, theta=0.49).staged
    plan_b = planner.plan(_routed(densy, store.n, 2, key=1), 2, None,
                          scheme=densy)
    assert plan_b.path in ("fold", "parity")
    assert plan_b.m_budget is None
    # and both execute (the collision used to crash the dense build)
    for sch, plan in ((gathery, plan_a), (densy, plan_b)):
        routed = _routed(sch, store.n, 2, key=2)
        np.testing.assert_array_equal(
            np.asarray(plan(routed.payload[0])),
            np.asarray(ref.xor_fold_ref(store.packed, routed.payload[0])),
        )


def test_plan_forced_parity_crossover():
    store = make_synthetic_store(128, 8, seed=3)
    sch = make_scheme("chor", d=2, d_a=1).staged
    planner = KernelPlanner(store, parity_min_batch=8, table=AutotuneTable())
    lo = planner.plan(_routed(sch, store.n, 4), 4, None, scheme=sch)
    hi = planner.plan(_routed(sch, store.n, 16), 16, None, scheme=sch)
    assert (lo.path, lo.source) == ("fold", "forced")
    assert (hi.path, hi.source) == ("parity", "forced")


def test_plan_never_measures_on_request_path():
    """Satellite bugfix: a cold cell costs zero microbenchmarks on the
    calling (request) thread — plan() answers from the analytic prior
    and queues the cell for the idle-slot search."""
    store = make_synthetic_store(200, 8, seed=4)
    sch = make_scheme("chor", d=2, d_a=1).staged
    measure, calls = _counting_measure()
    planner = KernelPlanner(store, table=AutotuneTable(), measure=measure)

    cold = planner.plan(_routed(sch, store.n, 64), 64, None, scheme=sch)
    assert cold.source == "model"
    assert calls == []  # nothing was timed inline
    key = planner._table_key("chor", 64, "ref")
    assert planner.pending() == (key,)
    assert planner.table.get(key) is None  # priors are not table entries


def test_tune_step_measures_all_candidates_and_replan_uses_winner():
    """The idle-slot search measures every candidate for the cell,
    records the winner + all timings + the device fingerprint, and a
    re-plan of the same cell returns the measured winner."""
    store = make_synthetic_store(200, 8, seed=4)
    sch = make_scheme("chor", d=2, d_a=1).staged
    measure, calls = _counting_measure()
    planner = KernelPlanner(store, table=AutotuneTable(), measure=measure)
    routed = _routed(sch, store.n, 64)
    planner.plan(routed, 64, None, scheme=sch)

    assert planner.tune_step() == 1
    assert planner.pending() == ()
    key = planner._table_key("chor", 64, "ref")
    entry = planner.table.get(key)
    assert entry["source"] == "measured"
    assert entry["device"] == device_fingerprint()
    # the dense-mask family races fold vs parity on the resolved impl
    assert set(entry["us"]) == {"fold/ref", "parity/ref"}
    assert {c.path for c in calls} == {"fold", "parity"}
    # first measured candidate got the fastest fake timing
    assert entry["path"] == calls[0].path

    warm = planner.plan(routed, 64, None, scheme=sch)
    assert warm.source == "measured" and warm.path == entry["path"]
    # and nothing else got queued or re-measured by the warm plan
    assert len(calls) == 2 and planner.pending() == ()


def test_autotune_search_deterministic_under_fixed_seed():
    """Same planner seed, same cells, same (injected) timer ⇒ identical
    bench payloads, candidate order, labels and recorded winner."""
    store = make_synthetic_store(256, 16, seed=2)
    sch = make_scheme("sparse", d=2, d_a=1, theta=0.25).staged
    runs = []
    for _ in range(2):
        seen = []

        def measure(fn, payload, candidate=None, seen=seen):
            seen.append(
                (candidate.label,
                 np.asarray(payload).sum(), np.asarray(payload).shape)
            )
            return float(len(seen))

        planner = KernelPlanner(
            store, backend="pallas", table=AutotuneTable(),
            seed=7, measure=measure,
        )
        planner.plan(_routed(sch, store.n, 8, key=1), 8, None, scheme=sch)
        planner.tune_pending()
        key = planner._table_key("sparse", 8, "pallas", 0.25)
        entry = planner.table.get(key)
        runs.append((seen, entry["path"], entry["blocks"], entry["us"]))
    assert runs[0] == runs[1]


def test_never_regress_ref_baseline_wins_when_pallas_slowed():
    """The never-regress guarantee: under the auto backend the search
    always races the ref-oracle baseline; artificially slowing every
    pallas candidate makes the recorded winner — and the re-planned
    executor — the ref path, bit-identically."""
    store = make_synthetic_store(256, 16, seed=2)
    sch = make_scheme("sparse", d=2, d_a=1, theta=0.25).staged

    def slow_pallas(fn, *args, candidate=None):
        return 10_000.0 if candidate.impl == "pallas" else 1.0

    planner = KernelPlanner(
        store, backend="auto", table=AutotuneTable(), measure=slow_pallas
    )
    # on this CPU host auto resolves to ref; force the pallas resolution
    # so the search actually has a kernel side to lose (interpret mode
    # keeps the pallas candidates runnable off-TPU)
    planner.backend = type(
        "StubAuto", (), {"name": "auto", "resolve": lambda self: "pallas"}
    )()
    routed = _routed(sch, store.n, 8, key=1)
    cold = planner.plan(routed, 8, None, scheme=sch)
    assert cold.impl == "pallas" and cold.source == "model"

    planner.tune_pending()
    key = planner._table_key("sparse", 8, "pallas", 0.25)
    entry = planner.table.get(key)
    assert (entry["path"], entry["impl"]) == ("sparse_ref", "ref")
    assert "sparse_ref/ref" in entry["us"]
    assert any(lbl.startswith("sparse_fused/pallas") for lbl in entry["us"])

    warm = planner.plan(routed, 8, None, scheme=sch)
    assert (warm.path, warm.impl, warm.source) == (
        "sparse_ref", "ref", "measured"
    )
    np.testing.assert_array_equal(
        np.asarray(warm(routed.payload[0])),
        np.asarray(ref.xor_fold_ref(store.packed, routed.payload[0])),
    )


def test_sparse_search_space_covers_blocks_and_grid_orders():
    """The sparse-family search space is (fused vs pair) × block_w ×
    grid_order — and a real (wall-clock) tuned winner stays
    bit-identical to the oracle whatever point it lands on."""
    store = make_synthetic_store(256, 16, seed=2)
    sch = make_scheme("sparse", d=2, d_a=1, theta=0.25).staged
    planner = KernelPlanner(store, backend="pallas", table=AutotuneTable())
    routed = _routed(sch, store.n, 4, key=3)
    planner.plan(routed, 4, None, scheme=sch)
    assert planner.tune_pending() == 1
    entry = planner.table.get(planner._table_key("sparse", 4, "pallas", 0.25))
    fused = [l for l in entry["us"] if l.startswith("sparse_fused")]
    pair = [l for l in entry["us"] if l.startswith("sparse_pair")]
    assert fused and pair
    assert any("grid_order=qw" in l for l in fused)
    assert any("grid_order=wq" in l for l in fused)
    assert any("grid_order=qwm" in l for l in pair)
    assert any("grid_order=wqm" in l for l in pair)
    warm = planner.plan(routed, 4, None, scheme=sch)
    assert warm.source == "measured"
    np.testing.assert_array_equal(
        np.asarray(warm(routed.payload[0])),
        np.asarray(ref.xor_fold_ref(store.packed, routed.payload[0])),
    )


def test_plan_cache_returns_same_plan():
    store = make_synthetic_store(64, 8, seed=5)
    sch = make_scheme("chor", d=2, d_a=1).staged
    planner = KernelPlanner(store, table=AutotuneTable())
    a = planner.plan(_routed(sch, store.n, 4), 4, None, scheme=sch)
    b = planner.plan(_routed(sch, store.n, 4, key=9), 4, None, scheme=sch)
    assert a is b
    planner.invalidate()
    c = planner.plan(_routed(sch, store.n, 4), 4, None, scheme=sch)
    assert c is not a


# ------------------------------------------- registry-parameterized identity
@pytest.mark.parametrize("backend", sorted(registered_backends()))
@pytest.mark.parametrize(
    "name,kw",
    [("chor", {}), ("sparse", dict(theta=0.25)), ("subset", dict(t=3)),
     ("direct", dict(p=8))],
)
def test_every_backend_answers_bit_identically(backend, name, kw):
    """Acceptance bar: for every registered backend, the planned
    execution of every wire kind reconstructs the exact records — and the
    mask-family partial answers equal the jnp oracle server-for-server."""
    store = make_synthetic_store(222, 20, seed=6)
    sch = make_scheme(name, d=4, d_a=2, **kw).staged
    router = SchemeRouter(sch)
    routed = router.plan(jax.random.key(7), store.n, jnp.asarray([0, 97, 221]))
    exec_backend = ShardedBackend(store, backend=backend)
    responses = exec_backend.answer_batch(routed, scheme=sch)
    if routed.kind == "mask":
        for pos in range(len(routed.servers)):
            np.testing.assert_array_equal(
                np.asarray(responses[pos]),
                np.asarray(
                    ref.xor_fold_ref(store.packed, routed.payload[pos])
                ),
            )
    out = router.finalize(routed, responses)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(store.packed)[np.asarray([0, 97, 221])]
    )


def test_prepared_plan_is_used_by_answer_batch():
    store = make_synthetic_store(96, 12, seed=8)
    sch = make_scheme("sparse", d=3, d_a=1, theta=0.3).staged
    backend = ShardedBackend(store, backend="pallas")
    routed = _routed(sch, store.n, 4)
    plan = backend.prepare(routed, scheme=sch)
    assert isinstance(plan, ExecutionPlan)
    assert plan.path.startswith("sparse") and plan.impl == "pallas"
    # handing the plan back skips re-planning and answers identically
    got = backend.answer_batch(routed, plan=plan, scheme=sch)
    want = jnp.stack([
        ref.xor_fold_ref(store.packed, routed.payload[p])
        for p in range(len(routed.servers))
    ])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_candidates_that_cannot_fit_the_device_stay_out_of_the_race():
    """With the device's memory limit known, the parity path (whose
    bitplanes take 6 bytes per record bit) and the sparse oracle (which
    gathers [bucket, m, W] rows) leave the race once they cannot fit
    beside the store — and a cold dense cell's prior stays on fold even
    past the parity crossover."""
    store = make_synthetic_store(256, 16, seed=2)
    planner = KernelPlanner(store, backend="auto", table=AutotuneTable())
    dense = TuneCell(scheme="chor", bucket=256, impl="pallas", theta=None,
                     n_eff=256, m_budget=None)
    sparse = TuneCell(scheme="sparse", bucket=1024, impl="pallas",
                      theta=0.25, n_eff=256, m_budget=96)
    labels = lambda cell: [c.label for c in planner._candidates(cell)]

    assert "parity/pallas" in labels(dense)
    assert "sparse_ref/ref" in labels(sparse)
    assert planner._prior(dense)[0] == "parity"

    planner = KernelPlanner(
        store, backend="auto", table=AutotuneTable(),
        memory_limit_bytes=store.nbytes + 256 * 128 * 6 - 1,
    )
    assert labels(dense) == ["fold/pallas", "fold/ref"]
    assert planner._prior(dense)[0] == "fold"
    assert labels(sparse)
    assert all("/pallas" in label for label in labels(sparse))
