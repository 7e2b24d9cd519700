"""Per-kernel validation: Pallas (interpret mode on CPU) vs pure-jnp oracle,
swept over shapes and dtypes. PIR is bit-exact — comparisons are equality."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.db import make_synthetic_store
from repro.kernels import (
    fused_block_w,
    fused_gather_fold,
    gather_xor,
    indices_from_mask,
    ops,
    parity_matmul,
    ref,
    xor_fold,
)

SHAPES = [
    # (n records, record_bytes, q queries)
    (64, 8, 1),
    (100, 12, 5),       # ragged W
    (256, 64, 16),
    (300, 50, 17),      # everything ragged
    (1024, 4, 33),      # tiny records
    (37, 129, 3),       # W > block
]

MASK_DTYPES = [jnp.uint8, jnp.int32, jnp.bool_]


def _case(n, rb, q, seed=0):
    store = make_synthetic_store(n=n, record_bytes=rb, seed=seed)
    key = jax.random.key(seed + 1)
    mask = (jax.random.uniform(key, (q, n)) < 0.4).astype(jnp.uint8)
    return store, mask


@pytest.mark.parametrize("n,rb,q", SHAPES)
def test_xor_fold_matches_ref(n, rb, q):
    store, mask = _case(n, rb, q)
    want = np.asarray(ref.xor_fold_ref(store.packed, mask))
    got = np.asarray(xor_fold(store.packed, mask, interpret=True))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("dtype", MASK_DTYPES)
def test_xor_fold_mask_dtypes(dtype):
    store, mask = _case(128, 16, 7)
    want = np.asarray(ref.xor_fold_ref(store.packed, mask))
    got = np.asarray(
        xor_fold(store.packed, mask.astype(dtype), interpret=True)
    )
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("block_q,block_n,block_w", [(4, 64, 32), (8, 256, 128), (16, 32, 8)])
def test_xor_fold_block_sweep(block_q, block_n, block_w):
    store, mask = _case(200, 40, 11)
    want = np.asarray(ref.xor_fold_ref(store.packed, mask))
    got = np.asarray(
        xor_fold(
            store.packed, mask,
            block_q=block_q, block_n=block_n, block_w=block_w,
            interpret=True,
        )
    )
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("n,rb,q", SHAPES)
def test_parity_matmul_matches_ref(n, rb, q):
    store, mask = _case(n, rb, q)
    planes = store.bitplanes()
    want = np.asarray(ref.parity_matmul_ref(mask, planes))
    got = np.asarray(parity_matmul(mask, planes, interpret=True))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("in_dtype", [jnp.uint8, jnp.float32, jnp.bfloat16])
def test_parity_matmul_dtypes(in_dtype):
    store, mask = _case(128, 16, 9)
    planes = store.bitplanes().astype(in_dtype)
    want = np.asarray(ref.parity_matmul_ref(mask, store.bitplanes()))
    got = np.asarray(
        parity_matmul(mask.astype(in_dtype), planes, interpret=True)
    )
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("n,rb,q", SHAPES)
def test_gather_xor_matches_ref(n, rb, q):
    store, mask = _case(n, rb, q)
    m = min(n, 192)
    idx = indices_from_mask(mask, m)
    want = np.asarray(ref.gather_xor_ref(store.packed, idx))
    got = np.asarray(gather_xor(store.packed, idx, interpret=True))
    np.testing.assert_array_equal(got, want)


def test_gather_xor_all_padding():
    store, _ = _case(64, 8, 2)
    idx = jnp.full((2, 16), -1, jnp.int32)
    got = np.asarray(gather_xor(store.packed, idx, interpret=True))
    np.testing.assert_array_equal(got, 0)


def test_indices_from_mask_roundtrip():
    _, mask = _case(150, 8, 6)
    idx = np.asarray(indices_from_mask(mask, 150))
    mask_np = np.asarray(mask)
    for row in range(mask_np.shape[0]):
        sel = sorted(idx[row][idx[row] >= 0].tolist())
        want = sorted(np.nonzero(mask_np[row])[0].tolist())
        assert sel == want


def test_server_paths_agree_end_to_end():
    """fold == parity == sparse on the same masks (the three server paths
    are interchangeable implementations of the same GF(2) contract)."""
    store, mask = _case(222, 36, 13)
    fold = np.asarray(ops.server_answer_fold(store.packed, mask))
    par = np.asarray(ops.server_answer_parity(store.bitplanes(), mask))
    sp = np.asarray(ops.server_answer_sparse(store.packed, mask, theta=0.4))
    np.testing.assert_array_equal(fold, par)
    np.testing.assert_array_equal(fold, sp)


# --------------------------------------------------------------------------
# Fused gather→xor→fold (the one-kernel Sparse-PIR answer): must be
# bit-identical to BOTH halves it replaces — the indices_from_mask +
# gather_xor streaming pair and the dense xor_fold — and to the jnp
# oracle. Single-record and non-pow2 edge shapes ride the same sweep.
# --------------------------------------------------------------------------
EDGE_SHAPES = [
    # (n records, record_bytes, q queries) — single-record/single-query
    # degenerate corners the bucketed serving path can still produce
    (1, 8, 1),
    (1, 24, 5),
    (2, 4, 1),
    (7, 129, 1),
]


@pytest.mark.parametrize("n,rb,q", SHAPES + EDGE_SHAPES)
def test_fused_matches_oracle_and_unfused_pair(n, rb, q):
    store, mask = _case(n, rb, q)
    idx = indices_from_mask(mask, n)  # m = n: no truncation, fold comparable
    want = np.asarray(ref.gather_xor_ref(store.packed, idx))
    got = np.asarray(fused_gather_fold(store.packed, idx, interpret=True))
    np.testing.assert_array_equal(got, want)
    # the composition the fused kernel replaces, both halves:
    np.testing.assert_array_equal(
        got, np.asarray(gather_xor(store.packed, idx, interpret=True))
    )
    np.testing.assert_array_equal(
        got, np.asarray(xor_fold(store.packed, mask, interpret=True))
    )


@pytest.mark.parametrize("block_w", [8, 32, 128])
def test_fused_block_sweep(block_w):
    store, mask = _case(211, 21, 6, seed=4)
    idx = indices_from_mask(mask, 120)
    want = np.asarray(ref.gather_xor_ref(store.packed, idx))
    got = np.asarray(
        fused_gather_fold(store.packed, idx, block_w=block_w, interpret=True)
    )
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("grid_order", ["qw", "wq"])
@pytest.mark.parametrize("block_w", [8, 32])
def test_fused_grid_order_sweep_bit_identical(grid_order, block_w):
    """Both fused grid layouts ("qw": queries outer, "wq": word-blocks
    outer, reusing the query slab across the w sweep) are pure schedule
    choices — bit-identical to the ref gather for every block width the
    autotuner may pick."""
    store, mask = _case(211, 21, 6, seed=4)
    idx = indices_from_mask(mask, 120)
    want = np.asarray(ref.gather_xor_ref(store.packed, idx))
    got = np.asarray(fused_gather_fold(
        store.packed, idx, block_w=block_w, grid_order=grid_order,
        interpret=True,
    ))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("grid_order", ["qwm", "wqm"])
@pytest.mark.parametrize("block_w", [16, 64])
def test_gather_xor_grid_order_sweep_bit_identical(grid_order, block_w):
    """The streaming pair's two outer-loop orders (queries-major vs
    word-blocks-major; m always innermost so the XOR accumulation stays
    sequential) agree bit-for-bit with the ref gather."""
    store, mask = _case(211, 21, 6, seed=5)
    idx = indices_from_mask(mask, 120)
    want = np.asarray(ref.gather_xor_ref(store.packed, idx))
    got = np.asarray(gather_xor(
        store.packed, idx, block_w=block_w, grid_order=grid_order,
        interpret=True,
    ))
    np.testing.assert_array_equal(got, want)


def test_fused_all_padding_rows():
    store, _ = _case(64, 8, 2)
    idx = jnp.full((2, 16), -1, jnp.int32)
    got = np.asarray(fused_gather_fold(store.packed, idx, interpret=True))
    np.testing.assert_array_equal(got, 0)


def test_fused_truncated_budget_matches_pair():
    """With m below the row weight the fused kernel and the streaming
    pair see the SAME truncated index set — identical answers even in
    the overflow regime the budget makes negligible."""
    store, mask = _case(90, 10, 4, seed=9)
    idx = indices_from_mask(mask, 8)
    np.testing.assert_array_equal(
        np.asarray(fused_gather_fold(store.packed, idx, interpret=True)),
        np.asarray(gather_xor(store.packed, idx, interpret=True)),
    )


def test_fused_block_w_vmem_gate():
    # fits: tiny store keeps the full default block
    assert fused_block_w(256, 16) == 16
    assert fused_block_w(4096, 512) == 128  # capped at the default block
    # shrinks to fit: W = 384 words is too wide, 128 lanes fit
    assert fused_block_w(16384, 384, block_w=512) == 128
    # never below one 128-lane vreg: 64k × 128 words × 4 B = 32 MiB > budget
    assert fused_block_w(65536, 128) == 0
    # nothing fits at CT scale on one host -> 0 = fall back to the pair
    assert fused_block_w(10**6, 384) == 0
    # W under 128 lanes is only legal whole: no sliver blocks ever escape
    assert fused_block_w(150_000, 12) == 12  # whole 12-word slab (7.2 MB)
    assert fused_block_w(200_000, 12) == 0   # 9.6 MB doesn't fit -> pair
    # on a TPU the budget is a quarter of the VMEM the table lists
    assert fused_block_w(65536, 128, budget_bytes=32 << 20) == 128


def test_sparse_index_budget_bounds():
    m = ops.sparse_index_budget(10_000, 0.25)
    assert 2500 < m < 3000 and m % 8 == 0
    assert ops.sparse_index_budget(16, 0.5) == 16  # clamped at n


# --------------------------------------------------------------------------
# Non-power-of-two database shapes (interpret mode on CPU): the Pallas
# kernels pad/clamp internally; every ragged edge must still be bit-exact
# against the pure-JAX oracles in kernels/ref.py.
# --------------------------------------------------------------------------
NONPOW2_SHAPES = [
    # (n records, record_bytes, q queries) — nothing a power of two
    (91, 12, 3),
    (137, 24, 7),
    (333, 36, 5),
    (1000, 20, 11),
    (63, 129, 9),     # W crosses the default block boundary
]


@pytest.mark.parametrize("n,rb,q", NONPOW2_SHAPES)
def test_gather_xor_nonpow2_shapes(n, rb, q):
    store, mask = _case(n, rb, q, seed=n)
    m = min(n, 160)
    idx = indices_from_mask(mask, m)
    want = np.asarray(ref.gather_xor_ref(store.packed, idx))
    got = np.asarray(gather_xor(store.packed, idx, interpret=True))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("n,rb,q", NONPOW2_SHAPES)
def test_parity_matmul_nonpow2_shapes(n, rb, q):
    store, mask = _case(n, rb, q, seed=n + 1)
    planes = store.bitplanes()
    want = np.asarray(ref.parity_matmul_ref(mask, planes))
    got = np.asarray(parity_matmul(mask, planes, interpret=True))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("block_q,block_b,block_n", [(4, 8, 32), (16, 128, 512)])
def test_parity_matmul_nonpow2_block_sweep(block_q, block_b, block_n):
    """Ragged shapes × non-aligned blocks: the padding path end to end."""
    store, mask = _case(147, 18, 5, seed=3)
    planes = store.bitplanes()
    want = np.asarray(ref.parity_matmul_ref(mask, planes))
    got = np.asarray(
        parity_matmul(
            mask, planes,
            block_q=block_q, block_b=block_b, block_n=block_n,
            interpret=True,
        )
    )
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("block_w", [8, 64])
def test_gather_xor_nonpow2_block_sweep(block_w):
    store, mask = _case(211, 21, 6, seed=4)
    idx = indices_from_mask(mask, 120)
    want = np.asarray(ref.gather_xor_ref(store.packed, idx))
    got = np.asarray(
        gather_xor(store.packed, idx, block_w=block_w, interpret=True)
    )
    np.testing.assert_array_equal(got, want)


# --------------------------------------------------------------------------
# Jagged multi-index fusion (DESIGN.md §Multi-index wire format): the
# fused multi kernel must be bit-identical to the streaming pair and the
# jnp oracle on the jagged_row_mask-masked index matrix — the identity
# that lets the autotune search race all three forms for a multi bucket
# without ever picking a non-bit-identical candidate.
# --------------------------------------------------------------------------
from repro.kernels import fused_multi_gather_fold, jagged_row_mask  # noqa: E402

JAGGED_CASES = [
    # (counts per request, k_max) — incl. the degenerate serving corners
    ((5,), 8),                # 1 request × k indices
    ((1, 1, 1, 1, 1, 1, 1, 1), 1),  # k requests × 1 index
    ((3, 0, 8, 1), 8),        # empty row + full row + stragglers
    ((2, 2), 2),              # exact fit, no padding rows
]


def _jagged_case(n, rb, counts, k_max, seed=0, garbage=False):
    """Random per-index sparse masks laid out on the padded multi grid.
    Dead rows (i >= counts[r]) hold -1 padding — or, with ``garbage``,
    live-looking indices the kernel's jagged mask must suppress."""
    store = make_synthetic_store(n=n, record_bytes=rb, seed=seed)
    rng = np.random.default_rng(seed + 7)
    m = min(n, 24)
    idx = np.full((len(counts) * k_max, m), -1, np.int32)
    for r, c in enumerate(counts):
        upto = k_max if garbage else c
        for i in range(upto):
            w = int(rng.integers(1, m + 1))
            idx[r * k_max + i, :w] = rng.choice(n, size=w, replace=False)
    offsets = np.cumsum([0] + list(counts)).astype(np.int32)
    return store, jnp.asarray(idx), jnp.asarray(offsets)


def _masked(idx, offsets, k_max):
    """The oracle's view: dead rows forced to all-padding."""
    live = np.asarray(jagged_row_mask(offsets, k_max, idx.shape[0]))
    return jnp.asarray(np.where(live[:, None], np.asarray(idx), -1))


@pytest.mark.parametrize("counts,k_max", JAGGED_CASES)
@pytest.mark.parametrize("grid_order", ["rw", "wr"])
def test_fused_multi_matches_masked_pair_and_oracle(counts, k_max, grid_order):
    store, idx, off = _jagged_case(100, 12, counts, k_max, seed=k_max)
    got = np.asarray(fused_multi_gather_fold(
        store.packed, idx, off, k_max=k_max, grid_order=grid_order,
        interpret=True,
    ))
    masked = _masked(idx, off, k_max)
    np.testing.assert_array_equal(
        got, np.asarray(ref.gather_xor_ref(store.packed, masked))
    )
    np.testing.assert_array_equal(
        got, np.asarray(gather_xor(store.packed, masked, interpret=True))
    )


@pytest.mark.parametrize("block_w", [8, 32, 128])
def test_fused_multi_block_sweep_nonpow2_w(block_w):
    """Non-pow2 record width across every block the search may pick."""
    store, idx, off = _jagged_case(91, 21, (4, 0, 7), 8, seed=3)
    want = np.asarray(ref.gather_xor_ref(store.packed, _masked(idx, off, 8)))
    got = np.asarray(fused_multi_gather_fold(
        store.packed, idx, off, k_max=8, block_w=block_w, interpret=True,
    ))
    np.testing.assert_array_equal(got, want)


def test_fused_multi_zeroes_dead_rows_regardless_of_contents():
    """The jagged descriptor, not the -1 convention, is what silences a
    padding row: even live-looking garbage indices in dead rows must
    answer zero (the serving path relies on this when it reuses a
    scratch index buffer across buckets)."""
    store, idx, off = _jagged_case(64, 8, (3, 0, 1), 4, seed=9, garbage=True)
    got = np.asarray(fused_multi_gather_fold(
        store.packed, idx, off, k_max=4, interpret=True,
    ))
    live = np.asarray(jagged_row_mask(off, 4, idx.shape[0]))
    np.testing.assert_array_equal(got[~live], 0)
    np.testing.assert_array_equal(
        got, np.asarray(ref.gather_xor_ref(store.packed, _masked(idx, off, 4)))
    )


def test_fused_multi_all_live_matches_flat_forms():
    """With the serving layer's canonical all-live offsets (every flat
    column a real query — padding columns are dummies whose responses the
    client discards) the multi kernel degenerates to the flat contract:
    bit-identical to fused_gather_fold and gather_xor on the same index
    matrix, for both grid orders."""
    store, mask = _case(128, 16, 8, seed=6)
    idx = indices_from_mask(mask, 64)
    k_max = 4
    off = jnp.arange(idx.shape[0] // k_max + 1, dtype=jnp.int32) * k_max
    want = np.asarray(fused_gather_fold(store.packed, idx, interpret=True))
    for go in ("rw", "wr"):
        got = np.asarray(fused_multi_gather_fold(
            store.packed, idx, off, k_max=k_max, grid_order=go,
            interpret=True,
        ))
        np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(
        want, np.asarray(gather_xor(store.packed, idx, interpret=True))
    )


def test_fused_multi_validates_layout():
    store, idx, off = _jagged_case(64, 8, (2, 2), 2, seed=1)
    with pytest.raises(ValueError, match="grid_order"):
        fused_multi_gather_fold(store.packed, idx, off, k_max=2,
                                grid_order="zz", interpret=True)
    with pytest.raises(ValueError, match="multiple of k_max"):
        fused_multi_gather_fold(store.packed, idx, off, k_max=3,
                                interpret=True)
    with pytest.raises(ValueError, match=r"offsets must be \[R\+1\]"):
        fused_multi_gather_fold(store.packed, idx, off[:-1], k_max=2,
                                interpret=True)


def test_jagged_row_mask_matches_python():
    off = jnp.asarray(np.array([0, 3, 3, 4, 12], np.int32))
    k_max, rows = 8, 32
    got = np.asarray(jagged_row_mask(off, k_max, rows))
    counts = np.diff(np.asarray(off))
    for r in range(4):
        for i in range(k_max):
            assert got[r * k_max + i] == (i < counts[r]), (r, i)


def test_multi_vmem_gate_falls_back_to_pair():
    """When the db word-block cannot fit VMEM (fused_block_w == 0) the
    planner's multi-bucket prior and candidate set must both drop to the
    streaming pair — the fused multi kernel never runs outside its
    residency envelope."""
    from repro.kernels import AutotuneTable, KernelPlanner
    from repro.core import make_scheme

    store = make_synthetic_store(n=256, record_bytes=16, seed=2)
    sch = make_scheme("sparse", d=4, d_a=2, theta=0.25).staged
    plan = KernelPlanner(
        store, backend="pallas", table=AutotuneTable(),
        vmem_budget_bytes=1,  # nothing fits: the gate closes
    ).plan(
        sch.query(sch.precompute(jax.random.key(0), store.n, 8),
                  jnp.zeros((8,), jnp.int32)),
        8, None, scheme=sch, k_max=4,
    )
    assert plan.path == "sparse_pair", plan.path
    # with a real budget the same multi cell priors to the fused form
    plan2 = KernelPlanner(
        store, backend="pallas", table=AutotuneTable(),
    ).plan(
        sch.query(sch.precompute(jax.random.key(0), store.n, 8),
                  jnp.zeros((8,), jnp.int32)),
        8, None, scheme=sch, k_max=4,
    )
    assert plan2.path == "sparse_multi_fused", plan2.path
    assert dict(plan2.blocks)["k_max"] == 4


def test_fused_vmem_budget_names_the_tpu_kind(monkeypatch):
    """v5e's VMEM is in the table (a quarter of 128 MiB for the slab); a
    TPU kind the table does not list is an error, never a default."""
    from repro.kernels import fused

    class Dev:
        platform = "tpu"
        device_kind = "TPU v5 lite"

    monkeypatch.setattr(fused.jax, "devices", lambda: [Dev()])
    assert fused.fused_vmem_budget() == 32 * 1024 * 1024
    Dev.device_kind = "TPU v99"
    with pytest.raises(ValueError, match="TPU v99"):
        fused.fused_vmem_budget()


@pytest.mark.parametrize("q", [1, 5])
def test_index_chunks_match_one_call(monkeypatch, q):
    """An index list longer than scalar memory holds runs as chunks whose
    answers XOR together — bit-identical to the oracle, -1 padding and
    ragged chunk edges included, for every kernel that takes one."""
    from repro.kernels import tiles

    store, mask = _case(300, 20, q, seed=4)
    idx = indices_from_mask(mask, 136)
    want = np.asarray(ref.gather_xor_ref(store.packed, idx))
    # 64 bytes of indices per call: every call is split into many chunks
    monkeypatch.setattr(tiles, "SMEM_INDEX_BYTES", 64)
    got = gather_xor(store.packed, idx, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), want)
    np.testing.assert_array_equal(
        np.asarray(fused_gather_fold(store.packed, idx, interpret=True)), want
    )
