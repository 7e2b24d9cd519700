"""Pallas TPU kernel: fused gather→xor→fold — Sparse-PIR's answer in ONE kernel.

The unfused Sparse-PIR server path is a *pair* of kernel-shaped steps:
``indices_from_mask`` ranks the selected record ids, then ``gather_xor``
streams one selected record per innermost grid step, XOR-accumulating the
output block across m grid iterations (m = index budget). That pair costs
one grid *step* per selected record: every step re-enters the kernel body
and re-touches the output block, and the accumulator state lives across
grid steps (DESIGN.md §Execution backends has the fusion diagram).

This kernel fuses the gather, the XOR, and the fold into a single grid
step per (query, word-block): the whole record axis of one word-block is
made VMEM-resident, and a ``fori_loop`` *inside* the kernel body walks the
scalar-prefetched indices, dynamic-slicing selected rows out of VMEM and
folding them into a register accumulator. One kernel launch, one output
write, no cross-step accumulator — the gather→xor→fold chain the unfused
pair spreads over m grid steps collapses into in-kernel control flow.

Two shape knobs are exposed to the execution planner's autotune search
(DESIGN.md §Execution backends): ``block_w`` (the word-block width) and
``grid_order`` — ``"qw"`` walks queries in the outer grid axis (the db
word-block is re-fetched per query), ``"wq"`` walks word-blocks outer so
one VMEM-resident db block serves *every* query before the next block is
fetched. Which wins depends on q, n·BW, and the DMA/compute balance of
the host — exactly the kind of question the planner settles by
measurement, not by napkin.

The price is VMEM residency: the db word-block is [n, BW] uint32, so the
kernel only applies when ``n·BW·4`` fits the VMEM budget —
:func:`fused_block_w` picks the widest lane-legal BW that fits (W
itself, or a multiple of 128 words: the TPU compiler refuses narrower
slices of a wider row) and returns 0 when none does, which is exactly
the signal the execution planner (``repro.kernels.backend``) uses to
fall back to the unfused pair. The budget derives from the *local*
device (:func:`fused_vmem_budget`: a quarter of the device's VMEM, by
``device_kind``, since the slab is double-buffered), falling back to the
:data:`FUSED_VMEM_BUDGET_BYTES` constant off-TPU; a TPU kind the table
does not list is an error. At CT scale (n = 10⁶) the fused form only
applies per record *shard*; single-host million-record stores take the
streaming pair.

Outputs follow :mod:`~repro.kernels.gather_xor`'s layout: one [8, BW]
tile per query whose row 0 holds the answer, folded by the wrapper, and
index lists longer than scalar memory holds run as XOR-ed chunks.

Bit-identity: fused(db, idx) == gather_xor(db, idx) == xor_fold(db, mask)
== the jnp oracle, proven exactly in tests/test_kernels.py and swept by
hypothesis in tests/test_kernel_properties.py.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiles import SUBLANES, fold_sublanes, xor_over_index_chunks

__all__ = [
    "fused_gather_fold",
    "fused_multi_gather_fold",
    "jagged_row_mask",
    "fused_block_w",
    "fused_vmem_budget",
    "FUSED_VMEM_BUDGET_BYTES",
]

DEFAULT_BLOCK_W = 128

# VMEM budget the fused db word-block may occupy off-TPU (interpret mode)
FUSED_VMEM_BUDGET_BYTES = 8 * 1024 * 1024

# per-core VMEM by TPU device kind (jax's ``device_kind`` string), each
# confirmed by compiling the fused kernel for that chip. A kind missing
# here raises: a wrong size would let the planner propose a kernel the
# compiler refuses, or hide one that fits.
_TPU_VMEM_BYTES = {
    "TPU v5 lite": 128 * 1024 * 1024,  # v5e
}

# lanes per vreg: a block narrower than the row must be a multiple of it
LANES = 128
# VMEM the fused kernels reserve beyond the double-buffered slab
_VMEM_HEADROOM = 4 * 1024 * 1024


def fused_vmem_budget() -> int:
    """VMEM budget for the fused db word-block, derived from the local
    device: a quarter of the device's per-core VMEM on TPU (the slab is
    double-buffered, and the rest stays free for the output tiles and the
    loop state), the :data:`FUSED_VMEM_BUDGET_BYTES` constant anywhere
    else. Raises for a TPU kind :data:`_TPU_VMEM_BYTES` does not list.
    The execution planner threads a ``PIRConfig.fused_vmem_budget_bytes``
    override past this entirely."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return FUSED_VMEM_BUDGET_BYTES
    kind = str(getattr(dev, "device_kind", ""))
    if kind not in _TPU_VMEM_BYTES:
        raise ValueError(
            f"no VMEM size known for TPU kind {kind!r}; add it to "
            f"repro.kernels.fused._TPU_VMEM_BYTES after confirming it by "
            f"a compile for that chip"
        )
    return _TPU_VMEM_BYTES[kind] // 4


def fused_block_w(n: int, w: int, *, block_w: int = DEFAULT_BLOCK_W,
                  budget_bytes: Optional[int] = None) -> int:
    """Widest lane-legal word-block ≤ min(block_w, W) whose [n, BW]
    uint32 db slab fits the VMEM budget: W itself, or a power-of-two
    multiple of 128 words. 0 when none fits (caller must fall back to the
    unfused streaming pair). ``budget_bytes=None`` derives the budget
    from the local device (:func:`fused_vmem_budget`)."""
    if budget_bytes is None:
        budget_bytes = fused_vmem_budget()
    cap = min(block_w, w)
    widths = {w} if cap == w else set()
    bw = LANES
    while bw <= cap:
        widths.add(bw)
        bw *= 2
    for bw in sorted(widths, reverse=True):
        if n * bw * 4 <= budget_bytes:
            return bw
    return 0


def _vmem_limit(n: int, bw: int, out_rows: int) -> int:
    """Scoped VMEM a fused call needs: the double-buffered [n, BW] slab
    and output tiles, plus headroom."""
    return 2 * (n * bw + out_rows * SUBLANES * bw) * 4 + _VMEM_HEADROOM


def _fold_rows(idx_ref, db_ref, row: int, m: int, bw: int) -> jnp.ndarray:
    """[1, BW] XOR of the db rows that index row ``row`` of the (flat)
    index list selects; padded (-1) slots clamp to row 0 and are masked
    out of the fold."""

    def body(i, acc):
        j = idx_ref[row * m + i]
        r = db_ref[pl.ds(jnp.maximum(j, 0), 1), :]
        return acc ^ jnp.where(j >= 0, r, jnp.uint32(0))

    return jax.lax.fori_loop(0, m, body, jnp.zeros((1, bw), jnp.uint32))


def _answer_tile(acc: jnp.ndarray) -> jnp.ndarray:
    """[1, BW] answer -> [8, BW] output tile holding it in row 0."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, acc.shape[1]), 0)
    return jnp.where(rows == 0, acc, jnp.uint32(0))


def _kernel(idx_ref, db_ref, out_ref, *, b_axis: int, m: int):
    # gather+xor+fold: a register accumulator across the in-kernel index
    # walk — the single output write below is the whole answer for this
    # block
    acc = _fold_rows(idx_ref, db_ref, pl.program_id(b_axis), m,
                     out_ref.shape[-1])
    out_ref[...] = _answer_tile(acc)


@functools.partial(
    jax.jit, static_argnames=("block_w", "grid_order", "interpret")
)
def fused_gather_fold(
    db: jnp.ndarray,
    idx: jnp.ndarray,
    *,
    block_w: int = DEFAULT_BLOCK_W,
    grid_order: str = "qw",
    interpret: bool = False,
) -> jnp.ndarray:
    """db: [n, W] uint32; idx: [q, m] int32 (−1 = padding) -> [q, W].

    Semantics identical to ``gather_xor(db, idx)`` for every
    ``grid_order``; see the module docstring for the knobs the planner's
    autotune search sweeps and when it picks which.
    """
    if grid_order not in ("qw", "wq"):
        raise ValueError(f"grid_order must be 'qw' or 'wq', got {grid_order!r}")
    n, w = db.shape
    q = idx.shape[0]

    bw = min(block_w, w)
    wblocks = -(-w // bw)  # a ragged last word-block reads past W

    if grid_order == "qw":
        # queries outer: the db word-block is re-fetched per query
        grid = (q, wblocks)
        db_map = lambda b, j, idx_ref: (0, j)
        out_map = lambda b, j, idx_ref: (b, 0, j)
        b_axis = 0
    else:
        # word-blocks outer: one resident db block answers every query
        # before the next block is DMA'd in
        grid = (wblocks, q)
        db_map = lambda j, b, idx_ref: (0, j)
        out_map = lambda j, b, idx_ref: (b, 0, j)
        b_axis = 1

    def call(ix: jnp.ndarray) -> jnp.ndarray:
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                # the whole record axis of one word-block, VMEM-resident
                # for the duration of the in-kernel index walk
                pl.BlockSpec((n, bw), db_map),
            ],
            out_specs=pl.BlockSpec((None, SUBLANES, bw), out_map),
        )
        return pl.pallas_call(
            functools.partial(_kernel, b_axis=b_axis, m=ix.shape[1]),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(
                (q, SUBLANES, wblocks * bw), jnp.uint32
            ),
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=_vmem_limit(n, bw, 1)
            ),
            interpret=interpret,
        )(ix.reshape(-1), db)

    return fold_sublanes(xor_over_index_chunks(call, idx))[:, :w]


# --------------------------------------------------------------------------
# Jagged multi-index fusion (DESIGN.md §Multi-index wire format)
# --------------------------------------------------------------------------
def jagged_row_mask(offsets: jnp.ndarray, k_max: int, rows: int) -> jnp.ndarray:
    """[rows] bool: which flat rows of the padded multi-index layout are
    live. Row ``r·k_max + i`` is live iff ``i < offsets[r+1] − offsets[r]``
    — the mask the streaming-pair and oracle fallbacks apply to their
    index matrices so all three multi paths stay bit-identical, padding
    rows included (they all answer zero there)."""
    off = jnp.asarray(offsets, jnp.int32)
    r = jnp.arange(rows, dtype=jnp.int32) // k_max
    i = jnp.arange(rows, dtype=jnp.int32) % k_max
    return i < off[r + 1] - off[r]


def _multi_kernel(off_ref, idx_ref, db_ref, out_ref, *, b_axis: int,
                  k_max: int, m: int):
    r = pl.program_id(b_axis)
    bw = out_ref.shape[-1]
    # the jagged descriptor rides in scalar memory: this request's live
    # column count bounds which of its k_max rows carry real queries
    count = off_ref[r + 1] - off_ref[r]

    def fold(i, carry):
        acc = _fold_rows(idx_ref, db_ref, r * k_max + i, m, bw)
        out_ref[i] = _answer_tile(jnp.where(i < count, acc, jnp.uint32(0)))
        return carry

    # one grid step answers ALL of this request's indices: the db
    # word-block is fetched once per request (once per *batch* in "wr"
    # order), not once per index as the flat kernel's grid does
    jax.lax.fori_loop(0, k_max, fold, 0)


@functools.partial(
    jax.jit, static_argnames=("k_max", "block_w", "grid_order", "interpret")
)
def fused_multi_gather_fold(
    db: jnp.ndarray,
    idx: jnp.ndarray,
    offsets: jnp.ndarray,
    *,
    k_max: int,
    block_w: int = DEFAULT_BLOCK_W,
    grid_order: str = "rw",
    interpret: bool = False,
) -> jnp.ndarray:
    """db: [n, W] uint32; idx: [R·k_max, m] int32 (−1 = padding);
    offsets: [R+1] int32 jagged descriptor -> [R·k_max, W].

    The multi-index answer stage fused across a request's whole index
    list: the grid walks (request, word-block) — ``"rw"`` requests outer,
    ``"wr"`` word-blocks outer so one VMEM-resident db block serves every
    request before the next block is DMA'd — and an in-kernel loop folds
    all k_max index rows of the request against the resident block.
    Row ``r·k_max + i`` of the output is ``gather_xor(db, idx[r·k_max+i])``
    when live (``i < offsets[r+1] − offsets[r]``) and zero otherwise;
    equivalently ``gather_xor(db, idx_masked)`` with
    :func:`jagged_row_mask` applied — the bit-identity the parity sweep
    pins against the streaming pair and the jnp oracle.
    """
    if grid_order not in ("rw", "wr"):
        raise ValueError(f"grid_order must be 'rw' or 'wr', got {grid_order!r}")
    n, w = db.shape
    b = idx.shape[0]
    if k_max < 1 or b % k_max:
        raise ValueError(f"idx rows {b} not a multiple of k_max={k_max}")
    r_count = b // k_max
    if offsets.shape[0] != r_count + 1:
        raise ValueError(
            f"offsets must be [R+1]={r_count + 1}, got {offsets.shape[0]}"
        )

    bw = min(block_w, w)
    wblocks = -(-w // bw)  # a ragged last word-block reads past W

    if grid_order == "rw":
        grid = (r_count, wblocks)
        db_map = lambda r, j, off_ref, idx_ref: (0, j)
        out_map = lambda r, j, off_ref, idx_ref: (r, 0, j)
        b_axis = 0
    else:
        grid = (wblocks, r_count)
        db_map = lambda j, r, off_ref, idx_ref: (0, j)
        out_map = lambda j, r, off_ref, idx_ref: (r, 0, j)
        b_axis = 1
    offsets = jnp.asarray(offsets, jnp.int32)

    def call(ix: jnp.ndarray) -> jnp.ndarray:
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((n, bw), db_map),
            ],
            out_specs=pl.BlockSpec((k_max, SUBLANES, bw), out_map),
        )
        return pl.pallas_call(
            functools.partial(
                _multi_kernel, b_axis=b_axis, k_max=k_max, m=ix.shape[1]
            ),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(
                (b, SUBLANES, wblocks * bw), jnp.uint32
            ),
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=_vmem_limit(n, bw, k_max)
            ),
            interpret=interpret,
        )(offsets, ix.reshape(-1), db)

    return fold_sublanes(xor_over_index_chunks(call, idx))[:, :w]
