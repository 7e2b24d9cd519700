"""Pallas TPU kernel: gather-XOR — the Sparse-PIR server hot path.

Sparse-PIR's entire point (paper §4.3, Table 1) is that each server touches
only θ·n records: C_p = θ·d·n·(c_acc + c_prc). A dense fold cannot exploit
that, so this kernel streams *only the selected records* out of HBM using
scalar-prefetched indices to drive the BlockSpec index_map — the TPU
analogue of the CPU implementation's pointer-chasing gather.

Layout: idx [q, m] int32 (selected record ids per query, padded with -1;
m = ceil(θ·n·slack) is static). Grid: (q, w_blocks, m) by default; the
output tile stays in VMEM across the m innermost steps while selected
record blocks are DMA'd in. ``grid_order="wqm"`` swaps the two outer axes
(word-blocks outer, queries middle) — the m accumulation axis always
stays innermost, so both orders write each output block exactly once and
are bit-identical; which order streams better is the execution planner's
autotune search to settle (DESIGN.md §Execution backends), along with the
``block_w`` tile.

TPU tiling: a block's last two dims must be multiples of (8, 128) or span
the array. So each step fetches the 8-row tile holding the selected
record and XORs only that record's row into the matching row of an
[8, BW] output tile (padded -1 slots keep none); the wrapper folds the 8
rows. The index list rides in scalar memory, which holds 1 MiB on a v5e:
:func:`~repro.kernels.tiles.xor_over_index_chunks` splits a longer list
into calls that each fit scalar memory and XORs their answers.

Per-step VMEM: db tile 8·BW·4 + out 8·BW·4 = 8 KiB at BW=128 — the
kernel is pure DMA-bound streaming, as the cost model says it should be.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiles import SUBLANES, fold_sublanes, xor_over_index_chunks

__all__ = ["gather_xor", "indices_from_mask"]

DEFAULT_BLOCK_W = 128


def _kernel(idx_ref, db_ref, out_ref, *, b_axis: int, m: int):
    b = pl.program_id(b_axis)
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    j = idx_ref[b * m + i]
    rows = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 0)
    keep = (rows == j % SUBLANES) & (j >= 0)
    out_ref[...] = out_ref[...] ^ jnp.where(keep, db_ref[...], jnp.uint32(0))


@functools.partial(
    jax.jit, static_argnames=("block_w", "grid_order", "interpret")
)
def gather_xor(
    db: jnp.ndarray,
    idx: jnp.ndarray,
    *,
    block_w: int = DEFAULT_BLOCK_W,
    grid_order: str = "qwm",
    interpret: bool = False,
) -> jnp.ndarray:
    """db: [n, W] uint32; idx: [q, m] int32 (−1 = padding) -> [q, W]."""
    if grid_order not in ("qwm", "wqm"):
        raise ValueError(
            f"grid_order must be 'qwm' or 'wqm', got {grid_order!r}"
        )
    w = db.shape[1]
    q = idx.shape[0]

    # the store is never copied: ragged edge tiles read past its end,
    # and the row select and the final slice discard that
    bw = min(block_w, w)
    wblocks = -(-w // bw)

    if grid_order == "qwm":
        b_axis, j_axis = 0, 1
    else:
        b_axis, j_axis = 1, 0

    def call(ix: jnp.ndarray) -> jnp.ndarray:
        c = ix.shape[1]
        grid = (q, wblocks, c) if grid_order == "qwm" else (wblocks, q, c)

        def db_map(*args):
            ids, idx_ref = args[:3], args[3]
            # the 8-row tile holding the selected record; padded (-1)
            # slots clamp to tile 0 and keep no row in-kernel
            j = idx_ref[ids[b_axis] * c + ids[2]]
            return (jnp.maximum(j, 0) // SUBLANES, ids[j_axis])

        def out_map(*args):
            ids = args[:3]
            return (ids[b_axis], 0, ids[j_axis])

        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[pl.BlockSpec((SUBLANES, bw), db_map)],
            out_specs=pl.BlockSpec((None, SUBLANES, bw), out_map),
        )
        return pl.pallas_call(
            functools.partial(_kernel, b_axis=b_axis, m=c),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(
                (q, SUBLANES, wblocks * bw), jnp.uint32
            ),
            interpret=interpret,
        )(ix.reshape(-1), db)

    return fold_sublanes(xor_over_index_chunks(call, idx))[:, :w]


@functools.partial(jax.jit, static_argnames=("m",))
def indices_from_mask(mask: jnp.ndarray, m: int) -> jnp.ndarray:
    """[q, n] {0,1} request vectors -> [q, m] selected indices, -1 padded.

    ``m`` must bound the per-row weight; Sparse-PIR uses
    m = ceil(θ·n·slack) and the weight concentrates tightly (Binomial).
    Rows whose weight exceeds m would be truncated — callers size m via
    repro.kernels.ops.sparse_index_budget which makes that probability
    negligible, and the serving engine falls back to xor_fold on overflow.
    """
    q, n = mask.shape
    # stable sort moves the 1s' column indices to the front of each row
    order = jnp.argsort(-(mask != 0).astype(jnp.int32), axis=1, stable=True)
    keep = order[:, :m]
    valid = jnp.take_along_axis((mask != 0), keep, axis=1)
    return jnp.where(valid, keep, -1).astype(jnp.int32)
