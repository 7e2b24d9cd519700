"""Pallas TPU kernel: masked XOR fold over bit-packed records (VPU path).

The Chor/Sparse-PIR server answer for a batch of queries:

    out[q, :] = XOR_{i : mask[q, i] = 1} db[i, :]

db is [n, W] uint32 (W = record words). The kernel streams record blocks
HBM→VMEM once per query block and XOR-accumulates on the VPU; arithmetic
intensity is ~1 int-op/byte, so this path is HBM-bandwidth-bound — used for
small query batches (latency serving). Large batches use parity_matmul
(MXU path) instead; see DESIGN.md §Hardware adaptation.

Grid: (q_blocks, w_blocks, n_blocks), n innermost so the output block
stays resident in VMEM while records stream through.

The TPU compiler lowers no XOR ``reduce``, so the record axis folds by
halving: the selected [BQ, BN, BW] rows XOR pairwise down to one
8-sublane tile per query, the kernel accumulates those [BQ, 8, BW] tiles,
and the wrapper folds the last 8 rows with an XLA reduce. BN is therefore
8 times a power of two.

VMEM working set per step (defaults BQ=8, BN=256, BW=128):
  mask 8·256·4 + db 256·128·4 + out 8·8·128·4 + select temp 8·256·128·4
  ≈ 1.2 MiB  « 16 MiB VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tiles import SUBLANES, fold_sublanes

__all__ = ["xor_fold"]

DEFAULT_BLOCK_Q = 8
DEFAULT_BLOCK_N = 256
DEFAULT_BLOCK_W = 128


def fold_halves(x: jnp.ndarray, axis: int) -> jnp.ndarray:
    """XOR the two halves of ``axis`` together until it is 8 long (the
    axis length must be 8 times a power of two)."""
    while x.shape[axis] > SUBLANES:
        h = x.shape[axis] // 2
        x = (jax.lax.slice_in_dim(x, 0, h, axis=axis)
             ^ jax.lax.slice_in_dim(x, h, 2 * h, axis=axis))
    return x


def _kernel(mask_ref, db_ref, out_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    m = mask_ref[...]  # [BQ, BN] int32
    db = db_ref[...]  # [BN, BW] uint32
    sel = jnp.where(m[:, :, None] != 0, db[None, :, :], jnp.uint32(0))
    out_ref[...] = out_ref[...] ^ fold_halves(sel, 1)  # [BQ, 8, BW]


@functools.partial(
    jax.jit, static_argnames=("block_q", "block_n", "block_w", "interpret")
)
def xor_fold(
    db: jnp.ndarray,
    mask: jnp.ndarray,
    *,
    block_q: int = DEFAULT_BLOCK_Q,
    block_n: int = DEFAULT_BLOCK_N,
    block_w: int = DEFAULT_BLOCK_W,
    interpret: bool = False,
) -> jnp.ndarray:
    """db: [n, W] uint32; mask: [q, n] integer {0,1} -> [q, W] uint32."""
    q, n = mask.shape
    n2, w = db.shape
    assert n == n2, (mask.shape, db.shape)

    bq, bw = min(block_q, q), min(block_w, w)
    # BN: the smallest 8·2^k covering min(block_n, n), so the halving
    # fold always lands on one 8-row tile
    bn = SUBLANES << (-(-min(block_n, n) // SUBLANES) - 1).bit_length()
    # pad the mask to whole blocks with zeros (mask 0 selects nothing);
    # the store is never copied: its ragged edge blocks read past the
    # end, and the zero mask columns and the final slice discard that
    qp, np_ = (-q % bq), (-n % bn)
    mask_p = jnp.pad(mask.astype(jnp.int32), ((0, qp), (0, np_)))
    wblocks = -(-w // bw)
    grid = ((q + qp) // bq, wblocks, (n + np_) // bn)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, bn), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn, bw), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bq, SUBLANES, bw), lambda i, j, k: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct(
            (q + qp, SUBLANES, wblocks * bw), jnp.uint32
        ),
        interpret=interpret,
    )(mask_p, db)
    return fold_sublanes(out)[:q, :w]
