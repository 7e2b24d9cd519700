"""Pallas TPU kernel: masked XOR fold over bit-packed records (VPU path).

The Chor/Sparse-PIR server answer for a batch of queries:

    out[q, :] = XOR_{i : mask[q, i] = 1} db[i, :]

db is [n, W] uint32 (W = record words). One call streams the store
HBM→VMEM once per block of up to 8 queries and XOR-accumulates on the
VPU; arithmetic intensity is ~1 int-op/byte, so this path is bound by HBM
bandwidth and a few VPU ops per store vreg — used for small query batches
(latency serving). Large batches use parity_matmul (MXU path) instead;
see DESIGN.md §Hardware adaptation.

Grid: (q_blocks, w_blocks, n_blocks), n innermost so the output block
stays resident in VMEM while records stream through. The default blocks
follow the shapes (:func:`fold_blocks`): whole records (BW = W) in
record blocks of ~2–4 MiB, so a step's DMA is one contiguous run of rows
and one server's pass over the CT store (10^6 × 384 words) is 489 steps.

Select: the wrapper packs a query block's mask bits into one int32 word
per record (bit j = query j). Each step broadcasts that row of words
across lanes once — a sublane broadcast and one XLU transpose — into a
[BN, 128] scratch with records on sublanes. Query j's select for 8
records is then a shift and a sign test of one vreg, shared by every
128-word lane block of the record. The record axis folds four 8-row
tiles an iteration into one [8, BW] register tile per query (the TPU
lowers no XOR ``reduce``); the wrapper folds the last 8 rows with an XLA
reduce.

VMEM working set per step (CT shape: BQ=8, BN=2048, BW=384):
  db 2·2048·384·4 + bits 2·8·2048·4 + out 2·8·8·384·4 + broadcast
  scratch and its transpose 2·2048·128·4 ≈ 8.3 MiB ≤ the 16 MiB budget;
  ``vmem_limit_bytes`` asks for that plus 4 MiB of headroom.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fused import LANES
from repro.kernels.tiles import SUBLANES, fold_sublanes

__all__ = ["fold_blocks", "fold_vmem_bytes", "xor_fold"]

# queries per store pass: mask bits ride in one int32 word per record
DEFAULT_BLOCK_Q = 8
MAX_BLOCK_Q = 32
# store bytes one step streams, and the largest record block
STEP_BYTES = 4 * 1024 * 1024
MAX_BLOCK_N = 4096
# VMEM the working set may take: a v5e core's default scoped limit
VMEM_BUDGET_BYTES = 16 * 1024 * 1024
# records one loop iteration folds: 4 tiles per query and lane block
CHUNK_ROWS = 4 * SUBLANES
# scoped VMEM asked for beyond the working set (compiler temporaries)
_VMEM_HEADROOM = 4 * 1024 * 1024


def fold_vmem_bytes(bq: int, bn: int, bw: int) -> int:
    """VMEM one step holds: the double-buffered db slab, mask-bit row
    (padded to 8 sublanes) and output tiles, plus the lane-broadcast
    mask scratch and the transpose that fills it."""
    return 4 * (
        2 * bn * bw + 2 * SUBLANES * bn + 2 * bq * SUBLANES * bw
        + 2 * bn * LANES
    )


def fold_blocks(n: int, w: int, q: int) -> Tuple[int, int, int]:
    """The default (BQ, BN, BW) for an [n, W] store and q queries.

    BW is the whole record (W ≤ 128 lanes or a multiple of 128) unless
    the smallest record block of it overflows the VMEM budget; then
    128-lane word blocks. BN is the largest power-of-two multiple of 128,
    up to :data:`MAX_BLOCK_N`, whose slab stays within
    :data:`STEP_BYTES` and whose working set fits the budget — but no
    more rows than the store rounded up to whole 8-row tiles."""
    bq = min(DEFAULT_BLOCK_Q, q)
    whole = w <= LANES or w % LANES == 0
    for bw in ([w] if whole else []) + ([LANES] if w > LANES else []):
        bn = MAX_BLOCK_N
        while bn > LANES and (
            bn * bw * 4 > STEP_BYTES
            or fold_vmem_bytes(bq, bn, bw) > VMEM_BUDGET_BYTES
        ):
            bn //= 2
        if fold_vmem_bytes(bq, bn, bw) <= VMEM_BUDGET_BYTES:
            break
    return bq, min(bn, -(-n // SUBLANES) * SUBLANES), bw


def _kernel(bits_ref, db_ref, out_ref, bcast_ref, *, n: int, bq: int,
            bw: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # records onto sublanes, each record's mask word across all lanes
    bits = bits_ref[0]  # [1, BN] int32
    bn = bits.shape[1]
    bcast_ref[...] = jnp.broadcast_to(bits, (LANES, bn)).T
    lanes = [(c, min(LANES, bw - c)) for c in range(0, bw, LANES)]

    def rows(base, size):
        """XOR rows [r, r + size) into every query's accumulators."""
        def body(t, accs):
            r = pl.multiple_of(base + t * size, SUBLANES)
            b = bcast_ref[pl.ds(r, size), :]  # [size, 128]
            d = [db_ref[pl.ds(r, size), c:c + wd] for c, wd in lanes]
            out = []
            for j in range(bq):
                on = (b << (31 - j)) < 0  # query j's bit, per record row
                out.append(tuple(
                    a ^ _fold_tiles(jnp.where(on[:, :wd], x, jnp.uint32(0)))
                    for a, x, (_, wd) in zip(accs[j], d, lanes)
                ))
            return tuple(out)
        return body

    # whole chunks, then the 8-row tiles that still hold records: no load
    # reaches past the store's last tile in a ragged final block
    chunk = min(CHUNK_ROWS, bn)
    live = jnp.minimum(bn, n - k * bn)
    chunks = live // chunk
    tiles = (live - chunks * chunk + SUBLANES - 1) // SUBLANES
    zero = tuple(
        tuple(jnp.zeros((SUBLANES, wd), jnp.uint32) for _, wd in lanes)
        for _ in range(bq)
    )
    accs = jax.lax.fori_loop(0, chunks, rows(0, chunk), zero)
    accs = jax.lax.fori_loop(0, tiles, rows(chunks * chunk, SUBLANES), accs)
    for j in range(bq):
        for (c, wd), a in zip(lanes, accs[j]):
            out_ref[j, :, c:c + wd] ^= a


def _fold_tiles(x: jnp.ndarray) -> jnp.ndarray:
    """XOR of the 8-row tiles of ``x`` -> one [8, W] tile."""
    acc = x[:SUBLANES]
    for r in range(SUBLANES, x.shape[0], SUBLANES):
        acc = acc ^ x[r:r + SUBLANES]
    return acc


@functools.partial(
    jax.jit, static_argnames=("block_q", "block_n", "block_w", "interpret")
)
def xor_fold(
    db: jnp.ndarray,
    mask: jnp.ndarray,
    *,
    block_q: Optional[int] = None,
    block_n: Optional[int] = None,
    block_w: Optional[int] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """db: [n, W] uint32; mask: [q, n] integer {0,1} -> [q, W] uint32.

    A block left ``None`` comes from :func:`fold_blocks`."""
    q, n = mask.shape
    n2, w = db.shape
    assert n == n2, (mask.shape, db.shape)

    dq, dn, dw = fold_blocks(n, w, q)
    bq = min(block_q or dq, q, MAX_BLOCK_Q)
    bw = min(block_w or dw, w)
    bn = -(-min(block_n or dn, n) // SUBLANES) * SUBLANES
    # one int32 mask word per record and query block (bit j = query j),
    # zero-padded to whole blocks (a zero bit selects nothing); the store is
    # never copied: its ragged edge blocks read past the end, and the
    # zero mask words and the final slice discard that
    nq, qp, np_ = -(-q // bq), -q % bq, -n % bn
    on = jnp.pad((mask != 0).astype(jnp.int32), ((0, qp), (0, np_)))
    shifts = jnp.arange(bq, dtype=jnp.int32)[None, :, None]
    bits = jnp.sum(on.reshape(nq, bq, n + np_) << shifts, axis=1)
    wblocks = -(-w // bw)
    grid = (nq, wblocks, (n + np_) // bn)
    out = pl.pallas_call(
        functools.partial(_kernel, n=n, bq=bq, bw=bw),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bn), lambda i, j, k: (i, 0, k)),
            pl.BlockSpec((bn, bw), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bq, SUBLANES, bw), lambda i, j, k: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct(
            (nq * bq, SUBLANES, wblocks * bw), jnp.uint32
        ),
        scratch_shapes=[pltpu.VMEM((bn, LANES), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=fold_vmem_bytes(bq, bn, bw) + _VMEM_HEADROOM
        ),
        interpret=interpret,
    )(bits[:, None, :], db)
    return fold_sublanes(out)[:q, :w]
