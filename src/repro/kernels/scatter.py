"""Pallas TPU kernel: scatter-into-packed-words — the delta-ingest write path.

Serving a mutable database (DESIGN.md §13) needs one write-side primitive:
apply a batch of record updates ``db[rows[i]] = vals[i]`` to the packed
[n, W] uint32 substrate *on device*, producing the next version's buffer
without round-tripping the whole store through the host. Reads stay on the
answer kernels; this is the only kernel that writes.

Shape of the kernel: the grid walks row-blocks of the store, the update
rows ride in scalar-prefetch memory and the update payload is VMEM-resident
for every grid step. Each block starts from the old db block and folds the
m updates over it functionally (a ``fori_loop`` of masked selects — the
same register-accumulator idiom as the fused gather kernel, no conditional
stores), so a block none of the updates touch is a straight copy and a
touched block applies updates in index order: **for duplicate rows the last
update wins**, matching the host-numpy replay oracle. Callers that cannot
guarantee unique rows (``repro.db.live.Delta`` dedups at construction)
must dedup first, because the jnp ref oracle's ``.at[].set`` leaves
duplicate ordering to XLA.

The update batch ``vals`` is [m, W] and VMEM-resident, so m is bounded by
the VMEM budget; ``repro.db.live`` chunks large deltas before calling in.
Backend choice (this kernel vs the jnp oracle) is raced through the
execution-backend registry by :func:`repro.kernels.backend.scatter_update`
— consumers outside the package go through that, never through here
(tools/check_api.py fences this module like the other raw kernels).

Bit-identity: scatter_rows(db, rows, vals) == scatter_rows_ref(db, rows,
vals) == the host-numpy replay, proven in tests/test_db_live.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["scatter_rows", "DEFAULT_BLOCK_N"]

DEFAULT_BLOCK_N = 512


def _kernel(rows_ref, vals_ref, db_ref, out_ref, *, bn: int):
    blk = pl.program_id(0)
    start = blk * bn
    m = vals_ref.shape[0]
    # local row ids of this block; an update lands here iff its target row
    # falls inside [start, start+bn)
    local = jax.lax.broadcasted_iota(jnp.int32, (bn, 1), 0)

    def body(i, acc):
        j = rows_ref[i] - start
        sel = local == j  # [bn, 1]; out-of-block (incl. j<0) selects nothing
        return jnp.where(sel, vals_ref[pl.ds(i, 1), :], acc)

    # start from the old block and fold updates over it in index order —
    # last write wins for duplicate rows, matching the host replay oracle
    out_ref[...] = jax.lax.fori_loop(0, m, body, db_ref[...])


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def scatter_rows(
    db: jnp.ndarray,
    rows: jnp.ndarray,
    vals: jnp.ndarray,
    *,
    block_n: int = DEFAULT_BLOCK_N,
    interpret: bool = False,
) -> jnp.ndarray:
    """db: [n, W]; rows: [m] int; vals: [m, W] (cast to db.dtype) -> [n, W].

    Functional row scatter: returns a new buffer equal to ``db`` with
    ``out[rows[i]] = vals[i]`` applied in index order (last write wins).
    Dtype-generic over the scattered element type (uint32 packed words
    on the ingest path, uint8 bitplanes on the sharded serve layer's
    per-shard parity refresh).
    """
    n, w = db.shape
    m = rows.shape[0]
    if m == 0:
        return db
    bn = max(1, min(block_n, n))
    # no padded copy of the store: the ragged last block reads past the
    # end and its write-back is clipped to the array
    grid = (-(-n // bn),)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            # the whole update payload, VMEM-resident for every block step
            pl.BlockSpec((m, w), lambda i, rows_ref: (0, 0)),
            pl.BlockSpec((bn, w), lambda i, rows_ref: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bn, w), lambda i, rows_ref: (i, 0)),
    )
    return pl.pallas_call(
        functools.partial(_kernel, bn=bn),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, w), db.dtype),
        interpret=interpret,
    )(rows.astype(jnp.int32), vals.astype(db.dtype), db)
