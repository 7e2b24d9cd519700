"""Output-tile and index-chunk layout shared by the answer kernels.

TPU blocks tile as (8, 128): a block's last two dims must be multiples of
those or span the array. So every answer kernel (xor_fold, gather_xor,
the fused gather-folds) writes one [8, BW] partial tile per query, and
:func:`fold_sublanes` folds its 8 rows in XLA. Kernels that scalar-prefetch
an index list keep each call's list under :data:`SMEM_INDEX_BYTES` through
:func:`xor_over_index_chunks`.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

__all__ = [
    "SMEM_INDEX_BYTES",
    "SUBLANES",
    "fold_sublanes",
    "xor_over_index_chunks",
]

SUBLANES = 8

# scalar memory one call's index list may take: half of a v5e core's
# 1 MiB SMEM, leaving the rest to the compiler
SMEM_INDEX_BYTES = 512 * 1024


def fold_sublanes(out: jnp.ndarray) -> jnp.ndarray:
    """[q, 8, W] per-query partial tiles -> [q, W]: the last fold, in XLA."""
    return jax.lax.reduce(out, jnp.uint32(0), jax.lax.bitwise_xor, (1,))


def xor_over_index_chunks(
    call: Callable[[jnp.ndarray], jnp.ndarray], idx: jnp.ndarray
) -> jnp.ndarray:
    """XOR of ``call(chunk)`` over column chunks of ``idx`` [q, m], each
    small enough for scalar memory. Every answer kernel is XOR-linear in
    its index list and ignores -1 slots, so this equals ``call(idx)``."""
    q, m = idx.shape
    chunks = max(1, -(-q * m * 4 // SMEM_INDEX_BYTES))
    if chunks == 1:
        return call(idx)
    c = -(-m // chunks)
    idx = jnp.pad(idx, ((0, 0), (0, chunks * c - m)), constant_values=-1)
    parts = idx.reshape(q, chunks, c).transpose(1, 0, 2)
    return jax.lax.fori_loop(
        1, chunks, lambda i, acc: acc ^ call(parts[i]), call(parts[0])
    )
