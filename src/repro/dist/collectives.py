"""Hand-written collectives for the sharded hot paths.

Three families:

* **Vocab-sharded lookups** (:func:`sharded_vocab_lookup` for LM embedding
  tables, :func:`sharded_table_lookup` for RecSys tables): each shard owns
  a contiguous row range, answers only the ids that land in its range, and
  the partial rows are psum'd — one [ids, D] all-reduce instead of
  all-gathering the table. Exactly one shard contributes each row (the
  rest add 0.0), so the result is bit-exact vs ``jnp.take``.

* **Compressed gradient all-reduce** (:func:`compressed_psum` +
  :func:`quantize_int8` / :func:`dequantize_int8`): int8 wire format with
  a shared pmax'd scale. Pairs with train.optimizer.ErrorFeedbackCompressor
  which makes the update *sequence* unbiased.

* **GF(2) collectives for the PIR serve path** (:func:`xor_psum`,
  :func:`sharded_record_lookup`): XOR is the reduction the PIR algebra
  wants — partial parities/folds from record shards combine exactly, with
  32× fewer collective bytes than an int32 psum of unpacked bits. The
  record lookup is the Direct-Requests gather with rows sharded over the
  "records" logical axis; exactly one shard owns each row, so the XOR
  all-reduce reconstructs it bit-exactly.

All entry points degrade to their single-device reference when no mesh is
active, the logical axis is unmapped, or shapes don't divide — identical
numerics, asserted in tests/_multidevice_checks.py.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.dist.sharding import current_mesh, mesh_axis_names

__all__ = [
    "sharded_vocab_lookup",
    "sharded_table_lookup",
    "compressed_psum",
    "quantize_int8",
    "dequantize_int8",
    "xor_psum",
    "sharded_record_lookup",
]


# --------------------------------------------------------------------------
# Vocab-sharded lookups
# --------------------------------------------------------------------------
def _sharded_lookup(table: jnp.ndarray, ids: jnp.ndarray, vocab_logical: str):
    # clamp ids in EVERY path: out-of-range ids would otherwise behave
    # differently on-mesh (no shard owns them -> psum of zeros) vs off-mesh
    # (jnp.take's jit default fills NaN) — lookups must not depend on mesh
    ids = jnp.clip(ids, 0, table.shape[0] - 1)

    mesh = current_mesh()
    vaxes = mesh_axis_names(vocab_logical)
    if mesh is None or not vaxes:
        return jnp.take(table, ids, axis=0)

    v = table.shape[0]
    vshards = math.prod(mesh.shape[a] for a in vaxes)
    if vshards <= 1 or v % vshards != 0:
        # can't row-shard evenly (e.g. dien's 18-dim table on 16-way TP)
        return jnp.take(table, ids, axis=0)
    v_loc = v // vshards

    baxes = tuple(a for a in mesh_axis_names("batch") if a not in vaxes)
    bshards = math.prod(mesh.shape[a] for a in baxes) if baxes else 1
    if baxes and ids.shape[0] % bshards != 0:
        baxes = ()

    ids_spec = P(baxes or None, *([None] * (ids.ndim - 1)))
    out_spec = P(baxes or None, *([None] * ids.ndim))

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(vaxes, *([None] * (table.ndim - 1))), ids_spec),
        out_specs=out_spec,
        check_vma=False,
    )
    def _lookup(tbl, idl):
        lin = jnp.int32(0)
        for a in vaxes:
            lin = lin * mesh.shape[a] + jax.lax.axis_index(a)
        rel = idl - lin * v_loc
        ok = (rel >= 0) & (rel < v_loc)
        rows = jnp.take(tbl, jnp.clip(rel, 0, v_loc - 1), axis=0)
        rows = jnp.where(ok[..., None], rows, jnp.zeros((), rows.dtype))
        return jax.lax.psum(rows, vaxes)

    return _lookup(table, ids)


def sharded_vocab_lookup(table: jnp.ndarray, ids: jnp.ndarray) -> jnp.ndarray:
    """LM token-embedding gather. table: [V, D] (rows sharded over the
    "vocab" rule); ids: int32 [...] (lead dim sharded over "batch").
    Returns [..., D], bit-exact vs ``jnp.take(table, ids, axis=0)`` for
    in-range ids; out-of-range ids clamp (identically on and off mesh)."""
    return _sharded_lookup(table, ids, "vocab")


def sharded_table_lookup(table: jnp.ndarray, ids: jnp.ndarray) -> jnp.ndarray:
    """RecSys embedding-table gather, rows sharded over "table_vocab"."""
    return _sharded_lookup(table, ids, "table_vocab")


# --------------------------------------------------------------------------
# GF(2) collectives (PIR serve path)
# --------------------------------------------------------------------------
def xor_psum(x: jnp.ndarray, axis_names) -> jnp.ndarray:
    """XOR all-reduce — call INSIDE shard_map over ``axis_names``.

    Power-of-two axes use a log2-round ppermute butterfly (each round moves
    the packed uint32 payload once); other sizes fall back to all_gather +
    fold. XOR is associative/commutative, so the result is bit-exact
    regardless of schedule. Requires an active mesh_rules context at trace
    time (for the static axis sizes).
    """
    axes = (axis_names,) if isinstance(axis_names, str) else tuple(axis_names)
    mesh = current_mesh()
    if mesh is None:
        raise ValueError("xor_psum needs an active mesh_rules context")
    for ax in axes:
        size = mesh.shape[ax]
        if size & (size - 1) == 0:
            k = 1
            while k < size:
                perm = [(i, i ^ k) for i in range(size)]
                x = x ^ jax.lax.ppermute(x, ax, perm)
                k *= 2
        else:
            g = jax.lax.all_gather(x, ax)
            x = jax.lax.reduce(
                g, jnp.zeros((), x.dtype), jax.lax.bitwise_xor, (0,)
            )
    return x


def sharded_record_lookup(packed: jnp.ndarray, ids: jnp.ndarray) -> jnp.ndarray:
    """Record gather with rows sharded over the "records" logical axis.

    packed: [n, W] uint32 (the RecordStore payload); ids: int32 [...].
    Returns [..., W] uint32, bit-exact vs ``jnp.take(packed, ids, axis=0)``
    for in-range ids (out-of-range clamp, identically on and off mesh).
    Each shard answers only the rows it owns (the rest contribute 0) and the
    partials XOR-combine — the Direct-Requests server path at mesh scale.
    """
    ids = jnp.clip(ids, 0, packed.shape[0] - 1)

    mesh = current_mesh()
    raxes = mesh_axis_names("records")
    if mesh is None or not raxes:
        return jnp.take(packed, ids, axis=0)

    n = packed.shape[0]
    rshards = math.prod(mesh.shape[a] for a in raxes)
    if rshards <= 1 or n % rshards != 0:
        return jnp.take(packed, ids, axis=0)
    n_loc = n // rshards

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(raxes, *([None] * (packed.ndim - 1))),
                  P(*([None] * ids.ndim))),
        out_specs=P(*([None] * (ids.ndim + 1))),
        check_vma=False,
    )
    def _lookup(db, idl):
        lin = jnp.int32(0)
        for a in raxes:
            lin = lin * mesh.shape[a] + jax.lax.axis_index(a)
        rel = idl - lin * n_loc
        ok = (rel >= 0) & (rel < n_loc)
        rows = jnp.take(db, jnp.clip(rel, 0, n_loc - 1), axis=0)
        rows = jnp.where(ok[..., None], rows, jnp.zeros((), rows.dtype))
        return xor_psum(rows, raxes)

    return _lookup(packed, ids)


# --------------------------------------------------------------------------
# int8 compression + compressed all-reduce
# --------------------------------------------------------------------------
def quantize_int8(
    x: jnp.ndarray, scale: jnp.ndarray | None = None
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-tensor symmetric int8: returns (q int8, scale f32 scalar) with
    x ≈ q·scale, |error| ≤ scale/2 elementwise. Pass ``scale`` to quantize
    onto a shared grid (compressed_psum pmax-shares it across shards)."""
    xf = x.astype(jnp.float32)
    if scale is None:
        scale = jnp.maximum(jnp.max(jnp.abs(xf)), 1e-30) / 127.0
    q = jnp.clip(jnp.round(xf / scale), -127.0, 127.0).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    return q.astype(jnp.float32) * scale


def compressed_psum(x: jnp.ndarray, axis_names) -> jnp.ndarray:
    """int8-compressed psum — call INSIDE shard_map over ``axis_names``.

    The scale is pmax-shared first so every shard quantizes onto the same
    grid; the int8 payloads then sum losslessly in int32 (what crosses the
    wire is the 1-byte tensor + one scalar). Total error is bounded by
    ``n_shards · scale/2`` elementwise — asserted in the multidevice checks.
    """
    axes = tuple(axis_names) if not isinstance(axis_names, str) else (axis_names,)
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf)), 1e-30) / 127.0
    scale = jax.lax.pmax(scale, axes)
    q, _ = quantize_int8(xf, scale)
    acc = jax.lax.psum(q.astype(jnp.int32), axes)
    return (acc.astype(jnp.float32) * scale).astype(x.dtype)
