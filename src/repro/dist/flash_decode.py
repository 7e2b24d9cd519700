"""Flash-decode: one-token attention against a sequence-sharded KV cache.

At 524k-token decode the KV cache is the whole memory budget, so it lives
sharded over mesh axes along the *sequence* dim (rule "kv_seq"). Each shard
computes a partial softmax over its local cache slice as the flash triple
(running max m, sum-of-exp l, exp-weighted values o); the triples combine
exactly across shards with one pmax + two psums:

    m* = pmax(m)        l* = Σ e^{m−m*}·l        o* = Σ e^{m−m*}·o
    out = o* / l*

which is algebraically identical to softmax over the full cache — the
multidevice checks assert fp-closeness (2e-5) against the dense reference,
windowed and unwindowed.

``length`` and ``window`` may be traced scalars (the transformer scans
layers with per-layer windows), so all masking is data-dependent; only
``window=None`` is a static branch.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.dist.sharding import current_mesh, mesh_axis_names

__all__ = ["flash_decode"]

_NEG = -1e30  # mask value; large-negative (not -inf) keeps exp() NaN-free


def _repeat_kv(kv: jnp.ndarray, groups: int) -> jnp.ndarray:
    b, s, h, d = kv.shape
    kv = jnp.broadcast_to(kv[:, :, :, None, :], (b, s, h, groups, d))
    return kv.reshape(b, s, h * groups, d)


def _partial_softmax(q, k, v, length, offset, window, attn_softcap):
    """Local flash triple over one cache slice.

    q: [B, 1, Hq, D]; k/v: [B, S_loc, Hkv, D]; offset: first global
    position of this slice. Returns (m [B,Hq], l [B,Hq], o [B,Hq,D]) f32.
    """
    b, _, hq, dh = q.shape
    hkv = k.shape[2]
    k = _repeat_kv(k, hq // hkv)
    v = _repeat_kv(v, hq // hkv)

    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
    )[:, :, 0, :] / math.sqrt(dh)                      # [B, Hq, S_loc]
    if attn_softcap > 0.0:
        s = attn_softcap * jnp.tanh(s / attn_softcap)

    kpos = offset + jnp.arange(k.shape[1])             # global positions
    valid = kpos < length
    if window is not None:
        valid &= kpos > length - 1 - window
    s = jnp.where(valid[None, None, :], s, _NEG)

    m = jnp.max(s, axis=-1)                            # [B, Hq]
    p = jnp.where(valid[None, None, :], jnp.exp(s - m[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhk,bkhd->bhd", p, v.astype(jnp.float32))
    return m, l, o


def _dense_decode(q, k_cache, v_cache, length, window, attn_softcap):
    """Single-device reference (same math as layers.decode_attention)."""
    m, l, o = _partial_softmax(q, k_cache, v_cache, length, 0, window,
                               attn_softcap)
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out[:, None].astype(q.dtype)


def flash_decode(
    q: jnp.ndarray,          # [B, 1, Hq, D]
    k_cache: jnp.ndarray,    # [B, Smax, Hkv, D]
    v_cache: jnp.ndarray,
    length: jnp.ndarray,     # scalar: #valid cache entries
    *,
    axis_names,              # mesh axes the cache sequence is sharded over
    window=None,             # None | python int | traced scalar
    attn_softcap: float = 0.0,
) -> jnp.ndarray:
    """Two-pass sequence-parallel decode attention. Returns [B, 1, Hq, D].

    Falls back to the dense path when no mesh is active, the named axes
    are absent, or Smax doesn't divide over them.
    """
    mesh = current_mesh()
    axes = tuple(a for a in axis_names if mesh is not None and a in mesh.shape)
    s_max = k_cache.shape[1]
    n_sh = math.prod(mesh.shape[a] for a in axes) if axes else 1
    if not axes or n_sh <= 1 or s_max % n_sh != 0:
        return _dense_decode(q, k_cache, v_cache, length, window, attn_softcap)
    s_loc = s_max // n_sh

    baxes = tuple(a for a in mesh_axis_names("batch") if a not in axes)
    bshards = math.prod(mesh.shape[a] for a in baxes) if baxes else 1
    if baxes and q.shape[0] % bshards != 0:
        baxes = ()

    q_spec = P(baxes or None, None, None, None)
    kv_spec = P(baxes or None, axes, None, None)

    has_window = window is not None
    args = (q, k_cache, v_cache, jnp.asarray(length))
    in_specs = [q_spec, kv_spec, kv_spec, P()]
    if has_window:
        args += (jnp.asarray(window),)
        in_specs.append(P())

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=q_spec,
        check_vma=False,
    )
    def _decode(qc, kc, vc, ln, *rest):
        win = rest[0] if has_window else None
        lin = jnp.int32(0)
        for a in axes:
            lin = lin * mesh.shape[a] + jax.lax.axis_index(a)
        m, l, o = _partial_softmax(
            qc, kc, vc, ln, lin * s_loc, win, attn_softcap
        )
        m_g = jax.lax.pmax(m, axes)
        alpha = jnp.exp(m - m_g)              # ≤ 1; 0 for fully-masked shards
        l_g = jax.lax.psum(alpha * l, axes)
        o_g = jax.lax.psum(alpha[..., None] * o, axes)
        out = o_g / jnp.maximum(l_g, 1e-30)[..., None]
        return out[:, None].astype(qc.dtype)

    return _decode(*args)
