"""Mixture-of-Experts block (top-k routing, capacity dispatch).

Expert parallelism is expressed as *tensor parallelism over the expert
axis*: tokens are sharded over batch axes and replicated over "model";
each model shard owns E/shards experts, dispatches its local share of
every token's top-k, and the partial outputs are psum'd over "model" —
one [T, D] all-reduce per MoE layer, no all-to-all, fully static shapes
(GSPMD-proof; see DESIGN.md §5).

Capacity-position assignment is sort-based (argsort + searchsorted rank-
within-run) instead of the GShard cumsum-of-one-hot, which would build a
[T·k, E] intermediate (≈400 MB for kimi-k2 locally). Dispatch/combine
loop over the k slots so the peak temp is [T, D], not [T·k, D].
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.dist.sharding import current_mesh, mesh_axis_names

__all__ = ["moe_init", "moe_apply", "moe_capacity"]


def moe_init(key, d_model: int, d_ff: int, n_experts: int, dtype=jnp.float32):
    kr, k1, k2, k3 = jax.random.split(key, 4)
    s_in = 1.0 / jnp.sqrt(d_model)
    s_out = 1.0 / jnp.sqrt(d_ff)
    return {
        "router": (jax.random.normal(kr, (d_model, n_experts)) * s_in).astype(
            jnp.float32  # router always fp32 (numerics)
        ),
        "w_gate": (jax.random.normal(k1, (n_experts, d_model, d_ff)) * s_in).astype(dtype),
        "w_in": (jax.random.normal(k2, (n_experts, d_model, d_ff)) * s_in).astype(dtype),
        "w_out": (jax.random.normal(k3, (n_experts, d_ff, d_model)) * s_out).astype(dtype),
    }


def moe_capacity(tokens_local: int, n_experts: int, top_k: int, factor: float) -> int:
    c = math.ceil(top_k * tokens_local * factor / n_experts)
    return max(8, -(-c // 8) * 8)


def _positions_within_expert(e_flat: jnp.ndarray) -> jnp.ndarray:
    """[N] expert ids -> [N] arrival rank within each expert (sort-based)."""
    n = e_flat.shape[0]
    order = jnp.argsort(e_flat, stable=True)
    sorted_e = jnp.take(e_flat, order)
    first = jnp.searchsorted(sorted_e, sorted_e, side="left")
    rank_sorted = jnp.arange(n) - first
    return jnp.zeros((n,), jnp.int32).at[order].set(rank_sorted.astype(jnp.int32))


def _moe_local(
    x: jnp.ndarray,            # [T, D] local tokens
    router_w: jnp.ndarray,     # [D, E] replicated
    w_gate: jnp.ndarray,       # [E_loc, D, F]
    w_in: jnp.ndarray,
    w_out: jnp.ndarray,        # [E_loc, F, D]
    *,
    e0,                        # first local expert id (traced or 0)
    n_experts: int,
    top_k: int,
    capacity: int,
):
    t, d = x.shape
    e_loc = w_gate.shape[0]

    # router matmul in the token dtype (a f32 upcast of x would materialise
    # a [T, D] copy — 940 MB/device at kimi scale); only the [T, E] logits
    # are upcast for a stable softmax.
    logits = (x @ router_w.astype(x.dtype)).astype(jnp.float32)  # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, top_k)                # [T, k]
    top_p = (top_p / jnp.sum(top_p, axis=-1, keepdims=True)).astype(x.dtype)

    pos = _positions_within_expert(top_e.reshape(-1)).reshape(t, top_k)
    keep = pos < capacity

    # ---- dispatch: scatter tokens into [E_loc, C, D], one slot at a time
    buf = jnp.zeros((e_loc, capacity, d), x.dtype)

    def dispatch(slot, buf):
        e = top_e[:, slot] - e0
        ok = keep[:, slot] & (e >= 0) & (e < e_loc)
        upd = jnp.where(ok[:, None], x, 0)
        return buf.at[
            jnp.clip(e, 0, e_loc - 1), jnp.clip(pos[:, slot], 0, capacity - 1)
        ].add(upd)

    buf = jax.lax.fori_loop(0, top_k, dispatch, buf)

    # ---- expert FFN (SwiGLU), batched over local experts
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w_gate)) * jnp.einsum(
        "ecd,edf->ecf", buf, w_in
    )
    out_buf = jnp.einsum("ecf,efd->ecd", h, w_out)            # [E_loc, C, D]

    # ---- combine: gather each slot's expert output back to its token
    def combine(slot, acc):
        e = top_e[:, slot] - e0
        ok = keep[:, slot] & (e >= 0) & (e < e_loc)
        rows = out_buf[
            jnp.clip(e, 0, e_loc - 1), jnp.clip(pos[:, slot], 0, capacity - 1)
        ]
        return acc + jnp.where(ok[:, None], rows * top_p[:, slot][:, None], 0)

    out = jax.lax.fori_loop(0, top_k, combine, jnp.zeros_like(x))

    # Switch-style load-balance aux loss (local share)
    me = jnp.mean(probs, axis=0)                              # [E]
    ce = jnp.mean(
        (jax.nn.one_hot(top_e[:, 0], n_experts, dtype=jnp.float32)), axis=0
    )
    aux = n_experts * jnp.sum(me * ce)
    return out, aux


def moe_apply(
    params: Dict,
    x: jnp.ndarray,            # [B, S, D] or [T, D]
    *,
    n_experts: int,
    top_k: int,
    capacity_factor: float = 1.25,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (y, aux_loss). Shards over "experts" rules if a mesh is up."""
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    t = x2.shape[0]

    mesh = current_mesh()
    exp_axes = mesh_axis_names("experts")
    batch_axes = mesh_axis_names("batch")

    if mesh is None or not exp_axes:
        cap = moe_capacity(t, n_experts, top_k, capacity_factor)
        y, aux = _moe_local(
            x2, params["router"], params["w_gate"], params["w_in"],
            params["w_out"], e0=0, n_experts=n_experts, top_k=top_k,
            capacity=cap,
        )
        return y.reshape(shape), aux

    b_sh = 1
    for a in batch_axes:
        b_sh *= mesh.shape[a]
    e_sh = 1
    for a in exp_axes:
        e_sh *= mesh.shape[a]
    t_loc = t // b_sh
    e_loc = n_experts // e_sh
    cap = moe_capacity(t_loc, n_experts, top_k, capacity_factor)

    tok_spec = P(batch_axes or None, None)
    ew_spec = P(exp_axes, None, None)

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(tok_spec, P(None, None), ew_spec, ew_spec, ew_spec),
        out_specs=(tok_spec, P()),
        check_vma=False,
    )
    def _blk(xt, rw, wg, wi, wo):
        lin = jnp.int32(0)
        for a in exp_axes:
            lin = lin * mesh.shape[a] + jax.lax.axis_index(a)
        y, aux = _moe_local(
            xt, rw, wg, wi, wo,
            e0=lin * e_loc, n_experts=n_experts, top_k=top_k, capacity=cap,
        )
        y = jax.lax.psum(y, exp_axes)
        aux = jax.lax.psum(aux, exp_axes) / e_sh
        if batch_axes:
            aux = jax.lax.pmean(aux, batch_axes)
        return y, aux

    y, aux = _blk(
        x2, params["router"], params["w_gate"], params["w_in"], params["w_out"]
    )
    return y.reshape(shape), aux
