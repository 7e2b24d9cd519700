"""GCN (Kipf & Welling, arXiv:1609.02907) with segment-sum message passing.

JAX sparse is BCOO-only, so message passing is built from first principles:
gather source features along an edge list, scale by the symmetric-norm edge
weight 1/√(deg_s·deg_d), and ``jax.ops.segment_sum`` into destinations —
this IS part of the system per the brief.

Distribution (full-batch, ogb_products-scale): nodes AND edges sharded over
("data","model") flattened. Hidden width is small (16), so each layer
all-gathers the [N, H] hidden matrix, aggregates its local edge shard into
partial [N, H] sums, and reduce-scatters (psum_scatter) back to node shards
— the classic full-batch GNN DP schedule. Single-device falls back to plain
segment_sum (same numerics; tests assert equality on a host mesh).

Minibatch (GraphSAGE-style fanout sampling) consumes the fixed-shape padded
subgraphs produced by repro.data.pipeline.NeighborSampler.
"""

from __future__ import annotations

from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.configs.base import GNNConfig
from repro.dist.sharding import current_mesh, mesh_axis_names
from repro.models import layers as L

__all__ = [
    "gcn_init",
    "gcn_apply",
    "node_xent",
    "batched_graph_apply",
    "graph_xent",
    "sym_norm_weights",
]


def sym_norm_weights(src, dst, n_nodes):
    """Symmetric normalisation 1/√(deg_s·deg_d) (cfg.norm == "sym")."""
    ones = jnp.ones_like(src, jnp.float32)
    deg = jax.ops.segment_sum(ones, dst, num_segments=n_nodes) + jax.ops.segment_sum(
        ones, src, num_segments=n_nodes
    )
    deg = jnp.maximum(deg, 1.0) * 0.5
    return jax.lax.rsqrt(jnp.take(deg, src) * jnp.take(deg, dst))


def gcn_init(key, cfg: GNNConfig, d_feat: int) -> Dict:
    dims = [d_feat] + [cfg.d_hidden] * (cfg.n_layers - 1) + [cfg.n_classes]
    keys = jax.random.split(key, len(dims) - 1)
    return {
        f"w{i}": L.dense_init(keys[i], dims[i], dims[i + 1])
        for i in range(len(dims) - 1)
    }


def _aggregate(h, src, dst, w, n_nodes, mean_deg=None):
    """Σ_{(s→d)} w·h[s] into d. Sharded when a mesh context is present."""
    mesh = current_mesh()
    node_axes = mesh_axis_names("nodes")
    if mesh is None or not node_axes:
        msg = jnp.take(h, src, axis=0) * w[:, None]
        agg = jax.ops.segment_sum(msg, dst, num_segments=n_nodes)
        if mean_deg is not None:
            agg = agg / mean_deg[:, None]
        return agg

    shards = 1
    for a in node_axes:
        shards *= mesh.shape[a]
    edge_axes = mesh_axis_names("edges") or node_axes

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(
            P(node_axes, None),   # h rows sharded
            P(edge_axes),         # edges sharded
            P(edge_axes),
            P(edge_axes),
            P(node_axes) if mean_deg is not None else P(),
        ),
        out_specs=P(node_axes, None),
        check_vma=False,
    )
    def _agg(h_loc, src_loc, dst_loc, w_loc, md_loc):
        h_full = jax.lax.all_gather(h_loc, node_axes, axis=0, tiled=True)
        msg = jnp.take(h_full, src_loc, axis=0) * w_loc[:, None]
        partial_sum = jax.ops.segment_sum(msg, dst_loc, num_segments=n_nodes)
        out = jax.lax.psum_scatter(
            partial_sum, node_axes, scatter_dimension=0, tiled=True
        )
        if mean_deg is not None:
            out = out / md_loc[:, None]
        return out

    md = mean_deg if mean_deg is not None else jnp.zeros((), jnp.float32)
    return _agg(h, src, dst, w, md)


def gcn_apply(
    params: Dict,
    cfg: GNNConfig,
    feats: jnp.ndarray,      # [N, F]
    src: jnp.ndarray,        # [E] int32
    dst: jnp.ndarray,        # [E] int32
    edge_w: jnp.ndarray,     # [E] f32 (sym-norm weights; 0 for padding)
    mean_deg: jnp.ndarray | None = None,  # [N] (aggregator="mean"); pipeline-
                                          # precomputed so no extra scatter
) -> jnp.ndarray:
    """Returns node logits [N, n_classes]."""
    n = feats.shape[0]
    if cfg.aggregator == "mean" and mean_deg is None:
        deg = jax.ops.segment_sum(
            (edge_w > 0).astype(jnp.float32), dst, num_segments=n
        )
        mean_deg = jnp.maximum(deg, 1.0)

    h = feats
    for i in range(cfg.n_layers):
        h = L.dense(params[f"w{i}"], h)           # transform-then-aggregate
        h = _aggregate(h, src, dst, edge_w, n, mean_deg)
        if i < cfg.n_layers - 1:
            h = jax.nn.relu(h)
    return h


def node_xent(logits, labels, mask):
    """Cross-entropy on labelled nodes. labels: [N] int32; mask: [N] f32."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0]
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


# --------------------------------------------------------------- molecule
def batched_graph_apply(
    params: Dict,
    cfg: GNNConfig,
    feats: jnp.ndarray,      # [B, Nn, F]
    src: jnp.ndarray,        # [B, Ne]
    dst: jnp.ndarray,        # [B, Ne]
    edge_w: jnp.ndarray,     # [B, Ne]
) -> jnp.ndarray:
    """Graph classification over batched small graphs -> [B, n_classes]."""

    def one(f, s, d, w):
        logits = gcn_apply(params, cfg, f, s, d, w)
        return jnp.mean(logits, axis=0)  # mean-pool readout

    return jax.vmap(one)(feats, src, dst, edge_w)


def graph_xent(logits, labels):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))
