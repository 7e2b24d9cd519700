"""Cell builders: (architecture × input-shape × mesh) → a lowerable program.

A Cell packages the jit-able step function, ShapeDtypeStruct inputs (no
device allocation — the dry-run pattern), input shardings, and the analytic
MODEL_FLOPS for the roofline's useful-compute ratio. Builders must run
inside a ``mesh_rules`` context.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_arch
from repro.configs.base import GNNConfig, LMConfig, PIRConfig, RecSysConfig, ShapeSpec
from repro.data.pipeline import NeighborSampler
from repro.dist.params import (
    generic_param_specs,
    lm_param_specs,
    tree_named_shardings,
)
from repro.dist.sharding import current_mesh, logical_to_spec
from repro.models import gnn, recsys as R, transformer as T
from repro.train.train_step import (
    default_optimizer,
    gnn_full_loss_fn,
    gnn_minibatch_loss_fn,
    gnn_molecule_loss_fn,
    lm_loss_fn,
    make_train_step,
    recsys_loss_fn,
)

__all__ = ["Cell", "build_cell", "SKIP"]

SKIP = "skip"


@dataclasses.dataclass
class Cell:
    arch: str
    shape: str
    kind: str
    fn: Optional[Callable] = None
    args: Tuple = ()
    in_shardings: Any = None
    donate_argnums: Tuple[int, ...] = ()
    model_flops: float = 0.0
    skip_reason: Optional[str] = None
    rules_override: Optional[Dict] = None


def _ns(*logical):
    mesh = current_mesh()
    return NamedSharding(mesh, logical_to_spec(*logical))


def _sanitize_shardings(shardings, args):
    """Drop per-dim sharding where the dim isn't divisible by the mesh-axis
    product (jax rejects uneven jit-argument shardings). Affects e.g.
    embed tables with dim 10/18 (can't FSDP the feature dim) and tiny
    query batches — correctness-neutral, memory noted in EXPERIMENTS.md."""
    mesh = current_mesh()

    def one(sh, arg):
        if not isinstance(sh, NamedSharding):
            return sh
        shape = arg.shape
        parts = list(sh.spec) + [None] * (len(arg.shape) - len(sh.spec))
        new = []
        for i, part in enumerate(parts):
            if part is None:
                new.append(None)
                continue
            axes = (part,) if isinstance(part, str) else part
            size = 1
            for a in axes:
                size *= mesh.shape[a]
            new.append(part if shape[i] % size == 0 else None)
        return NamedSharding(mesh, P(*new))

    return jax.tree.map(
        one, shardings, args,
        is_leaf=lambda x: isinstance(x, NamedSharding),
    )


def _sds(shape, dtype, sharding=None):
    return jax.ShapeDtypeStruct(shape, dtype)


def _pad_to(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _mesh_size() -> int:
    mesh = current_mesh()
    return math.prod(mesh.shape.values())


# --------------------------------------------------------------------------
# opt-state sharding: mirror param specs through the optimizer state tree
# --------------------------------------------------------------------------
def _state_shardings(state_shapes, param_spec_tree):
    """TrainState(params, opt_state, comp_state, step) shardings."""
    mesh = current_mesh()
    param_sh = tree_named_shardings(param_spec_tree)
    flat_specs = {
        _path(p): s
        for p, s in jax.tree_util.tree_flatten_with_path(
            param_spec_tree, is_leaf=lambda x: isinstance(x, P)
        )[0]
    }

    def opt_leaf(path, leaf):
        ps = _path(path)
        # strip optimizer-tree prefixes/suffixes to find the param path
        for prefix in ("m/", "v/", "second/"):
            if ps.startswith(prefix):
                ps = ps[len(prefix):]
                break
        suffix = None
        for sfx in ("/row", "/col", "/v"):
            if ps.endswith(sfx):
                suffix = sfx
                ps = ps[: -len(sfx)]
                break
        spec = flat_specs.get(ps)
        if spec is None:
            return NamedSharding(mesh, P(*([None] * leaf.ndim)))
        parts = list(spec)
        if suffix == "/row":
            parts = parts[:-1]
        elif suffix == "/col":
            parts = parts[:-2] + parts[-1:]
        parts = (parts + [None] * leaf.ndim)[: leaf.ndim]
        return NamedSharding(mesh, P(*parts))

    opt_sh = jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(state_shapes.opt_state),
        [
            opt_leaf(p, l)
            for p, l in jax.tree_util.tree_flatten_with_path(
                state_shapes.opt_state
            )[0]
        ],
    )
    comp_sh = param_sh if state_shapes.comp_state else {}
    from repro.train.train_step import TrainState

    return TrainState(
        params=param_sh,
        opt_state=opt_sh,
        comp_state=comp_sh,
        step=NamedSharding(mesh, P()),
    )


def _path(path) -> str:
    return "/".join(
        str(getattr(p, "key", getattr(p, "idx", getattr(p, "name", p))))
        for p in path
    )


# --------------------------------------------------------------------------
# LM cells
# --------------------------------------------------------------------------
def _lm_dtype(cfg):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def _lm_variant() -> str:
    """LM-train perf-iteration selector (EXPERIMENTS.md §Perf):
    baseline    : Megatron TP(model) × FSDP(data) × SP residuals
    fsdp        : pure ZeRO-3 — batch over every axis, no tensor
                  parallelism (dense models: kills the per-layer TP
                  activation psums/gathers)
    fsdp_dots   : + remat policy saves dot outputs (less recompute)"""
    return _os.environ.get("REPRO_LM_VARIANT", "baseline")


def _lm_train_cell(arch, cfg: LMConfig, sp: ShapeSpec) -> Cell:
    p = sp.p()
    b, s = p["global_batch"], p["seq_len"]
    variant = _lm_variant()
    if variant == "fsdp_dots":
        cfg = dataclasses.replace(cfg, remat_policy="dots")
    mb = 4 if variant == "mb4" else 1
    opt = default_optimizer(cfg)
    init_fn, step_fn = make_train_step(lm_loss_fn(cfg), opt, microbatches=mb)

    state_shapes = jax.eval_shape(
        lambda k: init_fn(T.init_lm(k, cfg)), jax.random.key(0)
    )
    specs = lm_param_specs(state_shapes.params)
    state_sh = _state_shardings(state_shapes, specs)
    batch_sh = {"tokens": _ns("batch", None)}
    tokens = _sds((b, s), jnp.int32)

    toks_per_step = b * s
    return Cell(
        arch=arch, shape=sp.name, kind=sp.kind,
        fn=step_fn,
        args=(state_shapes, {"tokens": tokens}),
        in_shardings=(state_sh, batch_sh),
        donate_argnums=(0,),
        model_flops=6.0 * cfg.params_active * toks_per_step,
    )


def _lm_prefill_cell(arch, cfg: LMConfig, sp: ShapeSpec) -> Cell:
    p = sp.p()
    b, s = p["global_batch"], p["seq_len"]
    params_shapes = jax.eval_shape(
        lambda k: T.init_lm(k, cfg), jax.random.key(0)
    )
    specs = lm_param_specs(params_shapes)
    fn = partial(_prefill_fn, cfg=cfg, max_len=s)
    return Cell(
        arch=arch, shape=sp.name, kind=sp.kind,
        fn=fn,
        args=(params_shapes, _sds((b, s), jnp.int32)),
        in_shardings=(tree_named_shardings(specs), _ns("batch", None)),
        model_flops=2.0 * cfg.params_active * b * s
        + 4.0 * b * s * s * cfg.n_heads * cfg.head_dim / 2,  # causal attn
    )


def _prefill_fn(params, tokens, *, cfg, max_len):
    return T.prefill(params, cfg, tokens, max_len)


def _decode_fn(params, cache, token, pos, *, cfg):
    return T.decode_step(params, cfg, cache, token, pos)


def _lm_decode_cell(arch, cfg: LMConfig, sp: ShapeSpec, long: bool) -> Cell:
    p = sp.p()
    b, s = p["global_batch"], p["seq_len"]
    if long and cfg.full_attention_only:
        return Cell(
            arch=arch, shape=sp.name, kind=sp.kind,
            skip_reason=(
                "pure full-attention arch: 524k-token cell skipped per brief "
                "(DESIGN.md §4 — sub-quadratic attention required)"
            ),
        )
    params_shapes = jax.eval_shape(lambda k: T.init_lm(k, cfg), jax.random.key(0))
    specs = lm_param_specs(params_shapes)
    dt = _lm_dtype(cfg)
    cache = T.KVCache(
        k=_sds((cfg.n_layers, b, s, cfg.n_kv_heads, cfg.head_dim), dt),
        v=_sds((cfg.n_layers, b, s, cfg.n_kv_heads, cfg.head_dim), dt),
    )
    cache_sh = T.KVCache(
        k=_ns(None, "batch", "kv_seq", None, None),
        v=_ns(None, "batch", "kv_seq", None, None),
    )
    token = _sds((b, 1), jnp.int32)
    pos = _sds((), jnp.int32)

    attn_flops = 4.0 * b * s * cfg.n_heads * cfg.head_dim
    return Cell(
        arch=arch, shape=sp.name, kind=sp.kind,
        fn=partial(_decode_fn, cfg=cfg),
        args=(params_shapes, cache, token, pos),
        in_shardings=(tree_named_shardings(specs), cache_sh, _ns("batch", None), _ns()),
        donate_argnums=(1,),
        model_flops=2.0 * cfg.params_active * b + attn_flops,
    )


# --------------------------------------------------------------------------
# GNN cells
# --------------------------------------------------------------------------
def _gnn_state(cfg: GNNConfig, d_feat: int, loss_fn):
    opt = default_optimizer(cfg)
    init_fn, step_fn = make_train_step(loss_fn, opt)
    state_shapes = jax.eval_shape(
        lambda k: init_fn(gnn.gcn_init(k, cfg, d_feat)), jax.random.key(0)
    )
    mesh = current_mesh()
    state_sh = jax.tree.map(
        lambda l: NamedSharding(mesh, P(*([None] * getattr(l, "ndim", 0)))),
        state_shapes,
    )
    return step_fn, state_shapes, state_sh


def _gnn_flops(n, e, f, h, c, train=True):
    fwd = 2.0 * (n * f * h + e * h + n * h * c + e * c)
    return fwd * (3.0 if train else 1.0)


def _gnn_full_cell(arch, cfg: GNNConfig, sp: ShapeSpec) -> Cell:
    p = sp.p()
    shards = _mesh_size()
    n = _pad_to(p["n_nodes"], shards)
    e = _pad_to(p["n_edges"], shards)
    f, c = p["d_feat"], p["n_classes"]
    cfg = dataclasses.replace(cfg, n_classes=c)
    step_fn, state_shapes, state_sh = _gnn_state(cfg, f, gnn_full_loss_fn(cfg))

    batch = {
        "feats": _sds((n, f), jnp.float32),
        "src": _sds((e,), jnp.int32),
        "dst": _sds((e,), jnp.int32),
        "edge_w": _sds((e,), jnp.float32),
        "labels": _sds((n,), jnp.int32),
        "label_mask": _sds((n,), jnp.float32),
        "mean_deg": _sds((n,), jnp.float32),
    }
    batch_sh = {
        "feats": _ns("nodes", None),
        "src": _ns("edges"),
        "dst": _ns("edges"),
        "edge_w": _ns("edges"),
        "labels": _ns("nodes"),
        "label_mask": _ns("nodes"),
        "mean_deg": _ns("nodes"),
    }
    return Cell(
        arch=arch, shape=sp.name, kind=sp.kind,
        fn=step_fn, args=(state_shapes, batch),
        in_shardings=(state_sh, batch_sh),
        donate_argnums=(0,),
        model_flops=_gnn_flops(n, e, f, cfg.d_hidden, c),
    )


def _gnn_minibatch_cell(arch, cfg: GNNConfig, sp: ShapeSpec) -> Cell:
    p = sp.p()
    b, f1, f2 = p["batch_nodes"], p["fanout1"], p["fanout2"]
    n_sub, e_sub = NeighborSampler.subgraph_shapes(b, f1, f2, p["d_feat"])
    f, c = p["d_feat"], p["n_classes"]
    cfg = dataclasses.replace(cfg, n_classes=c)
    step_fn, state_shapes, state_sh = _gnn_state(cfg, f, gnn_minibatch_loss_fn(cfg))

    batch = {
        "feats": _sds((n_sub, f), jnp.float32),
        "src": _sds((e_sub,), jnp.int32),
        "dst": _sds((e_sub,), jnp.int32),
        "edge_w": _sds((e_sub,), jnp.float32),
        "labels": _sds((n_sub,), jnp.int32),
        "seed_mask": _sds((n_sub,), jnp.float32),
    }
    batch_sh = {
        "feats": _ns("nodes", None),
        "src": _ns("edges"),
        "dst": _ns("edges"),
        "edge_w": _ns("edges"),
        "labels": _ns("nodes"),
        "seed_mask": _ns("nodes"),
    }
    return Cell(
        arch=arch, shape=sp.name, kind=sp.kind,
        fn=step_fn, args=(state_shapes, batch),
        in_shardings=(state_sh, batch_sh),
        donate_argnums=(0,),
        model_flops=_gnn_flops(n_sub, e_sub, f, cfg.d_hidden, c),
    )


def _gnn_molecule_cell(arch, cfg: GNNConfig, sp: ShapeSpec) -> Cell:
    p = sp.p()
    b, nn, ne = p["batch"], p["n_nodes"], p["n_edges"]
    f, c = p["d_feat"], p["n_classes"]
    cfg = dataclasses.replace(cfg, n_classes=c)
    step_fn, state_shapes, state_sh = _gnn_state(cfg, f, gnn_molecule_loss_fn(cfg))

    batch = {
        "feats": _sds((b, nn, f), jnp.float32),
        "src": _sds((b, ne), jnp.int32),
        "dst": _sds((b, ne), jnp.int32),
        "edge_w": _sds((b, ne), jnp.float32),
        "labels": _sds((b,), jnp.int32),
    }
    batch_sh = {
        "feats": _ns("batch", None, None),
        "src": _ns("batch", None),
        "dst": _ns("batch", None),
        "edge_w": _ns("batch", None),
        "labels": _ns("batch"),
    }
    return Cell(
        arch=arch, shape=sp.name, kind=sp.kind,
        fn=step_fn, args=(state_shapes, batch),
        in_shardings=(state_sh, batch_sh),
        donate_argnums=(0,),
        model_flops=b * _gnn_flops(nn, ne, f, cfg.d_hidden, c),
    )


# --------------------------------------------------------------------------
# RecSys cells
# --------------------------------------------------------------------------
def _recsys_init(cfg: RecSysConfig):
    return {
        "fm": R.fm_init, "dlrm": R.dlrm_init,
        "dien": R.dien_init, "bert4rec": R.bert4rec_init,
    }[cfg.model]


def _recsys_batch_sds(cfg: RecSysConfig, b: int):
    if cfg.model == "fm":
        batch = {"ids": _sds((b, cfg.n_sparse), jnp.int32),
                 "label": _sds((b,), jnp.float32)}
        sh = {"ids": _ns("batch", None), "label": _ns("batch")}
    elif cfg.model == "dlrm":
        batch = {
            "ids": _sds((b, cfg.n_sparse), jnp.int32),
            "dense": _sds((b, cfg.n_dense), jnp.float32),
            "label": _sds((b,), jnp.float32),
        }
        sh = {"ids": _ns("batch", None), "dense": _ns("batch", None),
              "label": _ns("batch")}
    elif cfg.model == "dien":
        batch = {
            "hist": _sds((b, cfg.seq_len), jnp.int32),
            "target": _sds((b,), jnp.int32),
            "label": _sds((b,), jnp.float32),
        }
        sh = {"hist": _ns("batch", None), "target": _ns("batch"),
              "label": _ns("batch")}
    else:  # bert4rec
        batch = {
            "seq": _sds((b, cfg.seq_len), jnp.int32),
            "labels": _sds((b, cfg.seq_len), jnp.int32),
            "mask": _sds((b, cfg.seq_len), jnp.int32),
        }
        sh = {"seq": _ns("batch", None), "labels": _ns("batch", None),
              "mask": _ns("batch", None)}
    return batch, sh


def _recsys_flops(cfg: RecSysConfig, b: int, train: bool) -> float:
    mult = 3.0 if train else 1.0
    if cfg.model == "fm":
        return mult * 2.0 * b * cfg.n_sparse * cfg.embed_dim * 2
    if cfg.model == "dlrm":
        dims = (cfg.n_dense,) + cfg.bot_mlp
        bot = sum(2 * a * bb for a, bb in zip(dims, dims[1:]))
        nf = cfg.n_sparse + 1
        inter = 2 * nf * nf * cfg.embed_dim
        tdims = (cfg.bot_mlp[-1] + nf * (nf - 1) // 2,) + cfg.top_mlp
        top = sum(2 * a * bb for a, bb in zip(tdims, tdims[1:]))
        return mult * b * (bot + inter + top)
    if cfg.model == "dien":
        gru = 2 * cfg.seq_len * 3 * (cfg.embed_dim + cfg.gru_dim) * cfg.gru_dim
        augru = 2 * cfg.seq_len * 3 * (2 * cfg.gru_dim) * cfg.gru_dim
        mdims = (cfg.gru_dim + 2 * cfg.embed_dim,) + cfg.mlp_dims + (1,)
        mlp = sum(2 * a * bb for a, bb in zip(mdims, mdims[1:]))
        return mult * b * (gru + augru + mlp)
    # bert4rec
    d, s = cfg.embed_dim, cfg.seq_len
    blk = 2 * s * (4 * d * d) + 4 * s * s * d + 2 * s * (8 * d * d)
    head = 2 * s * d * (cfg.n_items + 2)
    return mult * b * (cfg.n_blocks * blk + head)


def _recsys_train_cell(arch, cfg: RecSysConfig, sp: ShapeSpec) -> Cell:
    b = sp.p()["batch"]
    opt = default_optimizer(cfg)
    init_fn, step_fn = make_train_step(recsys_loss_fn(cfg), opt)
    state_shapes = jax.eval_shape(
        lambda k: init_fn(_recsys_init(cfg)(k, cfg)), jax.random.key(0)
    )
    specs = generic_param_specs(state_shapes.params)
    state_sh = _state_shardings(state_shapes, specs)
    batch, batch_sh = _recsys_batch_sds(cfg, b)
    return Cell(
        arch=arch, shape=sp.name, kind=sp.kind,
        fn=step_fn, args=(state_shapes, batch),
        in_shardings=(state_sh, batch_sh),
        donate_argnums=(0,),
        model_flops=_recsys_flops(cfg, b, train=True),
    )


def _recsys_serve_fn(params, batch, *, cfg):
    if cfg.model == "bert4rec":
        return R.bert4rec_logits(params, cfg, batch["seq"])
    score = {"fm": R.fm_score, "dlrm": R.dlrm_score, "dien": R.dien_score}[cfg.model]
    return score(params, cfg, batch)


def _recsys_serve_cell(arch, cfg: RecSysConfig, sp: ShapeSpec) -> Cell:
    b = sp.p()["batch"]
    params_shapes = jax.eval_shape(
        lambda k: _recsys_init(cfg)(k, cfg), jax.random.key(0)
    )
    specs = generic_param_specs(params_shapes)
    batch, batch_sh = _recsys_batch_sds(cfg, b)
    return Cell(
        arch=arch, shape=sp.name, kind=sp.kind,
        fn=partial(_recsys_serve_fn, cfg=cfg),
        args=(params_shapes, batch),
        in_shardings=(tree_named_shardings(specs), batch_sh),
        model_flops=_recsys_flops(cfg, b, train=False),
    )


def _recsys_retrieval_fn(params, batch, cand, *, cfg):
    uv = R.user_vector(params, cfg, batch)
    scores = R.retrieval_scores(uv, cand)
    return jax.lax.top_k(scores, 10)


def _recsys_retrieval_cell(arch, cfg: RecSysConfig, sp: ShapeSpec) -> Cell:
    from repro.dist.sharding import axis_size

    p = sp.p()
    b, nc = p["batch"], p["n_candidates"]
    nc = _pad_to(nc, max(axis_size("candidates"), 1))  # shardable pad
    params_shapes = jax.eval_shape(
        lambda k: _recsys_init(cfg)(k, cfg), jax.random.key(0)
    )
    specs = generic_param_specs(params_shapes)
    batch, batch_sh = _recsys_batch_sds(cfg, b)
    batch.pop("label", None)
    batch_sh.pop("label", None)
    cand = _sds((nc, cfg.embed_dim), jnp.float32)
    return Cell(
        arch=arch, shape=sp.name, kind=sp.kind,
        fn=partial(_recsys_retrieval_fn, cfg=cfg),
        args=(params_shapes, batch, cand),
        in_shardings=(
            tree_named_shardings(specs), batch_sh, _ns("candidates", None)
        ),
        model_flops=2.0 * b * nc * cfg.embed_dim,
    )


# --------------------------------------------------------------------------
# PIR serve cells (the paper's own workload)
#
# Variants (hillclimb log in EXPERIMENTS.md §Perf; select via
# REPRO_PIR_VARIANT, default = fully-optimized "xorbfly"):
#   baseline : paper-faithful batched Chor — queries sharded over batch
#              axes, records over "model"; f32 operands; f32 psum.
#   bf16     : feed the MXU bf16 (0/1 exact) — removes the f32 plane copy.
#   reshard  : records sharded over ALL axes, queries replicated — DB read
#              per device drops |data|×; turns the step compute-bound.
#   xorbfly  : + GF(2) all-reduce: partial parities bit-packed to uint32
#              and combined by a log2(shards)-round XOR butterfly
#              (collective bytes 32× below an int32 psum; XOR is what the
#              algebra wants — DESIGN.md §Hardware adaptation).
# --------------------------------------------------------------------------
import os as _os


def _pir_variant() -> str:
    return _os.environ.get("REPRO_PIR_VARIANT", "xorbfly")


def _pir_serve_fn_baseline(masks, planes):
    from repro.db import packing

    acc = jnp.einsum(
        "qn,nv->qv",
        masks.astype(jnp.float32),
        planes.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    bits = jnp.mod(acc, 2.0).astype(jnp.uint8)
    return packing.pack_bits(bits)


def _pir_serve_fn_bf16(masks, planes):
    from repro.db import packing

    acc = jnp.einsum(
        "qn,nv->qv", masks, planes, preferred_element_type=jnp.float32
    )
    bits = jnp.mod(acc, 2.0).astype(jnp.uint8)
    return packing.pack_bits(bits)


def _pir_serve_fn_xorbfly(masks, planes):
    """shard_map: local bf16 parity matmul → pack bits → XOR butterfly."""
    from jax import shard_map
    from repro.db import packing
    from repro.dist.sharding import current_mesh, mesh_axis_names

    mesh = current_mesh()
    rec_axes = mesh_axis_names("records")

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(None, rec_axes), P(rec_axes, None)),
        out_specs=P(None, None),
        check_vma=False,
    )
    def _f(m_loc, p_loc):
        acc = jnp.dot(m_loc, p_loc, preferred_element_type=jnp.float32)
        bits = jnp.mod(acc, 2.0).astype(jnp.uint8)
        packed = packing.pack_bits(bits)            # [q, W] uint32
        # XOR all-reduce: butterfly within each record axis
        for ax in rec_axes:
            size = mesh.shape[ax]
            k = 1
            while k < size:
                perm = [(i, i ^ k) for i in range(size)]
                packed = packed ^ jax.lax.ppermute(packed, ax, perm)
                k *= 2
        return packed

    return _f(masks, planes)


def _pir_cell(arch, cfg: PIRConfig, sp: ShapeSpec) -> Cell:
    q = sp.p()["query_batch"]
    n = cfg.n_records
    bits = cfg.record_bytes * 8
    variant = _pir_variant()
    if variant in ("reshard", "xorbfly"):
        from repro.dist.sharding import axis_size

        n = _pad_to(n, max(axis_size("records"), 1))  # shardable pad (zeros)
    masks = _sds((q, n), jnp.bfloat16)
    planes = _sds((n, bits), jnp.bfloat16)

    if variant == "baseline":
        fn, in_sh = _pir_serve_fn_baseline, (
            _ns("queries", "records"), _ns("records", None))
    elif variant == "bf16":
        fn, in_sh = _pir_serve_fn_bf16, (
            _ns("queries", "records"), _ns("records", None))
    elif variant == "reshard":
        fn, in_sh = _pir_serve_fn_bf16, (
            _ns(None, "records"), _ns("records", None))
    else:  # xorbfly
        fn, in_sh = _pir_serve_fn_xorbfly, (
            _ns(None, "records"), _ns("records", None))

    cell = Cell(
        arch=arch, shape=sp.name, kind=sp.kind,
        fn=fn,
        args=(masks, planes),
        in_shardings=in_sh,
        model_flops=2.0 * q * n * bits,
    )
    return cell


# --------------------------------------------------------------------------
# dispatch
# --------------------------------------------------------------------------
def rules_for_cell(sp: ShapeSpec, multi_pod: bool = False) -> Dict:
    """Per-cell logical-rule overrides, merged into the mesh rules by the
    driver BEFORE build_cell (shardings are resolved eagerly under them)."""
    if sp.kind == "lm_long_decode":
        # batch=1: nothing to shard on data; spread KV over data AND model
        return {"batch": None, "kv_seq": ("data", "model")}
    if sp.kind == "gnn_batched":
        # tiny graphs under vmap: aggregation must NOT take shard_map path
        return {"nodes": None, "edges": None}
    if sp.kind == "recsys_retrieval":
        return {"batch": None}  # batch=1
    if sp.kind == "pir_serve" and _pir_variant() in ("reshard", "xorbfly"):
        # records over EVERY axis: DB read per device drops |data|(·|pod|)×
        axes = ("pod", "data", "model") if multi_pod else ("data", "model")
        return {"records": axes, "queries": None}
    if sp.kind == "lm_train" and _lm_variant() in ("fsdp", "fsdp_dots"):
        # pure ZeRO-3: batch/FSDP over EVERY axis, no TP, no SP
        axes = ("pod", "data", "model") if multi_pod else ("data", "model")
        return {
            "batch": axes, "fsdp": axes, "heads": None, "kv_heads": None,
            "ff": None, "vocab": None, "seq_res": None, "experts": None,
        }
    return {}


def build_cell(arch_id: str, sp: ShapeSpec) -> Cell:
    mod = get_arch(arch_id)
    cfg = mod.CONFIG
    kind = sp.kind
    if kind == "lm_train":
        return _lm_train_cell(arch_id, cfg, sp)
    if kind == "lm_prefill":
        return _lm_prefill_cell(arch_id, cfg, sp)
    if kind == "lm_decode":
        return _lm_decode_cell(arch_id, cfg, sp, long=False)
    if kind == "lm_long_decode":
        return _lm_decode_cell(arch_id, cfg, sp, long=True)
    if kind == "gnn_full":
        return _gnn_full_cell(arch_id, cfg, sp)
    if kind == "gnn_minibatch":
        return _gnn_minibatch_cell(arch_id, cfg, sp)
    if kind == "gnn_batched":
        return _gnn_molecule_cell(arch_id, cfg, sp)
    if kind == "recsys_train":
        return _recsys_train_cell(arch_id, cfg, sp)
    if kind == "recsys_serve":
        return _recsys_serve_cell(arch_id, cfg, sp)
    if kind == "recsys_retrieval":
        return _recsys_retrieval_cell(arch_id, cfg, sp)
    if kind == "pir_serve":
        return _pir_cell(arch_id, cfg, sp)
    raise ValueError(f"unknown cell kind {kind!r}")


_DISPATCH = build_cell


def build_cell_sanitized(arch_id: str, sp: ShapeSpec) -> Cell:
    cell = _DISPATCH(arch_id, sp)
    if cell.in_shardings is not None:
        cell.in_shardings = tuple(
            _sanitize_shardings(sh, arg)
            for sh, arg in zip(cell.in_shardings, cell.args)
        )
    return cell
