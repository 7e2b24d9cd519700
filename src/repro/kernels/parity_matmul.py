"""Pallas TPU kernel: batched parity matmul — Chor's XOR fold on the MXU.

GF(2) identity: the XOR fold of selected records equals the *parity* of an
integer matmul over {0,1} operands:

    out_bits = (mask @ bitplanes) mod 2          mask: [q, n], planes: [n, B]

Products are 0/1 so bf16 inputs are exact; accumulation is fp32 (exact for
n < 2^24 summands). This converts the paper's "touch every record" server
burden into a dense GEMM at MXU-native arithmetic intensity — the batched-
query form is our paper-faithful Chor baseline on TPU (DESIGN.md §Hardware
adaptation).

Grid: (q_blocks, b_blocks, n_blocks), n innermost; fp32 accumulator lives
in a VMEM scratch buffer, the mod-2 epilogue runs on the last n step so
only uint8 bits are written back to HBM (8× less write traffic than f32).

Default blocks (BQ=BB=128, BN=512) are MXU-aligned (multiples of 128);
VMEM: a 128·512·2 + b 512·128·2 + acc 128·128·4 ≈ 0.3 MiB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

__all__ = ["parity_matmul"]

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_B = 128
DEFAULT_BLOCK_N = 512


def _kernel(mask_ref, planes_ref, out_ref, acc_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        mask_ref[...].astype(jnp.float32),
        planes_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == pl.num_programs(2) - 1)
    def _epilogue():
        # parity in int32, then narrow: the TPU compiler has no direct
        # float32 -> uint8 cast
        bits = acc_ref[...].astype(jnp.int32) & 1
        out_ref[...] = bits.astype(jnp.uint8)


@functools.partial(
    jax.jit,
    static_argnames=("block_q", "block_b", "block_n", "interpret"),
)
def parity_matmul(
    mask: jnp.ndarray,
    planes: jnp.ndarray,
    *,
    block_q: int = DEFAULT_BLOCK_Q,
    block_b: int = DEFAULT_BLOCK_B,
    block_n: int = DEFAULT_BLOCK_N,
    interpret: bool = False,
) -> jnp.ndarray:
    """mask: [q, n] {0,1}; planes: [n, B] {0,1} -> [q, B] uint8 bits.

    Inputs may be any integer/float dtype holding 0/1; they are fed to the
    MXU in bf16 (exact for 0/1) with fp32 accumulation.
    """
    q, n = mask.shape
    n2, b = planes.shape
    assert n == n2, (mask.shape, planes.shape)

    bq, bb, bn = min(block_q, q), min(block_b, b), min(block_n, n)
    qp, bp, np_ = (-q % bq), (-b % bb), (-n % bn)
    mask_p = jnp.pad(mask.astype(jnp.bfloat16), ((0, qp), (0, np_)))
    planes_p = jnp.pad(planes.astype(jnp.bfloat16), ((0, np_), (0, bp)))

    grid = ((q + qp) // bq, (b + bp) // bb, (n + np_) // bn)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, bn), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn, bb), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bq, bb), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((q + qp, b + bp), jnp.uint8),
        scratch_shapes=[pltpu.VMEM((bq, bb), jnp.float32)],
        interpret=interpret,
    )(mask_p, planes_p)
    return out[:q, :b]
