"""Compile rehearsal for one TPU v5e chip, run here without one.

Every Pallas kernel of the served path, and every candidate the planner
can race for the paper's CT cell (n = 10^6 records of 1,536 B, so
W = 384 words; Sparse-PIR θ = 0.25 and Chor at batch 8), is compiled
for a described v5e chip. Nothing runs: the TPU compiler only has to
accept the program, which is what interpret-mode tests cannot show
(block tiling, scalar-memory and VMEM limits, unsupported lowerings).
Each compile must contain the kernel (``tpu_custom_call``).

The topology is described inside a fixture, so only the worker that
runs this file loads the TPU compiler, and the tests skip where it
cannot be described. JAX's persistent compilation cache is switched off
around them: a compile for a described chip cannot be read back.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.db import RecordStore
from repro.kernels import backend as kb
from repro.kernels import ops
from repro.kernels.fused import (
    fused_block_w,
    fused_gather_fold,
    fused_multi_gather_fold,
)
from repro.kernels.gather_xor import gather_xor
from repro.kernels.parity_matmul import parity_matmul
from repro.kernels.scatter import scatter_rows
from repro.kernels.xor_fold import xor_fold

N, W = 10**6, 384      # the CT store: 10^6 records of 1,536 B
M = ops.sparse_index_budget(N, 0.25)
N_VMEM = 65536         # the largest store whose 128-word slab fits VMEM
V5E_HBM = 16 * 10**9   # one v5e's device memory, as the planner reads it


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


KERNELS = {
    "xor_fold": (
        lambda db, m: xor_fold(db, m),
        [((N, W), jnp.uint32), ((8, N), jnp.uint8)],
    ),
    "parity_matmul": (
        lambda m, planes: parity_matmul(m, planes),
        [((128, N_VMEM), jnp.uint8), ((N_VMEM, 32 * W), jnp.float32)],
    ),
    "gather_xor": (
        lambda db, idx: gather_xor(db, idx),
        [((N, W), jnp.uint32), ((8, M), jnp.int32)],
    ),
    "fused_gather_fold": (
        lambda db, idx: fused_gather_fold(db, idx),
        [((N_VMEM, W), jnp.uint32),
         ((8, ops.sparse_index_budget(N_VMEM, 0.25)), jnp.int32)],
    ),
    "fused_multi_gather_fold": (
        lambda db, idx, off: fused_multi_gather_fold(db, idx, off, k_max=4),
        [((N_VMEM, W), jnp.uint32),
         ((8, ops.sparse_index_budget(N_VMEM, 0.25)), jnp.int32),
         ((3,), jnp.int32)],
    ),
    "scatter_rows": (
        lambda db, rows, vals: scatter_rows(db, rows, vals),
        [((N, W), jnp.uint32), ((64,), jnp.int32), ((64, W), jnp.uint32)],
    ),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = KERNELS[name]
    _compile(fn, one_chip, *shapes)


def test_fused_slab_fits_v5e_budget():
    """The planner only proposes the fused kernels the compile above
    proves: on a v5e the slab budget is a quarter of 128 MiB."""
    assert fused_block_w(N_VMEM, W, budget_bytes=32 << 20) == 128
    assert fused_block_w(N, W, budget_bytes=32 << 20) == 0


CELLS = {
    # scheme -> (θ, the candidate labels the planner races at CT scale)
    "chor": (None, ["fold/pallas"]),
    "sparse": (0.25, [
        f"sparse_pair/pallas+block_w={bw}+grid_order={go}"
        for bw in (W, 128) for go in ("qwm", "wqm")
    ]),
}


@pytest.mark.parametrize("scheme", sorted(CELLS))
def test_ct_cell_candidates_compile_for_v5e(one_chip, scheme):
    """Every candidate the planner races for the CT cell at batch 8 is a
    kernel the v5e compiler accepts — and the parity path, whose float32
    bitplanes (49 GB) cannot fit the chip, is not among them."""
    theta, labels = CELLS[scheme]
    store = RecordStore(
        packed=jax.ShapeDtypeStruct((N, W), jnp.uint32), record_bits=W * 32
    )
    planner = kb.KernelPlanner(
        store, backend="pallas", memory_limit_bytes=V5E_HBM
    )
    cell = kb.TuneCell(
        scheme=scheme, bucket=8, impl="pallas", theta=theta, n_eff=N,
        m_budget=M if theta is not None else None,
    )
    cands = planner._candidates(cell)
    assert [c.label for c in cands] == labels
    for c in cands:
        run = kb._path_answer_fn(
            c.path, c.impl, cell.m_budget, False, dict(c.blocks)
        )
        _compile(run, one_chip, ((N, W), jnp.uint32), ((8, N), jnp.uint8))
