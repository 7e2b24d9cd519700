import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# the 512 devices are forced CPU devices: never reach for an accelerator
os.environ["JAX_PLATFORMS"] = "cpu"

# --- everything above runs before ANY other import (jax locks device count
# on first init; smoke tests / benches must keep seeing 1 device, so this
# module is only ever imported by the dry-run entrypoint itself). ---

"""Multi-pod dry-run driver (brief deliverable e).

For every (architecture × input shape × mesh) cell:
    jax.jit(step, in_shardings=…).lower(*ShapeDtypeStructs).compile()
then record memory_analysis(), cost_analysis(), and the collective bytes
parsed from the optimized (post-SPMD) HLO into results/dryrun/*.json —
EXPERIMENTS.md §Dry-run/§Roofline are generated from these artifacts.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch all --mesh both
    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma2-2b \
        --shape decode_32k --mesh single
"""

import argparse
import json
import re
import time
import traceback

import jax

from repro.configs import get_arch, list_archs
from repro.dist.sharding import DEFAULT_RULES, MULTIPOD_RULES, mesh_rules
from repro.launch.cells import build_cell_sanitized as build_cell
from repro.launch.cells import rules_for_cell
from repro.launch.hlo_cost import analyze_hlo
from repro.launch.mesh import make_production_mesh

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")

COLLECTIVE_OPS = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")


def _shape_bytes(type_str: str) -> int:
    """Total bytes of an HLO type string, incl. tuple types."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def parse_collectives(hlo_text: str) -> dict:
    """Sum result-shape bytes of every collective op in the optimized HLO.

    Methodology (EXPERIMENTS.md §Roofline): the result shape of all-reduce /
    all-to-all / collective-permute equals the per-device payload; for
    all-gather it is the post-gather (received) bytes; for reduce-scatter we
    count the (larger) operand side via the result×group_size ≈ operand.
    This is the 'operand sizes summed' estimate the brief asks for, counted
    once per device.
    """
    out = {op: 0 for op in COLLECTIVE_OPS}
    counts = {op: 0 for op in COLLECTIVE_OPS}
    for line in hlo_text.splitlines():
        s = line.strip()
        for op in COLLECTIVE_OPS:
            # match "= TYPE op-name(" and fused variants like all-reduce-start
            if f" {op}(" in s or f" {op}-start(" in s:
                lhs = s.split("=", 1)
                if len(lhs) != 2:
                    continue
                rhs = lhs[1]
                type_part = rhs.strip().split(op)[0]
                out[op] += _shape_bytes(type_part)
                counts[op] += 1
                break
    return {
        "bytes": out,
        "counts": counts,
        "total_bytes": float(sum(out.values())),
    }


def run_cell(arch_id: str, sp, multi_pod: bool, out_dir: str, force=False,
             tag_suffix: str = ""):
    mesh_name = "multipod_2x16x16" if multi_pod else "pod_16x16"
    tag = f"{arch_id}__{sp.name}__{mesh_name}{tag_suffix}"
    path = os.path.join(out_dir, tag + ".json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            rec = json.load(f)
        if rec.get("ok"):  # failures are always retried (they are bugs)
            print(f"[cached] {tag}: ok={rec.get('ok')}")
            return rec

    rec = {
        "arch": arch_id, "shape": sp.name, "kind": sp.kind, "mesh": mesh_name,
        "chips": 512 if multi_pod else 256, "ok": False,
    }
    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        base = MULTIPOD_RULES if multi_pod else DEFAULT_RULES
        rules = dict(base, **rules_for_cell(sp, multi_pod=multi_pod))
        with mesh_rules(mesh, rules):
            cell = build_cell(arch_id, sp)
            if cell.skip_reason:
                rec.update(ok="skipped", skip_reason=cell.skip_reason)
                _write(path, rec)
                print(f"[skip]   {tag}: {cell.skip_reason}")
                return rec

            jitted = jax.jit(
                cell.fn,
                in_shardings=cell.in_shardings,
                donate_argnums=cell.donate_argnums,
            )
            lowered = jitted.lower(*cell.args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis()
            if isinstance(cost, (list, tuple)):  # newer jax: per-partition
                cost = cost[0] if cost else {}
            hlo = compiled.as_text()
            coll = parse_collectives(hlo)          # flat (loop-unaware) view
            trip_true = analyze_hlo(hlo)           # loop-aware per-device cost

            mem_rec = {}
            for f in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "alias_size_in_bytes",
                      "generated_code_size_in_bytes"):
                v = getattr(mem, f, None)
                if v is not None:
                    mem_rec[f] = int(v)
            # bytes resident per device during the step
            live = (
                mem_rec.get("argument_size_in_bytes", 0)
                + mem_rec.get("output_size_in_bytes", 0)
                + mem_rec.get("temp_size_in_bytes", 0)
                - mem_rec.get("alias_size_in_bytes", 0)
            )
            rec.update(
                ok=True,
                lower_s=round(t_lower, 2),
                compile_s=round(t_compile, 2),
                # loop-aware per-device numbers (see hlo_cost.py): XLA's own
                # cost_analysis counts while bodies once, so scanned layers
                # and their per-layer collectives would be ~L× undercounted
                flops=trip_true.flops,
                bytes_accessed=trip_true.bytes,
                collectives={
                    "bytes": trip_true.coll_bytes,
                    "counts": trip_true.coll_counts,
                    "total_bytes": trip_true.total_collective_bytes,
                },
                xla_raw={
                    "flops": float(cost.get("flops", 0.0)) if cost else 0.0,
                    "bytes_accessed": float(cost.get("bytes accessed", 0.0))
                    if cost else 0.0,
                    "collectives_flat": coll,
                },
                memory=mem_rec,
                bytes_per_device=int(live),
                model_flops=cell.model_flops,
            )
            print(
                f"[ok]     {tag}: compile={t_compile:.1f}s "
                f"mem/dev={live/2**30:.2f}GiB flops/dev={rec['flops']:.3g} "
                f"coll/dev={trip_true.total_collective_bytes:.3g}B"
            )
    except Exception as e:  # record the failure — dry-run bugs are bugs
        rec.update(ok=False, error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
        print(f"[FAIL]   {tag}: {type(e).__name__}: {e}")
    _write(path, rec)
    return rec


def _write(path, rec):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)


def iter_cells(arch_filter="all", shape_filter=None):
    for arch_id in list_archs():
        if arch_filter not in ("all", arch_id):
            continue
        mod = get_arch(arch_id)
        for sp in mod.SHAPES:
            if shape_filter and sp.name != shape_filter:
                continue
            yield arch_id, sp


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default=os.path.normpath(RESULTS_DIR))
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--tag", default="", help="suffix for perf-iteration runs")
    args = ap.parse_args()

    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    n_ok = n_fail = n_skip = 0
    for arch_id, sp in iter_cells(args.arch, args.shape):
        for multi_pod in meshes:
            rec = run_cell(arch_id, sp, multi_pod, args.out, force=args.force,
                           tag_suffix=args.tag)
            if rec["ok"] == "skipped":
                n_skip += 1
            elif rec["ok"]:
                n_ok += 1
            else:
                n_fail += 1
    print(f"\ndry-run complete: {n_ok} ok, {n_skip} skipped, {n_fail} FAILED")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
