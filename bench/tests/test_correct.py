"""A whole run on the CPU at a tiny size (the chip check is the entry
scripts' and is not made here): sound, it is ``correct``; with the answer
stage broken as each fault in ``faults.py`` says (the control among
them), it is not, and the number that the fault breaks is over its
limit."""

import json
import time

import pytest

import faults
import harness

BENCH = json.loads((harness.ROOT / "BENCHMARK.json").read_text())


#: the number each fault drives over its limit
BREAKS = {None: None, "drop_server": "bad_lookups", "half_batch": "bad_lookups",
          "alter_answer": "bad_lookups", "thin_masks": "mask_density_z",
          "merge_servers": "short_batches"}


def tiny_spec(workload):
    from repro.core import make_scheme

    spec = harness.cell_spec(BENCH, workload)
    prog = spec["config"]["program"]
    prog.update(n_records=1024, record_bytes=64, d=4, d_a=2,
                query_batch=min(prog["query_batch"], 4))
    scheme = make_scheme(prog["scheme"], d=4, d_a=2, theta=prog["theta"]) \
        if prog["scheme"] == "sparse" else make_scheme(prog["scheme"], d=4, d_a=2)
    eps, delta = scheme.privacy(1024)
    spec["config"]["guarantee"].update(d=4, d_a=2, epsilon_per_lookup=eps,
                                       delta_per_lookup=delta)
    if spec["mix"]["loop"] == "closed":
        spec["mix"]["outstanding"] = 8
    else:
        spec["mix"]["arrivals"]["rate_qps"] = 40.0
    return spec


@pytest.fixture(scope="module")
def compiles():
    return harness.CompileLog()


def run(workload, compiles, fault=None, spec=None, log=lambda _: None):
    return harness.run_cell(
        BENCH, workload, seed=2**32 + 17, seconds=1.0, trace=False,
        t_start=time.perf_counter(), compiles=compiles, fault=fault,
        spec=spec or tiny_spec(workload), log=log)


def over(out):
    return {k for k, c in out["checks"].items() if c["value"] > c["limit"]}


@pytest.mark.parametrize("fault", [None, *faults.FAULTS])
@pytest.mark.parametrize("workload",
                         [w["name"] for w in BENCH["workloads"]])
def test_run_is_correct_only_when_sound(workload, fault, compiles):
    assert set(BREAKS) == {None, *faults.FAULTS}
    lines = []
    out = run(workload, compiles, fault, log=lines.append)
    assert out["attempted"] > 0
    assert list(out)[-1] == "checks"
    assert set(out["checks"]) == {"bad_lookups", "short_batches",
                                  "mask_density_z", "price_mismatch"}
    if fault is None:
        assert out["correct"] and not over(out) and out["failed"] == 0
        # the memo answered the repeated warm-up lookups, and was checked
        memo = [ln for ln in lines if ln.startswith("memo:")]
        assert memo and memo[0].split()[1] == memo[0].split()[3] != "0"
        assert out["checks"]["mask_density_z"]["value"] > 0
    else:
        assert not out["correct"] and BREAKS[fault] in over(out)


@pytest.mark.parametrize("workload",
                         [w["name"] for w in BENCH["workloads"]])
def test_a_price_unlike_the_stated_is_not_correct(workload, compiles):
    spec = tiny_spec(workload)
    spec["config"]["guarantee"]["epsilon_per_lookup"] += 1e-3
    out = run(workload, compiles, spec=spec)
    assert not out["correct"] and over(out) == {"price_mismatch"}
