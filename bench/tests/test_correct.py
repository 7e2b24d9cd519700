"""A whole run on the CPU at a tiny size (the chip check is the entry
scripts' and is not made here): sound, it is ``correct``; with the answer
stage broken as each fault in ``faults.py`` says (the control among
them), it is not, and the number that the fault breaks is over its
limit. Beside the benchmark's cells runs a tiny Direct Requests spec
(index wire), made from ``ct_chor.poisson``."""

import json
import time

import pytest

import faults
import harness

BENCH = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
DIRECT = "direct.tiny"


#: the number each fault drives over its limit
BREAKS = {None: None, "drop_server": "bad_lookups", "half_batch": "bad_lookups",
          "alter_answer": "bad_lookups", "thin_masks": "mask_density_z",
          "merge_servers": "short_batches",
          "no_dummies": "request_violations",
          "adjacent_dummies": "request_spread_z",
          "fixed_slot": "request_spread_z"}
#: the numbers a run compares, by the wire its guarantee states
CHECKS = {
    "mask": {"bad_lookups", "short_batches", "mask_density_z",
             "price_mismatch"},
    "index": {"bad_lookups", "short_batches", "request_violations",
              "request_spread_z", "price_mismatch"},
}


def tiny_spec(workload):
    if workload == DIRECT:
        return direct_spec()
    from repro.core import make_scheme

    spec = harness.cell_spec(BENCH, workload)
    prog = spec["config"]["program"]
    prog.update(n_records=1024, record_bytes=64, d=4, d_a=2,
                query_batch=min(prog["query_batch"], 4))
    scheme = make_scheme(prog["scheme"], d=4, d_a=2, theta=prog["theta"],
                         p=prog.get("p") or None)
    eps, delta = scheme.privacy(1024)
    spec["config"]["guarantee"].update(d=4, d_a=2, epsilon_per_lookup=eps,
                                       delta_per_lookup=delta)
    if spec["mix"]["loop"] == "closed":
        spec["mix"]["outstanding"] = 8
    else:
        spec["mix"]["arrivals"]["rate_qps"] = 40.0
    return spec


def direct_spec(p=8):
    """``ct_chor.poisson``'s tiny spec served under Direct Requests: d = 4,
    p = 8, the guarantee stated as ``requests``."""
    from repro.core import make_scheme

    spec = harness.cell_spec(BENCH, "ct_chor.poisson")
    spec["cell"] = dict(spec["cell"], name=DIRECT)
    spec["config"]["program"].update(n_records=1024, record_bytes=64, d=4,
                                     d_a=2, query_batch=4, scheme="direct",
                                     p=p)
    eps, delta = make_scheme("direct", d=4, d_a=2, p=p).privacy(1024)
    guarantee = spec["config"]["guarantee"]
    del guarantee["masks"], guarantee["masks_stated"]
    guarantee.update(d=4, d_a=2, epsilon_per_lookup=eps, delta_per_lookup=delta,
                     requests={"kind": "direct", "p": p})
    spec["config"]["limits"] = {"request_spread_z": 8.0}
    spec["mix"]["arrivals"]["rate_qps"] = 40.0
    return spec


def wire(workload):
    return "index" if workload == DIRECT else "mask"


WORKLOADS = [w["name"] for w in BENCH["workloads"]] + [DIRECT]
CASES = [(w, f) for w in WORKLOADS for f in (None, *faults.FAULTS)
         if f is None or faults.applies(f, wire(w))]


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


@pytest.mark.parametrize("workload,fault", CASES,
                         ids=[f"{w}-{f}" for w, f in CASES])
def test_run_is_correct_only_when_sound(workload, fault, compiles):
    assert set(BREAKS) == {None, *faults.FAULTS}
    lines = []
    out = run(workload, compiles, fault, log=lines.append)
    assert out["attempted"] > 0
    assert list(out)[-1] == "checks"
    assert set(out["checks"]) == CHECKS[wire(workload)]
    if fault is None:
        assert out["correct"] and not over(out) and out["failed"] == 0
        # the memo answered the repeated warm-up lookups, and was checked
        memo = [ln for ln in lines if ln.startswith("memo:")]
        assert memo and memo[0].split()[1] == memo[0].split()[3] != "0"
        spread = "mask_density_z" if wire(workload) == "mask" \
            else "request_spread_z"
        assert out["checks"][spread]["value"] > 0
    else:
        assert not out["correct"] and BREAKS[fault] in over(out)


@pytest.mark.parametrize("workload", WORKLOADS)
def test_a_price_unlike_the_stated_is_not_correct(workload, compiles):
    spec = tiny_spec(workload)
    spec["config"]["guarantee"]["epsilon_per_lookup"] += 1e-3
    out = run(workload, compiles, spec=spec)
    assert not out["correct"] and over(out) == {"price_mismatch"}


@pytest.mark.parametrize("fault", [f for f in faults.FAULTS
                                   if faults.WIRE[f] is not None])
def test_a_fault_leaves_the_other_wire_alone(fault, compiles):
    workload = next(w for w in WORKLOADS if wire(w) != faults.WIRE[fault])
    out = run(workload, compiles, fault)
    assert out["correct"] and not over(out)


def test_a_direct_run_stating_chor_masks_is_not_correct(compiles):
    spec = tiny_spec(DIRECT)
    guarantee, config = spec["config"]["guarantee"], spec["config"]
    del guarantee["requests"]
    guarantee["masks"] = {"kind": "chor"}
    config["limits"] = {"mask_density_z": 8.0}
    out = run(DIRECT, compiles, spec=spec)
    assert set(out["checks"]) == CHECKS["mask"]
    assert not out["correct"] and "short_batches" in over(out)
