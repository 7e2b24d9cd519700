"""A whole run on the CPU at a tiny size (the chip check is the entry
scripts' and is not made here): sound, it is ``correct``; with the answer
stage broken as each fault in ``faults.py`` says (the control among
them), it is not, and the number that the fault breaks is over its
limit. Which faults and checks apply follows the wire each cell's
guarantee states, never its name. Beside the benchmark's cells runs a
tiny Direct Requests spec (index wire), made from ``ct_chor.poisson``,
and a cell added the way a later configuration adds one: a new
configuration file and new ``BENCHMARK.json`` entries, nothing edited."""

import copy
import json
import time

import pytest

import faults
import harness
import traffic

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


def tiny_spec(workload, bench=BENCH):
    """The cell cut to a CPU size: 1024 records of 64 B, d = 4, d_a = 2,
    at most 4 lookups a batch, the price stated anew for that size. An
    index-wire cell keeps its slots per server: p = 4 * (p / d)."""
    if workload == DIRECT:
        return direct_spec()
    from repro.core import make_scheme

    spec = harness.cell_spec(bench, workload)
    prog, guarantee = spec["config"]["program"], spec["config"]["guarantee"]
    if "requests" in guarantee:
        slots = int(guarantee["requests"]["p"]) // int(guarantee["d"])
        prog["p"] = 4 * slots
        guarantee["requests"] = dict(guarantee["requests"], p=4 * slots)
    prog.update(n_records=1024, record_bytes=64, d=4, d_a=2,
                query_batch=min(prog["query_batch"], 4))
    scheme = make_scheme(prog["scheme"], d=4, d_a=2, theta=prog["theta"],
                         p=prog.get("p") or None)
    eps, delta = scheme.privacy(1024)
    guarantee.update(d=4, d_a=2, epsilon_per_lookup=eps,
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


def wire(workload, bench=BENCH):
    """The wire the cell's tiny spec states (``harness.stated_wire``)."""
    cfg = tiny_spec(workload, bench)["config"]
    return harness.stated_wire(cfg["guarantee"], cfg["limits"],
                               int(cfg["program"]["n_records"]))


WORKLOADS = [w["name"] for w in BENCH["workloads"]] + [DIRECT]
WIRES = {w: wire(w) for w in WORKLOADS}
CASES = [(w, f) for w in WORKLOADS for f in (None, *faults.FAULTS)
         if f is None or faults.applies(f, WIRES[w])]


def index_cell(tmp_path):
    """``BENCHMARK.json`` with one more cell, added as a new configuration
    file (under ``tmp_path``) and new entries alone: ``ct_chor.json``
    served under Direct Requests with p = d (one slot a server), its
    guarantee stated as ``requests`` and priced by the scheme, on the mix
    and metrics of the open-loop ``ct_chor`` cell. Returns the benchmark
    and the cell's name."""
    from repro.core import make_scheme

    bench = copy.deepcopy(BENCH)
    entry = next(c for c in bench["configs"] if c["name"] == "ct_chor")
    cfg = harness.load_json(harness.ROOT / entry["file"])
    guarantee, prog = cfg["guarantee"], cfg["program"]
    d = int(guarantee["d"])
    prog.update(scheme="direct", p=d)
    eps, delta = make_scheme("direct", d=d, d_a=int(guarantee["d_a"]),
                             p=d).privacy(int(prog["n_records"]))
    del guarantee["masks"], guarantee["masks_stated"]
    guarantee.update(epsilon_per_lookup=eps, delta_per_lookup=delta,
                     requests={"kind": "direct", "p": d})
    cfg.update(name="index_cell", limits={"request_spread_z": 8.0})
    path = tmp_path / "index_cell.json"
    path.write_text(json.dumps(cfg))
    bench["configs"].append(dict(entry, name="index_cell", file=str(path)))
    model = next(w for w in bench["workloads"] if w["config"] == "ct_chor"
                 and traffic.load(w["traffic"])["loop"] == "open")
    name = f"index_cell.{model['traffic']}"
    bench["workloads"].append(dict(model, name=name, config="index_cell"))
    for m in bench["end_to_end"] + bench["per_layer"]:
        if model["name"] in m.get("workloads", ()):
            m["workloads"].append(name)
    return bench, name


@pytest.fixture(scope="module")
def compiles():
    return harness.CompileLog()


def run(workload, compiles, fault=None, spec=None, log=lambda _: None,
        bench=BENCH):
    return harness.run_cell(
        bench, workload, seed=2**32 + 17, seconds=1.0, trace=False,
        t_start=time.perf_counter(), compiles=compiles, fault=fault,
        spec=spec or tiny_spec(workload, bench), log=log)


def over(out):
    return {k for k, c in out["checks"].items() if c["value"] > c["limit"]}


def assert_judged(out, lines, wire, fault):
    """A run on ``wire`` compares that wire's checks; sound, or under a
    fault of the other wire, it is ``correct`` with every lookup right and
    its spread read; under a fault of its wire, the fault's number is over
    its limit."""
    assert out["attempted"] > 0
    assert list(out)[-1] == "checks"
    assert set(out["checks"]) == CHECKS[wire]
    if fault is None or not faults.applies(fault, wire):
        assert out["correct"] and not over(out) and out["failed"] == 0
        # the memo answered the repeated warm-up lookups, and was checked
        memo = [ln for ln in lines if ln.startswith("memo:")]
        assert memo and memo[0].split()[1] == memo[0].split()[3] != "0"
        spread = "mask_density_z" if wire == "mask" else "request_spread_z"
        assert out["checks"][spread]["value"] > 0
        # set-up warmed every program the window runs
        window = [ln for ln in lines if ln.startswith("window: compiles")]
        assert window and "'compiles': 0," in window[0]
    else:
        assert not out["correct"] and BREAKS[fault] in over(out)


@pytest.mark.parametrize("workload,fault", CASES,
                         ids=[f"{w}-{f}" for w, f in CASES])
def test_run_is_correct_only_when_sound(workload, fault, compiles):
    assert set(BREAKS) == {None, *faults.FAULTS}
    lines = []
    out = run(workload, compiles, fault, log=lines.append)
    assert_judged(out, lines, WIRES[workload], fault)


@pytest.mark.parametrize("fault", [None, *faults.FAULTS])
def test_an_index_cell_added_as_files_and_entries(fault, compiles, tmp_path):
    bench, name = index_cell(tmp_path)
    spec = tiny_spec(name, bench)
    assert spec["config"]["program"]["p"] == 4
    assert spec["config"]["guarantee"]["requests"]["p"] == 4
    stated = wire(name, bench)
    assert stated == "index"
    lines = []
    out = run(name, compiles, fault, spec=spec, log=lines.append, bench=bench)
    assert_judged(out, lines, stated, fault)


@pytest.mark.parametrize("workload", WORKLOADS)
def test_a_price_unlike_the_stated_is_not_correct(workload, compiles):
    spec = tiny_spec(workload)
    spec["config"]["guarantee"]["epsilon_per_lookup"] += 1e-3
    out = run(workload, compiles, spec=spec)
    assert not out["correct"] and over(out) == {"price_mismatch"}


@pytest.mark.parametrize("fault", [f for f in faults.FAULTS
                                   if faults.WIRE[f] is not None])
def test_a_fault_leaves_the_other_wire_alone(fault, compiles):
    workload = next(w for w in WORKLOADS if WIRES[w] != faults.WIRE[fault])
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
