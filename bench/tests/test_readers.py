"""Every metric reader, on runs made up here: each reads what its
docstring says and returns None where it finds nothing to read."""

import json
import math
from pathlib import Path

import pytest

import harness
import xplane
from traffic import Lookup

BENCH = json.loads((Path(harness.ROOT) / "BENCHMARK.json").read_text())
NAMES = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
PEAKS = harness.load_json(harness.HERE / "peaks.json")


def lookup(t_sched, t_done, tag=None):
    return Lookup("c", (1,), t_sched, t_sched, t_done, tag)


def red(spans=(), busy=((0.0, 1e9),), window=(0.0, 2e9)):
    return xplane.Reduction(
        window=window, devices=1, busy_ns=xplane.measure(list(busy)),
        op_ns={}, busy=[list(busy)], gaps=[], spans=list(spans),
        clock_offsets_ns=[0.0])


def run(**kw):
    base = {"lookups": [], "ok": [], "t_open": 0.0, "t_close": 10.0,
            "trace": None, "setup_s": 12.5,
            "counters_open": {"queries": 0, "cache_hits": 0, "batches": 0},
            "counters_close": {"queries": 0, "cache_hits": 0, "batches": 0},
            "device": {"kind": "TPU v5 lite"}, "peaks": PEAKS}
    base.update(kw)
    return base


@pytest.mark.parametrize("name", NAMES)
def test_every_metric_has_a_reader(name):
    assert callable(harness.load_reader(name))


@pytest.mark.parametrize("name", [m["name"] for m in BENCH["per_layer"]])
def test_per_layer_reader_finds_nothing_in_an_empty_run(name):
    assert harness.load_reader(name)(run()) is None


def test_setup_s():
    assert harness.load_reader("setup_s")(run()) == 12.5


def test_lookups_per_s_skips_the_first_batch_and_the_edges():
    lks = ([lookup(0, 1.0, "a")] * 4 + [lookup(0, 3.0, "b")] * 4
           + [lookup(0, 5.0, "c")] * 4 + [lookup(0, 11.0, "d")] * 4)
    ok = [True] * 15 + [False]
    r = run(lookups=lks, ok=ok)
    # batches b and c land 4 s after a: 8 lookups
    assert harness.load_reader("lookups_per_s")(r) == pytest.approx(2.0)
    ok[4] = False  # a wrong answer in batch b does not count
    assert harness.load_reader("lookups_per_s")(run(lookups=lks, ok=ok)) \
        == pytest.approx(7 / 4)
    assert harness.load_reader("lookups_per_s")(run(lookups=lks[:4],
                                                    ok=ok[:4])) is None


def test_latency_percentiles_count_every_lookup():
    lks = [lookup(float(i), float(i) + (i + 1) / 100) for i in range(100)]
    assert harness.load_reader("lookup_p50_ms")(run(lookups=lks)) \
        == pytest.approx(505.0)
    assert harness.load_reader("lookup_p95_ms.poisson")(run(lookups=lks)) \
        == pytest.approx(950.5)
    lks[0] = lookup(0.0, math.nan)  # never answered: infinitely late
    assert harness.load_reader("lookup_p95_ms.poisson")(run(lookups=lks)) \
        == pytest.approx(960.5)


@pytest.mark.parametrize("name", ["device_idle.saturated",
                                  "device_idle.poisson"])
def test_device_idle(name):
    assert harness.load_reader(name)(run(trace=red())) == pytest.approx(50.0)


def answer_span(t0, t1, **stats):
    return xplane.Span("bench.answer_batch", t0, t1, stats)


def test_answer_roofline_dense_and_sparse():
    n, w, d, b = 10**6, 384, 100, 32
    dense = answer_span(0, 1e9, servers=d, bucket=b, n=n, words=w, kind="mask")
    least = d * (n * w * 4 + b * n / 8 + b * w * 4)
    want = 100 * least / 819e9 / 1.0
    got = harness.load_reader("answer_roofline")(run(trace=red([dense])))
    assert got == pytest.approx(want)
    sparse = answer_span(0, 1e9, servers=d, bucket=4, n=10**5, words=w,
                         kind="mask", union_records=6_800_000)
    least = 6_800_000 * w * 4 + d * (4 * 10**5 / 8 + 4 * w * 4)
    got = harness.load_reader("answer_roofline")(run(trace=red([sparse])))
    assert got == pytest.approx(100 * least / 819e9)


def test_answer_roofline_index_wire():
    # Direct Requests: each server gathers B * k records, reads and writes
    # each once, and reads its index
    n, w, d, b, k = 10**6, 384, 100, 8, 1
    span = answer_span(0, 1e9, servers=d, bucket=b, n=n, words=w,
                       kind="index", k=k)
    least = d * b * k * (2 * w * 4 + 4)
    got = harness.load_reader("answer_roofline")(run(trace=red([span])))
    assert got == pytest.approx(100 * least / 819e9)


def test_answer_roofline_counts_device_time_inside_its_spans_only():
    span = answer_span(0, 1e9, servers=1, bucket=1, n=1000, words=4,
                       kind="mask")
    one = harness.load_reader("answer_roofline")(run(trace=red([span])))
    two = harness.load_reader("answer_roofline")(run(trace=red(
        [span], busy=[(0.0, 0.5e9), (1.5e9, 2e9)])))
    assert two == pytest.approx(2 * one)


def test_answer_roofline_refuses_an_unknown_device():
    span = answer_span(0, 1e9, servers=1, bucket=1, n=1000, words=4,
                       kind="mask")
    with pytest.raises(KeyError):
        harness.load_reader("answer_roofline")(
            run(trace=red([span]), device={"kind": "TPU v99"}))


def test_query_gen_ms():
    spans = [xplane.Span("bench.query_gen", 0, 2e6, {}),
             xplane.Span("bench.query_gen", 5e6, 9e6, {})]
    assert harness.load_reader("query_gen_ms.sparse")(run(trace=red(spans))) \
        == pytest.approx(3.0)


def test_lookups_per_batch():
    r = run(counters_open={"queries": 10, "cache_hits": 1, "batches": 2},
            counters_close={"queries": 40, "cache_hits": 3, "batches": 9})
    assert harness.load_reader("lookups_per_batch.poisson")(r) \
        == pytest.approx(28 / 7)
