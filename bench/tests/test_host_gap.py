"""``answer_host_gap_ms.*`` on hand-built traces, and the span labelling of
``tools/pir_readings.py`` on made-up spans and gaps."""

import importlib.util

import pytest

import harness
import xplane

GAP_READERS = ["answer_host_gap_ms.saturated", "answer_host_gap_ms.poisson"]


def red(spans, busy, window=(0.0, 10e9)):
    return xplane.Reduction(
        window=window, devices=1, busy_ns=xplane.measure(list(busy)),
        op_ns={}, busy=[list(busy)], gaps=[], spans=list(spans),
        clock_offsets_ns=[0.0])


def answer(t0, t1):
    return xplane.Span("bench.answer_batch", t0, t1, {})


@pytest.mark.parametrize("name", GAP_READERS)
def test_host_gap_is_span_time_less_device_time_per_batch(name):
    # two batches of 1 s and 3 s holding 0.6 s and 2 s of device work;
    # device work outside any answer span does not count
    spans = [answer(1e9, 2e9), answer(4e9, 7e9)]
    busy = [(0.5e9, 1.2e9), (1.5e9, 1.9e9), (4e9, 6e9), (8e9, 9e9)]
    got = harness.load_reader(name)({"trace": red(spans, busy)})
    assert got == pytest.approx(((1 - 0.6) + (3 - 2)) / 2 * 1e3)


@pytest.mark.parametrize("name", GAP_READERS)
def test_host_gap_skips_spans_cut_by_the_window(name):
    spans = [answer(-1e9, 0.5e9), answer(1e9, 2e9), answer(9.5e9, 11e9)]
    busy = [(0.0, 0.4e9), (1e9, 1.5e9)]
    got = harness.load_reader(name)({"trace": red(spans, busy)})
    assert got == pytest.approx(500.0)


@pytest.mark.parametrize("name", GAP_READERS)
def test_host_gap_finds_nothing_without_answer_spans(name):
    other = [xplane.Span("bench.query_gen", 1e9, 2e9, {})]
    assert harness.load_reader(name)({"trace": red(other, [])}) is None
    assert harness.load_reader(name)({"trace": None}) is None


@pytest.fixture(scope="module")
def tool():
    spec = importlib.util.spec_from_file_location(
        "pir_readings", harness.HERE / "tools" / "pir_readings.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def span(name, a, b):
    return xplane.Span(name, a, b, {})


def test_gaps_take_the_innermost_span_and_waits_only_alone(tool):
    spans = [span("bench.window", 0, 100),
             span("bench.execute", 10, 40), span("pir.execute", 11, 39),
             span("pir.answer", 12, 30),
             span("pir.wait.arrivals", 0, 60), span("pir.idle.prefill", 45, 50)]
    gaps = [(20, 22), (33, 35), (46, 48), (52, 56), (70, 80)]
    got = [lab for _, _, lab in tool.label_sweep(spans, gaps)]
    assert got == ["pir.answer", "pir.execute", "pir.idle.prefill",
                   "pir.wait.arrivals", "host"]
    pir = tool.label_sweep(spans, [(9.0, 10.5)], skip_bench=True)
    assert pir == [(9.0, 10.5, "pir.wait.arrivals")]
    assert tool.label_sweep(spans, [(9.0, 10.5)])[0][2] == "pir.wait.arrivals"
    assert tool.label_sweep(spans, [(10.0, 10.8)])[0][2] == "bench.execute"
    assert tool.label_sweep(spans, [(10.0, 10.8)], skip_bench=True)[0][2] \
        == "pir.wait.arrivals"


def test_window_waits_per_request(tool):
    from traffic import Lookup

    c0 = {"admit_wait_s": 1.0, "admit_count": 10, "queue_wait_s": 2.0,
          "dequeued": 10, "dispatch_wait_s": 0.0, "execute_s": 1.0}
    c1 = {"admit_wait_s": 1.5, "admit_count": 14, "queue_wait_s": 4.0,
          "dequeued": 14, "dispatch_wait_s": 0.4, "execute_s": 5.0}
    lks = [Lookup("c", (1,), 0.0, 0.1, 2.0, None),
           Lookup("c", (2,), 1.0, 1.1, 3.0, None)]
    out = tool.window_waits({"counters_open": c0, "counters_close": c1,
                             "lookups": lks})
    assert out["admit_wait_ms"] == pytest.approx(125.0)
    assert out["queue_wait_ms"] == pytest.approx(500.0)
    assert out["dispatch_wait_ms"] == pytest.approx(100.0)
    assert out["execute_ms"] == pytest.approx(1000.0)
    assert out["latency_less_lateness_ms"] == pytest.approx(1900.0)
    assert out["sum_parts_ms"] == pytest.approx(1725.0)
    # a program without the wait counters reads none of them
    bare = tool.window_waits({"counters_open": {"batches": 1},
                              "counters_close": {"batches": 3},
                              "lookups": lks})
    assert bare["queue_wait_ms"] is None and "sum_parts_ms" not in bare
