"""The trace reduction, on a trace recorded on a v5e
(``record_trace_fixture.py``): a ``bench.window`` span holding two
rounds of a ``bench.query_gen`` span (one small program, then 30 ms of
host sleep) and a ``bench.answer_batch`` span (a 256 MiB program, 20 ms
of sleep, the same program again)."""

from pathlib import Path

import pytest

import xplane

FIXTURE = Path(__file__).with_name("data") / "v5e_fixture.xplane.pb"


@pytest.fixture(scope="module")
def red():
    return xplane.load(str(FIXTURE))


def test_window_and_busy(red):
    assert red.devices == 1
    assert red.window_s == pytest.approx(0.108501046)
    assert 0 < red.busy_s < 0.01 * red.window_s + 0.001
    # the device clock runs about 1.36 ms behind the host's in this trace
    assert red.clock_offsets_ns[0] == pytest.approx(1.359e6, rel=0.01)


def test_device_time_lands_in_its_span(red):
    # four runs of the 256 MiB program, each ~0.378 ms, all inside the
    # two answer spans once the device clock is aligned
    fusion = red.op_ns["jit__lambda:fusion"] / 1e9
    assert fusion == pytest.approx(4 * 0.3777e-3, rel=0.01)
    assert red.busy_within("bench.answer_batch") == pytest.approx(fusion)
    assert 0 < red.busy_within("bench.query_gen") < 0.001


def test_gaps_are_labelled_by_host_span(red):
    by = xplane.idle_by_label(red)
    assert by["bench.query_gen"] == pytest.approx(0.062, abs=0.002)
    assert by["bench.answer_batch"] == pytest.approx(0.044, abs=0.002)
    longest = xplane.breakdown(red)["idle_gaps"][:4]
    assert [g[0] for g in longest] == ["bench.query_gen"] * 2 + ["bench.answer_batch"] * 2
    assert all(0.02 < g[1] < 0.032 for g in longest)


def test_breakdown_shape(red):
    out = xplane.breakdown(red)
    assert set(out) == {"device_ops", "idle_gaps"}
    assert len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10
    assert out["device_ops"][0][0] == "jit__lambda:fusion"


def test_span_stats_survive(red):
    spans = red.spans_named("bench.answer_batch")
    assert [s.stats["least_bytes"] for s in spans] == [256 * 2**20] * 2


@pytest.mark.parametrize("a,b,want", [
    ([(0, 2), (1, 3), (5, 6)], [(0, 10)], 4),
    ([(0, 2)], [(1, 5)], 1),
    ([(0, 1)], [(1, 2)], 0),
])
def test_merge_and_overlap(a, b, want):
    assert xplane.overlap(xplane.merge(a), xplane.merge(b)) == want


def test_gaps_of():
    merged = xplane.merge([(1, 2), (4, 5)])
    assert xplane.gaps_of(merged, 0, 6) == [(0, 1), (2, 4), (5, 6)]
    assert xplane.gaps_of(merged, 1.5, 4.5) == [(2, 4)]


@pytest.mark.parametrize("modules,launches,want", [
    ([10.0, 20.0], [11.0, 22.0], 2.0),
    ([10.0, 20.0], [11.0], 0.0),
    ([], [], 0.0),
])
def test_clock_offset(modules, launches, want):
    assert xplane.clock_offset_ns(modules, launches) == want


def test_short_op():
    assert xplane.short_op("jit_answer(123)", "%custom-call.7 = u32[8] ...") \
        == "jit_answer:custom-call"
    assert xplane.short_op("m", "%fusion = f32[] fusion()") == "m:fusion"


def test_device_planes_only():
    assert xplane._is_device_plane("/device:TPU:0")
    assert xplane._is_device_plane("/device:TPU:3")
    assert not xplane._is_device_plane("/device:CUSTOM:Megascale Trace")
    assert not xplane._is_device_plane("/host:CPU")
