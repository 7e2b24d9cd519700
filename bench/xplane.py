"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

A traced run wraps its window in a ``bench.window`` span and the calls
into each layer in ``bench.<layer>`` spans (``jax.profiler.TraceAnnotation``
on the host, so they share the trace's clock with the device). From the
device planes this takes every operation's interval; from the host plane
every ``bench.*`` span with its stats. Then, inside the window:

* busy: the measure of the union of device-operation intervals, averaged
  over the devices the run used;
* op seconds: each operation name's summed duration;
* idle gaps: every stretch with no device operation, labelled with the
  innermost ``bench.*`` span (other than the window) that covers its
  middle on the host, or ``host`` where none does.
"""

from __future__ import annotations

import bisect
import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
# the device line that holds one event per executed operation; the other
# lines ("XLA Modules", "Async XLA Ops", ...) nest over these
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# the host event that launches one program on a device
LAUNCH_EVENT = "PJRT_LoadedExecutable_Execute"


@dataclasses.dataclass
class Span:
    name: str
    start_ns: float
    end_ns: float
    stats: Dict[str, object]


@dataclasses.dataclass
class Reduction:
    window: Interval                     # ns, on the trace's clock
    devices: int
    busy_ns: float                       # averaged over devices
    op_ns: Dict[str, float]              # op name -> summed duration
    busy: List[List[Interval]]           # per device: merged busy intervals
    gaps: List[Tuple[float, float, str]]  # device 0's idle gaps, labelled
    spans: List[Span]                    # bench.* spans inside the window
    clock_offsets_ns: List[float]        # shift applied to each device

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        return self.busy_ns / 1e9

    def spans_named(self, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name]

    def busy_within(self, name: str) -> float:
        """Seconds of device work (averaged over devices) that fall
        inside spans called ``name``."""
        cover = merge([(s.start_ns, s.end_ns) for s in self.spans_named(name)])
        total = sum(overlap(dev, cover) for dev in self.busy)
        return total / max(1, len(self.busy)) / 1e9


def merge(intervals: Sequence[Interval]) -> List[Interval]:
    """Sorted union of half-open intervals."""
    out: List[List[float]] = []
    for lo, hi in sorted(intervals):
        if hi <= lo:
            continue
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def clip(merged: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in merged if b > lo and a < hi]


def measure(merged: Sequence[Interval]) -> float:
    return sum(b - a for a, b in merged)


def overlap(a: Sequence[Interval], b: Sequence[Interval]) -> float:
    """Measure of the intersection of two merged interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def gaps_of(merged: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """Stretches of [lo, hi) that no interval of ``merged`` covers."""
    out, at = [], lo
    for a, b in clip(merged, lo, hi):
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if at < hi:
        out.append((at, hi))
    return out


def _is_device_plane(name: str) -> bool:
    # "/device:TPU:0"; not "/device:CUSTOM:..." or other side planes
    head, _, ordinal = name.rpartition(":")
    return head == "/device:TPU" and ordinal.isdigit()


def short_op(module: str, op: str) -> str:
    """A stable name for one operation: its program's name without the
    fingerprint, and the HLO instruction's name without its number, as in
    ``jit_answer:custom-call``."""
    mod = module.split("(", 1)[0]
    head = op.split(" = ", 1)[0].lstrip("%")
    base = head.rsplit(".", 1)[0] if head.rsplit(".", 1)[-1].isdigit() else head
    return f"{mod}:{base}"


def _line_events(plane, line: str) -> List[Tuple[str, float, float]]:
    return [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for ln in plane.lines if ln.name == line for e in ln.events]


def _launches(profile) -> List[float]:
    return sorted(float(e.start_ns) for p in profile.planes
                  if p.name.startswith("/host:") for ln in p.lines
                  for e in ln.events if e.name == LAUNCH_EVENT)


def clock_offset_ns(modules: Sequence[float], launches: Sequence[float]) -> float:
    """How far the device clock runs behind the host's: the median of
    (launch - program start) over programs matched to their launches in
    order. A TPU trace shows a program starting about a millisecond before
    the host call that launched it; without this shift the first kernel
    of a span falls outside it. 0 where the counts differ and no order
    match exists."""
    if not modules or len(modules) != len(launches):
        return 0.0
    diffs = sorted(h - d for h, d in zip(sorted(launches), sorted(modules)))
    return diffs[len(diffs) // 2]


def _host_spans(profile) -> List[Span]:
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            for e in ln.events:
                if e.name.startswith(SPAN_PREFIX):
                    out.append(Span(e.name, float(e.start_ns),
                                    float(e.start_ns + e.duration_ns),
                                    dict(e.stats)))
    return out


def _label(spans: Sequence[Span], t: float) -> str:
    """The innermost non-window bench span covering host time ``t``."""
    best: Optional[Span] = None
    for s in spans:
        if s.name == WINDOW_SPAN or not (s.start_ns <= t < s.end_ns):
            continue
        if best is None or s.end_ns - s.start_ns < best.end_ns - best.start_ns:
            best = s
    return best.name if best is not None else "host"


def reduce_profile(profile) -> Reduction:
    """The whole reduction of one loaded trace (``ProfileData``)."""
    spans = _host_spans(profile)
    windows = [s for s in spans if s.name == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"trace holds {len(windows)} {WINDOW_SPAN} spans, "
                         f"not 1")
    lo, hi = windows[0].start_ns, windows[0].end_ns
    planes = [p for p in profile.planes if _is_device_plane(p.name)]
    if not planes:
        raise ValueError("trace holds no device plane")
    launches = _launches(profile)
    busy, op_ns, offsets = [], {}, []
    for plane in sorted(planes, key=lambda p: p.name):
        shift = clock_offset_ns(
            [a for _, a, _ in _line_events(plane, MODULES_LINE)], launches
        ) if len(planes) == 1 else 0.0
        offsets.append(shift)
        modules = sorted(_line_events(plane, MODULES_LINE), key=lambda m: m[1])
        starts = [m[1] for m in modules]
        ops = []
        for nm, a, b in _line_events(plane, OPS_LINE):
            k = bisect.bisect_right(starts, a) - 1
            mod = modules[k][0] if k >= 0 and a < modules[k][2] else "?"
            ops.append((short_op(mod, nm), a + shift, b + shift))
        busy.append(clip(merge([(a, b) for _, a, b in ops]), lo, hi))
        for name, a, b in ops:
            a, b = max(a, lo), min(b, hi)
            if b > a:
                op_ns[name] = op_ns.get(name, 0.0) + (b - a)
    inside = [s for s in spans if s.end_ns > lo and s.start_ns < hi]
    gaps = [(a, b, _label(inside, (a + b) / 2)) for a, b in gaps_of(busy[0], lo, hi)]
    return Reduction(
        window=(lo, hi),
        devices=len(busy),
        busy_ns=sum(measure(b) for b in busy) / len(busy),
        op_ns=op_ns,
        busy=busy,
        gaps=gaps,
        spans=inside,
        clock_offsets_ns=offsets,
    )


def load(path: str) -> Reduction:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path))


def breakdown(red: Reduction, top: int = 10) -> Dict[str, list]:
    """The result line's ``breakdown``: the device operations that took
    most time, and the longest idle gaps with what the host was doing."""
    ops = sorted(red.op_ns.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(red.gaps, key=lambda g: g[0] - g[1])[:top]
    return {
        "device_ops": [[name, ns / 1e9] for name, ns in ops],
        "idle_gaps": [[label, (b - a) / 1e9] for a, b, label in gaps],
    }


def idle_by_label(red: Reduction) -> Dict[str, float]:
    """Device-idle seconds in the window, summed by host span."""
    out: Dict[str, float] = {}
    for a, b, label in red.gaps:
        out[label] = out.get(label, 0.0) + (b - a) / 1e9
    return out
