"""answer_host_gap_ms.saturated: mean milliseconds per answered batch that
the device sits idle inside the backend's answer loop: each
``bench.answer_batch`` span's duration less the device time inside it,
over the spans that lie wholly inside the traced window (a span cut by the
window's edge would count host time without its device time). The loop
dispatches each of the d servers and waits for it before the next, so
this is the per-server host work and launch latency summed over a batch
(device trace's host plane)."""

import xplane


def read(run):
    red = run["trace"]
    if red is None:
        return None
    lo, hi = red.window
    spans = [s for s in red.spans_named("bench.answer_batch")
             if s.start_ns >= lo and s.end_ns <= hi]
    if not spans:
        return None
    cover = xplane.merge([(s.start_ns, s.end_ns) for s in spans])
    busy = sum(xplane.overlap(dev, cover) for dev in red.busy) / max(1, len(red.busy))
    return (xplane.measure(cover) - busy) / len(spans) / 1e6
