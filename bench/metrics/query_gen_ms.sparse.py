"""query_gen_ms.sparse: mean milliseconds per batch in the router's query
generation (``SchemeRouter.plan``), from the benchmark's
``bench.query_gen`` spans, which wait for the payload inside the span in
the traced run (device trace's host plane)."""


def read(run):
    red = run["trace"]
    if red is None:
        return None
    spans = red.spans_named("bench.query_gen")
    if not spans:
        return None
    return sum(s.end_ns - s.start_ns for s in spans) / len(spans) / 1e6
