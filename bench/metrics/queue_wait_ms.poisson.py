"""queue_wait_ms.poisson: the mean wait of a request cut in the window
from its enqueue to the scheduler's cut of its batch, in ms: the change in
the program's ``queue_wait_s`` over the change in ``dequeued`` (program
counter, on the scheduler's clock)."""


def read(run):
    c0, c1 = run["counters_open"], run["counters_close"]
    if any(k not in c for c in (c0, c1) for k in ("queue_wait_s", "dequeued")):
        return None
    count = c1["dequeued"] - c0["dequeued"]
    if count <= 0:
        return None
    return (c1["queue_wait_s"] - c0["queue_wait_s"]) / count * 1e3
