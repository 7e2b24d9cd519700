"""dispatch_wait_ms.poisson: the mean time of a request in the window
from the start of its batch's planning (query generation) to the start
of its execution, in ms, which holds the wait of a planned batch for the
double buffer's other slot: the change in the program's
``dispatch_wait_s`` over the change in ``dequeued`` (program counter, on
the scheduler's clock)."""


def read(run):
    c0, c1 = run["counters_open"], run["counters_close"]
    if any(k not in c for c in (c0, c1)
           for k in ("dispatch_wait_s", "dequeued")):
        return None
    count = c1["dequeued"] - c0["dequeued"]
    if count <= 0:
        return None
    return (c1["dispatch_wait_s"] - c0["dispatch_wait_s"]) / count * 1e3
