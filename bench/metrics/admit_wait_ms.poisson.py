"""admit_wait_ms.poisson: the mean wait of a request in the window from
its submit to the frontend's admission (or refusal), in ms: the change in
the program's ``admit_wait_s`` over the change in ``admit_count``
(program counter, on the scheduler's clock)."""


def read(run):
    c0, c1 = run["counters_open"], run["counters_close"]
    if any(k not in c for c in (c0, c1) for k in ("admit_wait_s", "admit_count")):
        return None
    count = c1["admit_count"] - c0["admit_count"]
    if count <= 0:
        return None
    return (c1["admit_wait_s"] - c0["admit_wait_s"]) / count * 1e3
