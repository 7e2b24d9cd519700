"""answer_host_gap_ms.poisson: ``answer_host_gap_ms.saturated`` read in
the open-loop cells, where it moves the latency rather than the rate."""

import harness


def read(run):
    return harness.load_reader("answer_host_gap_ms.saturated")(run)
