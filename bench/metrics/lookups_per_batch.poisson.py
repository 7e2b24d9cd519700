"""lookups_per_batch.poisson: lookups that went to the servers per batch
in the window, from the pipeline's counters: (queries - cache_hits) /
batches (program counter)."""


def read(run):
    c0, c1 = run["counters_open"], run["counters_close"]
    batches = c1["batches"] - c0["batches"]
    if batches <= 0:
        return None
    sent = (c1["queries"] - c0["queries"]) - (c1["cache_hits"] - c0["cache_hits"])
    return sent / batches
