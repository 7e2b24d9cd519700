"""answer_roofline: the answer stage's share of the chip's memory roofline.

Numerator: the least bytes each server's answer must move, over the
chip's peak bandwidth (``peaks.json``, by device kind; an unknown kind is
an error). Per server and batch of B queries over n records of W words:

* a dense mask (Chor): the store once (n * W * 4), the masks as packed
  bits (B * n / 8) and the outputs (B * W * 4);
* a sparse mask (Sparse-PIR): the records any of the B masks selects
  (the union, at most the store once), the packed masks and the outputs;
* an index wire (Direct Requests, k slots a query): each of the B * k
  records gathered, read and written once, and its index:
  B * k * (2 * W * 4 + 4).

The answer span's stats give the shapes and, for a sparse wire, the
union summed over servers. Denominator: device time inside the
benchmark's ``bench.answer_batch`` spans. Whatever kernel answers, the
work counted is the same, so the share stays defined when a kernel is
replaced (device trace)."""


def least_bytes(stats) -> float:
    servers, b = int(stats["servers"]), int(stats["bucket"])
    n, w = int(stats["n"]), int(stats["words"])
    if stats["kind"] == "index":
        return servers * b * int(stats["k"]) * (2 * w * 4 + 4)
    if stats["kind"] != "mask":
        raise ValueError(f"no least-bytes rule for a {stats['kind']} wire")
    records = int(stats["union_records"]) if "union_records" in stats \
        else servers * n
    return records * w * 4 + servers * (b * n / 8 + b * w * 4)


def read(run):
    red = run["trace"]
    if red is None:
        return None
    spans = red.spans_named("bench.answer_batch")
    busy = red.busy_within("bench.answer_batch")
    if not spans or busy <= 0:
        return None
    kind = run["device"]["kind"]
    peaks = run["peaks"]
    if kind not in peaks:
        raise KeyError(f"no peak bandwidth for device kind {kind!r}")
    least_s = sum(least_bytes(s.stats) for s in spans) / peaks[kind]["hbm_bytes_per_s"]
    return 100.0 * least_s / busy
