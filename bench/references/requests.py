"""Plain reference for the requests a server sees under Direct Requests.

Direct Requests (paper section 4.1, Algorithm 4.1) hides the real index
among p - 1 dummies: the p indices of one lookup are distinct ids in
[0, n), the dummies are drawn uniformly from the rest of the store,
independent of the real index, and the real index sits in a uniformly
random one of the p slots, which are split evenly over the d servers.

One batch's wire is ``payload [d, B, k]`` (k = p / d; lookup b's slots
are ``payload[:, b, :]``, server-major) and its real indices ``q_idx
[B]``. :func:`reduce_batch` returns the lookups that break the first
rule, and counts that pool over batches into what the other two rules
predict:

* ``dummies``: the dummies in each of ``BINS`` equal ranges of [0, n),
  with ``reals``, the real indices in the same ranges, which set what
  the dummy counts should be (a dummy never equals its own real index);
* ``offsets``: each dummy's offset (x - q) mod n from its real index, in
  ``BINS`` equal ranges of [1, n), uniform if the dummies are
  independent of the real index;
* ``servers``: the slots holding the real index, by server, each
  server's share 1 / d.

:func:`spread_z` turns pooled counts into the largest distance of any
count from its expectation, in standard deviations of the binomial count
it would be if every draw were independent. The dummies of one lookup
are drawn without replacement, which spreads them less, so the binomial
standard deviation is the conservative one. Nothing here imports the
program.
"""

from __future__ import annotations

import math
from typing import Dict, List, Tuple

import numpy as np

#: equal ranges of the store, and of the offsets, that the counts use
BINS = 16


def ranges(lo: int, hi: int, bins: int = BINS) -> Tuple[int, np.ndarray]:
    """Equal ranges of [lo, hi): each ``width`` wide (the last one what is
    left). Returns the width and the size of each range."""
    width = -(-(hi - lo) // bins)
    starts = lo + width * np.arange(bins, dtype=np.int64)
    sizes = np.clip(np.minimum(starts + width, hi) - starts, 0, None)
    return width, sizes


def reduce_batch(payload, q_idx, n: int,
                 bins: int = BINS) -> Tuple[List[int], Dict[str, np.ndarray]]:
    """One batch: the lookups whose p indices are not p distinct ids in
    [0, n) holding the real index exactly once, and the batch's counts
    (see the module's docstring). Padding lookups count like any other."""
    payload = np.asarray(payload, np.int64)
    q_idx = np.asarray(q_idx, np.int64)
    d, b, k = payload.shape
    w_store, _ = ranges(0, n, bins)
    w_offset, _ = ranges(1, n, bins)
    counts = {
        "lookups": np.int64(b),
        "dummies": np.zeros(bins, np.int64),
        "reals": np.zeros(bins, np.int64),
        "offsets": np.zeros(bins, np.int64),
        "servers": np.zeros(d, np.int64),
    }
    bad = []
    for col in range(b):
        q = int(q_idx[col])
        counts["reals"][q // w_store] += 1
        slots = [int(x) for x in payload[:, col, :].reshape(-1)]
        inside = all(0 <= x < n for x in slots)
        if not (inside and len(set(slots)) == len(slots)
                and slots.count(q) == 1):
            bad.append(col)
        for s, x in enumerate(slots):
            if x == q:
                counts["servers"][s // k] += 1
            elif 0 <= x < n:
                counts["dummies"][x // w_store] += 1
                counts["offsets"][((x - q) % n - 1) // w_offset] += 1
    return bad, counts


def pool(batches: List[Dict[str, np.ndarray]]) -> Dict[str, np.ndarray]:
    """The counts of many batches, summed."""
    return {key: sum(np.asarray(c[key], np.int64) for c in batches)
            for key in batches[0]}


def _z(count, mean, var) -> np.ndarray:
    count, mean, var = (np.asarray(a, np.float64) for a in (count, mean, var))
    sd = np.sqrt(var)
    safe = np.where(sd > 0, sd, 1.0)
    return np.where(sd > 0, (count - mean) / safe,
                    np.where(count == mean, 0.0, np.inf))


def zs(counts: Dict[str, np.ndarray], n: int, p: int) -> Dict[str, np.ndarray]:
    """How far each pooled count lies from what Algorithm 4.1 predicts,
    in binomial standard deviations (``dummies``, ``offsets``,
    ``servers``)."""
    lookups = float(counts["lookups"])
    d = len(counts["servers"])
    bins = len(counts["dummies"])
    _, store = ranges(0, n, bins)
    _, offset = ranges(1, n, bins)
    # a lookup's p - 1 dummies each fall in range r with probability
    # |r| / (n - 1), or (|r| - 1) / (n - 1) where r holds its real index
    reals = np.asarray(counts["reals"], np.float64)
    p0, p1 = store / (n - 1), (store - 1) / (n - 1)
    mean = (p - 1) * ((lookups - reals) * p0 + reals * p1)
    var = (p - 1) * ((lookups - reals) * p0 * (1 - p0) + reals * p1 * (1 - p1))
    out = {"dummies": _z(counts["dummies"], mean, var)}
    po = offset / (n - 1)
    out["offsets"] = _z(counts["offsets"], lookups * (p - 1) * po,
                        lookups * (p - 1) * po * (1 - po))
    out["servers"] = _z(counts["servers"], lookups / d,
                        lookups * (1 / d) * (1 - 1 / d))
    return out


def spread_z(counts: Dict[str, np.ndarray], n: int, p: int) -> float:
    """The largest |z| over the three statistics (0.0 with no lookups)."""
    if not counts["lookups"]:
        return 0.0
    return float(max(np.abs(z).max() for z in zs(counts, n, p).values()))


def check_stated(p: int, d: int, n: int) -> None:
    """Refuse a stated p that Algorithm 4.1 cannot run."""
    if p <= 1 or p % d or p > n:
        raise ValueError(f"requests guarantee needs 1 < p <= n with p a "
                         f"multiple of d (p={p}, d={d}, n={n})")
