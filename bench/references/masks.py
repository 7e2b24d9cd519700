"""Plain reference for the request masks a server sees: how dense they are.

A scheme's privacy rests on what each server's mask looks like. Every
bit of a mask is drawn on its own per record, so the number of ones in
one server's mask for one query over n records is Binomial(n, p), where
p is the share of ones the scheme states:

* ``chor``: every mask is uniformly random, p = 1/2.
* ``sparse`` (Sparse-PIR, paper section 4.3): each record's column of d
  bits has a Binomial(d, theta) weight conditioned to be even (odd at the
  queried record, one column of n, left out here), placed uniformly; p is
  that conditioned mean weight over d.

:func:`row_z` turns counts of ones into how many standard deviations each
lies from n * p. Nothing here imports the program.
"""

from __future__ import annotations

import math

import numpy as np


def density(kind: str, d: int, theta: float = 0.5) -> float:
    """The share of ones in one server's mask under the stated scheme."""
    if kind == "chor":
        return 0.5
    if kind != "sparse":
        raise ValueError(f"no mask density for scheme {kind!r}")
    even = weighted = 0.0
    for w in range(0, d + 1, 2):
        pmf = math.comb(d, w) * theta ** w * (1.0 - theta) ** (d - w)
        even += pmf
        weighted += w * pmf
    return weighted / even / d


def row_z(counts: np.ndarray, n: int, p: float) -> np.ndarray:
    """Standard deviations from n * p of each count of ones in n bits."""
    mean = n * p
    sd = math.sqrt(n * p * (1.0 - p))
    return (np.asarray(counts, np.float64) - mean) / sd
