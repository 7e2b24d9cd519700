"""Faults planted under the answer stage, for the control and the tests.

A fault may change what the answer stage is asked (``routed``: the
router's per-server masks and the servers they go to) and what it
returns (``out``: the stacked per-server responses, servers first, the
batch second), the way a faulty answer path would:

* ``drop_server``: the last server's response is left out (zeros), so the
  lookup is rebuilt from d - 1 of the d servers the configuration
  promises. This is the control: it breaks the stated guarantee.
* ``half_batch``: the second half of the batch gets no answer (zeros);
  a batch of one loses its only query.
* ``alter_answer``: one bit of one server's answer to the batch's first
  query is flipped where it is produced.
* ``thin_masks``: every server's mask but the last is ANDed with the next
  one, and the last is set so that the masks still XOR to the one-hot
  query: the bytes come back right, but each server sees a mask of the
  wrong density (theta squared), the privacy a faster, sparser query
  would give away.
* ``merge_servers``: the last two servers' masks are XORed into one and
  sent to one server: the bytes come back right from d - 1 servers, and
  a coalition of fewer than the stated d_a now learns the query.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Tuple

FAULTS = ("drop_server", "half_batch", "alter_answer", "thin_masks",
          "merge_servers")


def _same(x):
    return x


def _thin_payload(p):
    import jax

    def xor_all(m):
        return jax.lax.reduce(m, m.dtype.type(0), jax.lax.bitwise_xor, (0,))

    thin = p[:-1] & p[1:]
    last = xor_all(p) ^ xor_all(thin)
    return jax.numpy.concatenate([thin, last[None]], axis=0)


def _merge_payload(p):
    import jax

    return jax.numpy.concatenate([p[:-2], (p[-2] ^ p[-1])[None]], axis=0)


def _thin(routed):
    import jax

    return dataclasses.replace(
        routed, payload=jax.jit(_thin_payload)(routed.payload))


def _merge(routed):
    import jax

    return dataclasses.replace(
        routed, payload=jax.jit(_merge_payload)(routed.payload),
        servers=routed.servers[:-1])


def breaker(fault: Optional[str]) -> Tuple[Callable, Callable]:
    """(what the answer stage is asked, what it returns) under ``fault``."""
    if fault is None:
        return _same, _same
    if fault == "drop_server":
        return _same, lambda out: out.at[-1].set(0)
    if fault == "half_batch":
        return _same, lambda out: out.at[:, out.shape[1] // 2:].set(0)
    if fault == "alter_answer":
        def alter(out):
            first = (0,) * out.ndim
            return out.at[first].set(out[first] ^ 1)
        return _same, alter
    if fault == "thin_masks":
        return _thin, _same
    if fault == "merge_servers":
        return _merge, _same
    raise ValueError(f"unknown fault {fault!r}; have {FAULTS}")
