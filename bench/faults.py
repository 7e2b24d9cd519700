"""Faults planted under the answer stage, for the control and the tests.

A fault may change what the answer stage is asked (``routed``: the
router's per-server payload and the servers it goes to) and what it
returns (``out``: the stacked per-server responses, servers first, the
batch second), the way a faulty answer path would. The pipeline
reconstructs from the batch it planned, not from what the fault sent.

On either wire:

* ``drop_server``: the last server's response is left out (zeros), so the
  lookup is rebuilt from d - 1 of the d servers the configuration
  promises. This is the control: it breaks the stated guarantee.
* ``half_batch``: the second half of the batch gets no answer (zeros);
  a batch of one loses its only query.
* ``alter_answer``: one bit of the answer to the batch's first query is
  flipped where it is produced: in the first server's response on a mask
  wire, in the response of the slot that holds the real index on an
  index wire.

On a mask wire only (Chor, Sparse-PIR):

* ``thin_masks``: every server's mask but the last is ANDed with the next
  one, and the last is set so that the masks still XOR to the one-hot
  query: the bytes come back right, but each server sees a mask of the
  wrong density (theta squared), the privacy a faster, sparser query
  would give away.
* ``merge_servers``: the last two servers' masks are XORed into one and
  sent to one server: the bytes come back right from d - 1 servers, and
  a coalition of fewer than the stated d_a now learns the query.

On an index wire only (Direct Requests), each keeping the bytes right:

* ``no_dummies``: every one of a lookup's p slots asks the real index, so
  every server learns it.
* ``adjacent_dummies``: the dummies become q + 1, ..., q + p - 1 mod n, in
  the same slots: distinct, but they point at the real index.
* ``fixed_slot``: the real index is swapped into server 0's first slot
  (and the responses swapped back), so server 0 always holds it.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Tuple

#: the wire each fault applies to (None: either)
WIRE = {"drop_server": None, "half_batch": None, "alter_answer": None,
        "thin_masks": "mask", "merge_servers": "mask",
        "no_dummies": "index", "adjacent_dummies": "index",
        "fixed_slot": "index"}
FAULTS = tuple(WIRE)


def applies(fault: str, wire: str) -> bool:
    return WIRE[fault] in (None, wire)


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


def _flat(a):
    """[d, B, k, ...] -> [B, d * k, ...]: each lookup's slots, server-major."""
    import jax.numpy as jnp

    d, b, k = a.shape[:3]
    return jnp.moveaxis(a, 1, 0).reshape(b, d * k, *a.shape[3:])


def _unflat(a, d):
    import jax.numpy as jnp

    b, p = a.shape[:2]
    return jnp.moveaxis(a.reshape(b, d, p // d, *a.shape[2:]), 0, 1)


def _no_dummies(payload, q_idx):
    import jax.numpy as jnp

    return jnp.broadcast_to(q_idx.astype(payload.dtype)[None, :, None],
                            payload.shape)


def _adjacent_dummies(payload, q_idx, n):
    import jax.numpy as jnp

    x = _flat(payload)
    q = q_idx.astype(x.dtype)[:, None]
    real = x == q
    # the j-th dummy slot of a lookup, in slot order, asks q + j mod n
    rank = jnp.cumsum(~real, axis=1, dtype=x.dtype)
    return _unflat(jnp.where(real, x, (q + rank) % n), payload.shape[0])


def _slot_swap(payload, q_idx):
    """Per lookup, the slot order with the real index's slot and slot 0
    exchanged (its own inverse)."""
    import jax.numpy as jnp

    x = _flat(payload)
    at = jnp.argmax(x == q_idx.astype(x.dtype)[:, None], axis=1)
    order = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape)
    order = jnp.where(order == at[:, None], 0, order)
    return order.at[:, 0].set(at)


def _take_slots(a, order):
    import jax.numpy as jnp

    flat = _flat(a)
    idx = order.reshape(order.shape + (1,) * (flat.ndim - 2))
    return _unflat(jnp.take_along_axis(flat, idx, axis=1), a.shape[0])


def _alter_real(out, payload, q_idx):
    hit = payload == q_idx.astype(payload.dtype)[None, :, None]
    first = hit.at[:, 1:].set(False)
    return out.at[..., 0].set(out[..., 0] ^ first.astype(out.dtype))


def _with_payload(fn):
    import jax

    jitted = jax.jit(fn)

    def ask(routed):
        return dataclasses.replace(
            routed, payload=jitted(routed.payload, routed.q_idx))
    return ask


def breaker(fault: Optional[str], n: int) -> Tuple[Callable, Callable]:
    """(what the answer stage is asked, what it returns given the batch
    the pipeline sent) under ``fault``, on a store of ``n`` records. A
    fault leaves a batch on a wire it does not apply to as it is."""
    import jax

    ask, ret = (lambda routed: routed), (lambda out, sent: out)
    if fault is None:
        pass
    elif fault not in WIRE:
        raise ValueError(f"unknown fault {fault!r}; have {FAULTS}")
    elif fault == "drop_server":
        ret = lambda out, sent: out.at[-1].set(0)  # noqa: E731
    elif fault == "half_batch":
        ret = lambda out, sent: out.at[:, out.shape[1] // 2:].set(0)  # noqa: E731
    elif fault == "alter_answer":
        alter_real = jax.jit(_alter_real)

        def ret(out, sent):
            if sent.kind == "index":
                return alter_real(out, sent.payload, sent.q_idx)
            first = (0,) * out.ndim
            return out.at[first].set(out[first] ^ 1)
    elif fault == "thin_masks":
        thin = jax.jit(_thin_payload)

        def ask(routed):
            return dataclasses.replace(routed, payload=thin(routed.payload))
    elif fault == "merge_servers":
        merge = jax.jit(_merge_payload)

        def ask(routed):
            return dataclasses.replace(
                routed, payload=merge(routed.payload),
                servers=routed.servers[:-1])
    elif fault == "no_dummies":
        ask = _with_payload(_no_dummies)
    elif fault == "adjacent_dummies":
        ask = _with_payload(lambda p, q: _adjacent_dummies(p, q, n))
    elif fault == "fixed_slot":
        def swap(a, payload, q_idx):
            return _take_slots(a, _slot_swap(payload, q_idx))

        swap_out = jax.jit(swap)
        ask = _with_payload(lambda p, q: swap(p, p, q))

        def ret(out, sent):
            return swap_out(out, sent.payload, sent.q_idx)
    if fault is None or WIRE[fault] is None:
        return ask, ret
    wire = WIRE[fault]
    return ((lambda routed: ask(routed) if routed.kind == wire else routed),
            (lambda out, sent: ret(out, sent) if sent.kind == wire else out))
