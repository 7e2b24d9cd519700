"""Plain reference for a store of fixed-width records: what record i holds.

The benchmark makes every store from ``--seed`` with a counter-based hash:
word w of record i is ``mix(mix(i + k0) ^ (w * GOLDEN + k1))``, where
``mix`` is a 32-bit integer finalizer and (k0, k1) come from the seed.
:func:`device_store` computes the whole [n, W] uint32 store on the device
in one jitted call. :func:`expected_rows` computes any records on the host
with numpy, and :func:`expected_bytes` turns them into record bytes the
way a little-endian wire carries them. The two are separate
implementations of one formula, so a served answer is checked against
rows that nothing served has touched. Nothing here imports the program.
"""

from __future__ import annotations

import numpy as np

GOLDEN = 0x9E3779B9
_M1 = 0x7FEB352D
_M2 = 0x846CA68B
MASK32 = 0xFFFFFFFF


def _mix_np(x: np.ndarray) -> np.ndarray:
    x = x ^ (x >> np.uint32(16))
    x = x * np.uint32(_M1)
    x = x ^ (x >> np.uint32(15))
    x = x * np.uint32(_M2)
    return x ^ (x >> np.uint32(16))


def seed_keys(seed: int) -> tuple:
    """(k0, k1): two 32-bit keys from a seed of any size."""
    lo = np.uint32(seed & MASK32)
    hi = np.uint32((seed >> 32) & MASK32)
    with np.errstate(over="ignore"):
        k0 = _mix_np(np.asarray([lo ^ np.uint32(0x5BD1E995)], np.uint32))[0]
        k1 = _mix_np(np.asarray([hi ^ k0], np.uint32))[0]
    return int(k0), int(k1)


def expected_rows(seed: int, indices, words: int) -> np.ndarray:
    """[k, W] uint32: the records at ``indices`` of the store made from
    ``seed``."""
    k0, k1 = seed_keys(seed)
    rows = np.asarray(indices, np.uint32).reshape(-1, 1)
    cols = np.arange(words, dtype=np.uint32).reshape(1, -1)
    with np.errstate(over="ignore"):
        a = _mix_np(rows + np.uint32(k0))
        return _mix_np(a ^ (cols * np.uint32(GOLDEN) + np.uint32(k1)))


def expected_bytes(seed: int, indices, words: int, nbytes: int) -> np.ndarray:
    """[k, nbytes] uint8: the record bytes at ``indices``, word by word in
    little-endian order."""
    rows = expected_rows(seed, indices, words).astype("<u4")
    return rows.view(np.uint8).reshape(len(rows), words * 4)[:, :nbytes]


def device_store(seed: int, n: int, words: int):
    """The whole [n, W] uint32 store, made on the default device in one
    jitted call."""
    import jax
    import jax.numpy as jnp

    k0, k1 = seed_keys(seed)

    def mix(x):
        x = x ^ (x >> 16)
        x = x * jnp.uint32(_M1)
        x = x ^ (x >> 15)
        x = x * jnp.uint32(_M2)
        return x ^ (x >> 16)

    @jax.jit
    def make(keys):
        rows = jax.lax.broadcasted_iota(jnp.uint32, (n, words), 0)
        cols = jax.lax.broadcasted_iota(jnp.uint32, (n, words), 1)
        return mix(mix(rows + keys[0]) ^ (cols * jnp.uint32(GOLDEN) + keys[1]))

    return make(jnp.asarray([k0, k1], jnp.uint32))
