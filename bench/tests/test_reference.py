"""The store the benchmark makes and the reference that rebuilds it are
two implementations of one formula: they must agree on every word."""

import numpy as np
import pytest

from references import ct_store


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 3, 2**40 + 11])
def test_device_store_matches_reference(seed):
    packed = np.asarray(ct_store.device_store(seed, 300, 12))
    rows = np.arange(300)
    assert np.array_equal(packed, ct_store.expected_rows(seed, rows, 12))


def test_seeds_and_records_differ():
    a = ct_store.expected_rows(1, [0, 1, 2], 8)
    b = ct_store.expected_rows(2, [0, 1, 2], 8)
    assert not np.array_equal(a, b)
    assert len({tuple(r) for r in a}) == 3


def test_bytes_are_little_endian_words():
    words = ct_store.expected_rows(5, [9], 3)[0]
    raw = ct_store.expected_bytes(5, [9], 3, 12)[0]
    assert raw.dtype == np.uint8
    assert int(raw[0]) == int(words[0]) & 0xFF
    assert int(raw[7]) == int(words[1]) >> 24
