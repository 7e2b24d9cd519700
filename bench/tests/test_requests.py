"""The request guarantee of Direct Requests: the probe's reduction on
the device counts what ``references/requests.py`` counts, the counts
tell a sound wire from each index fault's, and a configuration that
states no guarantee the harness knows is refused before set-up."""

import functools

import numpy as np
import pytest

import faults
import harness
from references import requests
from test_correct import direct_spec

N, D, P, B = 1024, 4, 8, 64


@pytest.fixture(scope="module")
def on_device():
    import jax

    return jax.jit(functools.partial(harness.request_counts, n=N,
                                     bins=requests.BINS))


def direct_batch(seed, n=N, d=D, p=P, b=B):
    """A batch as the program's Direct Requests plans it: payload [d, B,
    p / d] and the real indices [B]."""
    import jax
    import jax.numpy as jnp
    from repro.core import direct

    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.integers(0, n, b), jnp.int32)
    return direct.gen_queries(jax.random.PRNGKey(seed), n, d, p, q), q


def faulted(fault, payload, q):
    from repro.core.protocol import Queries

    ask, _ = faults.breaker(fault, n=N)
    return ask(Queries("index", payload, tuple(range(D)), q)).payload


def assert_same(device, payload, q):
    bad, counts = requests.reduce_batch(np.asarray(payload), np.asarray(q), N)
    got = {k: np.asarray(v) for k, v in device.items()}
    assert int(got.pop("violations")) == len(bad)
    assert set(got) == set(counts)
    for key in counts:
        assert np.array_equal(got[key], counts[key]), key
    return bad, counts


@pytest.mark.parametrize("fault", [None, "no_dummies", "adjacent_dummies",
                                   "fixed_slot"])
@pytest.mark.parametrize("seed", [3, 2**31 + 5])
def test_device_counts_equal_the_reference(fault, seed, on_device):
    payload, q = direct_batch(seed)
    if fault is not None:
        payload = faulted(fault, payload, q)
    bad, _ = assert_same(on_device(payload, q), payload, q)
    assert (len(bad) == B) if fault == "no_dummies" else not bad


def test_sound_batches_spread_as_algorithm_4_1_says(on_device):
    counts = [requests.reduce_batch(*map(np.asarray, direct_batch(s)), N)[1]
              for s in range(20)]
    pooled = requests.pool(counts)
    assert int(pooled["lookups"]) == 20 * B
    assert int(pooled["servers"].sum()) == 20 * B
    assert 0 < requests.spread_z(pooled, N, P) < 5


@pytest.mark.parametrize("fault,statistic", [
    ("adjacent_dummies", "offsets"), ("fixed_slot", "servers")])
def test_each_fault_trips_its_statistic(fault, statistic):
    counts = []
    for s in range(4):
        payload, q = direct_batch(s)
        payload = faulted(fault, payload, q)
        counts.append(requests.reduce_batch(np.asarray(payload),
                                            np.asarray(q), N)[1])
    z = requests.zs(requests.pool(counts), N, P)
    assert np.abs(z[statistic]).max() > 8


def test_dummies_from_half_the_store_trip_the_range_counts():
    rng = np.random.default_rng(0)
    lookups = 4 * B
    q = rng.integers(0, N, lookups)
    payload = np.empty((D, lookups, P // D), np.int64)
    for b in range(lookups):
        pool = np.setdiff1d(np.arange(N // 2), [q[b]])
        slots = np.concatenate([[q[b]], rng.choice(pool, P - 1, replace=False)])
        payload[:, b, :] = rng.permutation(slots).reshape(D, P // D)
    bad, counts = requests.reduce_batch(payload, q, N)
    assert not bad
    z = requests.zs(counts, N, P)
    assert np.abs(z["dummies"]).max() > 8


@pytest.mark.parametrize("slots,why", [
    ([5, 1, 2, 3, 4, 6, 7, 8], None),
    ([5, 5, 2, 3, 4, 6, 7, 8], "the real index twice"),
    ([9, 1, 2, 3, 4, 6, 7, 8], "no real index"),
    ([5, 1, 1, 3, 4, 6, 7, 8], "a dummy twice"),
    ([5, 1, 2, 3, 4, 6, 7, N], "an id outside the store"),
    ([5, 1, 2, 3, 4, 6, 7, -1], "a negative id"),
])
def test_violations(slots, why, on_device):
    payload = np.asarray(slots, np.int32).reshape(D, 1, P // D)
    q = np.asarray([5], np.int32)
    bad, _ = assert_same(on_device(payload, q), payload, q)
    assert bad == ([] if why is None else [0])


def test_the_server_holding_the_real_index_is_counted():
    payload = np.arange(P).reshape(D, 1, P // D)
    _, counts = requests.reduce_batch(payload, [P - 1], N)
    assert counts["servers"].tolist() == [0, 0, 0, 1]
    assert counts["offsets"].sum() == counts["dummies"].sum() == P - 1


@pytest.mark.parametrize("guarantee,limits,message", [
    ({}, None, "exactly one"),
    ({"masks": {"kind": "chor"}, "requests": {"kind": "direct", "p": 8}},
     None, "exactly one"),
    ({"requests": {"kind": "naive", "p": 8}}, None, "kind"),
    ({"requests": {"kind": "direct", "p": 6}}, None, "multiple of d"),
    ({"masks": {"kind": "subset"}}, {"mask_density_z": 8.0}, "mask density"),
    ({"requests": {"kind": "direct", "p": 8}}, {"mask_density_z": 8.0},
     "request_spread_z"),
])
def test_an_unknown_guarantee_fails_before_set_up(guarantee, limits, message,
                                                  monkeypatch):
    spec = direct_spec()
    stated = spec["config"]["guarantee"]
    del stated["requests"]
    stated.update(guarantee)
    if limits is not None:
        spec["config"]["limits"] = limits
    load = harness.load_reference

    def reference(name):
        assert name != "ct_store", "set-up began: the store is being made"
        return load(name)

    monkeypatch.setattr(harness, "load_reference", reference)
    with pytest.raises(ValueError, match=message):
        harness.Session(spec, seed=1, traced=False)
