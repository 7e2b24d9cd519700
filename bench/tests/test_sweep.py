"""``bench/sweep.py``'s knee rule on made-up backlogs: a queue that holds
passes at any rate, one that outgrows its load fails, and the sweep that
fixed ``ct_chor.poisson``'s rate reads the same knee."""

import math

import numpy as np
import pytest

import sweep
import traffic
from traffic import Lookup


def served(rate, cap, period, seconds, seed):
    """The benchmark's Poisson arrivals at ``rate`` a second over
    ``seconds`` (gaps from ``seed``), cut every ``period`` seconds into a
    batch of at most ``cap`` of the oldest lookups waiting, each answered
    at its cut."""
    t = traffic.offsets({"arrivals": {"process": "poisson", "rate_qps": rate,
                                      "gap_seed": seed, "order": "fixed"}},
                        seconds, seed)
    done = np.full(t.size, math.nan)
    head, cut = 0, period
    while head < t.size and cut < seconds + 60:
        take = min(cap, int(np.searchsorted(t, cut, side="right")) - head)
        done[head:head + take] = cut
        head, cut = head + take, cut + period
    return [Lookup("c", (0,), a, a, b) for a, b in zip(t, done)]


def holds(lookups, seconds):
    grown = sweep.growth(lookups, 0.0, seconds)
    return grown < sweep.growth_limit(len(lookups))


def test_a_stationary_backlog_of_a_batch_holds():
    # 500 lookups a second, all that wait cut every 0.3 s: the backlog
    # swings by a batch of ~150 and does not grow
    held = sum(holds(served(500.0, 10**6, 0.3, 40.0, seed), 40.0)
               for seed in range(100))
    assert held >= 99


@pytest.mark.parametrize("seed", range(5))
def test_a_queue_that_grows_by_two_percent_of_its_load_fails(seed):
    # capacity 150 every 0.3 s (500/s) against 2% more offered
    assert not holds(served(500.0 / 0.98, 150, 0.3, 40.0, seed), 40.0)


@pytest.mark.parametrize("rate", [2.0, 2.25, 2.5, 2.75, 3.0, 3.5, 4.0])
def test_the_low_rates_of_the_chor_sweep_keep_two_lookups(rate):
    mix = dict(traffic.load("poisson_knee"))
    mix["arrivals"] = dict(mix["arrivals"], rate_qps=rate)
    sent = len(traffic.offsets(mix, 40.0, seed=5200000040))
    assert sweep.growth_limit(sent) == sweep.GROWTH_FLOOR == 2.0


def test_the_chor_sweep_reads_the_same_knee():
    # the sweep that fixed poisson_knee (40 s a rate): rate, backlog growth
    grown = {2.0: -0.11, 2.25: 0.80, 2.5: -0.00, 2.75: 4.26, 3.0: 1.96,
             3.5: 9.59}
    rows = [{"rate_qps": r, "sent": round(r * 40), "backlog_growth": g}
            for r, g in grown.items()]
    assert sweep.knee(rows) == 2.5


def test_backlog_counts_sent_and_not_yet_answered():
    lks = [Lookup("c", (0,), 0.0, 0.0, 2.0), Lookup("c", (0,), 1.0, 1.0, 1.5),
           Lookup("c", (0,), 3.0, 3.0, math.nan)]
    got = sweep.backlog(lks, np.array([0.5, 1.0, 1.7, 2.5, 4.0]))
    assert got.tolist() == [1, 2, 1, 0, 1]
