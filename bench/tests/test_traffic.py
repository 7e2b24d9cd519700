"""The traffic generator: what each mix file draws, and how the two loops
send it."""

import threading
import time
from concurrent.futures import Future
from pathlib import Path

import numpy as np
import pytest

import traffic

MIXES = sorted(p.stem for p in (Path(traffic.HERE) / "traffic").glob("*.json"))


@pytest.mark.parametrize("name", MIXES)
def test_every_mix_file_draws(name):
    mix = traffic.load(name)
    reqs = traffic.draw(mix, 1000, 64, seed=2**33 + 5)
    assert len(reqs) == 64
    assert all(0 <= i < 1000 for _, idx in reqs for i in idx)
    assert all(len(idx) == int(mix.get("k", 1)) for _, idx in reqs)
    assert traffic.draw(mix, 1000, 64, seed=2**33 + 5) == reqs
    assert traffic.draw(mix, 1000, 64, seed=2**33 + 6) != reqs
    if mix["loop"] == "open":
        t = traffic.offsets(mix, 10.0, seed=1)
        assert np.all((t >= 0) & (t < 10.0)) and np.all(np.diff(t) >= 0)


def test_draw_follows_population():
    mix = {"clients": 10, "zipf_a": 1.3, "repoll_p": 1.0, "k": 1}
    for client, (idx,) in traffic.draw(mix, 1000, 200, seed=3):
        who = int(client[1:])
        assert idx == (who * 131 + 17) % 1000


def test_poisson_seeds_reorder_one_set_of_gaps():
    mix = {"loop": "open",
           "arrivals": {"process": "poisson", "rate_qps": 7.0, "gap_seed": 0}}
    a = traffic.offsets(mix, 30.0, seed=1)
    b = traffic.offsets(mix, 30.0, seed=2)
    assert len(a) == len(b) == 210
    assert not np.allclose(a, b)
    gaps = lambda t: np.sort(np.diff(np.concatenate([[0.0], t])))  # noqa: E731
    # the same gaps, save the one that closes the window
    assert np.isin(np.round(gaps(a), 9), np.round(np.sort(np.diff(
        np.concatenate([[0.0], b, [30.0]]))), 9)).sum() >= len(a) - 1


def test_poisson_fixed_order_is_one_schedule_for_every_seed():
    mix = {"loop": "open",
           "arrivals": {"process": "poisson", "rate_qps": 7.0, "gap_seed": 3,
                        "order": "fixed"}}
    a = traffic.offsets(mix, 30.0, seed=1)
    b = traffic.offsets(mix, 30.0, seed=2**33 + 5)
    assert len(a) == 210 and np.array_equal(a, b)
    other = dict(mix, arrivals=dict(mix["arrivals"], gap_seed=4))
    assert not np.allclose(a, traffic.offsets(other, 30.0, seed=1))
    with pytest.raises(ValueError):
        traffic.offsets({"arrivals": dict(mix["arrivals"], order="sorted")},
                        30.0, seed=1)


def test_bursty_rate_follows_duty():
    mix = {"loop": "open", "arrivals": {"process": "bursty", "base_qps": 5.0,
                                        "burst_qps": 50.0, "period_s": 1.0,
                                        "duty": 0.2}}
    t = traffic.offsets(mix, 200.0, seed=4)
    burst = ((t % 1.0) < 0.2).sum()
    assert burst / 200 / 0.2 == pytest.approx(50, rel=0.1)
    assert (len(t) - burst) / 200 / 0.8 == pytest.approx(5, rel=0.15)


class Server:
    """Answers each submit after ``delay_s`` on its own thread."""

    def __init__(self, delay_s):
        self.delay_s = delay_s
        self.inflight = self.peak = 0
        self.mu = threading.Lock()

    def submit(self, client, indices):
        fut = Future()
        with self.mu:
            self.inflight += 1
            self.peak = max(self.peak, self.inflight)

        def answer():
            time.sleep(self.delay_s)
            with self.mu:
                self.inflight -= 1
            fut.set_result(np.zeros(4, np.uint8))

        threading.Thread(target=answer, daemon=True).start()
        return fut


def test_closed_loop_keeps_outstanding():
    srv = Server(0.01)
    drv = traffic.Driver(srv.submit, lambda: None)
    mix = {"loop": "closed", "outstanding": 5, "clients": 10, "zipf_a": 1.3,
           "repoll_p": 0.2}
    drv.run(mix, traffic.draw(mix, 100, 10_000, 0), 0.5, seed=0)
    assert drv.wait_all(5.0) == 0
    assert srv.peak == 5
    assert 100 < len(drv.lookups) < 300
    assert all(lk.answer is not None for lk in drv.lookups)


def test_open_loop_sends_on_schedule():
    srv = Server(0.001)
    drv = traffic.Driver(srv.submit, lambda: None)
    mix = {"loop": "open", "arrivals": {"process": "poisson", "rate_qps": 100.0},
           "clients": 10, "zipf_a": 1.3, "repoll_p": 0.2}
    drv.run(mix, traffic.draw(mix, 100, 1000, 0), 1.0, seed=0)
    assert drv.wait_all(5.0) == 0
    assert len(drv.lookups) == 100
    late = [lk.t_submit - lk.t_sched for lk in drv.lookups]
    assert max(late) < 0.05
    assert drv.t_close - drv.t_open == pytest.approx(1.0, abs=0.05)
