"""The one traffic generator: reads a mix from ``bench/traffic/<name>.json``.

A mix file holds parameters only:

    {"loop": "closed", "outstanding": 8,            # or
     "loop": "open", "arrivals": {"process": "poisson", "rate_qps": 6.0,
                                  "gap_seed": 0, "order": "seed"},
     "clients": 10000, "zipf_a": 1.3, "repoll_p": 0.2, "k": 1}

Who asks for what follows the fleet's client population (copied from
``repro.fleet.clients.ClientPopulation.draw``): a Zipf(a) popularity over
records, except that each client re-polls its own hot record with
probability ``repoll_p``. ``k`` indices go out as one request.

A closed loop keeps ``outstanding`` requests in flight and sends the next
one as each answer arrives. An open loop sends on a schedule drawn ahead
of time and never waits. Poisson arrivals use one fixed set of gaps for
every seed (drawn from ``gap_seed``), in an order the seed draws, so seeds
change the order and not the amount of work; with ``"order": "fixed"``
every seed keeps the gaps' own order, so only who asks for what changes
with the seed (near the knee the order alone moves the latency tails far
more than the records asked for do). Bursty arrivals (on/off modulated Poisson, copied from
``repro.fleet.arrivals.BurstyArrivals``) are thinned from the seed.
"""

from __future__ import annotations

import dataclasses
import json
import math
import queue
import time
from pathlib import Path
from typing import Callable, List, Optional, Tuple

import numpy as np

HERE = Path(__file__).resolve().parent


def load(name: str) -> dict:
    with open(HERE / "traffic" / f"{name}.json") as f:
        return json.load(f)


def draw(mix: dict, n_records: int, count: int, seed: int) -> List[Tuple[str, tuple]]:
    """``count`` requests of ``(client, indices)``, from ``seed``."""
    k = int(mix.get("k", 1))
    n_clients = int(mix["clients"])
    rng = np.random.default_rng(seed)
    who = rng.integers(0, n_clients, size=count)
    popular = (rng.zipf(float(mix["zipf_a"]), size=(count, k)) - 1) % n_records
    hot = ((who * 131 + 17) % n_records)[:, None]
    repoll = rng.random((count, k)) < float(mix["repoll_p"])
    idx = np.where(repoll, hot, popular)
    return [(f"c{int(w):06d}", tuple(int(i) for i in row))
            for w, row in zip(who, idx)]


def _poisson_offsets(rate: float, seconds: float, seed: int,
                     gap_seed: int, order: str) -> np.ndarray:
    n = int(round(rate * seconds))
    gaps = np.random.default_rng(gap_seed).exponential(1.0 / rate, size=n + 1)
    if order == "seed":
        gaps = np.random.default_rng(seed).permutation(gaps)
    elif order != "fixed":
        raise ValueError(f"unknown Poisson gap order {order!r}")
    return np.cumsum(gaps)[:n] * (seconds / gaps.sum())


def _bursty_offsets(spec: dict, seconds: float, seed: int) -> np.ndarray:
    base, burst = float(spec["base_qps"]), float(spec["burst_qps"])
    period, duty = float(spec.get("period_s", 1.0)), float(spec.get("duty", 0.2))
    peak = max(base, burst)
    rng = np.random.default_rng(seed)
    expect = peak * seconds
    t = np.cumsum(rng.exponential(1.0 / peak,
                                  size=int(expect + 6 * math.sqrt(expect) + 16)))
    t = t[t < seconds]
    rate = np.where((t % period) < duty * period, burst, base)
    return t[rng.random(t.size) * peak < rate]


def offsets(mix: dict, seconds: float, seed: int) -> np.ndarray:
    """An open loop's arrival offsets in [0, seconds)."""
    spec = mix["arrivals"]
    if spec["process"] == "poisson":
        return _poisson_offsets(float(spec["rate_qps"]), seconds, seed,
                                int(spec.get("gap_seed", 0)),
                                spec.get("order", "seed"))
    if spec["process"] == "bursty":
        return _bursty_offsets(spec, seconds, seed)
    raise ValueError(f"unknown arrival process {spec['process']!r}")


@dataclasses.dataclass
class Lookup:
    client: str
    indices: tuple
    t_sched: float
    t_submit: float = math.nan
    t_done: float = math.nan
    tag: object = None       # which resolve call answered it
    answer: Optional[np.ndarray] = None
    error: Optional[str] = None


class Driver:
    """Sends one mix into ``submit(client, indices) -> Future`` and keeps
    every lookup it sent. ``tag()`` is read when an answer lands and names
    the batch that answered it."""

    def __init__(self, submit: Callable, tag: Callable[[], object],
                 clock: Callable[[], float] = time.perf_counter):
        self.submit = submit
        self.tag = tag
        self.clock = clock
        self.lookups: List[Lookup] = []
        self.done: "queue.SimpleQueue[int]" = queue.SimpleQueue()
        self.t_open = self.t_close = math.nan

    def send(self, client: str, indices: tuple, t_sched: float) -> None:
        lk = Lookup(client, indices, t_sched)
        pos = len(self.lookups)
        self.lookups.append(lk)
        lk.t_submit = self.clock()
        try:
            fut = self.submit(client, indices)
        except Exception as exc:  # refused at the door: never answered
            lk.error = f"{type(exc).__name__}: {exc}"
            lk.t_done = self.clock()
            self.done.put(pos)
            return

        def landed(f) -> None:
            t = self.clock()
            lk.tag = self.tag()
            if f.cancelled():
                lk.error = "cancelled"
            elif f.exception() is not None:
                exc = f.exception()
                lk.error = f"{type(exc).__name__}: {exc}"
            else:
                lk.answer = np.asarray(f.result())
            lk.t_done = t  # last: a set t_done means the lookup is whole
            self.done.put(pos)

        fut.add_done_callback(landed)

    def run(self, mix: dict, requests: List[Tuple[str, tuple]],
            seconds: float, seed: int) -> None:
        """Send for ``seconds``; returns at the close, with lookups still
        in flight."""
        self.t_open = self.clock()
        close = self.t_open + seconds
        it = iter(requests)
        if mix["loop"] == "closed":
            for _ in range(int(mix["outstanding"])):
                self.send(*next(it), self.clock())
            while True:
                left = close - self.clock()
                if left <= 0:
                    break
                try:
                    self.done.get(timeout=left)
                except queue.Empty:
                    break
                if self.clock() < close:
                    self.send(*next(it), self.clock())
        elif mix["loop"] == "open":
            for at in offsets(mix, seconds, seed):
                due = self.t_open + float(at)
                wait = due - self.clock()
                if wait > 0:
                    time.sleep(wait)
                self.send(*next(it), due)
            left = close - self.clock()
            if left > 0:
                time.sleep(left)
        else:
            raise ValueError(f"unknown loop {mix['loop']!r}")
        self.t_close = self.clock()

    def wait_all(self, timeout_s: float) -> int:
        """Wait up to ``timeout_s`` for every lookup sent; returns how
        many are still unanswered."""
        end = self.clock() + timeout_s
        for lk in self.lookups:
            while math.isnan(lk.t_done):
                left = end - self.clock()
                if left <= 0:
                    return sum(math.isnan(x.t_done) for x in self.lookups)
                time.sleep(min(left, 0.01))
        return 0


def request_budget(mix: dict, seconds: float) -> int:
    """How many requests to draw so a run never runs out: an open loop
    sends its schedule, a closed loop at most one per answer."""
    if mix["loop"] == "open":
        spec = mix["arrivals"]
        peak = float(spec.get("rate_qps", 0) or max(spec.get("base_qps", 0),
                                                     spec.get("burst_qps", 0)))
        return int(peak * seconds * 2 + 64)
    return 200_000
