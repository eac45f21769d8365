import asyncio
import time
from dataclasses import dataclass

import numpy as np

from chipbench import traffic


@dataclass
class _Resp:
    ids: np.ndarray
    dists: np.ndarray
    queue_wait_ms: float = 0.0
    compute_ms: float = 1.0


class FakeTier:
    """Answers each request ``service_s`` after it is submitted; the
    submit numbered ``stall_at`` blocks the loop for ``stall_s`` first."""

    def __init__(self, service_s=0.002, stall_at=None, stall_s=0.0):
        self.service_s, self.stall_at, self.stall_s = (service_s, stall_at,
                                                       stall_s)
        self.n = 0
        self.in_flight = 0
        self.max_in_flight = 0

    def submit(self, query, tenant):
        loop = asyncio.get_running_loop()
        if self.n == self.stall_at:
            time.sleep(self.stall_s)
        self.n += 1
        self.in_flight += 1
        self.max_in_flight = max(self.max_in_flight, self.in_flight)
        fut = loop.create_future()

        def done():
            self.in_flight -= 1
            fut.set_result(_Resp(np.arange(3), np.zeros(3)))
        loop.call_later(self.service_s, done)
        return fut


POOL = np.zeros((16, 4), np.float32)


def test_open_loop_counts_a_stall_against_the_requests_due_after_it():
    rate, seconds, stall = 200.0, 1.0, 0.25
    offsets = np.arange(int(rate * seconds)) / rate
    tier = FakeTier(stall_at=50, stall_s=stall)
    win = asyncio.run(traffic.open_loop(tier, "t", POOL, np.arange(16),
                                        offsets=offsets, seconds=seconds))
    t_stall = win.requests[50].due
    lat = np.array([r.latency_ms() for r in win.requests])
    during = [i for i, r in enumerate(win.requests)
              if t_stall < r.due < t_stall + stall - 0.05]
    assert len(during) > 20
    for i in during:
        # sent only after the stall, yet timed from when it was due
        assert lat[i] >= (t_stall + stall - win.requests[i].due) * 1e3 - 1
    assert np.median(lat[:40]) < 50
    assert all(r.answered for r in win.requests)


def test_open_offsets_same_gaps_for_every_seed():
    a = traffic.open_offsets(300.0, 4.0, np.random.default_rng(1))
    b = traffic.open_offsets(300.0, 4.0, np.random.default_rng(2))
    assert len(a) == len(b) == 1200
    assert a[0] == 0.0 and a[-1] < 4.0 and np.all(np.diff(a) > 0)
    gaps = [np.sort(np.diff(np.append(x, 4.0))) for x in (a, b)]
    np.testing.assert_allclose(gaps[0], gaps[1], rtol=1e-9)
    assert not np.allclose(a, b)


def test_closed_loop_keeps_at_most_its_clients_in_flight():
    tier = FakeTier(service_s=0.003)
    win = asyncio.run(traffic.closed_loop(tier, "t", POOL, np.arange(16),
                                          clients=8, seconds=0.3))
    assert tier.max_in_flight == 8
    assert len(win.requests) > 100
    assert all(r.answered for r in win.requests)
