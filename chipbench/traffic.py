"""The one load generator: a closed or an open loop over the serving tier,
parameterised by a mix file (``traffic/<name>.json``).

- ``closed``: ``clients_per_slot * max_batch`` clients, each sending its
  next request when its reply arrives.
- ``open``: arrivals on a fixed schedule at ``rate_per_s``.  Every seed
  gets the same multiset of gaps (the quantiles of an exponential
  distribution), in its own order, so seeds differ in order and not in
  how bursty the load is.  Each request is timed from when it was due,
  so a stall delays the requests due after it as well.

Queries cycle through the held-out pool in a seeded order.  The window
is ``seconds`` long; requests still in flight when it closes are awaited
(at most ``GRACE_S`` more) so that every answer can be checked.
"""
from __future__ import annotations

import asyncio
import math
import time
from dataclasses import dataclass, field

import numpy as np

#: how long past the window's close an answer is still waited for
GRACE_S = 60.0


@dataclass
class Request:
    qi: int                          # index into the query pool
    due: float                       # perf_counter seconds
    sent: float = math.nan
    done: float = math.nan
    ids: np.ndarray | None = None
    dists: np.ndarray | None = None
    queue_wait_ms: float = math.nan  # the tier's own per-response split
    compute_ms: float = math.nan
    error: str | None = None
    refused: bool = False            # rejected at the door: failed, not wrong

    @property
    def answered(self) -> bool:
        return self.ids is not None

    def latency_ms(self) -> float:
        """Due time to reply; a request with no answer never met a limit."""
        return (self.done - self.due) * 1e3 if self.answered else math.inf


@dataclass
class Window:
    t0: float
    seconds: float
    requests: list = field(default_factory=list)

    @property
    def t1(self) -> float:
        return self.t0 + self.seconds


def query_order(n_pool: int, rng: np.random.Generator) -> np.ndarray:
    return rng.permutation(n_pool)


def open_offsets(rate: float, seconds: float,
                 rng: np.random.Generator) -> np.ndarray:
    """Due offsets in [0, seconds): ``round(rate * seconds)`` arrivals
    whose gaps are the exponential quantiles at (i + 1/2) / n, shuffled."""
    n = max(1, round(rate * seconds))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    gaps = rng.permutation(gaps)
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return due * (seconds / gaps.sum())


def _finish(req: Request, fut: asyncio.Future, clock) -> None:
    req.done = clock()
    if fut.cancelled():
        req.error = "cancelled"
        return
    exc = fut.exception()
    if exc is not None:
        req.error = f"{type(exc).__name__}: {exc}"
        return
    resp = fut.result()
    req.ids, req.dists = resp.ids, resp.dists
    req.queue_wait_ms, req.compute_ms = resp.queue_wait_ms, resp.compute_ms


def _submit(tier, tenant, query, req: Request, clock):
    """Send one request; a rejection at the door is a failed request."""
    req.sent = clock()
    try:
        fut = tier.submit(query, tenant)
    except Exception as e:                 # typed rejections of the tier
        req.error = f"{type(e).__name__}: {e}"
        req.refused = True
        req.done = clock()
        return None
    fut.add_done_callback(lambda f: _finish(req, f, clock))
    return fut


async def _await_all(futs, deadline: float, clock) -> None:
    pending = [f for f in futs if f is not None]
    if pending:
        await asyncio.wait(pending, timeout=max(0.0, deadline - clock()))


async def closed_loop(tier, tenant: str, pool: np.ndarray, order, *,
                      clients: int, seconds: float, clock=time.perf_counter,
                      on_start=None) -> Window:
    win = Window(clock(), seconds)
    if on_start is not None:
        on_start(win)
    cursor = iter(range(1 << 62))
    futs = []

    async def client():
        while clock() < win.t1:
            i = next(cursor)
            req = Request(int(order[i % len(order)]), due=clock())
            win.requests.append(req)
            fut = _submit(tier, tenant, pool[req.qi], req, clock)
            if fut is None:
                await asyncio.sleep(0)
                continue
            futs.append(fut)
            await asyncio.wait([fut], timeout=max(
                0.0, win.t1 + GRACE_S - clock()))

    tasks = [asyncio.ensure_future(client()) for _ in range(clients)]
    await asyncio.gather(*tasks)
    await _await_all(futs, win.t1 + GRACE_S, clock)
    return win


async def open_loop(tier, tenant: str, pool: np.ndarray, order, *,
                    offsets: np.ndarray, seconds: float,
                    clock=time.perf_counter, on_start=None) -> Window:
    win = Window(clock(), seconds)
    if on_start is not None:
        on_start(win)
    futs = []
    for i, off in enumerate(offsets):
        req = Request(int(order[i % len(order)]), due=win.t0 + float(off))
        wait = req.due - clock()
        if wait > 0:
            await asyncio.sleep(wait)
        win.requests.append(req)
        futs.append(_submit(tier, tenant, pool[req.qi], req, clock))
    await _await_all(futs, win.t1 + GRACE_S, clock)
    return win


def clients_for(mix: dict, max_batch: int) -> int:
    return int(mix["clients_per_slot"]) * max_batch


async def drive(mix: dict, tier, tenant: str, pool: np.ndarray, *,
                max_batch: int, seconds: float, rng: np.random.Generator,
                clock=time.perf_counter, on_start=None) -> Window:
    """One window of the mix ``mix`` against a started tier."""
    order = query_order(len(pool), rng)
    if mix["kind"] == "closed":
        return await closed_loop(tier, tenant, pool, order,
                                 clients=clients_for(mix, max_batch),
                                 seconds=seconds, clock=clock,
                                 on_start=on_start)
    if mix["kind"] == "open":
        offsets = open_offsets(float(mix["rate_per_s"]), seconds, rng)
        return await open_loop(tier, tenant, pool, order, offsets=offsets,
                               seconds=seconds, clock=clock,
                               on_start=on_start)
    raise ValueError(f"unknown traffic kind {mix['kind']!r}")


def lateness_ms(win: Window) -> np.ndarray:
    """(n, 2): when each sent request was due, in seconds into the
    window, and how late the generator sent it, in ms."""
    return np.array([(r.due - win.t0, (r.sent - r.due) * 1e3)
                     for r in win.requests if not math.isnan(r.sent)]
                    ).reshape(-1, 2)
