"""Arrivals, and the clients that drive ``AsyncServingEngine.submit``.

Two kinds of arrival, named by a traffic file's ``arrival.kind``:

* ``closed``: ``clients`` callers, each sending its next request as soon as
  the last one resolves, with no think time. Requests are taken from the
  stream in order. Latency is measured from the send.
* ``poisson``: an open loop. ``round(rate * seconds)`` requests are due in
  the window at times drawn as a Poisson process conditioned on that count
  (so every seed sends the same number). Each request is timed from when it was due, so a stall of
  the generator or the server counts against every request behind it.

The clients record one outcome per request and never raise. Their own
host work is marked for the trace: ``bench.generate`` (the open loop's
send) and ``bench.record`` (taking an answer).
"""
from __future__ import annotations

import asyncio
import time

import numpy as np
from jax.profiler import TraceAnnotation

# outcome codes
PENDING, OK, REJECTED, SHED, TIMEOUT, ERROR = range(6)
OUTCOME_NAMES = ("pending", "ok", "rejected", "shed", "timeout", "error")


def due_times(arrival: dict, seed: int, seconds: float) -> np.ndarray:
    """Due times in [0, seconds) of an open loop, ascending."""
    count = int(round(float(arrival["rate"]) * seconds))
    rng = np.random.default_rng([seed, 2])
    return np.sort(rng.random(count)) * seconds


class Log:
    """Per-request record of one run: times on ``time.monotonic``."""

    def __init__(self, N: int, k: int):
        # the program is imported here and not with this module: importing
        # it starts JAX's backend, which a run that must first build its
        # index in a child process may not do yet
        from repro import serve

        self._serve = serve
        self.t_due = np.full(N, np.nan)
        self.t_sent = np.full(N, np.nan)
        self.t_done = np.full(N, np.nan)
        self.outcome = np.zeros(N, np.int8)
        self.ids = np.full((N, k), -1, np.int64)
        self.dists = np.full((N, k), np.inf, np.float32)
        self.errors: list[str] = []
        self.sent = 0

    async def send(self, engine, reqs, j: int):
        sv = self._serve
        r = sv.Request(reqs.vectors[j], float(reqs.lo[j]),
                       float(reqs.hi[j]), reqs.k)
        self.t_sent[j] = time.monotonic()
        try:
            res = await engine.submit(r)
        except sv.OverloadedError:
            self.outcome[j] = REJECTED
        except sv.ShedError:
            self.outcome[j] = SHED
        except sv.DeadlineExceededError:
            self.outcome[j] = TIMEOUT
        except Exception as e:  # noqa: BLE001 — any other outcome is a fault
            self.outcome[j] = ERROR
            if len(self.errors) < 5:
                self.errors.append(repr(e))
        else:
            self.outcome[j] = OK
            with TraceAnnotation("bench.record"):
                kk = min(len(res.ids), reqs.k)
                self.ids[j, :kk] = res.ids[:kk]
                self.dists[j, :kk] = res.dists[:kk]
        self.t_done[j] = time.monotonic()


async def closed_loop(engine, reqs, log: Log, clients: int, t0: float,
                      seconds: float):
    """``clients`` callers, sending from ``t0`` until ``t0 + seconds``;
    returns once every request sent has resolved. The window closes by a
    timer of the event loop, so the callers whose answers one flush
    resolves all send again, or none does: the loop forms no partial
    batch, after the window either."""
    is_open = [True]
    asyncio.get_running_loop().call_later(
        max(t0 + seconds - time.monotonic(), 0.0), is_open.clear)
    nxt = iter(range(len(reqs)))

    async def client():
        while is_open:
            j = next(nxt, None)
            if j is None:
                return
            log.t_due[j] = time.monotonic()
            log.sent = max(log.sent, j + 1)
            await log.send(engine, reqs, j)

    await asyncio.gather(*(client() for _ in range(clients)))


async def open_loop(engine, reqs, log: Log, due: np.ndarray, t0: float):
    """Send request j at ``t0 + due[j]``; returns once all have resolved.
    ``log.t_sent - log.t_due`` is how late the generator ran. A request's
    task is dropped once it is done (``send`` never raises), so the
    generator holds no more objects than there are requests in flight:
    the garbage collector's pauses stay the server's own."""
    pending: set[asyncio.Task] = set()
    for j, d in enumerate(due):
        t = t0 + d
        log.t_due[j] = t
        wait = t - time.monotonic()
        if wait > 0:
            await asyncio.sleep(wait)
        with TraceAnnotation("bench.generate"):
            task = asyncio.create_task(log.send(engine, reqs, j))
            pending.add(task)
            task.add_done_callback(pending.discard)
            log.sent = j + 1
    await asyncio.gather(*pending)
