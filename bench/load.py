"""The one load generator: reads a traffic file and drives requests
through a ``submit(x) -> Future`` for a fixed window.

Traffic keys (``bench/traffic/<mix>.json``):

* ``loop``: ``"closed"`` (``clients`` callers, each sending its next
  request when the last is answered) or ``"open"`` (Poisson arrivals at
  ``rate_rps`` drawn up front, ``arrival``: ``"poisson"``, sent by
  ``senders`` threads whatever the answers do);
* ``sizes``: samples per request, drawn uniformly: every seed sends the
  same multiset of sizes in another order, so the seed changes the
  order and the images, never the amount of work;
* ``arrival_seed``: the open loop's arrival times, the same for every
  run seed.

Each request records when it was due, sent and answered on the host's
monotonic clock; latency runs from when it was due (the open loop's
schedule, or the send time of a closed-loop caller).  A request's images
are a slice of the pool, a view: the window copies no image, so the
host time in it is the server's own.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time

import numpy as np


@dataclasses.dataclass
class Request:
    n: int
    first: int                 # first image of the pool; first + n fits
    due: float | None = None   # seconds after the window opens
    sent: float | None = None  # time.monotonic(), the server's clock
    done: float | None = None
    result: dict | None = None
    error: str | None = None

    def images(self, pool):
        """The request's images: a view of ``n`` consecutive rows."""
        return pool[self.first:self.first + self.n]


def arrival_times(rate, secs, rng):
    """Poisson arrival offsets (s) at ``rate`` over ``secs``."""
    t, out = 0.0, []
    while True:
        t += rng.exponential(1.0 / rate)
        if t >= secs:
            return np.asarray(out)
        out.append(t)


def balanced_sizes(sizes, count, rng):
    """``count`` request sizes, each of ``sizes`` equally often (up to
    the remainder), in an order drawn from ``rng``."""
    reps = -(-count // len(sizes))
    return [int(s) for s in rng.permutation(np.tile(sizes, reps)[:count])]


def plan_open(traffic, seed: int, seconds: float, pool_size: int):
    """The open loop's requests, due times set, in due order."""
    if traffic["arrival"] != "poisson":
        raise KeyError(f"unknown arrival {traffic['arrival']!r}")
    due = arrival_times(traffic["rate_rps"], seconds,
                        np.random.default_rng(traffic["arrival_seed"]))
    rng = np.random.default_rng([seed, 1])
    sizes = balanced_sizes(traffic["sizes"], len(due), rng)
    return [Request(n=n, first=int(rng.integers(pool_size - n + 1)),
                    due=float(t)) for n, t in zip(sizes, due)]


def plan_closed(traffic, seed: int, per_client: int, pool_size: int):
    """Each closed-loop client's queue of requests."""
    out = []
    for c in range(traffic["clients"]):
        rng = np.random.default_rng([seed, 2, c])
        out.append([Request(n=n, first=int(rng.integers(pool_size - n + 1)))
                    for n in balanced_sizes(traffic["sizes"], per_client,
                                            rng)])
    return out


def _send(submit, req, pool, t0):
    req.sent = time.monotonic()
    if req.due is None:
        req.due = req.sent - t0

    def done(fut):
        req.done = time.monotonic()
        if fut.exception() is not None:
            req.error = repr(fut.exception())
        else:
            req.result = fut.result()

    fut = submit(req.images(pool))
    fut.add_done_callback(done)
    return fut


def run_closed(submit, plans, pool, seconds: float):
    """Drive the closed loop for ``seconds``; returns (t0, t1, sent
    requests).  Every request sent is answered (or failed) on return."""
    sent: list = []
    lock = threading.Lock()
    t0 = time.monotonic()
    t1 = t0 + seconds

    def client(reqs):
        for req in reqs:
            if time.monotonic() >= t1:
                return
            with lock:
                sent.append(req)
            _send(submit, req, pool, t0).exception(timeout=seconds + 60)
        raise RuntimeError("a closed-loop client ran out of requests")

    threads = [threading.Thread(target=client, args=(reqs,), daemon=True)
               for reqs in plans]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=seconds + 120)
    return t0, t1, sent


def run_open(submit, reqs, pool, seconds: float, senders: int):
    """Send ``reqs`` at their due times for ``seconds``; returns (t0,
    t1, requests).  Waits for every answer up to a minute past the
    window."""
    work: queue.Queue = queue.Queue()
    futs: list = []

    def sender():
        while (req := work.get()) is not None:
            futs.append(_send(submit, req, pool, t0))

    threads = [threading.Thread(target=sender, daemon=True)
               for _ in range(senders)]
    for th in threads:
        th.start()
    t0 = time.monotonic()
    for req in reqs:
        wait = t0 + req.due - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        work.put(req)
    for _ in threads:
        work.put(None)
    t1 = t0 + seconds
    for th in threads:
        th.join(timeout=seconds + 60)
    deadline = t1 + 60
    for fut in list(futs):
        try:
            fut.exception(timeout=max(deadline - time.monotonic(), 0))
        except TimeoutError:
            pass
    return t0, t1, reqs
