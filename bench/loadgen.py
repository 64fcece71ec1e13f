"""The load generator: an open loop on a schedule or a closed loop of
outstanding requests, through the wire or into the serving loop.

Every request is timed by the generator's own monotonic clock: ``due``
(when the schedule says it is sent; for a closed loop, when a slot frees),
``sent`` (when the submit call was made) and ``done`` (when its answer
reached the client). Latency runs from ``due``, so a stall in the
generator or the server delays every later request's clock too.
"""
from __future__ import annotations

import threading
import time

import numpy as np

clock = time.monotonic


class Records:
    """Per-request timings and answers, indexed like the query list."""

    def __init__(self, n: int):
        self.due = np.full(n, np.nan)
        self.sent = np.full(n, np.nan)
        self.done = np.full(n, np.nan)
        self.answers: list = [None] * n      # the response as the client got it
        self._lock = threading.Lock()
        self._left = 0
        self._all_done = threading.Event()
        self._all_done.set()
        self.window = (0.0, 0.0)
        self.last_done = 0.0

    def begin(self, i: int, due: float) -> None:
        with self._lock:
            self.due[i] = due
            self.sent[i] = clock()
            self._left += 1
            self._all_done.clear()

    def finish(self, i: int, resp) -> None:
        """Record the answer to request i: a ``NetResult`` or a
        ``QueryResponse`` (status, result, method, batch_size, wait_s,
        service_s, trace_id)."""
        t = clock()
        with self._lock:
            self.done[i] = self.last_done = t
            self.answers[i] = resp
            self._left -= 1
            if self._left == 0:
                self._all_done.set()

    def status(self, i: int) -> str | None:
        """Request i's status name, None while unanswered."""
        a = self.answers[i]
        return None if a is None else a.status.name

    @property
    def outstanding(self) -> int:
        return self._left

    def wait(self, timeout_s: float) -> bool:
        return self._all_done.wait(timeout_s)

    @property
    def issued(self) -> np.ndarray:
        return np.nonzero(~np.isnan(self.sent))[0]


def arrival_offsets(n: int, seconds: float, seed: int) -> np.ndarray:
    """Poisson arrivals with a fixed set of gaps: the n quantiles of the
    exponential distribution, scaled to fill ``seconds`` and dealt in a
    seeded order, so every seed offers the same load."""
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps *= seconds / gaps.sum()
    gaps = np.random.default_rng([seed, 3]).permutation(gaps)
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


class NetTransport:
    """Requests as frames over a ``NetClient`` session."""

    def __init__(self, client):
        self.client = client

    def submit(self, q, on_done) -> None:
        fut = self.client.submit(terms=q.terms, threshold=q.threshold,
                                 top_k=q.top_k or None)

        def done(f):
            try:
                r = f.result()
            except Exception:                 # session died: no answer
                return
            on_done(r)

        fut.add_done_callback(done)


class LoopTransport:
    """Requests straight into an in-process ``ServingLoop``."""

    def __init__(self, loop):
        self.loop = loop

    def submit(self, q, on_done) -> None:
        self.loop.submit(terms=q.terms, threshold=q.threshold,
                         top_k=q.top_k or None, on_done=on_done)


def run_open(transport, queries, offsets, seconds: float,
             rec: Records) -> None:
    """Send query i at ``t0 + offsets[i]`` whatever the answers do."""
    t0 = clock() + 0.01
    rec.window = (t0, t0 + seconds)
    for i, q in enumerate(queries):
        due = t0 + float(offsets[i])
        delay = due - clock()
        if delay > 0:
            time.sleep(delay)
        rec.begin(i, due)
        transport.submit(q, lambda r, i=i: rec.finish(i, r))


def run_closed(transport, queries, outstanding: int, seconds: float,
               rec: Records) -> bool:
    """Keep ``outstanding`` requests in flight for ``seconds``; a slot is
    due the moment the answer that freed it arrives. Returns False where
    the pool ran out before the window closed (the rate then reads the
    pool's size over the window at most)."""
    slots = threading.Semaphore(outstanding)
    t0 = clock()
    rec.window = (t0, t0 + seconds)
    for i, q in enumerate(queries):
        slots.acquire()
        due = clock()
        if due >= rec.window[1]:
            return True
        rec.begin(i, due)

        def done(r, i=i):
            rec.finish(i, r)
            slots.release()

        transport.submit(q, done)
    return False
