"""The load generator: drives ``submit`` from the client's side.

Each request keeps three times on one monotonic clock: when it was due,
when it was handed to ``submit`` (sent), and when its future resolved
(done, taken in a done-callback, not when the client later gathers it).
Latency is done - due, so a stalled generator counts against the system
it stalled behind; lateness (sent - due) says how far the generator
itself fell behind.
"""
from __future__ import annotations

import collections
import concurrent.futures as cf
import threading
import time
from typing import Callable, List, Optional

import numpy as np


class Record:
    """Per-request times and outcomes, in send order.

    A resolved future is dropped once its verdict is kept, as a client
    that collects answers would: holding every future and response alive
    would grow the heap the program's garbage collector walks."""

    def __init__(self, clock: Callable[[], float]):
        self.clock = clock
        self.due: List[float] = []
        self.sent: List[float] = []
        self.done: List[float] = []
        self.futures: List[Optional[cf.Future]] = []
        self.verdicts: List[Optional[bool]] = []
        self.errors: List[Optional[str]] = []

    def __len__(self) -> int:
        return len(self.due)

    def send(self, submit, graph, due: float, on_done=None) -> None:
        i = len(self.due)
        self.due.append(due)
        self.done.append(float("nan"))
        self.verdicts.append(None)
        self.errors.append("unanswered")
        self.sent.append(self.clock())
        try:
            fut = submit(graph)
        except Exception as e:                # refused: counts as failed
            self.futures.append(None)
            self.errors[i] = f"{type(e).__name__}: {e}"
            self.done[i] = self.clock()
            if on_done is not None:
                on_done(i)
            return
        self.futures.append(fut)

        def resolved(f, i=i):
            self.done[i] = self.clock()
            if f.cancelled():
                self.errors[i] = "cancelled"
            elif f.exception() is not None:
                exc = f.exception()
                self.errors[i] = f"{type(exc).__name__}: {exc}"
            else:
                self.verdicts[i] = bool(f.result().verdict)
                self.errors[i] = None
            self.futures[i] = None
            if on_done is not None:
                on_done(i)

        fut.add_done_callback(resolved)

    def wait(self, deadline: float) -> None:
        """Wait for every future until ``deadline`` (clock seconds)."""
        pending = [f for f in list(self.futures) if f is not None]
        cf.wait(pending, timeout=max(0.0, deadline - self.clock()))

    def arrays(self):
        return (np.asarray(self.due), np.asarray(self.sent),
                np.asarray(self.done))

    def outcome(self, i: int):
        """(verdict or None, error or None) of request ``i``."""
        return self.verdicts[i], self.errors[i]


def closed_loop(submit, make, outstanding: int, t0: float, t_end: float,
                rec: Record) -> None:
    """Keep ``outstanding`` requests in flight until ``t_end``.

    Request k+outstanding is due when request k's slot frees; the first
    ``outstanding`` are due at ``t0``.
    """
    free = collections.deque([t0] * outstanding)
    cv = threading.Condition()

    def freed(i: int) -> None:
        with cv:
            free.append(rec.done[i])
            cv.notify()

    while True:
        with cv:
            if not free:
                now = rec.clock()
                if now >= t_end:
                    return
                cv.wait(t_end - now)
                continue
            due = free.popleft()
        if due >= t_end:
            return
        rec.send(submit, make(len(rec)), due, on_done=freed)


def open_loop(submit, make, due: np.ndarray, t0: float, rec: Record,
              sleep: Callable[[float], None] = time.sleep) -> None:
    """Send request i at ``t0 + due[i]``, whatever the system is doing."""
    for off in due:
        t = t0 + float(off)
        sleep_until(rec.clock, t, sleep)
        rec.send(submit, make(len(rec)), t)


def sleep_until(clock, t: float, sleep=time.sleep) -> None:
    """Sleep until ``clock()`` reaches ``t``. The clock moves between
    reads, so each wait is computed from one read and never negative."""
    while True:
        wait = t - clock()
        if wait <= 0:
            return
        sleep(wait)


def per_second(rec: Record, t0: float, seconds: float) -> list:
    """[answers resolved, worst latency in ms of the requests due] for
    each second of the window: shows a stall or a growing backlog."""
    due, _, done = rec.arrays()
    out = []
    for k in range(int(np.ceil(seconds))):
        lo, hi = t0 + k, t0 + k + 1
        got = (done >= lo) & (done < hi)
        mine = (due >= lo) & (due < hi)
        lat = (done[mine] - due[mine]) * 1e3
        out.append([int(got.sum()),
                    float(np.nanmax(lat)) if mine.any() else 0.0])
    return out


def lateness_ms(rec: Record) -> tuple:
    """(p50, p95) of sent - due, in ms."""
    due, sent, _ = rec.arrays()
    if not len(due):
        return 0.0, 0.0
    late = (sent - due) * 1e3
    return float(np.percentile(late, 50)), float(np.percentile(late, 95))
