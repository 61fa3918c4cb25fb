"""Pacing for background sweeps that share the interpreter with the
cycle thread.

The 30 s sweeps (reapers, monitor) walk every running task and every
pending job in Python.  The cycle thread is itself CPU-bound (its apply
is nine tenths CPU) and lets go of the GIL thousands of times a cycle —
a lock, a journal write, a commit wait — so a sweep that runs flat out
beside it takes half of the interpreter for as long as it lasts, and
every store-lock holder it delays (a REST submit, the cycle's own status
transactions) holds the lock that much longer: at eight pools one cycle
in nine ran twice its length (PERF.md section 5).  A sweep has half a
minute to finish and nobody waits for it, so it rests: after every burst
of its own CPU time it sleeps long enough to have used only ``share`` of
the time since the burst began.  On a small store no burst is ever
reached and nothing sleeps.
"""

from __future__ import annotations

import time
from typing import Callable, Iterable, Iterator, TypeVar

T = TypeVar("T")


class Pacer:
    """One sweep's budget: ``share`` of one core, settled after every
    ``burst_s`` of this thread's own CPU time, looked at once every
    ``every`` items of a paced walk."""

    def __init__(self, share: float = 0.25, burst_s: float = 0.01,
                 every: int = 1024,
                 cpu: Callable[[], float] = time.thread_time,
                 sleep: Callable[[float], None] = time.sleep):
        self.share = share
        self.burst_s = burst_s
        self.every = every
        self._cpu = cpu
        self._sleep = sleep
        self._mark = cpu()

    def breathe(self) -> None:
        """Rest if a burst's worth of CPU went by since the last rest."""
        used = self._cpu() - self._mark
        if used >= self.burst_s:
            self._sleep(used * (1.0 / self.share - 1.0))
            self._mark = self._cpu()

    def over(self, items: Iterable[T]) -> Iterator[T]:
        """``items``, with a look at the budget every ``every`` of them."""
        every = self.every
        for i, item in enumerate(items):
            if not i % every:
                self.breathe()
            yield item
