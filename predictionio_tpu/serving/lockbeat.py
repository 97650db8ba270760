"""The beat thread: the interpreter lock timed from outside the request
path, and the host caught standing still.

Every thread of a ``pio deploy`` (the HTTP threads, the batcher's two
workers) runs Python under one interpreter lock. The spans of
``utils/spans.py`` say how long a thread was off the CPU though it had
work; they cannot say what a thread that *becomes* runnable waits for the
lock, nor whether the lock is busy (Python at work: less work a request
helps) or handed about (threads waking and waiting: fewer threads help).
One daemon thread a :class:`~predictionio_tpu.workflow.serving
.QueryService`, started at the boot mark, does:

* every ``PERIOD_S`` it sleeps, and notes how late it ran
  (``lock.acquireMs``): a sleeping thread needs the lock to go on, so its
  lateness is the wait for the lock plus the timer's slack;
* every ``EVERY``-th beat it reads the CPU time the request path's
  threads have added (each worker on one of its cycles in 32, each HTTP
  thread once a group of 64 riders: monotonic totals; no other thread's clock is read, a thread
  that has ended has none) and appends ``100 * d(cpu) / d(wall)`` to
  ``lock.busyPct``;
* a beat late by over ``STALL_S`` is a stall, counted in ``lock.stalls``
  with its lateness and the process's CPU time over it. CPU a small share
  of the lateness says the machine stood still (nothing ran, this process
  included); CPU near the lateness says a thread held the lock and worked.
  The two running sums say the same of a whole run.

There is no watchdog that dumps tracebacks while the interpreter stands:
CPython's (the timed dump of its ``faulthandler`` module) walks the other threads'
frames from a C thread without the interpreter lock, and when those
threads run (a stalled machine going on all at once, or beats merely slow
under a busy lock) that ends the process with signal 11.
``tests/test_ci_guards.py`` keeps it out of the package.

jax-free, like the rest of this package.
"""

from __future__ import annotations

import threading
import time
import weakref
from typing import Callable

from predictionio_tpu.api.stats import LockStats

__all__ = ["LockBeat"]


class LockBeat:
    PERIOD_S = 0.05
    #: beats between two readings of the CPU totals
    EVERY = 4
    #: a beat this late is a stall
    STALL_S = 0.5

    def __init__(self, stats: LockStats, cpu_total_ns: Callable[[], int],
                 *, owner: object | None = None):
        self.stats = stats
        self._cpu_total_ns = cpu_total_ns
        #: the thread ends on its own once ``owner`` is gone (a service
        #: nobody closed): looked at from the beat's own thread, so no
        #: finalizer has to stop a thread from wherever the collector runs
        self._owner = weakref.ref(owner) if owner is not None else None
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, name="pio-lock-beat", daemon=True
        )

    def start(self) -> "LockBeat":
        self._thread.start()
        return self

    def alive(self) -> bool:
        return self._thread.is_alive()

    def stop(self) -> None:
        """End the thread. Safe to call twice, and before :meth:`start`."""
        self._stop.set()
        if self._thread.is_alive():
            self._thread.join(timeout=2.0)

    def _run(self) -> None:
        period_ms, stall_ms = self.PERIOD_S * 1e3, self.STALL_S * 1e3
        beat = 0
        wall_ns, cpu_ns = time.perf_counter_ns(), self._cpu_total_ns()
        while True:
            slept_ns = time.perf_counter_ns()
            process_s = time.process_time()
            if self._stop.wait(self.PERIOD_S):
                return
            if self._owner is not None and self._owner() is None:
                return
            late_ms = (time.perf_counter_ns() - slept_ns) / 1e6 - period_ms
            beat += 1
            busy_pct = None
            if beat % self.EVERY == 0:
                now_ns, now_cpu_ns = time.perf_counter_ns(), self._cpu_total_ns()
                busy_pct = 100.0 * (now_cpu_ns - cpu_ns) / (now_ns - wall_ns)
                wall_ns, cpu_ns = now_ns, now_cpu_ns
            if late_ms > stall_ms:
                self.stats.record_stall(
                    late_ms, (time.process_time() - process_s) * 1e3)
            self.stats.record_beat(max(0.0, late_ms), busy_pct)
