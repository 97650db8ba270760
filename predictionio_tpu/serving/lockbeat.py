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
* every ``EVERY``-th beat it re-arms ``faulthandler
  .dump_traceback_later(DUMP_AFTER_S)``, a C thread that needs no lock: if
  the interpreter stands still for that long, the tracebacks of all
  threads are written *while it stands*; and a beat late by over
  ``STALL_S`` is counted in ``lock.stalls`` with the process's CPU time
  over it and the dump's path. A dump with a culprit's stack says the
  interpreter stood still; a late beat with no dump, or a dump of threads
  at rest and ``cpuMs`` near 0, says the machine did. The watchdog writes
  into an unnamed temporary file; what a stall left there is appended to
  the named dump file once Python runs again, so a server that never
  stood still leaves no file.

``faulthandler`` has one such watchdog a process: where several services
share a process (tests), the first beat alive arms it and the others only
count their late beats (``dump`` None).

jax-free, like the rest of this package.
"""

from __future__ import annotations

import faulthandler
import logging
import os
import tempfile
import threading
import time
import weakref
from typing import Callable

from predictionio_tpu.api.stats import LockStats

__all__ = ["LockBeat"]

logger = logging.getLogger(__name__)


class LockBeat:
    PERIOD_S = 0.05
    #: beats between two readings of the CPU totals and two re-armings
    #: (a re-arming makes and joins a C thread: 0.1-0.3 ms)
    EVERY = 4
    #: the interpreter standing still this long gets its tracebacks dumped
    DUMP_AFTER_S = 0.6
    #: a beat this late is a stall (under DUMP_AFTER_S less the EVERY
    #: beats since the watchdog was armed would count stalls no dump
    #: could have seen)
    STALL_S = 0.5

    #: the beat that arms this process's one watchdog, and the beats whose
    #: threads run, under _armer_lock
    _armer: "LockBeat | None" = None
    _live: "weakref.WeakSet[LockBeat]" = weakref.WeakSet()
    _armer_lock = threading.Lock()

    def __init__(self, stats: LockStats, cpu_total_ns: Callable[[], int],
                 dump_path: str, owner: object | None = None):
        self.stats = stats
        self._cpu_total_ns = cpu_total_ns
        self.dump_path = dump_path
        #: the thread ends on its own once ``owner`` is gone (a service
        #: nobody closed): looked at from the beat's own thread, so no
        #: finalizer has to stop a thread from wherever the collector runs
        self._owner = weakref.ref(owner) if owner is not None else None
        self._dump_file = None
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, name="pio-lock-beat", daemon=True
        )

    @classmethod
    def armer(cls) -> "LockBeat | None":
        """The live beat that arms the process's watchdog, if any."""
        return cls._armer

    @classmethod
    def live(cls) -> "list[LockBeat]":
        """The beats of this process whose threads run (services nobody
        closed keep theirs: a test that needs the watchdog stops them)."""
        with cls._armer_lock:
            return list(cls._live)

    def start(self) -> "LockBeat":
        with self._armer_lock:
            LockBeat._live.add(self)
        self._thread.start()
        return self

    def alive(self) -> bool:
        return self._thread.is_alive()

    def stop(self) -> None:
        """End the thread, which cancels the armed dump on its way out.
        Safe to call twice, and before :meth:`start`."""
        self._stop.set()
        if self._thread.is_alive():
            self._thread.join(timeout=2.0)

    # ------------------------------------------------------------ the dump
    def _arm(self) -> None:
        """(Re-)arm the watchdog, if this beat is the process's armer or
        there is none."""
        with self._armer_lock:
            if LockBeat._armer is None:
                LockBeat._armer = self
            if LockBeat._armer is not self:
                return
        try:
            if self._dump_file is None:
                self._dump_file = tempfile.TemporaryFile()
            faulthandler.dump_traceback_later(
                self.DUMP_AFTER_S, file=self._dump_file
            )
        except (OSError, ValueError, RuntimeError) as e:
            # no dump, then: the beat still times the lock
            logger.warning("lock beat: cannot arm the traceback dump: %s", e)
            self._disarm()

    def _disarm(self) -> None:
        with self._armer_lock:
            if LockBeat._armer is not self:
                return
            LockBeat._armer = None
        faulthandler.cancel_dump_traceback_later()
        if self._dump_file is not None:
            self._dump_file.close()
            self._dump_file = None

    def _dumped(self) -> int:
        """Bytes the watchdog has written so far, 0 where this beat arms
        none."""
        try:
            return os.fstat(self._dump_file.fileno()).st_size
        except (AttributeError, OSError, ValueError):
            return 0

    def _keep_dump(self, start: int, end: int) -> str | None:
        """Append what the watchdog wrote during a stall to the named
        file; its path, or None where nothing was written."""
        if end <= start:
            return None
        try:
            text = os.pread(self._dump_file.fileno(), end - start, start)
            os.makedirs(os.path.dirname(self.dump_path), exist_ok=True)
            with open(self.dump_path, "ab") as f:
                f.write(text)
        except OSError as e:
            logger.warning("lock beat: cannot keep the traceback dump: %s", e)
            return None
        return self.dump_path

    # ------------------------------------------------------------ the beat
    def _run(self) -> None:
        try:
            self._beat()
        finally:
            self._disarm()
            with self._armer_lock:
                LockBeat._live.discard(self)

    def _beat(self) -> None:
        period_ms = self.PERIOD_S * 1e3
        beat = 0
        self._arm()
        dumped = self._dumped()
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
            if late_ms > self.STALL_S * 1e3:
                cpu_ms = (time.process_time() - process_s) * 1e3
                now_dumped = self._dumped()
                self.stats.record_stall(
                    late_ms, cpu_ms, self._keep_dump(dumped, now_dumped)
                )
                dumped = now_dumped
                self._arm()  # the watchdog fires once an arming
            elif beat % self.EVERY == 0:
                self._arm()
            self.stats.record_beat(max(0.0, late_ms), busy_pct)
