"""Program spans and the compile ledger — what ``pio train`` and ``pio
deploy`` say about where their own time goes.

:func:`span` times one phase on ``time.perf_counter_ns``. Always on: the
value lands where the caller puts it (``phase_timings`` and ``kernels`` of
the engine instance, ``latencyMs`` of ``/stats.json``), and a closed span
is also handed to the :class:`Collector` bound to the current thread, if
one is. On the thread that feeds the device (the batcher's dispatcher in
``pio deploy``, the main thread in ``pio train``) the collector is an
annotating one: every *leaf* span is then also a
``jax.profiler.TraceAnnotation("pio.<name>")``, so it lies in the
profiler's trace beside the device's ``XLA Ops`` on the profiler's clock.
Enclosing spans and the spans of other threads are never annotated: a
reduction that names an idle gap by the host event covering most of it
would otherwise name every gap after the longest parent.

On a collector made with ``cpu`` (the batcher's two workers, the main
thread of ``pio train``) a span also reads its thread's CPU clock at both
ends (``time.thread_time_ns``, a system call): ``wall - cpu`` of a span is
the time its thread was off the CPU, waiting for the interpreter lock,
blocked in a call that let the lock go, or waiting for a core. A thread
that waits for the lock accrues no CPU time.

The profiler (``POST /profiler/start``, or whoever wraps ``pio train``) is
the only store of raw spans; there is no exporter and no switch.

:class:`CompileLedger` is the program's one ``jax.monitoring`` listener:
per jitted function the count and seconds of tracing, lowering and backend
compile (on a persistent-cache hit: retrieval and load), the cache's
requests, hits and misses, and a boot mark after which compiles are
serve-time.

jax-free on import; an annotation is taken only in a process that has
imported jax already (one that can be profiled at all).
"""

from __future__ import annotations

import os
import sys
import threading
import time
from collections import deque
from typing import Callable, Iterable, NamedTuple

__all__ = [
    "Collector",
    "CompileLedger",
    "SpanRecord",
    "bind",
    "count",
    "cpu_ms",
    "current",
    "durations_ms",
    "process_age_s",
    "span",
]

_bound = threading.local()


class SpanRecord(NamedTuple):
    name: str
    #: the span open around this one on the same thread, if any
    parent: str | None
    #: the collector's sequence number when the span closed: the batch a
    #: dispatcher phase belongs to, the batch a request rode in
    seq: int
    start_ns: int
    end_ns: int
    #: the thread's CPU time between the two ends; 0 where the collector
    #: takes none
    cpu_ns: int = 0


class Collector:
    """The spans closed on the thread it is bound to since the last
    :meth:`take`. Owned by one thread: no lock."""

    #: a thread whose owner never takes keeps the newest spans only
    MAX_SPANS = 1024

    __slots__ = ("annotate", "cpu", "seq", "on_close", "_closed", "_open",
                 "_counts")

    def __init__(self, annotate: bool = False, cpu: bool = False):
        #: leaf spans of this thread also go into the profiler's trace
        self.annotate = annotate
        #: spans of this thread also read the thread's CPU clock; the
        #: owner may change it between spans
        self.cpu = cpu
        self.seq = 0
        #: called on the owning thread with the name of each span as it
        #: closes, for an owner that acts on a phase's end while the
        #: phases after it still run (the batcher: ``dispatch``)
        self.on_close: Callable[[str], None] | None = None
        self._closed: deque = deque(maxlen=self.MAX_SPANS)
        self._open: list[str] = []
        #: :func:`count` totals since the last :meth:`take_counts`; the
        #: names are the program's own, a handful
        self._counts: dict[str, int] = {}

    def take(self) -> list[SpanRecord]:
        out = list(self._closed)
        self._closed.clear()
        return out

    def take_counts(self) -> dict[str, int]:
        out, self._counts = self._counts, {}
        return out

    def counted(self, name: str) -> int:
        """The count ``name`` since the last :meth:`take_counts`."""
        return self._counts.get(name, 0)


def bind(collector: Collector | None) -> Collector | None:
    """Bind ``collector`` to the current thread (None unbinds); returns
    what was bound before."""
    previous = getattr(_bound, "collector", None)
    _bound.collector = collector
    return previous


def current() -> Collector | None:
    return getattr(_bound, "collector", None)


def count(name: str, n: int) -> None:
    """Add ``n`` to the count ``name`` of the collector bound to the
    current thread, if one is: work done at a span's boundary (rows
    scored by a dispatch), read where the spans are."""
    collector = getattr(_bound, "collector", None)
    if collector is not None:
        collector._counts[name] = collector._counts.get(name, 0) + n


class span:
    """``with span("bind") as s: ...`` then ``s.ms`` / ``s.seconds``.
    ``enclosing=True`` marks a span that has spans inside it: recorded,
    never annotated. ``start()``/``stop()`` are the two ends for a span
    whose ends do not share a block. On a collector that takes CPU time
    also ``s.cpu_ns`` / ``s.cpu_ms`` / ``s.cpu_seconds``: the CPU clock is
    read inside the wall clock's two reads, so it never exceeds the wall
    by more than the two clocks differ."""

    __slots__ = ("name", "enclosing", "start_ns", "end_ns", "cpu_ns",
                 "_collector", "_annotation", "_takes_cpu")

    def __init__(self, name: str, enclosing: bool = False):
        self.name = name
        self.enclosing = enclosing
        self.start_ns = self.end_ns = self.cpu_ns = 0
        self._collector = self._annotation = None
        self._takes_cpu = False

    def start(self) -> "span":
        collector = self._collector = getattr(_bound, "collector", None)
        if collector is not None:
            if collector.annotate and not self.enclosing:
                jax = sys.modules.get("jax")
                if jax is not None:
                    self._annotation = jax.profiler.TraceAnnotation(
                        "pio." + self.name, seq=collector.seq
                    )
                    self._annotation.__enter__()
            collector._open.append(self.name)
        self.start_ns = time.perf_counter_ns()
        # as the collector says when the span opens: its owner may turn
        # ``cpu`` on and off between spans (the batcher: one cycle in 32)
        self._takes_cpu = collector is not None and collector.cpu
        if self._takes_cpu:
            self.cpu_ns = -time.thread_time_ns()
        return self

    def stop(self) -> None:
        if self._takes_cpu:
            self.cpu_ns += time.thread_time_ns()
        self.end_ns = time.perf_counter_ns()
        collector = self._collector
        if collector is None:
            return
        collector._open.pop()
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
        collector._closed.append(SpanRecord(
            self.name,
            collector._open[-1] if collector._open else None,
            collector.seq, self.start_ns, self.end_ns, self.cpu_ns,
        ))
        if collector.on_close is not None:
            collector.on_close(self.name)

    __enter__ = start

    def __exit__(self, *exc) -> bool:
        self.stop()
        return False

    @property
    def ns(self) -> int:
        return self.end_ns - self.start_ns

    @property
    def ms(self) -> float:
        return self.ns / 1e6

    @property
    def seconds(self) -> float:
        return self.ns / 1e9

    @property
    def cpu_ms(self) -> float:
        return self.cpu_ns / 1e6

    @property
    def cpu_seconds(self) -> float:
        return self.cpu_ns / 1e9


def durations_ms(records: Iterable[SpanRecord]) -> dict[str, float]:
    """Milliseconds by span name, the spans of one name summed."""
    out: dict[str, float] = {}
    for r in records:
        out[r.name] = out.get(r.name, 0.0) + (r.end_ns - r.start_ns) / 1e6
    return out


def cpu_ms(records: Iterable[SpanRecord]) -> dict[str, float]:
    """Milliseconds of the thread's CPU time by span name, the spans of
    one name summed; all 0 for a collector that takes none."""
    out: dict[str, float] = {}
    for r in records:
        out[r.name] = out.get(r.name, 0.0) + r.cpu_ns / 1e6
    return out


def process_age_s() -> float | None:
    """Seconds since this process started, from the kernel's record of
    it (``/proc/self/stat`` field 22, in clock ticks since boot); None
    where there is no such record."""
    try:
        with open("/proc/self/stat") as f:
            # the command name (field 2) may hold spaces: count from ")"
            started_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        ticks = os.sysconf("SC_CLK_TCK")
        return time.clock_gettime(time.CLOCK_BOOTTIME) - started_ticks / ticks
    except (OSError, ValueError, IndexError, AttributeError):
        return None


_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE = "/jax/core/compile/backend_compile_duration"
_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "cacheRequests",
    "/jax/compilation_cache/cache_hits": "cacheHits",
    "/jax/compilation_cache/cache_misses": "cacheMisses",
}
_DURATIONS = {
    _TRACE: ("traces", "traceSeconds"),
    _LOWER: ("lowers", "lowerSeconds"),
    _COMPILE: ("compiles", "loadSeconds"),
}
_FIELDS = (
    "traces", "traceSeconds", "lowers", "lowerSeconds", "compiles",
    "loadSeconds", "cacheRequests", "cacheHits", "cacheMisses",
    "cacheRetrievalSeconds",
)


def _function_name(fun_name) -> str:
    """``als_sweep`` for the trace's ``als_sweep`` and for the module's
    ``jit(als_sweep)`` / ``jit_als_sweep`` alike."""
    name = str(fun_name or "?")
    if name.startswith("jit(") and name.endswith(")"):
        return name[4:-1]
    return name.removeprefix("jit_")


class CompileLedger:
    """Per jitted function (the module's ``jit(...)`` stripped to the name):
    ``traces``/``traceSeconds`` (Python to jaxpr), ``lowers``/
    ``lowerSeconds`` (jaxpr to MLIR, Pallas kernels to Mosaic included),
    ``compiles``/``loadSeconds`` (``backend_compile``: XLA's compile, or
    on a persistent-cache hit the retrieval and load of the executable,
    ``cacheRetrievalSeconds`` of it), and the persistent cache's
    ``cacheRequests``, ``cacheHits``, ``cacheMisses`` (JAX's own counts:
    a miss is a compile that was written to the cache).

    The cache's events carry no function name; JAX fires them inside the
    ``backend_compile`` of the function they belong to, on its thread,
    so they wait per thread for that event's name.

    The listeners run inside JAX's compile, under whatever locks the
    compiling caller holds: they take none, and only append the event to
    a queue (atomic under the interpreter lock) that every reader folds
    into the table first, under the ledger's own lock.

    One per process (``jax.monitoring`` has no use for two); every
    ``QueryService`` of the process shares the boot mark."""

    #: functions kept by name; the rest are summed under ``(other)``
    MAX_FUNCTIONS = 512
    #: events kept between two reads; beyond it the oldest go uncounted
    MAX_UNREAD = 65536

    _instance: "CompileLedger | None" = None
    _install_lock = threading.Lock()

    def __init__(self) -> None:
        self._mu = threading.Lock()
        self._unread: deque = deque(maxlen=self.MAX_UNREAD)
        self._functions: dict[str, dict] = {}
        #: thread id -> the cache's events since that thread's last
        #: backend_compile (emptied by it)
        self._waiting: dict[int, dict] = {}
        self._compiles = 0
        self._misses = 0
        self._boot_compiles = 0
        self._boot_misses = 0

    @classmethod
    def install(cls) -> "CompileLedger":
        """The process's ledger, listening from the first call on."""
        with cls._install_lock:
            if cls._instance is None:
                import jax.monitoring

                ledger = cls()
                jax.monitoring.register_event_listener(ledger._on_event)
                jax.monitoring.register_event_duration_secs_listener(
                    ledger._on_duration
                )
                cls._instance = ledger
            return cls._instance

    # ------------------------------------------------------------ listeners
    def _on_event(self, event: str, **_kw) -> None:
        if event in _CACHE_EVENTS:
            self._unread.append((event, 1, None, threading.get_ident()))

    def _on_duration(self, event: str, seconds: float, **kw) -> None:
        if event in _DURATIONS or event == _RETRIEVAL:
            self._unread.append(
                (event, seconds, kw.get("fun_name"), threading.get_ident())
            )

    def _fold(self) -> None:
        """Queue into table. Every reader calls it first."""
        with self._mu:
            while True:
                try:
                    event, value, fun_name, thread = self._unread.popleft()
                except IndexError:
                    return
                if event not in _DURATIONS:
                    field = _CACHE_EVENTS.get(event, "cacheRetrievalSeconds")
                    waiting = self._waiting.setdefault(thread, {})
                    waiting[field] = waiting.get(field, 0) + value
                    if field == "cacheMisses":
                        self._misses += 1
                    continue
                name = _function_name(fun_name)
                if (name not in self._functions
                        and len(self._functions) >= self.MAX_FUNCTIONS):
                    name = "(other)"
                entry = self._functions.setdefault(
                    name, dict.fromkeys(_FIELDS, 0)
                )
                count, total = _DURATIONS[event]
                entry[count] += 1
                entry[total] += value
                if event == _COMPILE:
                    self._compiles += 1
                    for field, n in self._waiting.pop(thread, {}).items():
                        entry[field] += n

    # -------------------------------------------------------------- reading
    def mark_boot_complete(self) -> None:
        """Everything compiled so far was boot work (loading, warm-up);
        what compiles after this mark is serve-time."""
        self._fold()
        with self._mu:
            self._boot_compiles = self._compiles
            self._boot_misses = self._misses

    def since_boot(self) -> int:
        """Backend compiles since the boot mark (a persistent-cache hit
        counts: the request path still traced, lowered and loaded)."""
        self._fold()
        with self._mu:
            return self._compiles - self._boot_compiles

    def snapshot(self) -> dict[str, dict]:
        self._fold()
        with self._mu:
            return {name: dict(e) for name, e in self._functions.items()}

    def table(self, since: dict[str, dict] | None = None) -> dict[str, dict]:
        """``{function: fields}`` with seconds rounded; with ``since`` (an
        earlier :meth:`snapshot`) only what happened after it."""
        out = {}
        for name, entry in sorted(self.snapshot().items()):
            before = (since or {}).get(name, {})
            delta = {f: entry[f] - before.get(f, 0) for f in _FIELDS}
            if any(delta.values()):
                out[name] = {
                    f: round(v, 6) if isinstance(v, float) else v
                    for f, v in delta.items()
                }
        return out

    def to_json(self) -> dict:
        """The ``compile`` block of ``/stats.json``."""
        functions = self.table()
        with self._mu:
            out = {
                "sinceBoot": self._compiles - self._boot_compiles,
                "missesSinceBoot": self._misses - self._boot_misses,
                "compiles": self._compiles,
            }
        for f in ("cacheRequests", "cacheHits", "cacheMisses"):
            out[f] = sum(e[f] for e in functions.values())
        out["functions"] = functions
        return out
