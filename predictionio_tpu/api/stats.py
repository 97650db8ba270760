"""Live statistics for the API servers.

* :class:`Stats` — event-server ingest counters. Parity:
  ``data/api/Stats.scala`` + ``StatsActor`` — counts events by (appId,
  status-code, event-name, entity-type) over start-of-minute time
  buckets, served at ``/stats.json`` when the server runs with
  ``--stats``. Single-writer here (the service locks), no actor needed.
* :class:`ServingStats` — query-server micro-batcher gauges, counters and
  the latency decomposition per request and per batch (its worker's
  phases, the host gap before it, whether it overlapped the batch before
  it), served at the query server's ``GET /stats.json``. No reference
  counterpart (the reference has no cross-request batcher).
* :class:`HttpStats` — what the query server's HTTP threads spend on a
  request outside the service: reading it and writing the answer, and for
  a request that rode in a batch its whole stretch on the thread, split
  into CPU and waiting.
* :class:`LockStats` — what the query server's beat thread
  (``serving/lockbeat.py``) saw of the interpreter lock from outside the
  request path.
"""

from __future__ import annotations

import datetime as _dt
import threading
from collections import Counter, deque
from typing import Mapping, Sequence

__all__ = ["Stats", "ServingStats", "HttpStats", "LockStats", "BATCH_PHASES",
           "HOST_HALF_PHASES"]


def _bucket(dt: _dt.datetime) -> _dt.datetime:
    return dt.replace(second=0, microsecond=0)


class Stats:
    #: retain at most this many (appId, minute) buckets; oldest evicted
    #: first so a long-running server's memory and /stats.json response
    #: stay bounded (~24h of single-app traffic).
    MAX_BUCKETS = 1440

    def __init__(self, max_buckets: int | None = None):
        self._lock = threading.Lock()
        self.start_time = _dt.datetime.now(_dt.timezone.utc)
        self.max_buckets = max_buckets or self.MAX_BUCKETS
        # (appId, bucket) -> Counter keyed by ("status", code) /
        # ("event", name) / ("etype", entityType)
        self._counts: dict[tuple[int, _dt.datetime], Counter] = {}

    def update(
        self,
        app_id: int,
        status_code: int,
        event_name: str | None = None,
        entity_type: str | None = None,
        when: _dt.datetime | None = None,
    ) -> None:
        when = _bucket(when or _dt.datetime.now(_dt.timezone.utc))
        with self._lock:
            if (app_id, when) not in self._counts:
                while len(self._counts) >= self.max_buckets:
                    oldest = min(self._counts, key=lambda k: k[1])
                    del self._counts[oldest]
            c = self._counts.setdefault((app_id, when), Counter())
            c[("status", str(status_code))] += 1
            if event_name:
                c[("event", event_name)] += 1
            if entity_type:
                c[("etype", entity_type)] += 1

    def to_json(self) -> dict:
        with self._lock:
            out = []
            for (app_id, bucket), c in sorted(self._counts.items(), key=lambda kv: (kv[0][1], kv[0][0])):
                out.append(
                    {
                        "appId": app_id,
                        "bucket": bucket.isoformat(),
                        "status": {k: v for (kind, k), v in c.items() if kind == "status"},
                        "event": {k: v for (kind, k), v in c.items() if kind == "event"},
                        "entityType": {k: v for (kind, k), v in c.items() if kind == "etype"},
                    }
                )
            return {"startTime": self.start_time.isoformat(), "statsByMinute": out}


def _percentiles(samples, points=(50, 95, 99), mean=False) -> dict[str, float]:
    """Nearest-rank percentiles of a sample window, no numpy needed on
    this hot-ish path; with ``mean`` also the window's mean (for what is
    made of a CPU clock's readings: where that clock ticks coarsely, 10
    ms on some hosts, a short span reads 0 or a whole tick, and only the
    mean of many says what it took)."""
    names = [f"p{p}" for p in points] + (["mean"] if mean else [])
    if not samples:
        return dict.fromkeys(names)
    s = sorted(samples)
    out = {}
    for p in points:
        idx = min(len(s) - 1, max(0, int(round(p / 100.0 * len(s))) - 1))
        out[f"p{p}"] = round(s[idx], 3)
    if mean:
        out["mean"] = round(sum(s) / len(s), 3)
    return out


#: a batcher worker's leaf spans (utils/spans.py), in the order it
#: passes through them from one ``handle_batch`` return to the next; each
#: is a window of ``latencyMs``
BATCH_PHASES = (
    "release", "take", "drain", "batchForm",
    "bind", "lookup", "queryVectors", "filterLookup", "filterBuild",
    "dispatch", "deviceWait", "format",
)

#: those of them in which a worker does not wait by design (for a
#: request, for batch mates, for the device): the worker's host half,
#: whose CPU time is ``hostCpu`` and whose wall less CPU is ``hostWait``
HOST_HALF_PHASES = tuple(
    p for p in BATCH_PHASES if p not in ("take", "drain", "deviceWait")
)

#: what a handler that filters its answers counts on the dispatcher's
#: collector as ``filter.<name>`` (templates/retrieval.py
#: ``FilteredItemRetrieval`` and the two engines that take it), and what
#: the event store counts there of a batch's read of its users' seen items
#: (``LEvents.targets_by_entities``: from its columns, or through events)
FILTER_COUNTS = ("excludedIds", "excludedPairs", "categoryRows", "hostPath",
                 "shortAnswers", "columnReads", "eventReads")

#: what the similar-product engine counts as ``similar.<name>``
#: (templates/similarproduct/engine.py)
SIMILAR_COUNTS = ("queryItems", "unknownItems")

#: the plans of ``ops.topk.select_plan``: the ``batcher.select`` counts
SELECT_PLANS = ("blocked", "plain")


class ServingStats:
    """Micro-batcher serving statistics (thread-safe).

    Latency decomposition, all in milliseconds. Per request:

    * ``queueWait`` — enqueue until the dispatcher formed its batch;
    * ``total`` — enqueue until the caller gets its result back;
    * ``wake`` — the worker's ``done.set()`` until the caller's
      thread runs again, past a worker that has the right of way
      (``serving/batcher.py``);
    * ``giveWay`` — the part of ``wake`` the caller's thread spent
      waiting for a worker that has the right of way (0 where none had).

    Per batch, on the worker thread that carried it (the batcher has
    two), flat and in this order (one cycle runs from one of the
    worker's ``handle_batch`` returns to its next):

    * ``release`` — answers handed to the riders of the worker's
      PREVIOUS batch;
    * ``take`` — blocked on the queue with nothing in it;
    * ``drain`` — waiting up to the batch delay for batch mates;
    * ``batchForm`` — drain-complete until ``handle_batch`` is entered
      (padding + bookkeeping);
    * ``handle`` — the ``handle_batch`` call itself, and inside it
      ``bind`` (query objects from bodies), ``lookup`` (ids to the
      padded index vector), ``queryVectors`` (the similar-product
      engine's query vectors, made from the query items' rows),
      ``filterLookup`` (a filtering engine's
      store reads for the batch), ``filterBuild`` (its rules into
      device inputs), ``dispatch`` (the call into the scoring
      program until it returns), ``deviceWait`` (the readback that
      blocks until the device is done), ``format`` (lists, result
      objects, serve tail); a handler that has no such boundary records
      none;
    * ``hostGap`` — this batch's ``dispatch`` end less the latest
      ``deviceWait`` end of the batches before it, less what of
      this batch's ``take`` lies between the two, floored at 0: what the
      host's own code kept the device waiting. A program enqueued while
      the one before it still runs kept it waiting 0 ms (absent for the
      first batch and for handlers without a device);
    * ``hostCpu`` and ``hostWait`` — over the phases of
      :data:`HOST_HALF_PHASES` of one cycle (all but ``take``, ``drain``
      and ``deviceWait``, which wait by design): the worker thread's CPU
      time in them, and their wall less that CPU: the time the worker
      was off the CPU though it had work: waiting for the interpreter
      lock, blocked in a call that let it go (a store read in the
      kernel), or waiting for a core. ``cpuMs`` holds the same CPU time
      phase by phase (every phase of :data:`BATCH_PHASES`), to say which
      phase a wait was in. A worker takes them on one of its cycles in 32
      (``serving/batcher.py`` ``_CPU_EVERY``: the CPU clock is a system
      call); where that clock ticks coarsely (10 ms on the chip's host) a
      batch reads 0 or a whole tick, and the windows' ``mean`` is the
      number to read, not their ``p50``.

    ``rest`` counts how often the batcher sent itself to rest since boot
    (each a rest of ``serving/batcher.py`` ``_REST_S`` seconds):
    ``secondBatch`` (no second batch while it lasts: a run of them met an
    idle device) and ``claims`` (no right of way while it lasts: claims
    left part-full batches behind them).

    ``inflightBatch`` counts the batches inside ``handle_batch`` now: 0,
    1 or 2. ``overlap`` counts the live batches by whether their
    ``dispatch`` ended before the batch before them had left the device
    (its ``deviceWait`` end): ``overlapped``, or ``alone`` (the first
    batch, a handler without a device, and every batch of a server
    whose queue never holds a full batch while another is handled: a
    second batch goes only then, and not for a while after a run of them
    met an idle device, ``serving/batcher.py``); ``overlapPct``
    is the share of ``overlapped`` among them, 0.0 before any batch.

    ``rowsScored`` and ``rowsReal`` count, over the live batches, the rows
    the scoring programs were dispatched with and the rows of them that
    held a query (the batcher's own filler slots are queries to the
    program: ``paddingOverhead`` has those). Their ratio is what the
    program's row bucket costs: 1.0 at a full batch, up to 8 for a lone
    query under the floor of 8 rows (``ops/topk.py`` ``bucket_rows``).

    ``select`` counts the live batches' device dispatches by the
    selection their program was built with (``ops/topk.py``
    ``select_plan``, from the dispatch's rows, columns and k bucket):
    ``blocked`` (the top k from the maxima of contiguous blocks) or
    ``plain`` (``lax.top_k`` over the whole row). A host GEMM and the
    tiers with kernels of their own (IVF, int8, sharded) count neither.

    ``filter`` counts what a filtering engine (the e-commerce and the
    similar-product templates) did over the live batches: ``excludedIds``
    (item ids its rows left out: seen, unavailable, black-listed, a
    query's own items; the unavailable ones once a row, though they
    travel as one mask), ``excludedPairs`` (the (row, item id) pairs the
    device dispatches were handed: the rows' own lists, without repeats),
    ``pairBucket.<P>`` (those dispatches by the pair bucket of their
    program, ``ops.als.tile_pairs``), ``categoryRows`` (rows that named a category),
    ``hostPath`` (queries answered by the host ``predict``: a white list
    or an unknown user), ``shortAnswers`` (rows the rules left fewer than
    ``num`` items), ``columnReads`` and ``eventReads`` (batches whose read
    of their users' seen items the event store answered from its columns,
    and through ``Event`` objects). ``similar`` counts the similar-product engine's query
    items: ``queryItems`` (all that its queries named) and
    ``unknownItems`` (those of them the model does not hold, dropped).

    Windows keep the most recent :attr:`WINDOW` samples so percentiles
    track current behavior on a long-running server; counters are
    monotonic over the process lifetime.
    """

    WINDOW = 4096

    def __init__(self, window: int | None = None):
        self._lock = threading.Lock()
        self.start_time = _dt.datetime.now(_dt.timezone.utc)
        n = window or self.WINDOW
        self.submitted = 0
        self.completed = 0
        self.rejected = 0  # 429s from the REJECT admission policy
        self.block_timeouts = 0  # 503s from the BLOCK admission policy
        self.batches = 0
        self.batched_queries = 0
        self.padded_queries = 0  # filler slots added for bucket padding
        #: the handler's own counts (templates/serving_util.py)
        self.rows_scored = 0
        self.rows_real = 0
        #: the handlers' named counts, a block of ``to_json`` each
        self.handler_counts = {
            block: dict.fromkeys(names, 0)
            for block, names in (("filter", FILTER_COUNTS),
                                 ("similar", SIMILAR_COUNTS),
                                 ("select", SELECT_PLANS))
        }
        self.queue_depth = 0  # last observed; gauge
        self.inflight_batch = 0  # 0|1|2 — the batcher's two workers
        self.overlap = {"overlapped": 0, "alone": 0}
        self.rest = {"secondBatch": 0, "claims": 0}
        #: the workers' thread CPU time since boot, each worker's added on
        #: one of its cycles in ``serving/batcher.py`` ``_CPU_EVERY``
        self.cpu_ns_workers = 0
        self.batch_size_hist: Counter = Counter()
        self.bucket_hist: Counter = Counter()
        #: buckets whose jit programs are assumed compiled (warm-up or a
        #: previous live dispatch); a dispatch to a bucket outside this
        #: set is counted as a miss == a likely recompile
        self.warmed_buckets: set[int] = set()
        self.bucket_misses = 0
        self.warmup_ms: dict[int, float] = {}
        self._queue_wait_ms: deque = deque(maxlen=n)
        self._handle_ms: deque = deque(maxlen=n)
        self._total_ms: deque = deque(maxlen=n)
        self._wake_ms: deque = deque(maxlen=n)
        self._give_way_ms: deque = deque(maxlen=n)
        self._host_gap_ms: deque = deque(maxlen=n)
        self._host_cpu_ms: deque = deque(maxlen=n)
        self._host_wait_ms: deque = deque(maxlen=n)
        self._phase_ms = {name: deque(maxlen=n) for name in BATCH_PHASES}
        self._phase_cpu_ms = {name: deque(maxlen=n) for name in BATCH_PHASES}

    # ------------------------------------------------------------ recording
    def record_submitted(self, queue_depth: int) -> None:
        with self._lock:
            self.submitted += 1
            self.queue_depth = queue_depth

    def record_rejected(self) -> None:
        with self._lock:
            self.rejected += 1

    def record_block_timeout(self) -> None:
        with self._lock:
            self.block_timeouts += 1

    def record_warmup(self, bucket: int, ms: float) -> None:
        with self._lock:
            self.warmed_buckets.add(bucket)
            # bounded by the batcher's finite bucket set, not request data
            self.warmup_ms[bucket] = round(ms, 3)  # piolint: disable=PIO205

    def record_rest(self, kind: str) -> None:
        """The batcher set a rest: ``secondBatch`` or ``claims``."""
        with self._lock:
            self.rest[kind] += 1

    def record_worker_cpu(self, cpu_ns: int) -> None:
        """A worker's thread CPU time over the cycle it just ended."""
        with self._lock:
            self.cpu_ns_workers += cpu_ns

    def record_batch_start(self, queue_depth: int) -> None:
        with self._lock:
            self.inflight_batch += 1
            self.queue_depth = queue_depth

    def record_batch(
        self,
        size: int,
        bucket: int,
        handle_ms: float,
        queue_wait_ms: Sequence[float] = (),
        phases: Mapping[str, float] | None = None,
        phases_cpu: Mapping[str, float] | None = None,
        host_gap_ms: float | None = None,
        overlapped: bool = False,
        rows_scored: int = 0,
        rows_real: int = 0,
        counts: Mapping[str, int] | None = None,
    ) -> None:
        """One dispatched batch: its riders' queue waits, ``handle``, the
        worker's ``phases`` ({name: ms}, names of
        :data:`BATCH_PHASES`) and the CPU time of each (``phases_cpu``,
        from a worker whose collector takes it: ``hostCpu`` and
        ``hostWait`` are made of the two here), the host gap before it,
        whether it was
        dispatched while the batch before it was on the device, the rows its
        scoring dispatches took and really held, and the handler's other
        ``counts``: those named ``<block>.<name>`` for a block of
        ``handler_counts`` (``filter``, ``similar``, ``select``). The names
        of ``FILTER_COUNTS``, ``SIMILAR_COUNTS`` and ``SELECT_PLANS`` are
        there from the start at 0; any other (``filter.pairBucket.<P>``)
        makes its key when first counted."""
        if phases_cpu is not None:
            host_cpu = sum(
                phases_cpu.get(name, 0.0) for name in HOST_HALF_PHASES
            )
            host_wait = sum(
                (phases or {}).get(name, 0.0) for name in HOST_HALF_PHASES
            ) - host_cpu
        with self._lock:
            self.inflight_batch -= 1
            self.overlap["overlapped" if overlapped else "alone"] += 1
            self.batches += 1
            self.batched_queries += size
            self.padded_queries += bucket - size
            self.rows_scored += rows_scored
            self.rows_real += rows_real
            for key, n in (counts or {}).items():
                block, _, name = key.partition(".")
                held = self.handler_counts.get(block)
                if held is not None:  # a name first seen makes its key
                    held[name] = held.get(name, 0) + n
            self.batch_size_hist[size] += 1
            self.bucket_hist[bucket] += 1
            if bucket not in self.warmed_buckets:
                self.bucket_misses += 1
                self.warmed_buckets.add(bucket)
            self._handle_ms.append(handle_ms)
            self._queue_wait_ms.extend(queue_wait_ms)
            for name, ms in (phases or {}).items():
                window = self._phase_ms.get(name)
                if window is not None:
                    window.append(ms)
            if phases_cpu is not None:
                for name, ms in phases_cpu.items():
                    window = self._phase_cpu_ms.get(name)
                    if window is not None:
                        window.append(ms)
                self._host_cpu_ms.append(host_cpu)
                self._host_wait_ms.append(host_wait)
            if host_gap_ms is not None:
                self._host_gap_ms.append(host_gap_ms)

    def record_request(
        self, total_ms: float, wake_ms: float | None = None,
        give_way_ms: float | None = None,
    ) -> None:
        with self._lock:
            self.completed += 1
            self._total_ms.append(total_ms)
            if wake_ms is not None:
                self._wake_ms.append(wake_ms)
            if give_way_ms is not None:
                self._give_way_ms.append(give_way_ms)

    # ------------------------------------------------------------- reporting
    def handle_p50_ms(self) -> float:
        """Median per-batch handle time over the window (0.0 before any
        batch ran) — feeds the batcher's Retry-After estimate."""
        with self._lock:
            p = _percentiles(self._handle_ms, points=(50,))["p50"]
        return p or 0.0

    def to_json(self) -> dict:
        with self._lock:
            real = max(1, self.batched_queries)
            return {
                "startTime": self.start_time.isoformat(),
                "submitted": self.submitted,
                "completed": self.completed,
                "rejected": self.rejected,
                "blockTimeouts": self.block_timeouts,
                "queueDepth": self.queue_depth,
                "inflightBatch": self.inflight_batch,
                "batches": self.batches,
                "overlap": dict(self.overlap),
                "overlapPct": round(
                    100.0 * self.overlap["overlapped"] / self.batches, 2
                ) if self.batches else 0.0,
                "rest": dict(self.rest),
                "batchedQueries": self.batched_queries,
                "meanBatchSize": round(self.batched_queries / self.batches, 2)
                if self.batches
                else 0.0,
                "paddingOverhead": round(self.padded_queries / real, 4),
                "rowsScored": self.rows_scored,
                "rowsReal": self.rows_real,
                **{b: dict(held) for b, held in self.handler_counts.items()},
                "batchSizeHist": {
                    str(k): v for k, v in sorted(self.batch_size_hist.items())
                },
                "bucketHist": {
                    str(k): v for k, v in sorted(self.bucket_hist.items())
                },
                "warmedBuckets": sorted(self.warmed_buckets),
                "bucketMisses": self.bucket_misses,
                "warmupMs": {str(k): v for k, v in sorted(self.warmup_ms.items())},
                "latencyMs": {
                    "queueWait": _percentiles(self._queue_wait_ms),
                    "handle": _percentiles(self._handle_ms),
                    "total": _percentiles(self._total_ms),
                    "wake": _percentiles(self._wake_ms),
                    "giveWay": _percentiles(self._give_way_ms),
                    "hostGap": _percentiles(self._host_gap_ms),
                    "hostCpu": _percentiles(self._host_cpu_ms, mean=True),
                    "hostWait": _percentiles(self._host_wait_ms, mean=True),
                    **{
                        name: _percentiles(window)
                        for name, window in self._phase_ms.items()
                    },
                },
                "cpuMs": {
                    name: _percentiles(window, mean=True)
                    for name, window in self._phase_cpu_ms.items()
                },
            }


class HttpStats:
    """What an HTTP thread of the query server spends on a request around
    the service's ``dispatch``, in milliseconds over the last
    :attr:`ServingStats.WINDOW` requests: ``httpRead`` (the body read
    and parsed), ``httpWrite`` (the answer to JSON bytes and onto the
    socket) and ``inServer``, their sum per request.

    For the requests that rode in a batch (``MicroBatcher.submit``) also
    their whole stretch on the thread, a group of one thread's riders at
    a time (``api/http.py`` ``RIDERS_A_CPU_READ``, or what a connection's
    end left of one; each window sample is a group's mean a request):
    ``request`` (the first instruction after the request line was read,
    ``parse_request``, to the flush of the answer), ``riderCpu`` (the
    thread's CPU time since the group before, over the group's requests:
    a thread burns next to none between its requests) and ``riderWait``
    (``request`` less the time the requests were meant to wait, from
    enqueue until their worker released them, less ``giveWay``, less
    ``riderCpu``: the thread off the CPU though it had work, which is the
    hand-over of the interpreter lock a rider pays). Per group
    ``riderCpu + riderWait + queued + giveWay = request``, each a mean a
    request. ``riderWait`` is the remainder of that identity: on an idle
    server it can read a hair under 0, because the CPU time a thread
    spends going to sleep lies inside the time it was meant to wait.
    Where the CPU clock ticks coarsely (10 ms on the chip's host) read
    the windows' ``mean``, not their ``p50``. ``cpu_ns_riders`` is the
    riders' CPU time since boot."""

    def __init__(self, window: int | None = None):
        self._lock = threading.Lock()
        n = window or ServingStats.WINDOW
        self.requests = 0
        self.cpu_ns_riders = 0
        self._read_ms: deque = deque(maxlen=n)
        self._write_ms: deque = deque(maxlen=n)
        self._in_server_ms: deque = deque(maxlen=n)
        self._request_ms: deque = deque(maxlen=n)
        self._rider_cpu_ms: deque = deque(maxlen=n)
        self._rider_wait_ms: deque = deque(maxlen=n)

    def record(self, read_ms: float, write_ms: float) -> None:
        with self._lock:
            self.requests += 1
            self._read_ms.append(read_ms)
            self._write_ms.append(write_ms)
            self._in_server_ms.append(read_ms + write_ms)

    def record_riders(self, requests: int, request_ns: int, cpu_ns: int,
                      queued_ns: int, give_way_ns: int) -> None:
        """One group of a thread's requests that rode in a batch: how
        many, their stretches on the HTTP thread summed, the thread's CPU
        time over the group, the time from their enqueue until their
        worker released them, and what of the rest they gave way to a
        worker."""
        per_ms = 1e6 * requests
        with self._lock:
            self.cpu_ns_riders += cpu_ns
            self._request_ms.append(request_ns / per_ms)
            self._rider_cpu_ms.append(cpu_ns / per_ms)
            self._rider_wait_ms.append(
                (request_ns - queued_ns - give_way_ns - cpu_ns) / per_ms
            )

    def to_json(self) -> dict:
        with self._lock:
            return {
                "requests": self.requests,
                "latencyMs": {
                    "httpRead": _percentiles(self._read_ms),
                    "httpWrite": _percentiles(self._write_ms),
                    "inServer": _percentiles(self._in_server_ms),
                    "request": _percentiles(self._request_ms, mean=True),
                    "riderCpu": _percentiles(self._rider_cpu_ms, mean=True),
                    "riderWait": _percentiles(self._rider_wait_ms, mean=True),
                },
            }


class LockStats:
    """What the beat thread of a query server (``serving/lockbeat.py``)
    saw of the interpreter lock, from outside the request path:

    * ``acquireMs`` — how late a beat ran after its sleep of 50 ms: what a
      thread that becomes runnable waits before it holds the lock, plus
      the timer's slack (which an idle server shows alone);
    * ``busyPct`` — every fourth beat, 100 x the CPU time the request
      path's threads added (a worker on one of its cycles in 32, an HTTP
      thread once a group of 64 riders) over the wall since the beat
      before: an upper bound of the
      share of wall in which the lock was held at work (numpy and XLA
      calls that let it go, and system calls, count too, so it may pass
      100); the time arrives a group of requests or of cycles at a time,
      so read its ``mean``;
    * ``stalls`` — beats late by over half a second: their ``count``, the
      ``longestMs``, the running sums ``lateMsTotal`` and ``cpuMsTotal``
      (the process's CPU time over the stalls), and of the ``last`` one
      ``at`` (UTC), ``lateMs`` and ``cpuMs``. CPU a small share of the
      lateness: the machine stood still; CPU near the lateness: a thread
      held the lock and worked. No traceback is taken (``lockbeat.py``
      says why).

    Windows keep the last :attr:`ServingStats.WINDOW` samples."""

    def __init__(self, window: int | None = None):
        self._lock = threading.Lock()
        n = window or ServingStats.WINDOW
        self._acquire_ms: deque = deque(maxlen=n)
        self._busy_pct: deque = deque(maxlen=n)
        self.stalls = 0
        self.longest_ms = 0.0
        self.late_ms_total = 0.0
        self.cpu_ms_total = 0.0
        self.last_stall: dict | None = None

    def record_beat(self, late_ms: float, busy_pct: float | None) -> None:
        with self._lock:
            self._acquire_ms.append(late_ms)
            if busy_pct is not None:
                self._busy_pct.append(busy_pct)

    def record_stall(self, late_ms: float, cpu_ms: float) -> None:
        with self._lock:
            self.stalls += 1
            self.longest_ms = max(self.longest_ms, late_ms)
            self.late_ms_total += late_ms
            self.cpu_ms_total += cpu_ms
            self.last_stall = {
                "at": _dt.datetime.now(_dt.timezone.utc).isoformat(),
                "lateMs": round(late_ms, 3),
                "cpuMs": round(cpu_ms, 3),
            }

    def to_json(self) -> dict:
        with self._lock:
            return {
                "acquireMs": _percentiles(self._acquire_ms),
                "busyPct": _percentiles(self._busy_pct, mean=True),
                "stalls": {
                    "count": self.stalls,
                    "longestMs": round(self.longest_ms, 3),
                    "lateMsTotal": round(self.late_ms_total, 3),
                    "cpuMsTotal": round(self.cpu_ms_total, 3),
                    "last": dict(self.last_stall) if self.last_stall else None,
                },
            }
