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
  request outside the service: reading it and writing the answer.
"""

from __future__ import annotations

import datetime as _dt
import threading
from collections import Counter, deque
from typing import Mapping, Sequence

__all__ = ["Stats", "ServingStats", "HttpStats", "BATCH_PHASES"]


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


def _percentiles(samples, points=(50, 95, 99)) -> dict[str, float]:
    """Nearest-rank percentiles of a sample window, no numpy needed on
    this hot-ish path."""
    if not samples:
        return {f"p{p}": None for p in points}
    s = sorted(samples)
    out = {}
    for p in points:
        idx = min(len(s) - 1, max(0, int(round(p / 100.0 * len(s))) - 1))
        out[f"p{p}"] = round(s[idx], 3)
    return out


#: a batcher worker's leaf spans (utils/spans.py), in the order it
#: passes through them from one ``handle_batch`` return to the next; each
#: is a window of ``latencyMs``
BATCH_PHASES = (
    "release", "take", "drain", "batchForm",
    "bind", "lookup", "queryVectors", "filterLookup", "filterBuild",
    "dispatch", "deviceWait", "format",
)

#: what a handler that filters its answers counts on the dispatcher's
#: collector as ``filter.<name>`` (templates/retrieval.py
#: ``FilteredItemRetrieval`` and the two engines that take it)
FILTER_COUNTS = ("excludedIds", "excludedPairs", "categoryRows", "hostPath",
                 "shortAnswers")

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
      (``serving/batcher.py``).

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
      first batch and for handlers without a device).

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
    ``num`` items). ``similar`` counts the similar-product engine's query
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
        self._host_gap_ms: deque = deque(maxlen=n)
        self._phase_ms = {name: deque(maxlen=n) for name in BATCH_PHASES}

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
        host_gap_ms: float | None = None,
        overlapped: bool = False,
        rows_scored: int = 0,
        rows_real: int = 0,
        counts: Mapping[str, int] | None = None,
    ) -> None:
        """One dispatched batch: its riders' queue waits, ``handle``, the
        worker's ``phases`` ({name: ms}, names of
        :data:`BATCH_PHASES`), the host gap before it, whether it was
        dispatched while the batch before it was on the device, the rows its
        scoring dispatches took and really held, and the handler's other
        ``counts``: those named ``<block>.<name>`` for a block of
        ``handler_counts`` (``filter``, ``similar``, ``select``). The names
        of ``FILTER_COUNTS``, ``SIMILAR_COUNTS`` and ``SELECT_PLANS`` are
        there from the start at 0; any other (``filter.pairBucket.<P>``)
        makes its key when first counted."""
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
            if host_gap_ms is not None:
                self._host_gap_ms.append(host_gap_ms)

    def record_request(
        self, total_ms: float, wake_ms: float | None = None
    ) -> None:
        with self._lock:
            self.completed += 1
            self._total_ms.append(total_ms)
            if wake_ms is not None:
                self._wake_ms.append(wake_ms)

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
                    "hostGap": _percentiles(self._host_gap_ms),
                    **{
                        name: _percentiles(window)
                        for name, window in self._phase_ms.items()
                    },
                },
            }


class HttpStats:
    """What an HTTP thread of the query server spends on a request around
    the service's ``dispatch``, in milliseconds over the last
    :attr:`ServingStats.WINDOW` requests: ``httpRead`` (the body read
    and parsed), ``httpWrite`` (the answer to JSON bytes and onto the
    socket) and ``inServer``, their sum per request."""

    def __init__(self, window: int | None = None):
        self._lock = threading.Lock()
        n = window or ServingStats.WINDOW
        self.requests = 0
        self._read_ms: deque = deque(maxlen=n)
        self._write_ms: deque = deque(maxlen=n)
        self._in_server_ms: deque = deque(maxlen=n)

    def record(self, read_ms: float, write_ms: float) -> None:
        with self._lock:
            self.requests += 1
            self._read_ms.append(read_ms)
            self._write_ms.append(write_ms)
            self._in_server_ms.append(read_ms + write_ms)

    def to_json(self) -> dict:
        with self._lock:
            return {
                "requests": self.requests,
                "latencyMs": {
                    "httpRead": _percentiles(self._read_ms),
                    "httpWrite": _percentiles(self._write_ms),
                    "inServer": _percentiles(self._in_server_ms),
                },
            }
