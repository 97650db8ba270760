"""Dynamic cross-request micro-batcher for the online query path.

The per-request serving path (``api/http.py`` -> ``QueryService
.handle_query``) pays one full predict dispatch per HTTP request: under
concurrency the device serializes on single-query programs while
``handle_batch`` demonstrably amortizes the same work across a whole
batch (see ``docs/performance.md``). This module closes that gap the way
TPU serving stacks do (cf. ALX's batched matrix-factorization serving,
arxiv 2112.02194): requests from independent HTTP handler threads
enqueue into a bounded queue with a per-request completion event; a
worker drains up to ``max_batch_size`` requests or waits
``max_batch_delay_ms`` past the oldest request (whichever comes first),
pads the batch up to a small set of **bucket sizes** so the jitted
predict programs compile once per bucket (warm-up at startup
pre-compiles all of them), routes the batch through the existing
``QueryService.handle_batch`` / ``batch_predict_base`` path — which
already guarantees per-item error isolation — and resolves each waiting
request with its own ``(status, payload)``.

**Two batches in flight.** Two workers share the queue. Forming (take +
drain) is serial under one forming lock, so a request rides in exactly
one batch, batches leave the queue in arrival order and a backlog of 64
is two batches of 32. ``handle_batch`` runs outside that lock: while one
worker blocks in the readback of batch n (which releases the interpreter
lock), the other forms batch n+1, binds it, reads the store for it and
calls its scoring program. JAX dispatch is asynchronous and the device
runs programs in the order they were enqueued, so the second program
starts the instant the first ends. Two, because a cycle has two halves
(the host's and the device's); a third batch would only queue behind the
second on the device and add a cycle to every rider's wait.

**The second batch is a full one.** With no batch in flight a worker
forms as the single dispatcher did: the first request, then the drain.
With one in flight the other worker forms only once the queue holds
``max_batch_size`` requests: the batch the drain would end on with no
waiting, so going now costs nobody a batchmate. Short of that it waits
for the batch in flight to return, as the requests behind a single
dispatcher did, and then forms by the first rule. So a second batch
never spreads the callers over more and smaller batches than one
dispatcher would have made of them (each batch has a batch's whole
overhead, and where the cycle is the interpreter lock's, that is all a
part-full second batch brings: PERF.md, PR 31), an idle or lightly
loaded server behaves as with one worker, and a backlogged one keeps
two batches out.

**And only while it finds the device busy.** A second batch pays when
part of its host half runs under the first one's device time. Where a
cycle is the interpreter lock's and not the device's (a small model:
the lock is shared with the HTTP threads), the second batch's program
meets an idle device every time (measured: 0.5% of the batches were
enqueued behind a running program there; where it pays, 45% of them
with the device the longer half, PERF.md, PR 31, and still 4-15% with
the host the longer half, where a second batch hides only part of its
host half and is worth 7% of the rate all the same, PERF.md, PR 37) and
all it does is empty the queue, so that now and then both
workers return to a short one and a part-full batch goes. So after
``_ALONE_RUN`` second batches in a row whose program met an idle device
no second batch goes for ``_REST_S`` seconds (the single dispatcher's
behaviour), then again. What the batcher observes is the queue's depth,
its own count of batches in flight and its own batches' spans: no flag,
no field.

**A loaded batch's worker has the right of way.** A worker and the HTTP
threads share the interpreter lock, and each step of a worker's host half
that lets go of it (a row gather, a store read, the call into the
runtime) queues behind every thread that wants it: among the 32 riders a
batch has just released, a host half of 3 ms took 10 to 30, the device
stood idle for it, and the next batch went out beside this one instead
of behind it (PERF.md, PR 36). A worker holds a batch's worth of callers
and the device; a rider holds one caller. So from the moment a worker has
gathered a batch at least half full (the sign of a backlog, where the
rate is what the callers pay for) until its program is enqueued (its
``dispatch`` span closes; for a handler without one, until it returns),
a rider that wakes waits, at most ``_GIVE_WAY_S``, before it goes on to
serialize its answer and read its caller's next request. A smaller batch
claims nothing: a lightly loaded server's riders never wait.

**And only while the batches stay full for it.** Where the riders and not
the worker are what a cycle waits for (a small model: the program is back
before the riders of the batch before it are), a worker that goes first
only finds a shorter queue: it makes more and smaller batches, each with a
batch's whole overhead (measured: 31.2 -> 27.1 queries a batch and 3.7%
fewer answers; PERF.md, PR 36). So the batcher watches the batch formed
after each claim: when more than ``_SHORT_SHARE`` of ``_CLAIM_WINDOW``
claims were followed by a part-full batch, none claims for ``_REST_S``
seconds, then again. Where the right of way pays, the batch after a claim
is a full one (99.6% of them, measured).

Admission control is explicit: when the queue is full the configured
policy either rejects immediately (HTTP 429 + ``Retry-After``) or
blocks the caller up to ``block_timeout_ms`` (503 on timeout). Queue
depth, in-flight batch state, bucket hit/miss counts and the latency
decomposition (per request: queue wait, total, wake and what of it gave
way to a worker; per batch: its worker's phases as spans of
``utils/spans.py``, each with the worker thread's CPU time beside its
wall, the host gap before it and whether it overlapped the batch before
it), and how often the batcher sent itself to either rest, are recorded in
:class:`predictionio_tpu.api.stats.ServingStats` and served from the
query server's ``GET /stats.json``. Each worker's leaf spans are also
``pio.*`` events in a running ``jax.profiler`` trace, one flat line a
worker.

No reference counterpart: the reference serves one query per spray
route invocation. This is the TPU-native replacement for that hot path.

NOTE: this module must not import jax (see package docstring) — batching
is host-side orchestration; the device work stays behind
``handle_batch``.
"""

from __future__ import annotations

import dataclasses
import enum
import logging
import queue
import threading
import time
from typing import Any, Callable, Sequence

from predictionio_tpu.api.stats import ServingStats
from predictionio_tpu.utils import spans
from predictionio_tpu.utils.spans import span

__all__ = ["AdmissionPolicy", "BatcherConfig", "MicroBatcher"]

logger = logging.getLogger(__name__)

#: a submit() whose dispatcher never answers (a bug, not a slow model)
#: must not hang the HTTP handler thread forever
_RESULT_TIMEOUT_S = 300.0

#: batches in flight at once: one on the device and one being prepared
#: cover a cycle of two halves (module text)
_WORKERS = 2
#: second batches in a row whose program met an idle device, after which
#: none goes for _REST_S seconds. Where a second batch pays, between one in
#: two (the device the longer half: PERF.md, PR 31) and one in twenty (the
#: host the longer half: PR 37) is enqueued behind a running program; where
#: it does not, one in two hundred. 128 in a row is 2**-110 by the first
#: share, 0.1% by the second and two seconds' worth of batches by the last
#: (at a cost of some 3% of the rate while they last). At 64 the second
#: share made it 4%: such a server rested half its time, and more with
#: every microsecond added to its host half (a host half 1.6% longer took
#: the share from 12% to 7%: the rest, not the work, cost 5% of the rate;
#: PERF.md, PR 37). A rest in seconds, not in batches, so that being
#: wrong costs a slow model no more than a fast one
_ALONE_RUN = 128
_REST_S = 10.0
#: the longest a rider that has its answer waits for a worker's host half
#: (module text): ten of those halves; one that takes longer (a compile, a
#: store that stalls) holds no caller up for it
_GIVE_WAY_S = 0.05
#: claims a window of the watch on the batches formed after them, and the
#: share of part-full ones among those from which claims rest for _REST_S
#: (module text): 0.4% of them where the right of way pays, 59% where it
#: does not
_CLAIM_WINDOW = 32
_SHORT_SHARE = 0.25
#: one of a worker's cycles in this many takes CPU time: its spans'
#: (``hostCpu``, ``hostWait``, ``cpuMs``; some 28 reads of the thread's CPU
#: clock) and, one read more, the thread's total since it last did
#: (``cpuNs.workers``). That clock is a system call made under the
#: interpreter lock: 0.3 us on a plain Linux host; on the chip's 6 us idle
#: and, by what the spans grew, 10-40 us under load (PERF.md, PR 37). Read
#: around every span and every request it cost ``serve_saturated`` 3-15%
#: there; at one cycle in 16 with the total read every cycle, this and the
#: counts a request together still cost the similar-product cell 1.6-2.5%
#: with two batches in flight, and twice that through the second batches'
#: rest (module text: a longer host half meets a busy device less often).
#: The share is fixed, not steered by a measured cost, so that a host's
#: numbers mean the same everywhere
_CPU_EVERY = 32


class AdmissionPolicy(str, enum.Enum):
    """What a full queue does to a new request."""

    REJECT = "reject"  # immediate 429 + Retry-After
    BLOCK = "block"  # wait up to block_timeout_ms for a slot, then 503


def _pow2_buckets(max_batch_size: int) -> tuple[int, ...]:
    """1, 2, 4, ... capped at (and always including) ``max_batch_size``."""
    sizes = [1]
    while sizes[-1] * 2 < max_batch_size:
        sizes.append(sizes[-1] * 2)
    if sizes[-1] != max_batch_size:
        sizes.append(max_batch_size)
    return tuple(sizes)


@dataclasses.dataclass(frozen=True)
class BatcherConfig:
    """Knobs of the micro-batcher (CLI: ``pio deploy --batching ...``).

    ``max_batch_delay_ms=0`` is a legal configuration: a lone request
    dispatches immediately (no added latency) and batching still happens
    opportunistically whenever multiple requests are already queued.
    """

    max_batch_size: int = 32
    #: how long a worker waits past the OLDEST queued request for
    #: batchmates; the p99 latency a request can gain over the
    #: per-request path is bounded by ~2x this (one wait while queued +
    #: the batch being formed ahead of it) and, short of a full batch
    #: queued, the rest of the batch in flight ahead of it
    max_batch_delay_ms: float = 2.0
    #: bounded admission queue; full -> the admission policy applies
    max_queue: int = 256
    admission: AdmissionPolicy = AdmissionPolicy.REJECT
    #: BLOCK policy only: how long submit() may wait for a queue slot
    block_timeout_ms: float = 1000.0
    #: batch sizes jit programs are padded to; () = powers of two up to
    #: ``max_batch_size``. Every dispatched batch is padded UP to the
    #: smallest bucket >= its size, so after warm-up no new predict
    #: shapes (hence no recompiles) occur.
    buckets: tuple[int, ...] = ()
    #: sample query body used to pre-compile every bucket at startup
    #: (None = skip warm-up; the first live batch of each bucket pays
    #: the compile instead)
    warmup_body: Any = None

    def __post_init__(self) -> None:
        if self.max_batch_size < 1:
            raise ValueError("max_batch_size must be >= 1")
        if self.max_queue < 1:
            raise ValueError("max_queue must be >= 1")
        if self.max_batch_delay_ms < 0:
            raise ValueError("max_batch_delay_ms must be >= 0")
        # accept plain strings from CLI/JSON configs
        object.__setattr__(
            self, "admission", AdmissionPolicy(self.admission)
        )
        if self.buckets:
            raw = sorted(set(int(x) for x in self.buckets))
            if raw[0] < 1:
                raise ValueError("bucket sizes must be >= 1")
            # buckets beyond max_batch_size can never be filled — they
            # would only inflate padding (and compile a dead shape)
            b = tuple(x for x in raw if x <= self.max_batch_size)
            if not b or b[-1] < self.max_batch_size:
                # the largest bucket must fit a full batch or padding
                # would have to truncate
                b = b + (self.max_batch_size,)
            object.__setattr__(self, "buckets", b)

    def bucket_sizes(self) -> tuple[int, ...]:
        return self.buckets or _pow2_buckets(self.max_batch_size)


class _Pending:
    __slots__ = ("body", "enqueued_at", "enqueued_ns", "done", "result",
                 "drained", "seq", "released_ns", "worker")

    def __init__(self, body: Any):
        self.body = body
        self.enqueued_at = time.monotonic()
        #: the same instant on the clock of ``released_ns``
        self.enqueued_ns = time.perf_counter_ns()
        self.done = threading.Event()
        self.result: tuple[int, Any] | None = None
        #: sequence number of the batch this request rode in
        self.seq = 0
        #: the worker thread that took this request off the queue
        self.worker: threading.Thread | None = None
        #: ``perf_counter_ns`` just before the dispatcher's ``done.set()``
        self.released_ns = 0
        #: answered by a dead-queue drain (shutdown / dead dispatcher),
        #: not by a dispatched batch — kept out of the latency stats
        self.drained = False


class _Flight:
    """What one batch's worker measured, until the batch is accounted."""

    __slots__ = ("record", "beside", "take_start_ns", "take_end_ns",
                 "dispatched_ns", "device_done_ns")

    def __init__(self, cycle: Sequence[spans.SpanRecord], record: dict,
                 beside: bool):
        #: the keywords of ``ServingStats.record_batch``
        self.record = record
        #: formed while another batch was in flight: a second batch
        self.beside = beside
        self.take_start_ns, self.take_end_ns = next(
            ((r.start_ns, r.end_ns) for r in cycle if r.name == "take"), (0, 0)
        )
        #: when the batch's (first) scoring program was enqueued, and when
        #: its last readback returned; None for a handler without a device
        self.dispatched_ns = next(
            (r.end_ns for r in cycle if r.name == "dispatch"), None
        )
        self.device_done_ns = max(
            (r.end_ns for r in cycle if r.name == "deviceWait"), default=None
        )


class MicroBatcher:
    """Coalesces concurrent ``submit()`` calls into ``handle_batch`` calls.

    ``handle_batch`` is any ``Sequence[body] -> list[(status, payload)]``
    aligned with its input — in production,
    :meth:`QueryService.handle_batch`, which already provides per-item
    error isolation (one poisoned query gets its own 4xx/5xx; its
    batchmates still get answers).
    """

    def __init__(
        self,
        handle_batch: Callable[[Sequence[Any]], list[tuple[int, Any]]],
        config: BatcherConfig | None = None,
        stats: ServingStats | None = None,
    ):
        self.config = config or BatcherConfig()
        self.stats = stats or ServingStats()
        self._handle = handle_batch
        # handlers that understand padding (QueryService.handle_batch)
        # get told how many leading slots are real, so filler queries pay
        # only predict compute — no serve tail, plugins, feedback, or
        # query-count side effects
        try:
            import inspect

            self._wants_n_real = (
                "n_real" in inspect.signature(handle_batch).parameters
            )
        except (TypeError, ValueError):
            self._wants_n_real = False
        self._buckets = self.config.bucket_sizes()
        self._queue: "queue.Queue[_Pending | None]" = queue.Queue(
            maxsize=self.config.max_queue
        )
        # guards writes to _closed (shared with submit() on HTTP handler
        # threads; piolint PIO201 keeps every post-__init__ write under
        # it) and the workers' shared bookkeeping below. Readers of
        # _closed stay lock-free on purpose: the submit/close race
        # is resolved by submit()'s post-enqueue re-check plus the
        # idempotent _drain_dead_queue(), not by mutual exclusion
        self._lock = threading.Lock()
        self._closed = False
        # held over take + drain: one worker gathers a batch at a time
        self._form_lock = threading.Lock()
        #: batches dispatched so far; the last one's sequence number
        self._seq = 0
        #: the last batch accounted (batches are, in the order of their
        #: numbers) and those that returned ahead of an earlier one
        self._accounted = 0
        self._returned: dict[int, _Flight | None] = {}
        #: the latest deviceWait end of the batches accounted so far
        self._device_done_ns: int | None = None
        #: batches inside handle_batch now; the event wakes a worker that
        #: waits to form (_may_form): set when one returns, when the queue
        #: reaches a full batch, and by close()
        self._in_flight = 0
        self._go = threading.Event()
        #: second batches in a row that met an idle device, and until when
        #: (``time.monotonic``) none goes because of it (_account)
        self._alone_run = 0
        self._shut_until = 0.0
        #: workers between a loaded batch gathered and its program enqueued;
        #: the event is set while there is none, and riders that wake wait
        #: for it (_claim_floor)
        self._preparing = 0
        self._floor = threading.Event()
        self._floor.set()
        #: did the batch formed last claim; of the claims of this window,
        #: how many, and how many a part-full batch followed; until when
        #: (``time.monotonic``) none claims because of it (_number)
        self._claimed_last = False
        self._claims = self._short_after = 0
        self._no_claim_until = 0.0
        if self.config.warmup_body is not None:
            self.warmup(self.config.warmup_body)
        self._threads = [
            threading.Thread(
                target=self._loop, name=f"pio-batcher-{i}", daemon=True
            )
            for i in range(_WORKERS)
        ]
        for t in self._threads:
            t.start()

    # ------------------------------------------------------------ client API
    def submit(self, body: Any) -> tuple[int, Any]:
        """Enqueue one query and block until its slice of a batch result
        is available. Returns ``(status, payload)`` exactly like
        ``QueryService.handle_query``."""
        cfg = self.config
        if self._closed:
            return 503, {"message": "Serving runtime is shut down."}
        if not self._workers_alive():
            # a dead worker (a bug — the loop is defensive) must fail
            # fast with a clean 503, not park the HTTP thread for the
            # full result timeout; /readyz turns unready via
            # dispatcher_alive() so orchestrators restart the pod
            self.stats.record_rejected()
            return 503, {
                "message": "Serving runtime dispatcher is not running.",
                "retryAfterSeconds": self.retry_after_seconds(),
            }
        pending = _Pending(body)
        try:
            if cfg.admission is AdmissionPolicy.REJECT:
                self._queue.put_nowait(pending)
            else:
                self._queue.put(pending, timeout=cfg.block_timeout_ms / 1000.0)
        except queue.Full:
            retry_after = self.retry_after_seconds()
            if cfg.admission is AdmissionPolicy.REJECT:
                self.stats.record_rejected()
                return 429, {
                    "message": "Server busy: batching queue is full.",
                    "retryAfterSeconds": retry_after,
                }
            self.stats.record_block_timeout()
            return 503, {
                "message": "Server busy: no queue slot within "
                f"{cfg.block_timeout_ms:g} ms.",
                "retryAfterSeconds": retry_after,
            }
        depth = self._queue.qsize()
        self.stats.record_submitted(depth)
        if depth >= cfg.max_batch_size:
            self._go.set()  # a full batch: a second one may go (_may_form)
        if self._closed:
            # raced with close(): the dispatcher may already be past its
            # final drain, so this request could sit in a dead queue —
            # answer everything still enqueued ourselves (idempotent with
            # close()'s own post-join drain; done.set() is at-most-once
            # effective)
            self._drain_dead_queue()
        give_up_at = time.monotonic() + _RESULT_TIMEOUT_S
        while not pending.done.wait(timeout=1.0):
            if not self._workers_alive():
                # a worker died while this request was queued: answer
                # every stranded request (ours included) instead of
                # letting them sit out the full result timeout
                self._drain_dead_queue(
                    "Serving runtime dispatcher died; request not processed."
                )
                if pending.done.is_set():
                    break
                worker = pending.worker
                if worker is not None and not worker.is_alive():
                    # in-flight when its worker died (not in the queue):
                    # manufacture the same 503, and count it like every
                    # other rejected response so /stats.json stays
                    # truthful during the incident
                    self.stats.record_rejected()
                    return 503, {
                        "message": (
                            "Serving runtime dispatcher died; request not "
                            "processed."
                        ),
                        "retryAfterSeconds": self.retry_after_seconds(),
                    }
                # else riding in the live worker's batch: it will answer,
                # or the result timeout below binds as ever
            if time.monotonic() >= give_up_at:
                return 500, {"message": "Batch dispatcher did not respond."}
        # a worker preparing a loaded batch goes first (module text)
        gave_way_ns = time.perf_counter_ns()
        self._floor.wait(timeout=_GIVE_WAY_S)
        woke_ns = time.perf_counter_ns()
        assert pending.result is not None
        if pending.drained:
            # a shutdown/dead-dispatcher 503, not a served request: keep
            # it out of the latency decomposition an operator reads
            # during exactly this kind of incident
            self.stats.record_rejected()
        else:
            self.stats.record_request(
                total_ms=(time.monotonic() - pending.enqueued_at) * 1e3,
                wake_ms=(woke_ns - pending.released_ns) / 1e6,
                give_way_ms=(woke_ns - gave_way_ns) / 1e6,
            )
            collector = spans.current()
            if collector is not None:
                # this request's spans share its batch's identifier
                collector.seq = pending.seq
                # for the HTTP thread's account of its riders
                # (api/http.py): how long this one was meant to wait, and
                # what of the rest it gave way
                spans.count("rider.requests", 1)
                spans.count(
                    "rider.queuedNs", pending.released_ns - pending.enqueued_ns
                )
                spans.count("rider.giveWayNs", woke_ns - gave_way_ns)
        return pending.result

    def retry_after_seconds(self) -> int:
        """Backoff hint for admission-control responses (the 429
        ``Retry-After`` header / ``retryAfterSeconds`` field): worst-case
        time for a full queue to drain, using the MEASURED per-batch
        handle time — the batch-forming delay alone would claim ~1 s
        while a slow model really needs many."""
        cfg = self.config
        waves = -(-cfg.max_queue // cfg.max_batch_size)
        per_wave_ms = cfg.max_batch_delay_ms + self.stats.handle_p50_ms()
        return max(1, -(-int(waves * per_wave_ms) // 1000))

    def warmup(self, body: Any) -> None:
        """Pre-compile every bucket shape with ``body`` replicated, largest
        first (jit caches often make smaller related shapes cheaper after
        the big one). Warm-up traffic flows through the REAL batch path so
        the exact programs live traffic will hit are the ones compiled.

        Every power of two under the largest bucket is sent as well: a
        live batch can reach the device with fewer rows than its bucket
        (a query for an unknown user is answered without one), and a
        scoring program's rows are a power of two (``ops/topk.py``
        ``bucket_rows``), so under ``--batch-buckets 32`` a batch of 32
        with 20 unknown users must not meet a shape first."""
        sizes = set(self._buckets)
        sizes.update(
            1 << s for s in range((self._buckets[-1] - 1).bit_length())
        )
        for size in sorted(sizes, reverse=True):
            t0 = time.monotonic()
            try:
                # n_real=0: every slot is padding — full predict compile,
                # zero serve-tail side effects (no plugin/feedback/count)
                self._call([body] * size, n_real=0)
            except Exception:
                # a bad warm-up body must not kill deploy; the bucket
                # simply compiles on first live traffic instead
                logger.exception("micro-batcher warm-up failed at size %d", size)
                continue
            if size in self._buckets:
                self.stats.record_warmup(size, (time.monotonic() - t0) * 1e3)

    def _workers_alive(self) -> bool:
        return all(t.is_alive() for t in self._threads)

    def dispatcher_alive(self) -> bool:
        """Are both workers able to answer submissions? False as soon as
        either is dead. Feeds the query server's ``/readyz`` readiness
        probe."""
        return not self._closed and self._workers_alive()

    def close(self) -> None:
        """Stop the workers. Batches already formed are answered
        normally; anything still queued (or racing in) gets 503."""
        with self._lock:
            self._closed = True
        self._go.set()
        try:
            self._queue.put_nowait(None)  # wake the worker in take at once
        except queue.Full:
            pass  # it looks at _closed at least every 50 ms
        for t in self._threads:
            t.join(timeout=5.0)
        # a submit() that passed its _closed check concurrently with this
        # close may have enqueued after the workers' final drain
        self._drain_dead_queue()

    def _drain_dead_queue(
        self, message: str = "Serving runtime is shut down."
    ) -> None:
        while True:
            try:
                item = self._queue.get_nowait()
            except queue.Empty:
                return
            if item is not None:
                item.drained = True
                item.result = (503, {"message": message})
                item.done.set()

    # ------------------------------------------------------------ dispatcher
    def _call(self, bodies: Sequence[Any], n_real: int) -> list[tuple[int, Any]]:
        if self._wants_n_real:
            return self._handle(bodies, n_real=n_real)
        return self._handle(bodies)

    def _bucket_for(self, n: int) -> int:
        for size in self._buckets:
            if size >= n:
                return size
        return self._buckets[-1]

    def _drain(self, first: _Pending) -> list[_Pending]:
        """Collect up to ``max_batch_size`` requests, waiting at most
        ``max_batch_delay_ms`` past the arrival of ``first``."""
        cfg = self.config
        batch = [first]
        deadline = first.enqueued_at + cfg.max_batch_delay_ms / 1000.0
        while len(batch) < cfg.max_batch_size:
            remaining = deadline - time.monotonic()
            try:
                if remaining <= 0:
                    # deadline passed: take whatever is already queued,
                    # but never wait for more
                    item = self._queue.get_nowait()
                else:
                    item = self._queue.get(timeout=remaining)
            except queue.Empty:
                break
            if item is None:  # close() sentinel
                break
            item.worker = threading.current_thread()
            batch.append(item)
        return batch

    def _take(self) -> _Pending | None:
        """Block until a request is queued; None once closed (what is
        queued then is answered 503 by the dead-queue drain)."""
        while not self._closed:
            try:
                first = self._queue.get(timeout=0.05)
            except queue.Empty:
                continue
            if first is not None:  # else close()'s sentinel
                first.worker = threading.current_thread()
                return first
        return None

    def _may_form(self) -> bool:
        """May a worker gather a batch now? With none in flight, always
        (the single dispatcher's rule); with one, only a full batch, and
        not while second batches rest (module text); with two, no."""
        in_flight = self._in_flight
        return in_flight == 0 or (
            in_flight < _WORKERS
            and self._queue.qsize() >= self.config.max_batch_size
            and time.monotonic() >= self._shut_until
        )

    def _loop(self) -> None:
        # a worker feeds the device: its leaf spans also go into a
        # running profiler trace (utils/spans.py), and on one of this
        # worker's cycles in _CPU_EVERY (its first is one) each records the
        # thread's CPU time beside its wall; taken once a batch
        collector = spans.Collector(annotate=True, cpu=True)
        spans.bind(collector)
        cpu_ns = time.thread_time_ns()
        cycles = 0
        while True:
            while True:
                # cleared before the look, so that what is set after it
                # (a batch returned, the queue filled up) ends the wait
                self._go.clear()
                if self._closed or self._may_form():
                    break
                self._go.wait(timeout=0.05)
            with self._form_lock:
                if not (self._closed or self._may_form()):
                    continue  # the other worker's batch went out meanwhile
                with span("take"):
                    first = self._take()
                if first is None:
                    break
                with span("drain"):
                    batch = self._drain(first)
                # before the forming lock goes: the next to form sees it
                beside, claims = self._number(collector, len(batch))
            cycles += 1
            sampled = cycles % _CPU_EVERY == 0
            self._dispatch(batch, collector, beside, claims, cpu_next=sampled)
            if sampled:
                # the whole thread's CPU time since it was last read, the
                # forming's polls and the accounting included
                cpu_ns, before = time.thread_time_ns(), cpu_ns
                self.stats.record_worker_cpu(cpu_ns - before)
        # drain leftovers so no client hangs on shutdown
        self._drain_dead_queue()

    def _number(self, collector: spans.Collector, size: int) -> tuple[bool, bool]:
        """Give the batch of ``size`` just formed its number and count it
        in flight. Was another in flight already, and does this one claim
        the right of way (module text)?"""
        full = size >= self.config.max_batch_size
        rests = False
        with self._lock:
            self._seq += 1
            collector.seq = self._seq
            self._in_flight += 1
            if self._claimed_last:  # what a claim left the next batch
                self._claims += 1
                self._short_after += not full
                if self._claims >= _CLAIM_WINDOW:
                    if self._short_after > _SHORT_SHARE * _CLAIM_WINDOW:
                        self._no_claim_until = time.monotonic() + _REST_S
                        rests = True
                    self._claims = self._short_after = 0
            self._claimed_last = (
                2 * size >= self.config.max_batch_size
                and time.monotonic() >= self._no_claim_until
            )
            numbered = self._in_flight > 1, self._claimed_last
        if rests:
            self.stats.record_rest("claims")
        return numbered

    def _claim_floor(self, collector: spans.Collector) -> Callable[[], None]:
        """The calling worker has gathered a loaded batch: riders that wake
        wait until its ``dispatch`` span closes or the returned function is
        called, whichever is first (module text)."""

        def give(name: str = "dispatch") -> None:
            if name != "dispatch" or collector.on_close is None:
                return
            collector.on_close = None
            with self._lock:
                self._preparing -= 1
                if not self._preparing:
                    self._floor.set()

        with self._lock:
            self._preparing += 1
            self._floor.clear()
        collector.on_close = give
        return give

    def _dispatch(
        self, batch: list[_Pending], collector: spans.Collector, beside: bool,
        claims: bool, cpu_next: bool = False,
    ) -> None:
        """Pad, run and answer one batch on the calling thread, a worker's:
        ``collector`` is the one bound to it; ``beside``: formed while
        another batch was in flight; ``claims``: its host half has the
        right of way; ``cpu_next``: the spans of the cycle that begins with
        this batch's release take CPU time. What lies between two of a worker's
        batches (release, take, drain) carries the earlier one's sequence
        number."""
        with span("batchForm"):
            formed_at = time.monotonic()
            waits = [(formed_at - p.enqueued_at) * 1e3 for p in batch]
            bodies = [p.body for p in batch]
            bucket = self._bucket_for(len(bodies))
            # pad with a copy of the first body: identical query class and
            # shape guarantees, results beyond len(bodies) are discarded
            padded = bodies + [bodies[0]] * (bucket - len(bodies))
            self.stats.record_batch_start(self._queue.qsize())
        flight = None
        give_floor = self._claim_floor(collector) if claims else None
        try:
            with span("handle", enclosing=True) as handle:
                try:
                    results = self._call(padded, n_real=len(bodies))
                    if len(results) < len(bodies):  # defensive: misaligned handler
                        raise RuntimeError(
                            f"handle_batch returned {len(results)} results "
                            f"for {len(padded)} queries"
                        )
                except Exception:
                    # handle_batch isolates per-item errors itself; reaching
                    # this means the batch MACHINERY failed — answer everyone
                    # rather than hanging the HTTP threads. Generic message:
                    # exception text can leak internals (details go to the log)
                    logger.exception("micro-batch dispatch failed")
                    results = [
                        (500, {"message": "Batch dispatch failed; see server log."})
                    ] * len(bodies)
            # one cycle of this worker: its previous batch's release, then
            # this batch's take ... handle
            cycle = collector.take()
            counts = collector.take_counts()
            # the cycle that starts here (with this batch's release) takes
            # CPU time or not as a whole
            took_cpu = collector.cpu
            collector.cpu = cpu_next
            flight = _Flight(
                cycle,
                dict(
                    size=len(bodies),
                    bucket=bucket,
                    handle_ms=handle.ms,
                    queue_wait_ms=waits,
                    phases=spans.durations_ms(cycle),
                    phases_cpu=spans.cpu_ms(cycle) if took_cpu else None,
                    rows_scored=counts.get("rowsScored", 0),
                    rows_real=counts.get("rowsReal", 0),
                    counts=counts,
                ),
                beside,
            )
        finally:
            # also when the handler killed this worker: the batches formed
            # after this one, and the riders, must not wait for it
            if give_floor is not None:
                give_floor()
            self._account(collector.seq, flight)
        with span("release"):
            for p, result in zip(batch, results):
                p.result = result
                p.seq = collector.seq
                p.released_ns = time.perf_counter_ns()
                p.done.set()

    def _account(self, seq: int, flight: "_Flight | None") -> None:
        """Hand batch ``seq``'s measurements to the stats. Batches are
        accounted in the order of their numbers, so that the ``deviceWait``
        end a batch's host gap is measured from is known: one that returns
        ahead of an earlier one is kept until that one has returned (its
        riders are not). An earlier one that stays out while more than
        ``_WORKERS`` wait for it (a handler that hangs) is passed over, and
        accounted with no gap whenever it does return. ``flight`` None:
        the batch measured nothing."""
        ready = []
        rests = False
        with self._lock:
            self._in_flight -= 1
            if seq <= self._accounted:  # passed over: nothing to measure from
                if flight is not None:
                    ready.append(flight.record)
            else:
                self._returned[seq] = flight
            while (self._accounted + 1 in self._returned
                   or len(self._returned) > _WORKERS):
                self._accounted += 1
                flight = self._returned.pop(self._accounted, None)
                if flight is None:
                    continue
                done = self._device_done_ns
                if flight.dispatched_ns is not None and done is not None:
                    # a program enqueued while the one before it still
                    # runs kept the device waiting 0 ms; an empty queue
                    # starves it through no fault of the host code: what
                    # of take lies in the gap is not the host's
                    gap_ns = (
                        flight.dispatched_ns - done
                        - max(0, flight.take_end_ns - max(flight.take_start_ns, done))
                    )
                    flight.record["host_gap_ms"] = max(0.0, gap_ns / 1e6)
                    flight.record["overlapped"] = flight.dispatched_ns < done
                if flight.device_done_ns is not None:
                    self._device_done_ns = max(flight.device_done_ns, done or 0)
                ready.append(flight.record)
                if flight.beside and "overlapped" in flight.record:
                    # a second batch that met an idle device hid nothing
                    # of its host half; a run of them: none for a while
                    self._alone_run = (
                        0 if flight.record["overlapped"] else self._alone_run + 1
                    )
                    if self._alone_run >= _ALONE_RUN:
                        self._alone_run = 0
                        self._shut_until = time.monotonic() + _REST_S
                        rests = True
        self._go.set()  # a worker that waits to form may now (_may_form)
        if rests:
            self.stats.record_rest("secondBatch")
        for record in ready:
            self.stats.record_batch(**record)
