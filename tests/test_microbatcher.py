"""Micro-batching serving runtime (predictionio_tpu.serving).

Covers the ISSUE-1 acceptance surface: concurrent clients get correct,
request-matched responses through the batcher (including a poisoned
query that fails alone), a lone request is served within about
``max_batch_delay_ms``, the bounded queue's reject policy produces 429 +
``Retry-After`` (and the block policy 503), bucket padding keeps
dispatch shapes inside the warmed set, and the stats endpoint exposes
the latency decomposition.
"""

from __future__ import annotations

import json
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest

from predictionio_tpu.api.stats import BATCH_PHASES, ServingStats
from predictionio_tpu.api.http import start_background
from predictionio_tpu.controller import local_context
from predictionio_tpu.serving import AdmissionPolicy, BatcherConfig, MicroBatcher
from predictionio_tpu.workflow import load_engine_variant, run_train
from predictionio_tpu.utils import spans
from predictionio_tpu.utils.spans import span
from predictionio_tpu.workflow.serving import QueryService

VARIANT = {
    "id": "batched-engine",
    "version": "0.1",
    "engineFactory": "fake_dase:engine0",
    "datasource": {"params": {"base": 10}},
    "algorithms": [
        {"name": "a0", "params": {"mult": 2}},
        {"name": "a1", "params": {"mult": 3}},
    ],
}
# fake_dase engine0: models 22 and 33, ServingSum -> query q answers 2q+55


@pytest.fixture()
def trained(memory_storage_env):
    variant = load_engine_variant(VARIANT)
    run_train(variant, local_context())
    return variant


def _echo_batch(bodies):
    """Stand-in handler: status 200, payload echoes the body."""
    return [(200, {"echo": b}) for b in bodies]


_INNER_PHASES = ("bind", "lookup", "queryVectors", "filterLookup", "filterBuild",
                 "dispatch", "deviceWait", "format")


def _phased_batch(bodies):
    """Stand-in handler cut like a device-backed ``handle_batch`` of an
    engine that filters: the eight inner phases, each a few hundred
    microseconds."""
    for name in _INNER_PHASES:
        with span(name):
            time.sleep(0.0003)
    return _echo_batch(bodies)


class _KeptStats(ServingStats):
    """Keeps the keywords of every ``record_batch`` in ``records``."""

    def __init__(self, records: list):
        super().__init__()
        self._records = records

    def record_batch(self, **record):
        self._records.append(record)
        super().record_batch(**record)


def _wait_until(condition, timeout=10.0):
    give_up = time.monotonic() + timeout
    while not condition():
        assert time.monotonic() < give_up, "the condition never held"
        time.sleep(0.002)


class _Device:
    """Stand-in handler with a device. A call spends ``host_s`` in
    ``bind``, passes ``dispatch``, then sits in ``deviceWait`` until its
    gate opens: the first ``gated`` calls have one, the rest wait
    ``device_s``."""

    def __init__(self, gated=0, host_s=0.0, device_s=0.0):
        self.gates = [threading.Event() for _ in range(gated)]
        self.host_s, self.device_s = host_s, device_s
        self.entered = []  # (bodies, batch seq, worker) a call, in order
        self.dispatched = 0
        self._lock = threading.Lock()

    def __call__(self, bodies):
        with self._lock:
            n = len(self.entered)
            self.entered.append(
                (list(bodies), spans.current().seq, threading.current_thread()))
        with span("bind"):
            time.sleep(self.host_s)
        with span("dispatch"):
            pass
        with self._lock:
            self.dispatched += 1
        with span("deviceWait"):
            if n < len(self.gates):
                self.gates[n].wait(timeout=10)
            else:
                time.sleep(self.device_s)
        return _echo_batch(bodies)

    def open(self):
        for gate in self.gates:
            gate.set()


def _riders(b, bodies, results=None):
    """One started thread a body, each started once the one before it is
    queued: the queue holds them in the order of ``bodies``."""
    threads = []
    for q in bodies:
        before = b.stats.submitted
        t = threading.Thread(
            target=lambda q=q: (results if results is not None else {}).update(
                {q: b.submit(q)}),
            daemon=True)
        t.start()
        threads.append(t)
        _wait_until(lambda: b.stats.submitted == before + 1)
    return threads


def _join(threads):
    for t in threads:
        t.join(timeout=15)
    assert not any(t.is_alive() for t in threads)


class _Lethal:
    """A handler that kills worker ``victim`` of ``batcher`` (SystemExit
    escapes ``_dispatch``'s ``except Exception``), after ``release`` if
    one is given; on the other worker it holds its batch until ``held`` is
    set, so that the next batch must go to the victim."""

    def __init__(self, victim, release=None):
        self.victim, self.release = victim, release
        self.held = threading.Event()
        self.batcher = None

    def __call__(self, bodies):
        if threading.current_thread() is self.batcher._threads[self.victim]:
            if self.release is not None:
                self.release.wait(timeout=10)
            raise SystemExit
        self.held.wait(timeout=10)
        return _echo_batch(bodies)


class TestConfig:
    def test_default_buckets_are_powers_of_two(self):
        assert BatcherConfig(max_batch_size=32).bucket_sizes() == (
            1, 2, 4, 8, 16, 32,
        )
        # non-power-of-two max is always its own (largest) bucket
        assert BatcherConfig(max_batch_size=48).bucket_sizes() == (
            1, 2, 4, 8, 16, 32, 48,
        )

    def test_explicit_buckets_sorted_and_capped(self):
        cfg = BatcherConfig(max_batch_size=16, buckets=(8, 4))
        # largest bucket must fit a full batch
        assert cfg.bucket_sizes() == (4, 8, 16)
        # oversized buckets would only inflate padding: dropped
        assert BatcherConfig(max_batch_size=32, buckets=(4, 64)).bucket_sizes() == (
            4, 32,
        )
        assert BatcherConfig(max_batch_size=8, buckets=(64,)).bucket_sizes() == (8,)

    def test_validation(self):
        with pytest.raises(ValueError):
            BatcherConfig(max_batch_size=0)
        with pytest.raises(ValueError):
            BatcherConfig(max_batch_delay_ms=-1)
        with pytest.raises(ValueError):
            BatcherConfig(admission="teapot")
        # CLI strings coerce to the enum
        assert BatcherConfig(admission="block").admission is AdmissionPolicy.BLOCK


class TestBatcherCore:
    def test_lone_request_served_within_delay(self):
        delay_ms = 50.0
        b = MicroBatcher(
            _echo_batch,
            BatcherConfig(max_batch_size=8, max_batch_delay_ms=delay_ms),
        )
        try:
            t0 = time.monotonic()
            status, payload = b.submit({"q": 1})
            elapsed = time.monotonic() - t0
            assert status == 200 and payload == {"echo": {"q": 1}}
            # must wait out the batch window but not much more (generous
            # upper bound for slow CI hosts)
            assert elapsed < 1.0
        finally:
            b.close()

    def test_zero_delay_dispatches_immediately(self):
        b = MicroBatcher(
            _echo_batch, BatcherConfig(max_batch_size=8, max_batch_delay_ms=0.0)
        )
        try:
            t0 = time.monotonic()
            status, _ = b.submit({"q": 2})
            assert status == 200
            assert time.monotonic() - t0 < 0.5
        finally:
            b.close()

    def test_batches_are_padded_to_buckets(self):
        sizes = []
        gate = threading.Event()

        def handler(bodies):
            sizes.append(len(bodies))
            if len(sizes) <= 2:  # hold a batch a worker so the rest queue up
                gate.wait(timeout=5)
            return _echo_batch(bodies)

        b = MicroBatcher(
            handler, BatcherConfig(max_batch_size=8, max_batch_delay_ms=5.0)
        )
        try:
            # two sacrificial requests occupy the two workers...
            warm = []
            for n in (1, 2):
                warm += _riders(b, [f"warm{n}"])
                _wait_until(lambda: len(sizes) == n)
            # ...so these three all sit in the queue together
            threads = _riders(b, range(3))
            assert b._queue.qsize() == 3
            gate.set()
            _join(warm + threads)
            # two batches of 1 (bucket 1), then the 3 queued padded to bucket 4
            assert sizes == [1, 1, 4]
            s = b.stats.to_json()
            assert s["batchedQueries"] == 5
            assert s["bucketHist"] == {"1": 2, "4": 1}
            assert s["paddingOverhead"] > 0
        finally:
            gate.set()
            b.close()

    def test_warmup_precompiles_every_bucket(self):
        seen = []

        def handler(bodies):
            seen.append(len(bodies))
            return _echo_batch(bodies)

        b = MicroBatcher(
            handler,
            BatcherConfig(
                max_batch_size=4, max_batch_delay_ms=0.0, warmup_body={"w": 1}
            ),
        )
        try:
            assert sorted(seen) == [1, 2, 4]  # every bucket, once
            assert sorted(b.stats.warmed_buckets) == [1, 2, 4]
            b.submit({"q": 1})
            # live traffic landed in an already-warm bucket: no miss
            assert b.stats.to_json()["bucketMisses"] == 0
        finally:
            b.close()

    def test_reject_policy_returns_429(self):
        release = threading.Event()

        def slow(bodies):
            release.wait(timeout=10)
            return _echo_batch(bodies)

        b = MicroBatcher(
            slow,
            BatcherConfig(
                max_batch_size=1, max_batch_delay_ms=0.0, max_queue=1,
                admission="reject",
            ),
        )
        try:
            results: list[tuple[int, dict]] = []
            threads = [
                threading.Thread(
                    target=lambda: results.append(b.submit({"q": 0}))
                )
                for _ in range(6)
            ]
            for t in threads:
                t.start()
            # wait until overload is observable, then release the handler
            for _ in range(400):
                if b.stats.rejected:
                    break
                time.sleep(0.005)
            release.set()
            for t in threads:
                t.join(timeout=10)
            statuses = sorted(s for s, _ in results)
            assert 429 in statuses, statuses
            assert statuses.count(200) >= 1
            rejected = next(p for s, p in results if s == 429)
            assert rejected["retryAfterSeconds"] >= 1
            assert b.stats.to_json()["rejected"] >= 1
        finally:
            b.close()

    def test_block_policy_times_out_with_503(self):
        release = threading.Event()

        def slow(bodies):
            release.wait(timeout=10)
            return _echo_batch(bodies)

        b = MicroBatcher(
            slow,
            BatcherConfig(
                max_batch_size=1, max_batch_delay_ms=0.0, max_queue=1,
                admission="block", block_timeout_ms=50.0,
            ),
        )
        try:
            results: list[tuple[int, dict]] = []
            threads = [
                threading.Thread(
                    target=lambda: results.append(b.submit({"q": 0}))
                )
                for _ in range(6)
            ]
            for t in threads:
                t.start()
            for _ in range(400):
                if b.stats.block_timeouts:
                    break
                time.sleep(0.005)
            release.set()
            for t in threads:
                t.join(timeout=10)
            assert any(s == 503 for s, _ in results)
            assert b.stats.to_json()["blockTimeouts"] >= 1
        finally:
            b.close()

    def test_handler_crash_answers_everyone(self):
        def broken(bodies):
            raise RuntimeError("kaboom")

        b = MicroBatcher(
            broken, BatcherConfig(max_batch_size=4, max_batch_delay_ms=0.0)
        )
        try:
            status, payload = b.submit({"q": 1})
            # everyone answered, but exception text stays out of responses
            assert status == 500 and "kaboom" not in payload["message"]
            assert "Batch dispatch failed" in payload["message"]
        finally:
            b.close()

    def test_close_answers_queued_requests(self):
        release = threading.Event()

        def slow(bodies):
            release.wait(timeout=10)
            return _echo_batch(bodies)

        b = MicroBatcher(
            slow,
            BatcherConfig(max_batch_size=1, max_batch_delay_ms=0.0, max_queue=4),
        )
        results: list[tuple[int, dict]] = []
        threads = [
            threading.Thread(target=lambda: results.append(b.submit({"q": 0})))
            for _ in range(3)
        ]
        for t in threads:
            t.start()
        time.sleep(0.05)
        b._closed = True  # stop the loop at the next wake
        release.set()
        b.close()
        for t in threads:
            t.join(timeout=10)
        assert len(results) == 3
        assert all(s in (200, 503) for s, _ in results)

    def test_graceful_close_drains_in_flight_requests(self):
        """ISSUE-2 satellite: close() during in-flight traffic — every
        request either completes normally or gets a clean 503; none hang,
        none are silently lost."""
        def slow(bodies):
            time.sleep(0.05)
            return _echo_batch(bodies)

        b = MicroBatcher(
            slow,
            BatcherConfig(max_batch_size=2, max_batch_delay_ms=0.0, max_queue=64),
        )
        results: list[tuple[int, dict]] = []
        lock = threading.Lock()

        def client(i):
            r = b.submit({"q": i})
            with lock:
                results.append(r)

        threads = [
            threading.Thread(target=client, args=(i,)) for i in range(12)
        ]
        for t in threads:
            t.start()
        time.sleep(0.08)  # some batches dispatched, some queued
        t0 = time.monotonic()
        b.close()
        for t in threads:
            t.join(timeout=15)
        assert time.monotonic() - t0 < 15  # drained, not timed out
        assert not any(t.is_alive() for t in threads)  # nobody hangs
        assert len(results) == 12  # every request got AN answer
        statuses = [s for s, _ in results]
        assert all(s in (200, 503) for s in statuses)
        assert statuses.count(200) >= 1  # in-flight work completed

    @pytest.mark.parametrize("victim", [0, 1])
    @pytest.mark.filterwarnings(
        "ignore::pytest.PytestUnhandledThreadExceptionWarning"
    )
    def test_dead_dispatcher_fails_fast_at_submit(self, victim):
        """ISSUE-2 satellite: a request must not wait out the full result
        timeout when a worker thread has died — submit detects it and
        answers 503 immediately. Either worker's death turns the batcher
        unready."""
        lethal = _Lethal(victim)
        b = lethal.batcher = MicroBatcher(
            lethal, BatcherConfig(max_batch_size=1, max_batch_delay_ms=0.0)
        )
        results = {}
        # the first request may land on the other worker, which then
        # holds it: the second can only go to the victim
        threads = _riders(b, [0], results)
        _wait_until(lambda: b.stats.inflight_batch == 1)
        b._threads[victim].join(timeout=0.3)
        if b._threads[victim].is_alive():
            threads += _riders(b, [1], results)
            b._threads[victim].join(timeout=5)
        assert not b._threads[victim].is_alive()
        assert b._threads[1 - victim].is_alive()
        assert b.dispatcher_alive() is False
        t0 = time.monotonic()
        status, payload = b.submit(2)
        assert time.monotonic() - t0 < 5.0  # fast, not _RESULT_TIMEOUT_S
        assert status == 503
        assert "dispatcher" in payload["message"]
        assert "retryAfterSeconds" in payload
        lethal.held.set()
        _join(threads)
        # the victim's rider was told so; the other worker's got its answer
        assert sorted(s for s, _ in results.values()) in ([503], [200, 503])

    @pytest.mark.parametrize("victim", [0, 1])
    @pytest.mark.filterwarnings(
        "ignore::pytest.PytestUnhandledThreadExceptionWarning"
    )
    def test_dispatcher_death_releases_queued_requests(self, victim):
        """A request queued when a worker dies, and the one that rode with
        it, are answered within seconds, not after the 300 s result
        timeout; the one riding with the other worker gets its answer."""
        release = threading.Event()
        lethal = _Lethal(victim, release)
        b = lethal.batcher = MicroBatcher(
            lethal,
            BatcherConfig(max_batch_size=1, max_batch_delay_ms=0.0, max_queue=8),
        )
        results = {}
        threads = _riders(b, [0, 1, 2], results)  # one a worker, one queued
        _wait_until(lambda: b.stats.inflight_batch == 2)
        assert b._queue.qsize() == 1
        release.set()  # the victim dies with the queue non-empty
        _wait_until(lambda: len(results) == 2)
        lethal.held.set()
        _join(threads)
        assert sorted(s for s, _ in results.values()) == [200, 503, 503]
        survivor = next(q for q, (s, _) in results.items() if s == 200)
        assert results[survivor] == (200, {"echo": survivor}) and survivor in (0, 1)


    @pytest.mark.filterwarnings(
        "ignore::pytest.PytestUnhandledThreadExceptionWarning"
    )
    def test_the_result_timeout_binds_a_rider_of_the_worker_that_lives(
        self, monkeypatch
    ):
        """One worker dead, the other's handler hung: its rider is told
        after the result timeout, not never."""
        from predictionio_tpu.serving import batcher as module

        monkeypatch.setattr(module, "_RESULT_TIMEOUT_S", 1.5)
        release = threading.Event()
        lethal = _Lethal(0, release)
        b = lethal.batcher = MicroBatcher(
            lethal, BatcherConfig(max_batch_size=1, max_batch_delay_ms=0.0)
        )
        results = {}
        try:
            threads = _riders(b, [0, 1], results)
            _wait_until(lambda: b.stats.inflight_batch == 2)  # one a worker
            release.set()
            b._threads[0].join(timeout=5)
            assert not b._threads[0].is_alive() and b._threads[1].is_alive()
            _join(threads)
        finally:
            lethal.held.set()
            b.close()
        assert sorted(s for s, _ in results.values()) == [500, 503]
        held = next(p for s, p in results.values() if s == 500)
        assert "did not respond" in held["message"]


def _overlap_scenario():
    """Three batches of one: the second dispatched while the first is on
    the device, the third after 150 ms of an empty queue. Returns the
    batcher's stats and the host gaps in the order of the batches."""
    device = _Device(gated=1, host_s=0.002)
    b = MicroBatcher(device, BatcherConfig(max_batch_size=1, max_batch_delay_ms=0.0))
    try:
        threads = _riders(b, ["first", "second"])
        _wait_until(lambda: device.dispatched == 2)
        device.open()  # the first leaves the device after the second's dispatch
        _join(threads)
        _wait_until(lambda: b.stats.batches == 2)
        time.sleep(0.15)  # the queue is empty: a worker sits in take
        assert b.submit("third")[0] == 200
        return b.stats.to_json(), list(b.stats._host_gap_ms)
    finally:
        device.open()
        b.close()


class TestTwoBatchesInFlight:
    """ISSUE 31: two workers over one queue. Forming is serial, handling
    overlaps; what the stats say of it."""

    CFG = dict(max_batch_size=1, max_batch_delay_ms=0.0)

    def test_the_second_batch_is_handled_while_the_first_waits_a_third_is_not(self):
        device = _Device(gated=3)
        b = MicroBatcher(device, BatcherConfig(**self.CFG))
        try:
            threads = _riders(b, [0])
            _wait_until(lambda: len(device.entered) == 1)
            threads += _riders(b, [1])
            _wait_until(lambda: len(device.entered) == 2)
            assert threads[0].is_alive()  # entered before the first returned
            threads += _riders(b, [2])
            time.sleep(0.2)
            assert len(device.entered) == 2 and b._queue.qsize() == 1
            device.gates[0].set()  # a worker comes free: now the third
            _wait_until(lambda: len(device.entered) == 3)
            assert len({worker for _, _, worker in device.entered}) == 2
        finally:
            device.open()
            b.close()
        _join(threads)

    def test_a_backlog_of_64_leaves_as_two_batches_of_32_in_arrival_order(self):
        device = _Device(gated=2)
        b = MicroBatcher(device, BatcherConfig(max_batch_size=32, max_batch_delay_ms=0.0))
        try:
            threads = _riders(b, ["a"])
            _wait_until(lambda: len(device.entered) == 1)  # one worker held
            threads += _riders(b, range(64))
            _wait_until(lambda: len(device.entered) == 2)  # and the other
            assert threads[0].is_alive() and b._queue.qsize() == 32
            device.open()
            _join(threads)
        finally:
            device.open()
            b.close()
        batches = [bodies for bodies, _, _ in device.entered[1:]]
        # not four of 16, no request in two batches, none left out
        assert batches == [list(range(32)), list(range(32, 64))]
        assert b.stats.to_json()["batchSizeHist"] == {"1": 1, "32": 2}

    def test_a_second_batch_goes_only_when_a_full_one_is_queued(self):
        """With a batch in flight, three of four queued wait; the fourth
        makes a full batch, and that goes at once, beside the first."""
        device = _Device(gated=2)
        b = MicroBatcher(device, BatcherConfig(max_batch_size=4, max_batch_delay_ms=0.0))
        try:
            threads = _riders(b, ["a"])
            _wait_until(lambda: len(device.entered) == 1)
            threads += _riders(b, [0, 1, 2])
            time.sleep(0.2)
            assert len(device.entered) == 1 and b._queue.qsize() == 3
            assert b.stats.to_json()["inflightBatch"] == 1
            threads += _riders(b, [3])
            _wait_until(lambda: len(device.entered) == 2)
            assert threads[0].is_alive()  # the first has not returned
            assert device.entered[1][0] == [0, 1, 2, 3]
            assert b.stats.to_json()["inflightBatch"] == 2
        finally:
            device.open()
            b.close()
        _join(threads)

    def test_short_of_a_full_batch_the_queue_waits_for_the_batch_in_flight(self):
        """As behind a single dispatcher: what is queued goes when the
        batch in flight returns, by the first batch's rule (part full)."""
        device = _Device(gated=1)
        b = MicroBatcher(device, BatcherConfig(max_batch_size=4, max_batch_delay_ms=0.0))
        try:
            threads = _riders(b, ["a"])
            _wait_until(lambda: len(device.entered) == 1)
            threads += _riders(b, [0, 1, 2])
            time.sleep(0.1)
            assert len(device.entered) == 1
            device.open()
            _join(threads)
        finally:
            device.open()
            b.close()
        assert [bodies[:3] for bodies, _, _ in device.entered[1:]] == [[0, 1, 2]]
        assert b.stats.to_json()["batchSizeHist"] == {"1": 1, "3": 1}

    def test_second_batches_that_meet_an_idle_device_stop_for_a_while(
        self, monkeypatch
    ):
        """A handler whose device is done at once and whose format is long
        (a cycle that is not the device's): three second batches in a row
        dispatched to an idle device, and the next request waits for the
        batch in flight; after the rest a second batch goes again. One that
        is enqueued behind a running program ends the run."""
        from predictionio_tpu.serving import batcher as module

        monkeypatch.setattr(module, "_ALONE_RUN", 3)
        monkeypatch.setattr(module, "_REST_S", 1.5)
        gates = [threading.Event() for _ in range(16)]
        entered = []

        def handler(bodies):
            n = len(entered)
            entered.append(list(bodies))
            with span("dispatch"):
                pass
            with span("deviceWait"):
                pass
            with span("format"):
                gates[n].wait(timeout=10)
            return _echo_batch(bodies)

        b = MicroBatcher(handler, BatcherConfig(**self.CFG))
        try:
            threads = _riders(b, [0])
            for n in range(1, 4):  # each beside the one before, device idle
                threads += _riders(b, [n])
                _wait_until(lambda: len(entered) == n + 1)
                gates[n - 1].set()
            gates[3].set()
            _join(threads)
            _wait_until(lambda: b.stats.batches == 4)
            assert b._shut_until > time.monotonic()
            assert b.stats.to_json()["rest"] == {"secondBatch": 1, "claims": 0}
            threads = _riders(b, [4, 5])
            time.sleep(0.15)
            assert len(entered) == 5 and b._queue.qsize() == 1  # one at a time
            _wait_until(lambda: len(entered) == 6)  # the rest is over
            assert threads[0].is_alive()
            for gate in gates:
                gate.set()
            _join(threads)
            assert b.stats.to_json()["overlap"]["overlapped"] == 0
            # counted once a rest, not once a batch that found it on
            assert b.stats.to_json()["rest"]["secondBatch"] == 1
        finally:
            for gate in gates:
                gate.set()
            b.close()

    def test_a_second_batch_behind_a_running_program_ends_the_run(self, monkeypatch):
        from predictionio_tpu.serving import batcher as module

        monkeypatch.setattr(module, "_ALONE_RUN", 2)
        device = _Device(gated=8)
        b = MicroBatcher(device, BatcherConfig(**self.CFG))
        try:
            threads = _riders(b, [0])
            for n in range(1, 6):  # each dispatched while the one before waits
                threads += _riders(b, [n])
                _wait_until(lambda: device.dispatched == n + 1)
                device.gates[n - 1].set()
            device.open()
            _join(threads)
            _wait_until(lambda: b.stats.batches == 6)
            assert b.stats.to_json()["overlap"]["overlapped"] == 5
            assert b._alone_run == 0 and b._shut_until == 0.0
            assert b.stats.to_json()["rest"] == {"secondBatch": 0, "claims": 0}
        finally:
            device.open()
            b.close()

    def test_a_batch_that_never_returns_does_not_park_the_later_ones(self):
        """A handler that hangs: the other worker's batches are accounted
        (the stats move, nothing piles up); the one that hung, when it does
        come back, with no host gap."""
        device = _Device(gated=1, device_s=0.001)
        b = MicroBatcher(device, BatcherConfig(**self.CFG))
        try:
            threads = _riders(b, ["hangs"])
            _wait_until(lambda: len(device.entered) == 1)
            for q in range(6):
                assert b.submit(q)[0] == 200
            _wait_until(lambda: b.stats.batches >= 4)
            assert len(b._returned) <= 2
            gaps = len(b.stats._host_gap_ms)
            device.open()
            _join(threads)
            _wait_until(lambda: b.stats.batches == 7)
            assert not b._returned and len(b.stats._host_gap_ms) == gaps
            assert b.stats.to_json()["inflightBatch"] == 0
        finally:
            device.open()
            b.close()

    def test_host_gap_is_0_overlapped_positive_after_idle_absent_for_the_first(self):
        stats, gaps = _overlap_scenario()
        # the first batch has none; the second was enqueued while the first
        # ran; before the third the device sat idle 150 ms, take left out:
        # what remains is the host's own 2 ms of bind and its bookkeeping
        assert len(gaps) == 2
        assert gaps[0] == 0.0
        assert 2.0 <= gaps[1] < 100.0
        assert stats["latencyMs"]["take"]["p99"] >= 140.0

    def test_overlap_counts_every_batch_and_the_share_follows(self):
        assert ServingStats().to_json()["overlapPct"] == 0.0
        stats, _ = _overlap_scenario()
        assert stats["overlap"] == {"overlapped": 1, "alone": 2}
        assert sum(stats["overlap"].values()) == stats["batches"] == 3
        assert stats["overlapPct"] == 33.33

    def test_inflight_batch_reaches_2_and_ends_at_0(self):
        device = _Device(gated=2)
        b = MicroBatcher(device, BatcherConfig(**self.CFG))
        try:
            assert b.stats.to_json()["inflightBatch"] == 0
            threads = _riders(b, [0, 1])
            _wait_until(lambda: len(device.entered) == 2)
            assert b.stats.to_json()["inflightBatch"] == 2
            device.gates[1].set()  # the later batch returns first
            _wait_until(lambda: not threads[1].is_alive())
            device.open()
            _join(threads)
            _wait_until(lambda: b.stats.batches == 2)
            assert b.stats.to_json()["inflightBatch"] == 0
        finally:
            device.open()
            b.close()

    def test_close_answers_both_in_flight_503s_the_queue_joins_both_workers(self):
        device = _Device(gated=2)
        b = MicroBatcher(device, BatcherConfig(max_queue=8, **self.CFG))
        results = {}
        threads = _riders(b, range(5), results)
        _wait_until(lambda: len(device.entered) == 2)
        closer = threading.Thread(target=b.close, daemon=True)
        closer.start()
        _wait_until(lambda: b._closed)
        device.open()
        _join([closer, *threads])
        assert not any(t.is_alive() for t in b._threads)
        assert b.dispatcher_alive() is False
        assert results[0] == (200, {"echo": 0}) and results[1] == (200, {"echo": 1})
        assert [results[q][0] for q in (2, 3, 4)] == [503, 503, 503]
        assert len(device.entered) == 2  # nothing was formed after the close

    def test_sequence_numbers_are_unique_a_batch_across_workers(self):
        device = _Device(device_s=0.003)
        b = MicroBatcher(device, BatcherConfig(max_batch_size=2, max_batch_delay_ms=0.0))
        rider_seqs = {}

        def rider(q):
            collector = spans.Collector()  # what an HTTP thread binds
            spans.bind(collector)
            b.submit(q)
            rider_seqs[q] = collector.seq

        threads = [threading.Thread(target=rider, args=(q,), daemon=True)
                   for q in range(40)]
        try:
            for t in threads:
                t.start()
            _join(threads)
        finally:
            b.close()
        seqs = [seq for _, seq, _ in device.entered]
        assert len(set(seqs)) == len(seqs) and min(seqs) >= 1
        assert len({worker for _, _, worker in device.entered}) == 2
        # a rider carries the number of the batch it rode in, and no other
        for bodies, seq, _ in device.entered:
            assert all(rider_seqs[q] == seq for q in bodies)

    def test_stress_every_request_is_answered_once_and_every_batch_counted(self):
        """More clients than cores at a short switch interval: a request in
        two batches, a lost count or a batch never accounted would show."""
        device = _Device(device_s=0.0005)
        b = MicroBatcher(device, BatcherConfig(max_batch_size=4, max_batch_delay_ms=0.2))
        answers = {}

        def client(c):
            for r in range(40):
                answers[(c, r)] = b.submit((c, r))

        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            threads = [threading.Thread(target=client, args=(c,), daemon=True)
                       for c in range(24)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            assert not any(t.is_alive() for t in threads)
            _wait_until(lambda: b.stats.batches == len(device.entered))
        finally:
            sys.setswitchinterval(interval)
            b.close()
        assert all(answers[k] == (200, {"echo": k}) for k in answers)
        assert len(answers) == 24 * 40
        ridden = [tuple(q) for bodies, _, _ in device.entered for q in bodies]
        # filler slots copy the first body: count each batch's real riders
        stats = b.stats.to_json()
        assert stats["batchedQueries"] == stats["completed"] == 24 * 40
        assert set(ridden) == set(answers)
        assert sum(stats["overlap"].values()) == stats["batches"]
        assert stats["overlap"]["overlapped"] > 0
        assert stats["inflightBatch"] == 0 and not b._returned


class TestDispatcherSpans:
    """The dispatcher's phases as spans: windows of ``latencyMs``, the
    host gap, the batch sequence number, and ``pio.*`` events in a
    profiler trace (ISSUE 25)."""

    def test_every_phase_is_a_window_and_the_inner_ones_fit_in_handle(self):
        b = MicroBatcher(_phased_batch, BatcherConfig(max_batch_delay_ms=0.0))
        try:
            assert b.submit("q")[0] == 200  # exactly one batch
            ms = b.stats.to_json()["latencyMs"]
        finally:
            b.close()
        assert set(ms) == set(BATCH_PHASES) | {
            "queueWait", "handle", "total", "wake", "giveWay", "hostGap",
            "hostCpu", "hostWait"}
        inner = _INNER_PHASES
        for name in ("take", "drain", "batchForm", "wake", *inner):
            assert ms[name]["p50"] is not None, name
        assert all(ms[name]["p50"] >= 0.3 for name in inner)
        # one batch in the window, so each p50 is that batch's own value
        assert sum(ms[n]["p50"] for n in inner) <= ms["handle"]["p50"] + 0.005
        # release is the PREVIOUS batch's, recorded with the next cycle
        assert ms["release"]["p50"] is None

    def test_host_cpu_and_wait_add_up_to_the_host_half_of_the_same_batch(
            self, monkeypatch):
        """Per batch ``hostCpu + hostWait`` is the wall of the cycle's
        phases that do not wait by design; a phase that sleeps is wait, a
        phase that spins is CPU, and ``cpuMs`` says which was which."""
        from predictionio_tpu.api.stats import HOST_HALF_PHASES
        from predictionio_tpu.serving import batcher

        monkeypatch.setattr(batcher, "_CPU_EVERY", 1)  # every cycle takes it

        def handler(bodies):
            with span("bind"):  # on the CPU: the thread's clock moves
                t0 = time.thread_time_ns()
                while time.thread_time_ns() - t0 < 5_000_000:
                    pass
            with span("filterLookup"):  # off it: a store read in the kernel
                time.sleep(0.03)
            with span("dispatch"):
                pass
            with span("deviceWait"):  # waits by design: not the host half
                time.sleep(0.02)
            return _echo_batch(bodies)

        records = []
        b = MicroBatcher(handler, BatcherConfig(max_batch_delay_ms=0.0),
                         stats=_KeptStats(records))
        try:
            for q in range(3):
                assert b.submit(q)[0] == 200
            _wait_until(lambda: len(records) == 3)
            out = b.stats.to_json()
        finally:
            b.close()
        assert "deviceWait" not in HOST_HALF_PHASES
        assert {"take", "drain"}.isdisjoint(HOST_HALF_PHASES)
        cpus, waits = b.stats._host_cpu_ms, b.stats._host_wait_ms
        assert len(cpus) == len(waits) == 3
        for record, cpu, wait in zip(records, cpus, waits):
            half = sum(record["phases"].get(n, 0.0) for n in HOST_HALF_PHASES)
            assert cpu + wait == pytest.approx(half, abs=1e-9)
            assert set(record["phases_cpu"]) == set(record["phases"])
            # each phase's CPU lies inside its wall (two clocks: a hair)
            for name, ms in record["phases_cpu"].items():
                assert 0.0 <= ms <= record["phases"][name] + 0.5, name
            assert record["phases_cpu"]["bind"] >= 5.0
            assert cpu >= 5.0 and wait >= 25.0  # the sleep is all wait
            # the wait is the store read's, and deviceWait's is nobody's
            assert record["phases_cpu"]["filterLookup"] < 0.1 * record["phases"]["filterLookup"]
            assert wait < record["phases"]["filterLookup"] + record["phases"]["bind"]
        assert out["latencyMs"]["hostCpu"]["p50"] >= 5.0
        assert out["latencyMs"]["hostWait"]["p50"] >= 25.0
        # the mean beside the percentiles: what to read where the CPU
        # clock ticks coarsely
        assert out["latencyMs"]["hostCpu"]["mean"] == pytest.approx(
            sum(cpus) / 3, abs=1e-3)
        assert out["latencyMs"]["hostWait"]["mean"] >= 25.0
        assert set(out["cpuMs"]) == set(BATCH_PHASES)
        assert out["cpuMs"]["bind"]["p50"] >= 5.0 <= out["cpuMs"]["bind"]["mean"]
        assert out["cpuMs"]["filterLookup"]["p50"] < 3.0
        # the workers' whole CPU time since boot holds the spinning at least
        assert b.stats.cpu_ns_workers >= 3 * 5_000_000

    def test_one_cycle_in_many_takes_cpu_time(self):
        """The CPU clock is a system call: a worker reads it around its
        spans on its first cycle and then on one of its cycles in
        ``_CPU_EVERY``, and adds its thread's whole CPU time since the last
        such cycle with it."""
        from predictionio_tpu.serving import batcher

        records = []
        b = MicroBatcher(_phased_batch, BatcherConfig(max_batch_delay_ms=0.0),
                         stats=_KeptStats(records))
        n = 2 * batcher._CPU_EVERY + 4
        try:
            for q in range(n):
                assert b.submit(q)[0] == 200
            _wait_until(lambda: len(records) == n)
        finally:
            b.close()
        took = [r["phases_cpu"] is not None for r in records]
        # each worker's first cycle, then the cycle after each of its
        # batches whose count divides by _CPU_EVERY (two workers share the n)
        assert 2 <= sum(took) <= 4, took
        assert len(b.stats._host_cpu_ms) == len(b.stats._host_wait_ms) == sum(took)
        for r in records:
            if r["phases_cpu"] is not None:  # a cycle takes it whole, or not at all
                assert set(r["phases_cpu"]) == set(r["phases"])
        assert b.stats.cpu_ns_workers > 0

    def test_give_way_is_the_part_of_wake_spent_behind_a_claiming_worker(self):
        b = MicroBatcher(_phased_batch, BatcherConfig(max_batch_delay_ms=0.0))
        try:
            for q in range(4):
                assert b.submit(q)[0] == 200
            ms = b.stats.to_json()["latencyMs"]
        finally:
            b.close()
        # nobody claimed (one of 32 is no loaded batch): nothing to give way to
        assert ms["giveWay"]["p50"] is not None
        assert 0.0 <= ms["giveWay"]["p99"] <= ms["wake"]["p99"]
        assert ms["giveWay"]["p99"] < 5.0

    def test_host_gap_is_absent_for_the_first_batch_and_excludes_take(self):
        b = MicroBatcher(_phased_batch, BatcherConfig(max_batch_delay_ms=0.0))
        try:
            b.submit("first")
            assert b.stats.to_json()["latencyMs"]["hostGap"]["p50"] is None
            time.sleep(0.15)  # the queue is empty: the dispatcher sits in take
            b.submit("second")
            ms = b.stats.to_json()["latencyMs"]
            b.submit("third")  # one of the two workers' second batch
            release = b.stats.to_json()["latencyMs"]["release"]
        finally:
            b.close()
        assert ms["take"]["p95"] >= 140.0
        # previous deviceWait end -> this dispatch end spans the idle
        # 150 ms; less take, what is left is the host's own code: release,
        # drain, batchForm, bind, lookup, dispatch (and format before them)
        assert ms["hostGap"]["p50"] is not None
        assert 0.9 <= ms["hostGap"]["p50"] < 100.0
        # a worker records a release with its next cycle
        assert release["p50"] is not None

    def test_a_handler_without_a_device_records_no_gap(self):
        b = MicroBatcher(_echo_batch, BatcherConfig(max_batch_delay_ms=0.0))
        try:
            for q in range(3):
                b.submit(q)
            ms = b.stats.to_json()["latencyMs"]
        finally:
            b.close()
        assert ms["hostGap"]["p50"] is None and ms["dispatch"]["p50"] is None
        assert ms["handle"]["p50"] is not None

    def test_a_batch_and_its_riders_share_a_sequence_number(self):
        batch_seqs = []

        def handler(bodies):
            batch_seqs.append(spans.current().seq)
            return _echo_batch(bodies)

        b = MicroBatcher(
            handler, BatcherConfig(max_batch_size=4, max_batch_delay_ms=50.0)
        )
        rider_seqs = []

        def rider(q):
            collector = spans.Collector()  # what an HTTP thread binds
            spans.bind(collector)
            b.submit(q)
            rider_seqs.append(collector.seq)

        try:
            threads = [
                threading.Thread(target=rider, args=(q,), daemon=True)
                for q in range(4)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=10)
            assert not any(t.is_alive() for t in threads)
            rider(99)  # a later batch: a later number
        finally:
            b.close()
            spans.bind(None)
        assert len(rider_seqs) == 5 and set(rider_seqs) == set(batch_seqs)
        assert rider_seqs[-1] == max(batch_seqs) > min(batch_seqs) >= 1

    def test_dispatcher_leaves_are_in_the_profiler_trace_flat_on_one_line(
        self, tmp_path
    ):
        """One flat line a worker (ISSUE 31: there are two)."""
        import glob

        import jax
        from jax.profiler import ProfileData

        b = MicroBatcher(_phased_batch, BatcherConfig(max_batch_delay_ms=0.0))

        def http_thread():
            spans.bind(spans.Collector())  # never an annotating one
            for q in range(3):
                with span("httpRead"):
                    pass
                b.submit(q)
                with span("httpWrite"):
                    pass

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            t = threading.Thread(target=http_thread, daemon=True)
            t.start()
            t.join(timeout=20)
            assert not t.is_alive()
        finally:
            jax.profiler.stop_trace()
            b.close()
        (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
        lines = {}  # (plane, its n-th line) -> [(start, end, name)]
        for plane in ProfileData.from_file(path).planes:
            for nth, line in enumerate(plane.lines):
                for ev in line.events:
                    if ev.name.startswith("pio."):
                        lines.setdefault((plane.name, nth), []).append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                        )
        assert 1 <= len(lines) <= 2, sorted(lines)  # the workers' threads
        names = {name for events in lines.values() for _, _, name in events}
        assert names == {"pio." + n for n in BATCH_PHASES}
        # no enclosing span, no HTTP thread's span
        assert not names & {"pio.handle", "pio.httpRead", "pio.httpWrite"}
        for events in lines.values():
            events.sort()
            for (_, end, name), (start, _, after) in zip(events, events[1:]):
                assert end <= start, f"{name} overlaps {after}"  # flat


class TestQueryServiceIntegration:
    CFG = dict(max_batch_size=8, max_batch_delay_ms=5.0)

    def test_concurrent_clients_get_matched_responses(self, trained):
        """N threads over real HTTP: every client gets ITS answer, and one
        poisoned query fails alone while its batchmates succeed."""
        qs = QueryService(trained, batching=BatcherConfig(**self.CFG))
        server, _ = start_background(qs.dispatch)
        port = server.server_address[1]
        n_clients, per_client = 12, 10
        poison = (3, 4)  # (client, request) that sends a non-numeric body
        results: dict[tuple[int, int], tuple[int, object]] = {}
        lock = threading.Lock()

        def client(cid: int):
            for r in range(per_client):
                body = b'"bad"' if (cid, r) == poison else str(
                    cid * 1000 + r
                ).encode()
                req = urllib.request.Request(
                    f"http://127.0.0.1:{port}/queries.json",
                    data=body,
                    headers={"Content-Type": "application/json"},
                    method="POST",
                )
                try:
                    with urllib.request.urlopen(req, timeout=30) as resp:
                        out = (resp.status, json.loads(resp.read()))
                except urllib.error.HTTPError as e:
                    out = (e.code, json.loads(e.read()))
                with lock:
                    results[(cid, r)] = out

        threads = [
            threading.Thread(target=client, args=(c,)) for c in range(n_clients)
        ]
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            assert len(results) == n_clients * per_client
            for (cid, r), (status, payload) in results.items():
                if (cid, r) == poison:
                    # per-item isolation: only the poisoned query fails
                    assert status == 500, (status, payload)
                else:
                    q = cid * 1000 + r
                    assert status == 200 and payload == 2 * q + 55, (
                        (cid, r), status, payload,
                    )
            # cross-request batching actually happened
            s = qs.batcher.stats.to_json()
            assert s["batches"] < s["batchedQueries"]
            assert s["meanBatchSize"] > 1.0
        finally:
            server.shutdown()
            server.server_close()
            qs.close()

    def test_batching_off_by_default(self, trained):
        qs = QueryService(trained)
        assert qs.batcher is None
        assert qs.status_json()["batching"] is False
        # per-request path still serves and /stats.json still answers
        assert qs.dispatch("POST", "/queries.json", {}, 7).status == 200
        r = qs.dispatch("GET", "/stats.json", {})
        assert r.status == 200 and r.body["batching"] is False

    def test_stats_endpoint_exposes_decomposition(self, trained):
        qs = QueryService(
            trained,
            batching=BatcherConfig(max_batch_size=4, max_batch_delay_ms=0.0),
        )
        try:
            assert qs.status_json()["batching"] is True
            for q in range(5):
                status, payload = qs.batcher.submit(q)
                assert status == 200 and payload == 2 * q + 55
            body = qs.dispatch("GET", "/stats.json", {}).body
            assert body["batching"] is True
            b = body["batcher"]
            assert b["submitted"] == b["completed"] == 5
            for phase in ("queueWait", "batchForm", "handle", "total",
                          "wake", "take", "drain", "bind", "format"):
                assert b["latencyMs"][phase]["p50"] is not None
            # fake_dase predicts on the host: no device phases, no gap
            for phase in ("dispatch", "deviceWait", "hostGap"):
                assert b["latencyMs"][phase]["p50"] is None
            assert b["queueDepth"] == 0 and b["inflightBatch"] == 0
            # every deploy counts its compiles; nothing compiled since boot
            assert body["compile"]["sinceBoot"] == 0
            assert body["compile"]["missesSinceBoot"] == 0
        finally:
            qs.close()

    def test_http_threads_record_read_and_write_per_request(
            self, trained, monkeypatch):
        from predictionio_tpu.api import http

        from predictionio_tpu.serving import batcher

        # a group of one: the thread's CPU clock is read once a rider, and
        # a worker's on every cycle
        monkeypatch.setattr(http, "RIDERS_A_CPU_READ", 1)
        monkeypatch.setattr(batcher, "_CPU_EVERY", 1)
        qs = QueryService(
            trained,
            batching=BatcherConfig(max_batch_size=4, max_batch_delay_ms=0.0),
        )
        riders, record_http = [], qs.record_http

        def keep(records, counts=None):
            if counts:
                riders.append(dict(counts))
            record_http(records, counts)

        qs.record_http = keep  # the wrapper finds the hook by name
        server, _ = start_background(qs.dispatch)
        port = server.server_address[1]
        try:
            for q in range(6):
                req = urllib.request.Request(
                    f"http://127.0.0.1:{port}/queries.json",
                    data=json.dumps(q).encode(),
                    headers={"Content-Type": "application/json"},
                    method="POST",
                )
                with urllib.request.urlopen(req, timeout=10) as r:
                    assert json.loads(r.read()) == 2 * q + 55
            # the sixth answer is on the wire before its thread records it
            for _ in range(200):
                http = qs.stats_json()["http"]
                if http["requests"] == 6:
                    break
                time.sleep(0.01)
            assert http["requests"] == 6
            ms = http["latencyMs"]
            assert set(ms) == {"httpRead", "httpWrite", "inServer",
                               "request", "riderCpu", "riderWait"}
            assert all(ms[k]["p50"] is not None for k in ms)
            # riderWait is a remainder: on an idle server it can read a
            # hair under 0 (the CPU a thread spends going to sleep lies
            # inside the time it was meant to wait)
            assert all(ms[k]["p50"] >= 0 for k in ms if k != "riderWait")
            assert ms["riderWait"]["p50"] > -0.5
            assert ms["inServer"]["p99"] >= max(
                ms["httpRead"]["p50"], ms["httpWrite"]["p50"])
            # a rider's request, measured: per request
            # riderCpu + riderWait + (enqueue to released) + giveWay = request
            stats = qs._http_stats
            assert len(riders) == len(stats._request_ms) == 6
            for counts, request, cpu, wait in zip(
                    riders, stats._request_ms, stats._rider_cpu_ms,
                    stats._rider_wait_ms):
                assert counts["rider.requests"] == 1
                assert request == counts["rider.requestNs"] / 1e6
                queued = counts["rider.queuedNs"] / 1e6
                gave_way = counts["rider.giveWayNs"] / 1e6
                assert cpu + wait + queued + gave_way == pytest.approx(
                    request, abs=1e-9)
                # the whole stretch holds its parts, in their order of size
                assert 0 < cpu <= request and 0 < queued < request
                assert 0 <= gave_way < request
                assert wait > -0.5  # two clocks: a hair under 0 at most
            assert stats.cpu_ns_riders == sum(c["rider.cpuNs"] for c in riders)
            assert ms["riderCpu"]["mean"] == pytest.approx(
                stats.cpu_ns_riders / 6e6, abs=1e-3)
            lock = qs.stats_json()["lock"]
            assert lock["cpuNs"]["riders"] == stats.cpu_ns_riders
            assert lock["cpuNs"]["workers"] == qs.batcher.stats.cpu_ns_workers > 0
            # the request's stretch holds the two spans inside it
            assert ms["request"]["p50"] >= ms["inServer"]["p50"]
            assert qs.batcher.stats.to_json()["latencyMs"]["giveWay"]["p50"] is not None
            # a request that rode in no batch records no rider
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/stats.json", timeout=10) as r:
                assert "lock" in json.loads(r.read())
            time.sleep(0.05)
            assert len(stats._request_ms) == 6
        finally:
            server.shutdown()
            server.server_close()
            qs.close()

    def test_riders_are_accounted_a_group_of_one_threads_requests_at_a_time(
            self, trained):
        """One read of an HTTP thread's CPU clock a group of its riders:
        a keep-alive connection's 32nd rider closes a group, the
        connection's end closes what is left, and the identity holds of
        each group's sums. Every rider is in exactly one group."""
        import http.client

        from predictionio_tpu.api.http import RIDERS_A_CPU_READ

        qs = QueryService(
            trained,
            batching=BatcherConfig(max_batch_size=4, max_batch_delay_ms=0.0),
        )
        groups, record_http = [], qs.record_http

        def keep(records, counts=None):
            if counts:
                groups.append(dict(counts))
            record_http(records, counts)

        qs.record_http = keep
        server, _ = start_background(qs.dispatch)
        n = RIDERS_A_CPU_READ + 5
        try:
            conn = http.client.HTTPConnection(
                "127.0.0.1", server.server_address[1], timeout=10)
            for q in range(n):
                conn.request("POST", "/queries.json", body=json.dumps(q),
                             headers={"Content-Type": "application/json"})
                assert json.loads(conn.getresponse().read()) == 2 * q + 55
                if q == RIDERS_A_CPU_READ - 2:
                    # a request that rides in no batch joins no group
                    conn.request("GET", "/stats.json")
                    assert "lock" in json.loads(conn.getresponse().read())
            _wait_until(lambda: len(groups) == 1)
            conn.close()  # the thread's finish() hands over the rest
            _wait_until(lambda: len(groups) == 2)
        finally:
            server.shutdown()
            server.server_close()
            qs.close()
        assert [g["rider.requests"] for g in groups] == [RIDERS_A_CPU_READ, 5]
        stats = qs._http_stats
        for g, request, cpu, wait in zip(
                groups, stats._request_ms, stats._rider_cpu_ms, stats._rider_wait_ms):
            k = g["rider.requests"]
            assert request == pytest.approx(g["rider.requestNs"] / 1e6 / k)
            assert cpu == pytest.approx(g["rider.cpuNs"] / 1e6 / k)
            assert (cpu + wait + (g["rider.queuedNs"] + g["rider.giveWayNs"]) / 1e6 / k
                    == pytest.approx(request, abs=1e-9))
            assert 0 < g["rider.cpuNs"] and 0 < g["rider.queuedNs"] < g["rider.requestNs"]
        assert stats.cpu_ns_riders == sum(g["rider.cpuNs"] for g in groups)
        assert qs.batcher.stats.completed == n

    def test_http_429_carries_retry_after_header(self, trained):
        qs = QueryService(
            trained,
            batching=BatcherConfig(
                max_batch_size=1, max_batch_delay_ms=0.0, max_queue=1
            ),
        )
        release = threading.Event()
        inner = qs.batcher._handle

        def slow(bodies, **kw):
            release.wait(timeout=10)
            return inner(bodies, **kw)

        qs.batcher._handle = slow
        try:
            answers = []
            threads = [
                threading.Thread(
                    target=lambda: answers.append(
                        qs.dispatch("POST", "/queries.json", {}, 1)
                    )
                )
                for _ in range(6)
            ]
            for t in threads:
                t.start()
            for _ in range(400):
                if qs.batcher.stats.rejected:
                    break
                time.sleep(0.005)
            release.set()
            for t in threads:
                t.join(timeout=10)
            rejected = [r for r in answers if r.status == 429]
            assert rejected, [r.status for r in answers]
            assert int(rejected[0].headers["Retry-After"]) >= 1
        finally:
            release.set()
            qs.close()

    def test_padding_and_warmup_have_no_serve_side_effects(self, trained):
        """Filler/warm-up queries compile the bucket shapes but must not
        count as queries or reach plugins (or, in production, feedback)."""
        from predictionio_tpu.workflow.serving import EngineServerPlugin

        seen = []

        class Sniffer(EngineServerPlugin):
            name = "sniffer"

            def process(self, query, prediction, service):
                seen.append(prediction)
                return prediction

        qs = QueryService(
            trained,
            plugins=[Sniffer()],
            batching=BatcherConfig(
                max_batch_size=4, max_batch_delay_ms=0.0, warmup_body=0
            ),
        )
        try:
            # warm-up ran buckets 4+2+1 = 7 filler queries
            assert qs.query_count == 0 and seen == []
            status, payload = qs.batcher.submit(10)
            assert status == 200 and payload == 75
            assert qs.query_count == 1 and seen == [75]
        finally:
            qs.close()

    def test_warmup_body_flows_through_real_engine(self, trained):
        qs = QueryService(
            trained,
            batching=BatcherConfig(
                max_batch_size=4, max_batch_delay_ms=0.0, warmup_body=0
            ),
        )
        try:
            assert sorted(qs.batcher.stats.warmed_buckets) == [1, 2, 4]
            status, payload = qs.batcher.submit(10)
            assert status == 200 and payload == 75
            assert qs.batcher.stats.to_json()["bucketMisses"] == 0
        finally:
            qs.close()


def test_serving_stats_percentiles_empty_and_filled():
    s = ServingStats(window=8)
    empty = s.to_json()
    assert empty["latencyMs"]["total"]["p99"] is None
    for ms in (1.0, 2.0, 3.0, 100.0):
        s.record_request(ms)
    j = s.to_json()
    assert j["completed"] == 4
    assert j["latencyMs"]["total"]["p50"] == 2.0
    assert j["latencyMs"]["total"]["p99"] == 100.0
    assert j["latencyMs"]["wake"]["p50"] is None  # none was passed
    s.record_request(5.0, wake_ms=0.25)
    assert s.to_json()["latencyMs"]["wake"]["p50"] == 0.25


class _HeldHost:
    """Stand-in handler whose host half can be held: a call sits in
    ``bind`` until ``host`` is set (call 0 never does), passes
    ``dispatch``, then sits in ``deviceWait`` until its own gate opens."""

    def __init__(self, calls=2, fail_in_bind=False):
        self.host = threading.Event()
        self.gates = [threading.Event() for _ in range(calls)]
        self.entered = 0
        self.dispatched = 0
        self.fail_in_bind = fail_in_bind
        self._lock = threading.Lock()

    def __call__(self, bodies):
        with self._lock:
            n, self.entered = self.entered, self.entered + 1
        with span("bind"):
            if n:
                self.host.wait(timeout=10)
            if self.fail_in_bind:
                raise RuntimeError("the host half broke")
        with span("dispatch"):
            pass
        with self._lock:
            self.dispatched += 1
        with span("deviceWait"):
            self.gates[n].wait(timeout=10)
        return _echo_batch(bodies)

    def open(self):
        self.host.set()
        for gate in self.gates:
            gate.set()


class TestALoadedBatchsWorkerGoesFirst:
    """ISSUE 36: a worker between a batch at least half full gathered and
    its program enqueued has the right of way over the riders that wake
    meanwhile."""

    @pytest.fixture()
    def patient(self, monkeypatch):
        from predictionio_tpu.serving import batcher

        monkeypatch.setattr(batcher, "_GIVE_WAY_S", 10.0)

    def test_riders_that_wake_wait_until_the_other_batchs_program_is_enqueued(
            self, patient):
        device = _HeldHost()
        # the delay: the first batch waits for its second rider
        b = MicroBatcher(device, BatcherConfig(max_batch_size=2, max_batch_delay_ms=500.0))
        try:
            first = _riders(b, ["a", "b"])
            _wait_until(lambda: device.dispatched == 1)
            assert b._floor.is_set()  # enqueued: the claim is over
            second = _riders(b, ["c", "d"])  # full: goes beside the first
            _wait_until(lambda: device.entered == 2)
            assert not b._floor.is_set()  # held in its host half
            device.gates[0].set()  # the first batch comes back
            _wait_until(lambda: b.stats.batches == 1)  # released, accounted
            time.sleep(0.1)
            assert all(t.is_alive() for t in first)  # answered, and waiting
            device.host.set()
            _join(first)  # the second's program is enqueued: they go on
            assert device.dispatched == 2 and all(t.is_alive() for t in second)
            assert b._floor.is_set()
        finally:
            device.open()
            b.close()
        _join(second)

    def test_a_batch_under_half_full_claims_nothing(self, patient):
        device = _HeldHost()
        b = MicroBatcher(device, BatcherConfig(max_batch_size=4, max_batch_delay_ms=0.0))
        try:
            first = _riders(b, ["a"])
            _wait_until(lambda: device.dispatched == 1)
            device.gates[0].set()
            _join(first)
            second = _riders(b, ["b"])
            _wait_until(lambda: device.entered == 2)  # held in bind, 1 of 4
            assert b._floor.is_set() and b._preparing == 0
        finally:
            device.open()
            b.close()
        _join(second)

    def test_a_handler_that_breaks_before_its_dispatch_gives_the_floor_back(
            self, patient):
        device = _HeldHost(fail_in_bind=True)
        device.host.set()  # no call is held
        b = MicroBatcher(device, BatcherConfig(max_batch_size=1, max_batch_delay_ms=0.0))
        try:
            assert b.submit("a")[0] == 500
            assert b._floor.is_set() and b._preparing == 0
            assert b.submit("b")[0] == 500  # the worker's next claim is its own
            assert b._floor.is_set() and b._preparing == 0
        finally:
            device.open()
            b.close()

    def test_a_rider_waits_no_longer_than_the_limit_for_a_host_half_that_hangs(self):
        from predictionio_tpu.serving import batcher

        device = _HeldHost()
        b = MicroBatcher(device, BatcherConfig(max_batch_size=1, max_batch_delay_ms=0.0))
        try:
            first = _riders(b, ["a"])
            _wait_until(lambda: device.dispatched == 1)
            second = _riders(b, ["b"])
            _wait_until(lambda: device.entered == 2)  # hangs in its host half
            device.gates[0].set()
            t0 = time.monotonic()
            _join(first)
            assert time.monotonic() - t0 < 40 * batcher._GIVE_WAY_S
            assert not b._floor.is_set()
        finally:
            device.open()
            b.close()
        _join(second)

    def test_wake_counts_the_wait(self, patient):
        device = _HeldHost()
        b = MicroBatcher(device, BatcherConfig(max_batch_size=1, max_batch_delay_ms=0.0))
        try:
            first = _riders(b, ["a"])
            _wait_until(lambda: device.dispatched == 1)
            second = _riders(b, ["b"])
            _wait_until(lambda: device.entered == 2)
            device.gates[0].set()
            _wait_until(lambda: b.stats.batches == 1)
            time.sleep(0.05)
            device.host.set()
            _join(first)
            ms = b.stats.to_json()["latencyMs"]
            assert ms["wake"]["p50"] >= 50.0
            # the wait was behind the other batch's worker, and is in both
            assert 45.0 <= ms["giveWay"]["p50"] <= ms["wake"]["p50"]
        finally:
            device.open()
            b.close()
        _join(second)

    def test_claims_rest_once_the_batches_after_them_come_out_part_full(
            self, monkeypatch):
        """The watch on what a claim leaves the next batch: batches of 1
        of 2 are loaded (they claim) and part full; after a window of such
        claims none claims for the rest, and a full batch after every claim
        keeps them."""
        from predictionio_tpu.serving import batcher

        monkeypatch.setattr(batcher, "_CLAIM_WINDOW", 4)
        b = MicroBatcher(_phased_batch, BatcherConfig(max_batch_size=2, max_batch_delay_ms=0.0))
        try:
            for q in range(4):
                assert b.submit(q)[0] == 200 and b._no_claim_until == 0.0
            assert b._claimed_last and b._claims == 3  # the first followed none
            assert b.submit(4)[0] == 200  # the fourth claim followed by a part-full one
            assert b._no_claim_until > time.monotonic() + 0.5 * batcher._REST_S
            assert (b._claims, b._short_after) == (0, 0) and not b._claimed_last
            assert b.submit(5)[0] == 200 and not b._claimed_last  # at rest
            assert b.stats.to_json()["rest"] == {"secondBatch": 0, "claims": 1}
        finally:
            b.close()
        full = MicroBatcher(_phased_batch, BatcherConfig(max_batch_size=1, max_batch_delay_ms=0.0))
        try:
            for q in range(12):
                assert full.submit(q)[0] == 200
            assert full._no_claim_until == 0.0 and full._claimed_last
            assert full._short_after == 0
            assert full.stats.to_json()["rest"]["claims"] == 0
        finally:
            full.close()
