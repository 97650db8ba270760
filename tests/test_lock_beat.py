"""The beat thread (predictionio_tpu/serving/lockbeat.py): the interpreter
lock timed from outside the request path, the host caught standing still
(ISSUE 37). Orderings and identities, not times: the suite shares its
machine."""

from __future__ import annotations

import ctypes
import faulthandler
import os
import threading
import time

import pytest

from predictionio_tpu.api.stats import LockStats
from predictionio_tpu.serving.lockbeat import LockBeat


def _stop_the_armer():
    # faulthandler has one watchdog a process: services that earlier tests
    # of this process left open keep their beats, and one of them arms it
    for left in LockBeat.live():
        left.stop()


@pytest.fixture()
def beat(tmp_path):
    _stop_the_armer()
    made = []

    def make(cpu_total_ns=lambda: 0):
        b = LockBeat(LockStats(), cpu_total_ns, str(tmp_path / "d" / "stalls.txt"))
        made.append(b)
        return b.start()

    yield make
    for b in made:
        b.stop()


def _wait_until(condition, timeout=20.0):
    give_up = time.monotonic() + timeout
    while not condition():
        assert time.monotonic() < give_up, "the condition never held"
        time.sleep(0.01)


def _beats(b: LockBeat) -> int:
    return len(b.stats._acquire_ms)


def hold_the_interpreter_lock(seconds: float) -> None:
    # PyDLL keeps the lock over the call: nothing in Python runs meanwhile
    usleep = ctypes.PyDLL(None).usleep
    usleep.argtypes, usleep.restype = [ctypes.c_uint], ctypes.c_int
    usleep(int(seconds * 1e6))


class TestLockBeat:
    def test_an_idle_process_beats_on_time_and_is_not_busy(self, beat):
        b = beat()
        _wait_until(lambda: _beats(b) >= 12)
        out = b.stats.to_json()
        assert out["acquireMs"]["p50"] is not None and out["acquireMs"]["p50"] >= 0
        # every fourth beat reads the totals
        assert len(b.stats._busy_pct) == _beats(b) // LockBeat.EVERY or (
            len(b.stats._busy_pct) == (_beats(b) - 1) // LockBeat.EVERY)
        assert out["busyPct"]["p99"] == 0.0  # nobody added CPU time
        assert out["stalls"] == {"count": 0, "longestMs": 0.0, "last": None}
        assert b.alive() and LockBeat.armer() is b
        assert not os.path.exists(b.dump_path)  # no stall, no file

    def test_threads_spinning_in_python_make_the_lock_busy_and_late(self, beat):
        """With two threads spinning in Python the request path's CPU
        share and a sleeper's wait for the lock read several times what
        the same process read idle."""
        totals = [0, 0]  # a slot a spinner: each adds its own clock, no lock
        b = beat(lambda: sum(totals))
        _wait_until(lambda: _beats(b) >= 16)
        idle = b.stats.to_json()
        stop = threading.Event()

        def spin(slot):
            start = time.thread_time_ns()
            while not stop.is_set():
                for _ in range(2000):
                    pass
                totals[slot] = time.thread_time_ns() - start

        threads = [threading.Thread(target=spin, args=(slot,), daemon=True)
                   for slot in range(2)]
        for t in threads:
            t.start()
        try:
            b.stats._acquire_ms.clear()
            b.stats._busy_pct.clear()
            _wait_until(lambda: len(b.stats._busy_pct) >= 6, timeout=60.0)
            busy = b.stats.to_json()
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=10)
        assert idle["busyPct"]["p50"] == 0.0
        # one lock: the two together are on the CPU for about one wall
        assert busy["busyPct"]["p50"] > 30.0
        # a sleeper that wakes queues for the lock behind the spinners (the
        # switch interval, 5 ms, at a time)
        assert busy["acquireMs"]["p50"] > 3 * max(idle["acquireMs"]["p50"], 0.1)

    def test_a_held_lock_is_a_stall_with_a_dump_and_a_sleep_is_none(self, beat):
        b = beat()
        _wait_until(lambda: _beats(b) >= 5)
        time.sleep(1.5)  # lets the lock go: the beat goes on
        assert b.stats.to_json()["stalls"]["count"] == 0
        assert not os.path.exists(b.dump_path)  # no stall, no file
        before = _beats(b)
        cpu0 = time.process_time()
        hold_the_interpreter_lock(1.5)
        held_cpu_ms = (time.process_time() - cpu0) * 1e3
        _wait_until(lambda: _beats(b) > before)
        stalls = b.stats.to_json()["stalls"]
        assert stalls["count"] == 1
        last = stalls["last"]
        assert last["lateMs"] > 1000.0 and stalls["longestMs"] == last["lateMs"]
        assert last["at"].endswith("+00:00")
        # the process slept through it: the holder used no CPU either
        assert 0.0 <= last["cpuMs"] <= held_cpu_ms + 200.0
        assert last["dump"] == b.dump_path
        with open(b.dump_path) as f:
            dump = f.read()
        # written while the lock was held: the holder's frame is in it
        assert "hold_the_interpreter_lock" in dump
        assert "Timeout (0:00:00.6" in dump and dump.count("Timeout") == 1
        # and the watchdog is armed again: the next stall is dumped too
        before = _beats(b)
        hold_the_interpreter_lock(0.9)
        _wait_until(lambda: _beats(b) > before)
        assert b.stats.to_json()["stalls"]["count"] == 2
        with open(b.dump_path) as f:
            assert f.read().count("Timeout") == 2

    def test_stop_ends_the_thread_and_disarms_the_dump(self, beat):
        b = beat()
        _wait_until(lambda: _beats(b) >= 2)
        b.stop()
        assert not b.alive() and LockBeat.armer() is None
        assert b not in LockBeat.live()
        hold_the_interpreter_lock(0.8)  # nothing armed: nothing dumped
        assert not os.path.exists(b.dump_path)  # no stall, no file
        b.stop()  # twice is fine

    def test_a_beat_whose_owner_is_gone_ends_on_its_own(self, tmp_path):
        class Owner:
            pass

        _stop_the_armer()
        owner = Owner()
        b = LockBeat(LockStats(), lambda: 0, str(tmp_path / "stalls.txt"),
                     owner=owner).start()
        try:
            _wait_until(lambda: _beats(b) >= 2)
            assert b.alive() and LockBeat.armer() is b
            del owner  # nobody closed it
            _wait_until(lambda: not b.alive())
            assert LockBeat.armer() is None  # disarmed on its way out
        finally:
            b.stop()

    def test_one_watchdog_a_process_the_first_beat_alive_arms_it(self, beat):
        first, second = beat(), beat()
        _wait_until(lambda: _beats(first) >= 5 and _beats(second) >= 5)
        assert LockBeat.armer() is first
        hold_the_interpreter_lock(0.9)
        _wait_until(lambda: first.stats.stalls == 1 and second.stats.stalls == 1)
        assert first.stats.last_stall["dump"] == first.dump_path
        assert second.stats.last_stall["dump"] is None  # counted, not dumped
        first.stop()
        _wait_until(lambda: LockBeat.armer() is second)


class TestTheServicesBeat:
    """One beat a QueryService, from the boot mark to close()."""

    @pytest.fixture()
    def trained(self, storage_env):
        from predictionio_tpu.controller import local_context
        from predictionio_tpu.workflow import load_engine_variant, run_train

        variant = load_engine_variant({
            "id": "beat-engine", "version": "0.1",
            "engineFactory": "fake_dase:engine0",
            "datasource": {"params": {"base": 10}},
            "algorithms": [{"name": "a0", "params": {"mult": 2}}],
        })
        run_train(variant, local_context())
        return variant

    def test_the_lock_block_and_the_thread_end_with_the_service(
            self, trained, tmp_path, monkeypatch):
        from predictionio_tpu.serving import BatcherConfig, batcher
        from predictionio_tpu.workflow.serving import QueryService

        # a worker adds its thread's CPU time on every cycle, not one in 32
        monkeypatch.setattr(batcher, "_CPU_EVERY", 1)
        _stop_the_armer()
        threads_before = {t.ident for t in threading.enumerate()}
        qs = QueryService(trained, batching=BatcherConfig(max_batch_delay_ms=0.0))
        try:
            new = [t.name for t in threading.enumerate()
                   if t.ident not in threads_before]
            assert new.count("pio-lock-beat") == 1
            for q in range(4):
                assert qs.batcher.submit(q)[0] == 200
            _wait_until(lambda: len(qs._lock_stats._busy_pct) >= 2)
            lock = qs.stats_json()["lock"]
            assert set(lock) == {"acquireMs", "busyPct", "stalls", "cpuNs"}
            assert lock["acquireMs"]["p50"] is not None
            assert lock["busyPct"]["p50"] is not None
            assert lock["stalls"]["count"] == 0
            assert lock["cpuNs"]["workers"] > 0 and lock["cpuNs"]["riders"] == 0
            assert qs._beat.dump_path == str(
                tmp_path / "deployments" / f"stalls-{os.getpid()}.txt")
        finally:
            qs.close()
        assert not qs._beat.alive() and qs._beat not in LockBeat.live()
        assert LockBeat.armer() is None
        qs.close()  # safe twice

    def test_a_service_without_batching_beats_too_and_stop_ends_it(self, trained):
        from predictionio_tpu.workflow.serving import QueryService

        _stop_the_armer()
        qs = QueryService(trained)
        try:
            assert qs._beat.alive()
            qs.stop_server = lambda: None
            assert qs.dispatch("GET", "/stop", {}).status == 200
            assert not qs._beat.alive()
            lock = qs.stats_json()["lock"]
            assert lock["cpuNs"] == {"workers": 0, "riders": 0}
        finally:
            qs.close()


def test_the_watchdog_is_the_standard_librarys():
    # the dump is faulthandler's: a C thread that needs no interpreter lock
    assert hasattr(faulthandler, "dump_traceback_later")
    assert hasattr(faulthandler, "cancel_dump_traceback_later")
