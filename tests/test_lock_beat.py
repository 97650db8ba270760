"""The beat thread (predictionio_tpu/serving/lockbeat.py): the interpreter
lock timed from outside the request path, the host caught standing still
(ISSUE 37), and no traceback taken by a thread without the lock (ISSUE 42).
Orderings and identities, not times: the suite shares its machine."""

from __future__ import annotations

import ctypes
import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.request

import pytest

from predictionio_tpu.api.stats import LockStats
from predictionio_tpu.serving.lockbeat import LockBeat

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

STALL_KEYS = {"count", "longestMs", "lateMsTotal", "cpuMsTotal", "last"}
LAST_KEYS = {"at", "lateMs", "cpuMs"}
NO_STALLS = {"count": 0, "longestMs": 0.0, "lateMsTotal": 0.0, "cpuMsTotal": 0.0,
             "last": None}


@pytest.fixture()
def beat():
    made = []

    def make(cpu_total_ns=lambda: 0):
        b = LockBeat(LockStats(), cpu_total_ns)
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
        assert out["stalls"] == NO_STALLS
        assert b.alive()

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

    def test_a_held_lock_is_a_stall_and_a_sleep_is_none(self, beat):
        b = beat()
        _wait_until(lambda: _beats(b) >= 5)
        time.sleep(1.5)  # lets the lock go: the beat goes on
        assert b.stats.to_json()["stalls"]["count"] == 0
        before = _beats(b)
        cpu0 = time.process_time()
        hold_the_interpreter_lock(1.5)
        held_cpu_ms = (time.process_time() - cpu0) * 1e3
        _wait_until(lambda: _beats(b) > before)
        stalls = b.stats.to_json()["stalls"]
        assert stalls["count"] == 1 and set(stalls) == STALL_KEYS
        last = stalls["last"]
        assert set(last) == LAST_KEYS  # no dump: nothing is written anywhere
        assert last["lateMs"] > 1000.0 and stalls["longestMs"] == last["lateMs"]
        assert last["at"].endswith("+00:00")
        # the process slept through it: the holder used no CPU either
        assert 0.0 <= last["cpuMs"] <= held_cpu_ms + 200.0
        # and the next one is counted too
        before = _beats(b)
        hold_the_interpreter_lock(0.9)
        _wait_until(lambda: _beats(b) > before)
        assert b.stats.to_json()["stalls"]["count"] == 2

    def test_stop_ends_the_thread_twice_and_before_start(self, beat):
        b = beat()
        _wait_until(lambda: _beats(b) >= 2)
        b.stop()
        assert not b.alive()
        beats = _beats(b)
        hold_the_interpreter_lock(0.8)  # nobody is left to count it
        assert _beats(b) == beats and b.stats.stalls == 0
        b.stop()  # twice is fine
        never_started = LockBeat(LockStats(), lambda: 0)
        never_started.stop()  # and so is before start()
        assert not never_started.alive()

    def test_a_beat_whose_owner_is_gone_ends_on_its_own(self):
        class Owner:
            pass

        owner = Owner()
        b = LockBeat(LockStats(), lambda: 0, owner=owner).start()
        try:
            _wait_until(lambda: _beats(b) >= 2)
            assert b.alive()
            del owner  # nobody closed it
            _wait_until(lambda: not b.alive())
        finally:
            b.stop()

    def test_the_totals_are_the_sums_of_the_stalls_and_the_longest_their_maximum(self):
        stats = LockStats()
        stalls = [(612.25, 40.5), (2662.436, 230.0), (801.0, 799.125)]
        for late_ms, cpu_ms in stalls:
            stats.record_stall(late_ms, cpu_ms)
        out = stats.to_json()["stalls"]
        assert set(out) == STALL_KEYS and set(out["last"]) == LAST_KEYS
        assert out["count"] == 3
        assert out["lateMsTotal"] == pytest.approx(sum(s[0] for s in stalls), abs=1e-3)
        assert out["cpuMsTotal"] == pytest.approx(sum(s[1] for s in stalls), abs=1e-3)
        assert out["longestMs"] == max(s[0] for s in stalls)
        assert (out["last"]["lateMs"], out["last"]["cpuMs"]) == stalls[-1]


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
        finally:
            qs.close()
        assert not qs._beat.alive()
        # the beat writes no file: nothing of it under the store's directory
        assert not os.path.exists(tmp_path / "deployments")
        qs.close()  # safe twice

    def test_a_service_without_batching_beats_too_and_stop_ends_it(self, trained):
        from predictionio_tpu.workflow.serving import QueryService

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

    def test_a_served_stats_json_holds_the_stalls_block_as_documented(self, trained):
        from predictionio_tpu.api.http import start_background
        from predictionio_tpu.workflow.serving import QueryService

        qs = QueryService(trained)
        server, _ = start_background(qs.dispatch)
        try:
            url = f"http://127.0.0.1:{server.server_address[1]}/stats.json"
            with urllib.request.urlopen(url, timeout=30) as r:
                stalls = json.loads(r.read())["lock"]["stalls"]
            assert stalls == NO_STALLS
            qs._lock_stats.record_stall(1400.0, 35.0)
            with urllib.request.urlopen(url, timeout=30) as r:
                stalls = json.loads(r.read())["lock"]["stalls"]
            assert set(stalls) == STALL_KEYS and set(stalls["last"]) == LAST_KEYS
            assert (stalls["count"], stalls["longestMs"]) == (1, 1400.0)
        finally:
            server.shutdown()
            server.server_close()
            qs.close()


# A process with a beat, told what to do on its stdin: `work N` starts N busy
# daemon threads, `hold` keeps the interpreter lock at work inside one
# regular expression that backtracks for about a second, `stalls` prints
# lock.stalls, `quit` exits 0.
_CHILD = r"""
import json, re, sys, threading, time
from predictionio_tpu.api.stats import LockStats
from predictionio_tpu.serving.lockbeat import LockBeat

beat = LockBeat(LockStats(), lambda: 0).start()

def rec(n):
    return json.dumps({"a": [1, 2, 3]}) if n == 0 else rec(n - 1)

def work():
    k = 0
    while True:
        rec(5 + k % 40)
        k += 1
        if k % 50 == 0:
            time.sleep(0.001)

def hold():
    # (a+)+$ against aaa...b tries every split of the a's: twice the time
    # a letter, inside one call of the matcher, which never lets the lock go
    n, took = 16, 0.0
    while took < 0.9:
        n += 1
        t0 = time.perf_counter()
        re.match(r"(a+)+$", "a" * n + "b")
        took = time.perf_counter() - t0

print("ready", flush=True)
for line in sys.stdin:
    cmd = line.split()
    if cmd[0] == "work":
        for _ in range(int(cmd[1])):
            threading.Thread(target=work, daemon=True).start()
    elif cmd[0] == "hold":
        hold()
        time.sleep(0.2)  # the late beat records itself once it runs again
    elif cmd[0] == "quit":
        sys.exit(0)
    print(json.dumps(beat.stats.to_json()["stalls"]), flush=True)
"""


class _Child:
    def __init__(self, tmp_path):
        script = tmp_path / "beat_child.py"
        script.write_text(_CHILD)
        self.proc = subprocess.Popen(
            [sys.executable, str(script)], cwd=REPO, text=True,
            env={**os.environ, "PYTHONPATH": REPO},
            stdin=subprocess.PIPE, stdout=subprocess.PIPE)
        assert self.proc.stdout.readline().strip() == "ready"

    def ask(self, command: str) -> dict:
        self.proc.stdin.write(command + "\n")
        self.proc.stdin.flush()
        line = self.proc.stdout.readline()
        assert line, f"the child ended: exit code {self.proc.poll()}"
        return json.loads(line)

    def stop_and_continue(self, seconds: float) -> None:
        """What a stall of the whole machine is from inside."""
        os.kill(self.proc.pid, signal.SIGSTOP)
        time.sleep(seconds)
        os.kill(self.proc.pid, signal.SIGCONT)

    def quit(self) -> int:
        self.proc.stdin.write("quit\n")
        self.proc.stdin.flush()
        return self.proc.wait(timeout=20)

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait(timeout=20)
        self.proc.stdin.close()
        self.proc.stdout.close()


@pytest.fixture()
def child(tmp_path):
    if not hasattr(signal, "SIGSTOP"):
        pytest.skip("needs POSIX job-control signals")
    c = _Child(tmp_path)
    yield c
    c.kill()


class TestStallsOfTheMachineAndOfTheInterpreter:
    def test_a_server_stopped_and_continued_among_busy_threads_lives(self, child):
        """ISSUE 42's provocation: faulthandler's watchdog, re-armed by the
        beat, woke with its deadline passed as the stopped process went on
        and walked 64 running threads' frames without the interpreter
        lock: signal 11 within some ten stops, most runs. The beat that
        only counts outlives them all. (Nothing is asserted of the old
        code: its death was a matter of chance.)"""
        child.ask("work 64")
        stops = 12
        for _ in range(stops):
            child.stop_and_continue(0.8)
            time.sleep(0.5)
            assert child.proc.poll() is None, "the process died of its own watch"
        stalls = child.ask("stalls")
        # a beat that was due while the process stood is over 0.5 s late;
        # two stops can fall into one beat on a crowded machine
        assert 8 <= stalls["count"]
        assert stalls["longestMs"] >= 700.0
        assert stalls["lateMsTotal"] >= stalls["count"] * 500.0
        assert child.quit() == 0

    def test_cpu_against_lateness_tells_the_machine_from_the_interpreter(self, child):
        """The two verdicts PERF.md draws from a stall's numbers."""
        child.stop_and_continue(1.2)  # idle but for the beat: the machine stood
        time.sleep(0.3)
        machine = child.ask("stalls")
        assert machine["count"] == 1
        assert machine["last"]["cpuMs"] < 0.2 * machine["last"]["lateMs"]
        assert machine["cpuMsTotal"] < 0.2 * machine["lateMsTotal"]
        # a thread that keeps the lock and works: most of the lateness is
        # CPU time (best of three: a crowded machine takes the CPU away too)
        shares = []
        for _ in range(3):
            before = child.ask("stalls")["count"]
            at_work = child.ask("hold")
            assert at_work["count"] > before
            shares.append(at_work["last"]["cpuMs"] / at_work["last"]["lateMs"])
            if shares[-1] > 0.5:
                break
        assert max(shares) > 0.5, shares
        assert max(shares) > 5 * machine["last"]["cpuMs"] / machine["last"]["lateMs"]
