"""The load generator: a process of its own, one thread, one asyncio loop,
HTTP/1.1 keep-alive.

It runs as a child (`python benchmark/loadgen.py spec.json`) with the
collector off: inside the harness's own process, which holds gigabytes of
tables, the loop was seen to freeze for 2.6 s in the middle of a window
(and for 0.1 s every few seconds), and an open loop turns such a freeze
into a burst, a full queue and hundreds of refusals that are none of the
server's doing.

Open loop: request ``i`` is due at ``t0 + due[i]`` whatever the server does;
its latency is timed from that instant, so a stall is paid by every request
that was due meanwhile, and ``sent - due`` says how late the generator ran.
Closed loop: ``clients`` callers each send their next query when the last
is answered.

Schedules are a pure function of the seed, and every seed gets the same
*set* of inter-arrival gaps in another order (exponential quantiles,
shuffled), so runs differ in order and never in amount of work.
"""

from __future__ import annotations

import asyncio
import gc
import json
import os
import pickle
import subprocess
import sys
import time

import numpy as np


def open_loop_gaps(rate: float, seconds: float) -> np.ndarray:
    """The set of inter-arrival gaps every seed gets: ``round(rate*seconds)``
    exponential quantiles of mean ``1/rate``, in rising order."""
    n = int(round(rate * seconds))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    if n:
        gaps *= (seconds * (n - 0.5) / n) / gaps.sum()  # last due < seconds
    return gaps


def open_loop_schedule(rate: float, seconds: float, seed: int) -> np.ndarray:
    """Due times in seconds from the window's start: Poisson-like arrivals,
    all inside the window; the seed only orders the gaps."""
    gaps = open_loop_gaps(rate, seconds)
    if not gaps.size:
        return gaps
    np.random.default_rng(seed).shuffle(gaps)
    return np.cumsum(gaps) - gaps[0]


def distinct_users(n_users: int, n: int, seed: int) -> np.ndarray:
    """``n`` different user codes, uniform: no answer can come from a result
    cache or be coalesced with a twin."""
    return np.random.default_rng(seed).choice(n_users, size=n, replace=False)


class _Conn:
    def __init__(self, reader, writer):
        self.reader, self.writer = reader, writer


class Client:
    """A pool of keep-alive connections to one host:port."""

    def __init__(self, port: int, timeout_s: float):
        self.port, self.timeout_s = port, timeout_s
        self.idle: list = []
        self.opened = 0

    async def _conn(self) -> _Conn:
        if self.idle:
            return self.idle.pop()
        reader, writer = await asyncio.open_connection("127.0.0.1", self.port)
        self.opened += 1
        return _Conn(reader, writer)

    async def _exchange(self, conn: _Conn, request: bytes) -> tuple[int, bytes]:
        conn.writer.write(request)
        await conn.writer.drain()
        head = await conn.reader.readuntil(b"\r\n\r\n")
        lines = head.split(b"\r\n")
        status = int(lines[0].split()[1])
        length, close = 0, False
        for line in lines[1:]:
            key, _, value = line.partition(b":")
            key = key.strip().lower()
            if key == b"content-length":
                length = int(value)
            elif key == b"connection" and value.strip().lower() == b"close":
                close = True
        body = await conn.reader.readexactly(length) if length else b""
        if close:
            conn.writer.close()
        else:
            self.idle.append(conn)
        return status, body

    async def post(self, path: str, body: bytes) -> tuple[int, bytes, float]:
        """(status or -1, body, monotonic time the request was written)."""
        request = (f"POST {path} HTTP/1.1\r\nHost: 127.0.0.1:{self.port}\r\n"
                   f"Content-Type: application/json\r\nContent-Length: {len(body)}\r\n"
                   "\r\n").encode() + body
        sent = time.monotonic()
        conn = None
        try:
            conn = await self._conn()
            sent = time.monotonic()
            status, payload = await asyncio.wait_for(
                self._exchange(conn, request), self.timeout_s)
            return status, payload, sent
        except (asyncio.TimeoutError, OSError, asyncio.IncompleteReadError,
                asyncio.LimitOverrunError, ValueError, IndexError):
            if conn is not None:
                conn.writer.close()
            return -1, b"", sent

    async def close(self) -> None:
        for conn in self.idle:
            conn.writer.close()
        self.idle.clear()


def _body(user: int, num: int) -> bytes:
    return json.dumps({"user": str(int(user)), "num": num}).encode()


async def _heartbeat(stalls: list, tick_s: float = 0.01) -> None:
    """Notes every time this loop came back more than 100 ms late from a
    ``tick_s`` sleep: (seconds into the run, seconds late). A stalled
    generator must not be read as a slow server."""
    t0 = last = time.monotonic()
    while True:
        await asyncio.sleep(tick_s)
        now = time.monotonic()
        if now - last - tick_s > 0.1:
            stalls.append((last - t0, now - last - tick_s))
        last = now


async def _open_loop(client: Client, due: np.ndarray, users: np.ndarray, num: int,
                     stalls: list | None = None):
    beat = asyncio.ensure_future(_heartbeat([] if stalls is None else stalls))
    t0 = time.monotonic() + 0.05
    out = [None] * due.size

    async def one(i: int) -> None:
        status, payload, sent = await client.post("/queries.json", _body(users[i], num))
        done = time.monotonic()
        out[i] = (status, payload, sent - (t0 + due[i]), done - (t0 + due[i]))

    tasks = []
    for i in range(due.size):
        delay = t0 + due[i] - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)
        tasks.append(asyncio.ensure_future(one(i)))
    await asyncio.gather(*tasks)
    beat.cancel()
    return out, time.monotonic() - t0


async def _closed_loop(client: Client, clients: int, seconds: float,
                       users: np.ndarray, num: int, stalls: list | None = None):
    beat = asyncio.ensure_future(_heartbeat([] if stalls is None else stalls))
    t0 = time.monotonic()
    out: list = []
    cursor = [0]

    async def caller() -> None:
        while time.monotonic() - t0 < seconds and cursor[0] < users.size:
            i = cursor[0]
            cursor[0] += 1
            status, payload, sent = await client.post(
                "/queries.json", _body(users[i], num))
            done = time.monotonic()
            # (status, body, user index, latency, completed at, seconds from t0)
            out.append((status, payload, i, done - sent, done - t0))

    await asyncio.gather(*[caller() for _ in range(clients)])
    beat.cancel()
    return out, time.monotonic() - t0


def _run(spec: dict) -> dict:
    """In the child: drive one window as ``spec`` says."""
    users = np.load(spec["users"])
    stalls: list = []

    async def main():
        client = Client(spec["port"], spec["timeout_s"])
        try:
            if spec["mode"] == "open":
                return await _open_loop(client, np.load(spec["due"]), users,
                                        spec["num"], stalls)
            return await _closed_loop(client, spec["clients"], spec["seconds"],
                                      users, spec["num"], stalls)
        finally:
            await client.close()

    out, wall = asyncio.run(main())
    return {"out": out, "wall": wall, "stalls": stalls}


def drive(workdir: str, name: str, spec: dict, users: np.ndarray,
          due: np.ndarray | None = None) -> dict:
    """Run one window in a child process. ``spec``: mode ("open" | "closed"),
    port, num, timeout_s, and for a closed loop clients and seconds.
    Returns ``out`` (open: [(status, body, late_s, latency_s)] in due
    order; closed: [(status, body, user index, latency_s, done_at_s)]),
    ``wall``, the child's loop ``stalls`` and its ``startup_s``."""
    base = os.path.join(workdir, f"loadgen-{name}")
    spec = {**spec, "users": base + ".users.npy", "out": base + ".out.pkl"}
    np.save(spec["users"], users)
    if due is not None:
        spec["due"] = base + ".due.npy"
        np.save(spec["due"], due)
    with open(base + ".json", "w") as f:
        json.dump(spec, f)
    t0 = time.monotonic()
    budget = spec.get("seconds", 0.0) + (float(due[-1]) if due is not None and due.size else 0.0)
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), base + ".json"],
        stdin=subprocess.DEVNULL, capture_output=True, text=True,
        timeout=budget + 10 * spec["timeout_s"] + 60)
    if proc.returncode != 0:
        raise RuntimeError(f"load generator failed ({proc.returncode}): {proc.stderr[-2000:]}")
    with open(spec["out"], "rb") as f:
        result = pickle.load(f)  # written by our own child a moment ago
    result["startup_s"] = time.monotonic() - t0 - result["wall"]
    return result


if __name__ == "__main__":
    gc.disable()  # a short-lived process: nothing here is worth a pause
    with open(sys.argv[1]) as f:
        _spec = json.load(f)
    _result = _run(_spec)
    with open(_spec["out"], "wb") as f:
        pickle.dump(_result, f, protocol=pickle.HIGHEST_PROTOCOL)
