"""The load generator for prepared bodies: ``benchmark/loadgen.py``'s
closed loop (one process, one thread, one asyncio loop, keep-alive, the
collector off, the heartbeat that tells a stalled generator from a slow
server), sending request ``i`` the bytes ``bodies[i]`` instead of a body
made from a user code: a query that carries categories or a black list.
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

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import loadgen  # noqa: E402


async def _closed_loop(client: loadgen.Client, clients: int, seconds: float,
                       bodies: list, stalls: list):
    beat = asyncio.ensure_future(loadgen._heartbeat(stalls))
    t0 = time.monotonic()
    out: list = []
    cursor = [0]

    async def caller() -> None:
        while time.monotonic() - t0 < seconds and cursor[0] < len(bodies):
            i = cursor[0]
            cursor[0] += 1
            status, payload, sent = await client.post("/queries.json", bodies[i])
            done = time.monotonic()
            # (status, body, query number, latency, completed at, seconds from t0)
            out.append((status, payload, i, done - sent, done - t0))

    await asyncio.gather(*[caller() for _ in range(clients)])
    beat.cancel()
    return out, time.monotonic() - t0


def _run(spec: dict) -> dict:
    with open(spec["bodies"], "rb") as f:
        bodies = pickle.load(f)  # written by drive() a moment ago
    stalls: list = []

    async def main():
        client = loadgen.Client(spec["port"], spec["timeout_s"])
        try:
            return await _closed_loop(client, spec["clients"], spec["seconds"],
                                      bodies, stalls)
        finally:
            await client.close()

    out, wall = asyncio.run(main())
    return {"out": out, "wall": wall, "stalls": stalls}


def drive(workdir: str, name: str, spec: dict, bodies: list) -> dict:
    """Run one closed-loop window in a child process. ``spec``: port,
    timeout_s, clients, seconds. Returns ``out`` [(status, body, query
    number, latency_s, done_at_s)], ``wall``, the child's loop ``stalls`` and
    its ``startup_s``, as ``loadgen.drive`` does."""
    base = os.path.join(workdir, f"bodygen-{name}")
    spec = {**spec, "bodies": base + ".bodies.pkl", "out": base + ".out.pkl"}
    with open(spec["bodies"], "wb") as f:
        pickle.dump(bodies, f, protocol=pickle.HIGHEST_PROTOCOL)
    with open(base + ".json", "w") as f:
        json.dump(spec, f)
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), base + ".json"],
        stdin=subprocess.DEVNULL, capture_output=True, text=True,
        timeout=spec["seconds"] + 10 * spec["timeout_s"] + 60)
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
