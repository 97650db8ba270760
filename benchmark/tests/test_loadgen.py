import asyncio
import time

import numpy as np

from benchmark import loadgen


def test_schedule_is_a_pure_function_of_the_seed():
    a = loadgen.open_loop_schedule(256.0, 10.0, 4100000001)
    b = loadgen.open_loop_schedule(256.0, 10.0, 4100000001)
    c = loadgen.open_loop_schedule(256.0, 10.0, 7)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    # same amount of work, inside the window, for every seed
    assert a.size == c.size == 2560
    assert a[0] == 0.0 and 0 < a[-1] < 10.0 and 0 < c[-1] < 10.0
    assert np.all(np.diff(a) > 0)
    # every seed gets the same SET of gaps, in another order (the due times
    # show all but each seed's first gap)
    gaps = loadgen.open_loop_gaps(256.0, 10.0)
    for due in (a, c):
        seen = np.sort(np.diff(due))
        assert np.all(np.isclose(seen, gaps[:-1], rtol=1e-9, atol=1e-12)
                      | np.isclose(seen, gaps[1:], rtol=1e-9, atol=1e-12))
    assert not np.array_equal(np.diff(a), np.diff(c))
    # Poisson-like: mean gap 1/rate, coefficient of variation near 1
    d = np.diff(a)
    assert abs(d.mean() - 1 / 256.0) < 1e-4
    assert 0.9 < d.std() / d.mean() < 1.1


def test_users_are_distinct_and_seeded():
    u = loadgen.distinct_users(1000, 500, 3)
    assert len(set(u.tolist())) == 500
    assert np.array_equal(u, loadgen.distinct_users(1000, 500, 3))


def test_latency_is_timed_from_the_due_instant():
    """A client that stalls 30 ms before it can write and then waits 20 ms
    for the answer: lateness reads the stall, latency reads both."""

    class Stalled(loadgen.Client):
        async def post(self, path, body):
            await asyncio.sleep(0.03)  # e.g. no free connection
            sent = time.monotonic()
            await asyncio.sleep(0.02)
            return 200, b"{}", sent

    due = np.asarray([0.0, 0.01, 0.02])
    users = np.asarray([1, 2, 3])

    async def main():
        return await loadgen._open_loop(Stalled(0, 1.0), due, users, 10)

    out, _ = asyncio.run(main())
    for status, _, late_s, latency_s in out:
        assert status == 200
        assert 0.03 <= late_s < 0.045
        assert 0.05 <= latency_s < 0.07
