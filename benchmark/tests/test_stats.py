import math

import pytest

from benchmark.stats import percentile, spread


def test_percentile_is_nearest_rank():
    s = sorted([10.0, 20.0, 30.0, 40.0])
    assert percentile(s, 50) == 20.0
    assert percentile(s, 95) == 40.0
    assert percentile(s, 25) == 10.0


def test_failures_count_as_infinitely_slow():
    s = sorted(float(x) for x in range(1, 91))  # 90 answers
    # 10 missing of 100: p50 is the 50th of 100, p95 falls among the missing
    assert percentile(s, 50, missing=10) == 50.0
    assert percentile(s, 90, missing=10) == 90.0
    assert math.isinf(percentile(s, 95, missing=10))
    assert math.isinf(percentile([], 50, missing=3))
    assert math.isinf(percentile([], 50))


def test_spread_is_the_contracts():
    # statistics.quantiles(n=4) of 1..6: q1 = 1.75, q3 = 5.25, median 3.5
    assert spread([1, 2, 3, 4, 5, 6]) == pytest.approx((5.25 - 1.75) / 3.5)


def test_memory_peak_is_one_instant_not_the_sum_of_two_peaks():
    from benchmark import harness, pio_child

    class Device:
        # live buffers peak first (bucketing), the program pool grows later
        readings = iter([(900, 100), (300, 750), (250, 750)])

        def memory_stats(self):
            in_use, reserved = next(self.readings)
            return {"bytes_in_use": in_use, "bytes_reserved": reserved,
                    "peak_bytes_in_use": 900, "peak_bytes_reserved": 750}

    dev = Device()
    watch = pio_child.MemoryWatch([dev])
    watch.sample()
    watch.sample()
    last = dev.memory_stats()
    rep = {"memory_watch": {"peaks": watch.peaks}, "memory": [last]}
    got = harness.memory_peak([rep, rep])
    assert (got["occupied"], got["in_use"], got["reserved"]) == (1050, 300, 750)
    assert got["occupied"] < got["allocator_peak_in_use"] + got["allocator_peak_reserved"]
