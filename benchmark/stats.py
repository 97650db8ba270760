"""Percentiles with failures counted as misses, and the spread rule."""

from __future__ import annotations

import math
import statistics


def percentile(sorted_values: list, q: float, missing: int = 0) -> float:
    """The ``q``-th percentile (0..100) of ``sorted_values`` plus ``missing``
    samples that never arrived, which count as infinitely slow. Nearest
    rank, so the answer is always one of the samples (or ``inf``)."""
    n = len(sorted_values) + missing
    if n == 0:
        return math.inf
    rank = max(1, math.ceil(q / 100.0 * n))  # 1-based
    if rank > len(sorted_values):
        return math.inf
    return float(sorted_values[rank - 1])


def spread(values: list) -> float:
    """Distance between the first and third quartile as a share of the
    median (``statistics.quantiles(values, n=4)``): the benchmark
    contract's measure of run-to-run noise."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
