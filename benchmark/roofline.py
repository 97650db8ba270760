"""The table of peaks, and the operations and bytes each kernel *needs*.

A roofline share is the least time the chip could take (the larger of
needed FLOPs over peak FLOP/s and needed bytes over peak bytes/s) over the
time it took. Needed means what the algorithm asks for at the cell's
shapes: no padded rows, no recomputation. So a share reads low while the
program pads, and can never honestly pass 100%.
"""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind.startswith("_") or device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in benchmark/peaks.json "
            f"(known: {sorted(k for k in table if not k.startswith('_'))})"
        )
    return table[device_kind]


def als_sweep_work(users: int, items: int, ratings: int, rank: int) -> dict:
    """One ALS-WR sweep (users given items, then items given users) in
    float32. Per rating and side: a rank x rank outer product into the
    Gramian (2 k^2 FLOPs) and a rank-vector into the right-hand side (2 k);
    per solved row a Cholesky-sized solve (k^3/3 + 2 k^2). Bytes: each
    rating's index and value read once a side (8), the other side's factor
    row gathered for it (4 k), each solved row written once (4 k)."""
    k = rank
    flops = 2 * ratings * (2 * k * k + 2 * k) + (users + items) * (k**3 / 3 + 2 * k * k)
    nbytes = 2 * ratings * (8 + 4 * k) + (users + items) * 4 * k
    return {"flops": float(flops), "bytes": float(nbytes)}


def topk_work(queries: float, items: int, rank: int, num: int) -> dict:
    """One scoring batch for the queries really in it: a [queries, rank] x
    [rank, items] product (2 q k i FLOPs); the item table read once, the
    queries' user rows read, and ``num`` (index, score) pairs written per
    query."""
    flops = 2.0 * queries * rank * items
    nbytes = 4.0 * items * rank + 4.0 * queries * rank + 8.0 * queries * num
    return {"flops": flops, "bytes": nbytes}


def least_seconds(work: dict, device_kind: str) -> tuple[float, str]:
    """(least time the chip could take, which bound binds)."""
    pk = peaks(device_kind)
    by_flops = work["flops"] / pk["flops_per_s"]
    by_bytes = work["bytes"] / pk["bytes_per_s"]
    return (by_flops, "flops") if by_flops >= by_bytes else (by_bytes, "bytes")
