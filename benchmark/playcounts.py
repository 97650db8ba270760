"""Play counts made from the seed: (user, song, count) triplets at a
published shape, for the implicit-feedback retrain.

As ``data.rating_events``: who listened to what is the configuration's
``structure_seed``'s (``data.degree_sequence`` over the users,
``data.pps_structure`` over the songs' popularity), the same distinct pairs
in every run; ``--seed`` draws the counts and shuffles the events. So every
run buckets to the same shapes and only the first in a compile cache
compiles.

A count is an integer from 1 to ``count_max``, drawn independently of user
and song with probability proportional to ``(count + count_shift) **
-count_exponent``: most triplets are one play, a few are thousands. The
event with the largest draw carries ``count_max`` itself, so every run holds
the published maximum and the confidence that goes with it.
"""

from __future__ import annotations

import numpy as np

from benchmark import data


def structure(shape: dict) -> tuple[np.ndarray, np.ndarray]:
    """The distinct (user code, song code) pairs, in the users' order.

    Where the shape names ``source_users`` and ``source_ratings``, the
    degrees are drawn for the whole source and ``users`` of its users are
    kept, a seeded sample, each with all of its triplets and every song
    still in reach (a smaller service of the same kind): ``ratings`` is
    then what those users hold, and is checked."""
    srng = np.random.default_rng(int(shape["structure_seed"]))
    degrees = data.degree_sequence(
        shape.get("source_users", shape["users"]),
        shape.get("source_ratings", shape["ratings"]),
        shape["user_floor"], shape["user_sigma"], srng)
    if "source_users" in shape:
        degrees = degrees[np.sort(srng.permutation(degrees.size)[: shape["users"]])]
        if int(degrees.sum()) != shape["ratings"]:
            raise ValueError(f"the kept users hold {int(degrees.sum()):,} triplets, "
                             f"the shape says {shape['ratings']:,}")
    users, rank = data.pps_structure(
        degrees, shape["items"], shape["item_exponent"], srng,
        shape.get("item_shift", 0.0))
    # ids in no order of degree or popularity, the same in every run
    user_of = srng.permutation(shape["users"]).astype(np.int32)
    item_of = srng.permutation(shape["items"]).astype(np.int32)
    return user_of[users], item_of[rank]


def count_distribution(shape: dict) -> np.ndarray:
    """P(count = 1 .. count_max)."""
    k = np.arange(1, int(shape["count_max"]) + 1, dtype=np.float64)
    p = (k + float(shape["count_shift"])) ** -float(shape["count_exponent"])
    return p / p.sum()


def play_events(shape: dict, seed: int) -> dict:
    """The events of one run: ``rows``/``cols`` int32 codes, ``vals`` whole
    play counts >= 1 as float32, ``time_us``; all pairs distinct."""
    rows, cols = structure(shape)
    rng = np.random.default_rng(seed)
    order = rng.permutation(rows.size)
    rows, cols = rows[order], cols[order]
    del order
    cdf = np.cumsum(count_distribution(shape))
    u = rng.random(rows.size)
    vals = (np.searchsorted(cdf, u, side="right") + 1).astype(np.float32)
    np.minimum(vals, np.float32(shape["count_max"]), out=vals)
    vals[int(np.argmax(u))] = shape["count_max"]
    time_us = 1_600_000_000_000_000 + rng.integers(0, 10**9, rows.size, dtype=np.int64)
    return {"rows": rows, "cols": cols, "vals": vals, "time_us": time_us}
