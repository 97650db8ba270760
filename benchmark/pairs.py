"""Interaction pairs made from the seed: distinct (user, item) pairs at a
published shape in which every user and every item occurs, so that an
engine whose tables are sized by the ids it reads has the source's row
counts in every run.

The structure (who interacted with what) is fixed by the configuration's
``structure_seed``; ``--seed`` shuffles the events and draws their times.
User degrees are ``data.degree_sequence`` with floor 1. Every item has one
*cover* pair with a user of its own (so no user's degree goes under 0),
and the other pairs draw items by popularity through
``data.pps_structure``: an item's count is 1 plus a shifted power law's.
"""

from __future__ import annotations

import numpy as np

from benchmark import data


def pair_structure(shape: dict) -> tuple[np.ndarray, np.ndarray]:
    """``(users, items)`` int32 codes of the ``shape["pairs"]`` distinct
    pairs; ids in no order of degree or popularity."""
    n_users, n_items, total = shape["users"], shape["items"], shape["pairs"]
    if not n_items <= n_users or total < n_users:
        raise ValueError("needs items <= users <= pairs")
    srng = np.random.default_rng(int(shape["structure_seed"]))
    degrees = data.degree_sequence(n_users, total, 1, shape["user_sigma"], srng)
    # item of popularity rank k is covered by user cover[k]; users distinct
    cover = srng.permutation(n_users)[:n_items].astype(np.int64)
    rest = degrees.copy()
    rest[cover] -= 1
    users, rank = data.pps_structure(rest, n_items, shape["item_exponent"], srng,
                                     shape.get("item_shift", 0.0))
    drawn = np.sort(users.astype(np.int64) * n_items + rank)
    for _ in range(16):
        key = cover * n_items + np.arange(n_items)
        at = np.minimum(np.searchsorted(drawn, key), drawn.size - 1)
        clash = np.flatnonzero(drawn[at] == key)
        if clash.size == 0:
            break
        # a cover pair the user also drew: the clashing items and one more
        # trade their users round
        spare = np.setdiff1d(np.arange(clash.size + 1), clash)[:1]
        ring = np.append(clash, spare)
        cover[ring] = np.roll(cover[ring], 1)
    else:
        raise ValueError("cover pairs still repeat drawn pairs")
    user_of = srng.permutation(n_users).astype(np.int32)
    item_of = srng.permutation(n_items).astype(np.int32)
    rows = np.concatenate([user_of[cover], user_of[users]])
    cols = np.concatenate([item_of, item_of[rank]])
    return rows, cols


def pair_events(shape: dict, seed: int) -> dict:
    """The events of one run: ``rows``/``cols`` int32 codes and
    ``time_us``, in the seed's order."""
    rows, cols = pair_structure(shape)
    rng = np.random.default_rng(seed)
    order = rng.permutation(rows.size)
    time_us = 1_600_000_000_000_000 + rng.integers(0, 10**9, rows.size, dtype=np.int64)
    return {"rows": rows[order], "cols": cols[order], "time_us": time_us}
