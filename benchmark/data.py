"""Inputs made from the seed: rating events at a published shape, and
factor tables for a serve-only configuration.

Every seed gives the same *set* of sizes in another order. The bipartite
structure (which user rated which item) is fixed by the configuration's
``structure_seed``; ``--seed`` draws the rating values and shuffles the
events. So every run of a cell buckets to the same shapes and only the
first run in a checkout compiles: the sweep is compiled per bucket shape,
``nnz`` is a static argument of it, and the hot rows are grouped in row
order, so even relabelling users would change a shape (measured: three
programs recompiled, 150 s inside the window).
"""

from __future__ import annotations

import numpy as np


def degree_sequence(n_users: int, total: int, floor: int, sigma: float,
                    rng: np.random.Generator) -> np.ndarray:
    """Heavy-tailed user degrees: ``floor`` plus a log-normal share of the
    rest, summing to ``total`` exactly."""
    x = rng.lognormal(0.0, sigma, n_users)
    extra = total - floor * n_users
    if extra < 0:
        raise ValueError("total is under floor * users")
    share = x / x.sum() * extra
    base = np.floor(share).astype(np.int64)
    short = int(extra - base.sum())
    # largest remainders get the last few
    order = np.argsort(-(share - base), kind="stable")[:short]
    base[order] += 1
    return base + floor


def pps_structure(degrees: np.ndarray, n_items: int, exponent: float,
                  rng: np.random.Generator, shift: float = 0.0,
                  ) -> tuple[np.ndarray, np.ndarray]:
    """Distinct (user, popularity rank) pairs: user ``u`` gets exactly
    ``degrees[u]`` different items, item of rank ``r`` with probability
    proportional to ``(r+1+shift) ** -exponent`` where that is possible
    (``shift`` flattens the head, so the most popular item can be held to
    a published count).

    Systematic sampling with probability proportional to size: inclusion
    probabilities ``pi_r = min(1, lam * p_r)`` summing to the degree, one
    point per unit of their running sum. The ``m`` most popular items are
    saturated (``pi = 1``), and no unsaturated item is wider than the step,
    so no item is drawn twice. Vectorised over all ratings."""
    p = (np.arange(1, n_items + 1, dtype=np.float64) + shift) ** -exponent
    p /= p.sum()
    cum = np.concatenate([[0.0], np.cumsum(p)])  # cum[m] = mass of ranks < m
    d = degrees.astype(np.int64)
    if d.max() > n_items:
        raise ValueError("a user cannot rate more items than exist")
    # smallest m with (d - m) * p[m] <= 1 - cum[m]; monotone in m
    lo = np.zeros_like(d)
    hi = np.minimum(d, n_items - 1)
    while np.any(lo < hi):
        mid = (lo + hi) // 2
        ok = (d - mid) * p[mid] <= 1.0 - cum[mid]
        hi = np.where(ok, mid, hi)
        lo = np.where(ok, lo, mid + 1)
    m = lo
    step = (1.0 - cum[m]) / np.maximum(d - m, 1)  # 1 / lam, in mass
    theta = rng.random(d.size)
    total = int(d.sum())
    users = np.repeat(np.arange(d.size, dtype=np.int32), d)
    k = np.arange(total, dtype=np.int32) - np.repeat((np.cumsum(d) - d).astype(np.int32), d)
    # mass of point k of user u: cum[m] + (k - m + theta) * step
    mass = np.repeat(step, d)
    mass *= k
    mass += np.repeat(cum[m] + (theta - m) * step, d)
    rank = (np.searchsorted(cum, mass, side="right") - 1).astype(np.int32)
    del mass
    m_r = np.repeat(m.astype(np.int32), d)
    np.clip(rank, m_r, n_items - 1, out=rank)
    saturated = k < m_r
    rank[saturated] = k[saturated]
    del k, m_r, saturated
    # rounding at a boundary could repeat a rank inside one user: ranks rise
    # with k, so a repeat is an equal neighbour
    same = (rank[1:] == rank[:-1]) & (users[1:] == users[:-1])
    if same.any():
        raise ValueError(f"{int(same.sum())} repeated (user, item) pairs")
    return users, rank


def rating_events(shape: dict, seed: int) -> dict:
    """The events of one run: ``rows``/``cols`` int32 codes, ``vals`` in
    halves from 0.5 to 5.0, ``time_us``; all pairs distinct."""
    srng = np.random.default_rng(int(shape["structure_seed"]))
    degrees = degree_sequence(
        shape["users"], shape["ratings"], shape["user_floor"],
        shape["user_sigma"], srng,
    )
    users, rank = pps_structure(degrees, shape["items"], shape["item_exponent"], srng,
                                shape.get("item_shift", 0.0))
    # ids in no order of degree or popularity, the same in every run
    user_of = srng.permutation(shape["users"]).astype(np.int32)
    item_of = srng.permutation(shape["items"]).astype(np.int32)
    rng = np.random.default_rng(seed)
    order = rng.permutation(users.size)
    rows = user_of[users][order]
    cols = item_of[rank][order]
    vals = rng.integers(1, 11, size=rows.size).astype(np.float32) / 2.0
    time_us = 1_600_000_000_000_000 + rng.integers(0, 10**9, rows.size, dtype=np.int64)
    return {"rows": rows, "cols": cols, "vals": vals, "time_us": time_us}


def factor_tables(shape: dict, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """Seeded float32 factor tables whose scores spread like trained ones:
    normal entries of scale ``1/sqrt(rank)``."""
    rng = np.random.default_rng(seed)
    k = int(shape["rank"])
    scale = np.float32(1.0 / np.sqrt(k))
    user = rng.standard_normal((shape["users"], k), dtype=np.float32) * scale
    item = rng.standard_normal((shape["items"], k), dtype=np.float32) * scale
    return user, item
