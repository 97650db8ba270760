"""The plain reference of the implicit-feedback ALS configurations, and the
comparison that decides ``correct``. Imports nothing of
``predictionio_tpu/ops``: numpy in float64, from the events the benchmark
itself wrote and the blob the program stored.

Hu, Koren and Volinsky (ICDM 2008): a play count ``r_ui`` is a preference
``p_ui = [r_ui > 0]`` held with confidence ``c_ui = 1 + alpha r_ui``, and the
item row ``y_i`` given the user rows ``X`` solves

    (X^T X + sum_u alpha r_ui x_u x_u^T + lambda n_i I) y_i
        = sum_u (1 + alpha r_ui) x_u          over the users with r_ui > 0

with ``X^T X`` over **all** user rows (every user holds an opinion of every
item: preference 0 at confidence 1) and ``n_i`` the count of positives.
That ``lambda`` is scaled by ``n_i`` is MLlib's ``ALS.trainImplicit``
(which Apache PredictionIO's templates call), a departure from the paper's
plain ``lambda``; the program states the same. For each sampled item of the
half-sweep that ran last, a stored item row must be that solution given the
stored user rows. The half-sweep before it solved the user rows the same way
from item rows no one kept; a stored user row is held to its solution given
the *stored* item rows, one item update away (``compare_user_side``).

Each comparison is also made on the *controls* the configuration lists
(``check.train_controls``): the same reference with every product (the
weighted Gramian, the right-hand side and ``X^T X``) rounded as a lower
matmul precision would round it (``p1``: one bf16 pass,
``Precision.DEFAULT``; ``p3``: three, ``Precision.HIGH``), put in the
program's place. Each control must come out over one of the limits in the
run itself, or the comparison has lost its teeth and the run is not correct.
"""

from __future__ import annotations

import numpy as np

from benchmark.references.als import Checks, _row_errors, load_models, matmul_passes


def gram_all(x: np.ndarray, block: int, how: str) -> np.ndarray:
    """``X^T X`` over every row of ``x`` [N, K], a block of rows at a time:
    float64, or float32 sums of products in 1 or 3 bf16 passes."""
    k = x.shape[1]
    g = np.zeros((k, k), np.float64 if how == "f64" else np.float32)
    for s in range(0, x.shape[0], block):
        xb = x[s:s + block]
        if how == "f64":
            x64 = xb.astype(np.float64)
            g += x64.T @ x64
        else:
            g += matmul_passes(xb.T, xb, int(how[1]))
    return g


def solve_rows(x_rows: list, r_rows: list, gram: np.ndarray, lam: float,
               alpha: float, how: str) -> np.ndarray:
    """The Hu-Koren-Volinsky solution of each sampled row from its users'
    rows ``x`` [n, K] and counts ``r`` [n]. ``how``: "f64" the reference;
    "p1"/"p3" the control (products in 1 or 3 bf16 passes, float32 solve)."""
    out = []
    for x, r in zip(x_rows, r_rows):
        k = x.shape[1]
        pos = r > 0
        n = max(int(pos.sum()), 1)
        if how == "f64":
            x64, w = x.astype(np.float64), alpha * np.abs(r.astype(np.float64))
            a = gram + (x64.T * w) @ x64 + lam * n * np.eye(k)
            out.append(np.linalg.solve(a, x64.T @ ((1.0 + w) * pos)))
        else:
            passes = int(how[1])
            w = np.float32(alpha) * np.abs(r.astype(np.float32))
            a = (gram + matmul_passes((x * w[:, None]).T, x, passes)
                 + np.float32(lam * n) * np.eye(k, dtype=np.float32))
            b = matmul_passes(x.T, ((1.0 + w) * pos).astype(np.float32)[:, None],
                              passes)[:, 0]
            out.append(np.linalg.solve(a, b).astype(np.float64))
    return np.stack(out)


def check_train(run, events: dict, instance: dict, blob: bytes) -> bool:
    cfg, c = run.config, Checks(run.say)
    model, shape = cfg["model"], cfg["shape"]
    als, dev = instance["kernels"]["als"], instance["device"]
    for key, want in cfg["expect"].items():
        got = dev.get(key) if key in ("platform",) else als.get(key)
        c.equal(f"instance {key}", got, want)
    c.equal("sweeps run", len(als.get("sweepSeconds", [])), model["iterations"])
    c.equal("instance positiveEntries", als.get("positiveEntries"),
            int(np.count_nonzero(events["vals"] > 0)))
    if not c.ok:  # another job than the cell's: its model is not opened
        return False

    m = load_models(blob)[0]
    user = np.asarray(m.user_factors, np.float32)
    item = np.asarray(m.item_factors, np.float32)
    # the model holds the entities the events name: at a cut scale a few of
    # the least-listened songs have no triplet, and no row
    users = np.flatnonzero(np.bincount(events["rows"], minlength=shape["users"]))
    items = np.flatnonzero(np.bincount(events["cols"], minlength=shape["items"]))
    c.equal("stored factor shapes", [list(user.shape), list(item.shape)],
            [[users.size, model["rank"]], [items.size, model["rank"]]])
    finite = bool(np.isfinite(user).all() and np.isfinite(item).all())
    c.equal("stored factors finite", finite, True)
    if not c.ok:
        return False

    def by_code(table, index, codes, n):  # the events' codes; zero rows where absent
        out = np.zeros((n, table.shape[1]), np.float32)
        out[codes] = table[[index[str(i)] for i in codes]]
        return out

    return compare_train(run, events, by_code(user, m.user_index, users, shape["users"]),
                         by_code(item, m.item_index, items, shape["items"]), c)


def _sampled(rng, own: np.ndarray, n_rows: int, heaviest: int):
    """A seeded sample of one side's codes that hold an event, with the
    ``heaviest`` that hold the most in it: their Gramians sum the most
    terms, so float32 accumulation shows there first. Returns the sample
    and every code's count of events."""
    counts = np.bincount(own)
    held = np.flatnonzero(counts)
    sample = np.unique(np.concatenate([
        rng.choice(held, min(n_rows, held.size), replace=False),
        np.argsort(-counts)[:heaviest],
    ]))
    return sample, counts


def _systems(own: np.ndarray, other: np.ndarray, vals: np.ndarray,
             table: np.ndarray, sample: np.ndarray) -> tuple[list, list]:
    """For each sampled code of ``own``: the other side's rows of ``table``
    it holds an event with, and those events' counts."""
    sel = np.nonzero(np.isin(own, sample))[0]
    order = sel[np.argsort(own[sel], kind="stable")]
    bounds = np.append(np.searchsorted(own[order], sample), order.size)
    spans = list(zip(bounds[:-1], bounds[1:]))
    return ([table[other[order[a:b]]] for a, b in spans],
            [vals[order[a:b]] for a, b in spans])


def compare_train(run, events: dict, user: np.ndarray, item: np.ndarray,
                  c: Checks) -> bool:
    """``user``/``item``: the stored tables in the events' own codes."""
    cfg = run.config
    model, lim, check = cfg["model"], cfg["limits"], cfg["check"]
    lam, alpha = float(model["lambda"]), float(model["alpha"])
    rows, cols, vals = events["rows"], events["cols"], events["vals"]
    block = int(check["gram_block"])
    rng = np.random.default_rng(run.seed + 7)
    # items are the side the last half-sweep solved: exact given the users
    sample, counts = _sampled(rng, cols, int(check["train_rows"]),
                              int(check["train_heaviest"]))
    x_rows, r_rows = _systems(cols, rows, vals, user, sample)
    ref = solve_rows(x_rows, r_rows, gram_all(user, block, "f64"), lam, alpha, "f64")
    err = _row_errors(item[sample].astype(np.float64), ref)
    worst = np.argsort(-err)[:5]
    run.say(f"rows: {sample.size} item rows against their float64 Hu-Koren-Volinsky "
            f"normal equations (X^T X over {user.shape[0]:,} user rows); relative L2 "
            f"error median {np.median(err):.3g} p90 {np.quantile(err, 0.9):.3g}; worst "
            f"(listeners, largest count, error): "
            f"{[(int(counts[sample[j]]), int(r_rows[j].max(initial=0)), float(f'{err[j]:.3g}')) for j in worst]}")
    c.leq("median item row error", float(np.median(err)), lim["train_median_row_err"])
    c.leq("worst item row error", float(err.max()), lim["train_worst_row_err"])
    # the controls, on the same rows: not correct by one of the limits
    for name in check["train_controls"]:
        ec = _row_errors(
            solve_rows(x_rows, r_rows, gram_all(user, block, name), lam, alpha, name), ref)
        median, most = float(np.median(ec)), float(ec.max())
        by_median = not (np.isfinite(median) and median <= lim["train_median_row_err"])
        by_worst = not (np.isfinite(most) and most <= lim["train_worst_row_err"])
        run.say(f"control the reference in {name}: median item row error {median:.6g} > "
                f"{lim['train_median_row_err']:g} -> {by_median}; worst {most:.6g} > "
                f"{lim['train_worst_row_err']:g} -> {by_worst}: "
                + ("fails as it must" if by_median or by_worst
                   else "PASSED: the comparison has no teeth"))
        c.ok &= by_median or by_worst
    return compare_user_side(run, events, user, item, c)


def compare_user_side(run, events: dict, user: np.ndarray, item: np.ndarray,
                      c: Checks) -> bool:
    """The half-sweep before the last. The stored user rows were solved from
    the item rows of the sweep before, which no one kept, so they cannot be
    held to an exact solution; they are held to the solution given the
    *stored* item rows, one item update away: the median distance of a
    sampled user row from it is what a sweep still moves at the job's end.
    A user table that was never solved (its unit-norm seed), or solved from
    something else, is as far from it as a random row: the control, a table
    seeded as the program seeds its own and put in the user rows' place,
    must come out over the limit in the run itself."""
    cfg = run.config
    model, lim, check = cfg["model"], cfg["limits"], cfg["check"]
    lam, alpha = float(model["lambda"]), float(model["alpha"])
    rng = np.random.default_rng(run.seed + 8)
    sample, counts = _sampled(rng, events["rows"], int(check["train_rows"]),
                              int(check["train_heaviest"]))
    y_rows, r_rows = _systems(events["rows"], events["cols"], events["vals"], item, sample)
    ref = solve_rows(y_rows, r_rows, gram_all(item, int(check["gram_block"]), "f64"),
                     lam, alpha, "f64")
    step = _row_errors(user[sample].astype(np.float64), ref)
    worst = np.argsort(-step)[:5]
    run.say(f"rows: {sample.size} user rows against their float64 solutions given the "
            f"stored item rows (one item update on); relative L2 distance median "
            f"{np.median(step):.3g} p90 {np.quantile(step, 0.9):.3g}; farthest (songs, "
            f"largest count, distance): "
            f"{[(int(counts[sample[j]]), int(r_rows[j].max(initial=0)), float(f'{step[j]:.3g}')) for j in worst]}")
    c.leq("median user row distance from its solution", float(np.median(step)),
          lim["train_median_user_step"])
    seed_rows = np.abs(rng.standard_normal((sample.size, user.shape[1])))
    seed_rows /= np.linalg.norm(seed_rows, axis=1, keepdims=True)
    c.control("user rows never solved (a unit-norm seed in their place): median distance",
              float(np.median(_row_errors(seed_rows, ref))), lim["train_median_user_step"])
    return c.ok
