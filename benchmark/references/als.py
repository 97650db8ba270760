"""The plain reference of the ALS configurations, and the comparison that
decides ``correct``. Imports nothing of ``predictionio_tpu/ops``: numpy in
float64, from the events and factor tables the benchmark itself made and
the blob the program stored.

Train: ALS-WR solves, for each item ``i`` of the half-sweep that ran last,
``(X_R^T X_R + lambda * n_i * I) y = X_R^T r`` over the ``n_i`` users who
rated it, with the user factors as stored. A stored item row must be that
solution. Serve: a score is ``v_i . u`` and the answer the ``num`` best.

Each comparison is also made on a *control*: the same reference with its
products rounded as a lower matmul precision would round them (three bf16
passes, ``Precision.HIGH``; one bf16 pass, ``Precision.DEFAULT``), put in
the program's place. The control must come out over the limit in the run
itself, or the comparison has lost its teeth and the run is not correct.
"""

from __future__ import annotations

import io
import json
import pickle

import numpy as np

_MAGIC = b"PIOTPU1\x00"


def bf16(x: np.ndarray) -> np.ndarray:
    """Round float32 to bfloat16 (nearest even), kept as float32."""
    b = np.array(x, np.float32, order="C").view(np.uint32)  # a copy, worked on in place
    odd = (b >> 16) & 1
    odd += 0x7FFF
    b += odd
    b &= 0xFFFF0000
    return b.view(np.float32)


def matmul_passes(a: np.ndarray, b: np.ndarray, passes: int) -> np.ndarray:
    """``a @ b`` as the MXU forms a float32 product in 1 or 3 bf16 passes:
    operands split into bf16 high and low parts, partial products summed in
    float32. (Six passes, HIGHEST, is float32 itself to the last bits.)"""
    ah, bh = bf16(a), bf16(b)
    if passes == 1:
        return ah @ bh
    if passes == 3:
        al, bl = bf16(a - ah), bf16(b - bh)
        return ah @ bh + (ah @ bl + al @ bh)
    raise ValueError("passes is 1 or 3")


def load_models(blob: bytes) -> list:
    """The stored blob without the classes that import jax: template model
    classes unpickle into plain attribute bags."""

    class Bag:
        pass

    class Unpickler(pickle.Unpickler):
        def find_class(self, module, name):
            if module.startswith("predictionio_tpu.templates."):
                return Bag
            return super().find_class(module, name)

    if not blob.startswith(_MAGIC):
        raise ValueError("model blob has no PIOTPU1 magic")
    return [m for _, m in Unpickler(io.BytesIO(blob[len(_MAGIC):])).load()]


class Checks:
    """Prints each number compared beside its limit; ``ok`` is their AND."""

    def __init__(self, say):
        self.say, self.ok = say, True

    def leq(self, what: str, value: float, limit: float) -> bool:
        good = bool(np.isfinite(value)) and value <= limit
        self.say(f"check {what}: {value:.6g} <= {limit:g} -> {'ok' if good else 'FAILED'}")
        self.ok &= good
        return good

    def geq(self, what: str, value: float, limit: float) -> bool:
        good = value >= limit
        self.say(f"check {what}: {value:g} >= {limit:g} -> {'ok' if good else 'FAILED'}")
        self.ok &= good
        return good

    def equal(self, what: str, got, want) -> bool:
        good = got == want
        self.say(f"check {what}: {got!r} == {want!r} -> {'ok' if good else 'FAILED'}")
        self.ok &= good
        return good

    def control(self, what: str, value: float, limit: float) -> bool:
        good = not (np.isfinite(value) and value <= limit)
        self.say(f"control {what}: {value:.6g} > {limit:g} -> "
                 f"{'fails as it must' if good else 'PASSED: the comparison has no teeth'}")
        self.ok &= good
        return good


# --------------------------------------------------------------------- train


def _solve_rows(x_rows: list, r_rows: list, lam: float, how: str) -> np.ndarray:
    """The ALS-WR solution of each sampled row. ``how``: "f64" the
    reference; "p1"/"p3" the control (Gramian and right-hand side in 1 or 3
    bf16 passes, float32 solve)."""
    out = []
    for x, r in zip(x_rows, r_rows):
        n = max(x.shape[0], 1)
        k = x.shape[1]
        if how == "f64":
            x64 = x.astype(np.float64)
            a = x64.T @ x64 + lam * n * np.eye(k)
            out.append(np.linalg.solve(a, x64.T @ r.astype(np.float64)))
        else:
            passes = int(how[1])
            a = matmul_passes(x.T, x, passes) + np.float32(lam * n) * np.eye(k, dtype=np.float32)
            b = matmul_passes(x.T, r[:, None], passes)[:, 0]
            out.append(np.linalg.solve(a, b).astype(np.float64))
    return np.stack(out)


def _row_errors(got: np.ndarray, ref: np.ndarray) -> np.ndarray:
    return np.linalg.norm(got - ref, axis=1) / np.maximum(
        np.linalg.norm(ref, axis=1), 1e-30)


def check_train(run, events: dict, instance: dict, blob: bytes) -> bool:
    cfg, c = run.config, Checks(run.say)
    model, shape, lim = cfg["model"], cfg["shape"], cfg["limits"]
    als, dev = instance["kernels"]["als"], instance["device"]
    for key, want in cfg["expect"].items():
        got = dev.get(key) if key in ("platform",) else als.get(key)
        c.equal(f"instance {key}", got, want)
    c.equal("sweeps run", len(als.get("sweepSeconds", [])), model["iterations"])

    m = load_models(blob)[0]
    user = np.asarray(m.user_factors, np.float32)
    item = np.asarray(m.item_factors, np.float32)
    c.equal("stored factor shapes", [list(user.shape), list(item.shape)],
            [[shape["users"], model["rank"]], [shape["items"], model["rank"]]])
    finite = bool(np.isfinite(user).all() and np.isfinite(item).all())
    c.equal("stored factors finite", finite, True)
    if not c.ok:
        return False

    user_row = np.asarray([m.user_index[str(u)] for u in range(shape["users"])])
    item_row = np.asarray([m.item_index[str(i)] for i in range(shape["items"])])
    return compare_train(run, events, user[user_row], item[item_row], c)


def compare_train(run, events: dict, user: np.ndarray, item: np.ndarray,
                  c: Checks) -> bool:
    """``user``/``item``: the stored tables in the events' own codes."""
    cfg = run.config
    model, shape, lim = cfg["model"], cfg["shape"], cfg["limits"]
    # a seeded sample of item rows (items are the side the last half-sweep
    # solved), with the most-rated items in it: their Gramians sum the most
    # terms, so float32 accumulation shows there first
    rng = np.random.default_rng(run.seed + 7)
    counts = np.bincount(events["cols"], minlength=shape["items"])
    n_rows = min(int(cfg["check"]["train_rows"]), shape["items"])
    sample = np.unique(np.concatenate([
        rng.choice(shape["items"], n_rows, replace=False),
        np.argsort(-counts)[: int(cfg["check"]["train_heaviest"])],
    ]))
    sel = np.nonzero(np.isin(events["cols"], sample))[0]
    order = sel[np.argsort(events["cols"][sel], kind="stable")]
    bounds = np.searchsorted(events["cols"][order], sample)
    bounds = np.append(bounds, order.size)
    x_rows = [user[events["rows"][order[a:b]]]
              for a, b in zip(bounds[:-1], bounds[1:])]
    r_rows = [events["vals"][order[a:b]] for a, b in zip(bounds[:-1], bounds[1:])]
    ref = _solve_rows(x_rows, r_rows, model["lambda"], "f64")
    err = _row_errors(item[sample].astype(np.float64), ref)
    worst = np.argsort(-err)[:5]
    run.say(f"rows: {sample.size} item rows against their float64 normal "
            f"equations; relative L2 error median {np.median(err):.3g} "
            f"p90 {np.quantile(err, 0.9):.3g}; worst (ratings, error): "
            f"{[(int(counts[sample[j]]), float(f'{err[j]:.3g}')) for j in worst]}")
    c.leq("median item row error", float(np.median(err)), lim["train_median_row_err"])
    c.leq("worst item row error", float(err.max()), lim["train_worst_row_err"])
    # the control, on the same rows
    name = cfg["check"]["train_control"]
    ec = _row_errors(_solve_rows(x_rows, r_rows, model["lambda"], name), ref)
    run.say(f"control reading ({name}): median {np.median(ec):.3g} worst {ec.max():.3g}")
    c.control(f"median item row error of the reference in {name}",
              float(np.median(ec)), lim["train_median_row_err"])
    return c.ok


# --------------------------------------------------------------------- serve


def answer_shape_ok(payload, num: int, n_items: int) -> bool:
    """Every answer: ``num`` distinct known items, finite scores, descending."""
    try:
        scores = payload["itemScores"]
        ids = [int(s["item"]) for s in scores]
        vals = [float(s["score"]) for s in scores]
    except (KeyError, TypeError, ValueError):
        return False
    return (len(ids) == num and len(set(ids)) == num
            and all(0 <= i < n_items for i in ids)
            and all(np.isfinite(v) for v in vals)
            and all(a >= b for a, b in zip(vals, vals[1:])))


def check_serve(run, user: np.ndarray, item: np.ndarray, sample: list) -> bool:
    """``sample``: [(user code, payload)] of answered queries. Tables are
    the ones the benchmark published; ids are their row numbers.

    Works through the sample in blocks of queries over buffers made once:
    the catalog-wide score rows are gigabytes, and fresh pages are slow on
    the sandboxed hosts this runs on."""
    cfg, c = run.config, Checks(run.say)
    lim, num = cfg["limits"], int(run.traffic["num"])
    c.geq("answers compared", len(sample), int(cfg["check"]["serve_queries"]))
    if not sample:
        return False
    rel, abs_ = lim["serve_tol_rel"], lim["serve_tol_abs"]
    name = cfg["check"]["serve_control"]
    n_items, block = item.shape[0], 32
    ids = np.asarray([[int(x["item"]) for x in p["itemScores"]] for _, p in sample])
    got = np.asarray([[x["score"] for x in p["itemScores"]] for _, p in sample], np.float64)
    u_all = user[[code for code, _ in sample]]  # [B, K] float32
    item64_t = np.ascontiguousarray(item.T, np.float64)  # [K, I]
    ih = bf16(item)
    ih_t, il_t = np.ascontiguousarray(ih.T), np.ascontiguousarray(bf16(item - ih).T)
    del ih
    ref = np.empty((block, n_items), np.float64)
    work64 = np.empty_like(ref)
    ctl = {k: np.empty((block, n_items), np.float32) for k in ("p1", "p3", "tmp", "work")}

    def scale(u: np.ndarray, which: np.ndarray) -> np.ndarray:
        """sum_k |u_k v_ik| at the items ``which`` [b, n] of each query."""
        return np.einsum("bk,bnk->bn", np.abs(u), np.abs(item[which])).astype(np.float64)

    sq = {"served": 0.0, "p1": 0.0, "p3": 0.0}
    worst = dict(sq)
    rank_ok, count = True, 0
    for s in range(0, len(sample), block):
        u = u_all[s:s + block]
        b = u.shape[0]
        rows = np.arange(b)[:, None]
        np.matmul(u.astype(np.float64), item64_t, out=ref[:b])
        np.copyto(work64[:b], ref[:b])
        work64[:b].partition(n_items - num, axis=1)
        kth = work64[:b, n_items - num]  # the reference's num-th best score

        def errors(key: str, which: np.ndarray, scores: np.ndarray) -> np.ndarray:
            sc = scale(u, which)
            e = np.abs(scores - ref[rows, which])
            worst[key] = max(worst[key], float(np.max(e / (rel * sc + abs_))))
            sq[key] += float(np.sum((e / sc) ** 2))
            return rel * sc + abs_

        tol = errors("served", ids[s:s + b], got[s:s + b])
        count += b * num
        # ranking beyond ties: every served item within tolerance of the
        # reference's num-th best, in non-increasing reference order
        served_ref = ref[rows, ids[s:s + b]]
        rank_ok &= bool(np.all(served_ref >= kth[:, None] - tol)
                        and np.all(np.diff(served_ref, axis=1) <= tol[:, 1:] + tol[:, :-1]))
        # the control: the reference's own answers in lower precision
        uh = bf16(u)
        np.matmul(uh, ih_t, out=ctl["p1"][:b])
        np.matmul(uh, il_t, out=ctl["p3"][:b])
        np.matmul(bf16(u - uh), ih_t, out=ctl["tmp"][:b])
        ctl["p3"][:b] += ctl["tmp"][:b]
        ctl["p3"][:b] += ctl["p1"][:b]
        for key in ("p1", "p3") if name == "p3" else ("p1",):
            sc = ctl[key][:b]
            np.copyto(ctl["work"][:b], sc)
            ctl["work"][:b].partition(n_items - num, axis=1)
            thr = ctl["work"][:b, n_items - num]
            top = np.stack([np.flatnonzero(sc[j] >= thr[j])[:num] for j in range(b)])
            errors(key, top, sc[rows, top].astype(np.float64))
    rms = {k: float(np.sqrt(v / count)) for k, v in sq.items()}
    c.leq("worst served score error over its tolerance "
          f"({rel:g} * sum_k|u_k v_ik| + {abs_:g})", worst["served"], 1.0)
    c.leq("rms served score error over sum_k|u_k v_ik|", rms["served"],
          lim["serve_rms_rel_err"])
    c.equal("served sets and order match the reference beyond ties", rank_ok, True)
    run.say("control readings: " + "; ".join(
        f"{k} worst/tol {worst[k]:.3g} rms {rms[k]:.3g}"
        for k in (("p1", "p3") if name == "p3" else ("p1",))))
    c.control(f"rms score error of the reference in {name}", rms[name],
              lim["serve_rms_rel_err"])
    return c.ok
