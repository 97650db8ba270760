"""The plain reference of the E-Commerce Recommendation deployment, and the
comparison that decides ``correct``. Imports nothing of
``predictionio_tpu/ops``: numpy in float64 over the tables, the category
codes, the histories and the constraint the benchmark itself made.

A score is ``v_i . u``. An item is allowed to a query when the user has not
seen it (no ``view`` / ``buy`` event), it is not in the ``unavailableItems``
constraint, it is not on the query's black list, and it carries one of the
query's categories (or the query names none). The answer is the ``num``
best allowed items by (descending score, ascending id), fewer where fewer
are allowed. Worked through in blocks of items: a float64 copy of a
15.5 M x 64 table is 8 GB.

The served scores are held to the limits of ``als`` (worst error over
``rel * sum_k |u_k v_ik| + abs``, rms error over ``sum_k |u_k v_ik|``, set
and order equal beyond ties), the *control* is the reference's own answers
scored as three bf16 passes (``Precision.HIGH``) and must come out over the
rms limit, and two comparisons are exact: no served item breaks a rule of
its query, and an answer holds ``min(num, allowed)`` items.
"""

from __future__ import annotations

import numpy as np

from benchmark.references.als import Checks, bf16

#: items scored at once: [queries, block] float64 and its masks
BLOCK = 1 << 18


def answer_shape_ok(payload, num: int, n_items: int) -> bool:
    """Every answer: 1 to ``num`` distinct known items, finite scores,
    descending. (How many exactly is the sampled comparison's.)"""
    try:
        scores = payload["itemScores"]
        ids = [int(s["item"]) for s in scores]
        vals = [float(s["score"]) for s in scores]
    except (KeyError, TypeError, ValueError):
        return False
    return (1 <= len(ids) <= num and len(set(ids)) == len(ids)
            and all(0 <= i < n_items for i in ids)
            and all(np.isfinite(v) for v in vals)
            and all(a >= b for a, b in zip(vals, vals[1:])))


def allowed_block(block: np.ndarray, lo: int, n_codes: int, queries: list,
                  unavailable: np.ndarray) -> np.ndarray:
    """``bool[queries, len(block)]``: which items of the block (``block`` =
    ``codes[lo:hi]``, int[., C], ``-1`` = none, codes under ``n_codes``)
    each query may be given. A query is a dict of ``seen``, ``black`` (item
    ids) and ``wanted`` (category codes, empty = none asked)."""
    hi = lo + block.shape[0]
    out = np.ones((len(queries), block.shape[0]), dtype=bool)
    gone = unavailable[(unavailable >= lo) & (unavailable < hi)] - lo
    for q, row in zip(queries, out):
        wanted = np.asarray(q["wanted"], np.int64)
        if wanted.size:
            table = np.zeros(n_codes + 1, dtype=bool)  # the last row: "none"
            table[wanted[wanted < n_codes]] = True
            row &= table[block].any(axis=1)  # the block's -1 reads the last row
        for ids in (q["seen"], q["black"]):
            ids = np.asarray(ids, np.int64)
            row[ids[(ids >= lo) & (ids < hi)] - lo] = False
        row[gone] = False
    return out


def reference_topk(user_rows: np.ndarray, item: np.ndarray, codes: np.ndarray,
                   queries: list, unavailable: np.ndarray, num: int,
                   block: int = BLOCK) -> tuple[list, list, np.ndarray]:
    """Per query the ``num`` best allowed items (ids, float64 scores; fewer
    where fewer are allowed) and how many items it is allowed at all."""
    n_q, n_items = len(queries), item.shape[0]
    u64 = np.asarray(user_rows, np.float64)
    best_ids = [np.zeros(0, np.int64) for _ in range(n_q)]
    best_sc = [np.zeros(0, np.float64) for _ in range(n_q)]
    n_allowed = np.zeros(n_q, np.int64)
    n_codes = int(codes.max()) + 1
    unavailable = np.asarray(unavailable, np.int64)
    for lo in range(0, n_items, block):
        hi = min(lo + block, n_items)
        scores = u64 @ item[lo:hi].astype(np.float64).T
        ok = allowed_block(codes[lo:hi], lo, n_codes, queries, unavailable)
        n_allowed += ok.sum(axis=1)
        # what can still enter a query's top num: not under its num-th best
        floor = np.asarray([s[num - 1] if s.size >= num else -np.inf for s in best_sc])
        ok &= scores >= floor[:, None]
        rows, cols = np.nonzero(ok)
        bounds = np.searchsorted(rows, np.arange(n_q + 1))
        for q in range(n_q):
            c = cols[bounds[q]:bounds[q + 1]]
            if not c.size:
                continue
            s = scores[q, c]
            if c.size > 4 * num:  # the first blocks: nearly all of it
                part = np.argpartition(-s, num - 1)[:num]
                # ties at the cut keep the lowest ids
                tied = np.flatnonzero(s == s[part].min())
                part = np.union1d(part[s[part] > s[part].min()], tied)
                c, s = c[part], s[part]
            ids = np.concatenate([best_ids[q], c + lo])
            sc = np.concatenate([best_sc[q], s])
            order = np.lexsort((ids, -sc))[:num]
            best_ids[q], best_sc[q] = ids[order], sc[order]
    return best_ids, best_sc, n_allowed


def _scores_at(u: np.ndarray, item: np.ndarray, ids: np.ndarray, how: str) -> np.ndarray:
    """``u . v_i`` at ``ids``: float64, or as 1 / 3 bf16 passes sum it."""
    v = item[ids]
    if how == "f64":
        return v.astype(np.float64) @ u.astype(np.float64)
    uh, vh = bf16(u), bf16(v)
    out = vh @ uh
    if how == "p3":
        out = out + (vh @ bf16(u - uh) + bf16(v - vh) @ uh)
    return out.astype(np.float64)


def compare_serve(say, limits: dict, control: str, num: int, user_rows: np.ndarray,
                  item: np.ndarray, codes: np.ndarray, queries: list,
                  unavailable: np.ndarray, answers: list) -> bool:
    """``answers``: per query (served ids, served scores). Prints each number
    compared beside its limit; True when all hold and the control fails."""
    c = Checks(say)
    rel, abs_ = limits["serve_tol_rel"], limits["serve_tol_abs"]
    ref_ids, ref_sc, n_allowed = reference_topk(
        user_rows, item, codes, queries, unavailable, num)
    gone = set(np.asarray(unavailable).tolist())
    broken, wrong_count, rank_ok = 0, 0, True
    worst = {"served": 0.0, "p1": 0.0, "p3": 0.0}
    sq, count = dict.fromkeys(worst, 0.0), 0
    for q, (rules, (ids, got)) in enumerate(zip(queries, answers)):
        ids, got, u = np.asarray(ids, np.int64), np.asarray(got, np.float64), user_rows[q]
        wrong_count += len(ids) != min(num, int(n_allowed[q]))
        wanted = set(np.asarray(rules["wanted"]).tolist())
        out = gone | set(np.asarray(rules["seen"]).tolist()) | set(
            np.asarray(rules["black"]).tolist())
        broken += sum(
            1 for i in ids.tolist()
            if i in out or (wanted and not wanted.intersection(codes[i].tolist())))
        if not ids.size or not ref_ids[q].size:
            continue
        scale = np.abs(item[ids]).astype(np.float64) @ np.abs(u).astype(np.float64)
        tol = rel * scale + abs_
        ref = _scores_at(u, item, ids, "f64")
        err = np.abs(got - ref)
        worst["served"] = max(worst["served"], float(np.max(err / tol)))
        sq["served"] += float(np.sum((err / scale) ** 2))
        count += ids.size
        # ranking beyond ties: every served item within tolerance of the
        # reference's last, in non-increasing reference order
        rank_ok &= bool(np.all(ref >= ref_sc[q][-1] - tol)
                        and np.all(np.diff(ref) <= tol[1:] + tol[:-1]))
        # the control: the reference's own answer, scored in lower precision
        own = ref_ids[q]
        own_scale = np.abs(item[own]).astype(np.float64) @ np.abs(u).astype(np.float64)
        for how in ("p1", "p3"):
            e = np.abs(_scores_at(u, item, own, how) - ref_sc[q])
            worst[how] = max(worst[how], float(np.max(e / (rel * own_scale + abs_))))
            sq[how] += float(np.sum((e / own_scale) ** 2))
    rms = {k: float(np.sqrt(v / max(count, 1))) for k, v in sq.items()}
    c.equal("served items that break a rule of their query", int(broken), 0)
    c.equal("answers that do not hold min(num, allowed) items", int(wrong_count), 0)
    c.leq("worst served score error over its tolerance "
          f"({rel:g} * sum_k|u_k v_ik| + {abs_:g})", worst["served"], 1.0)
    c.leq("rms served score error over sum_k|u_k v_ik|", rms["served"],
          limits["serve_rms_rel_err"])
    c.equal("served sets and order match the reference beyond ties", rank_ok, True)
    say("control readings: " + "; ".join(
        f"{k} worst/tol {worst[k]:.3g} rms {rms[k]:.3g}" for k in ("p1", "p3")))
    c.control(f"rms score error of the reference in {control}", rms[control],
              limits["serve_rms_rel_err"])
    return c.ok


def check_serve(run, user: np.ndarray, item: np.ndarray, sample: list) -> bool:
    """``sample``: [(query number, payload)] of answered queries; the
    queries, histories, category codes and constraint are the ones the kind
    made and kept on ``run.deployment``."""
    cfg, dep = run.config, run.deployment
    c = Checks(run.say)
    c.geq("answers compared", len(sample), int(cfg["check"]["serve_queries"]))
    if not sample:
        return False
    numbers = [n for n, _ in sample]
    answers = [([int(x["item"]) for x in p["itemScores"]],
                [x["score"] for x in p["itemScores"]]) for _, p in sample]
    ok = compare_serve(
        run.say, cfg["limits"], cfg["check"]["serve_control"], int(run.traffic["num"]),
        user[dep["query_user"][numbers]], item, dep["codes"],
        [dep["rules"](n) for n in numbers], dep["unavailable"], answers)
    return ok and c.ok
