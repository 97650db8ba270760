"""The plain reference of the Similar Product deployment, and the comparison
that decides ``correct``. Imports nothing of ``predictionio_tpu/ops`` or
``templates``: numpy in float64 over the item table, the category codes and
the queries the benchmark itself made.

For a query with known items ``Q`` (rows ``v_q`` of the stored table, each
of unit length):

    t = sum_{q in Q} v_q          u = t / |t|          score(i) = v_i . u

all in float64 from the float32 rows. Item ``i`` is allowed when it is not
in ``Q``, it is not on the query's black list, and it carries one of the
query's categories (or the query names none). The answer is the ``num``
best allowed items by (descending score, ascending id), fewer where fewer
are allowed. (Upstream sums the cosines item by item: ``sum_q v_i . v_q``
= ``|t|`` times this score, the same order.)

That is the e-commerce reference's rule with the query's own items where a
user's seen items stand, nothing out of stock, and ``u`` where a user row
stands: the block-wise top-K and the comparison are
``references/ecom.py``'s, given those. So the limits are its limits: the
worst served score error over ``rel * sum_k |u_k v_ik| + abs`` at most 1,
the rms error over ``sum_k |u_k v_ik|`` at most ``serve_rms_rel_err``, set
and order equal beyond ties, **0** served items that break a rule of their
query (a query item, a black-listed id, a wrong category), **0** answers
that do not hold ``min(num, allowed)`` items, and the control (the
reference's own answers scored as three bf16 passes) over the rms limit.

What ``u`` adds to the tolerance. The engine makes ``u`` in float32: at most
7 additions, a norm and a division, each rounding a component by at most
2^-24 of its size, so ``|du_k| <= 10 * 6e-8 * |u_k|`` at the very worst and
the served score is off by at most ``6e-7 * sum_k |u_k v_ik|`` on that
account: 1.2% of the worst-error tolerance (``5e-5 *`` the same sum). In the
rms the roundings of 64 components cancel like a random walk: about
``6e-8 / sqrt(64) * sqrt(10)`` = 2.4e-8 of the sum, beside the 4e-8 the
float32 product itself reads and under the limit of 2e-7 (three bf16 passes
read 9e-7, one pass 5e-4: PERF.md has the chip's readings).
"""

from __future__ import annotations

import numpy as np

from benchmark.references import ecom
from benchmark.references.als import Checks

answer_shape_ok = ecom.answer_shape_ok

_NOTHING = np.zeros(0, np.int64)


def query_vectors(item: np.ndarray, queries: list) -> np.ndarray:
    """``u`` of each query, ``f64[queries, rank]``: the float64 sum of its
    items' rows over that sum's length."""
    out = np.zeros((len(queries), item.shape[1]), np.float64)
    for row, q in zip(out, queries):
        t = item[np.asarray(q["items"], np.int64)].astype(np.float64).sum(axis=0)
        row[:] = t / np.sqrt(t @ t)
    return out


def _as_ecom(queries: list) -> list:
    """The queries as ``references/ecom.py`` takes rules: what a query
    leaves out beside its black list is its own items."""
    return [{"seen": q["items"], "black": q["black"], "wanted": q["wanted"]}
            for q in queries]


def reference_topk(item: np.ndarray, codes: np.ndarray, queries: list, num: int,
                   block: int = ecom.BLOCK) -> tuple[list, list, np.ndarray]:
    """Per query the ``num`` best allowed items (ids, float64 scores; fewer
    where fewer are allowed) and how many items it is allowed at all. A
    query is a dict of ``items``, ``black`` (item ids) and ``wanted``
    (category codes, empty = none asked)."""
    return ecom.reference_topk(query_vectors(item, queries), item, codes,
                               _as_ecom(queries), _NOTHING, num, block)


def compare_serve(say, limits: dict, control: str, num: int, item: np.ndarray,
                  codes: np.ndarray, queries: list, answers: list) -> bool:
    """``answers``: per query (served ids, served scores). Prints each number
    compared beside its limit; True when all hold and the control fails."""
    return ecom.compare_serve(say, limits, control, num, query_vectors(item, queries),
                              item, codes, _as_ecom(queries), _NOTHING, answers)


def check_serve(run, user, item: np.ndarray, sample: list) -> bool:
    """``sample``: [(query number, payload)] of answered queries; the
    queries and the category codes are the ones the kind made and kept on
    ``run.deployment``. ``user`` is unused: this model has no user table."""
    cfg, dep = run.config, run.deployment
    c = Checks(run.say)
    c.geq("answers compared", len(sample), int(cfg["check"]["serve_queries"]))
    if not sample:
        return False
    answers = [([int(x["item"]) for x in p["itemScores"]],
                [x["score"] for x in p["itemScores"]]) for _, p in sample]
    ok = compare_serve(
        run.say, cfg["limits"], cfg["check"]["serve_control"], int(run.traffic["num"]),
        item, dep["codes"], [dep["rules"](n) for n, _ in sample], answers)
    return ok and c.ok
