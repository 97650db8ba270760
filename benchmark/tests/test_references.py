"""Both references at toy size on the CPU: a float32 run passes, the same
run in one bf16 pass fails, for serve and for train. No program involved:
the stand-in for the program is plain float32 numpy."""

import numpy as np
import pytest

from benchmark import data
from benchmark.references import als


class FakeRun:
    def __init__(self, config, traffic=None, seed=11):
        self.config, self.traffic, self.seed = config, traffic or {"num": 10}, seed
        self.lines = []

    def say(self, msg):
        self.lines.append(msg)


CONFIG = {
    "shape": {"users": 500, "items": 200, "ratings": 20000, "user_floor": 20,
              "user_sigma": 0.5, "item_exponent": 0.8, "structure_seed": 1},
    "model": {"rank": 16, "iterations": 1, "lambda": 0.05},
    "check": {"train_rows": 64, "train_heaviest": 4, "serve_queries": 32, "train_control": "p1", "serve_control": "p3"},
    "limits": {"train_median_row_err": 1e-4, "train_worst_row_err": 2.5e-2,
               "serve_tol_rel": 5e-5, "serve_tol_abs": 1e-6, "serve_rms_rel_err": 2e-7},
}


def _item_rows(events, user, lam, passes):
    """The item half-sweep, in float32 (passes 0) or with the Gramian in
    1 or 3 bf16 passes: what the program's sweep computes."""
    n_items = CONFIG["shape"]["items"]
    k = user.shape[1]
    out = np.zeros((n_items, k), np.float32)
    order = np.argsort(events["cols"], kind="stable")
    bounds = np.searchsorted(events["cols"][order], np.arange(n_items + 1))
    for i in range(n_items):
        sel = order[bounds[i]:bounds[i + 1]]
        x, r = user[events["rows"][sel]], events["vals"][sel]
        if passes:
            a = als.matmul_passes(x.T, x, passes)
            b = als.matmul_passes(x.T, r[:, None], passes)[:, 0]
        else:
            a, b = x.T @ x, x.T @ r
        a = a + np.float32(lam * max(len(sel), 1)) * np.eye(k, dtype=np.float32)
        out[i] = np.linalg.solve(a, b)
    return out


@pytest.fixture(scope="module")
def trained():
    events = data.rating_events(CONFIG["shape"], 5)
    rng = np.random.default_rng(0)
    user = (rng.standard_normal((500, 16)) * 0.3).astype(np.float32)
    return events, user


def test_train_reference_passes_float32_and_fails_bf16(trained):
    events, user = trained
    run = FakeRun(CONFIG)
    ok = als.compare_train(run, events, user, _item_rows(events, user, 0.05, 0),
                           als.Checks(run.say))
    assert ok, run.lines
    assert any("fails as it must" in line for line in run.lines)
    run = FakeRun(CONFIG)
    bad = als.compare_train(run, events, user, _item_rows(events, user, 0.05, 1),
                            als.Checks(run.say))
    assert not bad
    assert any("median item row error" in line and "FAILED" in line for line in run.lines)


def test_train_reference_catches_an_unchanged_state(trained):
    events, user = trained
    rng = np.random.default_rng(1)
    stale = (rng.standard_normal((200, 16)) * 0.3).astype(np.float32)  # never solved
    run = FakeRun(CONFIG)
    assert not als.compare_train(run, events, user, stale, als.Checks(run.say))


def _answers(user, item, codes, passes, num=10):
    out = []
    for code in codes:
        s = (item @ user[code] if not passes
             else als.matmul_passes(item, user[code][:, None], passes)[:, 0])
        top = np.argsort(-s, kind="stable")[:num]
        out.append((int(code), {"itemScores": [
            {"item": str(int(i)), "score": float(s[i])} for i in top]}))
    return out


def test_serve_reference_passes_float32_and_fails_bf16():
    user, item = data.factor_tables({"users": 300, "items": 4000, "rank": 64}, 9)
    codes = np.arange(40)
    run = FakeRun(CONFIG)
    assert als.check_serve(run, user, item, _answers(user, item, codes, 0)), run.lines
    assert any("fails as it must" in line for line in run.lines)
    # one bf16 pass (Precision.DEFAULT) fails both numbers
    run = FakeRun(CONFIG)
    assert not als.check_serve(run, user, item, _answers(user, item, codes, 1))
    assert any("worst served score error" in line and "FAILED" in line for line in run.lines)
    # three passes (Precision.HIGH), the step below the stated HIGHEST, stay
    # inside the per-score tolerance and fail the rms limit
    run = FakeRun(CONFIG)
    assert not als.check_serve(run, user, item, _answers(user, item, codes, 3))
    assert any("worst served score error" in line and "-> ok" in line for line in run.lines)
    assert any("rms served score error" in line and "FAILED" in line for line in run.lines)


def test_serve_reference_wants_enough_answers_and_the_right_set():
    user, item = data.factor_tables({"users": 300, "items": 4000, "rank": 64}, 9)
    run = FakeRun(CONFIG)
    assert not als.check_serve(run, user, item, _answers(user, item, np.arange(8), 0))
    # a wrong item in an otherwise well-formed answer
    answers = _answers(user, item, np.arange(40), 0)
    worst = int(np.argmin(item @ user[0]))
    answers[0][1]["itemScores"][-1] = {
        "item": str(worst), "score": float(item[worst] @ user[0])}
    run = FakeRun(CONFIG)
    assert not als.check_serve(run, user, item, answers)


def test_answer_shape():
    good = {"itemScores": [{"item": "3", "score": 2.0}, {"item": "1", "score": 1.0}]}
    assert als.answer_shape_ok(good, 2, 10)
    assert not als.answer_shape_ok(good, 3, 10)  # too few
    assert not als.answer_shape_ok(good, 2, 3)  # unknown item
    assert not als.answer_shape_ok({"itemScores": good["itemScores"][::-1]}, 2, 10)
    assert not als.answer_shape_ok({"message": "no"}, 2, 10)
    twice = {"itemScores": [{"item": "3", "score": 2.0}, {"item": "3", "score": 1.0}]}
    assert not als.answer_shape_ok(twice, 2, 10)


def test_generator_gives_distinct_pairs_at_the_published_counts():
    shape = CONFIG["shape"]
    a, b = data.rating_events(shape, 5), data.rating_events(shape, 6)
    for ev in (a, b):
        assert ev["rows"].size == shape["ratings"]
        assert np.unique(ev["rows"].astype(np.int64) * shape["items"] + ev["cols"]).size \
            == shape["ratings"]
        assert np.bincount(ev["rows"], minlength=shape["users"]).min() >= shape["user_floor"]
        assert set(np.unique(ev["vals"])) <= {x / 2 for x in range(1, 11)}
    # another seed: the same pairs in another order with other values, so
    # the same sizes row by row on both sides (and the same bucket shapes)
    assert not np.array_equal(a["rows"], b["rows"])
    assert not np.array_equal(np.sort(a["vals"]), np.sort(b["vals"])) \
        or not np.array_equal(a["vals"], b["vals"])
    for key, n in (("rows", shape["users"]), ("cols", shape["items"])):
        assert np.array_equal(np.bincount(a[key], minlength=n),
                              np.bincount(b[key], minlength=n))
    same = data.rating_events(shape, 5)
    assert all(np.array_equal(a[k], same[k]) for k in a)
