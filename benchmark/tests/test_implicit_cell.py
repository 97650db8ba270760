"""The implicit train kind end to end on the CPU: a toy play-count
configuration and its traffic ADDED to the temporary copy that
``toy.make_toy_root`` makes (the play-count generator, the kind's set-up and
priming, the float64 Hu-Koren-Volinsky reference and its two controls, the
``imp.*`` readers). The reference alone against plain float32 numpy and the
same in one and three bf16 passes, against a user side left unsolved or
solved from half its entries, and against an instance that does not say which
objective a job ran (the parent's)."""

import json
import os

import numpy as np
import pytest

from benchmark import playcounts
from benchmark.references import als, als_implicit
from benchmark.tests import toy

CELL = "toy_imp.toy_retrain_implicit"
TOY_IMP = {
    "source": "toy play counts for the CPU tests; stands for nothing",
    "engine_factory": "predictionio_tpu.templates.recommendation:engine_factory",
    "shape": {"users": 600, "items": 300, "ratings": 18000, "user_floor": 10,
              "user_sigma": 0.8, "item_exponent": 1.0, "item_shift": 5.0,
              "count_max": 9667, "count_exponent": 2.49, "count_shift": 0.556,
              "structure_seed": 3},
    "model": {"rank": 16, "iterations": 3, "lambda": 0.01, "implicitPrefs": True,
              "alpha": 40.0},
    "precision": "float32",
    "reduced": [],
    "reference": "als_implicit",
    "expect": {"platform": "cpu", "solver": "cholesky", "bucketing": "host",
               "precision": "highest", "rank": 16, "objective": "implicit",
               "alpha": 40.0},
    "check": {"train_rows": 64, "train_heaviest": 4, "gram_block": 256,
              "train_controls": ["p1", "p3"]},
    # float32 reads ~3e-6 / ~5e-5 here, three bf16 passes ~4e-5 / ~9e-4; a
    # user row lies ~0.15 from its solution given the item rows after three
    # sweeps, a unit-norm seed ~0.95
    "limits": {"train_median_row_err": 1.5e-5, "train_worst_row_err": 1e-3,
               "train_median_user_step": 0.4},
}
IMP_METRICS = {
    "imp.wall_s", "imp.startup_s", "imp.read_s", "imp.transfer_s",
    "imp.bucketing_s", "imp.init_s", "imp.first_sweep_s", "imp.first_sweep_trace_s",
    "imp.first_sweep_lower_s", "imp.sweep_s", "imp.readback_s",
    "imp.publish_s", "imp.device_idle_pct", "imp.solve_systems_per_sweep",
    "imp.hot_rows", "imp.sweep_roofline_pct"}


def _add_cell(root: str) -> None:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    with open(os.path.join(root, "benchmark", "configs", "toy_imp.json"), "x") as f:
        json.dump(TOY_IMP, f)
    with open(os.path.join(root, "benchmark", "traffic",
                           "toy_retrain_implicit.json"), "x") as f:
        # one device whatever XLA_FLAGS a test session set
        json.dump({"kind": "train_implicit_job", "flags": ["--mesh", "none"]}, f)
    manifest["configs"].append({
        "name": "toy_imp", "source": "none", "reduced": [], "why": "test",
        "file": "benchmark/configs/toy_imp.json"})
    manifest["workloads"].append({
        "name": CELL, "config": "toy_imp", "traffic": "toy_retrain_implicit",
        "chips": 1, "why": "test"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if m["name"] == "train_device_s" or m["name"].startswith("imp."):
            m["workloads"] = m["workloads"] + [CELL]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = toy.make_toy_root(str(tmp_path_factory.mktemp("toy_imp")))
    _add_cell(root)
    return root


def test_the_manifest_lists_the_imp_metrics_inside_its_limit():
    """The driver refuses a manifest of more than 128 per-layer metrics
    before any run (PR 44's first hand-in had 130), and test_manifest.py does
    not count them."""
    here = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    with open(os.path.join(here, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    assert 1 <= len(manifest["per_layer"]) <= 128
    assert 1 <= len(manifest["configs"]) <= 24
    listed = {m["name"] for m in manifest["per_layer"] if m["name"].startswith("imp.")}
    assert listed == IMP_METRICS
    files = {f[:-5] for f in os.listdir(os.path.join(here, "benchmark", "layer_metrics"))
             if f.startswith("imp.")}
    assert files == IMP_METRICS


# ------------------------------------------------------------- the generator


def test_the_play_counts_keep_their_structure_and_their_shape():
    shape = TOY_IMP["shape"]
    a, b = playcounts.play_events(shape, 5), playcounts.play_events(shape, 3_000_000_019)
    for ev in (a, b):
        assert ev["rows"].size == shape["ratings"]
        key = ev["rows"].astype(np.int64) * shape["items"] + ev["cols"]
        assert np.unique(key).size == shape["ratings"]  # distinct pairs
        assert np.bincount(ev["rows"], minlength=shape["users"]).min() >= shape["user_floor"]
        assert ev["vals"].dtype == np.float32
        assert ev["vals"].min() >= 1 and np.array_equal(ev["vals"], np.round(ev["vals"]))
        assert ev["vals"].max() == shape["count_max"]  # the published maximum, planted
        assert 0.55 < np.mean(ev["vals"] == 1) < 0.65
    # the structure is the structure seed's: another seed, the same pairs in
    # another order, under other counts
    key_a = a["rows"].astype(np.int64) * shape["items"] + a["cols"]
    key_b = b["rows"].astype(np.int64) * shape["items"] + b["cols"]
    assert not np.array_equal(key_a, key_b)
    assert np.array_equal(np.sort(key_a), np.sort(key_b))
    assert not np.array_equal(a["vals"][np.argsort(key_a)], b["vals"][np.argsort(key_b)])


def test_the_count_distribution_is_the_configurations():
    with open(os.path.join(toy.REPO, "benchmark", "configs", "als_tasteprofile.json")) as f:
        shape = json.load(f)["shape"]
    p = playcounts.count_distribution(shape)
    k = np.arange(1, p.size + 1)
    assert p.size == 9667 and abs(p.sum() - 1) < 1e-12
    assert abs(p[0] - 0.6) < 0.005  # six in ten a single play
    assert abs(float((p * k).sum()) - 2.9) < 0.02  # mean about 2.9
    assert shape["ratings"] / shape["users"] > shape["user_floor"]


# ------------------------------------------------------------- the reference


class FakeRun:
    def __init__(self, config, seed=11):
        self.config, self.seed, self.lines = config, seed, []

    def say(self, msg):
        self.lines.append(msg)


def _half_sweep(own, other, vals, table, n_rows, model, passes):
    """One half-sweep in plain float32 numpy (passes 0), or with every
    product in 1 or 3 bf16 passes: what the program's sweep computes. ``own``
    are the codes of the side solved, ``table`` the other side's rows."""
    k = table.shape[1]
    lam, alpha = model["lambda"], np.float32(model["alpha"])
    gram = als.matmul_passes(table.T, table, passes) if passes else table.T @ table
    out = np.zeros((n_rows, k), np.float32)
    order = np.argsort(own, kind="stable")
    bounds = np.searchsorted(own[order], np.arange(n_rows + 1))
    for i in range(n_rows):
        sel = order[bounds[i]:bounds[i + 1]]
        x, w = table[other[sel]], alpha * vals[sel]
        if passes:
            a = als.matmul_passes((x * w[:, None]).T, x, passes)
            b = als.matmul_passes(x.T, (1 + w)[:, None], passes)[:, 0]
        else:
            a, b = (x * w[:, None]).T @ x, x.T @ (1 + w)
        a = a + gram + np.float32(lam * max(len(sel), 1)) * np.eye(k, dtype=np.float32)
        out[i] = np.linalg.solve(a, b)
    return out


def _item_rows(events, user, model, passes):
    return _half_sweep(events["cols"], events["rows"], events["vals"], user,
                       TOY_IMP["shape"]["items"], model, passes)


def _user_rows(events, item, model, select=slice(None)):
    return _half_sweep(events["rows"][select], events["cols"][select],
                       events["vals"][select], item, TOY_IMP["shape"]["users"], model, 0)


def _unit_rows(rng, n):
    t = np.abs(rng.standard_normal((n, 16))).astype(np.float32)
    return t / np.linalg.norm(t, axis=1, keepdims=True)


@pytest.fixture(scope="module")
def seeded():
    """The events, and the user rows and the item rows they were solved from
    after three plain sweeps from unit-norm seeds (the item rows one user
    half-sweep old, as a job leaves them before its last item half-sweep)."""
    events = playcounts.play_events(TOY_IMP["shape"], 5)
    model, rng = TOY_IMP["model"], np.random.default_rng(0)
    item = _unit_rows(rng, 300)
    for _ in range(3):
        user = _user_rows(events, item, model)
        before, item = item, _item_rows(events, user, model, 0)
    return events, user, before


@pytest.mark.parametrize("passes,correct", [(0, True), (3, False), (1, False)],
                         ids=["float32", "three_bf16_passes", "one_bf16_pass"])
def test_the_reference_passes_float32_and_fails_bf16(seeded, passes, correct):
    events, user, _ = seeded
    run = FakeRun(TOY_IMP)
    ok = als_implicit.compare_train(
        run, events, user, _item_rows(events, user, TOY_IMP["model"], passes),
        als.Checks(run.say))
    assert ok is correct, run.lines
    # the three controls fail in the run itself, whatever stood in the program's place
    assert sum("fails as it must" in line for line in run.lines) == 3
    if not correct:
        assert any("median item row error" in line and "FAILED" in line
                   for line in run.lines)


def test_the_reference_counts_every_user_in_the_shared_gramian(seeded):
    """A program that summed X^T X over the item's own listeners only (or
    left it out) is another objective: not correct."""
    events, user, _ = seeded
    model = TOY_IMP["model"]
    rows = _item_rows(events, user, model, 0)
    gram = user.T @ user
    k = user.shape[1]
    i = int(np.argmax(np.bincount(events["cols"])))
    sel = events["cols"] == i
    x, w = user[events["rows"][sel]], np.float32(model["alpha"]) * events["vals"][sel]
    a = (x * w[:, None]).T @ x + np.float32(model["lambda"] * sel.sum()) * np.eye(k, dtype=np.float32)
    assert np.allclose(np.linalg.solve(a + gram, x.T @ (1 + w)), rows[i], rtol=1e-4)
    stale = rows.copy()
    stale[i] = np.linalg.solve(a + x.T @ x, x.T @ (1 + w))
    run = FakeRun(TOY_IMP)
    assert not als_implicit.compare_train(run, events, user, stale, als.Checks(run.say))
    assert any("worst item row error" in line and "FAILED" in line for line in run.lines)


def test_the_reference_catches_an_unchanged_state(seeded):
    events, user, _ = seeded
    stale = _unit_rows(np.random.default_rng(1), 300) / 4  # never solved
    run = FakeRun(TOY_IMP)
    assert not als_implicit.compare_train(run, events, user, stale, als.Checks(run.say))


@pytest.mark.parametrize("user_side", ["never_solved", "half_its_entries"])
def test_the_reference_catches_a_user_half_sweep_that_did_not_run(seeded, user_side):
    """The item rows are exact solutions of whatever user rows are stored, so
    every item limit holds; the user rows are not what a half-sweep over the
    events leaves, and the run is not correct by their distance alone."""
    events, _, before = seeded
    model = TOY_IMP["model"]
    if user_side == "never_solved":
        user = _unit_rows(np.random.default_rng(4), 600)
    else:  # every other event left out of the user half-sweep
        user = _user_rows(events, before, model, slice(None, None, 2))
    run = FakeRun(TOY_IMP)
    assert not als_implicit.compare_train(
        run, events, user, _item_rows(events, user, model, 0), als.Checks(run.say))
    failed = [line for line in run.lines if "FAILED" in line]
    assert len(failed) == 1 and "median user row distance" in failed[0], run.lines


def test_the_kind_fails_on_an_instance_that_does_not_say_its_objective():
    """The parent's `pio train` under this cell runs to its end, and the run
    then fails by itself (exit code 1, no result line): the driver measures
    the cell on the change alone."""
    from benchmark.kinds import train_implicit_job

    train_implicit_job._require_objective({"solver": "pallas", "objective": "implicit"})
    with pytest.raises(RuntimeError, match="does not say which objective"):
        train_implicit_job._require_objective({"solver": "pallas", "rank": 64})


def test_an_instance_that_does_not_say_its_objective_is_not_correct():
    """The parent's instance under this cell: no ``objective``, ``alpha`` or
    ``positiveEntries``. The comparison ends by itself, not correct, before
    it opens the model."""
    run = FakeRun(TOY_IMP)
    instance = {"device": {"platform": "cpu"},
                "kernels": {"als": {"solver": "cholesky", "bucketing": "host",
                                    "precision": "highest", "rank": 16,
                                    "sweepSeconds": [0.1, 0.1, 0.1]}}}
    events = {"vals": np.ones(5, np.float32)}
    assert als_implicit.check_train(run, events, instance, b"") is False
    assert any("instance objective: None == 'implicit' -> FAILED" in line
               for line in run.lines)


def test_gram_all_sums_blocks():
    rng = np.random.default_rng(2)
    x = rng.standard_normal((1000, 8)).astype(np.float32)
    want = x.astype(np.float64).T @ x.astype(np.float64)
    assert np.allclose(als_implicit.gram_all(x, 300, "f64"), want, rtol=1e-12)
    assert np.allclose(als_implicit.gram_all(x, 300, "p3"), want, rtol=1e-4, atol=1e-3)
    assert not np.allclose(als_implicit.gram_all(x, 300, "p1"), want, rtol=1e-5, atol=1e-4)


# ------------------------------------------------------------------ the cell


def test_the_cell_is_correct_and_its_controls_fail(root):
    rc, line, out = toy.drive(root, CELL, seed=3_000_000_019, seconds=2.0)
    assert rc == 0, out[-3000:]
    assert line["correct"] is True, out[-3000:]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["metrics"]) == {"setup_s", "train_device_s"}
    assert "prime: done" in out  # an empty compile cache: one train in set-up
    assert out.count("fails as it must") == 3  # one and three bf16 passes and an unsolved user side, in the run itself
    assert "check instance objective: 'implicit' == 'implicit' -> ok" in out
    assert "check instance alpha: 40.0 == 40.0 -> ok" in out
    assert "check instance positiveEntries: 18000 == 18000 -> ok" in out


def test_a_traced_run_reports_the_imp_layer_metrics(root):
    rc, line, out = toy.drive(root, CELL, seed=11, seconds=2.0, trace=1)
    assert rc == 0, out[-3000:]
    assert line["correct"] is True, out[-3000:]
    assert IMP_METRICS <= set(line["metrics"]), (IMP_METRICS - set(line["metrics"]), out[-3000:])
    assert "nothing to read" not in out
    m = {k: v["value"] for k, v in line["metrics"].items()}
    shape = TOY_IMP["shape"]
    assert m["imp.solve_systems_per_sweep"] >= shape["users"] + shape["items"]
    rows, cols = playcounts.structure(shape)  # rows wider than the widest bucket
    assert m["imp.hot_rows"] == sum(int((np.bincount(x) > 512).sum()) for x in (rows, cols))
    assert "prime:" not in out  # the marker of the run before holds
