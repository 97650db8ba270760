"""The pairs train kind end to end on the CPU: a toy two-tower
configuration and its traffic ADDED to the temporary copy that
``toy.make_toy_root`` makes (the pairs generator, the kind's set-up and
priming, the reference's replay and controls, the ``tt.*`` readers). And the
kind's refusal on a checkout whose program lacks the row update."""

import json
import os
import shutil

import numpy as np
import pytest

from benchmark import pairs
from benchmark.tests import toy

CELL = "toy_tt.toy_retrain_pairs"
TOY_TT = {
    "source": "toy pairs for the CPU tests; stands for nothing",
    "engine_factory": "predictionio_tpu.templates.twotower:engine_factory",
    "shape": {"users": 900, "items": 400, "pairs": 4000, "user_sigma": 1.0,
              "item_exponent": 0.8, "item_shift": 5.0, "structure_seed": 3},
    "model": {"dim": 16, "batch": 256, "epochs": 3, "learning_rate": 0.05,
              "temperature": 0.1, "gemmDtype": "float32"},
    "reduced": [],
    "reference": "twotower",
    # the CPU has no Mosaic: the XLA cross-entropy; float32 operands, so that
    # the control with bf16 tables stands out at this size too
    "expect": {"platform": "cpu", "fusedCe": "xla", "gemmDtype": "float32",
               "optimizer": "rows", "batch": 256, "dim": 16},
    "check": {"replay_steps": 8, "final_batches": 4},
    "limits": {"replay_loss_gap": 5e-4, "replay_norm_gap": 2e-3, "row_norm_err": 1e-5, "final_over_first": 0.95,
               "final_over_last_max": 1.5, "final_over_last_min": 0.5},
}


def _add_cell(root: str) -> None:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    with open(os.path.join(root, "benchmark", "configs", "toy_tt.json"), "x") as f:
        json.dump(TOY_TT, f)
    with open(os.path.join(root, "benchmark", "traffic", "toy_retrain_pairs.json"), "x") as f:
        json.dump({"kind": "train_pairs_job", "flags": ["--mesh", "none"]}, f)  # one device whatever XLA_FLAGS a test session set
    manifest["configs"].append({
        "name": "toy_tt", "source": "none", "reduced": [], "why": "test",
        "file": "benchmark/configs/toy_tt.json"})
    manifest["workloads"].append({
        "name": CELL, "config": "toy_tt", "traffic": "toy_retrain_pairs", "chips": 1,
        "why": "test"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if m["name"] == "train_device_s" or m["name"].startswith("tt."):
            m["workloads"] = m["workloads"] + [CELL]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = toy.make_toy_root(str(tmp_path_factory.mktemp("toy_tt")))
    _add_cell(root)
    return root


def test_the_pairs_hold_every_user_and_item_once_a_pair():
    shape = TOY_TT["shape"]
    a, b = pairs.pair_events(shape, 5), pairs.pair_events(shape, 6)
    for ev in (a, b):
        assert ev["rows"].size == shape["pairs"]
        assert np.unique(ev["rows"]).size == shape["users"]
        assert np.unique(ev["cols"]).size == shape["items"]
        key = ev["rows"].astype(np.int64) * shape["items"] + ev["cols"]
        assert np.unique(key).size == shape["pairs"]
    # the structure is the structure seed's: another seed, the same pairs
    # in another order
    key_a = a["rows"].astype(np.int64) * shape["items"] + a["cols"]
    key_b = b["rows"].astype(np.int64) * shape["items"] + b["cols"]
    assert not np.array_equal(key_a, key_b)
    assert np.array_equal(np.sort(key_a), np.sort(key_b))


def test_the_cell_is_correct_and_its_controls_fail(root):
    rc, line, out = toy.drive(root, CELL, seed=3_000_000_019, seconds=2.0)
    assert rc == 0, out[-3000:]
    assert line["correct"] is True, out[-3000:]
    assert line["attempted"] == 1 and line["failed"] == 0
    assert set(line["metrics"]) == {"setup_s", "train_device_s"}
    assert "prime: done" in out  # an empty compile cache: one epoch in set-up
    assert out.count("fails as it must") == 3  # two replays and the bf16 tables, in the run itself
    assert "check instance optimizer: 'rows' == 'rows' -> ok" in out


def test_a_traced_run_reports_the_tt_layer_metrics(root):
    rc, line, out = toy.drive(root, CELL, seed=11, seconds=2.0, trace=1)
    assert rc == 0, out[-3000:]
    assert line["correct"] is True, out[-3000:]
    want = {"tt.wall_s", "tt.startup_s", "tt.read_s", "tt.prepare_s", "tt.ingest_s",
            "tt.first_epoch_s", "tt.epoch_s", "tt.step_ms", "tt.first_epoch_compile_s",
            "tt.finalize_s", "tt.publish_s", "tt.rows_touched_per_step",
            "tt.device_idle_pct", "tt.step_roofline_pct"}
    assert want <= set(line["metrics"]), (want - set(line["metrics"]), out[-3000:])
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert 0 < m["tt.rows_touched_per_step"] <= 512
    assert 0 < m["tt.step_roofline_pct"] < 100
    assert "prime:" not in out  # the marker of the run before holds


def test_the_kind_refuses_a_checkout_without_the_row_update(root, tmp_path):
    """The parent's tree: the benchmark's new files over a program whose
    ``ops/twotower.py`` exports no ``adam_rows``. Refused before an event
    is made, with no result line."""
    old = str(tmp_path / "old")
    os.makedirs(old)
    shutil.copytree(os.path.join(root, "benchmark"), os.path.join(old, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(root, "BENCHMARK.json"), old)
    shutil.copytree(os.path.join(toy.REPO, "predictionio_tpu"),
                    os.path.join(old, "predictionio_tpu"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    path = os.path.join(old, "predictionio_tpu", "ops", "twotower.py")
    with open(path) as f:
        src = f.read()
    with open(path, "w") as f:
        f.write(src.replace("def adam_rows(", "def _adam_on_rows("))
    rc, line, out = toy.drive(old, CELL, seconds=2.0, timeout=120.0)
    assert rc != 0 and line is None
    assert "exports no adam_rows" in out
    assert "events:" not in out
