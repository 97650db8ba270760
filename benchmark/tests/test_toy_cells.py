"""The harness end to end on the CPU, on a toy configuration and toy traffic
files ADDED to a temporary copy: what a later PR does to bring a cell. And
the same with the timed path broken underneath: `correct` must be false."""

import hashlib
import os

import pytest

from benchmark.tests import toy


def _digest(root):
    out = {}
    for base, _, files in os.walk(os.path.join(root, "benchmark")):
        for name in files:
            if "__pycache__" in base:
                continue
            path = os.path.join(base, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = hashlib.sha256(f.read()).hexdigest()
    return out


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return toy.make_toy_root(str(tmp_path_factory.mktemp("toy")))


def test_a_new_cell_takes_new_files_only(root):
    theirs, ours = _digest(root), _digest(toy.REPO)
    assert {k: v for k, v in theirs.items() if k in ours} == ours
    added = sorted(set(theirs) - set(ours))
    assert added == ["benchmark/configs/toy_als.json"] + [
        f"benchmark/traffic/{name}.json" for name in sorted(toy.TOY_TRAFFIC)]


CELLS = [
    ("toy_als.toy_retrain", "", True, {"setup_s", "train_device_s"}),
    ("toy_als.toy_retrain", "drop_events", False, None),
    ("toy_als.toy_steady", "", True, {"setup_s", "query_p50_ms", "query_p95_ms"}),
    ("toy_als.toy_steady", "alter_answer", False, None),
    ("toy_als.toy_saturated", "", True, {"setup_s", "served_qps"}),
    ("toy_als.toy_int8", "", False, None),
]


@pytest.mark.parametrize("cell,broken,correct,metrics", CELLS,
                         ids=[f"{c}-{b or 'sound'}" for c, b, _, _ in CELLS])
def test_toy_cell(root, cell, broken, correct, metrics):
    rc, line, out = toy.drive(root, cell, seconds=2.0, broken=broken)
    assert rc == 0, out[-3000:]
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is correct, out[-3000:]
    assert line["device"]["platform"] == "cpu"  # and so never a chip result
    assert line["attempted"] >= 1
    if metrics is not None:
        assert set(line["metrics"]) == metrics
        # the CPU's trace holds no device plane, so device seconds read 0 here
        assert all(v["value"] > 0 for k, v in line["metrics"].items()
                   if k != "train_device_s")


def test_a_traced_toy_run_reports_its_layer_metrics(root):
    rc, line, out = toy.drive(root, "toy_als.toy_steady", seconds=4.0, trace=1)
    assert rc == 0, out[-3000:]
    assert {"serve.queue_wait_ms", "serve.batch_fill", "serve.handle_ms",
            "serve.http_ms", "serve.gen_late_ms"} <= set(line["metrics"])
    assert {"busy_s", "window_s"} <= set(line["device"])


def test_a_traced_toy_train_reports_its_wall_per_layer(root):
    rc, line, out = toy.drive(root, "toy_als.toy_retrain", seconds=2.0, trace=1)
    assert rc == 0, out[-3000:]
    assert {"train.wall_s", "train.other_s", "train.sweep_s",
            "train.first_sweep_s"} <= set(line["metrics"])
    assert "train_device_s" not in line["metrics"]
    assert line["metrics"]["train.wall_s"]["value"] > 0
