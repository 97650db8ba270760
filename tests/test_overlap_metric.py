"""The benchmark's three ``*.overlap_pct`` metrics (ISSUE 31) read the
batcher's ``overlapPct`` from ``/stats.json``: a traced toy cell on the CPU
reports them, and against a program without the counter (this PR's parent)
their reader finds nothing and says so by ``None``."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.readers import path as path_reader  # noqa: E402
from benchmark.tests import toy  # noqa: E402

METRICS = {
    "ecom.overlap_pct": ("served_qps", "ecom_amazon2018.filtered_saturated"),
    "sat.overlap_pct": ("served_qps", "als_kddcup11.serve_saturated"),
    "serve.overlap_pct": ("query_p95_ms", "als_kddcup11.serve_steady"),
}


def _spec(name):
    with open(os.path.join(REPO, "benchmark", "layer_metrics", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", sorted(METRICS))
def test_the_manifest_entry_and_the_file_agree(name):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        (entry,) = [m for m in json.load(f)["per_layer"] if m["name"] == name]
    moves, cell = METRICS[name]
    assert entry == {
        "name": name, "unit": "%", "better": "higher", "source": "program_counter",
        "layer": "batcher", "moves": moves, "workloads": [cell]}
    spec = _spec(name)
    assert spec["reader"] == "path"
    assert spec["args"] == {"path": "stats.batcher.overlapPct"}
    # a program that has the counter, and one that has not (the parent)
    assert path_reader.read({"stats": {"batcher": {"overlapPct": 87.5}}},
                            spec["args"]) == 87.5
    assert path_reader.read({"stats": {"batcher": {"batches": 3}}},
                            spec["args"]) is None


def test_a_traced_toy_cell_reports_its_overlap_share(tmp_path):
    """``toy_saturated`` reports ``served_qps``, so the toy root lists it
    under both metrics that move it. The share itself may read 0 here: a
    toy batch is handled in less than the batch delay, so the second
    worker's drain seldom ends before the first batch is back."""
    root = toy.make_toy_root(str(tmp_path))
    rc, line, out = toy.drive(root, "toy_als.toy_saturated", seconds=3.0, trace=1)
    assert rc == 0, out[-3000:]
    for name in ("sat.overlap_pct", "ecom.overlap_pct"):
        assert line["metrics"][name]["unit"] == "%", out[-3000:]
        assert 0.0 <= line["metrics"][name]["value"] <= 100.0
    assert "serve.overlap_pct" not in line["metrics"]  # moves another metric
