"""Every per-layer metric that reads the program's own spans and counters
(ISSUE 25) resolves to its file and prints a finite number in a traced toy
run on the CPU, in the cells of its kind."""

import json
import math
import os

import pytest

from benchmark.tests import toy

SPAN_METRICS = {
    "toy_als.toy_retrain": {
        "train.startup_s", "train.publish_s", "train.first_sweep_trace_s",
        "train.first_sweep_lower_s", "train.first_sweep_load_s",
        "train.transfer_s"},
    "toy_als.toy_steady": {
        "serve.host_gap_ms", "serve.device_wait_ms", "serve.format_ms",
        "serve.bind_ms", "serve.wake_ms", "serve.http_in_ms",
        "serve.compiles_since_boot"},
    "toy_als.toy_saturated": {
        "sat.host_gap_ms", "sat.device_wait_ms", "sat.format_ms",
        "sat.compiles_since_boot"},
}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return toy.make_toy_root(str(tmp_path_factory.mktemp("toy_spans")))


def test_the_files_are_the_path_readers_and_cover_the_manifest():
    with open(os.path.join(toy.REPO, "BENCHMARK.json")) as f:
        declared = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in set().union(*SPAN_METRICS.values()):
        assert declared[name]["source"] in ("program_span", "program_counter")
        with open(os.path.join(
                toy.REPO, "benchmark", "layer_metrics", name + ".json")) as f:
            spec = json.load(f)
        assert spec["reader"] == "path"
        # harness.dig splits on dots: no key of the program's may hold one
        assert spec["args"]["path"].split(".")[0] in ("instance", "stats")


@pytest.mark.parametrize("cell", sorted(SPAN_METRICS))
def test_a_traced_toy_run_reads_every_span_metric(root, cell):
    rc, line, out = toy.drive(root, cell, seconds=4.0, trace=1)
    assert rc == 0, out[-3000:]
    metrics = line["metrics"]
    assert SPAN_METRICS[cell] <= set(metrics), (
        sorted(SPAN_METRICS[cell] - set(metrics)), out[-3000:])
    for name in SPAN_METRICS[cell]:
        assert math.isfinite(metrics[name]["value"]), name
        assert metrics[name]["value"] >= 0, name
    compiles = [m for m in metrics if m.endswith("compiles_since_boot")]
    assert all(metrics[m]["value"] == 0 for m in compiles), out[-3000:]
