"""The similar-product serve kind end to end on the CPU: a toy configuration
and toy traffic ADDED to the temporary copy that ``toy.make_toy_root`` makes
(its set-up, its generator of prepared bodies, the reference's comparison,
the ``sim.*`` per-layer readers). And the same deployed without
``--pin-model``: served from the host, so not correct."""

import json
import os

import pytest

from benchmark.tests import toy

TOY_SIMPROD = {
    "source": "toy catalog for the CPU tests; stands for nothing",
    "engine_factory": "predictionio_tpu.templates.similarproduct:engine_factory",
    "shape": {"items": 3000, "categories": 5, "rank": 8},
    "model": {"rank": 8},
    "category_products": {"a": 50, "b": 30, "c": 10, "d": 7, "e": 3},
    "popularity": {"shift": 50, "exponent": 0.8},
    "reduced": [],
    "reference": "simprod",
    "expect": {"platform": "cpu"},
    "check": {"serve_queries": 48, "serve_control": "p3"},
    "limits": {"serve_tol_rel": 5e-5, "serve_tol_abs": 1e-6,
               "serve_rms_rel_err": 2e-7},
}
_TRAFFIC = {"kind": "closed_loop_similar", "clients": 8, "num": 10,
            "warmup_s": 0.5, "timeout_s": 5.0,
            "query_items": {"one_share": 0.7, "min": 2, "max": 8},
            "categories_share": {"own": 0.5, "own_and_second": 0.1},
            "blacklist": {"share": 0.2, "min": 1, "max": 50}}
TOY_TRAFFIC = {
    "toy_similar": {**_TRAFFIC, "deploy_flags": ["--pin-model", "--batching"]},
    "toy_similar_host": {**_TRAFFIC, "deploy_flags": ["--batching"]},
}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = toy.make_toy_root(str(tmp_path_factory.mktemp("toy_simprod")))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    with open(os.path.join(root, "benchmark", "configs", "toy_simprod.json"), "x") as f:
        json.dump(TOY_SIMPROD, f)
    manifest["configs"].append({
        "name": "toy_simprod", "source": "none", "reduced": [], "why": "test",
        "file": "benchmark/configs/toy_simprod.json"})
    for name, doc in TOY_TRAFFIC.items():
        with open(os.path.join(root, "benchmark", "traffic", name + ".json"), "x") as f:
            json.dump(doc, f)
        cell = f"toy_simprod.{name}"
        manifest["workloads"].append({
            "name": cell, "config": "toy_simprod", "traffic": name, "chips": 1,
            "why": "test"})
        for m in manifest["end_to_end"] + manifest["per_layer"]:
            if m["name"] == "served_qps" or m["name"].startswith("sim."):
                m["workloads"] = m["workloads"] + [cell]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return root


def test_the_similar_cell_is_correct_and_served_from_the_device_path(root):
    rc, line, out = toy.drive(root, "toy_simprod.toy_similar", seconds=2.0)
    assert rc == 0, out[-3000:]
    assert line["correct"] is True, out[-3000:]
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert set(line["metrics"]) == {"setup_s", "served_qps"}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert "fails as it must" in out  # the control, in the run itself


def test_without_the_pin_it_is_served_from_the_host_and_not_correct(root):
    rc, line, out = toy.drive(root, "toy_simprod.toy_similar_host", seconds=2.0)
    assert rc == 0, out[-3000:]
    assert line["correct"] is False, out[-3000:]
    assert "check servedFrom" in out


def test_a_traced_run_reports_the_sim_layer_metrics(root):
    rc, line, out = toy.drive(root, "toy_simprod.toy_similar", seconds=3.0, trace=1)
    assert rc == 0, out[-3000:]
    # the CPU's trace holds no device plane: the device's three read nothing
    assert {"sim.batch_fill", "sim.handle_ms", "sim.query_vectors_ms",
            "sim.filter_build_ms", "sim.device_wait_ms", "sim.format_ms",
            "sim.host_gap_ms", "sim.overlap_pct", "sim.excluded_per_query",
            "sim.query_items_per_query", "sim.host_path_queries",
            "sim.compiles_since_boot"} <= set(line["metrics"]), out[-3000:]
    assert line["metrics"]["sim.host_path_queries"]["value"] == 0
    # 0.7 x 1 + 0.3 x 5 items a query as drawn (more as sent: this small catalog's
    # popular pages are drawn many times over, and a repeated query is dropped);
    # a fifth of the queries carry 25.5 black-listed ids more
    assert 2.0 <= line["metrics"]["sim.query_items_per_query"]["value"] <= 5.0
    assert (line["metrics"]["sim.excluded_per_query"]["value"]
            >= line["metrics"]["sim.query_items_per_query"]["value"])


def test_a_program_without_the_pin_hook_is_refused_before_a_table_is_made(monkeypatch):
    """The parent of the PR that brought this cell: the kind's first act."""
    from benchmark.kinds import closed_loop_similar
    from predictionio_tpu.templates.retrieval import FilteredItemRetrieval

    monkeypatch.delattr(FilteredItemRetrieval, "pin_model_for_serving")
    with pytest.raises(RuntimeError, match="no pin_model_for_serving"):
        closed_loop_similar.run(object())  # touches nothing of the run
