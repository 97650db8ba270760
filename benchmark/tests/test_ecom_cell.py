"""The filtered serve kind end to end on the CPU: a toy e-commerce
configuration and toy traffic ADDED to the temporary copy that
``toy.make_toy_root`` makes (its set-up, its generator of prepared bodies,
the reference's comparison, the ``ecom.*`` per-layer readers). And the same
deployed without ``--pin-model``: served from the host, so not correct."""

import json
import os

import pytest

from benchmark.tests import toy

TOY_ECOM = {
    "source": "toy shop for the CPU tests; stands for nothing",
    "engine_factory": "predictionio_tpu.templates.ecommerce:engine_factory",
    "shape": {"items": 3000, "categories": 5, "users": 400, "rank": 8},
    "model": {"rank": 8},
    "category_products": {"a": 50, "b": 30, "c": 10, "d": 7, "e": 3},
    "history": {"mean": 5.4, "sigma": 1.0, "max": 60, "buy_share": 0.2},
    "unavailable_items": 50,
    "reduced": [],
    "reference": "ecom",
    "expect": {"platform": "cpu"},
    "check": {"serve_queries": 48, "serve_control": "p3"},
    "limits": {"serve_tol_rel": 5e-5, "serve_tol_abs": 1e-6,
               "serve_rms_rel_err": 2e-7},
}
_TRAFFIC = {"kind": "closed_loop_filtered", "clients": 8, "num": 10,
            "warmup_s": 0.5, "timeout_s": 5.0,
            "categories_share": {"one": 0.6, "two": 0.1},
            "blacklist": {"share": 0.2, "min": 1, "max": 50}}
TOY_TRAFFIC = {
    "toy_filtered": {**_TRAFFIC, "deploy_flags": ["--pin-model", "--batching"]},
    "toy_filtered_host": {**_TRAFFIC, "deploy_flags": ["--batching"]},
}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = toy.make_toy_root(str(tmp_path_factory.mktemp("toy_ecom")))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    with open(os.path.join(root, "benchmark", "configs", "toy_ecom.json"), "x") as f:
        json.dump(TOY_ECOM, f)
    manifest["configs"].append({
        "name": "toy_ecom", "source": "none", "reduced": [], "why": "test",
        "file": "benchmark/configs/toy_ecom.json"})
    for name, doc in TOY_TRAFFIC.items():
        with open(os.path.join(root, "benchmark", "traffic", name + ".json"), "x") as f:
            json.dump(doc, f)
        cell = f"toy_ecom.{name}"
        manifest["workloads"].append({
            "name": cell, "config": "toy_ecom", "traffic": name, "chips": 1,
            "why": "test"})
        for m in manifest["end_to_end"] + manifest["per_layer"]:
            if m["name"] == "served_qps" or m["name"].startswith("ecom."):
                m["workloads"] = m["workloads"] + [cell]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return root


def test_the_filtered_cell_is_correct_and_served_from_the_device_path(root):
    rc, line, out = toy.drive(root, "toy_ecom.toy_filtered", seconds=2.0)
    assert rc == 0, out[-3000:]
    assert line["correct"] is True, out[-3000:]
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert set(line["metrics"]) == {"setup_s", "served_qps"}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert "fails as it must" in out  # the control, in the run itself


def test_without_the_pin_it_is_served_from_the_host_and_not_correct(root):
    rc, line, out = toy.drive(root, "toy_ecom.toy_filtered_host", seconds=2.0)
    assert rc == 0, out[-3000:]
    assert line["correct"] is False, out[-3000:]
    assert "check servedFrom" in out


def test_a_traced_run_reports_the_ecom_layer_metrics(root):
    rc, line, out = toy.drive(root, "toy_ecom.toy_filtered", seconds=3.0, trace=1)
    assert rc == 0, out[-3000:]
    # the CPU's trace holds no device plane: the device's three read nothing
    assert {"ecom.batch_fill", "ecom.handle_ms", "ecom.filter_lookup_ms",
            "ecom.filter_build_ms", "ecom.device_wait_ms", "ecom.format_ms",
            "ecom.host_gap_ms", "ecom.excluded_per_query", "ecom.host_path_queries",
            "ecom.compiles_since_boot"} <= set(line["metrics"]), out[-3000:]
    assert line["metrics"]["ecom.host_path_queries"]["value"] == 0
    assert line["metrics"]["ecom.excluded_per_query"]["value"] >= 50
