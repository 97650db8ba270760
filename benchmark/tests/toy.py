"""A temporary copy of the benchmark with a toy configuration and toy
traffic files ADDED to it (no existing file touched), for the CPU tests:
what a later PR does when it brings a cell of its own.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))

TOY_CONFIG = {
    "source": "toy shape for the CPU tests; stands for nothing",
    "engine_factory": "predictionio_tpu.templates.recommendation:engine_factory",
    "shape": {"users": 600, "items": 300, "ratings": 18000, "user_floor": 20,
              "user_sigma": 0.5, "item_exponent": 0.8, "structure_seed": 3},
    "model": {"rank": 8, "iterations": 3, "lambda": 0.05},
    "precision": "float32",
    "reduced": [],
    "reference": "als",
    "expect": {"platform": "cpu", "solver": "cholesky", "bucketing": "host",
               "precision": "highest", "rank": 8},
    "check": {"train_rows": 64, "train_heaviest": 4, "serve_queries": 64,
              "train_control": "p1", "serve_control": "p3"},
    "limits": {"train_median_row_err": 1e-4, "train_worst_row_err": 2.5e-2,
               "serve_tol_rel": 5e-5, "serve_tol_abs": 1e-6,
               "serve_rms_rel_err": 2e-7},
}
TOY_TRAFFIC = {
    "toy_retrain": {"kind": "train_job", "flags": []},
    "toy_steady": {"kind": "open_loop", "rate_per_s": 40, "num": 10,
                   "warmup_s": 0.5, "timeout_s": 5.0,
                   "deploy_flags": ["--pin-model", "--batching"]},
    "toy_saturated": {"kind": "closed_loop", "clients": 8, "num": 10,
                      "warmup_s": 0.5, "timeout_s": 5.0,
                      "deploy_flags": ["--pin-model", "--batching"]},
    # the program's own lower-precision path: must come out not correct
    "toy_int8": {"kind": "open_loop", "rate_per_s": 40, "num": 10,
                 "warmup_s": 0.5, "timeout_s": 5.0,
                 "deploy_flags": ["--pin-model", "--batching", "--quantize", "int8"]},
}


def make_toy_root(tmp: str) -> str:
    """Copy benchmark/ and BENCHMARK.json into ``tmp``, link the program
    beside them, and add the toy files and entries. Returns the root."""
    root = os.path.join(tmp, "checkout")
    os.makedirs(root)
    shutil.copytree(os.path.join(REPO, "benchmark"), os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(REPO, "predictionio_tpu"),
               os.path.join(root, "predictionio_tpu"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    with open(os.path.join(root, "benchmark", "configs", "toy_als.json"), "x") as f:
        json.dump(TOY_CONFIG, f)
    manifest["configs"].append({
        "name": "toy_als", "source": "none", "reduced": [], "why": "test",
        "file": "benchmark/configs/toy_als.json"})
    reports = {"train_job": "train_device_s", "open_loop": "query_p50_ms",
               "closed_loop": "served_qps"}
    for name, doc in TOY_TRAFFIC.items():
        with open(os.path.join(root, "benchmark", "traffic", name + ".json"), "x") as f:
            json.dump(doc, f)
        cell = f"toy_als.{name}"
        manifest["workloads"].append({
            "name": cell, "config": "toy_als", "traffic": name, "chips": 1,
            "why": "test"})
        for m in manifest["end_to_end"] + manifest["per_layer"]:
            # a new cell joins the metrics of its kind by listing itself
            ends = m["name"] if "moves" not in m else m["moves"]
            if "workloads" in m and (
                    ends == reports[doc["kind"]]
                    or (doc["kind"] == "open_loop" and ends == "query_p95_ms")):
                m["workloads"] = m["workloads"] + [cell]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    return root


def drive(root: str, workload: str, seed: int = 5, seconds: float = 3.0,
          trace: int = 0, broken: str = "", timeout: float = 600.0):
    """Run one toy cell on the CPU in a process of its own (the storage
    layer is a per-process singleton). Returns (returncode, result line or
    None, all output)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "tests", "drive.py"),
         root, workload, str(seed), str(seconds), str(trace), broken],
        env=env, cwd=root, capture_output=True, text=True, timeout=timeout)
    line = None
    out = proc.stdout.strip().splitlines()
    if proc.returncode == 0 and out:
        line = json.loads(out[-1])
    return proc.returncode, line, proc.stdout + proc.stderr
