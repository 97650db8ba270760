"""BENCHMARK.json against the contract's letter, and every name in it
against the files it must resolve to."""

import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_and_sizes(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(manifest["run_seconds"], int) and 1 <= manifest["run_seconds"] <= 51
    assert manifest["paths"] == ["benchmark"]
    assert 1 <= len(manifest["workloads"]) <= 24
    for word in manifest["command"]:
        assert 1 <= len(word) <= 200 and not word.startswith("/") and ".." not in word


def test_names_units_and_entry_keys(manifest):
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and all(NAME.match(k) for k in c["reduced"])
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"] and "\t" not in w["why"]
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.1
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["source"] in SOURCES and 1 <= len(m["layer"]) <= 200
    every = manifest["end_to_end"] + manifest["per_layer"]
    for m in every:
        assert NAME.match(m["name"]), m["name"]
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for group in (every, manifest["workloads"], manifest["configs"]):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))
    assert "setup_s" in {m["name"] for m in manifest["end_to_end"]}


def test_every_name_resolves_to_a_file(manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    cells = {w["name"] for w in manifest["workloads"]}
    ends = {m["name"] for m in manifest["end_to_end"]}
    files = [c["file"] for c in manifest["configs"]]
    assert len(files) == len(set(files))
    used = set()
    for w in manifest["workloads"]:
        assert w["config"] in configs
        used.add(w["config"])
        cfg_path = os.path.join(ROOT, configs[w["config"]]["file"])
        assert cfg_path.startswith(os.path.join(ROOT, "benchmark") + os.sep)
        with open(cfg_path) as f:
            cfg = json.load(f)
        assert sorted(cfg["reduced"]) == sorted(configs[w["config"]]["reduced"])
        assert all(key in cfg for key in cfg["reduced"])
        assert os.path.exists(os.path.join(ROOT, "benchmark", "references",
                                           cfg["reference"] + ".py"))
        with open(os.path.join(ROOT, "benchmark", "traffic", w["traffic"] + ".json")) as f:
            traffic = json.load(f)
        assert os.path.exists(os.path.join(ROOT, "benchmark", "kinds",
                                           traffic["kind"] + ".py"))
    assert used == set(configs), "a configuration no cell uses"
    for m in manifest["per_layer"]:
        assert m["moves"] in ends
        assert set(m.get("workloads", cells)) <= cells
        with open(os.path.join(ROOT, "benchmark", "layer_metrics", m["name"] + ".json")) as f:
            spec = json.load(f)
        assert os.path.exists(os.path.join(ROOT, "benchmark", "readers",
                                           spec["reader"] + ".py"))
    for m in manifest["end_to_end"]:
        assert set(m.get("workloads", cells)) <= cells
    # every cell reports setup_s, one more end-to-end and one per-layer metric
    for cell in cells:
        e2e = [m for m in manifest["end_to_end"] if cell in m.get("workloads", cells)]
        assert len(e2e) >= 2
        assert any(cell in m.get("workloads", cells) for m in manifest["per_layer"])
    four = sum(1 for w in manifest["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(manifest["workloads"]) // 4)


def test_check_fits_the_chip_budget(manifest):
    # 2 + 14 x 24 runs of run_seconds + 60 s, 2 x 90 s a cell to compile,
    # 1200 s spare, inside 43200 s: with the full 24 cells
    runs = 2 + 14 * 24
    assert runs * (manifest["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
