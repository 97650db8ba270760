"""The runner: finds a cell's files by the names in BENCHMARK.json, hands
them to the traffic kind's module, and turns what that returns into the
result line. Nothing here knows a configuration, a traffic mix or a metric
by name: those are files under configs/, traffic/, layer_metrics/, and
modules under kinds/, references/, readers/ loaded by the name a data file
gives.

This process never lets JAX open a device: the chip belongs to the `pio`
child (benchmark/pio_child.py), one at a time.
"""

from __future__ import annotations

import argparse
import hashlib
import importlib
import json
import math
import os
import shutil
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")

#: exit codes: 2 no checkout around the benchmark, 3 no TPU (or fewer chips
#: than the cell asks for), 4 bad arguments or manifest, 1 a failed run
EXIT_NO_CHECKOUT, EXIT_NO_CHIP, EXIT_USAGE = 2, 3, 4


class Refused(Exception):
    """The run cannot be made here; no result line is printed."""

    def __init__(self, code: int, why: str):
        super().__init__(why)
        self.code = code


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(package: str, name: str):
    if not name.replace("_", "").isalnum():
        raise Refused(EXIT_USAGE, f"bad module name {name!r}")
    return importlib.import_module(f"benchmark.{package}.{name}")


def dig(tree, path: str):
    """``a.b.0`` into nested dicts and lists; None where it leads nowhere."""
    for key in path.split("."):
        if isinstance(tree, dict):
            tree = tree.get(key)
        elif isinstance(tree, (list, tuple)) and key.lstrip("-").isdigit():
            i = int(key)
            tree = tree[i] if -len(tree) <= i < len(tree) else None
        else:
            return None
        if tree is None:
            return None
    return tree


class Run:
    """One run's state: the cell's files, the scratch directory, the
    children's environment and the log."""

    def __init__(self, root: str, cell: dict, config: dict, traffic: dict,
                 seed: int, seconds: float, trace: bool, t0: float,
                 platforms: str = "tpu", child_jax_platforms: str | None = None):
        self.root, self.cell, self.config, self.traffic = root, cell, config, traffic
        self.seed, self.seconds, self.trace, self.t0 = seed, seconds, trace, t0
        self.platforms = platforms  # only the tests pass anything but "tpu"
        # git-ignored; one directory per run, removed by close()
        self.workdir = os.path.join(root, ".benchmark_work", f"run-{os.getpid()}")
        self.children: list = []  # (process, its open log file)
        shutil.rmtree(self.workdir, ignore_errors=True)
        os.makedirs(self.workdir)
        # children get the environment this process was started with, plus
        # the store; the compile cache follows the repo's one rule
        # ($JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache)
        self.env = dict(os.environ)
        self.env.pop("BENCH_RUN", None)
        if child_jax_platforms is not None:
            # main() holds THIS process to the CPU; its children keep what
            # the command was started with
            self.env.pop("JAX_PLATFORMS", None)
            if child_jax_platforms:
                self.env["JAX_PLATFORMS"] = child_jax_platforms
        self.env["PYTHONPATH"] = root + os.pathsep + self.env.get("PYTHONPATH", "")
        for key, value in {
            "PIO_FS_BASEDIR": os.path.join(self.workdir, "store"),
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "COL",
            "PIO_STORAGE_SOURCES_COL_TYPE": "columnar",
            "PIO_STORAGE_SOURCES_COL_PATH": os.path.join(self.workdir, "events"),
        }.items():
            os.environ[key] = self.env[key] = value

    def say(self, msg: str) -> None:
        print(f"[{time.monotonic() - self.t0:7.1f}s] {msg}", flush=True)

    def elapsed(self) -> float:
        return time.monotonic() - self.t0

    def cache_dir(self) -> str:
        return self.env.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
            self.root, ".jax_cache")

    def spawn_pio(self, name: str, pio_argv: list, trace_dir: str | None = None,
                  ) -> tuple[subprocess.Popen, str]:
        """Start `pio <argv>` through the wrapper; (process, report path)."""
        report = os.path.join(self.workdir, f"{name}.report.json")
        argv = [sys.executable, os.path.join(HERE, "pio_child.py"),
                "--report", report, "--allow-platform", self.platforms]
        if trace_dir:
            argv += ["--trace-dir", trace_dir]
        log = open(os.path.join(self.workdir, f"{name}.log"), "w")
        proc = subprocess.Popen(
            argv + ["--", *pio_argv], env=self.env, cwd=self.root,
            stdout=log, stderr=subprocess.STDOUT, stdin=subprocess.DEVNULL,
        )
        self.children.append((proc, log))
        return proc, report

    def reap(self, proc: subprocess.Popen, name: str, report: str,
             timeout: float = 1100.0) -> dict:
        """Wait for a child; its report, or Refused / RuntimeError."""
        try:
            rc = proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
            rc = -9
        for child, log in self.children:
            if child is proc:
                log.close()
        rep = load_json(report) if os.path.exists(report) else {}
        if rc == 3 and "refused" in rep:
            raise Refused(EXIT_NO_CHIP, rep["refused"])
        if rc != 0:
            self.show_log(name)
            raise RuntimeError(f"{name}: exit code {rc}")
        return rep

    def show_log(self, name: str, tail: int = 6000) -> None:
        path = os.path.join(self.workdir, f"{name}.log")
        if os.path.exists(path):
            with open(path, errors="replace") as f:
                sys.stderr.write(f.read()[-tail:])

    def close(self) -> None:
        for proc, log in self.children:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
            log.close()
        shutil.rmtree(self.workdir, ignore_errors=True)


def program_fingerprint(root: str, *extra: str) -> str:
    """A hash over the program's sources and the given strings: what a
    compiled program may depend on. A cell that primes the compile cache
    keeps its marker under this name, so a change to the program primes
    again instead of compiling inside a window."""
    h = hashlib.sha256()
    for base, dirs, files in os.walk(os.path.join(root, "predictionio_tpu")):
        dirs.sort()
        for name in sorted(files):
            if name.endswith(".py"):
                path = os.path.join(base, name)
                h.update(os.path.relpath(path, root).encode())
                with open(path, "rb") as f:
                    h.update(f.read())
    for s in extra:
        h.update(s.encode())
    return h.hexdigest()[:20]


def memory_peak(reports: list) -> dict:
    """What the fullest chip held when it held most: the sample of the
    child's watch (benchmark/pio_child.py, five a second) at which live
    buffers (``in_use``) and the pool the runtime reserves for running
    programs' temporaries (``reserved``) were largest together. The
    allocator's own two peaks stand beside it; their sum is no peak."""
    best = {"occupied": 0, "in_use": 0, "reserved": 0}
    for rep in reports:
        for peak, stats in zip(rep["memory_watch"]["peaks"], rep["memory"]):
            if peak["occupied"] > best["occupied"]:
                best = {**peak,
                        "allocator_peak_in_use": int(stats.get("peak_bytes_in_use", 0)),
                        "allocator_peak_reserved": int(stats.get("peak_bytes_reserved", 0))}
    return best


def layer_metrics(manifest: dict, cell: dict, facts: dict, say) -> dict:
    """Every per-layer metric of the manifest that lists this cell, read by
    the reader its own file names. A reader that finds nothing returns
    None and the metric is left out."""
    reported = set(facts["end_to_end"])
    out = {}
    for m in manifest["per_layer"]:
        cells = m.get("workloads")
        if cells is not None and cell["name"] not in cells:
            continue
        if cells is None and m["moves"] not in reported:
            continue
        spec = load_json(HERE, "layer_metrics", m["name"] + ".json")
        reader = load_module("readers", spec["reader"])
        value = reader.read(facts, spec.get("args", {}))
        if value is None or not math.isfinite(value):
            say(f"layer metric {m['name']}: nothing to read")
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(root: str, manifest: dict, workload: str, seed: int, seconds: float,
             trace: bool, t0: float, platforms: str = "tpu",
             child_jax_platforms: str | None = None) -> dict:
    """Drive one cell; the result line as a dict. ``platforms`` other than
    "tpu" is for the benchmark's own tests: the command line never passes it."""
    cell = next((w for w in manifest["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise Refused(EXIT_USAGE, f"no workload {workload!r} in BENCHMARK.json")
    cfg_entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    config = load_json(root, cfg_entry["file"])
    traffic = load_json(root, "benchmark", "traffic", cell["traffic"] + ".json")
    kind = load_module("kinds", traffic["kind"])
    run = Run(root, cell, config, traffic, seed, seconds, trace, t0, platforms,
              child_jax_platforms)
    try:
        facts = kind.run(run)
    finally:
        run.close()
    device = facts["device"]
    if platforms == "tpu" and (
            device["platform"] != "tpu" or device["count"] < cell["chips"]):
        raise Refused(EXIT_NO_CHIP, f"ran on {device}, the cell needs "
                      f"{cell['chips']} TPU chip(s)")
    if trace:
        metrics = layer_metrics(manifest, cell, facts, run.say)
        device = {**device, "busy_s": facts["trace"]["busy_s"],
                  "window_s": facts["trace"]["window_s"]}
    else:
        units = {m["name"]: m["unit"] for m in manifest["end_to_end"]}
        metrics = {k: {"value": float(v), "unit": units[k]}
                   for k, v in facts["end_to_end"].items()}
    line = {
        "correct": bool(facts["correct"]),
        "attempted": int(facts["attempted"]),
        "failed": int(facts["failed"]),
        "metrics": metrics,
        # the driver reads memory_peak_bytes; its two parts are for the reader
        "device": {**device,
                   "memory_peak_bytes": int(facts["memory"]["occupied"]),
                   "memory_live_bytes": int(facts["memory"]["in_use"]),
                   "memory_program_reserved_bytes": int(facts["memory"]["reserved"])},
    }
    if trace and facts["trace"].get("device_ops"):
        line["breakdown"] = {"device_ops": facts["trace"]["device_ops"][:10],
                             "idle_gaps": facts["trace"]["idle_gaps"][:10]}
    return line


def main(argv: list, t0: float) -> int:
    ap = argparse.ArgumentParser(prog="benchmark/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        import predictionio_tpu.tools.console  # noqa: F401
    except ImportError as e:
        print(f"benchmark: needs a checkout of the repo around it ({e})",
              file=sys.stderr)
        return EXIT_NO_CHECKOUT
    held = os.environ.get("JAX_PLATFORMS", "")
    if held and "tpu" not in held.split(","):
        print(f"benchmark: JAX is held to {held!r}, so it can find no TPU; "
              "there is no CPU fallback", file=sys.stderr)
        return EXIT_NO_CHIP
    # this process reads traces with jax's parser and pickles model classes
    # whose modules import jax: it must never open the chip its children need
    os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        manifest = load_json(ROOT, "BENCHMARK.json")
        line = run_cell(ROOT, manifest, args.workload, args.seed, args.seconds,
                        bool(args.trace), t0, child_jax_platforms=held)
    except Refused as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return e.code
    except Exception as e:
        print(f"benchmark: FAILED: {type(e).__name__}: {e}", file=sys.stderr)
        import traceback

        traceback.print_exc()
        return 1
    print(json.dumps(line), flush=True)
    return 0
