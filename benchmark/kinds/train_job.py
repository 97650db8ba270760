"""Traffic kind ``train_job``: the window is whole `pio train` jobs.

Set-up writes the configuration's rating events (made from the seed) to a
fresh columnar store and, in a checkout whose compile cache has not seen
this program at this shape, trains once to fill it. The window then runs
one whole `pio train` child, process start to exit; a second job starts
only if it would also end inside the window. Every job runs under the
profiler (benchmark/pio_child.py; Python tracing off, no cost seen in the
wall). ``train_device_s``, the end-to-end metric, is the seconds in which
an operation ran on the device during one whole job, read from that trace
(the median over the window's jobs: one, at the shipped ``run_seconds``).
The job's wall on the host's clock is the per-layer ``train.wall_s``: the
hosts that run a check differ in it by more than the widest bound allows.

Afterwards the reference named by the configuration checks the stored
model against the events this module wrote.
"""

from __future__ import annotations

import json
import os
import shutil
import statistics
import time

import numpy as np

from benchmark import data, harness, xplane


def _engine_json(run, app: str, engine_id: str) -> str:
    model = run.config["model"]
    path = os.path.join(run.workdir, f"{engine_id}.json")
    with open(path, "w") as f:
        json.dump({
            "id": engine_id, "version": "1",
            "engineFactory": run.config["engine_factory"],
            "datasource": {"params": {"appName": app}},
            "algorithms": [{"name": "als", "params": {
                "rank": model["rank"], "numIterations": model["iterations"],
                "lambda": model["lambda"],
                "seed": run.seed % (2**31 - 1),
            }}],
        }, f)
    return path


def _write_events(run, app: str, events: dict) -> None:
    from predictionio_tpu.data.storage import Storage
    from predictionio_tpu.tools import commands

    shape = run.config["shape"]
    app_id = commands.app_new(app, out=lambda *_: None)[0].id
    n = Storage.get_p_events().write_columns(
        app_id,
        event="rate",
        entity_type="user",
        entity_codes=events["rows"],
        entity_vocab=np.asarray([str(i) for i in range(shape["users"])]),
        target_entity_type="item",
        target_codes=events["cols"],
        target_vocab=np.asarray([str(i) for i in range(shape["items"])]),
        event_time_us=events["time_us"],
        props={"rating": events["vals"].astype(np.float64)},
    )
    if n != events["rows"].size:
        raise RuntimeError(f"wrote {n} of {events['rows'].size} events")


def _train(run, name: str, engine_json: str, trace_dir: str | None = None):
    """One whole `pio train` child: (wall seconds, report)."""
    t0, spawned = time.monotonic(), time.time()
    proc, report = run.spawn_pio(
        name, ["train", "--engine-json", engine_json, *run.traffic.get("flags", [])],
        trace_dir,
    )
    rep = run.reap(proc, name, report)
    wall = time.monotonic() - t0
    # the wall in three: process start to the entry point (imports, first
    # device), the entry point itself, and from its return to process exit
    rep["start_s"] = rep["main_started"] - spawned
    rep["exit_s"] = wall - rep["start_s"] - rep["main_s"]
    return wall, rep


def _reduce(run, trace_dir: str, rep: dict) -> dict:
    """The job's trace reduced: device busy seconds, the ops that took most,
    the longest idle gaps; the traced span is the entry point's, by the
    child's clock."""
    path = xplane.find_xplane(trace_dir)
    if path is None:
        raise RuntimeError("the traced train left no .xplane.pb")
    t0 = time.monotonic()
    red = xplane.reduce_trace(path)
    if run.platforms == "tpu" and not red["devices"]:
        raise RuntimeError("the train's trace holds no device plane")
    red["window_s"] = rep["main_s"]
    red["idle_pct"] = 100.0 * (1.0 - red["busy_s"] / red["window_s"])
    run.say(f"trace: {os.path.getsize(path):,} bytes reduced in "
            f"{time.monotonic() - t0:.1f} s; busy {red['busy_s']:.3f} s of "
            f"{red['window_s']:.3f} s")
    shutil.rmtree(trace_dir, ignore_errors=True)
    return red


def run(run) -> dict:
    from predictionio_tpu.data.storage import Storage

    shape = run.config["shape"]
    events = data.rating_events(shape, run.seed)
    run.say(f"events: {events['rows'].size:,} rate events made from the seed")
    _write_events(run, "bench", events)
    engine_json = _engine_json(run, "bench", "bench")
    run.say("events: written through write_columns")
    t_events = run.elapsed()

    # prime: the first run in a compile cache trains once on the same events
    # in an app of its own, so the window never compiles. The marker is
    # named by a hash of the program's sources and the checkout's path (a
    # Pallas program's cache key holds its source locations), so a changed
    # or moved program primes again in set-up rather than compiling inside
    # its first window.
    mark = os.path.join(run.cache_dir(), "benchmark-primed-" + harness.program_fingerprint(
        run.root, os.path.abspath(run.root),
        json.dumps([run.config["shape"], run.config["model"],
                    run.traffic.get("flags", [])], sort_keys=True)))
    if not os.path.exists(mark):
        run.say("prime: this compile cache has not seen this program at this "
                "shape; training once in set-up")
        _write_events(run, "bench-prime", events)
        wall, _ = _train(run, "prime", _engine_json(run, "bench-prime", "bench-prime"))
        os.makedirs(run.cache_dir(), exist_ok=True)
        with open(mark, "w") as f:
            f.write(f"{wall:.1f}\n")
        run.say(f"prime: done in {wall:.1f} s")
    setup_s = run.elapsed()
    run.say(f"window: begins (set-up {setup_s:.2f} s; events {t_events:.2f}, "
            f"prime {setup_s - t_events:.2f})")

    walls, reports, traces = [], [], []
    t_window = time.monotonic()
    while True:
        trace_dir = os.path.join(run.workdir, f"trace{len(walls)}")
        wall, rep = _train(run, f"train{len(walls)}", engine_json, trace_dir)
        walls.append(wall)
        reports.append(rep)
        traces.append(_reduce(run, trace_dir, rep))
        cc = rep.get("compile_cache", {})
        run.say(f"window: pio train returned 0 after {wall:.3f} s (to the entry "
                f"point {rep['start_s']:.2f}, inside it {rep['main_s']:.2f}, to exit "
                f"{rep['exit_s']:.2f}); {cc.get('misses')} persistent-cache misses "
                f"in {cc.get('requests')} compile requests")
        used = time.monotonic() - t_window
        if run.trace or used + statistics.median(walls) > run.seconds:
            break
    train_device_s = statistics.median(t["busy_s"] for t in traces)

    inst = Storage.get_meta_data_engine_instances().get_latest_completed(
        "bench", "1", "bench")
    if inst is None:
        raise RuntimeError("no COMPLETED instance after the window")
    instance = {
        "phase_timings": json.loads(inst.env["phase_timings"]),
        "device": json.loads(inst.env["device"]),
        "kernels": json.loads(inst.env["kernels"]),
    }
    als = instance["kernels"]["als"]
    run.say(f"job: phases {instance['phase_timings']}; bucketing "
            f"{als.get('bucketingSeconds')}; sweeps {als.get('sweepSeconds')}")
    rep = reports[-1]
    memory = harness.memory_peak(reports)
    run.say(f"memory: most held at once {memory}; allocator at exit {rep['memory'][0]}")

    reference = harness.load_module("references", run.config["reference"])
    blob = Storage.get_model_data_models().get(inst.id).models
    ok = reference.check_train(run, events, instance, blob)

    facts = {
        "correct": ok,
        "attempted": len(walls),
        "failed": 0,
        "device": rep["device"],
        "memory": memory,
        "end_to_end": {"setup_s": setup_s, "train_device_s": train_device_s},
        "train_s": walls[-1],
        "instance": instance,
        "config": run.config,
        "compile_cache": rep.get("compile_cache", {}),
        "trace": traces[-1],
    }
    return facts
