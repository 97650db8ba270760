"""Traffic kind ``train_implicit_job``: the window is whole `pio train` jobs
of the recommendation template under the implicit objective.

As ``train_job`` (whose ``_write_events``, ``_train`` and ``_reduce`` this
takes), with two differences: the events' values are play counts
(benchmark/playcounts.py) and the engine's algorithm carries
``implicitPrefs`` and ``alpha``. Set-up writes the configuration's events to
a fresh columnar store and, in a compile cache that has not seen this
program at this shape, trains once on the same events under an engine id of
its own, for ONE sweep: the sweep's program is the same for any number of
sweeps (as ``train_pairs_job`` primes with one epoch); that train leaves the
datasource's read cache alone (``incremental: false``), so the window's job
reads the store whole, in a first run as in every other. The window is one
whole warm `pio train` child under the profiler, process start to exit 0,
run to its end even where it outlasts ``--seconds``; a second job starts only
if it would also end inside the window. ``train_device_s`` is the seconds in
which an operation ran on the device in one whole job, from its trace.
Afterwards the configuration's reference checks the instance and the stored
model against the events this module wrote. A checkout whose instance does
not say which objective its job ran (the parent of the PR that brought this
cell) cannot be held to the implicit one: its run ends by itself with exit
code 1 and no result line, so the driver measures the cell on the change alone.
"""

from __future__ import annotations

import json
import os
import statistics
import time

from benchmark import harness, playcounts
from benchmark.kinds import train_job


def _engine_json(run, engine_id: str, iterations: int, **datasource) -> str:
    model = run.config["model"]
    path = os.path.join(run.workdir, f"{engine_id}.json")
    with open(path, "w") as f:
        json.dump({
            "id": engine_id, "version": "1",
            "engineFactory": run.config["engine_factory"],
            "datasource": {"params": {"appName": "bench", **datasource}},
            "algorithms": [{"name": "als", "params": {
                "rank": model["rank"], "numIterations": iterations,
                "lambda": model["lambda"],
                "implicitPrefs": bool(model["implicitPrefs"]),
                "alpha": model["alpha"],
                "seed": run.seed % (2**31 - 1),
            }}],
        }, f)
    return path


def _require_objective(als: dict) -> None:
    """A program from before the instance said which objective a job ran
    cannot be held to this cell's: the run fails, with no result line."""
    if als.get("objective") is None:
        raise RuntimeError("the instance does not say which objective the job ran "
                           "(kernels.als.objective): this checkout cannot run the cell")


def run(run) -> dict:
    from predictionio_tpu.data.storage import Storage

    shape = run.config["shape"]
    events = playcounts.play_events(shape, run.seed)
    run.say(f"events: {events['rows'].size:,} rate events (play counts, mean "
            f"{events['vals'].mean():.3f}, largest {int(events['vals'].max()):,}) "
            "made from the seed")
    train_job._write_events(run, "bench", events)
    engine_json = _engine_json(run, "bench", run.config["model"]["iterations"])
    run.say("events: written through write_columns")
    t_events = run.elapsed()

    # prime: see train_job; here one sweep is enough to compile everything
    mark = os.path.join(run.cache_dir(), "benchmark-primed-" + harness.program_fingerprint(
        run.root, os.path.abspath(run.root),
        json.dumps([shape, {**run.config["model"], "iterations": 0},
                    run.traffic.get("flags", [])], sort_keys=True)))
    if not os.path.exists(mark):
        run.say("prime: this compile cache has not seen this program at this "
                "shape; training one sweep in set-up")
        wall, _ = train_job._train(
            run, "prime", _engine_json(run, "bench-prime", 1, incremental=False))
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
        wall, rep = train_job._train(run, f"train{len(walls)}", engine_json, trace_dir)
        walls.append(wall)
        reports.append(rep)
        traces.append(train_job._reduce(run, trace_dir, rep))
        cc = rep.get("compile_cache", {})
        run.say(f"window: pio train returned 0 after {wall:.3f} s (to the entry "
                f"point {rep['start_s']:.2f}, inside it {rep['main_s']:.2f}, to exit "
                f"{rep['exit_s']:.2f}); {cc.get('misses')} persistent-cache misses "
                f"in {cc.get('requests')} compile requests")
        used = time.monotonic() - t_window
        if run.trace or used + statistics.median(walls) > run.seconds:
            break

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
    _require_objective(als)
    run.say(f"job: phases {instance['phase_timings']}; objective {als.get('objective')} "
            f"alpha {als.get('alpha')}; hot rows {als.get('hotRows')} in groups "
            f"{als.get('hotGroups')}; {als.get('solveSystemsPerSweep')} systems a sweep; "
            f"bucketing {als.get('bucketingSeconds')}; init {als.get('initSeconds')}; "
            f"sweeps {als.get('sweepSeconds')}; readback {als.get('readbackSeconds')}")
    rep = reports[-1]
    memory = harness.memory_peak(reports)
    run.say(f"memory: most held at once {memory}; allocator at exit {rep['memory'][0]}")

    reference = harness.load_module("references", run.config["reference"])
    blob = Storage.get_model_data_models().get(inst.id).models
    ok = reference.check_train(run, events, instance, blob)
    return {
        "correct": ok,
        "attempted": len(walls),
        "failed": 0,
        "device": rep["device"],
        "memory": memory,
        "end_to_end": {"setup_s": setup_s,
                       "train_device_s": statistics.median(t["busy_s"] for t in traces)},
        "train_s": walls[-1],
        "instance": instance,
        "config": run.config,
        "compile_cache": rep.get("compile_cache", {}),
        "trace": traces[-1],
    }
