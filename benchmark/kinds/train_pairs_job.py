"""Traffic kind ``train_pairs_job``: the window is one whole `pio train` of
an engine that trains on interaction pairs (the Two-Tower template).

Set-up makes the configuration's pairs from the seed (benchmark/pairs.py),
writes them as ``rate`` events into a fresh columnar store and, in a compile
cache that has not seen the program at this shape, trains once in an app of
its own for ONE epoch: the epoch program is the same for any number of
epochs. The window is one whole warm `pio train` child under the profiler,
process start to exit; ``train_device_s`` is the seconds in which an
operation ran on the device in that child, from its trace. Afterwards the
configuration's reference checks the instance and the stored model against
the pairs this module wrote.

The kind's first act, before an event is made, is to ask the checkout for
the row update (``predictionio_tpu.ops.twotower.adam_rows``): a program
without it would train these tables with dense Adam, some 20 ms a step and
minutes a job, and is refused at once instead.
"""

from __future__ import annotations

import importlib
import json
import os

import numpy as np

from benchmark import harness, pairs
from benchmark.kinds import train_job

#: what the checkout must export for this kind to run it
NEEDS = ("predictionio_tpu.ops.twotower", "adam_rows")


def require_row_update() -> None:
    module = importlib.import_module(NEEDS[0])
    if not hasattr(module, NEEDS[1]):
        raise RuntimeError(
            f"this checkout's {NEEDS[0]} exports no {NEEDS[1]}: it has no Adam "
            "step on the rows a batch gathered, and the cell is not run on "
            "dense Adam over whole tables")


def _engine_json(run, app: str, engine_id: str, epochs: int, seed: int) -> str:
    model = run.config["model"]
    path = os.path.join(run.workdir, f"{engine_id}.json")
    with open(path, "w") as f:
        json.dump({
            "id": engine_id, "version": "1",
            "engineFactory": run.config["engine_factory"],
            "datasource": {"params": {"appName": app, "eventNames": ["rate"]}},
            "algorithms": [{"name": "twotower", "params": {
                "embeddingDim": model["dim"], "batchSize": model["batch"],
                "epochs": epochs, "learningRate": model["learning_rate"],
                "temperature": model["temperature"],
                "gemmDtype": model["gemmDtype"], "seed": seed,
            }}],
        }, f)
    return path


def _write_events(run, app: str, events: dict) -> None:
    from predictionio_tpu.data.storage import Storage
    from predictionio_tpu.tools import commands

    shape = run.config["shape"]
    app_id = commands.app_new(app, out=lambda *_: None)[0].id

    def ids(n: int) -> np.ndarray:  # "0".."n-1", no wider than they are
        return np.arange(n).astype(f"U{len(str(n - 1))}")

    n = Storage.get_p_events().write_columns(
        app_id,
        event="rate",
        entity_type="user",
        entity_codes=events["rows"],
        entity_vocab=ids(shape["users"]),
        target_entity_type="item",
        target_codes=events["cols"],
        target_vocab=ids(shape["items"]),
        event_time_us=events["time_us"],
    )
    if n != events["rows"].size:
        raise RuntimeError(f"wrote {n} of {events['rows'].size} events")


def run(run) -> dict:
    require_row_update()
    from predictionio_tpu.data.storage import Storage

    reference = harness.load_module("references", run.config["reference"])
    shape, model = run.config["shape"], run.config["model"]
    seed = reference.model_seed(run.seed)
    # what the check draws from the seed alone (the initial tables, the first
    # epoch's permutation) is drawn beside the set-up, not after the window
    draws = reference.Draws(seed, shape, model)
    draws.start()
    events = pairs.pair_events(shape, run.seed)
    events["model_seed"], events["draws"] = seed, draws
    run.say(f"events: {events['rows'].size:,} pairs of {shape['users']:,} users and "
            f"{shape['items']:,} items made from the seed")
    _write_events(run, "bench", events)
    engine_json = _engine_json(run, "bench", "bench", model["epochs"], seed)
    run.say("events: written through write_columns")
    t_events = run.elapsed()

    # prime: see train_job; here one epoch is enough to compile everything
    mark = os.path.join(run.cache_dir(), "benchmark-primed-" + harness.program_fingerprint(
        run.root, os.path.abspath(run.root),
        json.dumps([shape, {**model, "epochs": 0}, run.traffic.get("flags", [])],
                   sort_keys=True)))
    if not os.path.exists(mark):
        run.say("prime: this compile cache has not seen this program at this "
                "shape; training one epoch in set-up")
        _write_events(run, "bench-prime", events)
        wall, _ = train_job._train(
            run, "prime", _engine_json(run, "bench-prime", "bench-prime", 1, seed))
        os.makedirs(run.cache_dir(), exist_ok=True)
        with open(mark, "w") as f:
            f.write(f"{wall:.1f}\n")
        run.say(f"prime: done in {wall:.1f} s")
    draws.join()
    setup_s = run.elapsed()
    run.say(f"window: begins (set-up {setup_s:.2f} s; events {t_events:.2f}, "
            f"prime {setup_s - t_events:.2f})")

    trace_dir = os.path.join(run.workdir, "trace0")
    wall, rep = train_job._train(run, "train0", engine_json, trace_dir)
    trace = train_job._reduce(run, trace_dir, rep)
    cc = rep.get("compile_cache", {})
    run.say(f"window: pio train returned 0 after {wall:.3f} s (to the entry point "
            f"{rep['start_s']:.2f}, inside it {rep['main_s']:.2f}, to exit "
            f"{rep['exit_s']:.2f}); {cc.get('misses')} persistent-cache misses in "
            f"{cc.get('requests')} compile requests")

    inst = Storage.get_meta_data_engine_instances().get_latest_completed(
        "bench", "1", "bench")
    if inst is None:
        raise RuntimeError("no COMPLETED instance after the window")
    instance = {
        "phase_timings": json.loads(inst.env["phase_timings"]),
        "device": json.loads(inst.env["device"]),
        "kernels": json.loads(inst.env["kernels"]),
    }
    tt = instance["kernels"].get("twotower", {})
    run.say(f"job: phases {instance['phase_timings']}; read {tt.get('readSeconds')}; "
            f"epochs {tt.get('epochSeconds')}; {tt.get('stepMs')} ms a step; "
            f"{tt.get('rowsTouchedPerStep')} rows a step")
    memory = harness.memory_peak([rep])
    run.say(f"memory: most held at once {memory}; allocator at exit {rep['memory'][0]}")

    blob = Storage.get_model_data_models().get(inst.id).models
    ok = reference.check_train(run, events, instance, blob)
    return {
        "correct": ok,
        "attempted": 1,
        "failed": 0,
        "device": rep["device"],
        "memory": memory,
        "end_to_end": {"setup_s": setup_s, "train_device_s": trace["busy_s"]},
        "train_s": wall,
        "instance": instance,
        "config": run.config,
        "compile_cache": cc,
        "trace": trace,
    }
