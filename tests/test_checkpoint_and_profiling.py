"""Checkpoint/resume, per-phase timings, and profiler endpoint tests."""

import json

import numpy as np
import pytest

from predictionio_tpu.controller import local_context
from predictionio_tpu.ops.als import ALSConfig, train_als
from predictionio_tpu.workflow import load_engine_variant, run_train


def synthetic(seed=0):
    rng = np.random.default_rng(seed)
    U = rng.normal(size=(40, 4))
    V = rng.normal(size=(30, 4))
    full = U @ V.T / 2 + 3
    mask = rng.random((40, 30)) < 0.4
    rows, cols = np.nonzero(mask)
    return rows, cols, full[rows, cols].astype(np.float32)


class TestALSCheckpointing:
    def test_resume_matches_uninterrupted(self, tmp_path):
        rows, cols, vals = synthetic()
        # uninterrupted 6-iteration run
        base = train_als(rows, cols, vals, 40, 30, ALSConfig(rank=4, iterations=6, seed=1))
        # run 1: checkpoints every 2 steps but "preempted" after 4 (we run
        # iterations=4 with the same dir)
        ckpt = str(tmp_path / "ck")
        train_als(
            rows, cols, vals, 40, 30,
            ALSConfig(rank=4, iterations=4, seed=1, checkpoint_dir=ckpt,
                      checkpoint_interval=2),
        )
        # run 2: asks for 6 iterations; resumes from step 4
        resumed = train_als(
            rows, cols, vals, 40, 30,
            ALSConfig(rank=4, iterations=6, seed=1, checkpoint_dir=ckpt,
                      checkpoint_interval=2),
        )
        np.testing.assert_allclose(
            np.asarray(base.user), np.asarray(resumed.user), rtol=1e-5, atol=1e-6
        )

    def test_checkpoint_restores_across_mesh_shapes(self, tmp_path):
        """Checkpoints are written at the canonical (num_rows+1, K) shape,
        so a run preempted on one mesh resumes on a different model-axis
        size (round-2 advisor finding: padded shapes were mesh-bound)."""
        from predictionio_tpu.controller.context import mesh_context

        rows, cols, vals = synthetic()
        ckpt = str(tmp_path / "ck_mesh")
        cfg = dict(rank=4, iterations=4, seed=1, checkpoint_dir=ckpt,
                   checkpoint_interval=2)
        ctx_a = mesh_context(axis_sizes=(4, 2))  # model axis = 2
        train_als(rows, cols, vals, 40, 30, ALSConfig(**cfg),
                  mesh=ctx_a.mesh)
        # resume the finished run on model axis = 4 and on no mesh at all:
        # both must restore step 4 instead of crashing on a shape mismatch
        ctx_b = mesh_context(axis_sizes=(2, 4))
        on_b = train_als(rows, cols, vals, 40, 30, ALSConfig(**cfg),
                         mesh=ctx_b.mesh)
        single = train_als(rows, cols, vals, 40, 30, ALSConfig(**cfg))
        np.testing.assert_allclose(
            np.asarray(on_b.user), np.asarray(single.user), rtol=1e-4, atol=1e-5
        )

    def test_checkpoint_steps_recorded(self, tmp_path):
        from predictionio_tpu.utils.checkpoint import CheckpointManager

        rows, cols, vals = synthetic()
        ckpt = str(tmp_path / "ck2")
        train_als(
            rows, cols, vals, 40, 30,
            ALSConfig(rank=4, iterations=5, checkpoint_dir=ckpt, checkpoint_interval=2),
        )
        m = CheckpointManager(ckpt)
        assert m.latest_step() == 5
        state = m.restore(like=None)
        assert state["user"].shape == (41, 4)  # includes sentinel row
        m.close()


class TestPhaseTimings:
    def test_every_leaf_of_an_als_train_lies_in_a_named_phase(self):
        """The main thread's leaves from the upload to the host copy: no
        eager program of the job (the two slices that strip the sentinel
        rows were the last without) runs outside a span, and the readback's
        seconds hold both of its spans."""
        from predictionio_tpu.ops.als import factors_to_host
        from predictionio_tpu.utils import spans

        rows, cols, vals = synthetic()
        info = {}
        collector = spans.Collector(cpu=True)
        unbound = spans.bind(collector)
        try:
            factors = train_als(rows, cols, vals, 40, 30,
                                ALSConfig(rank=4, iterations=2, seed=1), info=info)
            stripped = info["readbackSeconds"]
            user, item = factors_to_host(info, factors.user, factors.item)
        finally:
            spans.bind(unbound)
        assert user.shape == (40, 4) and item.shape == (30, 4)
        records = collector.take()
        names = [r.name for r in records]
        assert names[-4:] == ["train.sweep", "train.sweep", "train.readback",
                              "train.readback"]
        assert set(names[:-4]) == {"train.transfer", "train.bucketing", "train.init"}
        # each phase begins where the one before it ended: a few lines of
        # Python between them, never a program
        for before, after in zip(records, records[1:]):
            assert 0 <= after.start_ns - before.end_ns < 500_000_000, (
                before.name, after.name)
        assert info["readbackSeconds"] >= stripped >= 0
        assert info["readbackSeconds"] == pytest.approx(
            spans.durations_ms(records)["train.readback"] / 1e3, abs=0.002)
        assert len(info["sweepCpuSeconds"]) == len(info["sweepSeconds"]) == 2

    def test_engine_instance_records_phase_timings(self, memory_storage_env):
        variant = load_engine_variant({
            "id": "fake-engine", "version": "0.1",
            "engineFactory": "fake_dase:engine0",
            "datasource": {"params": {"base": 10}},
            "algorithms": [{"name": "a0", "params": {"mult": 2}}],
        })
        instance = run_train(variant, local_context())
        timings = json.loads(instance.env["phase_timings"])
        assert set(timings) == {
            "read", "prepare", "train:a0", "serialize", "blob_write", "publish"}
        assert all(isinstance(v, float) for v in timings.values())
        assert timings["publish"] == pytest.approx(
            timings["serialize"] + timings["blob_write"], abs=0.002)
        # what the caller measured before the call rides along
        instance = run_train(
            variant, local_context(), phase_timings={"startup": 1.5})
        assert json.loads(instance.env["phase_timings"])["startup"] == 1.5

    def test_a_thread_whose_collector_takes_cpu_gets_every_phases_cpu(
            self, memory_storage_env):
        """``phase_timings.cpu``: made in one place from the collector's
        records, for every phase the instance holds; what the caller
        measured (`pio train`: ``startup``) rides along; no collector that
        takes CPU time, no ``cpu``."""
        from predictionio_tpu.utils import spans

        variant = load_engine_variant({
            "id": "fake-engine", "version": "0.1",
            "engineFactory": "fake_dase:engine0",
            "datasource": {"params": {"base": 10}},
            "algorithms": [{"name": "a0", "params": {"mult": 2}},
                           {"name": "a1", "params": {"mult": 3}}],
        })
        unbound = spans.bind(spans.Collector(cpu=True))
        try:
            instance = run_train(
                variant, local_context(),
                phase_timings={"startup": 1.5, "cpu": {"startup": 0.5}})
        finally:
            spans.bind(unbound)
        timings = json.loads(instance.env["phase_timings"])
        cpu = timings.pop("cpu")
        assert set(cpu) == set(timings) == {
            "startup", "read", "prepare", "train:a0", "train:a1",
            "serialize", "blob_write", "publish"}
        assert cpu["startup"] == 0.5
        # a thread's CPU time in a span lies inside the span's wall
        assert all(0 <= cpu[k] <= timings[k] + 0.002 for k in cpu), (cpu, timings)
        assert cpu["publish"] == pytest.approx(
            cpu["serialize"] + cpu["blob_write"], abs=0.002)
        for collector in (None, spans.Collector()):
            unbound = spans.bind(collector)
            try:
                instance = run_train(variant, local_context())
            finally:
                spans.bind(unbound)
            assert "cpu" not in json.loads(instance.env["phase_timings"])

    def test_a_toy_pio_train_instance_holds_the_job_by_phase(
        self, memory_storage_env, tmp_path
    ):
        """`pio train` through the console on the recommendation template:
        the new keys, and the old ones with their old meaning."""
        from predictionio_tpu.tools import commands
        from predictionio_tpu.tools.console import main
        from predictionio_tpu.utils import spans

        commands.app_new("spanapp", out=lambda *_: None)
        rng = np.random.default_rng(0)
        src = tmp_path / "events.jsonl"
        with open(src, "w") as f:
            for u in range(40):
                for i in rng.choice(30, 8, replace=False):
                    f.write(json.dumps({
                        "event": "rate", "entityType": "user",
                        "entityId": str(u), "targetEntityType": "item",
                        "targetEntityId": str(i),
                        "properties": {"rating": float(rng.integers(1, 6))},
                    }) + "\n")
        commands.import_events("spanapp", str(src), out=lambda *_: None)
        ej = tmp_path / "engine.json"
        ej.write_text(json.dumps({
            "id": "span-engine", "version": "1",
            "engineFactory":
                "predictionio_tpu.templates.recommendation:engine_factory",
            "datasource": {"params": {"appName": "spanapp"}},
            "algorithms": [{"name": "als", "params": {
                "rank": 8, "numIterations": 3, "lambda": 0.05, "seed": 3}}],
        }))
        assert main(["train", "--engine-json", str(ej), "--mesh", "none"]) == 0
        assert spans.current() is None  # the job's collector is unbound again
        inst = memory_storage_env.get_meta_data_engine_instances(
        ).get_latest_completed("span-engine", "1", "span-engine")
        timings = json.loads(inst.env["phase_timings"])
        cpu = timings.pop("cpu")
        assert set(cpu) == set(timings) == {
            "startup", "backend_init", "read", "prepare", "train:als",
            "serialize", "blob_write", "publish"}
        assert timings["startup"] > 0  # this process is older than that
        assert cpu["startup"] > 0  # the process's CPU seconds by then
        assert all(0 <= cpu[k] <= timings[k] + 0.002 for k in cpu
                   if k != "startup"), (cpu, timings)
        kernels = json.loads(inst.env["kernels"])
        als = kernels["als"]
        assert len(als["sweepSeconds"]) == 3  # one span a sweep, as before
        assert len(als["sweepCpuSeconds"]) == 3
        assert all(0 <= c <= s + 0.002 for c, s in zip(
            als["sweepCpuSeconds"], als["sweepSeconds"]))
        # the first sweep traces and lowers on this thread: it computes
        assert als["sweepCpuSeconds"][0] > 0
        # bucketingSeconds keeps its meaning: transfer, sort and fill
        assert als["bucketingSeconds"] >= als["transferSeconds"] >= 0
        assert als["readbackSeconds"] >= 0 and als["initSeconds"] >= 0
        assert timings["train:als"] >= sum(als["sweepSeconds"])
        sweep = kernels["compile"]["als_sweep"]
        assert sweep["traces"] == sweep["lowers"] == sweep["compiles"] == 1
        assert sweep["traceSeconds"] > 0 and sweep["lowerSeconds"] > 0
        assert sweep["loadSeconds"] > 0
        assert {"cacheRequests", "cacheHits", "cacheMisses",
                "cacheRetrievalSeconds"} <= set(sweep)
        # the first sweep carries the three of them
        assert (sweep["traceSeconds"] + sweep["lowerSeconds"]
                + sweep["loadSeconds"]) <= als["sweepSeconds"][0] + 0.01


class TestProfilerEndpoint:
    def test_start_stop_round_trip(self, memory_storage_env, tmp_path):
        from predictionio_tpu.workflow.serving import QueryService

        variant = load_engine_variant({
            "id": "fake-engine", "version": "0.1",
            "engineFactory": "fake_dase:engine0",
            "datasource": {"params": {"base": 10}},
            "algorithms": [{"name": "a0", "params": {"mult": 2}}],
        })
        run_train(variant, local_context())
        qs = QueryService(variant)
        log_dir = str(tmp_path / "prof")
        r = qs.dispatch("POST", "/profiler/start", {}, {"logDir": log_dir})
        assert r.status == 200
        qs.handle_query(3)  # traced work
        r2 = qs.dispatch("POST", "/profiler/stop", {})
        assert r2.status == 200
        # stopping again errors cleanly
        assert qs.dispatch("POST", "/profiler/stop", {}).status == 409
        import os

        assert os.path.isdir(log_dir), "trace dir written"

    def test_the_python_tracer_is_off_unless_asked_for(
        self, memory_storage_env, tmp_path, monkeypatch
    ):
        import jax

        from predictionio_tpu.workflow.serving import QueryService

        variant = load_engine_variant({
            "id": "fake-engine", "version": "0.1",
            "engineFactory": "fake_dase:engine0",
            "datasource": {"params": {"base": 10}},
            "algorithms": [{"name": "a0", "params": {"mult": 2}}],
        })
        run_train(variant, local_context())
        qs = QueryService(variant)
        started = []
        monkeypatch.setattr(
            jax.profiler, "start_trace",
            lambda log_dir, profiler_options=None: started.append(
                (log_dir, profiler_options.python_tracer_level,
                 profiler_options.host_tracer_level)),
        )
        log_dir = str(tmp_path / "prof")
        for body in ({"logDir": log_dir},
                     {"logDir": log_dir, "pythonTracer": True},
                     {"logDir": log_dir, "pythonTracer": "yes"}):
            assert qs.dispatch("POST", "/profiler/start", {}, body).status == 200
        assert started == [(log_dir, 0, 2), (log_dir, 1, 2), (log_dir, 0, 2)]
