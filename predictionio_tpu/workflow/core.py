"""Core workflow: train and evaluation runners with lineage records.

Parity: ``core/workflow/CoreWorkflow.scala`` (``runTrain`` — train, persist
models, insert COMPLETED ``EngineInstance`` with timings; ``runEvaluation``)
and the argument surface of ``core/workflow/WorkflowParams.scala``.
"""

from __future__ import annotations

import dataclasses
import datetime as _dt
import json
import logging
import uuid

from predictionio_tpu.controller.context import (
    WorkflowContext,
    device_info,
    device_memory,
)
from predictionio_tpu.controller.engine import EngineParams
from predictionio_tpu.controller.evaluation import (
    EngineParamsGenerator,
    Evaluation,
    MetricEvaluator,
    MetricEvaluatorResult,
)
from predictionio_tpu.controller.params import params_to_json
from predictionio_tpu.data.storage import Storage
from predictionio_tpu.data.storage.base import EngineInstance, EvaluationInstance, Model
from predictionio_tpu.utils import spans
from predictionio_tpu.utils.spans import CompileLedger, span
from predictionio_tpu.workflow.engine_json import EngineVariant

__all__ = ["WorkflowParams", "run_train", "run_evaluation"]

logger = logging.getLogger(__name__)


@dataclasses.dataclass(frozen=True)
class WorkflowParams:
    """Invocation flags (parity: ``WorkflowParams.scala``)."""

    batch: str = ""
    verbose: int = 0
    save_model: bool = True
    skip_sanity_check: bool = False
    stop_after_read: bool = False
    stop_after_prepare: bool = False
    #: seed algorithms from the latest COMPLETED instance's model
    #: (`pio train --warm-start`) — retrains converge in fewer sweeps
    warm_start: bool = False


def _now() -> _dt.datetime:
    return _dt.datetime.now(_dt.timezone.utc)


def _params_json(ep: EngineParams) -> dict[str, str]:
    return {
        "datasource_params": json.dumps(params_to_json(ep.datasource)),
        "preparator_params": json.dumps(params_to_json(ep.preparator)),
        "algorithms_params": json.dumps(
            [{"name": n, "params": params_to_json(p)} for n, p in ep.algorithms]
        ),
        "serving_params": json.dumps(params_to_json(ep.serving)),
    }


def _phase_cpu(timings: dict) -> dict:
    """``{phase: CPU seconds}`` for the phases of ``timings``, from the
    spans closed on this thread's collector (taken here): the one place
    where a phase's span becomes its CPU time. Empty where the thread has
    no collector that takes CPU time. What the caller measured itself
    (``timings["cpu"]``: `pio train`'s ``startup``) is kept."""
    collector = spans.current()
    if collector is None or not collector.cpu:
        return {}
    records = collector.take()
    by_span = spans.cpu_ms(records)
    # Engine.train's algorithms share one span name, in the order of
    # their keys
    algorithms = iter(r.cpu_ns / 1e6 for r in records if r.name == "train.algorithm")
    cpu = dict(timings.get("cpu") or {})
    for phase in timings:
        ms = (
            next(algorithms, None) if phase.startswith("train:")
            else by_span.get("train." + phase)
        )
        if ms is not None:
            cpu[phase] = round(ms / 1e3, 3)
    if "serialize" in cpu and "blob_write" in cpu:
        cpu["publish"] = round(cpu["serialize"] + cpu["blob_write"], 3)
    return cpu


def run_train(
    variant: EngineVariant,
    ctx: WorkflowContext,
    workflow_params: WorkflowParams = WorkflowParams(),
    engine_id: str | None = None,
    engine_version: str = "",
    phase_timings: dict | None = None,
) -> EngineInstance:
    """Train an engine variant end-to-end and record its lineage.

    Flow (parity: ``CoreWorkflow.runTrain``): insert a TRAINING
    ``EngineInstance`` -> ``Engine.train`` -> persist model blob into the
    ``Models`` repo -> update the instance to COMPLETED with timings and
    the resolved component params. On error the instance is marked FAILED
    and the exception re-raised.

    The instance's ``env["phase_timings"]`` holds, in seconds, what the
    caller measured before this call (``phase_timings``: `pio train`
    passes ``startup`` and ``backend_init``), ``read``, ``prepare`` and
    ``train:<algorithm>`` from ``Engine.train``, and ``publish`` with its
    parts ``serialize`` and ``blob_write``; under ``cpu`` the calling
    thread's CPU seconds in each of them, where the thread's collector
    takes CPU time (`pio train`'s does: a phase whose CPU is far under
    its wall waited, one near it computed); ``env["kernels"]["compile"]``
    is the compile ledger's table for this job (``utils/spans.py``).
    """
    ledger = CompileLedger.install()
    compiled_before = ledger.snapshot()
    engine = variant.build_engine()
    engine_params = variant.engine_params(engine)
    instances = Storage.get_meta_data_engine_instances()
    # Multi-host: every host participates in training (collectives need
    # all of them) but only host 0 — the coordinator, i.e. the Spark-driver
    # role — writes metadata and model blobs.
    is_writer = ctx.host_index == 0

    instance = EngineInstance(
        id=uuid.uuid4().hex,
        status="TRAINING",
        start_time=_now(),
        end_time=_now(),
        engine_id=engine_id or variant.id,
        engine_version=engine_version or variant.version,
        engine_variant=variant.id,
        engine_factory=variant.engine_factory,
        batch=workflow_params.batch,
        mesh_conf=(
            {"devices": str(ctx.num_devices), "axes": str(dict(ctx.mesh.shape))}
            if ctx.has_mesh
            else {}
        ),
        **_params_json(engine_params),
    )
    if is_writer:
        instances.insert(instance)
    try:
        warm_models = None
        warm_from = None
        if workflow_params.warm_start:
            prev = instances.get_latest_completed(
                instance.engine_id, instance.engine_version,
                instance.engine_variant,
            )
            if ctx.num_hosts > 1:
                # every host must seed from the SAME predecessor — another
                # train completing between per-host lookups would otherwise
                # give hosts different (or no) warm models and silently
                # break the identical-init invariant of the sharded train.
                # Host 0's choice wins, via the trusted rendezvous channel.
                from predictionio_tpu.parallel.exchange import allgather_objects

                prev_id = allgather_objects(
                    prev.id if (is_writer and prev is not None) else None
                )[0]
                if prev_id is None:
                    prev = None
                elif prev is None or prev.id != prev_id:
                    prev = instances.get(prev_id)
            blob = (
                Storage.get_model_data_models().get(prev.id)
                if prev is not None
                else None
            )
            if blob is not None:
                try:
                    warm_models = engine.models_from_bytes(
                        engine_params, prev.id, blob.models
                    )
                    warm_from = prev.id
                except Exception as e:
                    # a changed algorithm list raises ValueError; a stale
                    # pickle raises AttributeError/ModuleNotFoundError/
                    # UnpicklingError — ANY hydration failure must fall
                    # back to cold start, not turn the retrain flag into
                    # a hard failure (and in multi-host, a crash here
                    # would strand the other hosts at the consensus
                    # allgather below)
                    logger.warning(
                        "--warm-start: could not hydrate predecessor model "
                        "%s (%s: %s); cold start",
                        prev.id, type(e).__name__, e,
                    )
            else:
                logger.warning(
                    "--warm-start requested but no completed instance with a "
                    "stored model exists for this engine/variant; cold start"
                )
            if ctx.num_hosts > 1:
                # ALL hosts must agree to warm-start (and from the same
                # blob): a host whose models repo lacks the blob would
                # otherwise cold-init while others warm-init, silently
                # breaking the identical-init invariant of the sharded
                # train
                from predictionio_tpu.parallel.exchange import allgather_objects

                have = allgather_objects(warm_from)
                if any(h != have[0] for h in have):
                    logger.warning(
                        "--warm-start: not every host could load the "
                        "predecessor model (%s); cold start everywhere",
                        have,
                    )
                    warm_models = None
                    warm_from = None
            if warm_from is not None:
                logger.info(
                    "Warm-starting from completed instance %s", warm_from
                )
        timings: dict = dict(phase_timings or {})
        models = engine.train(
            ctx,
            engine_params,
            sanity_check=not workflow_params.skip_sanity_check,
            stop_after_read=workflow_params.stop_after_read,
            stop_after_prepare=workflow_params.stop_after_prepare,
            timings=timings,
            warm_models=warm_models,
        )
        if workflow_params.stop_after_read or workflow_params.stop_after_prepare:
            # debugging run — nothing to persist (parity: reference aborts
            # after printing the data); record it as not-completed.
            instance = instance.with_status("STOPPED", end_time=_now())
            if is_writer:
                instances.update(instance)
            return instance
        if workflow_params.save_model and is_writer:
            with span("train.serialize") as serialize:
                blob = engine.models_to_bytes(instance.id, engine_params, models)
            with span("train.blob_write") as blob_write:
                Storage.get_model_data_models().insert(
                    Model(id=instance.id, models=blob)
                )
            timings["serialize"] = round(serialize.seconds, 3)
            timings["blob_write"] = round(blob_write.seconds, 3)
            timings["publish"] = round(
                serialize.seconds + blob_write.seconds, 3
            )
            logger.info("Saved model blob for instance %s (%d bytes)", instance.id, len(blob))
        cpu = _phase_cpu(timings)
        if cpu:
            timings["cpu"] = cpu
        # what this job traced, lowered and compiled (or loaded from the
        # persistent cache), per jitted function
        ctx.run_info["compile"] = ledger.table(since=compiled_before)
        # where it ran and which kernels it took, beside the timings: a
        # reader (chip_smoke.py, a benchmark) tells a device run from a
        # quiet host run from the instance alone, without importing jax
        env = {
            **instance.env,
            "phase_timings": json.dumps(timings),
            "device": json.dumps({**device_info(), **device_memory()}),
            "kernels": json.dumps(ctx.run_info),
        }
        if warm_from is not None:
            env["warm_start_from"] = warm_from
        instance = dataclasses.replace(
            instance,
            status="COMPLETED",
            end_time=_now(),
            env=env,
        )
        if is_writer:
            instances.update(instance)
        logger.info(
            "Training completed: instance %s in %.1fs",
            instance.id,
            (instance.end_time - instance.start_time).total_seconds(),
        )
        return instance
    except Exception:
        if is_writer:
            instances.update(instance.with_status("FAILED", end_time=_now()))
        raise


def run_evaluation(
    evaluation: Evaluation,
    generator: EngineParamsGenerator,
    ctx: WorkflowContext,
    workflow_params: WorkflowParams = WorkflowParams(),
    evaluation_class: str = "",
    generator_class: str = "",
) -> tuple[EvaluationInstance, MetricEvaluatorResult]:
    """Run a parameter sweep and record an ``EvaluationInstance``
    (parity: ``CoreWorkflow.runEvaluation`` + ``EvaluationWorkflow``)."""
    instances = Storage.get_meta_data_evaluation_instances()
    instance = EvaluationInstance(
        id=uuid.uuid4().hex,
        status="EVALUATING",
        start_time=_now(),
        end_time=_now(),
        evaluation_class=evaluation_class or type(evaluation).__name__,
        engine_params_generator_class=generator_class or type(generator).__name__,
        batch=workflow_params.batch,
    )
    instances.insert(instance)
    try:
        evaluator = MetricEvaluator(
            metric=evaluation.metric, other_metrics=tuple(evaluation.other_metrics)
        )
        result = evaluator.evaluate_base(
            ctx, evaluation.engine, list(generator.engine_params_list)
        )
        instance = dataclasses.replace(
            instance,
            status="EVALCOMPLETED",
            end_time=_now(),
            evaluator_results=result.leaderboard(),
            evaluator_results_json=json.dumps(result.to_json(), default=str),
        )
        instances.update(instance)
        return instance, result
    except Exception:
        instances.update(dataclasses.replace(instance, status="FAILED", end_time=_now()))
        raise
