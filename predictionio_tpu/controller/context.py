"""Workflow context — the TPU-native replacement for the Spark ``sc``.

Everywhere the reference threads a ``SparkContext`` through the DASE stack
(``core/controller/PDataSource.scala`` ``readTraining(sc)``,
``core/core/BaseAlgorithm.scala`` ``trainBase(sc, pd)``), this framework
threads a :class:`WorkflowContext`: the device mesh the job runs on, the
host topology for sharded input reads, and run metadata. Components that
don't care about devices simply ignore it — exactly how local (L*)
components ignore ``sc`` in the reference.

Design note (tpu-first): the context does NOT expose a task-scheduling API.
There is no analog of ``rdd.map`` — distribution happens *inside* jitted
functions via ``jax.sharding`` annotations, and the context's job is only
to say which mesh to annotate against and which shard of the input files
this host owns.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Sequence

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec

__all__ = [
    "WorkflowContext",
    "device_info",
    "device_memory",
    "local_context",
    "mesh_context",
]

#: Canonical mesh-axis names used across the framework. ``data`` shards the
#: batch / entity dimension, ``model`` shards factor/feature dimensions.
DATA_AXIS = "data"
MODEL_AXIS = "model"


@dataclasses.dataclass(frozen=True)
class WorkflowContext:
    """Everything a DASE component may need from the runtime.

    Attributes:
      mesh: the ``jax.sharding.Mesh`` training runs under, or ``None`` for
        purely local components (the L* path of the reference).
      host_index / num_hosts: this process's slot in a multi-host job —
        drives deterministic shard selection in ``PEventStore.find``
        (replaces HBase region locality, SURVEY.md section 6.8).
      batch: free-form run label (parity: ``WorkflowParams.batch``).
      verbose: verbosity level (parity: ``WorkflowParams.verbose``).
    """

    mesh: Mesh | None = None
    host_index: int = 0
    num_hosts: int = 1
    batch: str = ""
    verbose: int = 0
    #: previous trained model for THIS algorithm when the run is a warm
    #: retrain (``pio train --warm-start``); set per-algorithm by
    #: ``Engine.train``. Algorithms that support it seed their optimizer
    #: state from it (SURVEY.md section 8.3 "incremental re-index" —
    #: the reference gets cheap retrains from Spark RDD caching).
    warm_model: Any = None
    #: facts the components record about THIS run — which kernel each
    #: algorithm actually took (ALS solver and bucketing, two-tower CE
    #: path). ``run_train`` copies it into the engine instance's ``env``
    #: beside the device block, so a reader can tell a device run from a
    #: quiet host run without importing jax. Shared (not copied) by
    #: ``dataclasses.replace``.
    run_info: dict = dataclasses.field(default_factory=dict, compare=False)

    # -- sharding helpers ---------------------------------------------------
    @property
    def has_mesh(self) -> bool:
        return self.mesh is not None and not self.mesh.empty

    def sharding(self, *spec: Any) -> NamedSharding:
        """NamedSharding on this context's mesh for the given PartitionSpec
        entries, e.g. ``ctx.sharding('data', None)`` for row-sharded 2-D."""
        if self.mesh is None:
            raise ValueError("WorkflowContext has no mesh; cannot build shardings")
        return NamedSharding(self.mesh, PartitionSpec(*spec))

    def replicated(self) -> NamedSharding:
        if self.mesh is None:
            raise ValueError("WorkflowContext has no mesh; cannot build shardings")
        return NamedSharding(self.mesh, PartitionSpec())

    @property
    def num_devices(self) -> int:
        return self.mesh.size if self.mesh is not None else 1


def device_info() -> dict:
    """Where this process computes, as JAX reports it. Initialises the
    backend: call it only from a process that uses (or may take) the
    device — a chip belongs to one process at a time."""
    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "deviceKind": devices[0].device_kind,
        "count": len(devices),
    }


def device_memory() -> dict:
    """Device 0's allocator counters where the backend keeps them (TPU
    does, XLA:CPU returns nothing): bytes in use, the peak since process
    start, and the limit."""
    stats = jax.devices()[0].memory_stats() or {}
    return {
        out: int(stats[key])
        for out, key in (
            ("bytesInUse", "bytes_in_use"),
            ("peakBytesInUse", "peak_bytes_in_use"),
            ("bytesLimit", "bytes_limit"),
        )
        if key in stats
    }


def local_context(batch: str = "", verbose: int = 0) -> WorkflowContext:
    """A mesh-less context for local algorithms and unit tests (the analog of
    the reference's ``local[*]`` SparkContext fixture)."""
    return WorkflowContext(mesh=None, batch=batch, verbose=verbose)


def mesh_context(
    axis_sizes: Sequence[int] | None = None,
    axis_names: Sequence[str] = (DATA_AXIS, MODEL_AXIS),
    devices: Sequence[jax.Device] | None = None,
    batch: str = "",
    verbose: int = 0,
) -> WorkflowContext:
    """Build a context over the available devices.

    ``axis_sizes=None`` puts every device on the ``data`` axis with a
    ``model`` axis of 1 — pure data parallelism, the safe default for the
    ALS/NB workloads this framework ships with. Over ONE device that is
    the mesh-less context: a 1x1 mesh shards nothing, yet ``mesh is not
    None`` routes training onto the sharded path (Cholesky, host
    bucketing, XLA cross-entropy) and away from the single-device
    kernels. Explicit ``axis_sizes`` always build the mesh asked for.
    """
    devs = list(devices if devices is not None else jax.devices())
    if axis_sizes is None:
        if len(devs) == 1:
            return local_context(batch=batch, verbose=verbose)
        axis_sizes = [len(devs)] + [1] * (len(axis_names) - 1)
    if len(axis_sizes) != len(axis_names):
        raise ValueError(
            f"axis_sizes {axis_sizes} does not match axis_names {axis_names}"
        )
    # Explicit axes, stated rather than inherited from make_mesh's default:
    # the sharded kernels are written against sharding-in-types
    # (``reshard``, ``out_sharding=``), and eager code on a sharded table
    # must say where its result lives (``parallel.sharding.gather_rows``)
    mesh = jax.make_mesh(
        tuple(axis_sizes), tuple(axis_names), devices=devs,
        axis_types=(AxisType.Explicit,) * len(axis_names),
    )
    return WorkflowContext(
        mesh=mesh,
        host_index=jax.process_index(),
        num_hosts=jax.process_count(),
        batch=batch,
        verbose=verbose,
    )
