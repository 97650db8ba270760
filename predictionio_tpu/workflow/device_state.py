"""Device-resident serving state — the ``--pin-model`` cache tier.

ALX (arxiv 2112.02194) keeps factor state device-resident across steps
instead of re-staging it per step; this module applies the same recipe
to the query path. When a :class:`~predictionio_tpu.serving.cache
.CacheConfig` enables ``pin_model``, each successful (re)load pins the
deployed models' scoring state on the accelerator ONCE per model
generation:

* factor/embedding matrices are ``device_put`` once and reused by every
  request (no per-request host->device staging);
* the jitted score+top-K programs those matrices feed are bucket-keyed
  on static ``k`` (``ops.als.top_k_items_batch``), so after the
  micro-batcher's warm-up — which flows through this very state — live
  traffic re-traces nothing;
* index-buffer donation was evaluated and deliberately omitted: the
  (chunk,) int32 staging buffer can never alias the larger top-K
  outputs, so donating it buys nothing and only emits warnings.

Algorithms opt in by implementing ``pin_model_for_serving(model) ->
(model, bytes_pinned)``; anything else is served untouched. This module
lives in ``workflow/`` — NOT ``serving/`` — because the serving package
must stay importable without jax (tier-1 CI guards it); jax itself is
imported lazily inside the functions so merely importing the workflow
keeps paying nothing.

The ``--ann`` retrieval tier rides the same boundary and the same
generation lifecycle: :func:`build_ann_pairs` asks each algorithm that
implements ``build_ann_for_serving(model, ann_config) -> (model,
info)`` to cluster its item factors into an on-device IVF index
(:mod:`predictionio_tpu.ops.ivf`) once per model generation, and
:func:`release_pairs` drops both the pinned factors AND the superseded
index when ``/reload`` swaps generations — ANN state hot-swaps exactly
like pinned factors.
"""

from __future__ import annotations

import logging
from typing import Sequence

from predictionio_tpu.templates.retrieval import serving_state

__all__ = [
    "DeviceUnavailableError",
    "pin_pairs",
    "release_pairs",
    "build_ann_pairs",
    "bytes_by_dtype",
    "aot_stats",
    "serving_device",
    "set_rows",
    "append_rows",
    "swap_side_rows",
    "update_ann_items",
    "shard_count",
]

logger = logging.getLogger(__name__)


class DeviceUnavailableError(RuntimeError):
    """A device flag cannot be honoured: this process cannot open the JAX
    backend (on a TPU host, another process holds the chip)."""

    #: ``pio deploy`` exits with this code (sysexits EX_UNAVAILABLE), so
    #: the fleet supervisor can tell "no chip for this replica" from a
    #: crash and does not respawn it (``fleet/supervisor.py``)
    exit_code = 69


def pin_pairs(
    pairs: Sequence, shard: bool = False, quantize: str | None = None,
    aot=None, instance_id: str | None = None,
) -> tuple[list, int]:
    """Pin every (algorithm, model) pair that supports it.

    Returns ``(pairs, bytes_pinned)`` — the possibly-replaced pair list
    and the total device bytes now held by pinned state (0 when nothing
    opted in or jax is unavailable). An algorithm's pin hook raising is
    best-effort — that pair is served unpinned rather than failing the
    load. A BACKEND that cannot be opened is not: the operator asked
    for device-resident state, and this process has no device (on a TPU
    host, another process holds the chip), so the load fails with
    :class:`DeviceUnavailableError` instead of serving from host arrays
    under a device flag.

    ``shard=True`` (``pio deploy --shard-factors``) prefers each
    algorithm's ``shard_model_for_serving`` hook — pin factor SHARDS
    per device over a one-axis model mesh instead of a full replica, so
    per-device factor memory is ``O(table / num_devices)`` — falling
    back to plain pinning when the hook is absent (or the host has one
    device, where sharding IS replication).

    ``quantize`` (``pio deploy --quantize int8``) prefers the
    ``quantize_model_for_serving(model, mode, shard)`` hook above both:
    factor tables pin as int8 codes + per-row f32 scales (``ops/quant``)
    so per-device factor bytes drop another ~4x ON TOP of the ``/S``
    from sharding — the two tiers compose multiplicatively. Hooks set
    the state's ``bytes_by_dtype`` so :func:`bytes_by_dtype` can report
    the served per-dtype ledger, not recomputed shape math.

    ``aot`` (a :class:`predictionio_tpu.workflow.aot.AotConfig` with
    ``enabled``, the ``pio deploy --aot`` tier) makes the replica BOOT
    BY DESERIALIZING: after pinning, the generation's exported serving
    programs are loaded from ``<aot.root>/<instance_id>/``, verified
    (fingerprint + per-blob SHA-256), warmed once, and attached as
    the state's ``aot`` — so the serving path compiles NOTHING at request
    time. Any load failure logs loudly and serves through the jitted
    path (tier 2 with the persistent compilation cache, else tier 3),
    bit-identical by construction; the tier report lands on the state's
    ``aot_report`` for /stats.json."""
    try:
        import jax  # noqa: F401  (availability probe only)
    except Exception:  # pragma: no cover - jax is a hard dep in practice
        logger.warning("--pin-model requested but jax is unavailable; "
                       "serving from host state")
        return list(pairs), 0
    if any(
        hasattr(algo, "pin_model_for_serving")
        or hasattr(algo, "shard_model_for_serving")
        or hasattr(algo, "quantize_model_for_serving")
        for algo, _ in pairs
    ):
        try:
            jax.devices()
        except RuntimeError as e:
            raise DeviceUnavailableError(
                "device-resident serving state was requested "
                "(--pin-model / --shard-factors / --quantize / --aot) but "
                f"this process cannot open the JAX backend: {e} — a chip "
                "belongs to one process at a time; another process on "
                "this host (a trainer, a deployment, a sibling replica) "
                "holds it."
            ) from e
    out = []
    total = 0
    for algo, model in pairs:
        pin = None
        if quantize is not None:
            qhook = getattr(algo, "quantize_model_for_serving", None)
            if qhook is not None:
                def pin(m, _q=qhook):
                    return _q(m, mode=quantize, shard=shard)
                pin.__name__ = "quantize_model_for_serving"
            else:
                logger.warning(
                    "--quantize requested but %s has no "
                    "quantize_model_for_serving hook; serving f32",
                    type(algo).__name__,
                )
        if pin is None and shard:
            pin = getattr(algo, "shard_model_for_serving", None)
        if pin is None:
            pin = getattr(algo, "pin_model_for_serving", None)
        if pin is None:
            out.append((algo, model))
            continue
        try:
            model, nbytes = pin(model)
            total += int(nbytes)
        except Exception:
            logger.exception(
                "%s failed for %s; serving unpinned",
                getattr(pin, "__name__", "pin_model_for_serving"),
                type(algo).__name__,
            )
        out.append((algo, model))
    if aot is not None and getattr(aot, "active", False):
        _attach_aot(out, aot, instance_id)
    return out, total


def _attach_aot(pairs: list, aot, instance_id: str | None) -> None:
    """Load the generation's AOT artifact set ONCE and attach the shared
    runtime (+ tier report) to every pinned model; failures are loud but
    never fatal — the models keep serving through their jitted paths."""
    from predictionio_tpu.workflow import aot as aot_mod

    if not instance_id or not aot.root:
        logger.warning(
            "--aot requested but no engine instance id / artifact root "
            "is known; serving through the JIT path"
        )
        return
    try:
        runtime, report = aot_mod.load_runtime(instance_id, aot.root)
    except Exception as e:  # pragma: no cover - load_runtime reports itself
        runtime, report = None, {
            "tier": aot_mod.fallback_tier(),
            "instance": instance_id,
            "loaded": 0,
            "problems": [f"{type(e).__name__}: {e}"],
        }
        logger.exception("AOT artifact load raised; serving via JIT")
    for algo, model in pairs:
        state = serving_state(model)
        if state.pinned:
            if runtime is not None:
                state.aot = runtime
            state.aot_report = report
            # warm the engine's eager GLUE ops too (the row gather
            # feeding the exported programs): jax caches eager-op
            # executables by shape, so one warm call at boot is the
            # difference between "zero serve-time compiles" and two
            # first-query compiles the witness would flag (duck-typed,
            # like the pin/shard hooks)
            warm = getattr(algo, "aot_warm_serving", None)
            if warm is not None and runtime is not None:
                try:
                    warm(model)
                except Exception as e:  # noqa: BLE001 - warm is advisory
                    logger.warning("AOT glue warm-up failed: %s", e)


def aot_stats(pairs: Sequence) -> dict | None:
    """The ``aot`` block of ``/stats.json``: the load-time tier report
    joined with the live runtime counters (hits/misses/disabled), or
    ``None`` when no served model carries AOT state."""
    report = None
    runtime = None
    for _, model in pairs:
        state = serving_state(model)
        if report is None:
            report = state.aot_report
        if runtime is None:
            runtime = state.aot
    if report is None and runtime is None:
        return None
    out = dict(report or {})
    if runtime is not None:
        out.update(runtime.stats())
    return out


def serving_device(pairs: Sequence) -> dict:
    """The ``device`` block of ``GET /``: whether predict reads device
    buffers or host arrays — judged from the arrays the models and their
    states hold NOW, so a ``serveOnDevice`` probe that fell back reads "host" —
    and, once this process has opened the backend, the platform,
    ``deviceKind`` and device count JAX reports. A host-serving process
    never opens it (a chip belongs to one process), so those stay null.
    The probe's outcome rides along when it ran."""
    on_device = any(
        hasattr(v, "addressable_shards") or getattr(v, "is_quantized", False)
        for _, model in pairs
        for holder in (model, serving_state(model))
        for v in getattr(holder, "__dict__", {}).values()
    )
    probes = [
        p
        for _, model in pairs
        if (p := serving_state(model).latency_probe) is not None
    ]
    out = {
        "servedFrom": "device" if on_device else "host",
        "platform": None,
        "deviceKind": None,
        "count": None,
    }
    if on_device or probes:
        from predictionio_tpu.controller.context import (
            device_info,
            device_memory,
        )

        out.update(device_info())
        out.update(device_memory())
    if probes:
        out["latencyProbe"] = probes[0]
    return out


def bytes_by_dtype(pairs: Sequence) -> dict:
    """Aggregate per-dtype pinned-byte ledger across the served models —
    the ``cache.bytesByDtype`` block of ``/stats.json``. Each pin hook
    records its own breakdown on the state's ``bytes_by_dtype`` from the
    ACTUAL arrays it placed (``{"float32": ...}`` for the classic tiers,
    ``{"int8": ..., "scalesFloat32": ...}`` quantized), so the stats
    report served truth instead of recomputed shape math."""
    agg: dict = {}
    for _, model in pairs:
        for dtype, nbytes in (
            serving_state(model).bytes_by_dtype or {}
        ).items():
            agg[dtype] = agg.get(dtype, 0) + int(nbytes)
    return agg


def shard_count(pairs: Sequence) -> int:
    """Model-axis size of the sharded serving state (0 when nothing is
    sharded) — the ``factor_shards`` gauge on ``/stats.json``."""
    n = 0
    for _, model in pairs:
        shards = serving_state(model).shards
        if shards is not None:
            n = max(n, shards.num_shards)
    return n


def build_ann_pairs(pairs: Sequence, ann_config) -> tuple[list, list]:
    """Build IVF retrieval state for every (algorithm, model) pair whose
    algorithm supports it (``build_ann_for_serving``).

    Returns ``(pairs, infos)`` — the possibly-updated pair list and one
    build-info dict per built index (the ``/stats.json`` ``ann``
    section). Best-effort like pinning: a pair whose build raises is
    served exact rather than failing the load, and a jax-less host
    serves everything exact with a warning."""
    try:
        import jax  # noqa: F401  (availability probe only)
    except Exception:  # pragma: no cover - jax is a hard dep in practice
        logger.warning("--ann requested but jax is unavailable; "
                       "serving exact retrieval")
        return list(pairs), []
    out = []
    infos = []
    for algo, model in pairs:
        build = getattr(algo, "build_ann_for_serving", None)
        if build is None:
            out.append((algo, model))
            continue
        try:
            model, info = build(model, ann_config)
            infos.append(info)
            logger.info(
                "Built IVF retrieval index for %s: nlist=%s nprobe=%s "
                "slabWidth=%s build=%ss",
                type(algo).__name__, info.get("nlist"), info.get("nprobe"),
                info.get("slabWidth"), info.get("buildSeconds"),
            )
        except Exception:
            logger.exception(
                "build_ann_for_serving failed for %s; serving exact",
                type(algo).__name__,
            )
        out.append((algo, model))
    return out, infos


def set_rows(mat, idx, rows):
    """Replace factor rows ``idx`` of ``mat`` with ``rows`` — the online
    fold-in's delta re-pin (ROADMAP item 3).

    Pinned (device-resident) state updates via an on-device scatter, so
    only the touched rows cross the host->device link instead of
    re-staging the whole table per fold; host arrays update
    copy-on-write and swap whole (an in-place row write could hand a
    concurrent reader a torn vector — attribute assignment of the new
    array is atomic, the old array stays internally consistent for any
    in-flight query that already grabbed it).

    A quantized table (``--quantize int8``) re-quantizes ONLY the
    touched rows on scatter — codes and per-row scales each route back
    through this same function, so the sharded/pinned/host scatter
    machinery is shared and freshness survives quantization at delta
    cost."""
    import numpy as np

    if getattr(mat, "is_quantized", False):
        from predictionio_tpu.ops import quant

        codes, scales = quant.quantize_table_host(
            np.asarray(rows, np.float32)
        )
        return type(mat)(
            set_rows(mat.codes, idx, codes),
            set_rows(mat.scales, idx, scales),
        )
    if isinstance(mat, np.ndarray):
        out = mat.copy()
        out[np.asarray(idx, np.int64)] = np.asarray(rows, mat.dtype)
        return out
    import jax.numpy as jnp

    from predictionio_tpu.parallel.sharding import row_sharding

    sharded = row_sharding(mat)
    if sharded is not None:
        # --shard-factors: route each touched row to the device OWNING
        # its shard — a jitted scatter whose output sharding is pinned
        # to the table's own, so the fold's delta crosses the link once
        # and the table never gathers host-side (the online-compose fix)
        return _sharded_set_rows(sharded)(
            mat,
            jnp.asarray(np.asarray(idx, np.int32)),
            jnp.asarray(np.asarray(rows), dtype=mat.dtype),
        )
    return mat.at[jnp.asarray(np.asarray(idx, np.int32))].set(
        jnp.asarray(np.asarray(rows), dtype=mat.dtype)
    )


#: one compiled scatter per distinct table sharding (NamedSharding is
#: hashable); folds reuse it instead of retracing per call
_SHARDED_SET_CACHE: dict = {}


def _sharded_set_rows(sharding):
    fn = _SHARDED_SET_CACHE.get(sharding)
    if fn is None:
        import jax

        fn = jax.jit(
            lambda m, i, r: m.at[i].set(r, out_sharding=sharding),
            out_shardings=sharding,
        )
        _SHARDED_SET_CACHE[sharding] = fn
    return fn


def append_rows(mat, rows):
    """Grow a factor table by cold-start rows (fold-in injection for
    never-seen entities); stays on device when the table is pinned.
    Quantized tables quantize only the NEW rows and grow codes + scales
    in step."""
    import numpy as np

    if getattr(mat, "is_quantized", False):
        from predictionio_tpu.ops import quant

        codes, scales = quant.quantize_table_host(
            np.asarray(rows, np.float32)
        )
        return type(mat)(
            append_rows(mat.codes, codes),
            append_rows(mat.scales, scales),
        )
    if isinstance(mat, np.ndarray):
        return np.concatenate([mat, np.asarray(rows, mat.dtype)], axis=0)
    import jax.numpy as jnp

    return jnp.concatenate(
        [mat, jnp.asarray(np.asarray(rows), dtype=mat.dtype)], axis=0
    )


def swap_side_rows(
    model, ids, rows, factors_attr: str, index_attr: str,
    rows_before_index: bool,
) -> tuple[int, int]:
    """Swap one side's online-update rows into a live model: split
    ``ids`` into known (scatter via :func:`set_rows`) and new
    (cold-start: :func:`append_rows` + ``BiMap.extended``), mutating the
    model's attributes by whole-object assignment only. The ONE place
    that encodes the swap-ordering contract both templates rely on:

    ``rows_before_index=True`` (user side) — a racing query resolving a
    fresh user must find its row already present (the reverse order
    could hand it an out-of-bounds row); until the index lands, the user
    just reads as unknown.

    ``rows_before_index=False`` (item side) — scoring runs over the
    factor table, so a new row must not become rankable before the index
    can translate it back to an item id.

    Under ``--shard-factors`` (the state's ``shards`` set) the table is
    padded to a multiple of the mesh axis, so cold-start rows first fill
    the existing padding slots via the shard-routed scatter; only when
    the physical capacity is exhausted does the table re-lay-out (host
    gather + re-shard with ``GROW_STEP`` headroom, so the O(table) cost
    amortizes over many fold-ins). The logical row count advances on
    ``ShardInfo.rows`` — kernels mask by it, so a padding slot becomes
    rankable exactly when its row lands.

    Returns ``(rows updated, rows added)``."""
    import numpy as np

    index = getattr(model, index_attr)
    known = [
        (j, idx)
        for j, e in enumerate(ids)
        if (idx := index.get(e)) is not None
    ]
    new = [j for j, e in enumerate(ids) if index.get(e) is None]
    rows = np.asarray(rows, np.float32)
    if known:
        setattr(
            model,
            factors_attr,
            set_rows(
                getattr(model, factors_attr),
                [idx for _, idx in known],
                rows[[j for j, _ in known]],
            ),
        )
    if new:
        new_ids = [ids[j] for j in new]
        shards = serving_state(model).shards

        def grow(mat):
            if shards is None:
                return append_rows(mat, rows[new])
            side = "user" if rows_before_index else "item"
            logical = int(shards.rows[side])
            capacity = int(mat.shape[0])
            if logical + len(new) <= capacity:
                # scatter into padding slots on their owner shards —
                # no re-layout, no host round trip of the table
                out = set_rows(
                    mat, list(range(logical, logical + len(new))), rows[new]
                )
            else:
                from predictionio_tpu.parallel import sharding

                # np.asarray dequantizes a quantized table — the
                # re-layout round-trips through f32 and re-quantizes,
                # which is value-stable (quantize∘dequantize is the
                # identity on already-quantized rows)
                host = np.asarray(mat)[:logical]
                relayout = (
                    sharding.shard_quantized_table
                    if getattr(mat, "is_quantized", False)
                    else sharding.shard_table
                )
                out = relayout(
                    np.concatenate([host, rows[new]]),
                    shards.mesh,
                    capacity=logical + len(new) + sharding.GROW_STEP,
                )
            shards.rows[side] = logical + len(new)
            return out

        if rows_before_index:
            setattr(model, factors_attr, grow(getattr(model, factors_attr)))
            setattr(model, index_attr, index.extended(new_ids))
        else:
            setattr(model, index_attr, index.extended(new_ids))
            setattr(model, factors_attr, grow(getattr(model, factors_attr)))
    return len(known), len(new)


def update_ann_items(model, item_ids, rows, index_attr: str = "item_index"):
    """Fold changed/new item rows into the model's incremental IVF index
    (when one is built); returns the update info dict or ``None``."""
    import numpy as np

    ann = serving_state(model).ann
    if ann is None:
        return None
    index = getattr(model, index_attr)
    all_idx = np.asarray([index[i] for i in item_ids], np.int64)
    return ann.update_items(
        all_idx, np.asarray(rows, np.float32), total_items=len(index)
    )


def release_pairs(pairs: Sequence) -> None:
    """Drop pinned device state AND ANN retrieval state of a superseded
    model generation so its buffers become collectable immediately (a
    hot-reloading server must not accumulate one catalog of HBM — or
    one IVF index — per reload)."""
    for algo, model in pairs:
        for name in ("release_pinned_model", "release_ann_state"):
            release = getattr(algo, name, None)
            if release is None:
                continue
            try:
                release(model)
            except Exception:
                logger.exception(
                    "%s failed for %s", name, type(algo).__name__
                )
