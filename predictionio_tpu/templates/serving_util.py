"""Shared serving-path helpers for engine templates."""

from __future__ import annotations

import dataclasses
import logging
import time
from typing import Any, Callable, Iterator, Sequence

import numpy as np

from predictionio_tpu.utils.spans import count, span

__all__ = [
    "device_latency_probe", "chunked_topk", "serving_row_buckets",
    "aligned_factor_init", "TopkFilter", "allowed_items_host",
]

logger = logging.getLogger(__name__)

#: the MOST queries one device dispatch / host GEMM of
#: :func:`chunked_topk` may score — a cap, not a shape: it bounds the
#: ``[rows, items]`` float32 score matrix (2,048 x 624,961 x 4 B = 5.1 GB
#: at the benchmark's catalog). A chunk's program is shaped by the rows
#: the chunk really holds (``ops.topk.bucket_rows``: pow2, floor 8), so
#: only a full chunk of ``pio batchpredict`` scores this many
TOPK_CHUNK = 2048


def serving_row_buckets(chunk: int = TOPK_CHUNK) -> list[int]:
    """Every row bucket :func:`chunked_topk` can dispatch for a batch of
    a default micro-batcher (at most ``BatcherConfig.max_batch_size``
    queries), and the cap (a full ``pio batchpredict`` chunk): the
    program shapes an ``--aot`` export must hold for a deploy to serve
    with zero serve-time compiles."""
    from predictionio_tpu.ops.topk import bucket_rows
    from predictionio_tpu.serving.batcher import BatcherConfig

    return sorted(
        {bucket_rows(n, chunk)
         for n in range(1, BatcherConfig.max_batch_size + 1)} | {chunk}
    )


@dataclasses.dataclass
class TopkFilter:
    """The rules of a filtered :func:`chunked_topk` call. Per item:
    ``codes`` (the category codes it carries, ``-1`` = none) and
    ``blocked`` (no row may be given it). Per entry of ``valid``, in its
    order: ``wanted`` ``i32[n, W]`` (category codes asked for, padded
    with ``-2``; a row whose first entry is negative names none and
    allows all; ``W`` an ``ops.topk.bucket_width`` bucket) and
    ``excluded`` ``i32[n, E]`` (item ids left out, padded with
    ``ops.topk.NO_ITEM``; ``E`` the longest list, no extent of any
    program).

    Unpinned, ``codes`` is ``i32[items, C]`` and ``blocked``
    ``bool[items]`` on the host. Pinned, ``item_tiles`` holds the item
    factors on the device as ``ops.als.tile_items`` laid them out, and
    ``codes`` / ``blocked`` are the device arrays of the same tiles
    (``blocked`` also marks the padding past the catalog)."""

    codes: Any
    blocked: Any
    wanted: np.ndarray
    excluded: np.ndarray
    item_tiles: Any = None


def allowed_items_host(
    codes: np.ndarray, blocked: np.ndarray, wanted: np.ndarray,
    excluded: np.ndarray,
) -> np.ndarray:
    """``bool[rows, items]``: the items each row is allowed, by the one
    rule the device program (``ops.als.top_k_items_filtered``) applies —
    the item carries a wanted category (or the row names none), is not
    blocked, and is not among the row's excluded ids. Arguments as the
    host fields of :class:`TopkFilter`."""
    n_items = codes.shape[0]
    allowed = np.empty((wanted.shape[0], n_items), dtype=bool)
    for r, (want, out) in enumerate(zip(wanted, excluded)):
        if want[0] < 0:
            allowed[r] = True
        else:
            allowed[r] = np.isin(codes, want[want >= 0]).any(axis=1)
        allowed[r, out[out < n_items]] = False
    allowed &= ~blocked
    return allowed


def _pad_rows(a: np.ndarray, rows: int, fill: int) -> np.ndarray:
    out = np.full((rows, a.shape[1]), fill, a.dtype)
    out[: a.shape[0]] = a
    return out


def _filtered_topk(
    user_mat, item_mat, valid: Sequence[tuple], chunk: int, k_max: int,
    filt: TopkFilter,
) -> Iterator[tuple[list, list, list]]:
    """The filtered branch of :func:`chunked_topk`: the same row and k
    buckets, leaf spans and sentinel trim as the others. The chunk's user
    rows are gathered where the user table lies and ride with the rules:
    a row gather from a pinned ``[users, rank]`` table makes XLA copy the
    whole table into a row-major layout on every dispatch. On the device
    the chunk's excluded ids go as (row, position) pairs grouped by tile
    (``ops.als.tile_pairs``), counted as ``filter.excludedPairs`` and, a
    dispatch, under the pair bucket of its program
    (``filter.pairBucket.<P>``)."""
    from predictionio_tpu.ops.topk import NO_ITEM, select_plan, top_k_host

    n_items = int(item_mat.shape[0])
    on_device = filt.item_tiles is not None
    if on_device:
        from predictionio_tpu.ops.als import (
            FILTER_SCORE_BYTES,
            tile_pairs,
            top_k_items_filtered,
        )

        # the [rows, width] float32 scores of one tile bound the rows
        n_tiles, _, width = map(int, filt.item_tiles.shape)
        most = FILTER_SCORE_BYTES // (4 * width)
        chunk = min(chunk, max(8, 1 << (most.bit_length() - 1)))
    staged: list = []
    for lo in range(0, len(valid), chunk):
        part = list(valid[lo : lo + chunk])
        padded = _row_bucket_index(part, chunk if on_device else len(part))
        if on_device:  # the program selects a tile at a time
            count("select." + select_plan(padded.size, width, min(k_max, width)), 1)
        with span("dispatch"):
            user_vecs = user_mat[padded]
            wanted = _pad_rows(filt.wanted[lo : lo + chunk], padded.size, -2)
            excluded = filt.excluded[lo : lo + chunk]
            if on_device:
                drop_row, drop_col, pairs = tile_pairs(excluded, n_tiles, width)
                count("filter.excludedPairs", pairs)
                count(f"filter.pairBucket.{drop_row.shape[1]}", 1)
                idx_b, score_b = top_k_items_filtered(
                    user_vecs, filt.item_tiles, filt.codes, filt.blocked,
                    wanted, drop_row, drop_col, k_max,
                )
            else:
                scores = np.where(
                    allowed_items_host(
                        filt.codes, filt.blocked, wanted,
                        _pad_rows(excluded, padded.size, NO_ITEM),
                    ),
                    np.asarray(user_vecs) @ np.asarray(item_mat).T,
                    -np.inf,
                )
                idx_b, score_b = top_k_host(scores, k_max)
                idx_b = np.where(score_b > -np.inf, idx_b, NO_ITEM)
        staged.append((part, idx_b, score_b))
    yield from _drain_staged(staged, n_items)


def _drain_staged(
    staged: list, n_items: int
) -> Iterator[tuple[list, list, list]]:
    """Drain chunk-staged device results with ONE link crossing: concat
    all chunks' ids/scores on device, transfer once, then trim each
    row's sentinel padding (id >= n_items at -inf) before any consumer
    sees it — shared by the ANN and quantized staging paths."""
    with span("deviceWait"):
        if len(staged) > 1 and not isinstance(staged[0][1], np.ndarray):
            import jax.numpy as jnp

            idx_all = np.asarray(
                jnp.concatenate([i for _, i, _ in staged], axis=0)
            )
            score_all = np.asarray(
                jnp.concatenate([s for _, _, s in staged], axis=0)
            )
        else:
            idx_all = np.concatenate([np.asarray(i) for _, i, _ in staged])
            score_all = np.concatenate([np.asarray(s) for _, _, s in staged])
    off = 0
    for part, idx_b, _ in staged:
        with span("format"):
            # the chunk's rows as lists at once; a row that holds a
            # sentinel (the rules left it fewer than k items) is trimmed
            rows = slice(off, off + len(part))
            keep = idx_all[rows] < n_items
            ids_l, scores_l = idx_all[rows].tolist(), score_all[rows].tolist()
            for r in np.flatnonzero(~keep.all(axis=1)).tolist():
                ids_l[r] = idx_all[off + r][keep[r]].tolist()
                scores_l[r] = score_all[off + r][keep[r]].tolist()
        yield part, ids_l, scores_l
        # chunks hold unequal rows (the tail's bucket is its own)
        off += int(idx_b.shape[0])


def _row_bucket_index(part: Sequence[tuple], chunk: int) -> np.ndarray:
    """The chunk's user rows as the zero-padded
    ``int32[bucket_rows(len(part), chunk)]`` every scoring program takes:
    the program's rows follow the rows the chunk holds, never over
    ``chunk``. Counts both (``rowsScored``, ``rowsReal``) on the
    thread's span collector, once a chunk; the branches whose program
    selects through ``ops.topk.select_top_k`` count its plan beside them
    (``select.blocked`` or ``select.plain``)."""
    from predictionio_tpu.ops.topk import bucket_rows

    with span("lookup"):
        padded = np.zeros(bucket_rows(len(part), chunk), np.int32)
        padded[: len(part)] = np.fromiter(
            (u for _, u, _ in part), np.int32, len(part)
        )
    count("rowsScored", padded.size)
    count("rowsReal", len(part))
    return padded


def chunked_topk(
    user_mat, item_mat, valid: Sequence[tuple], chunk: int = TOPK_CHUNK,
    ann=None, shards=None, quant=None, aot=None, filt=None,
) -> Iterator[tuple[list, list, list]]:
    """Chunked batch top-k over ``valid = [(slot, uidx, k), ...]``;
    yields ``(part, ids, scores)`` with ids/scores as Python lists — the
    shared engine-template core of batch-amortized serving (ref
    ``BatchPredict.scala`` ``batchPredictBase``).

    k buckets to the next power of two (floor 16): the jitted kernel's k
    is static, so raw ``max(num)`` would recompile per distinct value — a
    bounded bucket set keeps one XLA program per bucket; each query trims
    its own k from the padded result. A chunk's ROWS bucket the same way
    (``ops.topk.bucket_rows``: pow2, floor 8, capped at ``chunk``): an
    online batch of 32 scores 32 rows and only a full ``pio
    batchpredict`` chunk scores ``chunk``, so at most nine row shapes
    (8 ... 2048) exist per k bucket and chunks of one call may hold
    unequal rows. On device, dispatches stay async
    across chunks and ALL results concatenate on device to cross to the
    host in ONE transfer instead of one per chunk. ``tolist()`` converts
    whole chunks to Python scalars at C speed.

    ``ann`` (an :class:`predictionio_tpu.ops.ivf.AnnRuntime`) routes the
    scoring through the two-stage IVF kernel instead of the full-catalog
    GEMM: only ``nprobe`` cluster slabs are scored per query, so chunk
    cost scales with ``nprobe * (catalog / nlist)`` instead of the
    catalog. Queries whose ``k`` includes a filter over-fetch keep their
    guarantee — the merge returns ``k`` real candidates whenever the
    probed clusters hold that many (sentinel-padded rows are trimmed
    here, before any consumer sees them).

    ``shards`` (a :class:`predictionio_tpu.parallel.sharding.ShardInfo`,
    the ``--shard-factors`` tier) means both tables are model-sharded:
    the exact path routes through the shard_map kernel (each device
    scores only its ``[B,K]@[K,I/S]`` slice; tie-stable-identical
    results), and the ANN path resolves query rows through the sharded
    gather before the cluster-sharded probe kernel.

    ``aot`` (a :class:`predictionio_tpu.workflow.aot.AotRuntime`, the
    ``--aot`` tier) serves the exact on-device branch through the
    generation's DESERIALIZED batch program (same jaxpr as
    ``top_k_items_batch``, so results are bit-identical) instead of the
    jitted one — zero serve-time compiles; a call-time failure disables
    the program key and the very next chunk takes the jitted path.

    Every branch is cut by the same four leaf spans (utils/spans.py),
    never held across a ``yield``: ``lookup`` (the chunk's padded index
    vector), ``dispatch`` (the call into the scoring program until it
    returns), ``deviceWait`` (the readback that blocks until the device
    is done; none on the host branch) and ``format`` (``tolist``).

    ``quant`` (a :class:`predictionio_tpu.ops.quant.QuantRuntime`, the
    ``--quantize int8`` tier) means both tables are int8 codes + per-row
    scales: the exact path runs the two-stage kernel (int8 coarse scan
    over-fetching ``max(4k, k+64)``, f32 rescore of only the gathered
    candidates), composing with ``shards`` through the shard_map
    variant; the ANN path dequantizes only the chunk's query rows and
    probes the (int8-slabbed) index as usual.

    ``filt`` (a :class:`TopkFilter`: per-item category codes and a
    blocked mask, per-row wanted categories and excluded ids) returns the
    exact top ``k`` of the items each row is ALLOWED: on the device
    through ``ops.als.top_k_items_filtered`` (tiled over the items; the
    excluded pairs of its fullest tile and the category width are further
    buckets of its program),
    on the host through the same rule in numpy. A row with fewer than
    ``k`` allowed items comes back shorter. It composes with none of the
    tiers above."""
    if not valid:
        return
    # under --shard-factors the physical table is padded to a multiple
    # of the mesh axis; the LOGICAL catalog lives on the ShardInfo
    n_items = (
        int(shards.rows["item"]) if shards is not None
        else int(item_mat.shape[0])
    )
    from predictionio_tpu.ops.topk import bucket_k

    k_max = bucket_k(max(k for _, _, k in valid), n_items)
    if filt is not None:
        yield from _filtered_topk(user_mat, item_mat, valid, chunk, k_max, filt)
        return
    if ann is not None:
        import jax.numpy as jnp

        from predictionio_tpu.ops import ivf

        user_on_device = not isinstance(user_mat, np.ndarray)
        user_quantized = getattr(user_mat, "is_quantized", False)
        ann_staged: list = []
        for lo in range(0, len(valid), chunk):
            part = list(valid[lo : lo + chunk])
            padded = _row_bucket_index(part, chunk)
            with span("dispatch"):
                if user_quantized:
                    # --quantize: dequantize ONLY the chunk's user rows
                    # (the f32 queries the probe stage scores with); the
                    # probed slabs themselves stay int8 inside the index.
                    # The rows stay ON DEVICE — a host round trip here
                    # would serialize the chunk dispatches
                    qv = user_mat[jnp.asarray(padded)]
                    if shards is not None:
                        from predictionio_tpu.parallel import sharding

                        idx_b, score_b = sharding.sharded_ivf_topk(
                            qv, ann.index, k_max, ann.nprobe, shards.mesh
                        )
                    else:
                        idx_b, score_b = ivf.ivf_topk_batch(
                            qv, ann.index, k_max, ann.nprobe
                        )
                elif shards is not None:
                    from predictionio_tpu.parallel import sharding

                    qv = sharding.gather_rows(padded, user_mat, shards.mesh)
                    idx_b, score_b = sharding.sharded_ivf_topk(
                        qv, ann.index, k_max, ann.nprobe, shards.mesh
                    )
                elif user_on_device:
                    idx_b, score_b = ivf.ivf_topk_users(
                        padded, user_mat, ann.index, k_max, ann.nprobe
                    )
                else:
                    # unpinned model: gather the chunk's user rows on host
                    # so each dispatch uploads [rows, K] — NOT the whole
                    # user table, which would dwarf the nprobe savings
                    # per call
                    qv = np.zeros(
                        (padded.size, user_mat.shape[1]), np.float32
                    )
                    qv[: len(part)] = np.asarray(user_mat)[padded[: len(part)]]
                    idx_b, score_b = ivf.ivf_topk_batch(
                        jnp.asarray(qv), ann.index, k_max, ann.nprobe
                    )
            ann.note_queries(len(part))
            ann_staged.append((part, idx_b, score_b))
        # same staging discipline as the exact device path below: keep
        # dispatches async across chunks, cross the link ONCE
        yield from _drain_staged(ann_staged, n_items)
        return
    if quant is not None:
        from predictionio_tpu.ops import quant as quant_ops

        q_staged: list = []
        for lo in range(0, len(valid), chunk):
            part = list(valid[lo : lo + chunk])
            padded = _row_bucket_index(part, chunk)
            with span("dispatch"):
                idx_b, score_b = quant_ops.run_topk(
                    quant, user_mat, item_mat, padded, k_max, shards=shards
                )
            q_staged.append((part, idx_b, score_b))
        yield from _drain_staged(q_staged, n_items)
        return
    on_device = not isinstance(item_mat, np.ndarray)
    staged: list[tuple[list, object, object]] = []
    for lo in range(0, len(valid), chunk):
        part = list(valid[lo : lo + chunk])
        # the host GEMM has no compiled shape to share: its cap is the
        # rows it holds, so nothing is padded (rows scored = rows real)
        padded = _row_bucket_index(part, chunk if on_device else len(part))
        if shards is not None:
            from predictionio_tpu.parallel import sharding

            with span("dispatch"):
                idx_b, score_b = sharding.sharded_topk_users(
                    padded, user_mat, item_mat, k_max, n_items, shards.mesh
                )
        elif on_device:
            from predictionio_tpu.ops.als import top_k_items_batch
            from predictionio_tpu.ops.topk import select_plan

            count("select." + select_plan(padded.size, n_items, k_max), 1)
            with span("dispatch"):
                aot_key = f"top_k_items_batch_c{padded.size}_b{k_max}"
                fn = aot.get(aot_key) if aot is not None else None
                if fn is not None:
                    try:
                        idx_b, score_b = fn(padded, user_mat, item_mat)
                    except Exception as e:  # noqa: BLE001 - degrade, don't 500
                        aot.disable(aot_key, str(e))
                        fn = None
                if fn is None:
                    idx_b, score_b = top_k_items_batch(
                        padded, user_mat, item_mat, k_max
                    )
        else:
            from predictionio_tpu.ops.topk import top_k_host

            scores = np.asarray(user_mat)[padded] @ np.asarray(item_mat).T
            # descending score, ties broken by ascending item index —
            # the same rule lax.top_k uses, so host and device paths
            # agree wherever the float scores do (shared helper:
            # ops/topk.py)
            idx_b, score_b = top_k_host(scores, k_max)
        staged.append((part, idx_b, score_b))
    if on_device:
        with span("deviceWait"):
            if len(staged) > 1:
                import jax.numpy as jnp

                # dispatches stayed async across chunks: ONE link crossing
                idx_all = np.asarray(
                    jnp.concatenate([i for _, i, _ in staged], axis=0)
                )
                score_all = np.asarray(
                    jnp.concatenate([s for _, _, s in staged], axis=0)
                )
            else:
                idx_all = np.asarray(staged[0][1])
                score_all = np.asarray(staged[0][2])
        off = 0
        for part, idx_b, _ in staged:
            with span("format"):
                ids = idx_all[off : off + len(part)].tolist()
                scs = score_all[off : off + len(part)].tolist()
            yield part, ids, scs
            off += int(idx_b.shape[0])
        return
    for part, idx_b, score_b in staged:
        with span("format"):
            ids = np.asarray(idx_b)[: len(part)].tolist()
            scs = np.asarray(score_b)[: len(part)].tolist()
        yield part, ids, scs


def device_latency_probe(
    predict_once: Callable[[], None],
    budget_ms: float,
    samples: int = 5,
) -> dict:
    """Deploy-time guardrail for ``serveOnDevice``: measure the real
    per-query device latency and report whether its median fits the
    budget — one small GEMV per query can lose to host numpy on dispatch
    cost alone. ``budget_ms <= 0`` disables the measurement (always
    trust the caller). The first call is a warm-up (compile) and is not
    measured. Returns ``{"ok", "p50Ms", "budgetMs"}`` (``p50Ms`` None
    when disabled); callers keep it on the model so ``GET /`` can show
    the outcome, and fall back to host serving when ``ok`` is false."""
    predict_once()
    if budget_ms <= 0:
        return {"ok": True, "p50Ms": None, "budgetMs": budget_ms}
    lat = []
    for _ in range(samples):
        t0 = time.perf_counter()
        predict_once()
        lat.append((time.perf_counter() - t0) * 1e3)
    p50 = sorted(lat)[len(lat) // 2]
    ok = p50 <= budget_ms
    if not ok:
        logger.warning(
            "serveOnDevice probe: median device query latency %.1f ms "
            "exceeds the %.1f ms budget — serving from host arrays "
            "instead (GET / reports it). Set deviceLatencyBudgetMs <= 0 "
            "to force device serving.",
            p50,
            budget_ms,
        )
    return {"ok": ok, "p50Ms": round(p50, 3), "budgetMs": budget_ms}


def aligned_factor_init(
    old_factors: np.ndarray,
    old_index,
    new_index,
    rank: int,
    seed: int,
    fresh: Callable | None = None,
) -> tuple[np.ndarray, int]:
    """Carry a previous model's factor/embedding rows over to a new id
    space: entities present in both keep their vectors (overlapping
    columns when the rank changed); new entities get the standard
    abs(normal)/sqrt(rank) draw. This is what makes a warm retrain start
    near the previous optimum even as the catalog shifts (SURVEY §8.3;
    shared by the ALS and two-tower templates). Returns (init matrix,
    number of carried rows).

    ``fresh(rng, shape)`` draws the init for NON-carried rows; the
    default is ALS's nonnegative abs(normal)/sqrt(rank). Templates whose
    cold init differs (e.g. the two-tower's signed normal) must pass
    their own draw, or new entities would start in the wrong
    distribution — for towers, all in the positive orthant with pairwise
    cosine ~0.64 instead of ~0."""
    rng = np.random.default_rng(seed)
    shape = (len(new_index), rank)
    if fresh is None:
        out = (np.abs(rng.standard_normal(shape)) / np.sqrt(rank)).astype(
            np.float32
        )
    else:
        out = np.asarray(fresh(rng, shape), np.float32)
        if out.shape != shape:
            raise ValueError(f"fresh draw returned {out.shape}, want {shape}")
    old = np.asarray(old_factors)
    k = min(rank, old.shape[1])
    old_d, new_d = old_index.to_dict(), new_index.to_dict()
    if not old_d or not new_d:
        return out, 0
    # vectorized key intersection — a per-key Python loop would cost
    # minutes at catalog scale (review finding)
    old_keys = np.asarray(list(old_d), dtype=np.str_)
    old_rows = np.fromiter(old_d.values(), np.int64, len(old_d))
    new_keys = np.asarray(list(new_d), dtype=np.str_)
    new_rows = np.fromiter(new_d.values(), np.int64, len(new_d))
    o_sort = np.argsort(old_keys)
    pos = np.searchsorted(old_keys, new_keys, sorter=o_sort)
    pos_c = np.minimum(pos, old_keys.size - 1)
    hit = old_keys[o_sort[pos_c]] == new_keys
    src = old_rows[o_sort[pos_c[hit]]]
    ok = src < old.shape[0]
    out[new_rows[hit][ok], :k] = old[src[ok], :k]
    return out, int(ok.sum())
