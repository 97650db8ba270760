"""Two-table retrieval: the serving state of a model that answers by "a
user row times an item table", the hooks that build it, and its top-K.

An engine takes :class:`TwoTableRetrieval` and names its model's two
table attributes (docs/authoring.md); the hook NAMES it inherits are the
interface ``workflow/device_state.py`` and ``workflow/aot.py`` duck-type
on. What the hooks build rides on the model as ONE :class:`ServingState`,
reached through :func:`serving_state` by engines and workflow alike. jax
is imported inside the functions that need it.

:class:`FilteredItemRetrieval` is the other retrieval the templates share:
the top-K of one item table under per-query rules (categories asked for,
item ids left out, items no query may be given), whatever the query
vectors are rows of. The e-commerce and the similar-product engines take it.
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Any, Collection, Iterable, Iterator, Sequence

import numpy as np

from predictionio_tpu.data.aggregator import BiMap
from predictionio_tpu.templates.results import ItemScore, PredictedResult
from predictionio_tpu.templates.serving_util import (
    TOPK_CHUNK,
    TopkFilter,
    allowed_items_host,
    chunked_topk,
    device_latency_probe,
    serving_row_buckets,
)
from predictionio_tpu.utils.spans import count, span

__all__ = [
    "ServingState", "serving_state", "ItemTableAnn", "TwoTableRetrieval",
    "FilteredServingState", "FilteredItemRetrieval", "category_arrays",
    "WANTED_FLOOR",
]

logger = logging.getLogger(__name__)


@dataclasses.dataclass
class ServingState:
    """What serving hooks attach to a deployed model, per generation."""

    #: the tables are device-resident (any of pin / shard / quantize)
    pinned: bool = False
    #: ``parallel.sharding.ShardInfo`` under ``--shard-factors``
    shards: Any = None
    #: ``ops.quant.QuantRuntime`` under ``--quantize``
    quant: Any = None
    #: ``ops.ivf.AnnRuntime`` under ``--ann``
    ann: Any = None
    #: ``workflow.aot.AotRuntime`` under ``--aot``, and its load report
    aot: Any = None
    aot_report: dict | None = None
    #: device bytes by dtype, from the arrays the pin hook really placed
    bytes_by_dtype: dict | None = None
    #: the ``serveOnDevice`` probe's outcome (``GET /``)
    latency_probe: dict | None = None
    #: the online fold-in's per-model rating accumulator
    online: dict | None = None

    def __reduce__(self):
        # runtime state holds meshes and device buffers: a model that is
        # pickled after a hook ran carries None in its place
        return (type(None), ())


def serving_state(model, cls: type = ServingState) -> ServingState:
    """The model's serving state, made on first use. An engine that keeps
    fields of its own passes its subclass as ``cls``."""
    state = getattr(model, "_pio_serving", None)
    if not isinstance(state, cls):
        state = cls() if state is None else cls(**vars(state))
        try:
            model._pio_serving = state
        except AttributeError:
            # a model that takes no attributes (no hook serves it) reads
            # as the empty state
            pass
    return state


class ItemTableAnn:
    """The ``--ann`` hooks of an algorithm whose model holds an item
    table under ``ITEM_TABLE``."""

    ITEM_TABLE: str

    def build_ann_for_serving(self, model, ann) -> tuple[Any, dict]:
        """``--ann`` retrieval tier (workflow/device_state.py): cluster
        the item table into an on-device IVF index once per model
        generation; queries then score only ``nprobe`` cluster slabs
        instead of the whole catalog. Returns the model (the runtime on
        its state) and the build info for ``/stats.json``."""
        from predictionio_tpu.ops import ivf

        state = serving_state(model)
        # np.asarray dequantizes a --quantize table; k-means runs on the
        # f32 values either way, and the SERVED slabs re-quantize below
        items = np.asarray(getattr(model, self.ITEM_TABLE))
        if state.shards is not None:
            # sharded tables carry even-shard padding rows — the index
            # must cluster only the LOGICAL catalog
            items = items[: state.shards.rows["item"]]
        index, info = ivf.build_ivf(
            items,
            nlist=ann.nlist, seed=ann.seed, iters=ann.kmeans_iters,
            # --quantize composition: slabs stored int8 + per-lane
            # scales, so per-probe gather bytes drop ~4x (the centroid
            # stage stays f32)
            quantize=state.quant is not None,
        )
        state.ann = ivf.AnnRuntime(index, ann.nprobe, info)
        if state.shards is not None:
            # --shard-factors composition: the cluster-major slabs shard
            # over the same model axis as the factor tables
            info = dict(info, **ivf.shard_runtime(state.ann, state.shards.mesh))
        info = dict(info, algorithm=type(self).__name__,
                    nprobe=state.ann.nprobe)
        return model, info

    def release_ann_state(self, model) -> None:
        """Drop a superseded generation's IVF index (same contract as
        release_pinned_model: a hot-reloading server must not accumulate
        one index of device memory per swap)."""
        serving_state(model).ann = None


class TwoTableRetrieval(ItemTableAnn):
    """Serving hooks and top-K of a ``JaxAlgorithm`` whose model holds a
    user table under ``USER_TABLE`` and an item table under
    ``ITEM_TABLE``. ``params.serve_on_device`` and
    ``params.device_latency_budget_ms``, where the engine's params have
    them, steer :meth:`prepare_model_for_serving`."""

    USER_TABLE: str

    #: the most queries one device dispatch / host GEMM scores (a cap,
    #: not a shape — see serving_util.TOPK_CHUNK; kept as a class
    #: attribute so tests can shrink it to force multi-chunk coverage)
    BATCH_PREDICT_CHUNK = TOPK_CHUNK

    def _tables(self, model) -> tuple[Any, Any]:
        return getattr(model, self.USER_TABLE), getattr(model, self.ITEM_TABLE)

    def _set_tables(self, model, user, item) -> None:
        setattr(model, self.USER_TABLE, user)
        setattr(model, self.ITEM_TABLE, item)

    def prepare_model_for_serving(self, model):
        user, item = self._tables(model)
        servable = bool(user.shape[0] and item.shape[0])

        def query_once():
            self.top_k(model, 0, min(4, int(item.shape[0])))

        if getattr(self.params, "serve_on_device", False):
            import jax

            self._set_tables(
                model,
                jax.device_put(np.asarray(user)),
                jax.device_put(np.asarray(item)),
            )
            if servable:
                probe = device_latency_probe(
                    query_once,
                    getattr(self.params, "device_latency_budget_ms", 10.0),
                )
                serving_state(model).latency_probe = probe
                if not probe["ok"]:
                    self._set_tables(model, np.asarray(user), np.asarray(item))
            return model
        self._set_tables(
            model, np.ascontiguousarray(user), np.ascontiguousarray(item)
        )
        # warm-up so the first real query pays no compile / cache fill
        # (parity: CreateServer's deploy-time warm-up)
        if servable:
            query_once()
        return model

    # ------------------------------------------------------ pinned serving
    def pin_model_for_serving(self, model) -> tuple[Any, int]:
        """``--pin-model`` cache tier (workflow/device_state.py):
        ``device_put`` the two tables once per model generation so
        every request scores against resident buffers — no per-request
        host->device staging — and top_k/top_k_staged flip onto the
        jitted device path (bucket-keyed static-``k`` score+top-K
        programs). Returns the pinned model and the device bytes it
        holds (``bytesPinned`` on /stats.json). Idempotent: re-pinning
        an already-pinned model re-uses it."""
        import jax

        user, item = (
            jax.device_put(t) if isinstance(t, np.ndarray) else t
            for t in self._tables(model)
        )
        return self._pinned_float32(model, user, item)

    def _pinned_float32(self, model, user, item) -> tuple[Any, int]:
        self._set_tables(model, user, item)
        state = serving_state(model)
        state.pinned = True
        nbytes = int(user.nbytes + item.nbytes)
        state.bytes_by_dtype = {"float32": nbytes}
        return model, nbytes

    # ------------------------------------------------------ sharded serving
    def shard_model_for_serving(self, model) -> tuple[Any, int]:
        """``--shard-factors`` tier (workflow/device_state.py): pin
        table SHARDS per device — each of the ``S`` local devices holds
        a ``[rows/S, K]`` slice of each table instead of a replica, so
        per-device memory is ``O((U+I)·K / S)`` and the largest
        servable catalog scales with the mesh (the ALX layout training
        already uses, extended to the query path). Top-K routes through
        the shard_map kernel in ``parallel/sharding.py``, which is
        tie-stable-identical to the replicated exact path. Falls back to
        plain pinning on a single-device host."""
        from predictionio_tpu.parallel import sharding

        mesh = sharding.serving_mesh()
        if mesh is None:
            logger.warning(
                "--shard-factors requested but only one device is "
                "visible; falling back to --pin-model replication"
            )
            return self.pin_model_for_serving(model)
        user_f, item_f = (np.asarray(t) for t in self._tables(model))
        user = sharding.shard_table(user_f, mesh)
        item = sharding.shard_table(item_f, mesh)
        serving_state(model).shards = sharding.ShardInfo(
            mesh=mesh,
            rows={"user": int(user_f.shape[0]), "item": int(item_f.shape[0])},
        )
        return self._pinned_float32(model, user, item)

    # ---------------------------------------------------- quantized serving
    def quantize_model_for_serving(
        self, model, mode: str = "int8", shard: bool = False
    ) -> tuple[Any, int]:
        """``--quantize int8`` tier (workflow/device_state.py): pin the
        tables as int8 codes + per-row f32 scales (ops/quant.py's
        one rounding rule) so the served catalog costs ``rank + 4``
        bytes per row instead of ``4·rank``. Serving routes through the
        recall-guarded two-stage kernel (int8 coarse scan over-fetching
        ``max(4k, k+64)``, f32 rescore of only the gathered candidates,
        shared tie rule). ``shard=True`` composes with
        ``--shard-factors``: codes and scales shard over the model mesh,
        so per-device bytes are ``catalog·(rank+4)/S`` — the tiers
        multiply. Returns ``(model, real pinned bytes)``; the per-dtype
        ledger lands on the state's ``bytes_by_dtype``."""
        from predictionio_tpu.ops import quant

        user_f, item_f = (
            np.asarray(t, np.float32) for t in self._tables(model)
        )
        state = serving_state(model)
        mesh = None
        if shard:
            from predictionio_tpu.parallel import sharding

            mesh = sharding.serving_mesh()
            if mesh is None:
                logger.warning(
                    "--shard-factors requested but only one device is "
                    "visible; quantized tables pin replicated"
                )
        if mesh is not None:
            user = sharding.shard_quantized_table(user_f, mesh)
            item = sharding.shard_quantized_table(item_f, mesh)
            state.shards = sharding.ShardInfo(
                mesh=mesh,
                rows={"user": int(user_f.shape[0]), "item": int(item_f.shape[0])},
            )
        else:
            user = quant.quantize_table(user_f)
            item = quant.quantize_table(item_f)
        self._set_tables(model, user, item)
        breakdown = {
            "int8": user.nbytes_codes + item.nbytes_codes,
            "scalesFloat32": user.nbytes_scales + item.nbytes_scales,
        }
        state.pinned = True
        state.bytes_by_dtype = breakdown
        state.quant = quant.QuantRuntime(
            mode=mode,
            bytes_by_dtype=breakdown,
            bytes_f32=user_f.nbytes + item_f.nbytes,
            # item-side error is what reorders results; one pass at
            # load time, reported on /stats.json quant
            error=quant.quantization_error(
                item_f,
                np.asarray(item.codes)[: item_f.shape[0]],
                np.asarray(item.scales)[: item_f.shape[0]],
            ),
        )
        return model, sum(breakdown.values())

    def release_pinned_model(self, model) -> None:
        """Drop a superseded generation's pinned buffers (hot reload must
        not accumulate one catalog of device memory per swap). For a
        SHARDED generation this must drop every device's shard handles —
        not just device 0's — so the host-gather strips the even-shard
        padding and the ShardInfo goes with the buffers. Quantized
        tables dequantize back to host f32 (np.asarray reads through the
        codes), and the QuantRuntime goes with them."""
        state = serving_state(model)
        # the AOT runtime is per-generation (its programs are lowered
        # against this generation's table shapes) — it retires with the
        # pinned buffers
        state.aot = None
        if not (state.pinned or state.quant is not None
                or state.shards is not None):
            return
        user, item = (np.asarray(t) for t in self._tables(model))
        if state.shards is not None:
            user = user[: state.shards.rows["user"]]
            item = item[: state.shards.rows["item"]]
        self._set_tables(model, user, item)
        state.shards = None
        state.pinned = False
        state.quant = None

    # --------------------------------------------------- AOT serving export
    def aot_export_for_serving(self, model, buckets: list) -> dict:
        """``--aot`` tier (workflow/aot.py): lower + serialize the pinned
        exact serving programs per pow2 k-bucket, so replicas boot by
        DESERIALIZING instead of tracing — zero serve-time compiles.

        The export mirrors the JIT path's deliberate program split —
        k-independent ``predict_scores`` plus per-bucket ``top_k_scores``
        (and the batch GEMM+top-k per chunk/bucket) — rather than fusing
        score+select into one program, so bit-identity with the jitted
        path holds by construction: same jaxprs, same rounding, same tie
        order. Sharded/quantized/ANN generations export nothing — their
        kernels close over live runtime objects (mesh, codes, index) and
        serve through their own budgeted paths."""
        state = serving_state(model)
        if state.shards is not None or state.quant is not None:
            return {}
        import jax
        from jax import export as jax_export

        from predictionio_tpu.ops.als import predict_scores, top_k_items_batch
        from predictionio_tpu.ops.topk import top_k_scores

        user, item = self._tables(model)
        n_users, rank = (int(d) for d in user.shape)
        n_items = int(item.shape[0])
        f32 = np.dtype(np.float32)
        vec = jax.ShapeDtypeStruct((rank,), f32)
        users = jax.ShapeDtypeStruct((n_users, rank), f32)
        items = jax.ShapeDtypeStruct((n_items, rank), f32)
        out = {"predict_scores": jax_export.export(predict_scores)(vec, items)}
        for kb in buckets:
            # bind the static k through a jitted closure — jax.export
            # lowers concrete avals, static_argnames stay host-side
            out[f"top_k_scores_b{kb}"] = jax_export.export(
                jax.jit(lambda s, _k=kb: top_k_scores(s, _k))
            )(jax.ShapeDtypeStruct((n_items,), f32))
            batch = jax.jit(
                lambda u, um, im, _k=kb: top_k_items_batch(u, um, im, _k)
            )
            # one program per row bucket a deploy can dispatch: those of
            # a default batcher's batches, and a full batchpredict chunk
            for rows in serving_row_buckets(self.BATCH_PREDICT_CHUNK):
                out[f"top_k_items_batch_c{rows}_b{kb}"] = jax_export.export(
                    batch
                )(jax.ShapeDtypeStruct((rows,), np.dtype(np.int32)),
                  users, items)
        return out

    def aot_warm_serving(self, model) -> None:
        """Warm at boot what the pinned single-query path would compile
        on its first query: the eager ``user_table[uidx]`` row gather
        (dynamic_slice + squeeze, cached by jax per operand), and each
        exported selection program on the operand it meets in
        :meth:`top_k` — the COMMITTED device output of the exported
        scoring program, for which jax lowers ``call_exported`` once
        more after ``load_runtime`` warmed it with host zeros. One
        :meth:`top_k` per exported k bucket pays both here (its lookups
        count among the runtime's ``hits``)."""
        state = serving_state(model)
        user = getattr(model, self.USER_TABLE)
        if not (state.pinned and user.shape[0]):
            return
        _ = user[0]
        entries = state.aot.manifest.get("entries", ()) if state.aot else ()
        for entry in entries:
            kb = entry["key"].removeprefix("top_k_scores_b")
            if kb.isdigit():
                self.top_k(model, 0, int(kb))

    # ------------------------------------------------------------- top-K
    def top_k(self, model, uidx: int, k: int) -> list[tuple[int, float]]:
        """The ``k`` best items of user row ``uidx`` as ``[(item row,
        score)]``, through whichever tier the model's state names. The
        caller has already held ``k`` to ``1 .. catalog``."""
        user_mat, item_mat = self._tables(model)
        state = serving_state(model)
        shards, quantrt = state.shards, state.quant
        if state.ann is not None:
            from predictionio_tpu.ops import ivf

            if quantrt is not None or shards is not None:
                # quantized and/or sharded user table: only the
                # requested row is dequantized / leaves its shard
                from predictionio_tpu.parallel import sharding

                qvec = np.asarray(sharding.take_rows(user_mat, [uidx]))[0]
            else:
                qvec = np.asarray(user_mat[uidx])
            ids, scores = ivf.query_topk(state.ann, qvec, k)
            return list(zip(ids, scores))
        if quantrt is not None:
            # quantized exact: int8 coarse scan with over-fetch, f32
            # rescore of the gathered candidates (ops/quant.py); routes
            # through the shard_map kernel under --shard-factors
            from predictionio_tpu.ops import quant

            ids_b, scores_b = quant.topk_users(
                quantrt, user_mat, item_mat, [uidx], k, shards=shards
            )
            idx, scores = ids_b[0], scores_b[0]
        elif shards is not None:
            # sharded exact: one dispatch, each device scores its item
            # shard, only the S*k finalists cross the interconnect
            from predictionio_tpu.parallel import sharding

            ids_b, scores_b = sharding.topk_users(
                shards, user_mat, item_mat, [uidx], k
            )
            idx, scores = ids_b[0], scores_b[0]
        elif isinstance(item_mat, np.ndarray):
            # host path: one GEMV + partial sort, microseconds at catalog
            # sizes below ~10^6 items (shared tie rule: ops/topk.py)
            from predictionio_tpu.ops.topk import top_k_host

            idx, scores = top_k_host(item_mat @ np.asarray(user_mat[uidx]), k)
        else:
            # pinned-device path: k buckets to a power of two (floor 16)
            # so the jitted selection compiles once per bucket — raw
            # query.num would key the jit cache at request cardinality
            # (piolint PIO306; same idiom as ivf.query_topk). Scoring is
            # a SEPARATE k-independent program (predict_scores) so the
            # GEMV's float rounding — and therefore tie order vs the
            # host path — cannot drift with the chosen bucket
            from predictionio_tpu.ops.als import predict_scores
            from predictionio_tpu.ops.topk import bucket_k, top_k_scores

            kb = bucket_k(k, int(item_mat.shape[0]))
            idx = scores = None
            aot = state.aot
            if aot is not None:
                # --aot tier 1: the SAME two programs, deserialized at
                # boot instead of traced here; any call-time failure
                # (e.g. shape drift after an online catalog grow)
                # disables the key and the jitted path takes over
                score_fn = aot.get("predict_scores")
                topk_fn = aot.get(f"top_k_scores_b{kb}")
                if score_fn is not None and topk_fn is not None:
                    try:
                        idx, scores = topk_fn(
                            score_fn(user_mat[uidx], item_mat)
                        )
                    except Exception as e:  # noqa: BLE001 - degrade, don't 500
                        aot.disable("predict_scores", str(e))
                        aot.disable(f"top_k_scores_b{kb}", str(e))
                        idx = scores = None
            if idx is None:
                idx, scores = top_k_scores(
                    predict_scores(user_mat[uidx], item_mat), kb
                )
            idx, scores = np.asarray(idx)[:k], np.asarray(scores)[:k]
        return [(int(i), float(s)) for i, s in zip(idx, scores)]

    def top_k_staged(
        self, model, valid: list
    ) -> Iterator[tuple[list, list, list]]:
        """Chunked top-k over ``valid = [(slot, uidx, k), ...]`` — see
        :func:`predictionio_tpu.templates.serving_util.chunked_topk`,
        whose tier arguments the model's state fills."""
        state = serving_state(model)
        return chunked_topk(
            *self._tables(model), valid,
            chunk=self.BATCH_PREDICT_CHUNK,
            ann=state.ann, shards=state.shards, quant=state.quant,
            aot=state.aot,
        )


# ------------------------------------------------ filtered item retrieval
@dataclasses.dataclass
class FilteredServingState(ServingState):
    """What :class:`FilteredItemRetrieval` keeps beside a deployed model."""

    #: pinned: the item factors and the category codes on the device, as
    #: ``ops.als.tile_items`` cut them
    item_tiles: Any = None
    code_tiles: Any = None
    #: ``(blocked ids, mask)`` of the last
    #: :meth:`FilteredItemRetrieval.blocked_mask`
    blocked: tuple | None = None


def category_arrays(categories: dict, item_index: BiMap) -> tuple[np.ndarray, BiMap]:
    """``{item id: categories}`` as ``(codes int32[I, C], name -> code)``:
    any number of distinct categories, ``C`` the most one item carries."""
    index = BiMap.string_index(
        sorted({c for cats in categories.values() for c in cats})
    )
    width = max([1, *(len(cats) for cats in categories.values())])
    codes = np.full((len(item_index), width), -1, np.int32)
    for item, cats in categories.items():
        row = item_index.get(item)
        if row is not None:
            codes[row, : len(cats)] = [index[c] for c in cats]
    return codes, index


#: floor of the wanted-category width of a filtered top-K
#: (``ops.topk.bucket_width``): categories are compared item by item, so
#: the floor is what a category page asks for. (The excluded ids are no
#: extent of the program: they reach it as pairs grouped by tile,
#: ``ops.als.tile_pairs``.)
WANTED_FLOOR = 2


class FilteredItemRetrieval:
    """The filtered top-K of a ``JaxAlgorithm`` whose model holds an item
    table under ``ITEM_TABLE``, its ``item_index``, and the category rule
    as ``categories`` (``{item id: names}``, as training read them) and,
    built from it on first use, ``category_codes`` / ``category_index``
    (:func:`category_arrays`). The engine brings the query vectors and,
    per query, the item ids left out and the category names asked for;
    the rules become one mask however the query is answered: on the host
    over one score row (:meth:`allowed_on_host`), or inside
    ``serving_util.chunked_topk(filt=...)``, which under ``pio deploy
    --pin-model`` selects in one tiled device program
    (``ops.als.top_k_items_filtered``) over the tiles
    :meth:`pin_model_for_serving` laid out."""

    ITEM_TABLE: str

    @staticmethod
    def category_codes(model) -> tuple[np.ndarray, BiMap]:
        codes = getattr(model, "category_codes", None)
        if codes is None:
            # two batches may fill this at once (the batcher has two in
            # flight): the index first, so whoever sees the codes sees both
            codes, model.category_index = category_arrays(
                model.categories, model.item_index
            )
            model.category_codes = codes
        return codes, model.category_index

    def pin_model_for_serving(self, model) -> tuple[Any, int]:
        """``--pin-model`` (workflow/device_state.py): the item table and the
        category codes go to the device once per model generation, cut into
        the tiles ``ops.als.top_k_items_filtered`` scans, and
        :meth:`filtered_top_k` selects there. Whatever the query vectors
        are rows of stays on the host: a batch's rows ride with its rules
        (``serving_util._filtered_topk`` says why). Returns the model and
        the device bytes it holds."""
        from predictionio_tpu.ops.als import tile_items

        codes, _ = self.category_codes(model)
        state = serving_state(model, FilteredServingState)
        state.blocked = None
        state.item_tiles = tile_items(
            np.asarray(getattr(model, self.ITEM_TABLE), np.float32), 0.0
        )
        state.code_tiles = tile_items(codes, -1)
        state.pinned = True
        state.bytes_by_dtype = {
            "float32": int(state.item_tiles.nbytes),
            "int32": int(state.code_tiles.nbytes),
        }
        return model, sum(state.bytes_by_dtype.values())

    @staticmethod
    def blocked_mask(model, blocked_ids: Collection[str]):
        """The mask of the items no query may be given (``blocked_ids``: an
        engine's out-of-stock items, say) over the item rows — the host's
        ``bool[items]`` or, pinned, the device's ``bool[tiles, width]`` with
        the padding past the catalog blocked too — made when the ids
        change, not per batch. Two batches in flight may hold different
        reads of them: each is served the mask of its own (the cache is
        one tuple, read once and assigned once)."""
        state = serving_state(model, FilteredServingState)
        cached = state.blocked
        if cached is not None and (
            cached[0] is blocked_ids or cached[0] == blocked_ids
        ):
            return cached[1]
        n = len(model.item_index)
        tiles = state.item_tiles
        mask = np.zeros(n if tiles is None else tiles.shape[0] * tiles.shape[2], bool)
        mask[n:] = True
        rows = [model.item_index.get(i) for i in blocked_ids]
        mask[[r for r in rows if r is not None]] = True
        if tiles is not None:
            import jax

            mask = jax.device_put(mask.reshape(tiles.shape[0], tiles.shape[2]))
        state.blocked = (frozenset(blocked_ids), mask)  # itself, if it is one
        return mask

    def topk_filter(
        self, model, left_out: Sequence[Iterable[str]],
        asked: Sequence[Sequence[str]], blocked_ids: Collection[str] = frozenset(),
    ) -> TopkFilter:
        """The rules of a batch as the arrays the top-K takes: per query the
        item ids it leaves out (``left_out``; unknown ids are dropped) and
        the category names it asks for (``asked``; empty = any), and the
        ids blocked for all of them."""
        from predictionio_tpu.ops.topk import NO_ITEM, bucket_width

        codes, category_index = self.category_codes(model)
        item_row = model.item_index.get
        out_rows = [
            [r for r in map(item_row, ids) if r is not None] for ids in left_out
        ]
        excluded = np.full(
            (len(out_rows), max(1, *map(len, out_rows))), NO_ITEM, np.int32
        )
        for row, rows in zip(excluded, out_rows):
            row[: len(rows)] = rows
        wanted = np.full(
            (len(asked), bucket_width(max(map(len, asked)), WANTED_FLOOR)),
            -2, np.int32,
        )
        for row, names in zip(wanted, asked):
            # a name no item carries is a code no item carries
            row[: len(names)] = [
                category_index.get(str(c), len(category_index)) for c in names
            ]
        count("filter.excludedIds",
              sum(map(len, out_rows)) + len(blocked_ids) * len(out_rows))
        count("filter.categoryRows", sum(1 for names in asked if names))
        state = serving_state(model, FilteredServingState)
        return TopkFilter(
            codes=codes if state.item_tiles is None else state.code_tiles,
            blocked=self.blocked_mask(model, blocked_ids),
            wanted=wanted, excluded=excluded, item_tiles=state.item_tiles,
        )

    def allowed_on_host(
        self, model, filt: TopkFilter, white_list: Sequence[str] | None = None,
    ) -> np.ndarray:
        """``bool[rows, items]``: the items each row of ``filt`` is allowed,
        on the host, by the rule the device program applies; under a
        ``white_list`` (a rule the device path does not take) only its
        items."""
        n = len(model.item_index)
        codes, _ = self.category_codes(model)
        allowed = allowed_items_host(
            codes, np.asarray(filt.blocked).reshape(-1)[:n], filt.wanted,
            filt.excluded,
        )
        if white_list:
            listed = np.zeros(n, dtype=bool)
            rows = [model.item_index.get(i) for i in white_list]
            listed[[r for r in rows if r is not None]] = True
            allowed &= listed
        return allowed

    def filtered_top_k(
        self, model, vectors, valid: Sequence[tuple[int, int, int]],
        filt: TopkFilter,
    ) -> list[tuple[int, PredictedResult]]:
        """The answers of ``valid = [(slot, row of vectors, k), ...]`` under
        ``filt`` (its rows in ``valid``'s order): the selection inside
        ``chunked_topk`` (on the device when the model is pinned), an
        answer shorter than ``k`` where the rules allow fewer."""
        inverse = model.item_index.inverse
        results = []
        for part, idx_l, score_l in chunked_topk(
            vectors, getattr(model, self.ITEM_TABLE), valid, filt=filt
        ):
            with span("format"):
                for (slot, _, k), ids, scs in zip(part, idx_l, score_l):
                    if len(ids) < k:
                        count("filter.shortAnswers", 1)
                    results.append((
                        slot,
                        PredictedResult(tuple(
                            ItemScore(item=inverse(i), score=s)
                            for i, s in zip(ids[:k], scs[:k])
                        )),
                    ))
        return results
