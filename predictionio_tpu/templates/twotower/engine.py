"""Two-Tower retrieval engine: interaction events -> sharded-embedding
towers -> personalized top-N queries.

The DLRM/two-tower stretch family (BASELINE.md configs[4]). No reference
counterpart exists — PredictionIO ships no deep-retrieval template — so
this is parity-plus built on the framework's standard DASE shape:

* DataSource — implicit interaction pairs from the event store (any of
  ``eventNames``), with the same multi-host coherence recipe as the
  other templates (merge counts by key, global sorted vocabularies).
* Algorithm — :func:`predictionio_tpu.ops.twotower.train_two_tower`:
  embedding tables sharded over the mesh's ``model`` axis (the
  shard-local-gather + psum lookup shared with the ALS sweep),
  in-batch sampled-softmax, optax adam.
* Serving — cosine top-N from the L2-normalized tower outputs with the
  usual seen-item filter; same Query/PredictedResult wire shapes as the
  Recommendation template, so SDK clients need no changes.

engine.json::

    {"engineFactory": "predictionio_tpu.templates.twotower:engine_factory",
     "datasource": {"params": {"appName": "myapp",
                               "eventNames": ["view", "buy"]}},
     "algorithms": [{"name": "twotower",
                     "params": {"embeddingDim": 64, "batchSize": 512,
                                "epochs": 5, "learningRate": 0.05}}]}
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Any, Sequence

import numpy as np

from predictionio_tpu.controller import (
    DataSource,
    Engine,
    FirstServing,
    IdentityPreparator,
    JaxAlgorithm,
    OptionAverageMetric,
    Params,
    SanityCheck,
    WorkflowContext,
)
from predictionio_tpu.data.aggregator import BiMap
from predictionio_tpu.data.store import PEventStore
from predictionio_tpu.ops.twotower import TwoTowerConfig, train_two_tower

__all__ = [
    "DataSourceParams",
    "TrainingData",
    "TwoTowerDataSource",
    "TwoTowerParams",
    "TwoTowerAlgorithm",
    "Query",
    "PredictedResult",
    "ItemScore",
    "engine_factory",
]


# ------------------------------------------------------------------- queries
@dataclasses.dataclass(frozen=True)
class Query:
    user: str
    num: int = 10


@dataclasses.dataclass(frozen=True)
class ItemScore:
    item: str
    score: float

    def to_json(self) -> dict:
        return {"item": self.item, "score": self.score}


@dataclasses.dataclass(frozen=True)
class PredictedResult:
    item_scores: tuple = ()

    def to_json(self) -> dict:
        return {"itemScores": [s.to_json() for s in self.item_scores]}


# --------------------------------------------------------------- data source
@dataclasses.dataclass(frozen=True)
class DataSourceParams(Params):
    app_name: str = ""
    event_names: Sequence[str] = ("view", "rate", "buy", "like")
    eval_k: int = 2
    json_aliases = {
        "appName": "app_name",
        "eventNames": "event_names",
        "evalK": "eval_k",
    }


@dataclasses.dataclass
class TrainingData(SanityCheck):
    rows: np.ndarray  # user idx, one entry per (user, item) pair
    cols: np.ndarray  # item idx
    user_index: BiMap
    item_index: BiMap
    seen: dict  # user id -> set of item ids (serving-time filter)

    def sanity_check(self) -> None:
        if self.rows.size == 0:
            raise ValueError("No interaction events found — check appName/eventNames")


class TwoTowerDataSource(DataSource):
    params_class = DataSourceParams

    def __init__(self, params: DataSourceParams):
        super().__init__(params)

    def _read_pairs(self, ctx: WorkflowContext) -> list:
        """Sorted distinct (user, item) pairs — the GLOBAL set on every
        host. Training batches are replicated across a multi-host job and
        the saved model's seen-filter must cover every user, so a
        partitioned (per-host) merge would be incoherent; pairs are two
        ids each, small next to the raw events they dedup."""
        p = self.params
        if ctx.num_hosts == 1:
            # columnar fast path: dedup happens over code arrays, so the
            # remaining Python is O(distinct pairs), not O(events)
            from predictionio_tpu.templates.columnar_util import aggregate_pairs

            cols = PEventStore.find_columns(
                app_name=p.app_name, event_names=list(p.event_names)
            )
            u_sel, i_sel, _ = aggregate_pairs(cols)
            return sorted(
                zip(
                    cols.entity_vocab[u_sel].tolist(),
                    cols.target_vocab[i_sel].tolist(),
                )
            )
        pairs: dict[tuple[str, str], bool] = {}
        for e in PEventStore.find(
            app_name=p.app_name,
            event_names=list(p.event_names),
            shard_index=ctx.host_index,
            num_shards=ctx.num_hosts,
        ):
            if e.target_entity_id is None:
                continue
            pairs[(e.entity_id, e.target_entity_id)] = True
        if ctx.num_hosts > 1:
            from predictionio_tpu.parallel.exchange import allgather_objects

            merged = set()
            for contrib in allgather_objects(sorted(pairs)):
                merged.update(tuple(pr) for pr in contrib)
            return sorted(merged)
        return sorted(pairs)

    @staticmethod
    def _to_training_data(pairs: Sequence) -> TrainingData:
        user_index = BiMap.string_index(sorted({u for u, _ in pairs}))
        item_index = BiMap.string_index(sorted({i for _, i in pairs}))
        n = len(pairs)
        rows = np.fromiter((user_index[u] for u, _ in pairs), np.int64, n)
        cols = np.fromiter((item_index[i] for _, i in pairs), np.int64, n)
        seen: dict[str, set] = {}
        for u, i in pairs:
            seen.setdefault(u, set()).add(i)
        return TrainingData(rows, cols, user_index, item_index, seen)

    def _read_training_columnar(self, ctx: WorkflowContext) -> TrainingData:
        """Vectorized single-host read: columnar bulk scan + grouped pair
        dedup (in-batch softmax has no per-pair weight, so a distinct-
        pair set is the right shape) — no per-event Python. The seen-
        filter dict is built from the (much smaller) deduped pair set."""
        from predictionio_tpu.templates.columnar_util import (
            aggregate_pairs,
            densify_pairs,
        )

        p = self.params
        cols = PEventStore.find_columns(
            app_name=p.app_name, event_names=list(p.event_names)
        )
        u_sel, i_sel, _counts = aggregate_pairs(cols)
        rows, cols_idx, user_vocab, item_vocab = densify_pairs(
            cols, u_sel, i_sel
        )
        user_index = BiMap.string_index(user_vocab)
        item_index = BiMap.string_index(item_vocab)
        seen: dict[str, set] = {}
        for r, c in zip(rows.tolist(), cols_idx.tolist()):
            seen.setdefault(user_vocab[r], set()).add(item_vocab[c])
        return TrainingData(rows, cols_idx, user_index, item_index, seen)

    def read_training(self, ctx: WorkflowContext) -> TrainingData:
        # training consumes distinct (user, item) PAIRS — in-batch softmax
        # has no per-pair weight, so a set (not counts) is the right shape
        if ctx.num_hosts == 1:
            return self._read_training_columnar(ctx)
        return self._to_training_data(self._read_pairs(ctx))

    def read_eval(self, ctx: WorkflowContext):
        """K-fold split by stable hash of (user, item): train on k-1
        folds, query each user with held-out interactions for the full
        ranking, actual = the held-out item ids (consumed by
        :class:`RecallAtK`). Mirrors the Recommendation template's
        ``readEval`` shape."""
        import zlib

        pairs = self._read_pairs(ctx)
        k = max(2, self.params.eval_k)

        def fold_of(u: str, i: str) -> int:
            return zlib.crc32(f"{u}\x00{i}".encode()) % k

        folds = []
        for fold in range(k):
            train = [pr for pr in pairs if fold_of(*pr) != fold]
            held = [pr for pr in pairs if fold_of(*pr) == fold]
            td = self._to_training_data(train)
            by_user: dict[str, list] = {}
            for u, i in held:
                # only users the fold's model knows can be queried
                if u in td.user_index:
                    by_user.setdefault(u, []).append(i)
            num_items = len(td.item_index)
            qa = [
                (Query(user=u, num=num_items), tuple(items))
                for u, items in by_user.items()
                if items
            ]
            folds.append((td, {"fold": fold}, qa))
        return folds


# ----------------------------------------------------------------- algorithm
@dataclasses.dataclass(frozen=True)
class TwoTowerParams(Params):
    embedding_dim: int = 32
    batch_size: int = 256
    epochs: int = 5
    learning_rate: float = 0.05
    temperature: float = 0.1
    seed: int = 0
    #: rank the catalog on the accelerator (huge catalogs / batched
    #: queries); guarded by the same deploy-time latency probe as the
    #: ALS template
    serve_on_device: bool = False
    device_latency_budget_ms: float = 10.0
    #: "bfloat16" (TPU-native default) or "float32" for bit-for-bit runs
    gemm_dtype: str = "bfloat16"
    #: fused softmax-CE kernel: "auto" | "off" | "interpret" (see
    #: ops/fused_ce.py) — the opt-out if the Pallas path misbehaves
    fused_ce: str = "auto"
    json_aliases = {
        "embeddingDim": "embedding_dim",
        "batchSize": "batch_size",
        "learningRate": "learning_rate",
        "serveOnDevice": "serve_on_device",
        "deviceLatencyBudgetMs": "device_latency_budget_ms",
        "gemmDtype": "gemm_dtype",
        "fusedCe": "fused_ce",
    }


@dataclasses.dataclass
class TwoTowerServingModel:
    user_vecs: Any  # [U, D] L2-normalized
    item_vecs: Any  # [I, D] L2-normalized
    user_index: BiMap
    item_index: BiMap
    seen: dict
    loss_history: tuple = ()


class TwoTowerAlgorithm(JaxAlgorithm):
    params_class = TwoTowerParams
    query_class = Query

    def __init__(self, params: TwoTowerParams):
        super().__init__(params)

    def train(self, ctx: WorkflowContext, pd: TrainingData) -> TwoTowerServingModel:
        p = self.params
        init_user = init_item = None
        warm = ctx.warm_model
        if isinstance(warm, TwoTowerServingModel):
            # same carry-over as the ALS template: entities present in
            # both catalogs keep their embeddings; NEW ones draw the
            # tower's own signed-normal cold init (not ALS's abs draw)
            from predictionio_tpu.templates.serving_util import (
                aligned_factor_init,
            )

            def fresh(rng, shape):
                return rng.standard_normal(shape) / np.sqrt(shape[1])

            init_user, n_u = aligned_factor_init(
                warm.user_vecs, warm.user_index, pd.user_index,
                p.embedding_dim, p.seed, fresh=fresh,
            )
            init_item, n_i = aligned_factor_init(
                warm.item_vecs, warm.item_index, pd.item_index,
                p.embedding_dim, p.seed + 1, fresh=fresh,
            )
            logging.getLogger(__name__).info(
                "Warm start: carried %d/%d user and %d/%d item embeddings",
                n_u, len(pd.user_index), n_i, len(pd.item_index),
            )
        model = train_two_tower(
            pd.rows,
            pd.cols,
            num_users=len(pd.user_index),
            num_items=len(pd.item_index),
            config=TwoTowerConfig(
                dim=p.embedding_dim,
                batch_size=p.batch_size,
                epochs=p.epochs,
                learning_rate=p.learning_rate,
                temperature=p.temperature,
                seed=p.seed,
                gemm_dtype=p.gemm_dtype,
                fused_ce=p.fused_ce,
            ),
            mesh=ctx.mesh,
            init_user=init_user,
            init_item=init_item,
            info=ctx.run_info.setdefault("twotower", {}),
        )
        return TwoTowerServingModel(
            user_vecs=model.user_vecs,
            item_vecs=model.item_vecs,
            user_index=pd.user_index,
            item_index=pd.item_index,
            seen=pd.seen,
            loss_history=model.loss_history,
        )

    def prepare_model_for_serving(
        self, model: TwoTowerServingModel
    ) -> TwoTowerServingModel:
        if self.params.serve_on_device:
            import jax

            from predictionio_tpu.templates.serving_util import (
                device_latency_probe,
            )

            model.user_vecs = jax.device_put(np.asarray(model.user_vecs))
            model.item_vecs = jax.device_put(np.asarray(model.item_vecs))
            if len(model.user_index):
                probe = Query(user=model.user_index.keys()[0], num=4)
                model._pio_latency_probe = device_latency_probe(
                    lambda: self.predict(model, probe),
                    self.params.device_latency_budget_ms,
                )
                if not model._pio_latency_probe["ok"]:
                    model.user_vecs = np.asarray(model.user_vecs)
                    model.item_vecs = np.asarray(model.item_vecs)
            return model
        model.user_vecs = np.ascontiguousarray(model.user_vecs)
        model.item_vecs = np.ascontiguousarray(model.item_vecs)
        if len(model.user_index):
            self.predict(model, Query(user=model.user_index.keys()[0], num=4))
        return model

    # ------------------------------------------------------ pinned serving
    def pin_model_for_serving(
        self, model: TwoTowerServingModel
    ) -> tuple[TwoTowerServingModel, int]:
        """``--pin-model`` cache tier (workflow/device_state.py): same
        contract as the recommendation template — tower matrices are
        ``device_put`` once per model generation, predictions flip onto
        the jitted device path, and the pinned bytes surface on
        ``/stats.json``."""
        import jax

        user = model.user_vecs
        item = model.item_vecs
        if isinstance(user, np.ndarray):
            user = jax.device_put(user)
        if isinstance(item, np.ndarray):
            item = jax.device_put(item)
        model.user_vecs = user
        model.item_vecs = item
        model._pio_pinned = True
        nbytes = int(user.size) * user.dtype.itemsize
        nbytes += int(item.size) * item.dtype.itemsize
        model._pio_bytes_by_dtype = {"float32": nbytes}
        return model, nbytes

    # ------------------------------------------------------ sharded serving
    def shard_model_for_serving(
        self, model: TwoTowerServingModel
    ) -> tuple[TwoTowerServingModel, int]:
        """``--shard-factors`` tier: same contract as the recommendation
        template — tower matrices shard row-wise over a one-axis model
        mesh (each device holds ``rows/S``), retrieval routes through
        the tie-stable shard_map kernel, single-device hosts fall back
        to plain pinning."""
        from predictionio_tpu.parallel import sharding

        mesh = sharding.serving_mesh()
        if mesh is None:
            logging.getLogger(__name__).warning(
                "--shard-factors requested but only one device is "
                "visible; falling back to --pin-model replication"
            )
            return self.pin_model_for_serving(model)
        user = sharding.shard_table(np.asarray(model.user_vecs), mesh)
        item = sharding.shard_table(np.asarray(model.item_vecs), mesh)
        info = sharding.ShardInfo(
            mesh=mesh,
            rows={
                "user": int(np.asarray(model.user_vecs).shape[0]),
                "item": int(np.asarray(model.item_vecs).shape[0]),
            },
        )
        model.user_vecs = user
        model.item_vecs = item
        model._pio_shards = info
        model._pio_pinned = True
        nbytes = int(user.size) * user.dtype.itemsize
        nbytes += int(item.size) * item.dtype.itemsize
        model._pio_bytes_by_dtype = {"float32": nbytes}
        return model, nbytes

    # ---------------------------------------------------- quantized serving
    def quantize_model_for_serving(
        self, model: TwoTowerServingModel, mode: str = "int8",
        shard: bool = False,
    ) -> tuple[TwoTowerServingModel, int]:
        """``--quantize int8`` tier: same contract as the recommendation
        template — tower matrices pin as int8 codes + per-row scales,
        retrieval runs the recall-guarded two-stage kernel, and
        ``shard=True`` shards codes and scales over the model mesh so
        the memory tiers compose multiplicatively."""
        from predictionio_tpu.ops import quant

        user_f = np.asarray(model.user_vecs, np.float32)
        item_f = np.asarray(model.item_vecs, np.float32)
        mesh = None
        if shard:
            from predictionio_tpu.parallel import sharding

            mesh = sharding.serving_mesh()
            if mesh is None:
                logging.getLogger(__name__).warning(
                    "--shard-factors requested but only one device is "
                    "visible; quantized tables pin replicated"
                )
        if mesh is not None:
            from predictionio_tpu.parallel import sharding

            user = sharding.shard_quantized_table(user_f, mesh)
            item = sharding.shard_quantized_table(item_f, mesh)
            model._pio_shards = sharding.ShardInfo(
                mesh=mesh,
                rows={
                    "user": int(user_f.shape[0]),
                    "item": int(item_f.shape[0]),
                },
            )
        else:
            user = quant.quantize_table(user_f)
            item = quant.quantize_table(item_f)
        model.user_vecs = user
        model.item_vecs = item
        model._pio_pinned = True
        breakdown = {
            "int8": user.nbytes_codes + item.nbytes_codes,
            "scalesFloat32": user.nbytes_scales + item.nbytes_scales,
        }
        model._pio_bytes_by_dtype = breakdown
        model._pio_quant = quant.QuantRuntime(
            mode=mode,
            bytes_by_dtype=breakdown,
            bytes_f32=user_f.nbytes + item_f.nbytes,
            error=quant.quantization_error(
                item_f,
                np.asarray(item.codes)[: item_f.shape[0]],
                np.asarray(item.scales)[: item_f.shape[0]],
            ),
        )
        return model, sum(breakdown.values())

    def release_pinned_model(self, model: TwoTowerServingModel) -> None:
        shards = getattr(model, "_pio_shards", None)
        quantized = getattr(model, "_pio_quant", None) is not None
        # the AOT runtime is lowered against this generation's tower
        # shapes — it retires with the pinned buffers
        if getattr(model, "_pio_aot", None) is not None:
            model._pio_aot = None
        if shards is not None:
            # every device's shard handles die here, and the host copy
            # strips the even-shard padding rows (np.asarray dequantizes
            # a --quantize table back to f32)
            model.user_vecs = np.asarray(model.user_vecs)[
                : shards.rows["user"]
            ]
            model.item_vecs = np.asarray(model.item_vecs)[
                : shards.rows["item"]
            ]
            model._pio_shards = None
            model._pio_pinned = False
            model._pio_quant = None
            return
        if getattr(model, "_pio_pinned", False) or quantized:
            model.user_vecs = np.asarray(model.user_vecs)
            model.item_vecs = np.asarray(model.item_vecs)
            model._pio_pinned = False
            model._pio_quant = None

    # --------------------------------------------------- AOT serving export
    def aot_export_for_serving(
        self, model: TwoTowerServingModel, buckets: list
    ) -> dict:
        """``--aot`` tier (workflow/aot.py): same contract as the
        recommendation template — serialize the pinned exact serving
        programs (k-independent ``predict_scores`` + per-bucket top-k,
        plus the chunked batch GEMM) so replicas deserialize at boot
        instead of tracing; the two-program split keeps results
        bit-identical to the jitted path by construction. Sharded and
        quantized generations export nothing (their kernels close over
        live runtime objects)."""
        if getattr(model, "_pio_shards", None) is not None:
            return {}
        if getattr(model, "_pio_quant", None) is not None:
            return {}
        import jax
        from jax import export as jax_export

        from predictionio_tpu.ops.als import predict_scores, top_k_items_batch
        from predictionio_tpu.ops.topk import top_k_scores
        from predictionio_tpu.templates.serving_util import serving_row_buckets

        n_users, rank = (int(d) for d in model.user_vecs.shape)
        n_items = int(model.item_vecs.shape[0])
        f32 = np.dtype(np.float32)
        vec = jax.ShapeDtypeStruct((rank,), f32)
        users = jax.ShapeDtypeStruct((n_users, rank), f32)
        items = jax.ShapeDtypeStruct((n_items, rank), f32)
        out = {"predict_scores": jax_export.export(predict_scores)(vec, items)}
        for kb in buckets:
            out[f"top_k_scores_b{kb}"] = jax_export.export(
                jax.jit(lambda s, _k=kb: top_k_scores(s, _k))
            )(jax.ShapeDtypeStruct((n_items,), f32))
            batch = jax.jit(
                lambda u, um, im, _k=kb: top_k_items_batch(u, um, im, _k)
            )
            for rows in serving_row_buckets():
                out[f"top_k_items_batch_c{rows}_b{kb}"] = jax_export.export(
                    batch
                )(jax.ShapeDtypeStruct((rows,), np.dtype(np.int32)),
                  users, items)
        return out

    def aot_warm_serving(self, model: TwoTowerServingModel) -> None:
        """Warm the pinned predict path's eager GLUE at boot: the
        ``user_vecs[uidx]`` row gather (dynamic_slice + squeeze) is
        index-operand cached by jax, so one call here compiles the
        executables every user's query will reuse (see the
        recommendation template's twin)."""
        if getattr(model, "_pio_pinned", False):
            _ = model.user_vecs[0]
    def build_ann_for_serving(
        self, model: TwoTowerServingModel, ann
    ) -> tuple[TwoTowerServingModel, dict]:
        """``--ann`` retrieval tier (workflow/device_state.py): IVF over
        the L2-normalized item-tower embeddings; serving scores only
        ``nprobe`` cluster slabs per query. The seen-item filter keeps
        its over-fetch (num + |seen| candidates fetched BEFORE the
        merge), so ANN answers still hold ``num`` unseen items whenever
        the probed clusters do."""
        from predictionio_tpu.ops import ivf

        shards = getattr(model, "_pio_shards", None)
        items = np.asarray(model.item_vecs)  # dequantizes under --quantize
        if shards is not None:
            items = items[: shards.rows["item"]]
        index, info = ivf.build_ivf(
            items,
            nlist=ann.nlist, seed=ann.seed, iters=ann.kmeans_iters,
            quantize=getattr(model, "_pio_quant", None) is not None,
        )
        model._pio_ann = ivf.AnnRuntime(index, ann.nprobe, info)
        if shards is not None:
            info = dict(info, **ivf.shard_runtime(model._pio_ann, shards.mesh))
        info = dict(info, algorithm=type(self).__name__,
                    nprobe=model._pio_ann.nprobe)
        return model, info

    def release_ann_state(self, model: TwoTowerServingModel) -> None:
        if getattr(model, "_pio_ann", None) is not None:
            model._pio_ann = None

    # ----------------------------------------------- online streaming SGD
    def online_trainer_spec(self, model: TwoTowerServingModel) -> dict:
        """Opt into the streaming mini-batch trainer (``pio deploy
        --online``; online/trainer.py): towers have no closed-form
        fold-in, so their online path is small SGD steps on fresh pairs
        with the SAME in-batch softmax objective training uses."""
        p = self.params
        return {
            "learning_rate": p.learning_rate,
            "temperature": p.temperature,
            "seed": p.seed,
        }

    def apply_online_update(self, model: TwoTowerServingModel, upd) -> dict:
        """Swap streamed rows into the live towers — called under the
        query service's generation lock (row scatters only; the SGD ran
        on the trainer thread). Also grows the serving-time seen-item
        filter with the folded pairs so fresh interactions filter out of
        recommendations immediately, coherent with the row updates."""
        from predictionio_tpu.workflow import device_state

        info = {"usersUpdated": 0, "itemsUpdated": 0,
                "usersAdded": 0, "itemsAdded": 0}
        if upd.user_ids:
            info["usersUpdated"], info["usersAdded"] = (
                device_state.swap_side_rows(
                    model, upd.user_ids, upd.user_rows,
                    "user_vecs", "user_index", rows_before_index=True,
                )
            )
        if upd.item_ids:
            info["itemsUpdated"], info["itemsAdded"] = (
                device_state.swap_side_rows(
                    model, upd.item_ids, upd.item_rows,
                    "item_vecs", "item_index", rows_before_index=False,
                )
            )
            ann_info = device_state.update_ann_items(
                model, upd.item_ids, upd.item_rows
            )
            if ann_info is not None:
                info["ann"] = ann_info
        for u, i in upd.seen_pairs:
            # copy-on-write per user: a reader iterating the old set must
            # never observe a concurrent mutation
            model.seen[u] = set(model.seen.get(u, ())) | {i}
        return info

    def batch_predict(
        self, model: TwoTowerServingModel, queries
    ) -> list[tuple[int, PredictedResult]]:
        """Batch-amortized retrieval (same chunked-GEMM core as the ALS
        template — `pio batchpredict` and eval sweeps go through here
        instead of one GEMV/dispatch per query). Seen-item filtering
        matches :meth:`predict`: fetch ``num + len(seen)`` candidates,
        then drop seen ones host-side."""
        from predictionio_tpu.templates.serving_util import chunked_topk

        n_items = len(model.item_index)
        results: list[tuple[int, PredictedResult]] = []
        valid: list[tuple[int, int, int]] = []
        seen_by_slot: dict[int, tuple] = {}
        nums: dict[int, int] = {}
        for idx, q in queries:
            uidx = model.user_index.get(q.user)
            num = int(q.num)
            if uidx is None or num <= 0:
                results.append((idx, PredictedResult(())))
                continue
            seen = model.seen.get(q.user, ())
            k = min(num + len(seen), n_items)
            if k <= 0:
                results.append((idx, PredictedResult(())))
                continue
            seen_by_slot[idx] = seen
            nums[idx] = num
            valid.append((idx, uidx, k))
        inverse = model.item_index.inverse
        for part, idx_l, score_l in chunked_topk(
            model.user_vecs, model.item_vecs, valid,
            ann=getattr(model, "_pio_ann", None),
            shards=getattr(model, "_pio_shards", None),
            quant=getattr(model, "_pio_quant", None),
            aot=getattr(model, "_pio_aot", None),
        ):
            for (oi, _, k), ids, scs in zip(part, idx_l, score_l):
                seen = seen_by_slot[oi]
                num = nums[oi]
                out = []
                for i, s in zip(ids[:k], scs[:k]):
                    item = inverse(i)
                    if item in seen:
                        continue
                    out.append(ItemScore(item=item, score=s))
                    if len(out) >= num:
                        break
                results.append((oi, PredictedResult(tuple(out))))
        return results

    def predict(self, model: TwoTowerServingModel, query: Query) -> PredictedResult:
        uidx = model.user_index.get(query.user)
        if uidx is None or int(query.num) <= 0:
            return PredictedResult(())
        seen = model.seen.get(query.user, ())
        # over-fetch num + |seen| BEFORE the top-K so the post-hoc seen
        # filter still leaves num items (applies to the exact and ANN
        # paths alike)
        k = min(int(query.num) + len(seen), len(model.item_index))
        if k <= 0:
            return PredictedResult(())
        ann = getattr(model, "_pio_ann", None)
        shards = getattr(model, "_pio_shards", None)
        quantrt = getattr(model, "_pio_quant", None)
        if ann is not None:
            from predictionio_tpu.ops import ivf

            if quantrt is not None or shards is not None:
                from predictionio_tpu.parallel import sharding

                qvec = np.asarray(
                    sharding.take_rows(model.user_vecs, [uidx])
                )[0]
            else:
                qvec = np.asarray(model.user_vecs[uidx])
            ids, sc = ivf.query_topk(ann, qvec, k)
            pairs = list(zip(ids, sc))
        elif quantrt is not None:
            from predictionio_tpu.ops import quant

            ids_b, sc_b = quant.topk_users(
                quantrt, model.user_vecs, model.item_vecs, [uidx], k,
                shards=shards,
            )
            pairs = [(int(i), float(s)) for i, s in zip(ids_b[0], sc_b[0])]
        elif shards is not None:
            from predictionio_tpu.parallel import sharding

            ids_b, sc_b = sharding.topk_users(
                shards, model.user_vecs, model.item_vecs, [uidx], k
            )
            pairs = [(int(i), float(s)) for i, s in zip(ids_b[0], sc_b[0])]
        elif isinstance(model.item_vecs, np.ndarray):
            from predictionio_tpu.ops.topk import top_k_host

            scores = model.item_vecs @ np.asarray(model.user_vecs[uidx])
            # shared tie rule — descending score, ascending item index
            # (ops/topk.py), so host and device paths agree
            top, vals = top_k_host(scores, k)
            pairs = [(int(i), float(s)) for i, s in zip(top, vals)]
        else:
            # k buckets to a power of two (floor 16) so the jitted
            # selection compiles once per bucket — raw query.num would
            # key the jit cache at request cardinality (piolint PIO306).
            # Scoring runs in the k-independent predict_scores program so
            # GEMV rounding (and tie order vs the host path) cannot
            # drift with the chosen bucket
            from predictionio_tpu.ops.als import predict_scores
            from predictionio_tpu.ops.topk import bucket_k, top_k_scores

            kb = bucket_k(k, int(model.item_vecs.shape[0]))
            idx = sc = None
            aot = getattr(model, "_pio_aot", None)
            if aot is not None:
                # --aot tier 1: same two programs, deserialized at boot;
                # call-time failure disables the key and the jitted path
                # takes over on the next dispatch
                score_fn = aot.get("predict_scores")
                topk_fn = aot.get(f"top_k_scores_b{kb}")
                if score_fn is not None and topk_fn is not None:
                    try:
                        dev_scores = score_fn(
                            model.user_vecs[uidx], model.item_vecs
                        )
                        idx, sc = topk_fn(dev_scores)
                    except Exception as e:  # noqa: BLE001 - degrade, don't 500
                        aot.disable("predict_scores", str(e))
                        aot.disable(f"top_k_scores_b{kb}", str(e))
                        idx = sc = None
            if idx is None:
                dev_scores = predict_scores(
                    model.user_vecs[uidx], model.item_vecs
                )
                idx, sc = top_k_scores(dev_scores, kb)
            pairs = [
                (int(i), float(s))
                for i, s in zip(np.asarray(idx)[:k], np.asarray(sc)[:k])
            ]
        out = []
        for i, score in pairs:
            item = model.item_index.inverse(i)
            if item in seen:
                continue
            out.append(ItemScore(item=item, score=score))
            if len(out) >= int(query.num):
                break
        return PredictedResult(tuple(out))


class RecallAtK(OptionAverageMetric):
    """Fraction of held-out positives recovered in the top-k."""

    def __init__(self, k: int = 10):
        self.k = k

    def header(self) -> str:
        return f"Recall@{self.k}"

    def calculate_unit(self, query, predicted: PredictedResult, actual) -> float | None:
        positives = set(actual)
        if not positives:
            return None
        top = {s.item for s in predicted.item_scores[: self.k]}
        return len(top & positives) / len(positives)


def engine_factory() -> Engine:
    return Engine(
        datasource_class=TwoTowerDataSource,
        preparator_class=IdentityPreparator,
        algorithms_class_map={"twotower": TwoTowerAlgorithm},
        serving_class=FirstServing,
    )
