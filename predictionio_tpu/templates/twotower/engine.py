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
from predictionio_tpu.templates.retrieval import TwoTableRetrieval
from predictionio_tpu.utils.spans import span

__all__ = [
    "DataSourceParams",
    "TrainingData",
    "TwoTowerDataSource",
    "TwoTowerParams",
    "TwoTowerAlgorithm",
    "SeenItems",
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


class SeenItems:
    """The serving-time filter, user code -> the item codes the user has
    interacted with, as two arrays: ``items[offsets[u]:offsets[u + 1]]``
    (ascending) are user ``u``'s. Built from the training pairs with no
    per-pair Python and pickled as two buffers: at 11.45 M pairs the dict
    of 4.57 M sets this replaces took 33 s to build and 7 s to pickle
    (measured on a host, PR 32). What `--online` folds in afterwards lies
    in a small overlay, user code -> all of that user's codes, replaced
    whole on every addition so that a reader never sees a set change."""

    def __init__(self, offsets: np.ndarray, items: np.ndarray):
        self.offsets = offsets  # [users + 1] int64
        self.items = items  # [pairs] int32
        self._added: dict[int, np.ndarray] = {}

    @classmethod
    def from_pairs(cls, rows: np.ndarray, cols: np.ndarray, n_users: int) -> "SeenItems":
        """From distinct (user code, item code) pairs in any order (the
        columnar read hands them sorted already: no sort then)."""
        rows, cols = np.asarray(rows, np.int64), np.asarray(cols, np.int64)
        key = rows * (int(cols.max()) + 1 if cols.size else 1) + cols
        items = cols.astype(np.int32)
        if not np.all(key[1:] > key[:-1]):
            items = items[np.argsort(key, kind="stable")]
        offsets = np.zeros(n_users + 1, np.int64)
        np.cumsum(np.bincount(rows, minlength=n_users), out=offsets[1:])
        return cls(offsets, items)

    def codes(self, user: int) -> np.ndarray:
        """The item codes of user code ``user``, ascending."""
        added = self._added.get(user)
        if added is not None:
            return added
        if 0 <= user < self.offsets.size - 1:
            return self.items[self.offsets[user]:self.offsets[user + 1]]
        return self.items[:0]

    def add(self, user: int, item: int) -> None:
        self._added[user] = np.union1d(self.codes(user), np.int32(item))

    def as_dict(self, user_index: BiMap, item_index: BiMap) -> dict:
        """``{user id: {item ids}}`` of every user with an item."""
        users = set(np.flatnonzero(np.diff(self.offsets)).tolist()) | set(self._added)
        return {
            user_index.inverse(u): {item_index.inverse(int(i)) for i in self.codes(u)}
            for u in users
        }


@dataclasses.dataclass
class TrainingData(SanityCheck):
    rows: np.ndarray  # user idx, one entry per (user, item) pair
    cols: np.ndarray  # item idx
    user_index: BiMap
    item_index: BiMap
    seen: SeenItems  # the serving-time filter

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
        seen = SeenItems.from_pairs(rows, cols, len(user_index))
        return TrainingData(rows, cols, user_index, item_index, seen)

    def _read_training_columnar(self, ctx: WorkflowContext) -> TrainingData:
        """Vectorized single-host read: columnar bulk scan + grouped pair
        dedup (in-batch softmax has no per-pair weight, so a distinct-
        pair set is the right shape) — no per-event Python. Its three
        parts are spans (``train.pairs``, ``train.id_maps``,
        ``train.seen``) and land in ``kernels.twotower.readSeconds``."""
        from predictionio_tpu.templates.columnar_util import (
            aggregate_pairs,
            densify_pairs,
        )

        p = self.params
        with span("train.scan") as scan:
            cols = PEventStore.find_columns(
                app_name=p.app_name, event_names=list(p.event_names)
            )
        with span("train.pairs") as pairs:
            u_sel, i_sel, _counts = aggregate_pairs(cols)
        with span("train.id_maps") as id_maps:
            rows, cols_idx, user_vocab, item_vocab = densify_pairs(
                cols, u_sel, i_sel
            )
            user_index = BiMap.string_index(user_vocab)
            item_index = BiMap.string_index(item_vocab)
        with span("train.seen") as seen_span:
            seen = SeenItems.from_pairs(rows, cols_idx, len(user_index))
        ctx.run_info.setdefault("twotower", {})["readSeconds"] = {
            "scan": round(scan.seconds, 3),
            "pairs": round(pairs.seconds, 3),
            "idMaps": round(id_maps.seconds, 3),
            "seen": round(seen_span.seconds, 3),
        }
        # the calling thread's CPU seconds in each (0 where its collector
        # takes none)
        ctx.run_info["twotower"]["readCpuSeconds"] = {
            "scan": round(scan.cpu_seconds, 3),
            "pairs": round(pairs.cpu_seconds, 3),
            "idMaps": round(id_maps.cpu_seconds, 3),
            "seen": round(seen_span.cpu_seconds, 3),
        }
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
    #: :class:`SeenItems`; a ``{user id: item ids}`` dict (a blob stored
    #: before PR 32, a test's model) is read the same
    seen: Any
    loss_history: tuple = ()

    def seen_items(self, user: str):
        """The ids of the items ``user`` has interacted with."""
        if isinstance(self.seen, dict):
            return self.seen.get(user, ())
        uidx = self.user_index.get(user)
        if uidx is None:
            return ()
        inverse = self.item_index.inverse
        return {inverse(int(i)) for i in self.seen.codes(uidx)}


class TwoTowerAlgorithm(TwoTableRetrieval, JaxAlgorithm):
    params_class = TwoTowerParams
    query_class = Query
    USER_TABLE = "user_vecs"
    ITEM_TABLE = "item_vecs"

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
            if isinstance(model.seen, dict):
                model.seen[u] = set(model.seen.get(u, ())) | {i}
                continue
            u_code, i_code = model.user_index.get(u), model.item_index.get(i)
            if u_code is not None and i_code is not None:
                model.seen.add(u_code, i_code)
        return info

    def batch_predict(
        self, model: TwoTowerServingModel, queries
    ) -> list[tuple[int, PredictedResult]]:
        """Batch-amortized retrieval (same chunked-GEMM core as the ALS
        template — `pio batchpredict` and eval sweeps go through here
        instead of one GEMV/dispatch per query). Seen-item filtering
        matches :meth:`predict`: fetch ``num + len(seen)`` candidates,
        then drop seen ones host-side."""
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
            seen = model.seen_items(q.user)
            k = min(num + len(seen), n_items)
            if k <= 0:
                results.append((idx, PredictedResult(())))
                continue
            seen_by_slot[idx] = seen
            nums[idx] = num
            valid.append((idx, uidx, k))
        inverse = model.item_index.inverse
        for part, idx_l, score_l in self.top_k_staged(model, valid):
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
        seen = model.seen_items(query.user)
        # over-fetch num + |seen| BEFORE the top-K so the post-hoc seen
        # filter still leaves num items (applies to the exact and ANN
        # paths alike)
        k = min(int(query.num) + len(seen), len(model.item_index))
        if k <= 0:
            return PredictedResult(())
        out = []
        for i, score in self.top_k(model, uidx, k):
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
