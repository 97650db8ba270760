"""E-Commerce engine: view/buy events -> implicit ALS + business rules.

Parity map (reference scala-parallel-ecommercerecommendation template):

* ``DataSource.scala`` — ``view``/``buy`` events + ``$set`` item entities
  (``categories``) -> :class:`ECommerceDataSource`.
* ``ECommAlgorithm.scala`` — MLlib implicit ALS; at serving time it
  excludes items the user has already seen/bought (looked up through
  ``LEventStore`` per query — the low-latency local read path,
  SURVEY.md section 8.3), drops unavailable items (the
  ``constraint_unavailableItems`` ``$set`` entity), applies
  category/whiteList/blackList filters, and falls back to popularity
  ranking for unknown users -> :class:`ECommAlgorithm`.
* Query ``{"user": "u1", "num": 4, "categories"?, "whiteList"?,
  "blackList"?}`` -> ``{"itemScores": [...]}``.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import numpy as np

from predictionio_tpu.controller import (
    DataSource,
    Engine,
    FirstServing,
    IdentityPreparator,
    JaxAlgorithm,
    Params,
    SanityCheck,
    WorkflowContext,
)
from predictionio_tpu.data.aggregator import BiMap
from predictionio_tpu.data.store import LEventStore, PEventStore
from predictionio_tpu.ops.als import ALSConfig, factors_to_host, train_als
from predictionio_tpu.templates.results import ItemScore, PredictedResult

__all__ = [
    "Query",
    "DataSourceParams",
    "TrainingData",
    "ECommerceDataSource",
    "ECommAlgorithmParams",
    "ECommModel",
    "ECommAlgorithm",
    "engine_factory",
]


@dataclasses.dataclass(frozen=True)
class Query:
    user: str = ""
    num: int = 4
    categories: tuple | None = None
    white_list: tuple | None = None
    black_list: tuple | None = None
    json_aliases = {"whiteList": "white_list", "blackList": "black_list"}


@dataclasses.dataclass(frozen=True)
class DataSourceParams(Params):
    app_name: str = ""
    view_event: str = "view"
    buy_event: str = "buy"
    item_entity_type: str = "item"
    json_aliases = {
        "appName": "app_name",
        "viewEvent": "view_event",
        "buyEvent": "buy_event",
    }


@dataclasses.dataclass
class TrainingData(SanityCheck):
    rows: np.ndarray
    cols: np.ndarray
    vals: np.ndarray  # weighted counts (buys weigh more than views)
    user_index: BiMap
    item_index: BiMap
    categories: dict  # item id -> tuple of categories
    popularity: np.ndarray  # [I] view+buy counts

    def sanity_check(self) -> None:
        if self.rows.size == 0:
            raise ValueError("No view/buy events found — check appName")


class ECommerceDataSource(DataSource):
    params_class = DataSourceParams

    def __init__(self, params: DataSourceParams):
        super().__init__(params)

    def _read_categories(self) -> dict[str, tuple]:
        categories: dict[str, tuple] = {}
        for item_id, pm in PEventStore.aggregate_properties(
            app_name=self.params.app_name,
            entity_type=self.params.item_entity_type,
        ).items():
            categories[item_id] = tuple(
                str(c) for c in pm.opt("categories", list, [])
            )
        return categories

    def _read_training_columnar(self, ctx: WorkflowContext) -> TrainingData:
        """Vectorized single-host read: columnar bulk scan + grouped
        weighted sums (a buy is a much stronger signal than a view) —
        no per-event Python at 10^7+ events."""
        from predictionio_tpu.templates.columnar_util import (
            aggregate_pairs,
            densify_pairs,
            event_name_mask,
        )

        p = self.params
        cols_batch = PEventStore.find_columns(
            app_name=p.app_name, event_names=[p.view_event, p.buy_event]
        )
        weights = np.ones(len(cols_batch), np.float32)
        weights[event_name_mask(cols_batch, p.buy_event)] = 5.0
        u_sel, i_sel, vals = aggregate_pairs(cols_batch, weights)
        categories = self._read_categories()
        rows, cols_idx, user_vocab, item_vocab = densify_pairs(
            cols_batch, u_sel, i_sel, extra_items=categories
        )
        item_index = BiMap.string_index(item_vocab)
        popularity = np.zeros(len(item_index), dtype=np.float32)
        np.add.at(popularity, cols_idx, vals)
        return TrainingData(
            rows,
            cols_idx,
            vals,
            BiMap.string_index(user_vocab),
            item_index,
            categories,
            popularity,
        )

    def read_training(self, ctx: WorkflowContext) -> TrainingData:
        p = self.params
        if ctx.num_hosts == 1:
            return self._read_training_columnar(ctx)
        counts: dict[tuple[str, str], float] = {}
        for e in PEventStore.find(
            app_name=p.app_name,
            event_names=[p.view_event, p.buy_event],
            shard_index=ctx.host_index,
            num_shards=ctx.num_hosts,
        ):
            if e.target_entity_id is None:
                continue
            # a buy is a much stronger signal than a view
            weight = 5.0 if e.event == p.buy_event else 1.0
            key = (e.entity_id, e.target_entity_id)
            counts[key] = counts.get(key, 0.0) + weight
        categories = self._read_categories()
        # cross-host coherence (round-1 advisor high finding): merge
        # per-host weighted counts, build identical global BiMaps, and
        # sum popularity across hosts
        import operator

        from predictionio_tpu.parallel.exchange import global_sum_array, global_vocab, merge_keyed

        counts = merge_keyed(counts, combine=operator.add)
        user_index = BiMap.string_index(global_vocab(u for u, _ in counts))
        item_index = BiMap.string_index(
            global_vocab(list(i for _, i in counts) + list(categories))
        )
        n = len(counts)
        rows = np.fromiter((user_index[u] for u, _ in counts), np.int64, n)
        cols = np.fromiter((item_index[i] for _, i in counts), np.int64, n)
        vals = np.fromiter(counts.values(), np.float32, n)
        popularity = np.zeros(len(item_index), dtype=np.float32)
        np.add.at(popularity, cols, vals)
        popularity = global_sum_array(popularity)
        return TrainingData(
            rows, cols, vals, user_index, item_index, categories, popularity
        )


@dataclasses.dataclass(frozen=True)
class ECommAlgorithmParams(Params):
    app_name: str = ""  # for serving-time LEventStore lookups
    rank: int = 10
    num_iterations: int = 20
    lambda_: float = 0.01
    alpha: float = 1.0
    seed: int | None = 3
    #: exclude items of these recent user events at serving time
    unseen_only: bool = True
    seen_events: tuple = ("view", "buy")
    json_aliases = {
        "appName": "app_name",
        "numIterations": "num_iterations",
        "lambda": "lambda_",
        "unseenOnly": "unseen_only",
        "seenEvents": "seen_events",
    }


@dataclasses.dataclass
class ECommModel:
    user_factors: Any
    item_factors: Any
    user_index: BiMap
    item_index: BiMap
    categories: dict
    popularity: Any  # [I]


class ECommAlgorithm(JaxAlgorithm):
    params_class = ECommAlgorithmParams
    query_class = Query

    def __init__(self, params: ECommAlgorithmParams):
        super().__init__(params)

    def train(self, ctx: WorkflowContext, pd: TrainingData) -> ECommModel:
        p = self.params
        factors = train_als(
            pd.rows, pd.cols, pd.vals,
            num_users=len(pd.user_index), num_items=len(pd.item_index),
            config=ALSConfig(
                rank=p.rank, iterations=p.num_iterations, reg=p.lambda_,
                implicit=True, alpha=p.alpha, seed=0 if p.seed is None else p.seed,
            ),
            mesh=ctx.mesh,
            info=ctx.run_info.setdefault("als", {}),
        )
        user, item = factors_to_host(
            ctx.run_info["als"], factors.user, factors.item
        )
        return ECommModel(
            user_factors=user,
            item_factors=item,
            user_index=pd.user_index,
            item_index=pd.item_index,
            categories=pd.categories,
            popularity=pd.popularity,
        )

    # ------------------------------------------------------------- serving
    def _seen_items(self, user: str) -> set:
        """Items of the user's recent view/buy events, via the serving-time
        LEventStore path (parity: ECommAlgorithm's seen-events lookup)."""
        if not self.params.unseen_only or not self.params.app_name:
            return set()
        try:
            events = LEventStore.find_by_entity(
                app_name=self.params.app_name,
                entity_type="user",
                entity_id=user,
                event_names=list(self.params.seen_events),
                limit=None,
            )
        except Exception:
            return set()
        return {e.target_entity_id for e in events if e.target_entity_id}

    def _unavailable_items(self) -> set:
        """Current ``$set`` properties of the ``constraint_unavailableItems``
        entity (parity: the template's availability constraint)."""
        if not self.params.app_name:
            return set()
        try:
            pm = LEventStore.aggregate_properties_of_entity(
                app_name=self.params.app_name,
                entity_type="constraint",
                entity_id="unavailableItems",
            )
        except Exception:
            return set()
        if pm is None:
            return set()
        return set(pm.opt("items", list, []))

    def predict(self, model: ECommModel, query: Query) -> PredictedResult:
        n = model.item_factors.shape[0]
        uidx = model.user_index.get(query.user)
        if uidx is not None:
            scores = model.item_factors @ np.asarray(model.user_factors[uidx])
        else:
            # cold start: popularity ranking (parity: the template's
            # fallback to recent/popular items)
            scores = np.asarray(model.popularity, dtype=np.float64).copy()
        allowed = np.ones(n, dtype=bool)
        for item in self._seen_items(query.user) | self._unavailable_items():
            idx = model.item_index.get(item)
            if idx is not None:
                allowed[idx] = False
        if query.white_list:
            allowed &= np.isin(
                np.arange(n), [model.item_index.get(i, -1) for i in query.white_list]
            )
        if query.black_list:
            for item in query.black_list:
                idx = model.item_index.get(item)
                if idx is not None:
                    allowed[idx] = False
        if query.categories:
            wanted = set(query.categories)
            for idx in np.nonzero(allowed)[0]:
                cats = model.categories.get(model.item_index.inverse(int(idx)), ())
                if not wanted.intersection(cats):
                    allowed[idx] = False
        scores = np.where(allowed, scores, -np.inf)
        k = min(int(query.num), int(allowed.sum()))
        if k <= 0:
            return PredictedResult(())
        from predictionio_tpu.ops.topk import top_k_host

        top, _ = top_k_host(scores, k)  # shared tie rule (ops/topk.py)
        return PredictedResult(
            tuple(
                ItemScore(item=model.item_index.inverse(int(i)), score=float(scores[i]))
                for i in top
                if np.isfinite(scores[i])
            )
        )


def engine_factory() -> Engine:
    return Engine(
        datasource_class=ECommerceDataSource,
        preparator_class=IdentityPreparator,
        algorithms_class_map={"ecomm": ECommAlgorithm},
        serving_class=FirstServing,
    )
