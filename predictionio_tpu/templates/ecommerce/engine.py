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

The rules are one mask however a query is answered: per item its category
codes and whether it is in stock, per query the categories asked for and
the item ids left out (seen, black-listed). What is this engine's own is
where the rules come from (the event store, read at query time), the user
rows and the popularity fallback; the mask, the pin and the filtered top-K
are ``templates/retrieval.py``'s :class:`FilteredItemRetrieval`, which the
similar-product engine takes too.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Sequence

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
from predictionio_tpu.data.aggregator import BiMap, aggregate_properties_single
from predictionio_tpu.data.event import Event
from predictionio_tpu.data.store import LEventStore, PEventStore
from predictionio_tpu.ops.als import ALSConfig, factors_to_host, train_als
from predictionio_tpu.ops.topk import top_k_host
from predictionio_tpu.templates.results import ItemScore, PredictedResult
from predictionio_tpu.templates.retrieval import (
    FilteredItemRetrieval,
    category_arrays,
)
from predictionio_tpu.templates.serving_util import TopkFilter
from predictionio_tpu.utils.spans import count, span

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
    categories: dict  # item id -> tuple of categories, as training read them
    popularity: Any  # [I]
    #: the category rule as arrays (:func:`category_arrays`): per item row
    #: its codes into ``category_index``, ``-1`` = none. ``None`` in a blob
    #: written before they existed: built from ``categories`` on first use
    category_codes: Any = None  # int32 [I, C]
    category_index: BiMap | None = None


class ECommAlgorithm(FilteredItemRetrieval, JaxAlgorithm):
    params_class = ECommAlgorithmParams
    query_class = Query
    ITEM_TABLE = "item_factors"
    #: (the constraint entity's events, the ids they fold to) of this
    #: algorithm's last read that folded: :meth:`_unavailable_items`
    _constraint_fold: tuple[list, frozenset] | None = None

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
        codes, category_index = category_arrays(pd.categories, pd.item_index)
        return ECommModel(
            user_factors=user,
            item_factors=item,
            user_index=pd.user_index,
            item_index=pd.item_index,
            categories=pd.categories,
            popularity=pd.popularity,
            category_codes=codes,
            category_index=category_index,
        )

    # ------------------------------------------------------------- serving
    def _store_rules(self, users: Sequence[str]) -> tuple[dict[str, list], frozenset]:
        """What the event store says at query time, both reads for the
        whole batch: per user the items of their view/buy events as the
        store's columns give them (``LEventStore.targets_by_entities``;
        parity: ECommAlgorithm's seen-events lookup through
        ``LEventStore``), and the current ``items`` of the ``constraint``
        entity ``unavailableItems``, its ``$set`` / ``$unset`` / ``$delete``
        events folded (parity: the template's availability constraint). A
        store error in either means no filter."""
        p = self.params
        if not p.app_name:
            return {}, frozenset()
        constraint = ("constraint", "unavailableItems")
        try:
            seen = LEventStore.targets_by_entities(
                app_name=p.app_name, entity_type="user", entity_ids=users,
                event_names=p.seen_events,
            ) if p.unseen_only else {}
            changes = LEventStore.find_by_entities(
                app_name=p.app_name, entities=[constraint],
                event_names=["$set", "$unset", "$delete"],
            )[constraint]
        except Exception:
            return {}, frozenset()
        return seen, self._unavailable_items(changes)

    def _unavailable_items(self, changes: list[Event]) -> frozenset:
        """The ``items`` the constraint entity's events fold to: the very
        set of the last fold while the events are the last fold's (equal
        events fold the same, and a list of the very objects compares by
        identity: the columnar store hands those out while its tail's
        lines stand), so ``blocked_mask`` knows the set it was last given
        without comparing its ids. Two batches in flight may both fold:
        the holder is one tuple, read once and assigned once."""
        held = self._constraint_fold
        if held is None or held[0] != changes:
            pm = aggregate_properties_single(changes)
            held = (
                changes,
                frozenset(() if pm is None else pm.opt("items", list, [])),
            )
            self._constraint_fold = held
        return held[1]

    def _rules(
        self, model: ECommModel, queries: Sequence[Query], seen: dict,
        unavailable: set,
    ) -> TopkFilter:
        """The rules of ``queries`` as the arrays the top-K takes: a query
        leaves out what its user has seen and its black list; what is out
        of stock is blocked for all."""
        return self.topk_filter(
            model,
            [set(seen.get(q.user, ())).union(q.black_list or ()) for q in queries],
            [q.categories or () for q in queries],
            unavailable,
        )

    def predict(self, model: ECommModel, query: Query) -> PredictedResult:
        """One query on the host: one score row, the rules as one mask."""
        uidx = model.user_index.get(query.user)
        if uidx is not None:
            scores = np.asarray(model.item_factors) @ np.asarray(
                model.user_factors[uidx]
            )
        else:
            # cold start: popularity ranking (parity: the template's
            # fallback to recent/popular items)
            scores = np.asarray(model.popularity, dtype=np.float64).copy()
        filt = self._rules(model, [query], *self._store_rules([query.user]))
        allowed = self.allowed_on_host(model, filt, query.white_list)[0]
        scores = np.where(allowed, scores, -np.inf)
        k = min(int(query.num), int(allowed.sum()))
        if k <= 0:
            return PredictedResult(())
        top, _ = top_k_host(scores, k)  # shared tie rule (ops/topk.py)
        return PredictedResult(
            tuple(
                ItemScore(item=model.item_index.inverse(int(i)), score=float(scores[i]))
                for i in top
                if np.isfinite(scores[i])
            )
        )

    def batch_predict(
        self, model: ECommModel, queries: Sequence[tuple[int, Query]]
    ) -> list[tuple[int, PredictedResult]]:
        """A batch through one filtered top-K: the store read once for the
        batch's users, the rules compiled into arrays, the selection inside
        ``chunked_topk`` (on the device when the model is pinned). A white
        list and an unknown user keep :meth:`predict` (counted as
        ``filter.hostPath``)."""
        n_items = len(model.item_index)
        results: list[tuple[int, PredictedResult]] = []
        valid: list[tuple[int, int, int]] = []  # (slot, user row, k)
        rows: list[Query] = []
        with span("lookup"):
            for slot, q in queries:
                uidx = model.user_index.get(q.user)
                k = min(int(q.num), n_items)
                if k <= 0:
                    results.append((slot, PredictedResult(())))
                elif uidx is None or q.white_list:
                    count("filter.hostPath", 1)
                    results.append((slot, self.predict(model, q)))
                else:
                    valid.append((slot, uidx, k))
                    rows.append(q)
        if not valid:
            return results
        with span("filterLookup"):
            seen, unavailable = self._store_rules(list({q.user for q in rows}))
        with span("filterBuild"):
            filt = self._rules(model, rows, seen, unavailable)
        return results + self.filtered_top_k(model, model.user_factors, valid, filt)


def engine_factory() -> Engine:
    return Engine(
        datasource_class=ECommerceDataSource,
        preparator_class=IdentityPreparator,
        algorithms_class_map={"ecomm": ECommAlgorithm},
        serving_class=FirstServing,
    )
