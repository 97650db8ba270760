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
the item ids left out (seen, black-listed). ``predict`` applies it on the
host to one score row; ``batch_predict`` hands it to
``serving_util.chunked_topk(filt=...)``, which under ``pio deploy
--pin-model`` selects inside one tiled device program
(``ops.als.top_k_items_filtered``) over the item table and the category
codes that :meth:`ECommAlgorithm.pin_model_for_serving` laid out there.
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
from predictionio_tpu.data.store import LEventStore, PEventStore
from predictionio_tpu.ops.als import ALSConfig, factors_to_host, train_als
from predictionio_tpu.ops.topk import NO_ITEM, bucket_width, top_k_host
from predictionio_tpu.templates.results import ItemScore, PredictedResult
from predictionio_tpu.templates.retrieval import ServingState, serving_state
from predictionio_tpu.templates.serving_util import (
    TOPK_CHUNK,
    TopkFilter,
    allowed_items_host,
    chunked_topk,
)
from predictionio_tpu.utils.spans import count, span

__all__ = [
    "Query",
    "DataSourceParams",
    "TrainingData",
    "ECommerceDataSource",
    "ECommAlgorithmParams",
    "ECommModel",
    "category_arrays",
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


@dataclasses.dataclass
class ECommServingState(ServingState):
    """What this engine keeps beside a deployed model."""

    #: pinned: the item factors and the category codes on the device, as
    #: ``ops.als.tile_items`` cut them
    item_tiles: Any = None
    code_tiles: Any = None
    #: ``(unavailable ids, mask)`` of the last :meth:`ECommAlgorithm._blocked`
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


#: floors of the two per-row list widths of a filtered top-K
#: (``ops.topk.bucket_width``). The excluded ids scatter into a mask whose
#: cost hardly moves with their number (measured on a v5e, PERF.md), so one
#: wide bucket holds every history short of a thousand items and a deploy
#: compiles a single width; wanted categories are compared item by item,
#: so their floor is what a category page asks for
EXCLUDED_FLOOR = 1024
WANTED_FLOOR = 2


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
    def _store_rules(self, users: Sequence[str]) -> tuple[dict[str, set], set]:
        """What the event store says at query time, in ONE read where the
        store can (``LEventStore.find_by_entities``): per user the items of
        their view/buy events (parity: ECommAlgorithm's seen-events lookup
        through ``LEventStore``), and the current ``items`` of the
        ``constraint`` entity ``unavailableItems`` (parity: the template's
        availability constraint). A store error means no filter."""
        p = self.params
        if not p.app_name:
            return {}, set()
        constraint = ("constraint", "unavailableItems")
        people = [("user", u) for u in users] if p.unseen_only else []
        try:
            events = LEventStore.find_by_entities(
                app_name=p.app_name,
                entities=[*people, constraint],
                event_names=[*p.seen_events, "$set", "$unset", "$delete"],
            )
        except Exception:
            return {}, set()
        pm = aggregate_properties_single(events.pop(constraint, ()))
        seen = {
            user: {
                e.target_entity_id for e in found
                if e.target_entity_id and e.event in p.seen_events
            }
            for (_, user), found in events.items()
        }
        return seen, set() if pm is None else set(pm.opt("items", list, []))

    @staticmethod
    def _categories(model: ECommModel) -> tuple[np.ndarray, BiMap]:
        codes = getattr(model, "category_codes", None)
        if codes is None:
            # two batches may fill this at once (the batcher has two in
            # flight): the index first, so whoever sees the codes sees both
            codes, model.category_index = category_arrays(
                model.categories, model.item_index
            )
            model.category_codes = codes
        return codes, model.category_index

    @staticmethod
    def _blocked(model: ECommModel, unavailable: set):
        """The out-of-stock mask over the item rows — the host's
        ``bool[items]`` or, pinned, the device's ``bool[tiles, width]`` with
        the padding past the catalog blocked too — made when the constraint
        changes, not per batch. Two batches in flight may hold different
        reads of the constraint: each is served the mask of its own (the
        cache is one tuple, read once and assigned once)."""
        state = serving_state(model, ECommServingState)
        cached = state.blocked
        if cached is not None and cached[0] == unavailable:
            return cached[1]
        n = len(model.item_index)
        tiles = state.item_tiles
        mask = np.zeros(n if tiles is None else tiles.shape[0] * tiles.shape[2], bool)
        mask[n:] = True
        rows = [model.item_index.get(i) for i in unavailable]
        mask[[r for r in rows if r is not None]] = True
        if tiles is not None:
            import jax

            mask = jax.device_put(mask.reshape(tiles.shape[0], tiles.shape[2]))
        state.blocked = (set(unavailable), mask)
        return mask

    def _rules(
        self, model: ECommModel, queries: Sequence[Query], seen: dict,
        unavailable: set,
    ) -> TopkFilter:
        """The rules of ``queries`` as the arrays the top-K takes."""
        codes, category_index = self._categories(model)
        item_row = model.item_index.get
        left_out = []
        for q in queries:
            ids = set(seen.get(q.user, ())).union(q.black_list or ())
            left_out.append([r for r in map(item_row, ids) if r is not None])
        excluded = np.full(
            (len(queries), bucket_width(max(map(len, left_out)), EXCLUDED_FLOOR)),
            NO_ITEM, np.int32,
        )
        for row, rows in zip(excluded, left_out):
            row[: len(rows)] = rows
        asked = [q.categories or () for q in queries]
        wanted = np.full(
            (len(queries), bucket_width(max(map(len, asked)), WANTED_FLOOR)),
            -2, np.int32,
        )
        for row, names in zip(wanted, asked):
            # a name no item carries is a code no item carries
            row[: len(names)] = [
                category_index.get(str(c), len(category_index)) for c in names
            ]
        count("filter.excludedIds",
              sum(map(len, left_out)) + len(unavailable) * len(queries))
        count("filter.categoryRows", sum(1 for names in asked if names))
        state = serving_state(model, ECommServingState)
        return TopkFilter(
            codes=codes if state.item_tiles is None else state.code_tiles,
            blocked=self._blocked(model, unavailable),
            wanted=wanted, excluded=excluded, item_tiles=state.item_tiles,
        )

    def predict(self, model: ECommModel, query: Query) -> PredictedResult:
        """One query on the host: one score row, the rules as one mask."""
        n = len(model.item_index)
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
        codes, _ = self._categories(model)
        allowed = allowed_items_host(
            codes, np.asarray(filt.blocked).reshape(-1)[:n], filt.wanted,
            filt.excluded,
        )[0]
        if query.white_list:
            allowed &= np.isin(
                np.arange(n), [model.item_index.get(i, -1) for i in query.white_list]
            )
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

    #: the most queries one dispatch scores (serving_util.TOPK_CHUNK; the
    #: filtered branch lowers it to what its score tile allows)
    BATCH_PREDICT_CHUNK = TOPK_CHUNK

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
        inverse = model.item_index.inverse
        for part, idx_l, score_l in chunked_topk(
            model.user_factors, model.item_factors, valid,
            chunk=self.BATCH_PREDICT_CHUNK, filt=filt,
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

    def pin_model_for_serving(self, model: ECommModel) -> tuple[ECommModel, int]:
        """``--pin-model`` (workflow/device_state.py): the item table and the
        category codes go to the device once per model generation, cut into
        the tiles ``ops.als.top_k_items_filtered`` scans, and
        ``batch_predict`` selects there. The user table stays on the host:
        a batch's rows ride with its rules (``serving_util._filtered_topk``
        says why). Returns the model and the device bytes it holds."""
        from predictionio_tpu.ops.als import tile_items

        codes, _ = self._categories(model)
        state = serving_state(model, ECommServingState)
        state.blocked = None
        state.item_tiles = tile_items(
            np.asarray(model.item_factors, np.float32), 0.0
        )
        state.code_tiles = tile_items(codes, -1)
        state.pinned = True
        state.bytes_by_dtype = {
            "float32": int(state.item_tiles.nbytes),
            "int32": int(state.code_tiles.nbytes),
        }
        return model, sum(state.bytes_by_dtype.values())


def engine_factory() -> Engine:
    return Engine(
        datasource_class=ECommerceDataSource,
        preparator_class=IdentityPreparator,
        algorithms_class_map={"ecomm": ECommAlgorithm},
        serving_class=FirstServing,
    )
