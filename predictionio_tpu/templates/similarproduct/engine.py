"""Similar-Product engine: view events -> implicit ALS -> similar items.

Parity map (reference scala-parallel-similarproduct template):

* ``DataSource.scala`` — ``view`` events (user->item) + ``$set`` item
  entities carrying ``categories`` -> :class:`SimilarProductDataSource`.
* ``ALSAlgorithm.scala`` — MLlib implicit ``ALS.trainImplicit``; similar
  items ranked by cosine similarity against the *sum of the query items'
  factor vectors*, excluding the query items, with ``categories`` /
  ``whiteList`` / ``blackList`` filters -> :class:`ALSAlgorithm` over
  :func:`predictionio_tpu.ops.als.train_als`.
* Query ``{"items": ["i1"], "num": 4, "categories"?: [...],
  "whiteList"?: [...], "blackList"?: [...]}`` -> ``{"itemScores": [...]}``.

The score of item ``i`` is ``v_i . u``, ``u`` the normalised sum of the
query items' unit rows: the cosine against that sum (upstream sums the
cosines item by item, which is this score times ``|sum|``: the same order).
An item is allowed when it is not a query item, not on the ``blackList``,
and carries one of ``categories`` when the query names any. ``predict``
applies that on the host to one score row; ``batch_predict`` makes the
batch's query vectors on the host and hands them, with the rules, to
``templates/retrieval.py``'s :class:`FilteredItemRetrieval` (the object
the e-commerce engine takes), which under ``pio deploy --pin-model``
selects on the device. A ``whiteList`` keeps the host ``predict``.
"""

from __future__ import annotations

import dataclasses
import itertools
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
from predictionio_tpu.data.aggregator import BiMap
from predictionio_tpu.data.store import PEventStore
from predictionio_tpu.ops.als import ALSConfig, factors_to_host, train_als
from predictionio_tpu.templates.results import ItemScore, PredictedResult
from predictionio_tpu.templates.retrieval import (
    FilteredItemRetrieval,
    ItemTableAnn,
    category_arrays,
    serving_state,
)
from predictionio_tpu.utils.spans import count, span

__all__ = [
    "Query",
    "DataSourceParams",
    "TrainingData",
    "SimilarProductDataSource",
    "ALSAlgorithmParams",
    "ALSAlgorithm",
    "engine_factory",
]


@dataclasses.dataclass(frozen=True)
class Query:
    items: tuple = ()
    num: int = 4
    categories: tuple | None = None
    white_list: tuple | None = None
    black_list: tuple | None = None
    json_aliases = {"whiteList": "white_list", "blackList": "black_list"}


@dataclasses.dataclass(frozen=True)
class DataSourceParams(Params):
    app_name: str = ""
    view_event: str = "view"
    item_entity_type: str = "item"
    json_aliases = {"appName": "app_name", "viewEvent": "view_event"}


@dataclasses.dataclass
class TrainingData(SanityCheck):
    rows: np.ndarray  # user idx
    cols: np.ndarray  # item idx
    vals: np.ndarray  # view counts
    user_index: BiMap
    item_index: BiMap
    categories: dict  # item id -> tuple of category strings

    def sanity_check(self) -> None:
        if self.rows.size == 0:
            raise ValueError("No view events found — check appName/viewEvent")


class SimilarProductDataSource(DataSource):
    params_class = DataSourceParams

    def __init__(self, params: DataSourceParams):
        super().__init__(params)

    def _read_categories(self) -> dict[str, tuple]:
        """$set-only items included so catalog filters work for unviewed
        items."""
        categories: dict[str, tuple] = {}
        item_props = PEventStore.aggregate_properties(
            app_name=self.params.app_name,
            entity_type=self.params.item_entity_type,
        )
        for item_id, pm in item_props.items():
            cats = pm.opt("categories", list, [])
            categories[item_id] = tuple(str(c) for c in cats)
        return categories

    def _read_training_columnar(self, ctx: WorkflowContext) -> TrainingData:
        """Vectorized single-host read: columnar bulk scan + numpy
        per-pair view counting — the same no-per-event-Python path the
        recommendation template takes (VERDICT r3 next-round #1), with
        sum aggregation instead of latest-wins."""
        from predictionio_tpu.templates.columnar_util import (
            aggregate_pairs,
            densify_pairs,
        )

        p = self.params
        cols = PEventStore.find_columns(
            app_name=p.app_name, event_names=[p.view_event]
        )
        u_sel, i_sel, counts = aggregate_pairs(cols)
        # user vocab: viewed users only; item vocab: viewed + $set-only
        categories = self._read_categories()
        rows, cols_idx, user_vocab, item_vocab = densify_pairs(
            cols, u_sel, i_sel, extra_items=categories
        )
        return TrainingData(
            rows=rows,
            cols=cols_idx,
            vals=counts,
            user_index=BiMap.string_index(user_vocab),
            item_index=BiMap.string_index(item_vocab),
            categories=categories,
        )

    def read_training(self, ctx: WorkflowContext) -> TrainingData:
        p = self.params
        if ctx.num_hosts == 1:
            return self._read_training_columnar(ctx)
        counts: dict[tuple[str, str], float] = {}
        for e in PEventStore.find(
            app_name=p.app_name,
            event_names=[p.view_event],
            shard_index=ctx.host_index,
            num_shards=ctx.num_hosts,
        ):
            if e.target_entity_id is None:
                continue
            key = (e.entity_id, e.target_entity_id)
            counts[key] = counts.get(key, 0.0) + 1.0
        categories = self._read_categories()
        # cross-host coherence (round-1 advisor high finding): merge
        # per-host view counts by user, then build IDENTICAL global
        # BiMaps on every host from sorted vocabularies
        import operator

        from predictionio_tpu.parallel.exchange import global_vocab, merge_keyed

        counts = merge_keyed(counts, combine=operator.add)
        user_index = BiMap.string_index(global_vocab(u for u, _ in counts))
        item_index = BiMap.string_index(
            global_vocab(list(i for _, i in counts) + list(categories))
        )
        n = len(counts)
        rows = np.fromiter((user_index[u] for u, _ in counts), np.int64, n)
        cols = np.fromiter((item_index[i] for _, i in counts), np.int64, n)
        vals = np.fromiter(counts.values(), np.float32, n)
        return TrainingData(rows, cols, vals, user_index, item_index, categories)


@dataclasses.dataclass(frozen=True)
class ALSAlgorithmParams(Params):
    rank: int = 10
    num_iterations: int = 20
    lambda_: float = 0.01
    alpha: float = 1.0
    seed: int | None = 3
    json_aliases = {"numIterations": "num_iterations", "lambda": "lambda_"}


@dataclasses.dataclass
class SimilarProductModel:
    item_factors: Any  # [I, K], L2-normalized rows for cosine scoring
    item_index: BiMap
    categories: dict  # item id -> tuple of categories, as training read them
    #: the category rule as arrays (``retrieval.category_arrays``): per item
    #: row its codes into ``category_index``, ``-1`` = none. ``None`` in a
    #: blob written before they existed: built from ``categories`` on first
    #: use
    category_codes: Any = None  # int32 [I, C]
    category_index: BiMap | None = None


class ALSAlgorithm(FilteredItemRetrieval, ItemTableAnn, JaxAlgorithm):
    params_class = ALSAlgorithmParams
    query_class = Query
    #: ``--ann`` clusters the L2-normalized item factors: cosine scoring is
    #: the inner product on unit rows, so the clustered layout is exactly
    #: the metric the queries use
    ITEM_TABLE = "item_factors"

    def __init__(self, params: ALSAlgorithmParams):
        super().__init__(params)

    def train(self, ctx: WorkflowContext, pd: TrainingData) -> SimilarProductModel:
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
        (item,) = factors_to_host(ctx.run_info["als"], factors.item)
        norms = np.linalg.norm(item, axis=1, keepdims=True)
        norms[norms == 0] = 1.0
        codes, category_index = category_arrays(pd.categories, pd.item_index)
        return SimilarProductModel(
            item_factors=item / norms,
            item_index=pd.item_index,
            categories=pd.categories,
            category_codes=codes,
            category_index=category_index,
        )

    @staticmethod
    def _query_vectors(
        model: SimilarProductModel, rows: Sequence[list]
    ) -> tuple[np.ndarray, np.ndarray]:
        """``u`` of each query, its items at ``rows[q]`` (never empty):
        their stored unit rows summed and normalised, in float32, and
        whether the sum is a direction at all (``False`` where the rows
        cancel: that query's vector is zero). One gather and one pass for
        the batch: a dozen numpy calls, not four a query, on the thread
        that feeds the device."""
        sizes = np.fromiter(map(len, rows), np.int64, len(rows))
        gathered = np.asarray(model.item_factors)[
            np.fromiter(itertools.chain.from_iterable(rows), np.int64, int(sizes.sum()))
        ]
        sums = np.add.reduceat(gathered, np.cumsum(sizes) - sizes, axis=0)
        norms = np.sqrt(np.einsum("ij,ij->i", sums, sums))
        ok = norms > 0
        return sums / np.where(ok, norms, 1)[:, None], ok

    def _rules(self, model: SimilarProductModel, queries: Sequence[Query]):
        """The rules of ``queries`` as the arrays the top-K takes: a query
        leaves out its own items and its black list."""
        return self.topk_filter(
            model,
            [set(q.items).union(q.black_list or ()) for q in queries],
            [q.categories or () for q in queries],
        )

    def predict(self, model: SimilarProductModel, query: Query) -> PredictedResult:
        """One query on the host: one score row, the rules as one mask."""
        idxs = [model.item_index.get(i) for i in query.items]
        idxs = [i for i in idxs if i is not None]
        if not idxs:
            return PredictedResult(())
        units, ok = self._query_vectors(model, [idxs])
        if not ok[0]:
            return PredictedResult(())
        unit = units[0]
        ann = serving_state(model).ann
        if ann is not None and not query.white_list and not query.categories:
            # ANN path. Exclusions (query items + blacklist) are applied
            # by OVER-FETCHING num + |excluded| candidates before the
            # final merge: a post-hoc filter on an exact-num fetch
            # returns fewer than num items whenever the excluded items
            # are popular (high-scoring) — the latent hole approximate
            # retrieval amplifies. whiteList/categories queries fall
            # back to the exact masked path: a whitelisted item may live
            # in a cluster the probe never visits, so ANN cannot honor
            # those filters (docs/serving.md).
            from predictionio_tpu.ops import ivf

            num = int(query.num)
            if num <= 0:  # exact-path parity: k = min(num, ...) <= 0
                return PredictedResult(())
            exclude = set(idxs)
            for item in query.black_list or ():
                bidx = model.item_index.get(item)
                if bidx is not None:
                    exclude.add(bidx)
            ids, scores = ivf.query_topk(ann, unit, num + len(exclude))
            return PredictedResult(
                tuple(
                    ItemScore(item=model.item_index.inverse(int(i)), score=float(s))
                    for i, s in zip(ids, scores)
                    if i not in exclude
                )[:num]
            )
        scores = np.asarray(model.item_factors) @ unit  # cosine vs all items
        allowed = self.allowed_on_host(
            model, self._rules(model, [query]), query.white_list
        )[0]
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

    def batch_predict(
        self, model: SimilarProductModel, queries: Sequence[tuple[int, Query]]
    ) -> list[tuple[int, PredictedResult]]:
        """A batch through one filtered top-K: the batch's query vectors
        from the model's own table (a host gather of the query items'
        rows), the rules compiled into arrays, the selection inside
        ``chunked_topk`` (on the device when the model is pinned). A white
        list keeps :meth:`predict` (counted as ``filter.hostPath``), as
        does every query of an ``--ann`` deploy (its index is
        :meth:`predict`'s branch)."""
        if serving_state(model).ann is not None:
            return [(slot, self.predict(model, q)) for slot, q in queries]
        n_items = len(model.item_index)
        results: list[tuple[int, PredictedResult]] = []
        known: list[tuple[int, Query, list, int]] = []  # (slot, query, its items' rows, k)
        n_query_items = n_unknown = 0
        with span("lookup"):
            for slot, q in queries:
                idxs = [model.item_index.get(i) for i in q.items]
                n_query_items += len(idxs)
                n_unknown += idxs.count(None)
                idxs = [i for i in idxs if i is not None]
                k = min(int(q.num), n_items)
                if k <= 0 or not idxs:
                    results.append((slot, PredictedResult(())))
                elif q.white_list:
                    count("filter.hostPath", 1)
                    results.append((slot, self.predict(model, q)))
                else:
                    known.append((slot, q, idxs, k))
        count("similar.queryItems", n_query_items)
        count("similar.unknownItems", n_unknown)
        valid: list[tuple[int, int, int]] = []  # (slot, row of vectors, k)
        if not known:
            return results
        with span("queryVectors"):
            vectors, ok = self._query_vectors(model, [idxs for _, _, idxs, _ in known])
            vectors = vectors[ok].astype(np.float32, copy=False)
            kept: list[Query] = []
            for (slot, q, _, k), fine in zip(known, ok.tolist()):
                if not fine:
                    results.append((slot, PredictedResult(())))
                    continue
                valid.append((slot, len(valid), k))
                kept.append(q)
        if not valid:
            return results
        with span("filterBuild"):
            filt = self._rules(model, kept)
        return results + self.filtered_top_k(model, vectors, valid, filt)


def engine_factory() -> Engine:
    return Engine(
        datasource_class=SimilarProductDataSource,
        preparator_class=IdentityPreparator,
        algorithms_class_map={"als": ALSAlgorithm},
        serving_class=FirstServing,
    )
