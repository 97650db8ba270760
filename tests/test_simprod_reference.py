"""The similar-product engine against the benchmark's plain reference
(``benchmark/references/simprod.py``: numpy float64, nothing of ``ops/`` or
``templates/``) on seeded tables: the host ``predict``, ``batch_predict``
unpinned (host) and pinned (the tiled device program), and the
comparison's own teeth."""

import dataclasses
import os
import pickle
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.references import simprod as reference  # noqa: E402
from predictionio_tpu.data.aggregator import BiMap  # noqa: E402
from predictionio_tpu.ops import als  # noqa: E402
from predictionio_tpu.templates.retrieval import (  # noqa: E402
    category_arrays,
    serving_state,
)
from predictionio_tpu.templates.similarproduct.engine import (  # noqa: E402
    ALSAlgorithm,
    ALSAlgorithmParams,
    Query,
    SimilarProductModel,
)
from predictionio_tpu.utils import spans  # noqa: E402
from predictionio_tpu.utils.serialization import dumps_model, loads_model  # noqa: E402
from predictionio_tpu.workflow import device_state  # noqa: E402

N_ITEMS, RANK, NUM = 2000, 8, 10
NAMES = ["books", "garden", "music", "tools", "toys"]
LIMITS = {"serve_tol_rel": 5e-5, "serve_tol_abs": 1e-6, "serve_rms_rel_err": 2e-7}
#: items 7 and 8 hold the same row: a tie in every answer that holds both
TWIN, TWIN_OF = 8, 7


@pytest.fixture()
def shop(monkeypatch):
    """Seeded unit rows as a model with one category an item, and queries
    (single- and multi-item, categories, black lists, an unknown item, a
    ``num`` above the allowed count, a tie) as engine and reference take
    them."""
    monkeypatch.setattr(als, "FILTER_TILE", 768)  # three tiles, the last ragged
    rng = np.random.default_rng(17)
    item = rng.standard_normal((N_ITEMS, RANK)).astype(np.float32)
    item[TWIN] = item[TWIN_OF]
    item /= np.linalg.norm(item, axis=1, keepdims=True)
    code_of = rng.choice(len(NAMES), N_ITEMS, p=[0.4, 0.3, 0.2, 0.09, 0.01])
    code_of[TWIN] = code_of[TWIN_OF]
    cats = {str(i): (NAMES[c],) for i, c in enumerate(code_of)}
    item_index = BiMap({str(i): i for i in range(N_ITEMS)})
    codes, category_index = category_arrays(cats, item_index)
    model = SimilarProductModel(
        item_factors=item, item_index=item_index, categories=cats,
        category_codes=codes, category_index=category_index)
    queries, rules = [], []

    def add(items, wanted=(), black=(), num=NUM, unknown=()):
        queries.append(Query(
            items=tuple(str(i) for i in items) + tuple(unknown), num=num,
            categories=tuple(wanted) or None,
            black_list=tuple(str(int(i)) for i in black) or None))
        rules.append({"items": np.asarray(items, np.int64),
                      "black": np.asarray(black, np.int64),
                      "wanted": np.asarray([category_index[c] for c in wanted], np.int64)})

    for n in range(36):
        first = int(rng.integers(0, N_ITEMS))
        same = np.flatnonzero(code_of == code_of[first])
        more = rng.choice(same[same != first], int(rng.integers(1, 8)) if n % 3 == 0 else 0,
                          replace=False)
        own = NAMES[code_of[first]]
        wanted = [(), (own,), (own, NAMES[(code_of[first] + 1) % len(NAMES)])][n % 3]
        black = rng.choice(N_ITEMS, int(rng.integers(1, 51)) if n % 5 == 0 else 0,
                           replace=False)
        add([first, *more.tolist()], wanted, black)
    # an unknown item beside a known one is dropped
    add([11], unknown=("no-such-item",))
    # num above what the rules allow: the smallest category, most of it black-listed
    small = np.flatnonzero(code_of == NAMES.index("toys"))
    add([int(small[0])], ("toys",), small[4:])
    # the twin rows tie in the answer of an item near them
    near = int(np.argsort(-(item @ item[TWIN_OF]))[2])
    add([near])
    algo = ALSAlgorithm(ALSAlgorithmParams(rank=RANK))
    return algo, model, queries, rules, item, codes, len(small)


def _answers(model, results):
    return [([model.item_index[s.item] for s in r.item_scores],
             [s.score for s in r.item_scores]) for r in results]


def _compare(shop, answers, say=lambda *_: None, limits=LIMITS):
    _, _, _, rules, item, codes, _ = shop
    return reference.compare_serve(say, limits, "p3", NUM, item, codes, rules, answers)


def _served(algo, model, queries, how):
    if how == "predict":
        return [algo.predict(model, q) for q in queries]
    if how == "batch_pinned":
        model, nbytes = algo.pin_model_for_serving(model)
        assert serving_state(model).item_tiles.shape == (3, RANK, 768) and nbytes > 0
    got = dict(algo.batch_predict(model, list(enumerate(queries))))
    return [got[i] for i in range(len(queries))]


@pytest.mark.parametrize("how", ["predict", "batch_unpinned", "batch_pinned"])
def test_engine_agrees_with_the_plain_reference(shop, how):
    algo, model, queries, rules, *_, n_small = shop
    results = _served(algo, model, queries, how)
    lines = []
    assert _compare(shop, _answers(model, results), lines.append), "\n".join(lines)
    assert any("fails as it must" in ln for ln in lines)  # the control, in the run
    short = results[-2].item_scores  # the small category: one query item, 4 left
    assert len(short) == min(NUM, 3) and n_small >= 5
    twins = [model.item_index[s.item] for s in results[-1].item_scores]
    assert twins.index(TWIN_OF) + 1 == twins.index(TWIN)  # the tie: ascending id


def test_predict_and_both_batch_paths_give_the_same_answers(shop):
    algo, model, queries, *_ = shop
    single = _served(algo, model, queries, "predict")
    host = _served(algo, model, queries, "batch_unpinned")
    device = _served(algo, model, queries, "batch_pinned")
    for want, on_host, on_device in zip(single, host, device):
        for got in (on_host, on_device):
            assert [s.item for s in got.item_scores] == [s.item for s in want.item_scores]
            np.testing.assert_allclose([s.score for s in got.item_scores],
                                       [s.score for s in want.item_scores],
                                       rtol=2e-6, atol=1e-7)


def test_a_query_item_and_a_black_listed_item_are_never_served(shop):
    algo, model, queries, rules, *_ = shop
    model, _ = algo.pin_model_for_serving(model)
    for q, r, res in zip(queries, rules, _served(algo, model, queries, "batch")):
        served = {model.item_index[s.item] for s in res.item_scores}
        assert not served & set(r["items"].tolist()) and not served & set(r["black"].tolist())


def test_white_list_keeps_the_host_path_and_the_counters_count(shop):
    algo, model, queries, *_ = shop
    model, _ = algo.pin_model_for_serving(model)
    assert device_state.serving_device([(algo, model)])["servedFrom"] == "device"
    collector = spans.Collector()
    previous = spans.bind(collector)
    try:
        got = dict(algo.batch_predict(model, [
            (0, Query(items=("3",), num=3, white_list=("5", "6", "3", "9"))),
            (1, Query(items=("nobody",), num=3)),
            (2, Query(items=("4", "5"), num=0)),
            (3, queries[1]), (4, queries[-2]), (5, queries[-3])]))
    finally:
        spans.bind(previous)
    counts = collector.take_counts()
    assert counts["filter.hostPath"] == 1 and counts["filter.shortAnswers"] == 1
    assert counts["filter.categoryRows"] == 2
    # what the rows left out: their own items and black lists (the three
    # device rows', and the white-listed query's one item on the host)
    assert counts["filter.excludedIds"] == 1 + sum(
        len(set(q.items) | set(q.black_list or ())) - ("no-such-item" in q.items)
        for q in (queries[1], queries[-2], queries[-3]))
    # what the device dispatch was handed: the three rows' own known ids as
    # (row, id) pairs, at the floor of its program's pair bucket
    assert counts["filter.excludedPairs"] == counts["filter.excludedIds"] - 1
    assert counts["filter.pairBucket.128"] == 1
    n_items = 1 + 1 + 2 + sum(len(q.items) for q in (queries[1], queries[-2], queries[-3]))
    assert counts["similar.queryItems"] == n_items and counts["similar.unknownItems"] == 2
    assert {s.item for s in got[0].item_scores} == {"5", "6", "9"}
    assert got[1].item_scores == () and got[2].item_scores == ()
    assert len(got[3].item_scores) == NUM
    assert {"lookup", "queryVectors", "filterBuild", "dispatch", "deviceWait",
            "format"} <= {r.name for r in collector.take()}


def test_the_comparison_has_teeth(shop):
    """A query item served, a black-listed item served, a short answer, a
    nudged score, and a limit so wide that three bf16 passes meet it: each
    makes the comparison fail."""
    algo, model, queries, rules, *_ = shop
    sound = _answers(model, [algo.predict(model, q) for q in queries])
    assert _compare(shop, sound)

    def with_first(n, i):
        broken = list(sound)
        broken[n] = ([i] + sound[n][0][1:], sound[n][1])
        return broken

    assert not _compare(shop, with_first(0, int(rules[0]["items"][-1])))
    n_black = next(n for n, r in enumerate(rules) if r["black"].size)
    assert not _compare(shop, with_first(n_black, int(rules[n_black]["black"][0])))
    short = list(sound)
    short[5] = (sound[5][0][:-1], sound[5][1][:-1])
    assert not _compare(shop, short)
    nudged = [(ids, [s * (1 + 1e-3) for s in sc]) for ids, sc in sound]
    assert not _compare(shop, nudged)
    lines = []
    assert not _compare(shop, sound, lines.append, {**LIMITS, "serve_rms_rel_err": 1e-5})
    assert any("PASSED: the comparison has no teeth" in ln for ln in lines)


class _OldModel:
    """``SimilarProductModel`` as a blob written before the category arrays
    holds it: no such attributes in its state."""

    def __reduce__(self):
        return (_old_model, (self.item_factors, self.item_index, self.categories))


def _old_model(item_factors, item_index, categories):
    model = SimilarProductModel.__new__(SimilarProductModel)
    model.__dict__.update(item_factors=item_factors, item_index=item_index,
                          categories=categories)
    return model


def test_a_blob_pickled_without_category_codes_loads_and_serves(shop):
    algo, model, queries, *_ = shop
    want = _served(algo, model, queries, "predict")
    old = _OldModel()
    old.item_factors, old.item_index, old.categories = (
        model.item_factors, model.item_index, model.categories)
    loaded = pickle.loads(pickle.dumps(old))
    assert type(loaded) is SimilarProductModel and "category_codes" not in vars(loaded)
    assert _served(algo, loaded, queries, "batch_unpinned") == _served(
        algo, model, queries, "batch_unpinned")
    assert loaded.category_codes is not None and len(loaded.category_index) == len(NAMES)
    assert _served(algo, loaded, queries, "predict") == want
    # and a blob of today's model carries the arrays
    again = loads_model(dumps_model(model))
    np.testing.assert_array_equal(again.category_codes, model.category_codes)


def test_the_batchs_query_vectors_are_each_querys_own(shop):
    """One gather for the batch gives what a query alone gives: the unit
    sum of its items' rows in float32; rows that cancel are no direction."""
    algo, model, *_ = shop
    table = model.item_factors.copy()
    table[21] = -table[20]
    model = dataclasses.replace(model, item_factors=table)
    rows = [[5], [20, 21], [7, 8, 9, 1999], [20, 21, 3]]
    vectors, ok = algo._query_vectors(model, rows)
    assert vectors.dtype == np.float32 and ok.tolist() == [True, False, True, True]
    assert not vectors[1].any()
    for got, idxs in zip(vectors[ok], [rows[0], rows[2], rows[3]]):
        want = table[idxs].astype(np.float64).sum(axis=0)
        np.testing.assert_allclose(got, want / np.linalg.norm(want), rtol=0, atol=2e-7)
    alone, fine = algo._query_vectors(model, [rows[2]])
    assert fine.tolist() == [True] and np.array_equal(alone[0], vectors[2])
    # a batch holding a query whose items cancel answers it empty, the others as alone
    queries = [Query(items=tuple(str(i) for i in r), num=NUM) for r in rows]
    batch = _served(algo, model, queries, "batch_unpinned")
    assert batch[1].item_scores == ()
    for got, want in zip(batch, [algo.predict(model, q) for q in queries]):
        assert [s.item for s in got.item_scores] == [s.item for s in want.item_scores]
        np.testing.assert_allclose([s.score for s in got.item_scores],
                                   [s.score for s in want.item_scores],
                                   rtol=2e-6, atol=1e-7)
