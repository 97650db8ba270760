"""The two-table retrieval seam (ISSUE 30, ``templates/retrieval.py``).

An engine that only names its two tables gets every serving tier; the
single-query top-K and the staged one agree in every tier of both shipped
engines; the serving state has one name in the tree and never enters a
model blob.
"""

from __future__ import annotations

import dataclasses
import os
import re
from typing import Any

import numpy as np
import pytest

from predictionio_tpu.controller import JaxAlgorithm
from predictionio_tpu.data.aggregator import BiMap
from predictionio_tpu.ops.topk import top_k_host
from predictionio_tpu.serving import AnnConfig
from predictionio_tpu.templates.recommendation.engine import (
    ALSAlgorithm,
    ALSAlgorithmParams,
    ALSModel,
)
from predictionio_tpu.templates.retrieval import (
    WANTED_FLOOR,
    FilteredItemRetrieval,
    FilteredServingState,
    ServingState,
    TwoTableRetrieval,
    serving_state,
)
from predictionio_tpu.templates.twotower.engine import (
    TwoTowerAlgorithm,
    TwoTowerParams,
    TwoTowerServingModel,
)
from predictionio_tpu.utils.serialization import dumps_model, loads_model
from predictionio_tpu.workflow import aot, device_state

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N_USERS, N_ITEMS, RANK = 24, 88, 8

#: a single query scores by a GEMV and a batch by a GEMM: the same float32
#: products summed in another order differ in the last place
SCORE_RTOL = 1e-6


def _tables(seed: int = 0) -> tuple[np.ndarray, np.ndarray]:
    rng = np.random.default_rng(seed)
    return (
        rng.standard_normal((N_USERS, RANK)).astype(np.float32),
        rng.standard_normal((N_ITEMS, RANK)).astype(np.float32),
    )


# ------------------------------------------------ (a) a toy third engine
@dataclasses.dataclass
class ToyModel:
    queries: Any
    docs: Any


class ToyAlgorithm(TwoTableRetrieval, JaxAlgorithm):
    USER_TABLE = "queries"
    ITEM_TABLE = "docs"

    def predict(self, model: ToyModel, query: int) -> list:
        return self.top_k(model, query, 5)


def _toy() -> tuple[ToyAlgorithm, ToyModel]:
    return ToyAlgorithm(), ToyModel(*_tables())


def _host_top_k(u: int, k: int) -> tuple[list, np.ndarray]:
    user, item = _tables()
    ids, scores = top_k_host(item @ user[u], k)
    return [int(i) for i in ids], scores


TIERS = {
    "plain": {},
    "shard": {"shard": True},
    "int8": {"quantize": "int8"},
}


@pytest.mark.parametrize("tier", sorted(TIERS))
def test_toy_engine_pins_through_device_state(tier):
    algo, model = _toy()
    pairs, nbytes = device_state.pin_pairs([(algo, model)], **TIERS[tier])
    (_, model), = pairs
    state = serving_state(model)
    assert state.pinned and nbytes > 0
    assert sum(state.bytes_by_dtype.values()) == nbytes
    assert (state.shards is not None) == (tier == "shard")
    assert (state.quant is not None) == (tier == "int8")
    assert device_state.bytes_by_dtype(pairs) == state.bytes_by_dtype
    assert device_state.shard_count(pairs) == (8 if tier == "shard" else 0)
    for u in (0, 7, N_USERS - 1):
        got = algo.predict(model, u)
        want_ids, want_scores = _host_top_k(u, 5)
        if tier == "int8":  # lossy by design: the ranking may move
            assert len(got) == 5
            continue
        assert [i for i, _ in got] == want_ids
        np.testing.assert_allclose(
            [s for _, s in got], want_scores, rtol=SCORE_RTOL, atol=1e-6
        )
    device_state.release_pairs(pairs)
    state = serving_state(model)
    assert not state.pinned and state.shards is None and state.quant is None
    assert isinstance(model.docs, np.ndarray) and model.docs.shape == (N_ITEMS, RANK)
    assert [i for i, _ in algo.predict(model, 7)] == _host_top_k(7, 5)[0]


def test_toy_engine_host_top_k_is_top_k_host():
    algo, model = _toy()
    model = algo.prepare_model_for_serving(model)
    for u, k in ((0, 1), (3, 16), (N_USERS - 1, N_ITEMS)):
        ids, scores = _host_top_k(u, k)
        assert algo.top_k(model, u, k) == [
            (i, float(s)) for i, s in zip(ids, scores)
        ]


def test_toy_engine_builds_and_releases_ann():
    algo, model = _toy()
    ann = AnnConfig(enabled=True, nlist=4, nprobe=4, kmeans_iters=3)
    pairs, infos = device_state.build_ann_pairs([(algo, model)], ann)
    assert infos[0]["algorithm"] == "ToyAlgorithm" and infos[0]["nlist"] == 4
    assert serving_state(model).ann is not None
    # full probe: the exact ranking
    assert [i for i, _ in algo.predict(model, 3)] == _host_top_k(3, 5)[0]
    device_state.release_pairs(pairs)
    assert serving_state(model).ann is None


def test_toy_engine_exports_and_boots_from_aot(tmp_path):
    algo, model = _toy()
    manifest = aot.export_instance([(algo, model)], "toy-instance", str(tmp_path))
    keys = {e["key"] for e in manifest["entries"]}
    # the k buckets follow the table the engine NAMED (88 docs)
    assert {"predict_scores", "top_k_scores_b16", "top_k_scores_b88"} <= keys
    assert any(k.startswith("top_k_items_batch_c8_b") for k in keys)
    pairs, _ = device_state.pin_pairs(
        [(algo, model)],
        aot=aot.AotConfig(enabled=True, root=str(tmp_path)),
        instance_id="toy-instance",
    )
    stats = device_state.aot_stats(pairs)
    assert stats["tier"] == 1 and stats["loaded"] == len(keys)
    hits = stats["hits"]
    assert [i for i, _ in algo.predict(model, 3)] == _host_top_k(3, 5)[0]
    assert device_state.aot_stats(pairs)["hits"] == hits + 2


# ------------------- (b) top_k is a row of top_k_staged, in every tier
def _als() -> tuple:
    user, item = _tables(1)
    return ALSAlgorithm(ALSAlgorithmParams()), ALSModel(
        user_factors=user, item_factors=item,
        user_index=BiMap.string_index(str(i) for i in range(N_USERS)),
        item_index=BiMap.string_index(str(i) for i in range(N_ITEMS)),
    )


def _twotower() -> tuple:
    user, item = _tables(2)
    return TwoTowerAlgorithm(TwoTowerParams()), TwoTowerServingModel(
        user_vecs=user, item_vecs=item,
        user_index=BiMap.string_index(str(i) for i in range(N_USERS)),
        item_index=BiMap.string_index(str(i) for i in range(N_ITEMS)),
        seen={},
    )


def _in_tier(algo, model, tier: str):
    if tier == "host":
        return algo.prepare_model_for_serving(model)
    if tier == "ann":
        ann = AnnConfig(enabled=True, nlist=4, nprobe=4, kmeans_iters=3)
        return device_state.build_ann_pairs([(algo, model)], ann)[0][0][1]
    kwargs = {"quantize": "int8"} if tier == "int8" else {}
    return device_state.pin_pairs([(algo, model)], **kwargs)[0][0][1]


@pytest.mark.parametrize("tier", ["host", "pinned", "int8", "ann"])
@pytest.mark.parametrize("engine", [_als, _twotower], ids=["als", "twotower"])
def test_top_k_is_a_row_of_top_k_staged(engine, tier):
    algo, model = engine()
    model = _in_tier(algo, model, tier)
    users = [0, 5, 11, N_USERS - 1]
    ks = [1, 7, 16, 30]
    valid = [(slot, u, k) for slot, (u, k) in enumerate(zip(users, ks))]
    staged = {}
    for part, ids, scores in algo.top_k_staged(model, valid):
        for (slot, _, k), i, s in zip(part, ids, scores):
            staged[slot] = (i[:k], s[:k])
    assert sorted(staged) == list(range(len(valid)))
    for slot, u, k in valid:
        single = algo.top_k(model, u, k)
        assert [i for i, _ in single] == staged[slot][0]
        np.testing.assert_allclose(
            [s for _, s in single], staged[slot][1], rtol=SCORE_RTOL, atol=1e-6
        )


# ------------------------------------------ (c) one name, one accessor
def _sources(sub: str):
    for dirpath, _dirs, files in os.walk(os.path.join(REPO, sub)):
        for name in files:
            if name.endswith(".py"):
                path = os.path.join(dirpath, name)
                with open(path, encoding="utf-8") as fh:
                    yield os.path.relpath(path, REPO), fh.read()


def test_serving_state_has_one_name_in_the_tree():
    """Every ``_pio_*`` name under ``predictionio_tpu/`` is
    ``_pio_serving``, and it is spelt only where the accessor lives."""
    spelt = {
        (rel, name)
        for rel, src in _sources("predictionio_tpu")
        for name in re.findall(r"\b_pio_\w+", src)
    }
    assert spelt == {("predictionio_tpu/templates/retrieval.py", "_pio_serving")}


def test_workflow_reads_no_private_name_of_an_engine():
    for rel, src in _sources("predictionio_tpu/workflow"):
        assert 'getattr(model, "_pio_' not in src, rel
        assert "model._pio_" not in src, rel


def _ecommerce() -> tuple:
    from predictionio_tpu.templates.ecommerce.engine import (
        ECommAlgorithm,
        ECommAlgorithmParams,
        ECommModel,
    )

    user, item = _tables(3)
    return ECommAlgorithm(ECommAlgorithmParams()), ECommModel(
        user_factors=user, item_factors=item,
        user_index=BiMap.string_index(str(i) for i in range(N_USERS)),
        item_index=BiMap.string_index(str(i) for i in range(N_ITEMS)),
        categories={}, popularity=np.zeros(N_ITEMS),
    )


def _similarproduct() -> tuple:
    from predictionio_tpu.templates.similarproduct.engine import (
        ALSAlgorithm as SimilarAlgorithm,
        ALSAlgorithmParams as SimilarParams,
        SimilarProductModel,
    )

    _, item = _tables(4)
    return SimilarAlgorithm(SimilarParams()), SimilarProductModel(
        item_factors=item / np.linalg.norm(item, axis=1, keepdims=True),
        item_index=BiMap.string_index(str(i) for i in range(N_ITEMS)),
        categories={},
    )


FILTERING = pytest.mark.parametrize(
    "engine", [_ecommerce, _similarproduct], ids=["ecommerce", "similarproduct"]
)


@FILTERING
def test_served_from_reads_device_arrays_held_by_the_state_alone(engine):
    """The filtering engines pin tiles onto their state and leave the
    model's own tables on the host: ``GET /`` still says ``device``."""
    algo, model = engine()
    assert device_state.serving_device([(algo, model)])["servedFrom"] == "host"
    pairs, nbytes = device_state.pin_pairs([(algo, model)])
    assert nbytes > 0 and isinstance(model.item_factors, np.ndarray)
    assert device_state.serving_device(pairs)["servedFrom"] == "device"
    state = serving_state(model)
    assert type(state) is FilteredServingState and state.pinned
    assert state.bytes_by_dtype == {
        "float32": int(state.item_tiles.nbytes), "int32": int(state.code_tiles.nbytes)}


# ------------------ (c2) one filtered retrieval under both filtering engines
#: what ``FilteredItemRetrieval`` owns: an engine that defined one of these
#: itself would have a filter of its own again
FILTER_SEAM = ("category_codes", "pin_model_for_serving", "blocked_mask",
               "topk_filter", "allowed_on_host", "filtered_top_k")


@FILTERING
def test_a_filtering_engine_reaches_the_filter_through_the_shared_object(engine):
    algo, _ = engine()
    assert isinstance(algo, FilteredItemRetrieval)
    for name in FILTER_SEAM:
        owner = next(c for c in type(algo).__mro__ if name in vars(c))
        assert owner is FilteredItemRetrieval, (name, owner)


@pytest.mark.parametrize("pattern, what", [
    (r"\btile_items\(", "a call of ops.als.tile_items"),
    (r"^WANTED_FLOOR\s*=", "the floor of the filter's category width"),
    (r"\bTopkFilter\(", "a TopkFilter made"),
    (r"\bfilt=filt\b", "a filtered chunked_topk call"),
    (r"^def category_arrays\b", "category_arrays"),
])
def test_the_filter_is_built_in_one_module(pattern, what):
    """No second ``tile_items`` call site, no second ``WANTED_FLOOR``: what
    the two engines share lives once, in ``templates/retrieval.py``."""
    found = {
        rel for rel, src in _sources("predictionio_tpu/templates")
        if re.search(pattern, src, re.MULTILINE)
    }
    assert found == {"predictionio_tpu/templates/retrieval.py"}, what


@FILTERING
def test_both_engines_hand_the_same_rules_to_the_same_arrays(engine):
    """Lists of item ids and category names become the one ``TopkFilter``
    layout whichever engine asks: ids left out padded to the longest list
    (no extent of any program: they reach the device as pairs grouped by
    tile), unknown ids dropped, a name no item carries a code no item
    carries."""
    from predictionio_tpu.ops.topk import NO_ITEM

    algo, model = engine()
    model.categories = {"3": ("a",), "5": ("a", "b")}
    filt = algo.topk_filter(model, [["1", "2", "nobody"], []], [["b", "zzz"], []], {"7"})
    assert filt.excluded.shape == (2, 2) and filt.wanted.shape == (2, WANTED_FLOOR)
    assert filt.excluded.tolist() == [[1, 2], [NO_ITEM, NO_ITEM]]
    assert filt.wanted.tolist() == [[1, 2], [-2, -2]]
    assert np.flatnonzero(filt.blocked).tolist() == [7] and filt.item_tiles is None
    allowed = algo.allowed_on_host(model, filt, white_list=None)
    assert np.flatnonzero(allowed[0]).tolist() == [5]
    assert (~allowed[1]).sum() == 1 and not allowed[1, 7]
    assert np.flatnonzero(algo.allowed_on_host(model, filt, ["5", "7", "9"])[1]).tolist() == [5, 9]


# --------------------------------- (d) the state never enters a blob
def test_a_pinned_models_blob_holds_no_serving_state():
    algo, model = _als()
    pairs, _ = device_state.pin_pairs([(algo, model)])
    (_, model), = pairs
    assert serving_state(model).pinned
    blob = dumps_model(model)
    assert b"ServingState" not in blob and b"retrieval" not in blob
    loaded = loads_model(blob)
    state = serving_state(loaded)
    assert type(state) is ServingState and state == ServingState()
    np.testing.assert_array_equal(
        np.asarray(loaded.item_factors), np.asarray(model.item_factors)
    )
