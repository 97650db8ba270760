"""The e-commerce engine against the benchmark's plain reference
(``benchmark/references/ecom.py``: numpy float64, nothing of ``ops/``) on
seeded tables, with a store that holds the histories and the constraint:
``predict``, ``batch_predict`` unpinned (host) and pinned (the tiled device
program), and the comparison's own teeth."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.references import ecom as reference  # noqa: E402
from predictionio_tpu.data.aggregator import BiMap  # noqa: E402
from predictionio_tpu.data.event import DataMap, Event  # noqa: E402
from predictionio_tpu.data.storage.base import App  # noqa: E402
from predictionio_tpu.ops import als  # noqa: E402
from predictionio_tpu.templates.ecommerce.engine import (  # noqa: E402
    ECommAlgorithm,
    ECommAlgorithmParams,
    ECommModel,
    Query,
)
from predictionio_tpu.templates.retrieval import (  # noqa: E402
    FilteredServingState,
    category_arrays,
    serving_state,
)
from predictionio_tpu.utils import spans  # noqa: E402

APP, N_ITEMS, N_USERS, RANK, NUM = "shop", 700, 40, 8, 10
LIMITS = {"serve_tol_rel": 5e-5, "serve_tol_abs": 1e-6, "serve_rms_rel_err": 2e-7}


@pytest.fixture()
def shop(memory_storage_env, monkeypatch):
    return _shop_in(memory_storage_env, monkeypatch)


def _shop_in(storage, monkeypatch):
    """Seeded tables as a model, histories and the constraint in the store,
    and the same rules as the reference takes them."""
    monkeypatch.setattr(als, "FILTER_TILE", 256)  # three tiles, the last ragged
    rng = np.random.default_rng(11)
    user = (rng.standard_normal((N_USERS, RANK)) / np.sqrt(RANK)).astype(np.float32)
    item = (rng.standard_normal((N_ITEMS, RANK)) / np.sqrt(RANK)).astype(np.float32)
    names = [f"c{j}" for j in range(40)]
    cats = {str(i): tuple(rng.choice(names, int(rng.integers(0, 3)), replace=False))
            for i in range(N_ITEMS)}
    item_index = BiMap({str(i): i for i in range(N_ITEMS)})
    codes, category_index = category_arrays(cats, item_index)
    model = ECommModel(
        user_factors=user, item_factors=item,
        user_index=BiMap({str(u): u for u in range(N_USERS)}), item_index=item_index,
        categories=cats, popularity=np.zeros(N_ITEMS, np.float32),
        category_codes=codes, category_index=category_index)
    app_id = storage.get_meta_data_apps().insert(App(id=0, name=APP))
    le = storage.get_l_events()
    le.init(app_id)
    seen = {u: rng.choice(N_ITEMS, int(rng.integers(1, 30)), replace=False)
            for u in range(N_USERS)}
    for u, items in seen.items():
        for i in items:
            le.insert(Event(event="buy" if i % 5 == 0 else "view", entity_type="user",
                            entity_id=str(u), target_entity_type="item",
                            target_entity_id=str(int(i))), app_id)
    unavailable = rng.choice(N_ITEMS, 60, replace=False)
    le.insert(Event(event="$set", entity_type="constraint", entity_id="unavailableItems",
                    properties=DataMap({"items": [str(int(i)) for i in unavailable]})),
              app_id)
    queries, rules = [], []
    for u in range(N_USERS):
        wanted = list(rng.choice(names, int(rng.integers(0, 3)), replace=False))
        black = rng.choice(N_ITEMS, int(rng.integers(0, 20)), replace=False)
        queries.append(Query(user=str(u), num=NUM, categories=tuple(wanted) or None,
                             black_list=tuple(str(int(i)) for i in black) or None))
        rules.append({"seen": seen[u], "black": black,
                      "wanted": np.asarray([category_index[c] for c in wanted], np.int64)})
    # a query that leaves fewer than num: one small category, most of it black-listed
    small = [i for i in range(N_ITEMS) if "c0" in cats[str(i)]]
    queries[0] = Query(user="0", num=NUM, categories=("c0",),
                       black_list=tuple(str(i) for i in small[3:]))
    rules[0] = {"seen": seen[0], "black": np.asarray(small[3:]),
                "wanted": np.asarray([category_index["c0"]])}
    algo = ECommAlgorithm(ECommAlgorithmParams(app_name=APP, rank=RANK))
    return algo, model, queries, rules, unavailable, user, item, codes


def _answers(model, results):
    return [([model.item_index[s.item] for s in r.item_scores],
             [s.score for s in r.item_scores]) for r in results]


def _compare(shop, answers, say=lambda *_: None, limits=LIMITS):
    _, _, _, rules, unavailable, user, item, codes = shop
    return reference.compare_serve(say, limits, "p3", NUM, user, item, codes, rules,
                                   unavailable, answers)


@pytest.mark.parametrize("how", ["predict", "batch_unpinned", "batch_pinned"])
def test_engine_agrees_with_the_plain_reference(shop, how):
    algo, model, queries, *_ = shop
    if how == "predict":
        results = [algo.predict(model, q) for q in queries]
    else:
        if how == "batch_pinned":
            model, nbytes = algo.pin_model_for_serving(model)
            assert serving_state(model).item_tiles.shape == (3, RANK, 256) and nbytes > 0
        got = dict(algo.batch_predict(model, list(enumerate(queries))))
        results = [got[i] for i in range(len(queries))]
    lines = []
    assert _compare(shop, _answers(model, results), lines.append), "\n".join(lines)
    assert any("fails as it must" in ln for ln in lines)  # the control, in the run
    assert len(results[0].item_scores) < NUM  # the short answer came back short


def test_predict_and_both_batch_paths_give_the_same_answers(shop):
    algo, model, queries, *_ = shop
    single = [algo.predict(model, q) for q in queries]
    host = dict(algo.batch_predict(model, list(enumerate(queries))))
    pinned, _ = algo.pin_model_for_serving(model)
    device = dict(algo.batch_predict(pinned, list(enumerate(queries))))
    for i, want in enumerate(single):
        for got in (host[i], device[i]):
            assert [s.item for s in got.item_scores] == [s.item for s in want.item_scores]
            np.testing.assert_allclose([s.score for s in got.item_scores],
                                       [s.score for s in want.item_scores], rtol=2e-6)


def test_white_list_and_unknown_user_keep_the_host_path_and_are_counted(shop):
    algo, model, queries, *_ = shop
    model, _ = algo.pin_model_for_serving(model)
    collector = spans.Collector()
    previous = spans.bind(collector)
    try:
        got = dict(algo.batch_predict(model, [
            (0, Query(user="1", num=3, white_list=("5", "6", "7", "8"))),
            (1, Query(user="nobody", num=3)),
            (2, queries[2]), (3, queries[0])]))
    finally:
        spans.bind(previous)
    counts = collector.take_counts()
    assert counts["filter.hostPath"] == 2 and counts["filter.shortAnswers"] >= 1
    assert counts["filter.excludedIds"] > 60 and counts["filter.categoryRows"] >= 1
    assert {s.item for s in got[0].item_scores} <= {"5", "6", "7", "8"}
    assert len(got[1].item_scores) == 3 and len(got[2].item_scores) == NUM
    assert {"filterLookup", "filterBuild", "dispatch", "deviceWait", "format"} <= {
        r.name for r in collector.take()}


def test_the_comparison_has_teeth(shop):
    """An answer that breaks a rule, one that is short, one whose score is
    nudged, and a limit so wide that three bf16 passes meet it: each makes
    the comparison fail."""
    algo, model, queries, rules, unavailable, *_ = shop
    sound = _answers(model, [algo.predict(model, q) for q in queries])
    assert _compare(shop, sound)
    seen_item = int(rules[3]["seen"][0])
    broken = [(ids, sc) for ids, sc in sound]
    broken[3] = ([seen_item] + broken[3][0][1:], broken[3][1])
    assert not _compare(shop, broken)
    short = list(sound)
    short[5] = (sound[5][0][:-1], sound[5][1][:-1])
    assert not _compare(shop, short)
    nudged = [(ids, [s * (1 + 1e-3) for s in sc]) for ids, sc in sound]
    assert not _compare(shop, nudged)
    lines = []
    assert not _compare(shop, sound, lines.append,
                        {**LIMITS, "serve_rms_rel_err": 1e-5})
    assert any("PASSED: the comparison has no teeth" in ln for ln in lines)


def test_a_store_error_means_no_filter(shop, monkeypatch):
    algo, model, queries, *_ = shop
    from predictionio_tpu.data import store

    def boom(*a, **kw):
        raise RuntimeError("store down")

    monkeypatch.setattr(store.LEventStore, "find_by_entities", boom)
    assert algo._store_rules(["1", "2"]) == ({}, set())
    assert len(algo.predict(model, Query(user="1", num=5)).item_scores) == 5


@pytest.fixture(params=["columnar", "sqlite"])
def shop_on_disk(request, tmp_path, monkeypatch):
    """The same shop with its events in a driver that keeps columns (the
    histories sealed into a segment, the constraint in the tail behind it,
    as the benchmark's cell has them) and in one that does not."""
    from predictionio_tpu.data.storage import Storage

    source = {
        "columnar": {"PIO_STORAGE_SOURCES_EV_TYPE": "columnar",
                     "PIO_STORAGE_SOURCES_EV_PATH": str(tmp_path / "events")},
        "sqlite": {"PIO_STORAGE_SOURCES_EV_TYPE": "sqlite",
                   "PIO_STORAGE_SOURCES_EV_PATH": str(tmp_path / "pio.db")},
    }[request.param]
    Storage.configure({
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "EV",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
        "PIO_STORAGE_SOURCES_MEM_TYPE": "memory", **source})
    try:
        made = _shop_in(Storage, monkeypatch)
        if request.param == "columnar":
            le = Storage.get_l_events()
            app_id = Storage.get_meta_data_apps().get_by_name(APP).id
            constraint, = le.find(app_id, entity_type="constraint")
            assert le.delete(constraint.event_id, app_id)
            assert Storage.get_p_events().compact(app_id) > 0
            le.insert(constraint, app_id)
        yield request.param, made
    finally:
        Storage.configure(None)


@pytest.mark.parametrize("how", ["predict", "batch_unpinned", "batch_pinned"])
def test_engine_agrees_with_the_plain_reference_whatever_driver_holds_the_events(
        shop_on_disk, how):
    _, made = shop_on_disk
    test_engine_agrees_with_the_plain_reference(made, how)


def _batch_counts(algo, model, queries):
    collector = spans.Collector()
    previous = spans.bind(collector)
    try:
        got = dict(algo.batch_predict(model, list(enumerate(queries))))
    finally:
        spans.bind(previous)
    return got, collector.take_counts()


def test_a_batch_counts_which_way_the_store_answered_it(shop_on_disk):
    """``filter.columnReads`` a batch whose users' seen items came out of
    the store's columns, ``filter.eventReads`` one that went through
    ``Event`` objects (a driver without columns)."""
    driver, (algo, model, queries, *_) = shop_on_disk
    _, counts = _batch_counts(algo, model, queries[:8])
    mine, other = (("filter.columnReads", "filter.eventReads") if driver == "columnar"
                   else ("filter.eventReads", "filter.columnReads"))
    assert counts[mine] == 1 and other not in counts
    _, counts = _batch_counts(algo, model, queries[8:12])
    assert counts[mine] == 1


def test_a_batch_on_the_memory_driver_counts_an_event_read(shop):
    algo, model, queries, *_ = shop
    _, counts = _batch_counts(algo, model, queries[:8])
    assert counts["filter.eventReads"] == 1 and "filter.columnReads" not in counts


@pytest.mark.parametrize("read", ["targets_by_entities", "find_by_entities"])
def test_an_error_in_either_store_read_means_no_filter(shop, monkeypatch, read):
    algo, model, queries, _, unavailable, *_ = shop
    from predictionio_tpu.data import store

    seen, blocked = algo._store_rules(["1", "2"])
    assert seen["1"] and seen["2"] and blocked == {str(int(i)) for i in unavailable}

    def boom(*a, **kw):
        raise RuntimeError("store down")

    monkeypatch.setattr(store.LEventStore, read, boom)
    assert algo._store_rules(["1", "2"]) == ({}, set())
    got, counts = _batch_counts(algo, model, queries[:8])
    assert all(len(got[i].item_scores) == NUM for i in range(1, 8))
    assert "filter.columnReads" not in counts


def test_the_constraint_is_folded_once_while_its_events_stand_and_again_when_they_change(
        shop_on_disk):
    """While the constraint's events are the last read's (the columnar
    driver hands out the very objects of its parsed tail; another driver
    equal ones), the fold and ``blocked_mask``'s key are the same object
    batch after batch; a ``$set`` or a ``$delete`` posted between two
    reads shows in the second."""
    from predictionio_tpu.data.storage import Storage

    _, (algo, model, _, _, unavailable, *_) = shop_on_disk
    app_id = Storage.get_meta_data_apps().get_by_name(APP).id
    le = Storage.get_l_events()
    _, first = algo._store_rules(["1"])
    _, again = algo._store_rules(["2"])
    assert first == again == {str(int(i)) for i in unavailable}
    assert again is first
    mask = algo.blocked_mask(model, first)
    assert algo.blocked_mask(model, again) is mask
    le.insert(Event(event="$set", entity_type="constraint", entity_id="unavailableItems",
                    properties=DataMap({"items": ["3", "4"]})), app_id)
    _, changed = algo._store_rules(["1"])
    assert changed == {"3", "4"}
    assert algo.blocked_mask(model, changed) is not mask
    assert np.flatnonzero(algo.blocked_mask(model, changed)).tolist() == [3, 4]
    le.insert(Event(event="$delete", entity_type="constraint",
                    entity_id="unavailableItems"), app_id)
    assert algo._store_rules(["1"])[1] == set()


def test_two_batches_at_once_are_each_served_the_mask_of_their_own_read(shop, monkeypatch):
    """ISSUE 31: the batcher keeps two batches in flight, so ``batch_predict``
    runs against itself, and the constraint may change between the two
    store reads: each batch's answers obey the constraint *it* read."""
    import threading

    algo, model, queries, _, unavailable, *_ = shop
    model, _ = algo.pin_model_for_serving(model)
    read = algo._store_rules
    by_thread = {}  # a thread's read of the constraint

    def store_rules(users):
        seen, _ = read(users)
        return seen, set(by_thread[threading.current_thread().name])

    monkeypatch.setattr(algo, "_store_rules", store_rules)
    slots = list(enumerate(queries))
    constraints = {"old": {str(int(i)) for i in unavailable},
                   "new": {str(i) for i in range(0, N_ITEMS, 3)}}
    want = {}
    for name, ids in constraints.items():
        by_thread[threading.current_thread().name] = ids
        want[name] = dict(algo.batch_predict(model, slots))
    assert want["old"] != want["new"]
    wrong = []

    def batches(name):
        by_thread[threading.current_thread().name] = constraints[name]
        for _ in range(25):
            got = dict(algo.batch_predict(model, slots))
            if got != want[name]:
                wrong.append(name)

    threads = [threading.Thread(target=batches, args=(name,), name=name, daemon=True)
               for name in constraints]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads)
    assert wrong == []


def test_the_blocked_mask_is_read_from_its_cache_once(shop):
    """What the other batch assigns between two reads of the cache must not
    reach this one: the cache is one tuple, read once."""
    algo, model, *_ = shop
    mine, theirs = {"1", "2"}, {"3"}
    mask = algo.blocked_mask(model, mine)
    other = algo.blocked_mask(model, theirs)
    assert not np.array_equal(mask, other)

    class Swapped(FilteredServingState):
        """A state whose cache another batch re-assigns after every read."""
        reads = 0

        @property
        def blocked(self):
            Swapped.reads += 1
            return (mine, mask) if Swapped.reads == 1 else (theirs, other)

        @blocked.setter
        def blocked(self, value):
            pass

    model._pio_serving = Swapped()
    assert algo.blocked_mask(model, mine) is mask and Swapped.reads == 1
