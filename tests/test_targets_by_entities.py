"""The serving-time read by entity that answers from columns (ISSUE 43):
``LEvents.targets_by_entities`` gives, for a batch of entities, the target
ids of their events as the store holds them at the call. On the columnar
driver it reads the segments' columns (no ``Event`` a row); every other
driver answers through the base class. Both must say what
``find_by_entities`` says, reduced to ``target_entity_id``."""

import dataclasses
import datetime as dt
from collections import Counter

import numpy as np
import pytest

from predictionio_tpu.data.event import DataMap, Event
from predictionio_tpu.data.storage import columnar, memory, sqlite
from predictionio_tpu.data.storage.base import App, StorageClientConfig
from predictionio_tpu.utils import spans

UTC = dt.timezone.utc
APP = 1
BASE_T = dt.datetime(2024, 3, 1, tzinfo=UTC)
#: asked of every store: users the segments hold, one only the tail
#: holds, one nobody holds, and one longer than any vocabulary's widest
USERS = [f"u{k}" for k in range(0, 12, 2)] + [
    "late", "nobody", "a-user-whose-id-is-longer-than-any-the-store-has-seen"]
NAMES = {"seen": ("view", "buy"), "one": ("buy",), "any": None, "none": ("like",)}
STAGES = ("segments", "tail", "tombstones", "compacted", "tail_again")


def _events(n, seed, users=12):
    rng = np.random.default_rng(seed)
    out = []
    for k in range(n):
        target = None if k % 11 == 0 else f"i{rng.integers(0, 40)}"
        out.append(Event(
            event=str(rng.choice(["view", "buy", "rate"])),
            # an item entity that shares a user's id must not be read as it
            entity_type="item" if k % 13 == 0 else "user",
            entity_id=f"u{rng.integers(0, users)}",
            target_entity_type="item" if target else None,
            target_entity_id=target,
            properties=DataMap({"rating": 3.0} if k % 5 == 0 else {}),
            event_time=BASE_T + dt.timedelta(seconds=int(rng.integers(0, 500))),
        ))
    return out


def _columnar(tmp_path):
    client = columnar.StorageClient(StorageClientConfig(
        "C", "columnar", {"path": str(tmp_path / "cols"), "segment_rows": "64"}))
    le = client.get_l_events()
    le.init(APP)
    return client, le


def _fill(client, le, upto):
    """A columnar store built stage by stage, each stage on top of the ones
    before: several positional segments; a tail; tombstones on a segment's
    row and on a tail event; the tail compacted into an explicit-id segment
    (and one of its events deleted by id); a new tail behind it."""
    stages = STAGES[: STAGES.index(upto) + 1]
    client.get_p_events().write(_events(200, 1), APP)  # 4 segments of 64 rows
    tail_ids = []
    if "tail" in stages:
        tail_ids = [le.insert(e, APP) for e in _events(40, 2)]
        le.insert(Event(event="view", entity_type="user", entity_id="late",
                        target_entity_type="item", target_entity_id="i7"), APP)
    if "tombstones" in stages:
        firsts = [e for e in le.find(APP, entity_type="user", entity_id="u2")
                  if "@" in e.event_id][:2]
        assert len(firsts) == 2
        for e in firsts:
            assert le.delete(e.event_id, APP)
        assert le.delete(tail_ids[3], APP) and le.delete(tail_ids[8], APP)
    if "compacted" in stages:
        assert le.compact(APP) > 0
        assert le.delete(tail_ids[5], APP)  # now a row of an explicit-id segment
    if "tail_again" in stages:
        for e in _events(25, 3):
            le.insert(e, APP)
    return tail_ids


def _reduced(le, names, users=USERS):
    """``find_by_entities`` reduced to the targets: what the read must say."""
    found = le.find_by_entities(APP, [("user", u) for u in users], event_names=names)
    return {u: Counter(e.target_entity_id for e in evs if e.target_entity_id is not None)
            for (_, u), evs in found.items()}


def _targets(le, names, users=USERS):
    got = le.targets_by_entities(APP, "user", users, event_names=names)
    assert all(type(t) is str for ts in got.values() for t in ts)
    return {u: Counter(ts) for u, ts in got.items()}


@pytest.mark.parametrize("names", list(NAMES))
@pytest.mark.parametrize("stage", STAGES)
def test_the_columns_say_what_the_events_say(tmp_path, stage, names):
    client, le = _columnar(tmp_path)
    _fill(client, le, stage)
    want = _reduced(le, NAMES[names])
    assert set(want) == set(USERS)
    assert _targets(le, NAMES[names]) == want
    if names == "any":
        assert sum(map(len, want.values())) > 20 and not want["nobody"]
        assert bool(want["late"]) == (stage != "segments")
    if names == "none":
        assert not any(want.values())


def test_the_store_it_is_held_against_has_every_kind_of_row(tmp_path):
    """Several positional segments, an explicit-id one, a tail, tombstones
    of both kinds: else the comparisons above compare less than they say."""
    client, le = _columnar(tmp_path)
    _fill(client, le, "tail_again")
    d = le._stream_dir(APP, None)
    segs = [le._segment(p) for p in le._segment_paths(d)]
    assert sum(s.ids is None for s in segs) >= 3 and any(s.ids is not None for s in segs)
    _, tail_lines, tomb = le._snapshot(d)
    assert tail_lines and any("@" in t for t in tomb)
    dead_ids, dead_rows = le._split_tombstones(tomb)
    assert dead_rows and any(
        str(i) in dead_ids for s in segs if s.ids is not None for i in s.ids)


@pytest.mark.parametrize("names", ["seen", "any"])
def test_compacting_changes_no_answer(tmp_path, names):
    client, le = _columnar(tmp_path)
    _fill(client, le, "tombstones")
    before = _targets(le, NAMES[names])
    assert le.compact(APP) > 0
    assert _targets(le, NAMES[names]) == before == _reduced(le, NAMES[names])


@pytest.mark.parametrize("what", ["posted", "tail_deleted", "row_deleted", "compacted_deleted"])
def test_the_answer_is_the_stores_at_the_call(tmp_path, what):
    """An event posted, and one deleted, between two reads shows in the
    second: nothing is kept that a write does not change."""
    client, le = _columnar(tmp_path)
    tail_ids = _fill(client, le, "tail_again")
    first = _targets(le, NAMES["seen"])
    assert _targets(le, NAMES["seen"]) == first  # a second read of a store at rest
    if what == "posted":
        le.insert(Event(event="buy", entity_type="user", entity_id="u4",
                        target_entity_type="item", target_entity_id="brand-new"), APP)
        want = {**first, "u4": first["u4"] + Counter(["brand-new"])}
    else:
        held = {
            "tail_deleted": lambda e: "@" not in e.event_id and e.event_id not in tail_ids,
            "row_deleted": lambda e: "@" in e.event_id,
            "compacted_deleted": lambda e: e.event_id in tail_ids,
        }[what]
        gone = next(e for e in le.find(APP, entity_type="user", event_names=NAMES["seen"])
                    if e.entity_id in USERS and e.target_entity_id and held(e))
        assert le.delete(gone.event_id, APP)
        want = {**first, gone.entity_id:
                first[gone.entity_id] - Counter([gone.target_entity_id])}
    assert _targets(le, NAMES["seen"]) == want == _reduced(le, NAMES["seen"])


def _other_driver(kind, tmp_path):
    if kind == "memory":
        return memory.StorageClient(StorageClientConfig("M", "memory", {})).get_l_events()
    return sqlite.StorageClient(StorageClientConfig(
        "S", "sqlite", {"path": str(tmp_path / "pio.db")})).get_l_events()


@pytest.mark.parametrize("names", ["seen", "any"])
@pytest.mark.parametrize("kind", ["memory", "sqlite"])
def test_every_other_driver_answers_through_the_base_class(tmp_path, kind, names):
    """The same events in a driver without columns: the same answer, and
    the same as the columnar driver's."""
    le = _other_driver(kind, tmp_path)
    le.init(APP)
    events = _events(200, 1) + _events(40, 2)
    ids = [le.insert(e, APP) for e in events]
    assert le.delete(ids[17], APP)
    client, col = _columnar(tmp_path)
    client.get_p_events().write([e for k, e in enumerate(events) if k != 17], APP)
    got = _targets(le, NAMES[names])
    assert got == _reduced(le, NAMES[names]) == _targets(col, NAMES[names])
    assert sum(map(len, got.values())) > 20


@pytest.mark.parametrize("kind, counted", [
    ("columnar", "filter.columnReads"), ("memory", "filter.eventReads"),
    ("sqlite", "filter.eventReads")])
def test_the_read_says_which_way_it_answered(tmp_path, kind, counted):
    if kind == "columnar":
        client, le = _columnar(tmp_path)
        client.get_p_events().write(_events(100, 1), APP)
    else:
        le = _other_driver(kind, tmp_path)
        le.init(APP)
        for e in _events(30, 1):
            le.insert(e, APP)
    collector = spans.Collector()
    previous = spans.bind(collector)
    try:
        le.targets_by_entities(APP, "user", USERS, event_names=("view", "buy"))
        le.targets_by_entities(APP, "user", USERS[:2])
    finally:
        spans.bind(previous)
    counts = collector.take_counts()
    assert counts == {counted: 2}
    le.targets_by_entities(APP, "user", USERS)  # no collector bound: no error


@pytest.mark.parametrize("width", [np.int32, np.int64, np.int16])
def test_entity_rows_searches_in_the_columns_own_dtype(tmp_path, monkeypatch, width):
    """A needle wider than the haystack makes numpy cast the whole entity
    column, on every search: the needle is cast, not the column."""
    client, le = _columnar(tmp_path)
    client.get_p_events().write(_events(60, 1), APP)
    seg = le._segment(le._segment_paths(le._stream_dir(APP, None))[0])
    seg = dataclasses.replace(seg, eid_code=seg.eid_code.astype(width), _by_entity=None)
    want = np.flatnonzero(np.isin(seg.eid_vocab[seg.eid_code], USERS))
    searched = []
    real = np.searchsorted

    def searchsorted(a, v, *args, **kw):
        searched.append((np.asarray(a), np.asarray(v)))
        return real(a, v, *args, **kw)

    monkeypatch.setattr(columnar.np, "searchsorted", searchsorted)
    assert np.array_equal(seg.entity_rows(USERS), want) and want.size
    in_the_column = [(a, v) for a, v in searched if a.shape == seg.eid_code.shape]
    assert len(in_the_column) == 2  # lo and hi
    assert all(a.dtype == v.dtype == width for a, v in in_the_column)


def test_levent_store_reads_by_app_name_under_its_deadline(storage_env, monkeypatch):
    """``LEventStore.targets_by_entities``: the app by name, the driver's
    answer, and the ``timeout`` as the ambient deadline of the scan."""
    from predictionio_tpu import resilience
    from predictionio_tpu.data.store import LEventStore

    app_id = storage_env.get_meta_data_apps().insert(App(id=0, name="shop"))
    le = storage_env.get_l_events()
    le.init(app_id)
    for e in _events(60, 1):
        le.insert(e, app_id)
    want = le.targets_by_entities(app_id, "user", USERS, event_names=("view",))
    seen = []
    real = type(le).targets_by_entities

    def watched(self, *a, **kw):
        seen.append(resilience.current_deadline())
        return real(self, *a, **kw)

    monkeypatch.setattr(type(le), "targets_by_entities", watched)
    got = LEventStore.targets_by_entities(
        "shop", "user", USERS, event_names=("view",), timeout=2.0)
    assert {u: Counter(t) for u, t in got.items()} == {u: Counter(t) for u, t in want.items()}
    assert seen[0] is not None
    LEventStore.targets_by_entities("shop", "user", USERS)
    assert seen[1] is None


# --- the snapshot behind both reads: kept by what the files are, never by when ---

def _second_process(tmp_path):
    """Another client over the same directory: what a writer in another
    process is to this one (nothing in memory is shared)."""
    return _columnar(tmp_path)


@pytest.mark.parametrize("what", ["posted", "deleted", "compacted", "bulk_written"])
def test_a_write_from_another_process_shows_in_the_next_read(tmp_path, what):
    client, le = _columnar(tmp_path)
    tail_ids = _fill(client, le, "tail_again")
    first = _targets(le, NAMES["seen"])
    other_client, other = _second_process(tmp_path)
    if what == "posted":
        other.insert(Event(event="view", entity_type="user", entity_id="u0",
                           target_entity_type="item", target_entity_id="from-afar"), APP)
        want = {**first, "u0": first["u0"] + Counter(["from-afar"])}
    elif what == "deleted":
        gone = next(e for e in other.find(APP, entity_type="user", event_names=NAMES["seen"])
                    if e.entity_id in USERS and e.target_entity_id
                    and "@" not in e.event_id and e.event_id not in tail_ids)
        assert other.delete(gone.event_id, APP)
        want = {**first, gone.entity_id:
                first[gone.entity_id] - Counter([gone.target_entity_id])}
    elif what == "compacted":
        assert other.compact(APP) > 0
        want = first
    else:
        other_client.get_p_events().write(
            [Event(event="buy", entity_type="user", entity_id="u8",
                   target_entity_type="item", target_entity_id="in-bulk")], APP)
        want = {**first, "u8": first["u8"] + Counter(["in-bulk"])}
    assert _targets(le, NAMES["seen"]) == want == _reduced(le, NAMES["seen"])


def test_a_snapshot_reads_a_file_again_only_when_it_is_another(tmp_path, monkeypatch):
    client, le = _columnar(tmp_path)
    _fill(client, le, "tail_again")
    d = le._stream_dir(APP, None)
    segs, lines, tomb = le._snapshot(d)
    again = le._snapshot(d)
    assert again[1] is lines and again[2] is tomb and again[0] == segs and lines and tomb
    le.insert(Event(event="view", entity_type="user", entity_id="u0",
                    target_entity_type="item", target_entity_id="i1"), APP)
    grown = le._snapshot(d)
    assert grown[1] is not lines and len(grown[1]) == len(lines) + 1 and grown[2] is tomb
    assert le.delete(json_id(grown[1][-1]), APP)
    assert le._snapshot(d)[2] == tomb | {"t:" + json_id(grown[1][-1])}
    # a file that has grown over the size that is kept is read every time
    # (and what was kept of it goes)
    monkeypatch.setattr(type(le), "_KEPT_FILE_BYTES", 10)
    le.insert(Event(event="view", entity_type="user", entity_id="u0",
                    target_entity_type="item", target_entity_id="i2"), APP)
    one, two = le._snapshot(d), le._snapshot(d)
    assert one[1] == two[1] and one[1] is not two[1] and len(one[1]) == len(grown[1]) + 1
    assert not any(p.endswith("tail.jsonl") for p in le._kept_files)


def json_id(line):
    import json

    return json.loads(line)["eventId"]
