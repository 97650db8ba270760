"""Columnar event path: driver roundtrips, find_columns equivalence with
the event-stream path, and the recommendation template's vectorized read
(VERDICT r3 next-round #1 — the full product path at array speed)."""

import datetime as dt

import numpy as np
import pytest

from predictionio_tpu.data.event import DataMap, Event
from predictionio_tpu.data.storage import columnar, memory
from predictionio_tpu.data.storage.base import StorageClientConfig

UTC = dt.timezone.utc
APP = 1
BASE_T = dt.datetime(2023, 1, 1, tzinfo=UTC)


def _mk_events(n=400, seed=0):
    """Random rate/buy/view events with duplicate (user, item) pairs,
    timestamp ties, missing targets, and non-float properties."""
    rng = np.random.default_rng(seed)
    events = []
    for k in range(n):
        kind = rng.choice(["rate", "buy", "view"], p=[0.6, 0.25, 0.15])
        u, i = f"u{rng.integers(0, 25)}", f"i{rng.integers(0, 18)}"
        props = {}
        if kind == "rate":
            props["rating"] = float(rng.integers(1, 11)) / 2.0
        if k % 37 == 0:
            props["note"] = "stringy"  # forces the JSON residue column
        target = None if k % 29 == 0 else i
        events.append(
            Event(
                event=str(kind),
                entity_type="user",
                entity_id=u,
                target_entity_type="item" if target else None,
                target_entity_id=target,
                properties=DataMap(props),
                # coarse timestamps create (user, item) ties on purpose
                event_time=BASE_T + dt.timedelta(seconds=int(rng.integers(0, 50))),
            )
        )
    return events


def _columnar_client(tmp_path, segment_rows=100):
    return columnar.StorageClient(
        StorageClientConfig(
            "C", "columnar",
            {"path": str(tmp_path / "cols"), "segment_rows": str(segment_rows)},
        )
    )


def _decode(cols):
    """EventColumns -> set of (event, entity, target, time_us, prop) rows."""
    out = set()
    for j in range(len(cols)):
        out.add(
            (
                str(cols.event_vocab[cols.event_code[j]]),
                str(cols.entity_vocab[cols.entity_code[j]]),
                str(cols.target_vocab[cols.target_code[j]])
                if cols.target_code[j] >= 0
                else None,
                int(cols.event_time_us[j]),
                None
                if cols.prop is None or np.isnan(cols.prop[j])
                else float(cols.prop[j]),
            )
        )
    return out


class TestFindColumns:
    def test_columnar_matches_iterator_fallback(self, tmp_path):
        """The columnar driver's array-speed find_columns must return the
        same logical rows as the universal event-iterator fallback run on
        the same events (memory driver)."""
        events = _mk_events()
        mem = memory.StorageClient(StorageClientConfig("M", "memory"))
        mem.get_p_events().write(events, APP)
        col = _columnar_client(tmp_path)
        col.get_p_events().write(events, APP)

        kw = dict(event_names=["rate", "buy"], prop="rating")
        got_mem = _decode(mem.get_p_events().find_columns(APP, **kw))
        got_col = _decode(col.get_p_events().find_columns(APP, **kw))
        assert got_col == got_mem
        assert len(got_col) > 0

    def test_tail_and_segments_combine(self, tmp_path):
        col = _columnar_client(tmp_path)
        events = _mk_events(120)
        col.get_p_events().write(events[:100], APP)  # segments
        le = col.get_l_events()
        le.init(APP)
        for e in events[100:]:
            le.insert(e, APP)  # tail
        cols = col.get_p_events().find_columns(APP)
        assert len(cols) == 120

    def test_tombstones_respected(self, tmp_path):
        col = _columnar_client(tmp_path, segment_rows=10)
        col.get_p_events().write(_mk_events(30), APP)
        le = col.get_l_events()
        all_events = list(le.find(APP))
        dead = all_events[7].event_id
        assert le.delete(dead, APP)
        assert le.get(dead, APP) is None
        cols = col.get_p_events().find_columns(APP)
        assert len(cols) == 29
        assert len(list(le.find(APP))) == 29

    def test_sharding_partitions(self, tmp_path):
        col = _columnar_client(tmp_path, segment_rows=16)
        col.get_p_events().write(_mk_events(50), APP)
        pe = col.get_p_events()
        sizes = [
            len(pe.find_columns(APP, shard_index=s, num_shards=3))
            for s in range(3)
        ]
        assert sum(sizes) == 50 and all(s > 0 for s in sizes)

    def test_write_columns_bulk_ingest(self, tmp_path):
        """The vectorized sharded-writer path: COO arrays -> segments ->
        identical events via both the columnar and object reads."""
        col = _columnar_client(tmp_path, segment_rows=64)
        rng = np.random.default_rng(3)
        n, n_users, n_items = 200, 20, 12
        users = rng.integers(0, n_users, n)
        items = rng.integers(0, n_items, n)
        ratings = rng.integers(1, 6, n).astype(np.float64)
        t_us = (1_600_000_000_000_000 + np.arange(n)).astype(np.int64)
        written = col.get_p_events().write_columns(
            APP,
            event="rate",
            entity_type="user",
            entity_codes=users,
            entity_vocab=np.asarray([f"u{i}" for i in range(n_users)]),
            target_entity_type="item",
            target_codes=items,
            target_vocab=np.asarray([f"i{i}" for i in range(n_items)]),
            event_time_us=t_us,
            props={"rating": ratings},
        )
        assert written == n
        cols = col.get_p_events().find_columns(APP, prop="rating")
        assert len(cols) == n
        # spot-check one decoded event through the object path
        ev = next(iter(col.get_p_events().find(APP, entity_id="u3")))
        assert ev.entity_id == "u3" and ev.target_entity_type == "item"
        assert isinstance(ev.properties.get_as("rating", float), float)


class TestTemplateColumnarRead:
    def _train_data_via(self, client, path_kind):
        from predictionio_tpu.controller.context import local_context
        from predictionio_tpu.data.storage import Storage
        from predictionio_tpu.data.storage.base import App
        from predictionio_tpu.templates.recommendation.engine import (
            DataSourceParams,
            RecommendationDataSource,
        )

        ds = RecommendationDataSource(DataSourceParams(app_name="colapp"))
        ctx = local_context()
        if path_kind == "columnar":
            return ds._read_training_columnar(ctx)
        return ds._to_training_data(ds._read_ratings_stream(ctx), ctx)

    @pytest.fixture()
    def app_on(self, tmp_path):
        """Configure the process registry: metadata in memory, events on
        the given driver. Yields a setter used per-driver."""
        from predictionio_tpu.data.storage import Storage
        from predictionio_tpu.data.storage.base import App

        def setup(kind):
            env = {
                "PIO_FS_BASEDIR": str(tmp_path / "base"),
                "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
                "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
                "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
            }
            if kind == "columnar":
                env.update(
                    {
                        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "COL",
                        "PIO_STORAGE_SOURCES_COL_TYPE": "columnar",
                        "PIO_STORAGE_SOURCES_COL_PATH": str(tmp_path / kind),
                        "PIO_STORAGE_SOURCES_COL_SEGMENT_ROWS": "97",
                    }
                )
            else:
                env["PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE"] = "MEM"
            Storage.configure(env)
            app_id = Storage.get_meta_data_apps().insert(App(id=0, name="colapp"))
            Storage.get_l_events().init(app_id)
            return app_id

        yield setup
        Storage.configure(None)

    def test_vectorized_read_matches_event_stream_read(self, app_on):
        """The defining equivalence: on identical events, the vectorized
        columnar read and the per-event stream read produce the same
        rating matrix (same (user, item, rating) set, incl. latest-wins
        dedup and tie-breaks)."""
        from predictionio_tpu.data.storage import Storage

        events = _mk_events(500, seed=11)
        app_on("columnar")
        Storage.get_p_events().write(events, 1)
        td_fast = self._train_data_via(None, "columnar")
        td_slow = self._train_data_via(None, "triples")

        def as_set(td):
            return {
                (
                    td.user_index.inverse(int(r)),
                    td.item_index.inverse(int(c)),
                    round(float(v), 5),
                )
                for r, c, v in zip(td.rows, td.cols, td.vals)
            }

        assert len(td_fast.rows) == len(td_slow.rows)
        assert as_set(td_fast) == as_set(td_slow)

    def test_missing_rating_raises_both_paths(self, app_on):
        from predictionio_tpu.data.event import EventValidationError
        from predictionio_tpu.data.storage import Storage

        app_on("columnar")
        bad = Event(
            event="rate", entity_type="user", entity_id="u1",
            target_entity_type="item", target_entity_id="i1",
            properties=DataMap({}),  # no rating
        )
        Storage.get_p_events().write([bad], 1)
        with pytest.raises(EventValidationError):
            self._train_data_via(None, "columnar")
        with pytest.raises(Exception):
            self._train_data_via(None, "triples")


class TestIncrementalReindex:
    """Delta re-index on the append-only columnar store (SURVEY §8.3):
    repeat trains read only NEW segments/tail; the merged result is
    identical to a full re-read; any mutation that breaks the prefix
    assumption (tombstones, store recreation) falls back to a full read."""

    def _setup(self, tmp_path, monkeypatch):
        from predictionio_tpu.data.storage import Storage
        from predictionio_tpu.data.storage.base import App

        monkeypatch.setenv("PIO_FS_BASEDIR", str(tmp_path / "base"))
        Storage.configure(
            {
                "PIO_FS_BASEDIR": str(tmp_path / "base"),
                "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
                "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
                "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
                "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "COL",
                "PIO_STORAGE_SOURCES_COL_TYPE": "columnar",
                "PIO_STORAGE_SOURCES_COL_PATH": str(tmp_path / "ev"),
                "PIO_STORAGE_SOURCES_COL_SEGMENT_ROWS": "64",
            }
        )
        app_id = Storage.get_meta_data_apps().insert(App(id=0, name="incapp"))
        return app_id

    def _td_sets(self, td):
        return {
            (
                td.user_index.inverse(int(r)),
                td.item_index.inverse(int(c)),
                round(float(v), 5),
            )
            for r, c, v in zip(td.rows, td.cols, td.vals)
        }

    def _read(self, incremental=True):
        from predictionio_tpu.controller.context import local_context
        from predictionio_tpu.templates.recommendation.engine import (
            DataSourceParams,
            RecommendationDataSource,
        )

        ds = RecommendationDataSource(
            DataSourceParams(app_name="incapp", incremental=incremental)
        )
        return ds._read_training_columnar(local_context())

    def test_delta_merge_equals_full_read(self, tmp_path, monkeypatch):
        from predictionio_tpu.data.storage import Storage
        import predictionio_tpu.data.storage.columnar as colmod

        app_id = self._setup(tmp_path, monkeypatch)
        try:
            pe = Storage.get_p_events()
            pe.write(_mk_events(200, seed=1), app_id)
            td1 = self._read()  # builds the cache

            # new events arrive: bulk segments AND live tail inserts,
            # including updates to EXISTING (user, item) pairs
            pe.write(_mk_events(150, seed=2), app_id)
            le = Storage.get_l_events()
            for e in _mk_events(30, seed=3):
                le.insert(e, app_id)

            loads = []
            orig = colmod._load_segment

            def spy(path):
                loads.append(path)
                return orig(path)

            monkeypatch.setattr(colmod, "_load_segment", spy)
            # drop the decoded-segment cache so the spy sees real loads
            Storage.get_l_events()._seg_cache.clear()
            td_inc = self._read()  # incremental merge
            inc_loads = len(loads)
            loads.clear()
            td_full = self._read(incremental=False)  # full re-read
            full_loads = len(loads)
            assert self._td_sets(td_inc) == self._td_sets(td_full)
            assert len(td_inc.rows) == len(td_full.rows)
            # the delta read must have touched FEWER segment files than
            # the full read (only the post-cache segments)
            assert 0 < inc_loads < full_loads, (inc_loads, full_loads)
            assert len(td_inc.rows) > len(td1.rows)
            # unchanged store: the fast path reuses the cache and loads
            # ZERO segment files
            loads.clear()
            Storage.get_l_events()._seg_cache.clear()
            td_again = self._read()
            assert self._td_sets(td_again) == self._td_sets(td_full)
            assert len(loads) == 0, loads
        finally:
            Storage.configure(None)

    def test_tombstone_invalidates_cache(self, tmp_path, monkeypatch):
        from predictionio_tpu.data.storage import Storage

        app_id = self._setup(tmp_path, monkeypatch)
        try:
            pe = Storage.get_p_events()
            pe.write(_mk_events(120, seed=5), app_id)
            self._read()  # cache
            le = Storage.get_l_events()
            victim = next(iter(le.find(app_id, event_names=["rate"])))
            assert le.delete(victim.event_id, app_id)
            td_inc = self._read()
            td_full = self._read(incremental=False)
            assert self._td_sets(td_inc) == self._td_sets(td_full)
        finally:
            Storage.configure(None)

    def test_compaction_invalidates_cache_and_regrown_tail(
        self, tmp_path, monkeypatch
    ):
        """A compaction between trains must force a correct (full)
        re-read — including the aliasing case where the tail regrows
        past the cached length, which every legacy check would miss."""
        from predictionio_tpu.data.storage import Storage

        app_id = self._setup(tmp_path, monkeypatch)
        try:
            le = Storage.get_l_events()
            for e in _mk_events(40, seed=8):
                le.insert(e, app_id)
            self._read()  # cache records tail_lines=40, compactions=0
            le.compact(app_id)
            # regrow the tail PAST the recorded length with new events
            for e in _mk_events(55, seed=9):
                le.insert(e, app_id)
            td_inc = self._read()
            td_full = self._read(incremental=False)
            assert self._td_sets(td_inc) == self._td_sets(td_full)
        finally:
            Storage.configure(None)

    def test_compaction_between_scan_state_and_delta_read(
        self, tmp_path, monkeypatch
    ):
        """TOCTOU guard (review finding): a compaction landing between
        _try_incremental's scan_state and its delta find_columns moves
        the uncached tail into a segment outside new_segments — the
        generation recheck must reject the delta and fall back to a full
        read instead of silently dropping those events."""
        from predictionio_tpu.data.storage import Storage

        app_id = self._setup(tmp_path, monkeypatch)
        try:
            le = Storage.get_l_events()
            pe = Storage.get_p_events()
            for e in _mk_events(40, seed=10):
                le.insert(e, app_id)
            self._read()  # cache
            for e in _mk_events(25, seed=11):  # uncached tail events
                le.insert(e, app_id)

            real_find_columns = type(pe).find_columns
            fired = {"n": 0}

            def compact_then_find(self_pe, *a, **kw):
                if kw.get("segments") is not None and fired["n"] == 0:
                    # first DELTA read of this test: compact mid-flight
                    fired["n"] += 1
                    le.compact(app_id)
                return real_find_columns(self_pe, *a, **kw)

            monkeypatch.setattr(type(pe), "find_columns", compact_then_find)
            td_inc = self._read()
            monkeypatch.setattr(type(pe), "find_columns", real_find_columns)
            td_full = self._read(incremental=False)
            assert fired["n"] == 1, "delta read never happened"
            assert self._td_sets(td_inc) == self._td_sets(td_full)
            assert len(td_inc.rows) == len(td_full.rows)
        finally:
            Storage.configure(None)

    def test_store_recreation_invalidates_cache(self, tmp_path, monkeypatch):
        from predictionio_tpu.data.storage import Storage

        app_id = self._setup(tmp_path, monkeypatch)
        try:
            pe = Storage.get_p_events()
            pe.write(_mk_events(100, seed=6), app_id)
            self._read()  # cache against the first incarnation
            pe.delete(app_id)  # drop + recreate the stream
            pe.write(_mk_events(80, seed=7), app_id)
            td_inc = self._read()
            td_full = self._read(incremental=False)
            assert self._td_sets(td_inc) == self._td_sets(td_full)
            assert len(td_inc.rows) <= 80
        finally:
            Storage.configure(None)


class TestSimilarProductColumnarRead:
    def test_vectorized_counts_match_event_stream(self, tmp_path):
        """The similar-product template's vectorized view-count read must
        equal the per-event dict aggregation on identical events
        (including $set-only catalog items)."""
        from predictionio_tpu.controller.context import local_context
        from predictionio_tpu.data.storage import Storage
        from predictionio_tpu.data.storage.base import App
        from predictionio_tpu.templates.similarproduct.engine import (
            DataSourceParams,
            SimilarProductDataSource,
        )

        Storage.configure(
            {
                "PIO_FS_BASEDIR": str(tmp_path / "base"),
                "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
                "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
                "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
                "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "COL",
                "PIO_STORAGE_SOURCES_COL_TYPE": "columnar",
                "PIO_STORAGE_SOURCES_COL_PATH": str(tmp_path / "ev"),
                "PIO_STORAGE_SOURCES_COL_SEGMENT_ROWS": "77",
            }
        )
        try:
            app_id = Storage.get_meta_data_apps().insert(App(id=0, name="spapp"))
            rng = np.random.default_rng(8)
            events = []
            for _ in range(600):
                events.append(
                    Event(
                        event="view", entity_type="user",
                        entity_id=f"u{rng.integers(0, 30)}",
                        target_entity_type="item",
                        target_entity_id=f"i{rng.integers(0, 15)}",
                    )
                )
            # catalog items never viewed, carrying categories
            for k in range(3):
                events.append(
                    Event(
                        event="$set", entity_type="item",
                        entity_id=f"cold{k}",
                        properties=DataMap({"categories": ["c1"]}),
                    )
                )
            Storage.get_p_events().write(events, app_id)

            ds = SimilarProductDataSource(DataSourceParams(app_name="spapp"))
            ctx = local_context()
            td_fast = ds._read_training_columnar(ctx)

            # reference aggregation: plain dict over the event stream
            from predictionio_tpu.data.store import PEventStore

            counts = {}
            for e in PEventStore.find(app_name="spapp", event_names=["view"]):
                key = (e.entity_id, e.target_entity_id)
                counts[key] = counts.get(key, 0.0) + 1.0
            got = {
                (
                    td_fast.user_index.inverse(int(r)),
                    td_fast.item_index.inverse(int(c)),
                ): float(v)
                for r, c, v in zip(td_fast.rows, td_fast.cols, td_fast.vals)
            }
            assert got == counts
            # $set-only items are in the index (for catalog filters)
            for k in range(3):
                assert f"cold{k}" in td_fast.item_index
            assert td_fast.categories["cold0"] == ("c1",)
        finally:
            Storage.configure(None)


class TestECommerceColumnarRead:
    def test_vectorized_weighted_counts_match_event_stream(self, tmp_path):
        """The e-commerce template's vectorized weighted aggregation
        (buy=5, view=1) must equal the per-event dict path, incl. the
        popularity vector."""
        from predictionio_tpu.controller.context import local_context
        from predictionio_tpu.data.storage import Storage
        from predictionio_tpu.data.storage.base import App
        from predictionio_tpu.templates.ecommerce.engine import (
            DataSourceParams,
            ECommerceDataSource,
        )

        Storage.configure(
            {
                "PIO_FS_BASEDIR": str(tmp_path / "base"),
                "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
                "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
                "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
                "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "COL",
                "PIO_STORAGE_SOURCES_COL_TYPE": "columnar",
                "PIO_STORAGE_SOURCES_COL_PATH": str(tmp_path / "ev"),
                "PIO_STORAGE_SOURCES_COL_SEGMENT_ROWS": "53",
            }
        )
        try:
            app_id = Storage.get_meta_data_apps().insert(App(id=0, name="ecapp"))
            rng = np.random.default_rng(12)
            events = []
            for _ in range(500):
                kind = "buy" if rng.random() < 0.3 else "view"
                events.append(
                    Event(
                        event=kind, entity_type="user",
                        entity_id=f"u{rng.integers(0, 25)}",
                        target_entity_type="item",
                        target_entity_id=f"i{rng.integers(0, 12)}",
                    )
                )
            Storage.get_p_events().write(events, app_id)

            ds = ECommerceDataSource(DataSourceParams(app_name="ecapp"))
            td = ds._read_training_columnar(local_context())

            from predictionio_tpu.data.store import PEventStore

            want = {}
            for e in PEventStore.find(app_name="ecapp", event_names=["view", "buy"]):
                w = 5.0 if e.event == "buy" else 1.0
                key = (e.entity_id, e.target_entity_id)
                want[key] = want.get(key, 0.0) + w
            got = {
                (
                    td.user_index.inverse(int(r)),
                    td.item_index.inverse(int(c)),
                ): float(v)
                for r, c, v in zip(td.rows, td.cols, td.vals)
            }
            assert got == want
            # popularity = per-item weighted totals
            for item, pop in (
                ("i0", None), ("i5", None),
            ):
                expect = sum(v for (u, i), v in want.items() if i == item)
                assert float(td.popularity[td.item_index[item]]) == expect
        finally:
            Storage.configure(None)


class TestTwoTowerColumnarRead:
    def test_vectorized_pairs_match_event_stream(self, tmp_path):
        """The two-tower template's vectorized distinct-pair read must
        equal the per-event dict path, including the seen-filter."""
        from predictionio_tpu.controller.context import local_context
        from predictionio_tpu.data.storage import Storage
        from predictionio_tpu.data.storage.base import App
        from predictionio_tpu.templates.twotower.engine import (
            DataSourceParams,
            TwoTowerDataSource,
        )

        Storage.configure(
            {
                "PIO_FS_BASEDIR": str(tmp_path / "base"),
                "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
                "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
                "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
                "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "COL",
                "PIO_STORAGE_SOURCES_COL_TYPE": "columnar",
                "PIO_STORAGE_SOURCES_COL_PATH": str(tmp_path / "ev"),
                "PIO_STORAGE_SOURCES_COL_SEGMENT_ROWS": "61",
            }
        )
        try:
            app_id = Storage.get_meta_data_apps().insert(App(id=0, name="ttapp"))
            rng = np.random.default_rng(4)
            Storage.get_p_events().write(
                [
                    Event(
                        event=str(rng.choice(["view", "buy"])),
                        entity_type="user",
                        entity_id=f"u{rng.integers(0, 20)}",
                        target_entity_type="item",
                        target_entity_id=f"i{rng.integers(0, 14)}",
                    )
                    for _ in range(400)
                ],
                app_id,
            )
            ds = TwoTowerDataSource(DataSourceParams(app_name="ttapp"))
            ctx = local_context()
            td_fast = ds._read_training_columnar(ctx)
            td_slow = ds._to_training_data(ds._read_pairs(ctx))
            fast = {
                (td_fast.user_index.inverse(int(r)), td_fast.item_index.inverse(int(c)))
                for r, c in zip(td_fast.rows, td_fast.cols)
            }
            slow = {
                (td_slow.user_index.inverse(int(r)), td_slow.item_index.inverse(int(c)))
                for r, c in zip(td_slow.rows, td_slow.cols)
            }
            assert fast == slow and len(td_fast.rows) == len(td_slow.rows)
            assert td_fast.seen.as_dict(
                td_fast.user_index, td_fast.item_index
            ) == td_slow.seen.as_dict(td_slow.user_index, td_slow.item_index)
            assert sum(len(v) for v in td_fast.seen.as_dict(
                td_fast.user_index, td_fast.item_index).values()) == len(td_fast.rows)
        finally:
            Storage.configure(None)


class TestCompaction:
    """`compact()` seals the live tail into explicit-id segments (VERDICT
    r5: the documented tail-growth gap, now closed): ids survive, dead
    tail events drop, spent tombstones are garbage-collected, and the
    incremental manifest invalidates safely."""

    def _client(self, tmp_path, segment_rows=8):
        from predictionio_tpu.data.storage import columnar
        from predictionio_tpu.data.storage.base import StorageClientConfig

        return columnar.StorageClient(
            StorageClientConfig(
                "C", "columnar",
                {"path": str(tmp_path / "cc"),
                 "segment_rows": str(segment_rows)},
            )
        )

    def _ev(self, i):
        from predictionio_tpu.data.event import DataMap, Event

        return Event(
            event="rate", entity_type="user", entity_id=f"u{i % 5}",
            target_entity_type="item", target_entity_id=f"i{i % 3}",
            properties=DataMap({"rating": float(i % 5 + 1)}),
        )

    def test_ids_survive_and_remain_deletable(self, tmp_path):
        c = self._client(tmp_path)
        le = c.get_l_events()
        le.init(7)
        ids = [le.insert(self._ev(i), 7) for i in range(20)]
        dead = ids[3]
        assert le.delete(dead, 7)
        moved = le.compact(7)
        assert moved == 19  # the tombstoned event is dropped, not moved
        # tail is empty; events now live in segments
        assert le.scan_state(7)["tail_lines"] == 0
        assert len(le.scan_state(7)["segments"]) >= 3  # 19 rows / 8
        # spent t: tombstone was garbage-collected
        assert le.scan_state(7)["tombstones"] == 0
        # every acknowledged id still resolves to the same event
        for i, eid in enumerate(ids):
            got = le.get(eid, 7)
            if eid == dead:
                assert got is None
                continue
            assert got is not None and got.event_id == eid
            assert got.entity_id == f"u{i % 5}"
        # post-compaction deletes by original id still work
        assert le.delete(ids[5], 7)
        assert le.get(ids[5], 7) is None
        assert len(list(le.find(7))) == 18
        # and the columnar training read agrees
        assert len(c.get_p_events().find_columns(7, prop="rating")) == 18
        c.close()

    def test_compact_empty_and_idempotent(self, tmp_path):
        c = self._client(tmp_path)
        le = c.get_l_events()
        le.init(7)
        assert le.compact(7) == 0
        le.insert(self._ev(0), 7)
        assert le.compact(7) == 1
        assert le.compact(7) == 0  # nothing left in the tail
        assert len(list(le.find(7))) == 1
        c.close()

    def test_incremental_manifest_invalidates_even_after_tail_regrows(
        self, tmp_path
    ):
        """The review-found aliasing hazard: a manifest recorded before
        compaction must stay stale even once the tail REGROWS past the
        recorded length (tail_skip would otherwise silently skip new
        events). The generation counter is what breaks the alias."""
        c = self._client(tmp_path)
        le = c.get_l_events()
        le.init(7)
        for i in range(10):
            le.insert(self._ev(i), 7)
        before = le.scan_state(7)
        le.compact(7)
        after = le.scan_state(7)
        assert before["tail_lines"] > after["tail_lines"]
        assert set(before["segments"]) <= set(after["segments"])
        assert after["compactions"] == before["compactions"] + 1
        # regrow the tail past the recorded length: every legacy check
        # (tombstones equal, segments subset, tail_lines not shrunk)
        # would now pass — only the generation catches it
        for i in range(12):
            le.insert(self._ev(100 + i), 7)
        regrown = le.scan_state(7)
        assert regrown["tail_lines"] >= before["tail_lines"]
        assert regrown["tombstones"] == before["tombstones"]
        assert set(before["segments"]) <= set(regrown["segments"])
        assert regrown["compactions"] != before["compactions"]
        c.close()

    def test_crash_recovery_replays_or_discards(self, tmp_path):
        """Crash atomicity: a commit marker left by a killed compaction
        is replayed on the next access (no duplicates, no loss); stray
        pre-commit .pending files are discarded by the next compact."""
        import json as _json
        import os as _os

        c = self._client(tmp_path)
        le = c.get_l_events()
        le.init(7)
        ids = [le.insert(self._ev(i), 7) for i in range(6)]
        d = le._stream_dir(7, None)

        # simulate a crash AFTER the commit point: stage the pending
        # segment + marker exactly as compact() would, then "die" before
        # the rename/truncate
        live = list(le._tail_events(d))
        path = le._next_segment_path(d)
        name = _os.path.basename(path)
        le._write_segment_from_events(live, 7, None, keep_ids=True,
                                      path=path + ".pending")
        with open(_os.path.join(d, "compact.commit"), "w") as f:
            _json.dump({"pending": [name]}, f)
        # next scan triggers recovery: exactly 6 events, ids intact
        got = list(le.find(7))
        assert len(got) == 6
        assert {e.event_id for e in got} == set(ids)
        assert le.scan_state(7)["tail_lines"] == 0
        assert le.scan_state(7)["compactions"] == 1
        assert not _os.path.exists(_os.path.join(d, "compact.commit"))

        # stray PRE-commit .pending (no marker) must not surface events
        le.insert(self._ev(50), 7)
        live = list(le._tail_events(d))
        path2 = le._next_segment_path(d)
        le._write_segment_from_events(live, 7, None, keep_ids=True,
                                      path=path2 + ".pending")
        assert len(list(le.find(7))) == 7  # pending invisible
        le.compact(7)  # sweeps the stray, then compacts normally
        assert len(list(le.find(7))) == 7
        assert not any(
            n.endswith(".pending") for n in _os.listdir(d)
        )
        c.close()

    def test_cli_app_compact(self, tmp_path, monkeypatch):
        from predictionio_tpu.data.storage import Storage
        from predictionio_tpu.tools import commands

        Storage.configure({
            "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "COL",
            "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
            "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
            "PIO_STORAGE_SOURCES_COL_TYPE": "columnar",
            "PIO_STORAGE_SOURCES_COL_PATH": str(tmp_path / "cols"),
        })
        try:
            out: list[str] = []
            commands.app_new("capp", out=out.append)
            for i in range(5):
                Storage.get_l_events().insert(self._ev(i), 1)
            moved = commands.app_compact("capp", out=out.append)
            assert moved == 5
            assert "Compacted 5" in out[-1]
        finally:
            Storage.configure(None)

    def test_cli_compact_rejected_on_non_columnar(self):
        from predictionio_tpu.data.storage import Storage, StorageError
        from predictionio_tpu.tools import commands

        Storage.configure({
            "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
            "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
            "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
        })
        try:
            out: list[str] = []
            commands.app_new("mapp", out=out.append)
            with pytest.raises(StorageError, match="no tail to compact"):
                commands.app_compact("mapp", out=out.append)
        finally:
            Storage.configure(None)
