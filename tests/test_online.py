"""Online learning (predictionio_tpu.online + wiring) — ISSUE 7.

Covers the tentpole end to end plus the satellites: the tail follower's
exactly-once watermark across segment roll, compaction, and restart;
the fold-in solver against a closed-form oracle; cold-start injection;
the partial hot-swap through QueryService with per-scope (never full)
cache invalidation; incremental IVF maintenance; the streaming
two-tower trainer; feedback-loop eventId stamping; and the strictly-off
defaults.
"""

from __future__ import annotations

import json
import os
import time

import numpy as np
import pytest

from predictionio_tpu.data.event import DataMap, Event
from predictionio_tpu.data.storage import Storage
from predictionio_tpu.templates.retrieval import serving_state


@pytest.fixture()
def columnar_env(tmp_path):
    """Metadata/models in memory, EVENTDATA on the columnar driver —
    the store the tail follower streams from."""
    Storage.configure(
        {
            "PIO_FS_BASEDIR": str(tmp_path),
            "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "COL",
            "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
            "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
            "PIO_STORAGE_SOURCES_COL_TYPE": "columnar",
            "PIO_STORAGE_SOURCES_COL_PATH": str(tmp_path / "events"),
        }
    )
    yield Storage
    Storage.configure(None)


def _rate(u, i, r, eid=None, t=None):
    return Event(
        event="rate",
        entity_type="user",
        entity_id=str(u),
        target_entity_type="item",
        target_entity_id=str(i),
        properties=DataMap({"rating": float(r)}),
        event_id=eid,
        **({"event_time": t} if t is not None else {}),
    )


def _new_app(Storage, name):
    from predictionio_tpu.data.storage.base import App

    return Storage.get_meta_data_apps().insert(App(id=0, name=name))


# ---------------------------------------------------------------------------
# Tail follower: exactly-once across roll / compaction / restart
# ---------------------------------------------------------------------------


class TestTailFollower:
    def _follower(self, name="fapp"):
        from predictionio_tpu.online.follower import TailFollower

        return TailFollower(name)

    def test_starts_at_end_and_streams_new_tail(self, columnar_env):
        app_id = _new_app(columnar_env, "fapp")
        le = columnar_env.get_l_events()
        le.insert_batch([_rate(1, i, 3.0) for i in range(5)], app_id)
        f = self._follower()
        assert f.poll() == []  # history is the trained model's job
        f.commit()
        le.insert_batch([_rate(2, 1, 4.0, "a"), _rate(2, 2, 5.0, "b")], app_id)
        got = [e.event_id for e in f.poll()]
        assert got == ["a", "b"]
        f.commit()
        assert f.poll() == []  # nothing new

    def test_pre_construction_events_after_anchor_are_not_lost(
        self, columnar_env
    ):
        """The watermark anchors at CONSTRUCTION: events landing between
        construction and the first poll must stream, not vanish."""
        app_id = _new_app(columnar_env, "fapp")
        le = columnar_env.get_l_events()
        le.insert_batch([_rate(1, 1, 3.0)], app_id)
        f = self._follower()
        le.insert_batch([_rate(9, 9, 5.0, "late")], app_id)
        assert [e.event_id for e in f.poll()] == ["late"]

    def test_segment_roll_streams_bulk_segments(self, columnar_env):
        app_id = _new_app(columnar_env, "fapp")
        pe = columnar_env.get_p_events()
        f = self._follower()
        f.poll()
        f.commit()
        pe.write([_rate(3, i, 2.0) for i in range(7)], app_id)  # new segment
        assert len(f.poll()) == 7
        f.commit()
        assert f.poll() == []

    def test_torn_tail_bytes_never_shift_the_watermark(
        self, columnar_env, tmp_path
    ):
        """Crash-mid-append bytes are invisible to the cursor: a later
        append starts on a FRESH line (never merged into one undecodable
        hybrid with the torn bytes), the follower neither counts nor
        delivers them, and the recovery sweep's trim — which rewrites
        the tail without the torn line — cannot shift consumed indices
        under a live watermark and skip the next event."""
        app_id = _new_app(columnar_env, "fapp")
        le = columnar_env.get_l_events()
        f = self._follower()
        f.poll()
        f.commit()
        le.insert_batch([_rate(1, 1, 3.0, "t1")], app_id)
        assert [e.event_id for e in f.poll()] == ["t1"]
        f.commit()
        stream = os.path.join(
            str(tmp_path / "events"), "pio_events", f"app_{app_id}", "default"
        )
        with open(os.path.join(stream, "tail.jsonl"), "ab") as fh:
            fh.write(b'{"event": "rate", "entityI')  # kill -9 mid-append
        le.insert_batch([_rate(1, 2, 4.0, "t2")], app_id)  # must not merge
        assert [e.event_id for e in f.poll()] == ["t2"]
        f.commit()
        # restart repair trims the torn line; the cursor (which counted
        # decodable lines only) resumes exactly — no skip, no re-deliver
        report = {"quarantined": [], "tornTailLines": 0}
        le._repair_tail(stream, report)
        assert report["tornTailLines"] == 1
        le.insert_batch([_rate(1, 3, 5.0, "t3")], app_id)
        assert [e.event_id for e in f.poll()] == ["t3"]

    def test_compaction_is_exactly_once(self, columnar_env):
        app_id = _new_app(columnar_env, "fapp")
        le = columnar_env.get_l_events()
        pe = columnar_env.get_p_events()
        f = self._follower()
        f.poll()
        f.commit()
        le.insert_batch([_rate(1, 1, 3.0, "c1"), _rate(1, 2, 4.0, "c2")], app_id)
        assert [e.event_id for e in f.poll()] == ["c1", "c2"]
        f.commit()
        assert pe.compact(app_id) == 2
        assert f.poll() == []  # consumed tail moved into a segment: no refold
        f.commit()
        le.insert_batch([_rate(1, 3, 5.0, "c3")], app_id)
        assert [e.event_id for e in f.poll()] == ["c3"]

    def test_restart_resumes_exactly_once(self, columnar_env):
        app_id = _new_app(columnar_env, "fapp")
        le = columnar_env.get_l_events()
        f = self._follower()
        f.poll()
        f.commit()
        le.insert_batch([_rate(1, 1, 3.0, "r1")], app_id)
        assert [e.event_id for e in f.poll()] == ["r1"]
        f.commit()
        le.insert_batch([_rate(1, 2, 4.0, "r2")], app_id)
        f2 = self._follower()  # fresh process: same persisted watermark
        assert [e.event_id for e in f2.poll()] == ["r2"]
        f2.commit()
        assert self._follower().poll() == []

    def test_compaction_while_offline_with_partial_tail(self, columnar_env):
        """The hard case: some tail lines consumed, process stops, a
        compaction seals the WHOLE tail (consumed + unconsumed) into an
        explicit-id segment, process restarts — only the unconsumed
        suffix streams."""
        app_id = _new_app(columnar_env, "fapp")
        le = columnar_env.get_l_events()
        pe = columnar_env.get_p_events()
        f = self._follower()
        f.poll()
        f.commit()
        le.insert_batch([_rate(1, 1, 3.0, "p1"), _rate(1, 2, 4.0, "p2")], app_id)
        assert len(f.poll()) == 2
        f.commit()
        le.insert_batch([_rate(1, 3, 5.0, "p3"), _rate(1, 4, 2.0, "p4")], app_id)
        pe.compact(app_id)
        f2 = self._follower()
        assert [e.event_id for e in f2.poll()] == ["p3", "p4"]
        f2.commit()
        assert self._follower().poll() == []

    def test_uncommitted_poll_redelivers_after_restart(self, columnar_env):
        """Crash between poll and commit = at-least-once, never skipped."""
        app_id = _new_app(columnar_env, "fapp")
        le = columnar_env.get_l_events()
        f = self._follower()
        f.poll()
        f.commit()
        le.insert_batch([_rate(1, 1, 3.0, "u1")], app_id)
        assert [e.event_id for e in f.poll()] == ["u1"]
        # no commit — the "crash"
        f2 = self._follower()
        assert [e.event_id for e in f2.poll()] == ["u1"]

    def test_rollback_redelivers_in_process(self, columnar_env):
        """A poll whose batch could not be applied rolls back WITHOUT a
        restart: the next poll re-delivers from the committed watermark."""
        app_id = _new_app(columnar_env, "fapp")
        le = columnar_env.get_l_events()
        f = self._follower()
        f.poll()
        f.commit()
        le.insert_batch([_rate(1, 1, 3.0, "rb1")], app_id)
        assert [e.event_id for e in f.poll()] == ["rb1"]
        f.rollback()
        assert [e.event_id for e in f.poll()] == ["rb1"]
        f.commit()
        assert f.poll() == []

    def test_stream_recreate_resets_cursor(self, columnar_env):
        app_id = _new_app(columnar_env, "fapp")
        le = columnar_env.get_l_events()
        f = self._follower()
        f.poll()
        f.commit()
        le.remove(app_id)
        le.init(app_id)
        le.insert_batch([_rate(1, 1, 3.0, "n1")], app_id)
        # recreated stream: cursor resets (fresh anchor at the new end,
        # not a bogus resume that would mis-skip the regrown tail)
        f2 = self._follower()
        f2.poll()
        f2.commit()
        le.insert_batch([_rate(1, 2, 4.0, "n2")], app_id)
        assert [e.event_id for e in f2.poll()] == ["n2"]

    def test_unsupported_store_raises(self, memory_storage_env):
        from predictionio_tpu.online.follower import (
            FollowerUnsupportedError,
            TailFollower,
        )

        _new_app(memory_storage_env, "mapp")
        with pytest.raises(FollowerUnsupportedError):
            TailFollower("mapp")

    # ---------------------------------------------------- byte-offset cursor
    def test_poll_reads_o_delta_via_byte_offset(
        self, columnar_env, tmp_path, monkeypatch
    ):
        """ISSUE 8 satellite: a same-generation poll seeks to the
        persisted ``tail_bytes`` offset and scans ONLY the appended
        delta — never re-decoding the consumed tail — and the cursor's
        offset tracks the file size exactly."""
        from predictionio_tpu.data.storage import columnar as col

        app_id = _new_app(columnar_env, "fapp")
        le = columnar_env.get_l_events()
        f = self._follower()
        f.poll()
        f.commit()
        le.insert_batch([_rate(1, i, 3.0, f"base{i}") for i in range(200)], app_id)
        assert len(f.poll()) == 200
        f.commit()
        stream = os.path.join(
            str(tmp_path / "events"), "pio_events", f"app_{app_id}", "default"
        )
        tail = os.path.join(stream, "tail.jsonl")
        cursor = json.load(open(f._path))
        assert cursor["tail_bytes"] == os.path.getsize(tail)
        assert cursor["tail_lines"] == 200
        assert isinstance(cursor["tail_crc"], int)

        scans = []
        real_scan = col._ColumnarEvents._scan_tail_bytes

        def spy(path, offset):
            out = real_scan(path, offset)
            scans.append((offset, len(out[0])))
            return out

        monkeypatch.setattr(col._ColumnarEvents, "_scan_tail_bytes", staticmethod(spy))
        le.insert_batch([_rate(2, 1, 4.0, "d1"), _rate(2, 2, 5.0, "d2")], app_id)
        assert [e.event_id for e in f.poll()] == ["d1", "d2"]
        f.commit()
        # the scan started at the committed offset and decoded only the
        # two appended lines — O(delta), not O(tail)
        assert scans, "poll never scanned the tail"
        offset, n_decoded = scans[-1]
        assert offset == cursor["tail_bytes"] > 0
        assert n_decoded == 2

    def test_offset_mismatch_falls_back_to_line_count(
        self, columnar_env, tmp_path
    ):
        """A rewrite that shifts bytes under the persisted offset (the
        recovery trim's failure mode) is caught — by size, boundary, or
        checksum — and the poll falls back to the decodable-line-count
        scan with exactly-once semantics intact."""
        app_id = _new_app(columnar_env, "fapp")
        le = columnar_env.get_l_events()
        f = self._follower()
        f.poll()
        f.commit()
        le.insert_batch(
            [_rate(1, 1, 3.0, "m1"), _rate(1, 2, 4.0, "m2")], app_id
        )
        assert len(f.poll()) == 2
        f.commit()
        stream = os.path.join(
            str(tmp_path / "events"), "pio_events", f"app_{app_id}", "default"
        )
        tail = os.path.join(stream, "tail.jsonl")
        # same length, different bytes inside the CRC window: only the
        # checksum can catch this
        raw = open(tail, "rb").read()
        mutated = raw[:-10] + b"X" * 9 + b"\n"
        assert len(mutated) == len(raw)
        open(tail, "wb").write(mutated)
        # fallback: the mutated final line no longer decodes, so the
        # line-count scan sees 1 decodable line vs 2 consumed — nothing
        # is delivered twice and nothing crashes
        assert f.poll() == []
        f.commit()
        le.insert_batch([_rate(1, 3, 5.0, "m3")], app_id)
        assert [e.event_id for e in f.poll()] == ["m3"]
        f.commit()

    def test_truncated_tail_falls_back_cleanly(self, columnar_env, tmp_path):
        """File shorter than the persisted offset (out-of-band trim /
        reset): the poll must fall back, deliver nothing stale, and
        resume streaming fresh appends."""
        app_id = _new_app(columnar_env, "fapp")
        le = columnar_env.get_l_events()
        f = self._follower()
        f.poll()
        f.commit()
        le.insert_batch([_rate(1, 1, 3.0, "s1")], app_id)
        assert len(f.poll()) == 1
        f.commit()
        stream = os.path.join(
            str(tmp_path / "events"), "pio_events", f"app_{app_id}", "default"
        )
        open(os.path.join(stream, "tail.jsonl"), "wb").close()  # truncate
        assert f.poll() == []
        f.commit()
        le.insert_batch([_rate(1, 2, 4.0, "s2")], app_id)
        assert [e.event_id for e in f.poll()] == ["s2"]

    def test_lag_reports_consumed_byte_offset(self, columnar_env):
        app_id = _new_app(columnar_env, "fapp")
        le = columnar_env.get_l_events()
        f = self._follower()
        f.poll()
        f.commit()
        le.insert_batch([_rate(1, 1, 3.0, "g1")], app_id)
        f.poll()
        f.commit()
        lag = f.lag()
        assert lag["tailLinesConsumed"] == lag["tailLinesStore"]
        assert isinstance(lag["tailBytesConsumed"], int)
        assert lag["tailBytesConsumed"] > 0


# ---------------------------------------------------------------------------
# Fold-in solver vs closed form
# ---------------------------------------------------------------------------


class TestFollowerUnderSchedulerAndBulk:
    """ISSUE 12 satellite: the tail follower stays exactly-once while
    the BACKGROUND compaction scheduler bumps generations underneath it
    and the bulk route lands explicit-id chunk segments concurrently —
    the write-side pressure the cursor's re-anchor was built for."""

    def _chunk(self, ids):
        from predictionio_tpu.data.ingest import parse_chunk

        lines = [
            (
                json.dumps(
                    {
                        "eventId": eid,
                        "event": "rate",
                        "entityType": "user",
                        "entityId": f"u{k % 5}",
                        "targetEntityType": "item",
                        "targetEntityId": f"i{k % 9}",
                        "properties": {"rating": float(1 + k % 5)},
                    }
                )
                + "\n"
            ).encode()
            for k, eid in enumerate(ids)
        ]
        return parse_chunk(lines, 0).chunk

    def test_deterministic_interleave_is_exactly_once(self, columnar_env):
        from predictionio_tpu.data.storage.compaction import (
            CompactionConfig,
            CompactionScheduler,
        )

        app_id = _new_app(Storage, "schedapp")
        le = Storage.get_l_events()
        pe = Storage.get_p_events()
        le.init(app_id)
        _, cursor = pe.tail_follow(app_id)  # anchor at end
        sched = CompactionScheduler(
            le, CompactionConfig(tail_bytes_high=1, min_interval_s=0.0)
        )
        expected: list[str] = []
        seen: list[str] = []
        for rnd in range(12):
            tail_ids = [f"t{rnd}-{i}" for i in range(4)]
            for i, eid in enumerate(tail_ids):
                le.insert_dedup(_rate(i, i, 3.0, eid=eid), app_id)
            bulk_ids = [f"b{rnd}-{i}" for i in range(6)]
            le.ingest_chunk(self._chunk(bulk_ids), app_id)
            expected += tail_ids + bulk_ids
            if rnd % 3 == 1:
                assert sched.sweep_once() >= 1  # generation bump
            events, cursor = pe.tail_follow(app_id, cursor=cursor)
            seen += [e.event_id for e in events]
        events, cursor = pe.tail_follow(app_id, cursor=cursor)
        seen += [e.event_id for e in events]
        assert sorted(seen) == sorted(expected)  # no loss, no dups
        assert sched.to_json()["compactions"] >= 4

    def test_threaded_writers_and_scheduler_stay_exactly_once(
        self, columnar_env
    ):
        import threading

        from predictionio_tpu.data.storage.compaction import (
            CompactionConfig,
            CompactionScheduler,
        )

        app_id = _new_app(Storage, "schedapp2")
        le = Storage.get_l_events()
        pe = Storage.get_p_events()
        le.init(app_id)
        _, cursor = pe.tail_follow(app_id)
        sched = CompactionScheduler(
            le,
            CompactionConfig(
                interval_s=0.02, tail_bytes_high=256, min_interval_s=0.0
            ),
        )
        stop = threading.Event()
        written: list[str] = []
        lock = threading.Lock()

        def tail_writer():
            i = 0
            while not stop.is_set() and i < 150:
                eid = f"tw-{i:04d}"
                le.insert_dedup(_rate(i, i, 2.0, eid=eid), app_id)
                with lock:
                    written.append(eid)
                i += 1
                time.sleep(0.002)

        def bulk_writer():
            i = 0
            while not stop.is_set() and i < 30:
                ids = [f"bw-{i:03d}-{j}" for j in range(8)]
                le.ingest_chunk(self._chunk(ids), app_id)
                with lock:
                    written.extend(ids)
                i += 1
                time.sleep(0.005)

        threads = [
            threading.Thread(target=tail_writer, daemon=True),
            threading.Thread(target=bulk_writer, daemon=True),
        ]
        sched.start()
        for t in threads:
            t.start()
        seen: list[str] = []
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            events, cursor = pe.tail_follow(app_id, cursor=cursor)
            seen += [e.event_id for e in events]
            if all(not t.is_alive() for t in threads):
                break
            time.sleep(0.01)
        stop.set()
        for t in threads:
            t.join(timeout=10)
        sched.stop()
        # final drain polls (a compaction may land between the last poll
        # and the writer exit)
        for _ in range(3):
            events, cursor = pe.tail_follow(app_id, cursor=cursor)
            seen += [e.event_id for e in events]
        with lock:
            want = sorted(written)
        assert sorted(seen) == want, (
            f"lost={set(want) - set(seen)} dup="
            f"{[e for e in seen if seen.count(e) > 1][:5]}"
        )
        assert sched.to_json()["compactions"] >= 1


class TestFoldinSolver:
    def test_explicit_matches_normal_equations(self):
        from predictionio_tpu.online.foldin import foldin_rows

        rng = np.random.default_rng(0)
        Y = rng.standard_normal((60, 8)).astype(np.float32)
        ix, vs = [3, 7, 11, 20], [4.0, 2.0, 5.0, 1.0]
        reg = 0.07
        x = foldin_rows(Y, [(ix, vs)], reg=reg)[0]
        Ys = Y[ix]
        A = Ys.T @ Ys + reg * len(ix) * np.eye(8, dtype=np.float32)
        ref = np.linalg.solve(A, Ys.T @ np.asarray(vs, np.float32))
        np.testing.assert_allclose(x, ref, rtol=1e-4, atol=1e-5)

    def test_prior_anchor_pulls_toward_old_row(self):
        from predictionio_tpu.online.foldin import foldin_rows

        rng = np.random.default_rng(1)
        Y = rng.standard_normal((40, 8)).astype(np.float32)
        prior = rng.standard_normal(8).astype(np.float32)
        ix, vs = [1, 2], [5.0, 5.0]
        free = foldin_rows(Y, [(ix, vs)], reg=0.1)[0]
        anchored = foldin_rows(
            Y, [(ix, vs)], reg=0.1,
            priors=prior[None], prior_weights=np.asarray([1e6]),
        )[0]
        assert np.linalg.norm(anchored - prior) < np.linalg.norm(free - prior)

    def test_implicit_adds_gramian(self):
        from predictionio_tpu.online.foldin import foldin_rows, gram_yty

        rng = np.random.default_rng(2)
        Y = rng.standard_normal((30, 4)).astype(np.float32)
        yty = gram_yty(Y)
        ix, vs = [0, 5], [1.0, 2.0]
        alpha = 1.5
        x = foldin_rows(
            Y, [(ix, vs)], reg=0.1, implicit=True, alpha=alpha, yty=yty
        )[0]
        Ys = Y[ix]
        A = (
            yty
            + (Ys.T * (alpha * np.asarray(vs))) @ Ys
            + 0.1 * len(ix) * np.eye(4, dtype=np.float32)
        )
        b = Ys.T @ (1.0 + alpha * np.asarray(vs, np.float32))
        np.testing.assert_allclose(x, np.linalg.solve(A, b), rtol=1e-3,
                                   atol=1e-4)

    def test_batched_and_padded_rows_agree_with_single(self):
        from predictionio_tpu.online.foldin import foldin_rows

        rng = np.random.default_rng(3)
        Y = rng.standard_normal((50, 8)).astype(np.float32)
        entries = [
            ([1, 2, 3], [1.0, 2.0, 3.0]),
            ([4], [5.0]),
            (list(range(20)), [1.0] * 20),
        ]
        batched = foldin_rows(Y, entries, reg=0.05)
        for i, e in enumerate(entries):
            single = foldin_rows(Y, [e], reg=0.05)[0]
            np.testing.assert_allclose(batched[i], single, rtol=1e-4,
                                       atol=1e-5)


# ---------------------------------------------------------------------------
# Incremental IVF maintenance
# ---------------------------------------------------------------------------


class TestIncrementalIVF:
    def _catalog(self, n=400, dim=16, seed=4):
        rng = np.random.default_rng(seed)
        x = rng.standard_normal((n, dim)).astype(np.float32)
        return x / np.linalg.norm(x, axis=1, keepdims=True), rng

    def test_update_then_full_probe_is_exact(self):
        import jax.numpy as jnp

        from predictionio_tpu.ops import ivf

        X, rng = self._catalog()
        index, info = ivf.build_ivf(X, nlist=8, seed=0, iters=4)
        rt = ivf.AnnRuntime(index, nprobe=8, build_info=info)
        moved = np.asarray([1, 17, 399])
        vec = rng.standard_normal((3, 16)).astype(np.float32)
        vec /= np.linalg.norm(vec, axis=1, keepdims=True)
        rt.update_items(moved, vec, total_items=400)
        new = rng.standard_normal((6, 16)).astype(np.float32)
        new /= np.linalg.norm(new, axis=1, keepdims=True)
        rt.update_items(np.arange(400, 406), new, total_items=406)
        X2 = np.concatenate([X, new])
        X2[moved] = vec
        q = rng.standard_normal((64, 16)).astype(np.float32)
        ids, _ = ivf.ivf_topk_batch(
            jnp.asarray(q), rt.index, 10, rt.index.nlist
        )
        exact = np.argsort(-(q @ X2.T), axis=1, kind="stable")[:, :10]
        assert np.array_equal(np.asarray(ids), exact)

    def test_capacity_steps_not_per_item(self):
        from predictionio_tpu.ops import ivf

        X, rng = self._catalog(n=100)
        index, info = ivf.build_ivf(X, nlist=4, seed=0, iters=2)
        rt = ivf.AnnRuntime(index, nprobe=4, build_info=info)
        v = rng.standard_normal((1, 16)).astype(np.float32)
        rt.update_items(np.asarray([100]), v, total_items=101)
        cap = rt.index.num_items
        assert cap >= 101 and cap % 1024 == 0
        rt.update_items(np.asarray([101]), v, total_items=102)
        assert rt.index.num_items == cap  # no retrace-forcing growth

    def test_spill_when_target_cluster_full(self):
        from predictionio_tpu.ops import ivf

        X, rng = self._catalog(n=64)
        index, info = ivf.build_ivf(X, nlist=4, seed=0, iters=2)
        rt = ivf.AnnRuntime(index, nprobe=4, build_info=info)
        # hammer one region with new items until something must spill or
        # the width grows — either way every item stays retrievable
        target = np.asarray(index.centroids)[0]
        n_new = 3 * index.slab_width
        vec = np.tile(target, (n_new, 1)).astype(np.float32)
        vec /= np.linalg.norm(vec, axis=1, keepdims=True)
        rt.update_items(np.arange(64, 64 + n_new), vec, total_items=64 + n_new)
        ids = np.asarray(rt.index.slab_ids)
        live = ids[ids < rt.index.num_items]
        assert live.size == 64 + n_new  # nothing dropped
        assert np.unique(live).size == live.size  # nothing duplicated


# ---------------------------------------------------------------------------
# QueryService integration (recommendation template)
# ---------------------------------------------------------------------------


@pytest.fixture()
def online_service(columnar_env):
    """Trained recommendation engine on a columnar store + QueryService
    with cache and manual-cadence online learning."""
    from predictionio_tpu.controller import local_context
    from predictionio_tpu.online import OnlineConfig
    from predictionio_tpu.serving import CacheConfig
    from predictionio_tpu.workflow import load_engine_variant, run_train
    from predictionio_tpu.workflow.serving import QueryService

    app_id = _new_app(columnar_env, "ol-app")
    rng = np.random.default_rng(5)
    columnar_env.get_l_events().insert_batch(
        [
            _rate(u, i, (u + i) % 5 + 1)
            for u, i in zip(rng.integers(0, 30, 600), rng.integers(0, 60, 600))
        ],
        app_id,
    )
    variant = load_engine_variant(
        {
            "id": "ol-eng",
            "version": "1",
            "engineFactory": "predictionio_tpu.templates."
            "recommendation:engine_factory",
            "datasource": {"params": {"appName": "ol-app"}},
            "algorithms": [
                {
                    "name": "als",
                    "params": {"rank": 8, "numIterations": 2,
                               "lambda": 0.05, "seed": 5},
                }
            ],
        }
    )
    run_train(variant, local_context())
    qs = QueryService(
        variant,
        cache=CacheConfig(result_cache=True, result_cache_ttl_s=300.0),
        online=OnlineConfig(enabled=True, interval_s=600.0),  # manual folds
    )
    yield columnar_env, app_id, qs
    qs.close()


def _query(qs, user, num=4):
    return qs.dispatch("POST", "/queries.json", {}, {"user": user, "num": num})


class TestQueryServiceOnline:
    def test_fresh_user_visible_after_one_fold(self, online_service):
        Storage, app_id, qs = online_service
        assert _query(qs, "fresh-u").body == {"itemScores": []}
        Storage.get_l_events().insert_batch(
            [_rate("fresh-u", 1, 5.0, "q1"), _rate("fresh-u", 2, 5.0, "q2")],
            app_id,
        )
        r = qs.dispatch("POST", "/online/fold.json", {}, None)
        # the daemon's own first cycle may have won the race to these
        # events — either way, ONE fold (manual or daemon) applied them
        assert r.status == 200
        scores = _query(qs, "fresh-u").body["itemScores"]
        assert len(scores) == 4
        online = qs.stats_json()["online"]
        assert online["eventsFolded"] >= 2
        assert online["updatesApplied"] >= 1
        assert online["eventToVisibleSeconds"]["last"] is not None

    def test_new_item_ranked_for_its_rater(self, online_service):
        Storage, app_id, qs = online_service
        Storage.get_l_events().insert_batch(
            [_rate("3", "hot-new-item", 5.0, "ni1")], app_id
        )
        qs.dispatch("POST", "/online/fold.json", {}, None)
        items = [s["item"] for s in _query(qs, "3", num=60).body["itemScores"]]
        assert "hot-new-item" in items

    def test_partial_swap_invalidates_only_touched_scopes(
        self, online_service
    ):
        Storage, app_id, qs = online_service
        _query(qs, "1")
        _query(qs, "2")
        stats0 = qs.stats_json()["cache"]
        assert stats0["misses"] == 2
        Storage.get_l_events().insert_batch(
            [_rate("1", 7, 5.0, "sc1")], app_id
        )
        qs.dispatch("POST", "/online/fold.json", {}, None)
        cache = qs.stats_json()["cache"]
        # per-scope bumps only, NEVER the conservative full flush
        assert cache["invalidations"]["full"] == 0
        assert cache["invalidations"]["scope"] >= 1
        _query(qs, "1")  # invalidated: recomputed
        _query(qs, "2")  # untouched scope: served from cache
        cache = qs.stats_json()["cache"]
        assert cache["hits"] == 1
        assert cache["misses"] == 3

    def test_fold_is_idempotent_under_redelivery(self, online_service):
        """Re-solving the same accumulated history twice lands on the
        same factors — the property that makes the at-least-once crash
        window safe."""
        Storage, app_id, qs = online_service
        Storage.get_l_events().insert_batch(
            [_rate("idem-u", 3, 4.0, "i1")], app_id
        )
        qs.dispatch("POST", "/online/fold.json", {}, None)
        pairs, _ = qs.snapshot_pairs()
        algo, model = pairs[0]
        row1 = np.array(
            model.user_factors[model.user_index["idem-u"]], copy=True
        )
        # redeliver the same event body (same id — the accumulator's
        # latest-wins makes it a no-op history change) and re-fold
        deltas_state = serving_state(model).online["users"]["idem-u"].copy()
        from predictionio_tpu.online.types import EventDelta

        upd = algo.online_foldin(
            model,
            [EventDelta("rate", "idem-u", "3", 1, 4.0)],
            {"appName": "ol-app"},
            qs.online_config,
        )
        qs.apply_online_update([(0, upd)])
        row2 = np.asarray(model.user_factors[model.user_index["idem-u"]])
        assert serving_state(model).online["users"]["idem-u"] == deltas_state
        np.testing.assert_allclose(row1, row2, rtol=1e-5, atol=1e-6)

    def test_reload_supersedes_online_generation(self, online_service):
        from predictionio_tpu.online.types import OnlineUpdate

        Storage, app_id, qs = online_service
        _, gen = qs.snapshot_pairs()
        qs.reload()
        res = qs.apply_online_update(
            [(0, OnlineUpdate(user_ids=["1"],
                              user_rows=np.zeros((1, 8), np.float32)))],
            generation=gen,
        )
        assert res["applied"] is False
        assert "superseded" in res["reason"]

    def test_superseded_fold_rolls_back_watermark(self, online_service):
        """Rows solved against a superseded generation are dropped — but
        the watermark must NOT advance past their events: the next cycle
        re-delivers them against the current generation instead of
        losing them until the next retrain."""
        Storage, app_id, qs = online_service
        Storage.get_l_events().insert_batch(
            [_rate("rb-u", 4, 5.0, "rbw1")], app_id
        )
        real = qs.apply_online_update
        qs.apply_online_update = lambda updates, generation=None: {
            "applied": False, "reason": "superseded generation"
        }
        try:
            res = qs.online.fold_now()
        finally:
            qs.apply_online_update = real
        assert res.get("requeued") is True and "superseded" in res["reason"]
        res2 = qs.online.fold_now()  # re-delivery folds for real
        assert res2["applied"] is True
        assert len(_query(qs, "rb-u").body["itemScores"]) == 4

    def test_exception_mid_fold_rolls_back_watermark(self, online_service):
        """A transient apply/hook error must not advance the watermark:
        the failed batch re-delivers on the next cycle instead of being
        silently skipped until the next retrain."""
        Storage, app_id, qs = online_service
        Storage.get_l_events().insert_batch(
            [_rate("ex-u", 4, 5.0, "exw1")], app_id
        )
        real = qs.apply_online_update

        def boom(updates, generation=None):
            raise RuntimeError("transient apply failure")

        qs.apply_online_update = boom
        try:
            with pytest.raises(RuntimeError):
                qs.online.fold_now()
        finally:
            qs.apply_online_update = real
        res = qs.online.fold_now()  # re-delivery folds for real
        assert res["applied"] is True
        assert len(_query(qs, "ex-u").body["itemScores"]) == 4

    def test_status_and_route_wiring(self, online_service):
        _, _, qs = online_service
        assert qs.status_json()["online"] is True
        assert "online" in qs.stats_json()
        assert qs.dispatch("POST", "/online/fold.json", {}, None).status == 200


@pytest.fixture()
def sharded_online_service(columnar_env):
    """Same harness as ``online_service`` but serving under
    ``--shard-factors --pin-model``: factor tables live as per-device
    shards across the 8-way host mesh while fold-ins land."""
    from predictionio_tpu.controller import local_context
    from predictionio_tpu.online import OnlineConfig
    from predictionio_tpu.serving import CacheConfig
    from predictionio_tpu.workflow import load_engine_variant, run_train
    from predictionio_tpu.workflow.serving import QueryService

    app_id = _new_app(columnar_env, "ols-app")
    rng = np.random.default_rng(6)
    columnar_env.get_l_events().insert_batch(
        [
            _rate(u, i, (u + i) % 5 + 1)
            for u, i in zip(rng.integers(0, 30, 600), rng.integers(0, 60, 600))
        ],
        app_id,
    )
    variant = load_engine_variant(
        {
            "id": "ols-eng",
            "version": "1",
            "engineFactory": "predictionio_tpu.templates."
            "recommendation:engine_factory",
            "datasource": {"params": {"appName": "ols-app"}},
            "algorithms": [
                {
                    "name": "als",
                    "params": {"rank": 8, "numIterations": 2,
                               "lambda": 0.05, "seed": 5},
                }
            ],
        }
    )
    run_train(variant, local_context())
    qs = QueryService(
        variant,
        cache=CacheConfig(pin_model=True, shard_factors=True),
        online=OnlineConfig(enabled=True, interval_s=600.0),  # manual folds
    )
    yield columnar_env, app_id, qs
    qs.close()


class TestOnlineUnderShardFactors:
    """ISSUE 9 online-compose satellite: ``apply_online_update`` row
    scatters must route each touched row to the device OWNING its
    shard, and cold-start fold-ins must keep the tables sharded."""

    def test_fresh_user_folds_into_sharded_tables(
        self, sharded_online_service
    ):
        from jax.sharding import NamedSharding

        Storage, app_id, qs = sharded_online_service
        pairs, _ = qs.snapshot_pairs()
        _algo, model = pairs[0]
        shards = serving_state(model).shards
        assert shards is not None and shards.num_shards == 8
        assert _query(qs, "fresh-su").body == {"itemScores": []}
        Storage.get_l_events().insert_batch(
            [_rate("fresh-su", 1, 5.0, "s1"), _rate("fresh-su", 2, 5.0, "s2")],
            app_id,
        )
        r = qs.dispatch("POST", "/online/fold.json", {}, None)
        assert r.status == 200
        scores = _query(qs, "fresh-su").body["itemScores"]
        assert len(scores) == 4
        # the table is STILL model-sharded after the fold (the scatter
        # routed rows to their owner shard instead of gathering host-
        # side), and the logical row count advanced with the cold start
        s = model.user_factors.sharding
        assert isinstance(s, NamedSharding) and s.spec[0] == "model"
        assert shards.rows["user"] > 30  # trained users + the cold start
        uidx = model.user_index["fresh-su"]
        assert uidx < shards.rows["user"]
        row = np.asarray(model.user_factors)[uidx]
        assert np.abs(row).sum() > 0  # the solved row actually landed

    def test_known_row_update_lands_on_owner_shard(
        self, sharded_online_service
    ):
        Storage, app_id, qs = sharded_online_service
        pairs, _ = qs.snapshot_pairs()
        _algo, model = pairs[0]
        before = np.asarray(model.user_factors).copy()
        uidx = model.user_index["3"]
        Storage.get_l_events().insert_batch(
            [_rate("3", 7, 5.0, "ks1")], app_id
        )
        qs.dispatch("POST", "/online/fold.json", {}, None)
        after = np.asarray(model.user_factors)
        assert not np.allclose(before[uidx], after[uidx])
        # untouched OTHER-shard rows are bit-identical: only the touched
        # row moved (item side may move too; user table is the probe)
        untouched = [i for i in range(30) if i != uidx]
        np.testing.assert_array_equal(
            before[untouched], after[untouched]
        )


# ---------------------------------------------------------------------------
# Streaming trainer unit
# ---------------------------------------------------------------------------


class TestStreamingTrainer:
    def test_sgd_step_reduces_loss_and_keeps_norms(self):
        from predictionio_tpu.online.trainer import sgd_step

        rng = np.random.default_rng(0)
        U = rng.standard_normal((20, 16)).astype(np.float32)
        I = rng.standard_normal((40, 16)).astype(np.float32)
        U /= np.linalg.norm(U, axis=1, keepdims=True)
        I /= np.linalg.norm(I, axis=1, keepdims=True)
        u_idx = np.asarray([1, 2, 3, 4])
        i_idx = np.asarray([3, 4, 5, 6])
        losses = []
        for _ in range(15):
            uu, nu, ui, ni, loss = sgd_step(U, I, u_idx, i_idx, 0.5, 0.1)
            U[uu] = nu
            I[ui] = ni
            losses.append(loss)
        assert np.isfinite(losses).all()
        assert losses[-1] < losses[0]
        np.testing.assert_allclose(
            np.linalg.norm(U[u_idx], axis=1), 1.0, atol=1e-4
        )

    def test_duplicate_ids_accumulate_gradients(self):
        from predictionio_tpu.online.trainer import sgd_step

        rng = np.random.default_rng(1)
        U = rng.standard_normal((10, 8)).astype(np.float32)
        I = rng.standard_normal((10, 8)).astype(np.float32)
        uu, nu, ui, ni, _ = sgd_step(
            U, I, np.asarray([2, 2]), np.asarray([1, 3]), 0.1, 0.1
        )
        assert list(uu) == [2] and len(nu) == 1  # one row out per id
        assert sorted(ui) == [1, 3]

    def _model(self):
        from predictionio_tpu.data.aggregator import BiMap

        class M:
            pass

        rng = np.random.default_rng(7)
        m = M()
        m.user_index = BiMap({"u0": 0, "u1": 1})
        m.item_index = BiMap({"i0": 0, "i1": 1, "i2": 2})
        m.user_vecs = rng.standard_normal((2, 8)).astype(np.float32)
        m.item_vecs = rng.standard_normal((3, 8)).astype(np.float32)
        m.seen = {}
        return m

    def test_superseded_cold_start_abandons_item_cleanly(self):
        """When a /reload superseded the trainer's generation the
        cold-start apply is rejected — the new ids never entered the
        index, so the trainer must abandon the work item (the rebind is
        about to replace it) instead of crashing on a KeyError."""
        from predictionio_tpu.online.trainer import StreamingTrainer

        calls = []

        def apply(upd):
            calls.append(upd)
            return {"applied": False, "reason": "superseded generation"}

        t = StreamingTrainer(self._model(), apply, batch_size=4)
        try:
            t._train_one([("brand-new-user", "i0")], newest_us=123)
        finally:
            t.stop()
        assert len(calls) == 1  # cold start attempted, then abandoned
        assert t.steps == 0

    def test_applied_updates_carry_newest_us_for_freshness(self):
        """Streamed updates thread the batch's newest event time through
        to the runner's apply bridge, which records event->visible
        freshness for trainer-only (two-tower) deployments too."""
        from predictionio_tpu.online.trainer import StreamingTrainer

        calls = []

        def apply(upd):
            calls.append(upd)
            return {"applied": True}

        t = StreamingTrainer(self._model(), apply, batch_size=4)
        try:
            t._train_one([("u0", "i1"), ("u1", "i2")], newest_us=456_000_000)
        finally:
            t.stop()
        assert calls and all(
            u.info.get("newestUs") == 456_000_000 for u in calls
        )
        assert t.steps == 1


# ---------------------------------------------------------------------------
# Satellites: feedback eventId, strict-off defaults
# ---------------------------------------------------------------------------


class TestSatellites:
    def test_feedback_events_carry_deterministic_event_ids(
        self, memory_storage_env
    ):
        """The feedback worker's writes must be retry-safe under the
        event store's client-id dedup: the queued wire payload carries a
        deterministic eventId derived from the prediction id."""
        from predictionio_tpu.workflow.serving import QueryService

        payload = {"itemScores": []}
        svc = object.__new__(QueryService)  # no full deploy needed
        import queue as _q
        import threading as _t

        from predictionio_tpu.workflow.serving import FeedbackConfig

        svc.feedback = FeedbackConfig(
            event_server_url="http://127.0.0.1:1", access_key="k"
        )
        svc._feedback_queue = _q.Queue()
        svc._lock = _t.Lock()
        svc.feedback_dropped = 0
        svc._send_feedback({"user": "1"}, payload, "prid123")
        _, event = svc._feedback_queue.get_nowait()
        assert event["eventId"] == "pio_fb_prid123"
        # deterministic: same prId -> same eventId (a worker retry of
        # the same prediction dedups server-side)
        svc._send_feedback({"user": "1"}, payload, "prid123")
        _, again = svc._feedback_queue.get_nowait()
        assert again["eventId"] == event["eventId"]

    def test_online_types_import_no_jax(self):
        import subprocess
        import sys

        probe = (
            "import sys; import predictionio_tpu.online; "
            "sys.exit(1 if any(m == 'jax' or m.startswith('jax.') "
            "for m in sys.modules) else 0)"
        )
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        proc = subprocess.run(
            [sys.executable, "-c", probe], cwd=repo, capture_output=True
        )
        assert proc.returncode == 0, proc.stderr.decode()[-500:]

    def test_latest_wins_matches_training_rule(self):
        from predictionio_tpu.online.types import EventDelta, latest_wins

        deltas = [
            EventDelta("rate", "u", "i", 10, 2.0),
            EventDelta("rate", "u", "i", 20, 1.0),  # later wins
            EventDelta("rate", "u", "j", 20, 3.0),
            EventDelta("rate", "u", "j", 20, 5.0),  # tie -> higher
        ]
        out = latest_wins(deltas)
        assert out[("u", "i")] == (20, 1.0)
        assert out[("u", "j")] == (20, 5.0)
