"""Query-path caching & coalescing (predictionio_tpu.serving.cache +
QueryService wiring) — ISSUE 4.

The correctness-under-concurrency satellite: singleflight fans one
computation (or its exception) out to N waiters; event-driven
invalidation beats in-flight fills (no stale resurrect); a ``/reload``
to a new model generation never serves old-generation entries; and the
cache-off configuration leaves the serving path untouched.
"""

from __future__ import annotations

import json
import threading
import time

import numpy as np
import pytest

from predictionio_tpu.serving.cache import (
    CacheConfig,
    CacheStats,
    ResultCache,
    Singleflight,
    canonical_key,
    extract_scope,
    scopes_from_events,
)
from predictionio_tpu.templates.retrieval import serving_state

# ---------------------------------------------------------------------------
# Unit: keys, config, stats
# ---------------------------------------------------------------------------


class TestKeysAndConfig:
    def test_canonical_key_is_order_independent(self):
        assert canonical_key({"user": "1", "num": 4}) == canonical_key(
            {"num": 4, "user": "1"}
        )
        assert canonical_key({"a": 1}) != canonical_key({"a": 2})

    def test_unserializable_body_is_uncacheable(self):
        assert canonical_key(object()) is None
        assert canonical_key({"x": float("nan")}) is None  # NaN != NaN

    def test_all_default_config_enables_nothing(self):
        cfg = CacheConfig()
        assert not cfg.enabled
        assert CacheConfig(result_cache=True).enabled
        assert CacheConfig(coalesce=True).enabled
        assert CacheConfig(pin_model=True).enabled

    def test_scope_extraction(self):
        assert extract_scope({"user": "u9"}, "user") == "u9"
        assert extract_scope({"user": 9}, "user") == "9"
        assert extract_scope({"item": "i1"}, "user") is None
        assert extract_scope({"user": "u9"}, None) is None
        assert extract_scope("not-a-mapping", "user") is None

    def test_scopes_from_events(self):
        events = [
            {"event": "rate", "entityType": "user", "entityId": "u1"},
            {"event": "$set", "entityType": "item", "entityId": "i1"},
            {"entityType": "user", "entityId": "u2"},
            "garbage",
        ]
        assert scopes_from_events(events) == {"u1", "u2"}


# ---------------------------------------------------------------------------
# Unit: ResultCache
# ---------------------------------------------------------------------------


class TestResultCache:
    def _cache(self, **kw) -> ResultCache:
        defaults = dict(result_cache=True, result_cache_entries=8,
                        result_cache_ttl_s=60.0)
        defaults.update(kw)
        return ResultCache(CacheConfig(**defaults))

    def test_round_trip_and_lru_eviction(self):
        rc = self._cache(result_cache_entries=3)
        for i in range(5):
            rc.commit(rc.reserve(f"k{i}", None), (200, {"i": i}))
        assert len(rc) == 3
        assert rc.stats.evictions_entries == 2
        hit, _ = rc.get("k0")
        assert not hit  # oldest evicted
        hit, value = rc.get("k4")
        assert hit and value == (200, {"i": 4})

    def test_get_refreshes_lru_order(self):
        rc = self._cache(result_cache_entries=2)
        rc.commit(rc.reserve("a", None), (200, 1))
        rc.commit(rc.reserve("b", None), (200, 2))
        rc.get("a")  # a becomes most-recent
        rc.commit(rc.reserve("c", None), (200, 3))
        assert rc.get("a")[0] and not rc.get("b")[0]

    def test_ttl_expiry(self):
        rc = self._cache(result_cache_ttl_s=0.05)
        rc.commit(rc.reserve("k", None), (200, {}))
        assert rc.get("k")[0]
        time.sleep(0.08)
        assert not rc.get("k")[0]
        assert rc.stats.expirations == 1

    def test_byte_budget_evicts(self):
        rc = self._cache(result_cache_entries=1000,
                         result_cache_max_bytes=600)
        big = (200, {"payload": "x" * 200})
        for i in range(5):
            rc.commit(rc.reserve(f"k{i}", None), big)
        assert rc.stats.evictions_bytes > 0
        assert rc.stats.bytes <= 600

    def test_scope_invalidation_kills_only_that_scope(self):
        rc = self._cache()
        rc.commit(rc.reserve("q1", "u1"), (200, 1))
        rc.commit(rc.reserve("q2", "u2"), (200, 2))
        rc.invalidate_scope("u1")
        assert not rc.get("q1")[0]
        assert rc.get("q2")[0]
        assert rc.stats.invalidations_scope == 1

    def test_invalidation_wins_race_against_inflight_fill(self):
        """The no-stale-resurrect satellite: a fill computed under an old
        generation must be DROPPED at commit, not stored."""
        rc = self._cache()
        token = rc.reserve("q", "u1")  # fill starts...
        rc.invalidate_scope("u1")  # ...write arrives mid-flight
        assert rc.commit(token, (200, {"stale": True})) is False
        assert not rc.get("q")[0]
        assert rc.stats.stale_drops == 1
        # and a fresh fill after the invalidation stores normally
        assert rc.commit(rc.reserve("q", "u1"), (200, {"fresh": True}))
        assert rc.get("q")[1] == (200, {"fresh": True})

    def test_full_invalidation_wins_race_too(self):
        rc = self._cache()
        token = rc.reserve("q", None)
        rc.invalidate_all()
        assert rc.commit(token, (200, {})) is False
        assert rc.stats.stale_drops == 1

    def test_scope_counter_map_is_bounded(self):
        """A scope-scan (many distinct users) cannot grow the generation
        map without limit; evicting a scope's counter reaps its entries
        so forgotten bumps can never resurrect stale results."""
        rc = self._cache(result_cache_entries=4)
        # _max_scopes = max(16, entries * 4) = 16
        for i in range(40):
            rc.invalidate_scope(f"u{i}")
        assert len(rc._scope_gens) <= 16

    def test_concurrent_fills_and_invalidations_stay_consistent(self):
        rc = self._cache(result_cache_entries=64)
        stop = threading.Event()
        errors = []

        def filler(tid: int) -> None:
            rng = np.random.default_rng(tid)
            while not stop.is_set():
                key = f"q{rng.integers(0, 20)}"
                scope = f"u{rng.integers(0, 5)}"
                token = rc.reserve(key, scope)
                rc.commit(token, (200, {"t": tid}))
                rc.get(key)

        def invalidator() -> None:
            rng = np.random.default_rng(99)
            while not stop.is_set():
                rc.invalidate_scope(f"u{rng.integers(0, 5)}")

        threads = [
            threading.Thread(target=filler, args=(t,), daemon=True)
            for t in range(4)
        ] + [threading.Thread(target=invalidator, daemon=True)]
        for t in threads:
            t.start()
        time.sleep(0.3)
        stop.set()
        for t in threads:
            t.join(timeout=5)
        assert not errors
        # gauges stay coherent after the storm
        with rc._lock:
            assert rc._bytes == sum(e.nbytes for e in rc._entries.values())


# ---------------------------------------------------------------------------
# Unit: Singleflight
# ---------------------------------------------------------------------------


class TestSingleflight:
    def test_n_waiters_one_computation(self):
        sf = Singleflight()
        calls = []
        barrier = threading.Barrier(8)
        results = []
        lock = threading.Lock()

        def work():
            barrier.wait()
            def fn():
                calls.append(1)
                time.sleep(0.1)
                return (200, {"v": 42})
            value, led = sf.do("key", fn)
            with lock:
                results.append((value, led))

        threads = [threading.Thread(target=work) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(calls) == 1
        assert all(v == (200, {"v": 42}) for v, _ in results)
        assert sum(1 for _, led in results if led) == 1
        assert sf.stats.coalesced == 7
        assert sf.inflight() == 0

    def test_exception_fans_out_to_all_waiters(self):
        """The computation raising must fail EVERY waiter (not hang them
        or hand them None)."""
        sf = Singleflight()
        barrier = threading.Barrier(5)
        outcomes = []
        lock = threading.Lock()

        def work():
            barrier.wait()
            def fn():
                time.sleep(0.05)
                raise RuntimeError("scoring failed")
            try:
                sf.do("key", fn)
            except RuntimeError as e:
                with lock:
                    outcomes.append(str(e))

        threads = [threading.Thread(target=work) for _ in range(5)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert outcomes == ["scoring failed"] * 5
        assert sf.inflight() == 0

    def test_sequential_calls_do_not_coalesce(self):
        sf = Singleflight()
        v1, led1 = sf.do("k", lambda: 1)
        v2, led2 = sf.do("k", lambda: 2)
        assert (v1, led1) == (1, True)
        assert (v2, led2) == (2, True)  # fresh flight, fresh value

    def test_distinct_keys_run_independently(self):
        sf = Singleflight()
        started = threading.Event()
        release = threading.Event()

        def slow():
            started.set()
            release.wait(5)
            return "slow"

        t = threading.Thread(target=lambda: sf.do("a", slow), daemon=True)
        t.start()
        started.wait(5)
        # a different key must not block behind key "a"
        value, led = sf.do("b", lambda: "fast")
        assert (value, led) == ("fast", True)
        release.set()
        t.join(timeout=5)


# ---------------------------------------------------------------------------
# Integration: QueryService wiring
# ---------------------------------------------------------------------------


@pytest.fixture()
def trained_variant(memory_storage_env):
    """A small trained recommendation engine + its variant."""
    from predictionio_tpu.controller import local_context
    from predictionio_tpu.data.event import DataMap, Event
    from predictionio_tpu.data.storage.base import App
    from predictionio_tpu.workflow import load_engine_variant, run_train

    Storage = memory_storage_env
    app_id = Storage.get_meta_data_apps().insert(App(id=0, name="cache-app"))
    rng = np.random.default_rng(5)
    Storage.get_p_events().write(
        (
            Event(
                event="rate",
                entity_type="user",
                entity_id=str(u),
                target_entity_type="item",
                target_entity_id=str(i),
                properties=DataMap({"rating": float((u + i) % 5 + 1)}),
            )
            for u, i in zip(rng.integers(0, 30, 800), rng.integers(0, 60, 800))
        ),
        app_id,
    )
    variant = load_engine_variant(
        {
            "id": "cache-eng",
            "version": "1",
            "engineFactory": "predictionio_tpu.templates."
            "recommendation:engine_factory",
            "datasource": {"params": {"appName": "cache-app"}},
            "algorithms": [
                {
                    "name": "als",
                    "params": {
                        "rank": 8,
                        "numIterations": 2,
                        "lambda": 0.05,
                        "seed": 5,
                    },
                }
            ],
        }
    )
    run_train(variant, local_context())
    return Storage, variant


def _query(qs, user="1", num=4):
    return qs.dispatch(
        "POST", "/queries.json", {}, {"user": user, "num": num}
    )


class TestQueryServiceCache:
    def test_cache_off_is_default_and_identical_path(self, trained_variant):
        from predictionio_tpu.workflow.serving import QueryService

        _, variant = trained_variant
        qs = QueryService(variant)
        assert qs.cache_config is None
        assert qs._result_cache is None and qs._singleflight is None
        r = _query(qs)
        assert r.status == 200
        assert "cache" not in qs.stats_json()
        assert qs.status_json()["caching"] is False
        # the invalidation route 404s when no cache exists
        assert (
            qs.dispatch(
                "POST", "/cache/invalidate.json", {}, {"all": True}
            ).status
            == 404
        )

    def test_hits_skip_scoring_and_serve_tail(self, trained_variant):
        from predictionio_tpu.workflow.serving import QueryService

        _, variant = trained_variant
        qs = QueryService(
            variant, cache=CacheConfig(result_cache=True)
        )
        r1, r2 = _query(qs), _query(qs)
        assert r1.status == r2.status == 200
        assert r1.body == r2.body
        stats = qs.stats_json()["cache"]
        assert stats["hits"] == 1 and stats["misses"] == 1
        # a cached hit does not re-run the serve tail
        assert qs.query_count == 1

    def test_scope_invalidation_route(self, trained_variant):
        from predictionio_tpu.workflow.serving import QueryService

        _, variant = trained_variant
        qs = QueryService(variant, cache=CacheConfig(result_cache=True))
        _query(qs, user="1")
        _query(qs, user="2")
        r = qs.dispatch(
            "POST", "/cache/invalidate.json", {}, {"entityId": "1"}
        )
        assert r.status == 200 and r.body["invalidated"] == 1
        _query(qs, user="1")  # miss: invalidated
        _query(qs, user="2")  # hit: untouched scope
        stats = qs.stats_json()["cache"]
        assert stats["misses"] == 3 and stats["hits"] == 1
        # event-shaped bodies work too
        r = qs.dispatch(
            "POST",
            "/cache/invalidate.json",
            {},
            [{"event": "rate", "entityType": "user", "entityId": "2"}],
        )
        assert r.body["invalidated"] == 1
        _query(qs, user="2")
        assert qs.stats_json()["cache"]["misses"] == 4

    def test_reload_to_new_generation_never_serves_old_entries(
        self, trained_variant
    ):
        """The generation satellite: after /reload the old generation's
        cached results are unreachable, and the response reflects the
        NEW model."""
        from predictionio_tpu.controller import local_context
        from predictionio_tpu.workflow import run_train
        from predictionio_tpu.workflow.serving import QueryService

        _, variant = trained_variant
        qs = QueryService(variant, cache=CacheConfig(result_cache=True))
        r_old = _query(qs)
        assert qs.stats_json()["cache"]["modelGeneration"] == 1
        # retrain (new instance) then hot-swap
        run_train(variant, local_context())
        assert qs.dispatch("POST", "/reload", {}).status == 200
        stats = qs.stats_json()["cache"]
        assert stats["modelGeneration"] == 2
        assert stats["invalidations"]["full"] >= 1
        assert stats["entries"] == 0  # flushed
        r_new = _query(qs)
        assert r_new.status == 200
        assert qs.stats_json()["cache"]["misses"] == 2  # re-scored
        assert r_old.status == 200  # old response was served pre-swap

    def test_degraded_reload_flushes_cache(
        self, trained_variant, monkeypatch
    ):
        """A failed reload keeps the last-good model serving but must
        not keep serving the previous generation's cached results."""
        from predictionio_tpu.workflow.serving import (
            QueryService,
            QueryServerError,
        )

        _, variant = trained_variant
        qs = QueryService(variant, cache=CacheConfig(result_cache=True))
        _query(qs)
        assert qs.stats_json()["cache"]["entries"] == 1
        monkeypatch.setattr(
            qs,
            "_resolve_instance",
            lambda: (_ for _ in ()).throw(QueryServerError("storage down")),
        )
        assert qs.dispatch("POST", "/reload", {}).status == 503
        assert qs.degraded
        stats = qs.stats_json()["cache"]
        assert stats["entries"] == 0
        assert stats["invalidations"]["full"] >= 1
        # still serving (from the model, not the cache)
        assert _query(qs).status == 200

    def test_coalesce_collapses_identical_inflight_queries(
        self, trained_variant
    ):
        from predictionio_tpu.workflow.serving import QueryService

        _, variant = trained_variant
        qs = QueryService(variant, cache=CacheConfig(coalesce=True))
        # serialize scoring through a slow gate so concurrent identical
        # queries are provably in flight together
        real = qs.handle_query

        def slow_handle(body):
            time.sleep(0.1)
            return real(body)

        qs.handle_query = slow_handle
        barrier = threading.Barrier(6)
        results = []
        lock = threading.Lock()

        def client():
            barrier.wait()
            r = _query(qs, user="7", num=4)
            with lock:
                results.append(r)

        threads = [threading.Thread(target=client) for _ in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert all(r.status == 200 for r in results)
        assert len({json.dumps(r.body, sort_keys=True) for r in results}) == 1
        stats = qs.stats_json()["cache"]
        assert stats["coalesced"] >= 1
        # coalesced followers shared ONE scored computation
        assert stats["flights"] + stats["coalesced"] == 6

    def test_uncacheable_body_bypasses_tiers(self, trained_variant):
        from predictionio_tpu.workflow.serving import QueryService

        _, variant = trained_variant
        qs = QueryService(
            variant, cache=CacheConfig(result_cache=True, coalesce=True)
        )
        # a non-JSON-serializable body cannot be keyed; it must flow
        # through the normal (uncached) path untouched
        r = qs.dispatch(
            "POST", "/queries.json", {}, {"user": "1", "num": 4,
                                          "blob": object()}
        )
        assert qs.stats_json()["cache"]["uncacheable"] == 1
        assert r.status in (200, 400)

    def test_errors_are_not_cached(self, trained_variant):
        from predictionio_tpu.workflow.serving import QueryService

        _, variant = trained_variant
        qs = QueryService(variant, cache=CacheConfig(result_cache=True))
        r = qs.dispatch("POST", "/queries.json", {}, None)  # 400
        assert r.status == 400
        assert qs.stats_json()["cache"]["stores"] == 0


class TestPinnedServing:
    def test_pin_model_moves_factors_and_reports_bytes(self, trained_variant):
        from predictionio_tpu.workflow.serving import QueryService

        _, variant = trained_variant
        qs = QueryService(variant, cache=CacheConfig(pin_model=True))
        algo, model = qs._algo_model_pairs[0]
        assert serving_state(model).pinned
        assert not isinstance(model.user_factors, np.ndarray)
        stats = qs.stats_json()["cache"]
        assert stats["bytesPinned"] > 0
        # GET / says where predict computes: device buffers on the
        # platform JAX reports, vs host arrays in a process that never
        # opened the backend (platform stays null there)
        import jax

        qs_host = QueryService(variant)
        assert qs.status_json()["device"] == {
            "servedFrom": "device",
            "platform": "cpu",
            "deviceKind": jax.devices()[0].device_kind,
            "count": len(jax.devices()),
        }
        assert qs_host.status_json()["device"] == {
            "servedFrom": "host",
            "platform": None,
            "deviceKind": None,
            "count": None,
        }
        # pinned predictions match the host path's results
        r_pin = _query(qs, user="3", num=5)
        r_host = _query(qs_host, user="3", num=5)
        assert r_pin.status == r_host.status == 200
        pin_items = [s["item"] for s in r_pin.body["itemScores"]]
        host_items = [s["item"] for s in r_host.body["itemScores"]]
        assert pin_items == host_items

    def test_release_returns_factors_to_host(self, trained_variant):
        from predictionio_tpu.workflow import device_state
        from predictionio_tpu.workflow.serving import QueryService

        _, variant = trained_variant
        qs = QueryService(variant, cache=CacheConfig(pin_model=True))
        pairs = qs._algo_model_pairs
        device_state.release_pairs(pairs)
        _, model = pairs[0]
        assert isinstance(model.user_factors, np.ndarray)
        assert not serving_state(model).pinned
        # judged from the arrays held NOW, not from the flag it booted with
        assert qs.status_json()["device"]["servedFrom"] == "host"

    def test_pin_survives_algorithms_without_the_hook(self):
        from predictionio_tpu.workflow import device_state

        class Plain:
            pass

        pairs, nbytes = device_state.pin_pairs([(Plain(), object())])
        assert len(pairs) == 1 and nbytes == 0
