"""Fleet-layer tests: hash ring, registry, supervisor, and the router's
failure modes against scriptable in-process stub replicas.

The stubs answer real HTTP (the router only ever sees backends over the
wire), each with a settable behavior per route: serve, die mid-request
(accept the connection, then hang up without a response — exactly what a
SIGKILLed replica's kernel does to in-flight sockets), answer the drain
503 + Retry-After, or answer slowly. That makes every router failure
mode deterministic without subprocesses; the real subprocess fleet is
exercised by ``pio chaos-serve`` (bench ``serving_fleet`` section).
"""

from __future__ import annotations

import json
import threading
import time
from collections import Counter
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from predictionio_tpu.fleet import (
    HashRing,
    ModelRegistry,
    ReplicaSpec,
    RouterConfig,
    RouterService,
)


class StubReplica:
    """One scriptable HTTP backend with a live behavior switch."""

    def __init__(self, rid: str, generation: int = 1):
        self.rid = rid
        self.generation = generation
        self.ready = True
        self.draining = False
        #: per-path behavior: "ok" | "die" | "drain503" | "slow"
        self.behavior: dict[str, str] = {}
        self.requests: list[tuple[str, str]] = []
        self._lock = threading.Lock()
        stub = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, *a):
                pass

            def _json(self, status, payload, headers=()):
                raw = json.dumps(payload).encode()
                self.send_response(status)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(raw)))
                for k, v in headers:
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(raw)

            def _die(self):
                # no status line at all: the client sees the connection
                # drop mid-request, like a killed process
                self.close_connection = True

            def do_GET(self):
                with stub._lock:
                    stub.requests.append(("GET", self.path))
                if self.path == "/readyz":
                    self._json(
                        200 if stub.ready else 503,
                        {
                            "ready": stub.ready,
                            "draining": stub.draining,
                            "generation": stub.generation,
                            "replicaId": stub.rid,
                        },
                    )
                    return
                if self.path == "/":
                    self._json(
                        200,
                        {
                            "status": "alive",
                            "engineInstanceId": f"inst-of-{stub.rid}",
                        },
                    )
                    return
                self._json(200, {"path": self.path, "replica": stub.rid})

            def do_POST(self):
                length = int(self.headers.get("Content-Length") or 0)
                body = self.rfile.read(length) if length else b""
                with stub._lock:
                    stub.requests.append(("POST", self.path))
                mode = stub.behavior.get(self.path, "ok")
                if mode == "die":
                    self._die()
                    return
                if mode == "drain503":
                    self._json(
                        503,
                        {"message": "draining"},
                        headers=[("Retry-After", "2"), ("Connection", "close")],
                    )
                    return
                if mode == "slow":
                    time.sleep(0.8)
                if self.path == "/reload":
                    stub.generation += 1
                    self._json(200, {"message": "Reloaded"})
                    return
                try:
                    parsed = json.loads(body) if body else None
                except json.JSONDecodeError:
                    parsed = None
                self._json(
                    200,
                    {"replica": stub.rid, "echo": parsed},
                    headers=[
                        ("X-PIO-Replica", stub.rid),
                        ("X-PIO-Generation", str(stub.generation)),
                    ],
                )

        self.server = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        self.port = self.server.server_address[1]
        threading.Thread(
            target=self.server.serve_forever, daemon=True
        ).start()

    def count(self, method: str, path: str) -> int:
        with self._lock:
            return sum(1 for m, p in self.requests if m == method and p == path)

    def close(self):
        self.server.shutdown()
        self.server.server_close()


@pytest.fixture()
def stubs():
    created: list[StubReplica] = []

    def make(n: int, **kwargs) -> list[StubReplica]:
        for i in range(n):
            created.append(StubReplica(f"r{i}", **kwargs))
        return created

    yield make
    for s in created:
        s.close()


def make_router(replicas, **config_kwargs) -> RouterService:
    config = RouterConfig(
        probe_interval_s=0.05,
        breaker_reset_s=0.5,
        request_timeout_s=5.0,
        **config_kwargs,
    )
    router = RouterService(
        [(s.rid, "127.0.0.1", s.port) for s in replicas], config
    )
    router.probe_all()  # tests drive probes synchronously
    return router


def owner_user(router: RouterService, want: str, n: int = 200) -> dict:
    """A query body whose hash-ring owner is replica ``want``."""
    for u in range(n):
        body = {"user": f"u{u}", "num": 4}
        if router._ring.sequence(f"s:u{u}")[0] == want:
            return body
    raise AssertionError(f"no user found owned by {want}")


class TestHashRing:
    def test_membership_change_remaps_about_one_over_r(self):
        keys = [f"s:u{i}" for i in range(3000)]
        r3 = HashRing(["r0", "r1", "r2"])
        r2 = HashRing(["r0", "r1"])
        own3 = {k: r3.owner(k) for k in keys}
        # keys owned by a surviving member must not move at all; only the
        # removed member's ~1/R of keys redistribute
        stable_moved = sum(
            1
            for k in keys
            if own3[k] in ("r0", "r1") and r2.owner(k) != own3[k]
        )
        orphaned = sum(1 for k in keys if own3[k] == "r2")
        assert stable_moved == 0
        assert 0.2 < orphaned / len(keys) < 0.47  # ~1/3, smoothed by vnodes
        # load split is roughly even
        split = Counter(own3.values())
        assert max(split.values()) < 2 * min(split.values())

    def test_sequence_is_a_permutation(self):
        ring = HashRing(["a", "b", "c", "d"])
        seq = ring.sequence("s:x")
        assert sorted(seq) == ["a", "b", "c", "d"]
        assert ring.owner("s:x") == seq[0]

    def test_empty_ring(self):
        assert HashRing([]).owner("k") is None


class TestRegistry:
    def test_publish_monotonic_and_atomic(self, tmp_path):
        reg = ModelRegistry(str(tmp_path))
        assert reg.current() is None
        first = reg.publish("inst-1")
        second = reg.publish("inst-2", meta={"source": "test"})
        assert (first.generation, second.generation) == (1, 2)
        cur = reg.current()
        assert cur.engine_instance_id == "inst-2"
        assert cur.meta == {"source": "test"}
        assert [r.engine_instance_id for r in reg.history()] == [
            "inst-2",
            "inst-1",
        ]
        # torn/garbage file degrades to empty, never raises
        (tmp_path / "model-registry.json").write_text("{not json")
        assert reg.current() is None
        assert reg.publish("inst-3").generation == 1


class TestRouting:
    def test_scope_affinity_pins_a_user_to_one_replica(self, stubs):
        a, b = stubs(2)
        router = make_router([a, b])
        body = owner_user(router, "r0")
        for _ in range(5):
            resp = router.dispatch("POST", "/queries.json", {}, body)
            assert resp.status == 200
            assert json.loads(resp.json_bytes())["replica"] == "r0"
        assert a.count("POST", "/queries.json") == 5
        assert b.count("POST", "/queries.json") == 0
        # and the responder's identity/generation surface to the client
        assert resp.headers["X-PIO-Routed-Replica"] == "r0"
        assert resp.headers["X-Pio-Generation"] == "1"

    def test_scopes_spread_across_replicas(self, stubs):
        a, b = stubs(2)
        router = make_router([a, b])
        served = set()
        for u in range(40):
            resp = router.dispatch(
                "POST", "/queries.json", {}, {"user": f"u{u}", "num": 4}
            )
            assert resp.status == 200
            served.add(json.loads(resp.json_bytes())["replica"])
        assert served == {"r0", "r1"}

    def test_failover_retries_exactly_once_on_dead_replica(self, stubs):
        a, b = stubs(2)
        a.behavior["/queries.json"] = "die"
        router = make_router([a, b])
        body = owner_user(router, "r0")
        resp = router.dispatch("POST", "/queries.json", {}, body)
        # the in-flight casualty was retried on the peer: client sees 200
        assert resp.status == 200
        assert json.loads(resp.json_bytes())["replica"] == "r1"
        assert router.stats.to_json()["failovers"] == 1
        assert a.count("POST", "/queries.json") == 1
        # passive detection: the dead replica is already routed around
        # (no probe needed) — the SAME scope now goes straight to r1
        resp = router.dispatch("POST", "/queries.json", {}, body)
        assert resp.status == 200
        assert a.count("POST", "/queries.json") == 1

    def test_failover_budget_zero_surfaces_502(self, stubs):
        a, b = stubs(2)
        a.behavior["/queries.json"] = "die"
        router = make_router([a, b], failover_retries=0)
        body = owner_user(router, "r0")
        resp = router.dispatch("POST", "/queries.json", {}, body)
        assert resp.status == 502
        assert b.count("POST", "/queries.json") == 0

    def test_non_idempotent_post_is_never_retried(self, stubs):
        a, b = stubs(2)
        a.behavior["/online/fold.json"] = "die"
        b.behavior["/online/fold.json"] = "die"
        router = make_router([a, b])
        resp = router.dispatch("POST", "/online/fold.json", {}, {"x": 1})
        assert resp.status == 502
        body = json.loads(resp.json_bytes())
        assert "not idempotent" in body["message"]
        # exactly ONE replica saw exactly ONE attempt
        total = a.count("POST", "/online/fold.json") + b.count(
            "POST", "/online/fold.json"
        )
        assert total == 1

    def test_draining_503_is_a_routing_signal_not_a_client_answer(self, stubs):
        a, b = stubs(2)
        a.behavior["/queries.json"] = "drain503"
        router = make_router([a, b])
        body = owner_user(router, "r0")
        resp = router.dispatch("POST", "/queries.json", {}, body)
        # the drain 503 never reached the client: re-dispatched to r1
        assert resp.status == 200
        assert json.loads(resp.json_bytes())["replica"] == "r1"
        stats = router.stats.to_json()
        assert stats["redispatchDraining"] == 1
        assert stats["failovers"] == 0  # drain re-dispatch is not failover
        # the drain marking sticks: the next request skips r0 entirely
        router.dispatch("POST", "/queries.json", {}, body)
        assert a.count("POST", "/queries.json") == 1

    def test_all_replicas_down_fast_503_with_taxonomy(self, stubs):
        a, b = stubs(2)
        a.ready = False
        b.ready = False
        router = make_router([a, b])
        t0 = time.monotonic()
        resp = router.dispatch(
            "POST", "/queries.json", {}, {"user": "u1", "num": 4}
        )
        elapsed = time.monotonic() - t0
        assert resp.status == 503
        body = json.loads(resp.json_bytes())
        assert body["taxonomy"] in ("no_healthy_replicas", "breaker_open")
        assert resp.headers["Retry-After"]
        # fast fail: no forwards were attempted, no timeout was paid
        assert elapsed < 0.5
        assert a.count("POST", "/queries.json") == 0
        assert b.count("POST", "/queries.json") == 0
        assert router.stats.to_json()["fast503s"] == 1

    def test_hedged_request_wins_on_slow_primary(self, stubs):
        a, b = stubs(2)
        a.behavior["/queries.json"] = "slow"  # 0.8 s
        router = make_router([a, b], hedge_ms=50.0)
        body = owner_user(router, "r0")
        t0 = time.monotonic()
        resp = router.dispatch("POST", "/queries.json", {}, body)
        elapsed = time.monotonic() - t0
        assert resp.status == 200
        assert json.loads(resp.json_bytes())["replica"] == "r1"
        assert elapsed < 0.7  # did not wait out the slow primary
        stats = router.stats.to_json()
        assert stats["hedges"] == 1
        assert stats["hedgeWins"] == 1


class TestRollingReload:
    def test_rolling_reload_converges_one_replica_at_a_time(self, stubs):
        a, b = stubs(2)
        router = make_router([a, b])
        status, report = router.rolling_reload()
        assert status == 200 and report["ok"] is True
        assert report["converged"] is True
        assert report["generations"] == [2]
        for entry in report["replicas"].values():
            assert entry["generationBefore"] == 1
            assert entry["generationAfter"] == 2

    def test_rolling_reload_aborts_when_a_replica_fails(self, stubs):
        a, b = stubs(2)
        b.behavior["/reload"] = "die"
        router = make_router([a, b])
        status, report = router.rolling_reload()
        assert status == 500 and report["ok"] is False
        assert report["converged"] is False
        # the healthy replica DID rotate before the abort
        assert report["replicas"]["r0"]["generationAfter"] == 2

    def test_key_generation_guard_prefers_newer_generation(self, stubs):
        a, b = stubs(2)
        router = make_router([a, b])
        body = owner_user(router, "r0")
        key = f"s:{body['user']}"
        # mid-rollout state: r1 already serves generation 2, and this key
        # was last answered by generation 2
        b.generation = 2
        router.probe_all()
        router._key_gen_put(key, 2)
        resp = router.dispatch("POST", "/queries.json", {}, body)
        assert resp.status == 200
        # the ring owner (r0, still gen 1) is skipped: one cache key is
        # never served by two generations
        assert json.loads(resp.json_bytes())["replica"] == "r1"
        assert router.stats.to_json()["generationRegressions"] == 0

    def test_generation_regression_is_counted_when_unavoidable(self, stubs):
        a, b = stubs(2)
        router = make_router([a, b])
        body = owner_user(router, "r0")
        key = f"s:{body['user']}"
        router._key_gen_put(key, 5)  # key was served by a generation no
        resp = router.dispatch("POST", "/queries.json", {}, body)  # replica has
        assert resp.status == 200  # availability still wins...
        assert router.stats.to_json()["generationRegressions"] == 1  # ...visibly


class TestBroadcastAndStatus:
    def test_invalidation_broadcast_reaches_every_replica(self, stubs):
        a, b = stubs(2)
        router = make_router([a, b])
        resp = router.dispatch(
            "POST", "/cache/invalidate.json", {}, {"entityId": "u1"}
        )
        assert resp.status == 200
        body = json.loads(resp.json_bytes())
        assert body["ok"] is True
        assert set(body["replicas"]) == {"r0", "r1"}
        assert a.count("POST", "/cache/invalidate.json") == 1
        assert b.count("POST", "/cache/invalidate.json") == 1

    def test_broadcast_retries_transport_failures_once(self, stubs):
        a, b = stubs(2)
        a.behavior["/cache/invalidate.json"] = "die"
        router = make_router([a, b])
        resp = router.dispatch(
            "POST", "/cache/invalidate.json", {}, {"entityId": "u1"}
        )
        body = json.loads(resp.json_bytes())
        assert body["replicas"]["r1"]["ok"] is True
        assert body["replicas"]["r0"]["ok"] is False
        assert resp.status == 502  # partial delivery is loudly partial
        assert a.count("POST", "/cache/invalidate.json") == 2  # retried once

    def test_broadcast_skips_replica_that_was_already_down(self, stubs):
        """A replica that is DOWN before delivery cannot hold cache
        entries: its cache restarts cold, so failed delivery to it is a
        safe skip (200), not a lost invalidation (502). Delivery failure
        to a replica that WAS serving stays loudly partial (the test
        above)."""
        a, b = stubs(2)
        a.ready = False
        router = make_router([a, b])
        a.behavior["/cache/invalidate.json"] = "die"  # unreachable anyway
        resp = router.dispatch(
            "POST", "/cache/invalidate.json", {}, {"entityId": "u1"}
        )
        assert resp.status == 200
        body = json.loads(resp.json_bytes())
        assert body["ok"] is True
        assert body["replicas"]["r1"]["ok"] is True
        assert body["replicas"]["r0"]["ok"] is True
        assert "skipped" in body["replicas"]["r0"]

    def test_readiness_and_status(self, stubs):
        a, b = stubs(2)
        router = make_router([a, b])
        ready = router.readiness()
        assert ready["ready"] is True
        assert ready["checks"]["replicas"]["healthy"] == 2
        status = json.loads(
            router.dispatch("GET", "/", {}).json_bytes()
        )
        assert status["role"] == "router"
        assert status["generationConverged"] is True
        a.ready = False
        b.ready = False
        router.probe_all()
        assert router.readiness()["ready"] is False

    def test_stats_fanout(self, stubs):
        a, b = stubs(2)
        router = make_router([a, b])
        payload = json.loads(
            router.dispatch("GET", "/stats.json", {"fanout": "1"}).json_bytes()
        )
        assert payload["role"] == "router"
        assert set(payload["replicaStats"]) == {"r0", "r1"}


class TestSupervisor:
    def test_respawns_dead_replica_and_tracks_state(self, tmp_path):
        import os
        import signal

        from predictionio_tpu.fleet import FleetSupervisor

        state_path = str(tmp_path / "fleet-9999.json")
        spec = ReplicaSpec(
            "r0", 1234, ("-c", "import time; time.sleep(600)")
        )
        sup = FleetSupervisor(
            [spec], state_path, router_port=9999, poll_interval_s=0.05
        )
        sup.start()
        try:
            state = sup.state()
            pid = state["replicas"][0]["pid"]
            assert state["replicas"][0]["alive"] is True
            os.kill(pid, signal.SIGKILL)
            deadline = time.monotonic() + 10
            new_pid = None
            while time.monotonic() < deadline:
                state = sup.state()
                rep = state["replicas"][0]
                if rep["alive"] and rep["pid"] != pid:
                    new_pid = rep["pid"]
                    break
                time.sleep(0.05)
            assert new_pid is not None, "supervisor never respawned the replica"
            # the state FILE is what operators and the chaos drill read;
            # it is rewritten (durably: fsync + dir fsync) just after the
            # in-memory flip, so poll it within the same deadline
            on_disk = None
            while time.monotonic() < deadline:
                with open(state_path) as f:
                    on_disk = json.load(f)
                if on_disk["replicas"][0]["pid"] == new_pid:
                    break
                time.sleep(0.05)
            assert on_disk["replicas"][0]["pid"] == new_pid
        finally:
            sup.stop()
        assert not os.path.exists(state_path)
        # both pids are gone
        for p in (pid, new_pid):
            with pytest.raises(ProcessLookupError):
                os.kill(p, 0)

    def test_replica_without_a_chip_is_failed_not_respawned(
        self, tmp_path, caplog
    ):
        """A replica that exits with the device-unavailable code (a
        `pio deploy --pin-model` whose backend a sibling already holds)
        is marked failed at once with the cause logged — respawning
        cannot succeed while the sibling lives — and the sibling stays
        up."""
        import logging

        from predictionio_tpu.fleet import FleetSupervisor

        rc = FleetSupervisor.DEVICE_UNAVAILABLE_RC
        state_path = str(tmp_path / "fleet-9998.json")
        marker = tmp_path / "spawned"
        specs = [
            ReplicaSpec("r0", 1234, ("-c", "import time; time.sleep(600)")),
            ReplicaSpec(
                "r1", 1235,
                ("-c", f"import sys; open({str(marker)!r}, 'a').write('x'); "
                       f"sys.exit({rc})"),
            ),
        ]
        sup = FleetSupervisor(
            specs, state_path, router_port=9998, poll_interval_s=0.05
        )
        with caplog.at_level(logging.ERROR, "predictionio_tpu.fleet.supervisor"):
            sup.start()
            try:
                deadline = time.monotonic() + 10
                while time.monotonic() < deadline:
                    r0, r1 = sup.state()["replicas"]
                    if r1["failed"]:
                        break
                    time.sleep(0.05)
                assert r1["failed"] and not r1["alive"]
                assert r0["alive"] and not r0["failed"]
                time.sleep(0.3)  # several polls: still no respawn
                assert marker.read_text() == "x"
            finally:
                sup.stop()
        assert any(
            "could not open the accelerator" in r.getMessage()
            and "one process" in r.getMessage()
            for r in caplog.records
        )
