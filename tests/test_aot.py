"""Deploy-time AOT serving (ISSUE 19; ``workflow/aot.py``).

Covers the full artifact lifecycle: pow2 bucket enumeration, atomic
export with a fingerprinted manifest, stdlib verification, tier-1
deserialize with bit-identical results, the LOUD tiered fallback on
foreign-jaxlib / corrupt artifacts — with served-result parity across
the exact, ANN, quantized, and sharded deployments — plus the registry
stamp (inheritance + bounded-history GC), the router's pre-rotation
artifact gate, the ``pio status`` artifact column, the zero-compile
gate, and the boot-time glue warm hook.
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import shutil
import types

import numpy as np
import pytest

from predictionio_tpu.controller import local_context
from predictionio_tpu.data.event import DataMap, Event
from predictionio_tpu.data.storage import Storage
from predictionio_tpu.data.storage.base import App
from predictionio_tpu.templates.retrieval import serving_state
from predictionio_tpu.workflow import aot, load_engine_variant, run_train
from predictionio_tpu.workflow.serving import QueryService

N_USERS, N_ITEMS, N_EVENTS = 30, 50, 220


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    """One trained ALS instance on in-memory storage, shared by the
    module (each test builds its own QueryService/pairs on top)."""
    base = str(tmp_path_factory.mktemp("aot_store"))
    config = {
        "PIO_FS_BASEDIR": base,
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
        "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
    }
    Storage.configure(config)
    app_id = Storage.get_meta_data_apps().insert(App(id=0, name="aot-test"))
    rng = np.random.default_rng(7)
    Storage.get_p_events().write(
        (
            Event(
                event="rate",
                entity_type="user",
                entity_id=str(i % N_USERS),
                target_entity_type="item",
                target_entity_id=str(int(rng.integers(N_ITEMS))),
                properties=DataMap({"rating": float(1 + int(rng.integers(5)))}),
            )
            for i in range(N_EVENTS)
        ),
        app_id,
    )
    variant = load_engine_variant(
        {
            "id": "aot-test",
            "version": "1",
            "engineFactory": (
                "predictionio_tpu.templates.recommendation:engine_factory"
            ),
            "datasource": {"params": {"appName": "aot-test"}},
            "algorithms": [
                {
                    "name": "als",
                    "params": {
                        "rank": 8,
                        "numIterations": 2,
                        "lambda": 0.05,
                        "seed": 7,
                    },
                }
            ],
        }
    )
    ctx = local_context()
    instance = run_train(variant, ctx)
    yield types.SimpleNamespace(
        variant=variant, ctx=ctx, instance=instance, config=config
    )
    Storage.configure(None)


def _fresh_pairs(t):
    engine = t.variant.build_engine()
    engine_params = t.variant.engine_params(engine)
    model = Storage.get_model_data_models().get(t.instance.id)
    return engine.prepare_deploy(
        t.ctx, engine_params, t.instance.id, model.models
    )[1]


@pytest.fixture(scope="module")
def artifacts(trained, tmp_path_factory):
    """One healthy exported artifact set for the trained instance."""
    root = str(tmp_path_factory.mktemp("aot_root"))
    manifest = aot.export_instance(_fresh_pairs(trained), trained.instance.id, root)
    assert manifest is not None, "ALS pairs exported nothing"
    return root, manifest


def _copy_root(root: str, instance_id: str, dst) -> str:
    """Private mutable copy of the artifact root for tamper tests."""
    new_root = str(dst / "root")
    os.makedirs(new_root)
    adir = aot.artifact_dir(root, instance_id)
    shutil.copytree(adir, aot.artifact_dir(new_root, instance_id))
    return new_root


def _write_fake_artifacts(dirpath, payload: bytes = b"x" * 32) -> str:
    """A minimal VALID artifact set (stdlib schema only — no jax)."""
    os.makedirs(dirpath, exist_ok=True)
    with open(os.path.join(dirpath, "p.jaxprog"), "wb") as f:
        f.write(payload)
    manifest = {
        "version": 1,
        "engineInstanceId": os.path.basename(str(dirpath)),
        "fingerprint": {"jaxVersion": "0"},
        "entries": [
            {
                "key": "p",
                "file": "p.jaxprog",
                "bytes": len(payload),
                "sha256": hashlib.sha256(payload).hexdigest(),
            }
        ],
    }
    from predictionio_tpu.fleet.registry import AOT_MANIFEST_NAME

    with open(os.path.join(dirpath, AOT_MANIFEST_NAME), "w") as f:
        json.dump(manifest, f)
    return str(dirpath)


# ---------------------------------------------------------------------------
# Bucket math + export/verify
# ---------------------------------------------------------------------------


def test_serving_buckets_pow2_floor_and_caps():
    # pow2 walk from the floor, capped at the catalog, bounded in count
    assert aot.serving_buckets(100) == [16, 32, 64, 100]
    assert aot.serving_buckets(100, max_buckets=2) == [16, 32]
    assert aot.serving_buckets(1 << 12) == [16, 32, 64, 128, 256, 512]
    # tiny catalogs collapse to one bucket (dedupe keeps order)
    assert aot.serving_buckets(10) == [10]
    assert aot.serving_buckets(16) == [16]


def test_export_writes_fingerprinted_atomic_manifest(trained, artifacts):
    root, manifest = artifacts
    adir = aot.artifact_dir(root, trained.instance.id)
    # no torn .tmp siblings survive a successful publish
    assert [d for d in os.listdir(root) if d.startswith(".aot.")] == []
    entries = manifest["entries"]
    assert len(entries) >= 3  # predict_scores + per-bucket programs
    keys = {e["key"] for e in entries}
    assert "predict_scores" in keys
    assert any(k.startswith("top_k_scores_b") for k in keys)
    for entry in entries:
        path = os.path.join(adir, entry["file"])
        assert os.path.getsize(path) == entry["bytes"]
    # the manifest on disk round-trips and carries THIS env's identity
    ondisk = aot.read_manifest(adir)
    assert ondisk["engineInstanceId"] == trained.instance.id
    live = aot.current_fingerprint()
    assert aot.fingerprint_mismatches(ondisk["fingerprint"], live) == []
    verdict = aot.verify_artifacts(adir)
    assert verdict["ok"], verdict["problems"]
    assert verdict["programs"] == len(entries)
    assert verdict["bytes"] == sum(e["bytes"] for e in entries)


def test_load_runtime_tier1_bit_identical_to_jit(trained, artifacts):
    root, manifest = artifacts
    runtime, report = aot.load_runtime(trained.instance.id, root)
    assert runtime is not None, report
    assert report["tier"] == 1 and report["problems"] == []
    assert report["loaded"] == len(manifest["entries"])
    # the deserialized programs ARE the jitted path's jaxprs: same
    # scores, same selected ids, bit for bit
    from predictionio_tpu.ops.als import predict_scores
    from predictionio_tpu.ops.topk import top_k_scores

    _, model = _fresh_pairs(trained)[0]
    uvec = np.asarray(model.user_factors)[3]
    items = np.asarray(model.item_factors)
    jit_scores = np.asarray(predict_scores(uvec, items))
    aot_scores = np.asarray(runtime.get("predict_scores")(uvec, items))
    np.testing.assert_array_equal(jit_scores, aot_scores)
    kb = 16
    jit_idx, jit_top = top_k_scores(jit_scores, kb)
    aot_idx, aot_top = runtime.get(f"top_k_scores_b{kb}")(aot_scores)
    np.testing.assert_array_equal(np.asarray(jit_idx), np.asarray(aot_idx))
    np.testing.assert_array_equal(np.asarray(jit_top), np.asarray(aot_top))
    stats = runtime.stats()
    assert stats["tier"] == 1 and stats["hits"] >= 2
    # a missing key is a miss, not an error; disable() flips a live key
    assert runtime.get("no_such_program") is None
    runtime.disable("predict_scores", "test")
    assert runtime.get("predict_scores") is None
    assert runtime.stats()["disabled"] == 1


# ---------------------------------------------------------------------------
# Loud tiered fallback
# ---------------------------------------------------------------------------


def test_foreign_jaxlib_fingerprint_falls_back_loudly(
    trained, artifacts, tmp_path, caplog
):
    root, _ = artifacts
    new_root = _copy_root(root, trained.instance.id, tmp_path)
    adir = aot.artifact_dir(new_root, trained.instance.id)
    mpath = os.path.join(adir, aot.MANIFEST_NAME)
    with open(mpath) as f:
        doc = json.load(f)
    doc["fingerprint"]["jaxlibVersion"] = "0.0.0-foreign"
    with open(mpath, "w") as f:
        json.dump(doc, f)
    with caplog.at_level(logging.WARNING, logger="predictionio_tpu.workflow.aot"):
        runtime, report = aot.load_runtime(trained.instance.id, new_root)
    assert runtime is None
    assert report["tier"] == aot.fallback_tier() and report["tier"] in (2, 3)
    assert any("fingerprint mismatch" in p for p in report["problems"])
    assert any("jaxlibVersion" in p for p in report["problems"])
    assert "falling back to tier" in caplog.text  # loud, not silent


def test_corrupt_blob_fails_verification_and_load(trained, artifacts, tmp_path):
    root, manifest = artifacts
    new_root = _copy_root(root, trained.instance.id, tmp_path)
    adir = aot.artifact_dir(new_root, trained.instance.id)
    victim = os.path.join(adir, manifest["entries"][0]["file"])
    blob = bytearray(open(victim, "rb").read())
    blob[-8:] = b"\x00" * 8  # same size, different bytes -> digest path
    with open(victim, "wb") as f:
        f.write(blob)
    verdict = aot.verify_artifacts(adir)
    assert not verdict["ok"]
    assert any("digest mismatch" in p for p in verdict["problems"])
    runtime, report = aot.load_runtime(trained.instance.id, new_root)
    assert runtime is None and report["tier"] in (2, 3)
    # truncation is caught by the cheap size check before any hashing
    with open(victim, "wb") as f:
        f.write(blob[:-4])
    shallow = aot.verify_artifacts(adir, deep=False)
    assert any("size mismatch" in p for p in shallow["problems"])
    # and a missing manifest is its own loud problem
    os.unlink(os.path.join(adir, aot.MANIFEST_NAME))
    assert not aot.verify_artifacts(adir)["ok"]


def test_fallback_tier_prefers_persistent_cache(monkeypatch, tmp_path):
    import jax

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    prev = getattr(jax.config, "jax_compilation_cache_dir", None)
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        assert aot.fallback_tier() == 3
        # env var alone (replica subprocesses) counts as tier 2
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert aot.fallback_tier() == 2
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        assert aot.fallback_tier() == 2
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_serving_parity_tier1_and_on_fallback_across_modes(
    trained, artifacts, tmp_path
):
    """The bit-identity contract, end to end through QueryService: a
    healthy tier-1 boot serves byte-identical responses to the plain
    JIT path, and a BROKEN artifact set (foreign fingerprint) falls
    back without changing a single served byte — in the exact, ANN,
    quantized, and sharded deployments alike (the latter three export
    nothing and must stay untouched by construction)."""
    from predictionio_tpu.serving import CacheConfig
    from predictionio_tpu.serving.ann import AnnConfig

    root, _ = artifacts
    broken_root = _copy_root(root, trained.instance.id, tmp_path)
    adir = aot.artifact_dir(broken_root, trained.instance.id)
    mpath = os.path.join(adir, aot.MANIFEST_NAME)
    with open(mpath) as f:
        doc = json.load(f)
    doc["fingerprint"]["jaxlibVersion"] = "0.0.0-foreign"
    with open(mpath, "w") as f:
        json.dump(doc, f)

    queries = [{"user": str(u), "num": 7} for u in range(6)]

    def serve_all(svc):
        return [svc.handle_query(dict(q)) for q in queries]

    # the exact twin pins too: --aot implies device residency, so the
    # parity claim is against the pinned JIT path (the host path's
    # numpy GEMV rounds differently by design — see the engine docstring)
    modes = {
        "exact": {"cache": CacheConfig(pin_model=True)},
        "ann": {"ann": AnnConfig(enabled=True, nlist=4, nprobe=4, seed=1)},
        "quantized": {"cache": CacheConfig(pin_model=True, quantize="int8")},
        "sharded": {"cache": CacheConfig(shard_factors=True)},
    }
    for name, kwargs in modes.items():
        baseline = serve_all(
            QueryService(
                trained.variant, trained.ctx,
                instance_id=trained.instance.id, **kwargs,
            )
        )
        assert all(status == 200 for status, _ in baseline), name
        fellback = QueryService(
            trained.variant, trained.ctx, instance_id=trained.instance.id,
            aot=aot.AotConfig(enabled=True, root=broken_root), **kwargs,
        )
        assert serve_all(fellback) == baseline, (
            f"fallback changed served bytes in {name} mode"
        )
        if name == "exact":
            block = fellback.stats_json().get("aot") or {}
            assert block.get("tier") in (2, 3), block
    # and the healthy set: tier 1, programs actually serving, same bytes
    exact_baseline = serve_all(
        QueryService(
            trained.variant, trained.ctx, instance_id=trained.instance.id,
            cache=CacheConfig(pin_model=True),
        )
    )
    tier1 = QueryService(
        trained.variant, trained.ctx, instance_id=trained.instance.id,
        aot=aot.AotConfig(enabled=True, root=root),
    )
    assert serve_all(tier1) == exact_baseline, (
        "tier-1 AOT serving changed served bytes vs the JIT path"
    )
    block = tier1.stats_json()["aot"]
    assert block["tier"] == 1 and block["loaded"] >= 3
    assert block["hits"] > 0, "tier-1 boot never consulted the programs"
    assert block["serveTimeCompiles"] == 0


# ---------------------------------------------------------------------------
# Registry stamp: inheritance + bounded-history GC
# ---------------------------------------------------------------------------


def test_registry_stamp_inheritance_and_artifact_gc(tmp_path, monkeypatch):
    from predictionio_tpu.fleet import registry as reg

    monkeypatch.setattr(reg, "_HISTORY_LIMIT", 3)
    r = reg.ModelRegistry(str(tmp_path / "fleet"))

    def stamp(i):
        adir = _write_fake_artifacts(tmp_path / "aot" / f"inst{i}")
        return {"dir": adir, "programs": 1, "bytes": 32, "fingerprint": {}}

    a1 = stamp(1)
    rec1 = r.publish("inst1", artifacts=a1)
    assert rec1.generation == 1 and rec1.artifacts == a1
    # a re-publish of the same instance (router post-rotation) inherits
    # the newest prior stamp instead of orphaning the live artifact set
    rec2 = r.publish("inst1")
    assert rec2.artifacts == a1
    assert r.current().artifacts == a1
    # different instance without artifacts inherits nothing
    rec3 = r.publish("other")
    assert rec3.artifacts is None
    # gen1 falls off the bounded history but gen2 still references a1
    a4 = stamp(4)
    r.publish("inst4", artifacts=a4)
    assert os.path.isdir(a1["dir"]), "GC deleted a dir a survivor references"
    # one more publish evicts gen2 — now nothing references a1
    r.publish("inst5", artifacts=stamp(5))
    assert not os.path.isdir(a1["dir"]), "evicted artifact blobs leaked"
    assert os.path.isdir(a4["dir"])
    # safety: a stamped dir that does NOT look like an artifact set
    # (no manifest file) is never rmtree'd, whatever the record says
    plain = tmp_path / "not_artifacts"
    plain.mkdir()
    (plain / "keep.txt").write_text("precious")
    r.publish("inst6", artifacts={"dir": str(plain)})
    for i in range(4):
        r.publish(f"filler{i}")
    assert plain.is_dir() and (plain / "keep.txt").exists()


def test_router_rolling_reload_gates_on_artifacts(tmp_path):
    """The router refuses to rotate onto a generation whose declared
    artifact set fails stdlib verification — every replica keeps
    serving warm instead of the whole fleet demoting to JIT at once."""
    from predictionio_tpu.fleet.registry import ModelRegistry
    from predictionio_tpu.fleet.router import RouterService

    registry = ModelRegistry(str(tmp_path / "fleet"))
    gone = tmp_path / "gone"
    registry.publish(
        "inst-a", artifacts={"dir": str(gone), "programs": 1, "bytes": 32}
    )
    router = RouterService([], registry=registry)
    status, report = router.rolling_reload()
    assert status == 500
    assert report["artifactCheck"]["ok"] is False
    assert "aborted before touching any replica" in report["error"]
    assert report["replicas"] == {}, "gate ran after touching a replica"
    # same generation with a healthy set clears the gate (the empty
    # fleet still reports unconverged, but no artifact error)
    _write_fake_artifacts(gone)
    status, report = router.rolling_reload()
    assert report["artifactCheck"]["ok"] is True
    assert "error" not in report or "artifact" not in report["error"]


# ---------------------------------------------------------------------------
# pio status artifact column
# ---------------------------------------------------------------------------


def test_status_reports_artifact_states(tmp_path, trained):
    from predictionio_tpu.fleet.registry import ModelRegistry
    from predictionio_tpu.tools import commands

    # status reads the registry under Storage.base_dir()/fleet — reuse
    # the module fixture's basedir rather than reconfiguring Storage
    # (a reconfigure would wipe the shared in-memory model store)
    base = trained.config["PIO_FS_BASEDIR"]
    try:
        registry = ModelRegistry(os.path.join(base, "fleet"))
        lines: list[str] = []
        # unstamped registry: no rows, NO output (default status output
        # is byte-identical to a pre-AOT tree — CI-guarded opt-in)
        registry.publish("plain-jit")
        assert commands.aot_artifact_status(out=lines.append) is None
        assert lines == []
        # present: valid blobs + THIS host's fingerprint
        present_dir = _write_fake_artifacts(tmp_path / "aot" / "present")
        mpath = os.path.join(present_dir, aot.MANIFEST_NAME)
        doc = json.load(open(mpath))
        doc["fingerprint"] = aot.current_fingerprint()
        json.dump(doc, open(mpath, "w"))
        registry.publish("inst-present", artifacts={"dir": present_dir})
        # fingerprint-stale: valid blobs, foreign environment
        stale_dir = _write_fake_artifacts(tmp_path / "aot" / "stale")
        registry.publish("inst-stale", artifacts={"dir": stale_dir})
        # missing: stamped dir deleted out from under the registry
        gone_dir = _write_fake_artifacts(tmp_path / "aot" / "gone")
        registry.publish("inst-gone", artifacts={"dir": gone_dir})
        shutil.rmtree(gone_dir)

        rows = commands.aot_artifact_status(out=lines.append)
        by_id = {row["engineInstanceId"]: row for row in rows}
        assert by_id["inst-present"]["artifacts"] == "present"
        assert by_id["inst-stale"]["artifacts"] == "fingerprint-stale"
        assert any(
            "jaxVersion" in m for m in by_id["inst-stale"]["mismatches"]
        )
        assert by_id["inst-gone"]["artifacts"] == "missing"
        assert by_id["plain-jit"]["artifacts"] is None  # rendered "(jit)"
        rendered = "\n".join(lines)
        for needle in ("present", "fingerprint-stale", "missing", "(jit)"):
            assert needle in rendered
        # read-only: asking for status never creates or deletes anything
        assert not os.path.isdir(gone_dir)
        assert os.path.isdir(present_dir)
    finally:
        # leave no registry behind for other tests reading this basedir
        shutil.rmtree(os.path.join(base, "fleet"), ignore_errors=True)


# ---------------------------------------------------------------------------
# Zero-compile gate + glue warm hook
# ---------------------------------------------------------------------------


def test_zero_compile_gate_is_absolute():
    from predictionio_tpu.analysis.jit_witness import zero_compile_gate

    clean = zero_compile_gate({"compiles": {}})
    assert clean == {"ok": True, "compiles": 0, "sites": []}
    dirty = zero_compile_gate(
        {"compiles": {"ops/als.py:predict_scores:10": {"count": 2}}},
        ledger={
            "entries": [
                {
                    "entrypoint": "ops/als.py:predict_scores",
                    "maxCompiles": 4,
                }
            ]
        },
    )
    # within budget is STILL red — the AOT gate is absolute, the ledger
    # only annotates what the site would have been allowed pre-AOT
    assert dirty["ok"] is False and dirty["compiles"] == 2
    assert dirty["sites"][0]["budgetedMax"] == 4


def test_aot_warm_serving_glue_hook(trained):
    """The boot warm hook touches the pinned row-gather path (the
    eager-op executables every query reuses) and is a no-op on an
    unpinned model — and it is duck-typed exactly like the pin hooks."""
    algo, model = _fresh_pairs(trained)[0]
    assert not serving_state(model).pinned
    algo.aot_warm_serving(model)  # unpinned: must not raise, must not pin
    assert not serving_state(model).pinned
    from predictionio_tpu.workflow import device_state

    pairs, _ = device_state.pin_pairs([(algo, model)])
    _, pinned = pairs[0]
    assert serving_state(pinned).pinned
    algo.aot_warm_serving(pinned)  # pinned: compiles the glue, once


def test_compile_block_counts_from_after_the_warm_up_in_every_deploy(
    trained, artifacts
):
    """ISSUE 25: the compile ledger's boot mark sits after the batcher's
    bucket warm-up, in a plain deploy as under --aot, and
    ``aot.serveTimeCompiles`` reads the same count."""
    from predictionio_tpu.serving import BatcherConfig, CacheConfig

    root, _ = artifacts
    batching = BatcherConfig(
        max_batch_size=4, max_batch_delay_ms=0.0,
        warmup_body={"user": "1", "num": 5},
    )
    plain = QueryService(
        trained.variant, trained.ctx, instance_id=trained.instance.id,
        cache=CacheConfig(pin_model=True), batching=batching,
    )
    try:
        stats = plain.stats_json()
        assert "aot" not in stats  # opt-in block; the count is not
        # the warm-up compiled the batch program: boot work, not counted
        assert stats["compile"]["functions"]["top_k_items_batch"]["compiles"] >= 1
        assert stats["compile"]["sinceBoot"] == 0
        assert plain.batcher.submit({"user": "2", "num": 5})[0] == 200
        assert plain.stats_json()["compile"]["sinceBoot"] == 0  # warmed shape
        # num=40 takes the next k bucket (64): one fresh program
        assert plain.batcher.submit({"user": "2", "num": 40})[0] == 200
        assert plain.stats_json()["compile"]["sinceBoot"] == 1
        plain.reload()  # every later reload marks at its own end
        assert plain.stats_json()["compile"]["sinceBoot"] == 0
    finally:
        plain.close()
    tier1 = QueryService(
        trained.variant, trained.ctx, instance_id=trained.instance.id,
        aot=aot.AotConfig(enabled=True, root=root), batching=batching,
    )
    try:
        assert tier1.batcher.submit({"user": "3", "num": 5})[0] == 200
        stats = tier1.stats_json()
        assert stats["aot"]["tier"] == 1
        assert stats["aot"]["serveTimeCompiles"] == 0
        assert stats["compile"]["sinceBoot"] == stats["aot"]["serveTimeCompiles"]
    finally:
        tier1.close()
