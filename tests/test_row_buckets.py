"""A scoring program's rows follow the rows its chunk holds (ISSUE 26).

``ops.topk.bucket_rows`` is the one rule (pow2, floor 8, capped at
``TOPK_CHUNK``); ``chunked_topk`` pads every branch's chunk through it,
walks chunks of unequal rows, counts rows scored and rows real, and keys
the ``--aot`` batch programs by the row bucket. A deploy's warm-up must
still compile everything a live batch can hit — proven with the compile
ledger (``stats.compile.sinceBoot``).
"""

from __future__ import annotations

import threading
import types

import numpy as np
import pytest

from predictionio_tpu.data.aggregator import BiMap
from predictionio_tpu.ops.topk import bucket_k, bucket_rows, top_k_host
from predictionio_tpu.templates.recommendation.engine import (
    ALSAlgorithm,
    ALSAlgorithmParams,
    ALSModel,
)
from predictionio_tpu.templates.serving_util import (
    TOPK_CHUNK,
    serving_row_buckets,
)
from predictionio_tpu.utils import spans

CAP = 2048


@pytest.mark.parametrize(
    "n, want",
    [(1, 8), (7, 8), (8, 8), (9, 16), (32, 32), (33, 64), (2047, 2048),
     (2048, 2048), (5000, 2048)],
)
def test_bucket_rows_pow2_floor_8_capped(n, want):
    assert bucket_rows(n, CAP) == want
    # the cap wins over the floor and over the rounding: a dispatch never
    # scores more rows than it was allowed
    assert bucket_rows(n, 4) == 4
    assert bucket_rows(n, 20) == min(20, want)


def test_serving_row_buckets_are_a_batchers_and_the_cap():
    assert TOPK_CHUNK == CAP
    assert serving_row_buckets() == [8, 16, 32, CAP]  # a default batcher's
    assert serving_row_buckets(16) == [8, 16]
    assert serving_row_buckets(4) == [4]


# ---------------------------------------------------------------------------
# chunked_topk: every branch a CPU can reach
# ---------------------------------------------------------------------------

N_USERS, N_ITEMS, RANK, CHUNK = 90, 40, 8, 32


def _model() -> ALSModel:
    rng = np.random.default_rng(7)
    return ALSModel(
        user_factors=rng.standard_normal((N_USERS, RANK)).astype(np.float32),
        item_factors=rng.standard_normal((N_ITEMS, RANK)).astype(np.float32),
        user_index=BiMap({f"u{i}": i for i in range(N_USERS)}),
        item_index=BiMap({f"i{i}": i for i in range(N_ITEMS)}),
    )


def _ann(algo, model):
    from predictionio_tpu.serving.ann import AnnConfig

    # every cluster probed: the answer is the exact one
    cfg = AnnConfig(enabled=True, nlist=4, nprobe=4, seed=1)
    return algo.build_ann_for_serving(model, cfg)[0]


#: branch -> the serving hooks `pio deploy` would run for its flags
BRANCHES = {
    "host": lambda a, m: m,
    "device": lambda a, m: a.pin_model_for_serving(m)[0],
    "sharded": lambda a, m: a.shard_model_for_serving(m)[0],
    "int8": lambda a, m: a.quantize_model_for_serving(m)[0],
    "int8_sharded": lambda a, m: a.quantize_model_for_serving(
        m, shard=True)[0],
    "ann_unpinned": _ann,
    "ann_pinned": lambda a, m: _ann(a, a.pin_model_for_serving(m)[0]),
    "ann_int8": lambda a, m: _ann(a, a.quantize_model_for_serving(m)[0]),
    "ann_sharded": lambda a, m: _ann(a, a.shard_model_for_serving(m)[0]),
}


@pytest.fixture(scope="module")
def served():
    """Each branch's model, built once (k-means, sharding, int8 codes)."""
    algo = ALSAlgorithm(ALSAlgorithmParams(rank=RANK))
    return algo, {name: hook(algo, _model()) for name, hook in BRANCHES.items()}


@pytest.mark.parametrize("n", [1, 5, 32, 33, 2 * CHUNK + 3])
@pytest.mark.parametrize("branch", sorted(BRANCHES))
def test_chunked_topk_rows_in_input_order_and_rows_dispatched(
    served, branch, n, monkeypatch
):
    algo, models = served
    model = models[branch]
    monkeypatch.setattr(ALSAlgorithm, "BATCH_PREDICT_CHUNK", CHUNK)
    rng = np.random.default_rng(n)
    uidx = rng.permutation(N_USERS)[:n]
    ks = rng.integers(1, 12, size=n)
    valid = [(100 + s, int(u), int(k)) for s, (u, k) in enumerate(zip(uidx, ks))]
    collector = spans.Collector()
    previous = spans.bind(collector)
    try:
        got = list(algo.top_k_staged(model, valid))
    finally:
        spans.bind(previous)
    # the chunks, in order, hold the queries in input order
    assert [q for part, _, _ in got for q in part] == valid
    # what the rows are: top_k_host over the tables as served (int8
    # tables dequantized, a sharded table's padding rows cut)
    users = np.asarray(model.user_factors)[:N_USERS]
    items = np.asarray(model.item_factors)[:N_ITEMS]
    want_ids, want_scores = top_k_host(
        users[uidx] @ items.T, bucket_k(int(ks.max()), N_ITEMS))
    row = 0
    for part, ids, scores in got:
        for (_, _, k), r_ids, r_scores in zip(part, ids, scores):
            assert r_ids[:k] == want_ids[row][:k].tolist(), (branch, row)
            np.testing.assert_allclose(
                r_scores[:k], want_scores[row][:k], rtol=2e-5, atol=1e-6)
            row += 1
    assert row == n
    # what was dispatched: each chunk's own row bucket, never the cap
    counts = collector.take_counts()
    parts = [min(CHUNK, n - lo) for lo in range(0, n, CHUNK)]
    assert counts["rowsReal"] == n
    if branch == "host":
        assert counts["rowsScored"] == n  # a host GEMM pads nothing
    else:
        assert counts["rowsScored"] == sum(bucket_rows(p, CHUNK) for p in parts)
        assert counts["rowsScored"] < 2 * max(n, 8)
    # the selection's plan is counted where the program selects through
    # ops.topk.select_top_k: 40 items are ``lax.top_k``'s everywhere
    assert counts.get("select.plain", 0) == (
        len(parts) if branch == "device" else 0)
    assert "select.blocked" not in counts


# ---------------------------------------------------------------------------
# a deploy: the warm-up compiles every program a live batch can hit
# ---------------------------------------------------------------------------

N_TRAINED_USERS = 30


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    from predictionio_tpu.controller import local_context
    from predictionio_tpu.data.event import DataMap, Event
    from predictionio_tpu.data.storage import Storage
    from predictionio_tpu.data.storage.base import App
    from predictionio_tpu.workflow import load_engine_variant, run_train

    Storage.configure({
        "PIO_FS_BASEDIR": str(tmp_path_factory.mktemp("rows_store")),
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
        "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
    })
    app_id = Storage.get_meta_data_apps().insert(App(id=0, name="rows-test"))
    rng = np.random.default_rng(7)
    Storage.get_p_events().write(
        (
            Event(
                event="rate", entity_type="user",
                entity_id=str(i % N_TRAINED_USERS),
                target_entity_type="item",
                target_entity_id=str(int(rng.integers(50))),
                properties=DataMap({"rating": float(1 + int(rng.integers(5)))}),
            )
            for i in range(220)
        ),
        app_id,
    )
    variant = load_engine_variant({
        "id": "rows-test", "version": "1",
        "engineFactory": "predictionio_tpu.templates.recommendation:engine_factory",
        "datasource": {"params": {"appName": "rows-test"}},
        "algorithms": [{"name": "als", "params": {
            "rank": 8, "numIterations": 2, "lambda": 0.05, "seed": 7}}],
    })
    ctx = local_context()
    instance = run_train(variant, ctx)
    yield types.SimpleNamespace(variant=variant, ctx=ctx, instance=instance)
    Storage.configure(None)


def _batch_bodies(size: int) -> list:
    """``size`` queries, every third for a user the model never saw (it
    is answered without a row on the device). Where the first is one,
    the batcher's padding (copies of the first) brings no rows either."""
    return [
        {"user": f"ghost{size}-{i}" if (size + i) % 3 == 0
         else str((size + i) % N_TRAINED_USERS), "num": 10}
        for i in range(size)
    ]


def _submit_together(batcher, bodies: list) -> list:
    """One caller a query, all at once: they ride one batch (or, where a
    thread starts late, two)."""
    out: list = [None] * len(bodies)

    def call(i: int) -> None:
        out[i] = batcher.submit(bodies[i])

    threads = [threading.Thread(target=call, args=(i,)) for i in range(len(bodies))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not any(t.is_alive() for t in threads)
    return out


def _serve_every_batch_size(service) -> dict:
    for size in range(1, 33):
        bodies = _batch_bodies(size)
        for body, (status, payload) in zip(
                bodies, _submit_together(service.batcher, bodies)):
            assert status == 200, payload
            known = not body["user"].startswith("ghost")
            assert len(payload["itemScores"]) == (10 if known else 0)
    return service.stats_json()


@pytest.mark.parametrize("buckets", [(), (32,)], ids=["default", "buckets32"])
def test_deploy_warmup_leaves_no_compile_for_any_live_batch(trained, buckets):
    """`pio deploy --pin-model --batching [--batch-buckets 32]`: batches of
    every size 1-32, a third of each for unknown users, so the device
    sees row counts the batcher's own buckets never name."""
    import jax

    from predictionio_tpu.serving import BatcherConfig, CacheConfig
    from predictionio_tpu.workflow.serving import QueryService

    # a program an earlier test compiled for these shapes must not stand
    # in for this deploy's own warm-up
    jax.clear_caches()
    service = QueryService(
        trained.variant, trained.ctx, instance_id=trained.instance.id,
        cache=CacheConfig(pin_model=True),
        batching=BatcherConfig(
            max_batch_delay_ms=25.0, buckets=buckets,
            warmup_body={"user": "0", "num": 10}),
    )
    try:
        stats = _serve_every_batch_size(service)
    finally:
        service.close()
    assert stats["compile"]["sinceBoot"] == 0, stats["compile"]["functions"]
    b = stats["batcher"]
    assert b["bucketMisses"] == 0
    assert b["warmedBuckets"] == sorted(buckets or (1, 2, 4, 8, 16, 32))
    # rows that reached the device against rows it scored: the floor of 8
    # and the pow2 rounding, never the cap's 2048 a batch
    assert 0 < b["rowsReal"] <= b["rowsScored"] < 8 * b["rowsReal"]
    assert b["rowsScored"] <= 32 * b["batches"]
    # a batch of unknown users alone reaches no program
    assert b["select"]["blocked"] == 0 < b["select"]["plain"] <= b["batches"]


def test_aot_export_holds_every_row_bucket_and_serves_with_no_compile(
    trained, tmp_path
):
    import jax

    from predictionio_tpu.data.storage import Storage
    from predictionio_tpu.serving import BatcherConfig
    from predictionio_tpu.workflow import aot
    from predictionio_tpu.workflow.serving import QueryService

    jax.clear_caches()
    engine = trained.variant.build_engine()
    pairs = engine.prepare_deploy(
        trained.ctx, trained.variant.engine_params(engine), trained.instance.id,
        Storage.get_model_data_models().get(trained.instance.id).models,
    )[1]
    root = str(tmp_path / "aot")
    manifest = aot.export_instance(pairs, trained.instance.id, root)
    keys = {e["key"] for e in manifest["entries"]}
    n_items = int(np.asarray(pairs[0][1].item_factors).shape[0])
    for kb in aot.serving_buckets(n_items):
        for rows in (8, 16, 32, TOPK_CHUNK):
            assert f"top_k_items_batch_c{rows}_b{kb}" in keys
    service = QueryService(
        trained.variant, trained.ctx, instance_id=trained.instance.id,
        aot=aot.AotConfig(enabled=True, root=root),
        batching=BatcherConfig(
            max_batch_delay_ms=25.0, warmup_body={"user": "0", "num": 10}),
    )
    try:
        stats = _serve_every_batch_size(service)
    finally:
        service.close()
    block = stats["aot"]
    assert block["tier"] == 1 and block["disabled"] == 0
    assert block["serveTimeCompiles"] == 0
    # every batch found its row bucket's program: none fell to the jit
    assert block["misses"] == 0 and block["hits"] >= stats["batcher"]["batches"]


def test_two_handle_batch_calls_at_once_give_each_caller_its_answers(trained):
    """ISSUE 31: the batcher keeps two batches in flight, so
    ``QueryService.handle_batch`` runs against itself: on a pinned deploy
    two threads call it at once, over and over, with a reload under them,
    and every slot holds what the same body is answered alone."""
    from predictionio_tpu.serving import BatcherConfig, CacheConfig
    from predictionio_tpu.workflow.serving import QueryService

    service = QueryService(
        trained.variant, trained.ctx, instance_id=trained.instance.id,
        cache=CacheConfig(pin_model=True),
        batching=BatcherConfig(
            max_batch_delay_ms=1.0, warmup_body={"user": "0", "num": 10}),
    )
    try:
        users = [str(u) for u in range(N_TRAINED_USERS)] + ["ghost"]
        alone = {u: service.handle_batch([{"user": u, "num": 10}])[0] for u in users}
        assert all(status == 200 for status, _ in alone.values())
        assert len(alone["0"][1]["itemScores"]) == 10
        failures: list = []
        asked = [0, 0]
        counted = service.query_count
        start = threading.Barrier(2)

        def caller(which: int) -> None:
            rng = np.random.default_rng(which)
            for round_ in range(30):
                bodies = [{"user": u, "num": 10}
                          for u in rng.choice(users, int(rng.integers(1, 33)))]
                asked[which] += len(bodies)
                if round_ % 10 == 0:
                    start.wait(timeout=30)  # enter handle_batch together
                for body, (status, payload) in zip(
                        bodies, service.handle_batch(bodies)):
                    want = alone[body["user"]][1]["itemScores"]
                    got = payload["itemScores"] if status == 200 else None
                    if got is None or [s["item"] for s in got] != [
                            s["item"] for s in want]:
                        failures.append((which, round_, body, status, payload))
                if which == 0 and round_ == 15:
                    service.reload()  # the other caller holds its snapshot

        threads = [threading.Thread(target=caller, args=(w,), daemon=True)
                   for w in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
        assert failures == []
        # the serve tail ran once a query: no count lost between the two
        assert service.query_count - counted == sum(asked)
        # and through the batcher's two workers: every rider its own answer
        bodies = [{"user": users[i % len(users)], "num": 10} for i in range(96)]
        for body, (status, payload) in zip(
                bodies, _submit_together(service.batcher, bodies)):
            assert status == 200
            assert [s["item"] for s in payload["itemScores"]] == [
                s["item"] for s in alone[body["user"]][1]["itemScores"]]
        b = service.stats_json()["batcher"]
        assert sum(b["overlap"].values()) == b["batches"] and b["inflightBatch"] == 0
    finally:
        service.close()
