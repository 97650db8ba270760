"""Int8 quantized serving tier (ISSUE 13) — ``pio deploy --quantize``.

Covers the quantization primitives (one rounding rule, zero-row guard,
idempotent re-quantize), the recall-guarded two-stage top-K kernels
(tie-stability vs the f32 exact path, replicated AND sharded), the
QuantizedTable fold-in contract (scatter re-quantizes only touched rows,
parity with a full rebuild), the int8 IVF slab composition, and the
QueryService integration (stats, cache-key isolation, release)."""

from __future__ import annotations

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from predictionio_tpu.ops import quant  # noqa: E402
from predictionio_tpu.templates.retrieval import serving_state  # noqa: E402


def _table(rows: int, dim: int, seed: int = 0, ties: bool = False):
    rng = np.random.default_rng(seed)
    mat = rng.standard_normal((rows, dim)).astype(np.float32)
    if ties:
        # adversarial equal-score blocks: byte-identical rows quantize
        # identically, so every path must order them by ascending id
        mat[10:18] = mat[10]
        mat[rows // 2 : rows // 2 + 5] = mat[rows // 2]
        mat[-3:] = mat[-3]
    return mat


# ---------------------------------------------------------------------------
# Primitives
# ---------------------------------------------------------------------------


class TestPrimitives:
    def test_round_trip_error_is_bounded_per_row(self):
        mat = _table(257, 24, seed=1)
        codes, scales = quant.quantize_table_host(mat)
        assert codes.dtype == np.int8
        deq = np.asarray(quant.dequantize(codes, scales))
        # symmetric rounding: |error| <= scale/2 per element
        assert np.all(np.abs(deq - mat) <= scales[:, None] / 2 + 1e-7)
        err = quant.quantization_error(mat, codes, scales)
        assert 0 < err["maxRelError"] <= 0.5 / 127 + 1e-4
        assert err["rmsError"] < err["maxAbsError"]

    def test_zero_rows_survive_exactly(self):
        mat = np.zeros((4, 8), np.float32)
        mat[2] = np.linspace(-1, 1, 8)
        codes, scales = quant.quantize_table_host(mat)
        assert scales[0] == 0.0 and np.all(codes[0] == 0)
        deq = np.asarray(quant.dequantize(codes, scales))
        np.testing.assert_array_equal(deq[0], 0.0)
        np.testing.assert_array_equal(deq[3], 0.0)

    def test_host_and_traced_quantizers_agree_bitwise(self):
        mat = _table(64, 16, seed=2)
        ch, sh = quant.quantize_table_host(mat)
        cd, sd = quant.quantize_rows(jnp.asarray(mat))
        np.testing.assert_array_equal(ch, np.asarray(cd))
        np.testing.assert_array_equal(sh, np.asarray(sd))

    def test_requantize_is_identity_on_quantized_rows(self):
        mat = _table(128, 32, seed=3)
        codes, scales = quant.quantize_table_host(mat)
        deq = np.asarray(quant.dequantize(codes, scales))
        codes2, scales2 = quant.quantize_table_host(deq)
        np.testing.assert_array_equal(codes, codes2)
        np.testing.assert_allclose(scales, scales2, rtol=1e-6)

    def test_overfetch_rule(self):
        assert quant.overfetch(10, 10_000) == 74  # k + 64 dominates
        assert quant.overfetch(100, 10_000) == 400  # 4k dominates
        assert quant.overfetch(100, 150) == 150  # clamped to catalog
        assert quant.overfetch(1, 1) == 1

    def test_quantize_slabs_per_lane(self):
        slabs = np.stack([_table(5, 8, seed=i) for i in range(3)])
        slabs[1, 2] = 0.0  # padding lane
        codes, scales = quant.quantize_slabs(slabs)
        assert codes.shape == slabs.shape and scales.shape == (3, 5)
        assert scales[1, 2] == 0.0
        deq = codes.astype(np.float32) * scales[..., None]
        assert np.all(np.abs(deq - slabs) <= scales[..., None] / 2 + 1e-7)


# ---------------------------------------------------------------------------
# Two-stage kernels
# ---------------------------------------------------------------------------


class TestTwoStageTopK:
    def _models(self, ties: bool = True, items: int = 3000, users: int = 500,
                dim: int = 24):
        users_f = _table(users, dim, seed=4)
        items_f = _table(items, dim, seed=5, ties=ties)
        return users_f, items_f

    def test_replicated_matches_f32_exact_on_dequantized(self):
        from predictionio_tpu.ops.als import top_k_items_batch

        users_f, items_f = self._models()
        uq = quant.quantize_table(users_f)
        iq = quant.quantize_table(items_f)
        rt = quant.QuantRuntime("int8", {"int8": 0}, 0)
        uidx = np.arange(64, dtype=np.int32)
        ids_q, sc_q = quant.topk_users(rt, uq, iq, uidx, 16)
        # ground truth: exact f32 kernel over the DEQUANTIZED tables —
        # the strongest equality a lossy storage format admits, and the
        # tie rule must match exactly (descending score, ascending id)
        ids_e, sc_e = top_k_items_batch(
            uidx, jnp.asarray(np.asarray(uq)), jnp.asarray(np.asarray(iq)),
            16,
        )
        np.testing.assert_array_equal(ids_q, np.asarray(ids_e))
        np.testing.assert_allclose(sc_q, np.asarray(sc_e), rtol=1e-5,
                                   atol=1e-6)

    def test_adversarial_ties_rank_ascending_id(self):
        users_f, items_f = self._models(ties=True)
        iq = quant.quantize_table(items_f)
        uq = quant.quantize_table(users_f)
        rt = quant.QuantRuntime("int8", {}, 0)
        ids, _ = quant.topk_users(rt, uq, iq, [10], 3000)
        row = ids[0].tolist()
        # the 8 duplicated rows (ids 10..17) hold identical scores and
        # must appear consecutively in ascending id order
        pos = row.index(10)
        assert row[pos : pos + 8] == list(range(10, 18))

    def test_sharded_matches_replicated_bitwise(self):
        from predictionio_tpu.parallel import sharding

        mesh = sharding.serving_mesh()
        if mesh is None:
            pytest.skip("needs a multi-device host mesh")
        users_f, items_f = self._models(ties=True)
        uq_s = sharding.shard_quantized_table(users_f, mesh)
        iq_s = sharding.shard_quantized_table(items_f, mesh)
        uq_r = quant.quantize_table(users_f)
        iq_r = quant.quantize_table(items_f)
        info = sharding.ShardInfo(
            mesh=mesh,
            rows={"user": users_f.shape[0], "item": items_f.shape[0]},
        )
        rt = quant.QuantRuntime("int8", {}, 0)
        uidx = np.arange(48, dtype=np.int32)
        ids_s, sc_s = quant.topk_users(rt, uq_s, iq_s, uidx, 16, shards=info)
        ids_r, sc_r = quant.topk_users(rt, uq_r, iq_r, uidx, 16)
        np.testing.assert_array_equal(ids_s, ids_r)
        np.testing.assert_allclose(sc_s, sc_r, rtol=1e-5, atol=1e-6)

    def test_padding_rows_never_rank(self):
        from predictionio_tpu.parallel import sharding

        mesh = sharding.serving_mesh()
        if mesh is None:
            pytest.skip("needs a multi-device host mesh")
        users_f, items_f = self._models(ties=False, items=101)  # pads to 104
        iq_s = sharding.shard_quantized_table(items_f, mesh)
        uq_s = sharding.shard_quantized_table(users_f, mesh)
        info = sharding.ShardInfo(
            mesh=mesh, rows={"user": users_f.shape[0], "item": 101}
        )
        rt = quant.QuantRuntime("int8", {}, 0)
        ids, _ = quant.topk_users(rt, uq_s, iq_s, np.arange(16), 101,
                                  shards=info)
        assert ids.max() < 101

    def test_runtime_accounts_rescore_depth(self):
        users_f, items_f = self._models(ties=False)
        uq = quant.quantize_table(users_f)
        iq = quant.quantize_table(items_f)
        rt = quant.QuantRuntime("int8", {"int8": 100}, 400)
        quant.topk_users(rt, uq, iq, [1, 2, 3], 10)
        stats = rt.stats_json()
        assert stats["queries"] == 3
        # k=10 buckets to 16; overfetch = 16 + 64
        assert stats["rescoreDepthMax"] == 80
        assert stats["candidatesRescored"] == 240
        assert stats["bytesSaved"] == 300
        assert stats["overfetch"] == "max(4k, k+64)"


# ---------------------------------------------------------------------------
# QuantizedTable fold-in contract
# ---------------------------------------------------------------------------


class TestQuantizedTableFoldIn:
    def test_getitem_dequantizes_rows(self):
        mat = _table(40, 8, seed=6)
        qt = quant.quantize_table(mat)
        row = np.asarray(qt[7])
        codes, scales = quant.quantize_table_host(mat)
        np.testing.assert_allclose(
            row, codes[7].astype(np.float32) * scales[7], rtol=1e-6
        )
        many = np.asarray(qt[np.asarray([3, 7, 3])])
        assert many.shape == (3, 8)
        assert qt.shape == (40, 8) and len(qt) == 40

    def test_set_rows_requantizes_only_touched_rows(self):
        from predictionio_tpu.workflow import device_state

        mat = _table(50, 8, seed=7)
        qt = quant.quantize_table(mat)
        new = _table(2, 8, seed=8)
        out = device_state.set_rows(qt, [4, 44], new)
        rebuilt = mat.copy()
        rebuilt[[4, 44]] = new
        full = quant.quantize_table(rebuilt)
        # scatter == full rebuild, bit-for-bit (the fold-in parity
        # guarantee: freshness survives quantization)
        np.testing.assert_array_equal(
            np.asarray(out.codes), np.asarray(full.codes)
        )
        np.testing.assert_array_equal(
            np.asarray(out.scales), np.asarray(full.scales)
        )
        # the original table object is untouched (copy-on-write swap)
        np.testing.assert_array_equal(
            np.asarray(qt.codes), quant.quantize_table_host(mat)[0]
        )

    def test_sharded_set_rows_routes_to_owner_shard(self):
        from predictionio_tpu.parallel import sharding
        from predictionio_tpu.workflow import device_state

        mesh = sharding.serving_mesh()
        if mesh is None:
            pytest.skip("needs a multi-device host mesh")
        mat = _table(64, 8, seed=9)
        qt = sharding.shard_quantized_table(mat, mesh)
        new = _table(3, 8, seed=10)
        out = device_state.set_rows(qt, [0, 31, 63], new)
        rebuilt = mat.copy()
        rebuilt[[0, 31, 63]] = new
        full_codes, full_scales = quant.quantize_table_host(rebuilt)
        np.testing.assert_array_equal(np.asarray(out.codes), full_codes)
        np.testing.assert_allclose(np.asarray(out.scales), full_scales,
                                   rtol=1e-6)

    def test_append_rows_grows_codes_and_scales(self):
        from predictionio_tpu.workflow import device_state

        mat = _table(20, 8, seed=11)
        qt = quant.quantize_table(mat)
        new = _table(4, 8, seed=12)
        out = device_state.append_rows(qt, new)
        assert out.shape == (24, 8)
        want_c, want_s = quant.quantize_table_host(new)
        np.testing.assert_array_equal(np.asarray(out.codes)[20:], want_c)
        np.testing.assert_allclose(np.asarray(out.scales)[20:], want_s,
                                   rtol=1e-6)

    def test_foldin_rows_reads_through_quantized_opposite(self):
        """The ALS fold-in gathers opposite-side factors; a quantized
        table must hand it dequantized f32 rows transparently."""
        from predictionio_tpu.online.foldin import foldin_rows

        opp = _table(30, 8, seed=13)
        qt = quant.quantize_table(opp)
        entries = [([1, 2, 3], [4.0, 5.0, 3.0]), ([7], [2.0])]
        rows_q = foldin_rows(qt, entries, reg=0.05)
        rows_f = foldin_rows(np.asarray(qt), entries, reg=0.05)
        np.testing.assert_allclose(rows_q, rows_f, rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# IVF int8 slabs
# ---------------------------------------------------------------------------


class TestQuantizedIVF:
    def _catalog(self, n=2048, dim=16, seed=14):
        rng = np.random.default_rng(seed)
        centers = rng.standard_normal((32, dim)).astype(np.float32)
        draw = centers[rng.integers(0, 32, n)]
        draw = draw + 0.3 * rng.standard_normal((n, dim)).astype(np.float32)
        return draw.astype(np.float32)

    def test_quantized_index_shrinks_slab_bytes(self):
        from predictionio_tpu.ops import ivf

        # dim 64 (the bench rank): per lane the f32 layout pays
        # 4K + 4 (ids) bytes, int8 pays K + 4 + 4 (ids + scale) — the
        # ratio approaches 4x as rank grows
        items = self._catalog(n=1024, dim=64)
        _, info_f = ivf.build_ivf(items, nlist=16, seed=0, iters=2)
        idx_q, info_q = ivf.build_ivf(
            items, nlist=16, seed=0, iters=2, quantize=True
        )
        assert info_q["quantized"] is True
        assert idx_q.slab_scales is not None
        assert info_f["bytesIndex"] > 3.0 * info_q["bytesIndex"]

    def test_quantized_probe_recall_matches_f32_probe(self):
        from predictionio_tpu.ops import ivf

        items = self._catalog()
        q = self._catalog(n=128, seed=15)
        idx_f, _ = ivf.build_ivf(items, nlist=16, seed=0, iters=4)
        idx_q, _ = ivf.build_ivf(items, nlist=16, seed=0, iters=4,
                                 quantize=True)
        fi, _ = ivf.ivf_topk_batch(jnp.asarray(q), idx_f, 10, 4)
        qi, _ = ivf.ivf_topk_batch(jnp.asarray(q), idx_q, 10, 4)
        fi, qi = np.asarray(fi), np.asarray(qi)
        overlap = np.mean(
            [len(set(a.tolist()) & set(b.tolist())) / 10 for a, b in
             zip(fi, qi)]
        )
        assert overlap >= 0.95  # same probes, int8-rounded candidate scores

    def test_sharded_quantized_index_matches_unsharded(self):
        from predictionio_tpu.ops import ivf
        from predictionio_tpu.parallel import sharding

        mesh = sharding.serving_mesh()
        if mesh is None:
            pytest.skip("needs a multi-device host mesh")
        items = self._catalog()
        q = self._catalog(n=64, seed=16)
        idx_q, info = ivf.build_ivf(items, nlist=16, seed=0, iters=2,
                                    quantize=True)
        rt = ivf.AnnRuntime(idx_q, 4, info)
        delta = ivf.shard_runtime(rt, mesh)
        assert delta["shards"] == mesh.shape["model"]
        ui, _ = ivf.ivf_topk_batch(jnp.asarray(q), idx_q, 8, 4)
        si, _ = sharding.sharded_ivf_topk(jnp.asarray(q), rt.index, 8, 4,
                                          mesh)
        np.testing.assert_array_equal(np.asarray(si), np.asarray(ui))

    def test_update_ivf_requantizes_touched_lanes_only(self):
        from predictionio_tpu.ops import ivf

        items = self._catalog()
        idx_q, _ = ivf.build_ivf(items, nlist=8, seed=0, iters=2,
                                 quantize=True)
        before_codes = np.array(idx_q.slabs)
        before_scales = np.array(idx_q.slab_scales)
        vec = self._catalog(n=1, seed=17)
        new_index, state, info = ivf.update_ivf(
            idx_q, np.asarray([0]), vec, idx_q.num_items
        )
        assert new_index.slabs.dtype == idx_q.slabs.dtype
        assert new_index.slab_scales is not None
        # the touched lane decodes to the quantized new vector
        pos = state["pos"][0]
        cl, lane = divmod(int(pos), new_index.slab_width)
        got = np.asarray(new_index.slabs)[cl, lane].astype(np.float32)
        got = got * np.asarray(new_index.slab_scales)[cl, lane]
        wc, ws = quant.quantize_table_host(vec)
        np.testing.assert_allclose(got, wc[0].astype(np.float32) * ws[0],
                                   rtol=1e-6)
        # every untouched lane is bit-identical
        after_codes = np.asarray(new_index.slabs)
        after_scales = np.asarray(new_index.slab_scales)
        changed = np.any(after_codes != before_codes, axis=-1)
        changed |= after_scales != before_scales
        assert changed.sum() <= 2  # old lane (if moved) + new lane


# ---------------------------------------------------------------------------
# QueryService integration
# ---------------------------------------------------------------------------


@pytest.fixture()
def quant_variant(memory_storage_env):
    from predictionio_tpu.controller import local_context
    from predictionio_tpu.data.event import DataMap, Event
    from predictionio_tpu.data.storage.base import App
    from predictionio_tpu.workflow import load_engine_variant, run_train

    Storage = memory_storage_env
    app_id = Storage.get_meta_data_apps().insert(App(id=0, name="quant-app"))
    rng = np.random.default_rng(21)
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
            for u, i in zip(rng.integers(0, 30, 900), rng.integers(0, 70, 900))
        ),
        app_id,
    )
    variant = load_engine_variant(
        {
            "id": "quant-eng",
            "version": "1",
            "engineFactory": "predictionio_tpu.templates."
            "recommendation:engine_factory",
            "datasource": {"params": {"appName": "quant-app"}},
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


def _query(qs, user="1", num=5):
    return qs.dispatch("POST", "/queries.json", {}, {"user": user, "num": num})


class TestQueryServiceQuantized:
    def _service(self, variant, **cache_kw):
        from predictionio_tpu.serving import CacheConfig
        from predictionio_tpu.workflow.serving import QueryService

        return QueryService(variant, cache=CacheConfig(**cache_kw))

    def test_quantized_deploy_serves_and_reports(self, quant_variant):
        _, variant = quant_variant
        qs = self._service(variant, quantize="int8")
        _, model = qs._algo_model_pairs[0]
        assert serving_state(model).quant is not None
        assert getattr(model.item_factors, "is_quantized", False)
        r = _query(qs)
        assert r.status == 200 and len(r.body["itemScores"]) == 5
        stats = qs.stats_json()
        cache = stats["cache"]
        assert cache["bytesPinned"] > 0
        # the per-dtype ledger: int8 codes + their f32 scales, no f32
        # factor bytes left pinned
        bbd = cache["bytesByDtype"]
        assert set(bbd) == {"int8", "scalesFloat32"}
        assert bbd["int8"] == cache["bytesPinned"] - bbd["scalesFloat32"]
        quant_block = stats["quant"]
        assert quant_block["dtype"] == "int8"
        m = quant_block["models"][0]
        assert m["bytesSaved"] > 0
        assert m["rescoreDepthMax"] >= 64  # overfetch floor k+64
        assert m["quantizationError"]["maxRelError"] <= 0.5 / 127 + 1e-4
        status = qs.status_json()
        assert status["quantize"] == "int8"
        assert status["bytesPinnedByDtype"] == bbd

    def test_quantized_results_match_dequantized_exact(self, quant_variant):
        """The served ranking equals the f32 exact path run over the
        dequantized tables — the two-stage kernel loses nothing beyond
        the storage format itself."""
        from predictionio_tpu.workflow.serving import QueryService

        _, variant = quant_variant
        qs_q = self._service(variant, quantize="int8")
        qs_f = QueryService(variant)
        _, model_q = qs_q._algo_model_pairs[0]
        _, model_f = qs_f._algo_model_pairs[0]
        # overwrite the f32 model with the dequantized tables
        model_f.user_factors = np.asarray(model_q.user_factors)
        model_f.item_factors = np.asarray(model_q.item_factors)
        for user in ("1", "7", "23"):
            rq = _query(qs_q, user=user, num=8)
            rf = _query(qs_f, user=user, num=8)
            assert [s["item"] for s in rq.body["itemScores"]] == [
                s["item"] for s in rf.body["itemScores"]
            ]

    def test_composes_with_shard_factors(self, quant_variant):
        _, variant = quant_variant
        qs_s = self._service(variant, quantize="int8", shard_factors=True)
        qs_r = self._service(variant, quantize="int8")
        _, model = qs_s._algo_model_pairs[0]
        assert serving_state(model).shards is not None
        for user in ("1", "7"):
            rs = _query(qs_s, user=user, num=8)
            rr = _query(qs_r, user=user, num=8)
            assert rs.status == 200
            assert [s["item"] for s in rs.body["itemScores"]] == [
                s["item"] for s in rr.body["itemScores"]
            ]

    def test_composes_with_ann(self, quant_variant):
        from predictionio_tpu.serving import AnnConfig, CacheConfig
        from predictionio_tpu.workflow.serving import QueryService

        _, variant = quant_variant
        qs = QueryService(
            variant,
            cache=CacheConfig(quantize="int8"),
            ann=AnnConfig(enabled=True, nlist=8, nprobe=8),
        )
        _, model = qs._algo_model_pairs[0]
        assert serving_state(model).ann.index.slab_scales is not None  # int8 slabs
        r = _query(qs)
        assert r.status == 200 and len(r.body["itemScores"]) == 5
        ann_stats = qs.stats_json()["ann"]["models"][0]
        assert ann_stats["quantized"] is True

    def test_batch_paths_agree_with_single_query(self, quant_variant):
        _, variant = quant_variant
        qs = self._service(variant, quantize="int8")
        single = [
            [s["item"] for s in _query(qs, user=u, num=6).body["itemScores"]]
            for u in ("1", "2", "3")
        ]
        batch = qs.handle_batch(
            [{"user": u, "num": 6} for u in ("1", "2", "3")]
        )
        batched = [
            [s["item"] for s in payload["itemScores"]]
            for status, payload in batch
        ]
        assert single == batched

    def test_cache_keys_isolate_quantized_results(self, quant_variant):
        """--quantize answers are (slightly) different results for the
        same body: the cache-mode tag must keep them in a disjoint key
        namespace from f32 entries."""
        _, variant = quant_variant
        qs_q = self._service(variant, quantize="int8", result_cache=True)
        qs_f = self._service(variant, result_cache=True)
        assert qs_q._cache_mode != qs_f._cache_mode
        assert qs_q._cache_mode.endswith("+qint8")

    def test_fold_in_parity_with_full_rebuild(self, quant_variant):
        """Satellite: a re-quantized touched row serves the same top-K
        as a full rebuild of the quantized table."""
        from predictionio_tpu.online.types import EventDelta, OnlineConfig

        _, variant = quant_variant
        qs = self._service(variant, quantize="int8")
        algo, model = qs._algo_model_pairs[0]
        host_u = np.array(np.asarray(model.user_factors))
        host_i = np.array(np.asarray(model.item_factors))
        cfg = OnlineConfig(enabled=True)
        upd = algo.online_foldin(
            model,
            [EventDelta("rate", "1", "7", 1, 5.0),
             EventDelta("rate", "newu", "3", 2, 5.0)],
            {},
            cfg,
        )
        qs.apply_online_update([(0, upd)])
        # rebuild: apply the same rows to the host copies, quantize whole
        uid = model.user_index
        rebuilt_u = host_u.copy()
        for j, ent in enumerate(upd.user_ids):
            row = uid.get(ent)
            if row is not None and row < rebuilt_u.shape[0]:
                rebuilt_u[row] = upd.user_rows[j]
            else:
                rebuilt_u = np.concatenate([rebuilt_u, upd.user_rows[j:j+1]])
        rebuilt_i = host_i.copy()
        iid = model.item_index
        for j, ent in enumerate(upd.item_ids):
            row = iid.get(ent)
            if row is not None and row < rebuilt_i.shape[0]:
                rebuilt_i[row] = upd.item_rows[j]
        # the folded quantized tables ARE the full-rebuild quantization
        got_u_codes = np.asarray(model.user_factors.codes)
        want_u_codes, _ = quant.quantize_table_host(rebuilt_u)
        np.testing.assert_array_equal(got_u_codes, want_u_codes)
        got_i_codes = np.asarray(model.item_factors.codes)
        want_i_codes, _ = quant.quantize_table_host(rebuilt_i)
        np.testing.assert_array_equal(got_i_codes, want_i_codes)
        # and the fresh user serves from the re-quantized row
        r = _query(qs, user="newu", num=3)
        assert r.status == 200 and len(r.body["itemScores"]) == 3

    def test_release_returns_dequantized_host_factors(self, quant_variant):
        from predictionio_tpu.workflow import device_state

        _, variant = quant_variant
        for shard in (False, True):
            qs = self._service(
                variant, quantize="int8", shard_factors=shard
            )
            pairs = qs._algo_model_pairs
            device_state.release_pairs(pairs)
            _, model = pairs[0]
            assert isinstance(model.user_factors, np.ndarray)
            assert model.user_factors.dtype == np.float32
            assert serving_state(model).quant is None
            assert not serving_state(model).pinned

    def test_reload_swaps_quantized_generations(self, quant_variant):
        _, variant = quant_variant
        qs = self._service(variant, quantize="int8")
        gen1_model = qs._algo_model_pairs[0][1]
        qs.reload()
        gen2_model = qs._algo_model_pairs[0][1]
        assert gen2_model is not gen1_model
        # the superseded generation's quant state was released
        assert serving_state(gen1_model).quant is None
        assert isinstance(gen1_model.user_factors, np.ndarray)
        assert _query(qs).status == 200


class TestTwoTowerQuantized:
    def test_twotower_quantize_hook_round_trip(self):
        from predictionio_tpu.data.aggregator import BiMap
        from predictionio_tpu.templates.twotower.engine import (
            TwoTowerAlgorithm,
            TwoTowerParams,
            TwoTowerServingModel,
        )

        rng = np.random.default_rng(30)
        uv = rng.standard_normal((20, 8)).astype(np.float32)
        iv = rng.standard_normal((40, 8)).astype(np.float32)
        model = TwoTowerServingModel(
            user_vecs=uv,
            item_vecs=iv,
            user_index=BiMap.string_index([str(i) for i in range(20)]),
            item_index=BiMap.string_index([f"i{i}" for i in range(40)]),
            seen={},
        )
        algo = TwoTowerAlgorithm(TwoTowerParams(embedding_dim=8))
        model, nbytes = algo.quantize_model_for_serving(model)
        assert nbytes == model.user_vecs.nbytes_codes \
            + model.user_vecs.nbytes_scales \
            + model.item_vecs.nbytes_codes + model.item_vecs.nbytes_scales
        from predictionio_tpu.templates.twotower.engine import Query

        r = algo.predict(model, Query(user="3", num=4))
        assert len(r.item_scores) == 4
        batch = algo.batch_predict(model, [(0, Query(user="3", num=4))])
        assert [s.item for s in batch[0][1].item_scores] == [
            s.item for s in r.item_scores
        ]
        algo.release_pinned_model(model)
        assert isinstance(model.user_vecs, np.ndarray)
