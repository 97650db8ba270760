"""Sharded factor serving (ISSUE 9) — ``pio deploy --shard-factors``.

The parity CI guard: sharded-vs-replicated ALS factors and top-K ids
must be comparable at a small catalog on the 1×8 host mesh (scores
within tolerance, ids tie-stable), sharding strictly opt-in, the
``/reload`` hot-swap must drop the previous generation's shard handles
on EVERY device, and per-device memory must follow the
``catalog / model_axis`` model the whole PR exists for.
"""

from __future__ import annotations

import gc
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.data.aggregator import BiMap
from predictionio_tpu.ops.als import ALSConfig, top_k_items_batch, train_als
from predictionio_tpu.parallel import sharding
from predictionio_tpu.templates.recommendation.engine import (
    ALSAlgorithm,
    ALSAlgorithmParams,
    ALSModel,
    Query,
)
from predictionio_tpu.templates.retrieval import serving_state


def _factors(U=70, I=130, K=8, seed=3):
    rng = np.random.default_rng(seed)
    uf = rng.standard_normal((U, K)).astype(np.float32)
    vf = rng.standard_normal((I, K)).astype(np.float32)
    return uf, vf


def _model(uf, vf) -> ALSModel:
    U, I = uf.shape[0], vf.shape[0]
    return ALSModel(
        uf.copy(),
        vf.copy(),
        BiMap.string_index([f"u{i}" for i in range(U)]),
        BiMap.string_index([f"i{i}" for i in range(I)]),
    )


# ---------------------------------------------------------------------------
# Kernel-level parity
# ---------------------------------------------------------------------------


class TestShardTable:
    def test_padding_and_placement(self):
        mesh = sharding.serving_mesh()
        assert mesh is not None and mesh.shape["model"] == 8
        uf, _ = _factors(U=61)
        tbl = sharding.shard_table(uf, mesh)
        assert tbl.shape == (64, uf.shape[1])  # padded to a multiple of 8
        # every device holds exactly one [8, K] shard — the memory model
        assert sharding.per_device_bytes(tbl) == 8 * uf.shape[1] * 4
        host = np.asarray(tbl)
        np.testing.assert_array_equal(host[:61], uf)
        np.testing.assert_array_equal(host[61:], 0.0)

    def test_byte_math_matches_measured(self):
        mesh = sharding.serving_mesh()
        uf, _ = _factors(U=100, K=16)
        tbl = sharding.shard_table(uf, mesh)
        assert sharding.per_device_bytes(tbl) == sharding.sharded_table_bytes(
            100, 16, 8
        )
        # the OOM-shape regression is pure shape math: the BENCH_r01
        # table cannot fit replicated, its 8-way shard must
        hbm = 17 * 2**30
        assert 2 * sharding.table_bytes(64_761_856, 64) > hbm
        assert 2 * sharding.sharded_table_bytes(64_761_856, 64, 8) < hbm

    def test_serving_mesh_caps_and_single_device(self):
        assert sharding.serving_mesh(shards=1) is None
        m2 = sharding.serving_mesh(shards=2)
        assert m2 is not None and m2.shape["model"] == 2


class TestShardedTopK:
    def test_ids_and_scores_match_replicated_exact(self):
        mesh = sharding.serving_mesh()
        uf, vf = _factors()
        ut, it = sharding.shard_table(uf, mesh), sharding.shard_table(vf, mesh)
        rng = np.random.default_rng(0)
        idx = rng.integers(0, uf.shape[0], 48).astype(np.int32)
        for k in (1, 5, 16):
            ids_s, sc_s = sharding.sharded_topk_users(
                idx, ut, it, k, vf.shape[0], mesh
            )
            ids_r, sc_r = top_k_items_batch(
                jnp.asarray(idx), jnp.asarray(uf), jnp.asarray(vf), k
            )
            np.testing.assert_array_equal(np.asarray(ids_s), np.asarray(ids_r))
            np.testing.assert_allclose(
                np.asarray(sc_s), np.asarray(sc_r), rtol=1e-6
            )

    def test_tie_stability_across_shard_boundaries(self):
        """Duplicate item rows land on DIFFERENT shards (ids 3, 77, 120
        of 130 items over 8 shards) yet must merge in ascending-id order
        exactly like the replicated kernel."""
        mesh = sharding.serving_mesh()
        uf, vf = _factors()
        vf[3] = vf[120]
        vf[77] = vf[120]
        uf[0] = vf[120]  # query aligned with the tied rows
        ut, it = sharding.shard_table(uf, mesh), sharding.shard_table(vf, mesh)
        idx = np.zeros(4, np.int32)
        ids_s, _ = sharding.sharded_topk_users(idx, ut, it, 6, vf.shape[0], mesh)
        ids_r, _ = top_k_items_batch(
            jnp.asarray(idx), jnp.asarray(uf), jnp.asarray(vf), 6
        )
        np.testing.assert_array_equal(np.asarray(ids_s), np.asarray(ids_r))
        assert {3, 77, 120} <= set(np.asarray(ids_s)[0].tolist())

    def test_padding_rows_never_rank(self):
        """Zero padding rows would outrank real negative scores if the
        num_items mask slipped — force an all-negative score row."""
        mesh = sharding.serving_mesh()
        uf, vf = _factors(U=8, I=13)
        uf[0] = 1.0
        vf[:] = -np.abs(vf)  # every real score strictly negative
        ut, it = sharding.shard_table(uf, mesh), sharding.shard_table(vf, mesh)
        ids_s, sc_s = sharding.sharded_topk_users(
            np.zeros(1, np.int32), ut, it, 13, 13, mesh
        )
        assert np.asarray(ids_s).max() < 13
        assert np.asarray(sc_s).max() < 0

    def test_gather_rows_resolves_across_shards(self):
        mesh = sharding.serving_mesh()
        uf, _ = _factors(U=37)
        ut = sharding.shard_table(uf, mesh)
        idx = np.asarray([0, 8, 17, 36], np.int32)
        np.testing.assert_array_equal(
            np.asarray(sharding.gather_rows(idx, ut, mesh)), uf[idx]
        )


# ---------------------------------------------------------------------------
# Parity guard: sharded-vs-replicated TRAINING on the 1×8 host mesh
# ---------------------------------------------------------------------------


class TestTrainingParity1x8:
    def test_all_model_mesh_matches_unsharded(self):
        """The ISSUE 9 parity satellite: a 1×8 (data=1, model=8) mesh —
        factor tables fully sharded, no data parallelism — must train
        factors matching the single-device run, and serving top-K over
        the two models must return identical ids."""
        from predictionio_tpu.controller.context import mesh_context

        rng = np.random.default_rng(7)
        n = 500
        rows = rng.integers(0, 60, n).astype(np.int64)
        cols = rng.integers(0, 40, n).astype(np.int64)
        vals = rng.uniform(1, 5, n).astype(np.float32)
        cfg = ALSConfig(rank=4, iterations=4, seed=5)
        single = train_als(rows, cols, vals, 60, 40, cfg)
        ctx = mesh_context(axis_sizes=(1, 8))
        assert ctx.mesh.shape["model"] == 8
        sharded = train_als(rows, cols, vals, 60, 40, cfg, mesh=ctx.mesh)
        np.testing.assert_allclose(
            np.asarray(single.user), np.asarray(sharded.user),
            rtol=1e-4, atol=1e-5,
        )
        np.testing.assert_allclose(
            np.asarray(single.item), np.asarray(sharded.item),
            rtol=1e-4, atol=1e-5,
        )
        # serving top-K ids agree between the two trainings AND between
        # the sharded and replicated serving layouts of each
        mesh = sharding.serving_mesh()
        it_single = sharding.shard_table(np.asarray(single.item), mesh)
        ut_single = sharding.shard_table(np.asarray(single.user), mesh)
        idx = np.arange(16, dtype=np.int32)
        ids_shard, _ = sharding.sharded_topk_users(
            idx, ut_single, it_single, 8, 40, mesh
        )
        ids_repl, _ = top_k_items_batch(
            jnp.asarray(idx),
            jnp.asarray(np.asarray(single.user)),
            jnp.asarray(np.asarray(single.item)),
            8,
        )
        np.testing.assert_array_equal(
            np.asarray(ids_shard), np.asarray(ids_repl)
        )


# ---------------------------------------------------------------------------
# Template serving hooks
# ---------------------------------------------------------------------------


class TestServingHooks:
    def test_shard_then_predict_matches_pinned(self):
        uf, vf = _factors()
        algo = ALSAlgorithm(ALSAlgorithmParams())
        m_s, nbytes = algo.shard_model_for_serving(_model(uf, vf))
        m_p, _ = algo.pin_model_for_serving(_model(uf, vf))
        assert serving_state(m_s).shards is not None
        assert serving_state(m_s).shards.num_shards == 8
        assert nbytes >= uf.nbytes + vf.nbytes  # padding only adds
        for u in ("u0", "u13", "u69"):
            got = algo.predict(m_s, Query(user=u, num=7))
            want = algo.predict(m_p, Query(user=u, num=7))
            assert [s.item for s in got.item_scores] == [
                s.item for s in want.item_scores
            ]
        queries = [(j, Query(user=f"u{j % uf.shape[0]}", num=5)) for j in range(40)]
        got_b = dict(algo.batch_predict(m_s, queries))
        want_b = dict(algo.batch_predict(m_p, queries))
        for j in got_b:
            assert [s.item for s in got_b[j].item_scores] == [
                s.item for s in want_b[j].item_scores
            ]

    def test_per_device_memory_is_sharded_not_replicated(self):
        uf, vf = _factors(U=96, I=160, K=16)
        algo = ALSAlgorithm(ALSAlgorithmParams())
        m, _ = algo.shard_model_for_serving(_model(uf, vf))
        per_dev = sharding.per_device_bytes(
            m.user_factors
        ) + sharding.per_device_bytes(m.item_factors)
        repl = uf.nbytes + vf.nbytes
        assert per_dev <= repl / 8 * 1.1, (per_dev, repl)

    def test_release_restores_host_rows_and_drops_every_shard(self):
        """Satellite: the superseded generation's shard handles must die
        on EVERY device — the global array handle owns all per-device
        buffers, so it becoming unreferenced (weakref dead after gc)
        proves no stale per-device buffer stays registered."""
        uf, vf = _factors()
        algo = ALSAlgorithm(ALSAlgorithmParams())
        m, _ = algo.shard_model_for_serving(_model(uf, vf))
        old_user, old_item = m.user_factors, m.item_factors
        assert {s.device for s in old_user.addressable_shards} == set(
            jax.devices()
        )
        ref_u, ref_i = weakref.ref(old_user), weakref.ref(old_item)
        del old_user, old_item
        algo.release_pinned_model(m)
        assert serving_state(m).shards is None
        assert isinstance(m.user_factors, np.ndarray)
        assert m.user_factors.shape == uf.shape  # padding stripped
        np.testing.assert_array_equal(m.user_factors, uf)
        np.testing.assert_array_equal(m.item_factors, vf)
        gc.collect()
        assert ref_u() is None and ref_i() is None, (
            "released generation's sharded tables are still referenced — "
            "stale per-device buffers would accumulate per /reload"
        )

    def test_ann_sharded_matches_unsharded(self):
        from predictionio_tpu.serving.ann import AnnConfig

        uf, vf = _factors(U=40, I=400, K=16)
        algo = ALSAlgorithm(ALSAlgorithmParams())
        cfg = AnnConfig(enabled=True, nlist=13, nprobe=4, seed=1)
        m_s, _ = algo.shard_model_for_serving(_model(uf, vf))
        m_s, info_s = algo.build_ann_for_serving(m_s, cfg)
        m_p, _ = algo.pin_model_for_serving(_model(uf, vf))
        m_p, _info = algo.build_ann_for_serving(m_p, cfg)
        assert info_s["shards"] == 8
        assert serving_state(m_s).ann.shard_mesh is not None
        assert serving_state(m_s).ann.host_index is not None
        for u in ("u0", "u7", "u39"):
            got = algo.predict(m_s, Query(user=u, num=9))
            want = algo.predict(m_p, Query(user=u, num=9))
            assert [s.item for s in got.item_scores] == [
                s.item for s in want.item_scores
            ], u
        queries = [(j, Query(user=f"u{j % 40}", num=6)) for j in range(30)]
        got_b = dict(algo.batch_predict(m_s, queries))
        want_b = dict(algo.batch_predict(m_p, queries))
        for j in got_b:
            assert [s.item for s in got_b[j].item_scores] == [
                s.item for s in want_b[j].item_scores
            ]

    def test_ann_sharded_nprobe_eq_nlist_is_exact(self):
        """The bit-identity contract survives the sharded layout: with
        every cluster probed, sharded IVF == replicated exact batch."""
        from predictionio_tpu.ops import ivf

        mesh = sharding.serving_mesh()
        rng = np.random.default_rng(2)
        vf = rng.standard_normal((300, 8)).astype(np.float32)
        q = rng.standard_normal((16, 8)).astype(np.float32)
        index, info = ivf.build_ivf(vf, nlist=12, seed=0, iters=4)
        rt = ivf.AnnRuntime(index, nprobe=12, build_info=info)
        ivf.shard_runtime(rt, mesh)
        ids_s, sc_s = sharding.sharded_ivf_topk(
            jnp.asarray(q), rt.index, 10, 12, mesh
        )
        uidx = np.arange(16, dtype=np.int32)
        ids_e, sc_e = top_k_items_batch(uidx, jnp.asarray(q), jnp.asarray(vf), 10)
        np.testing.assert_array_equal(np.asarray(ids_s), np.asarray(ids_e))
        np.testing.assert_array_equal(np.asarray(sc_s), np.asarray(sc_e))

    def test_twotower_shard_hook_parity(self):
        from predictionio_tpu.templates.twotower.engine import (
            TwoTowerAlgorithm,
            TwoTowerParams,
            TwoTowerServingModel,
        )
        from predictionio_tpu.templates.twotower.engine import Query as TTQuery

        rng = np.random.default_rng(4)
        U, I, K = 30, 80, 8
        uv = rng.standard_normal((U, K)).astype(np.float32)
        iv = rng.standard_normal((I, K)).astype(np.float32)

        def mk():
            return TwoTowerServingModel(
                user_vecs=uv.copy(),
                item_vecs=iv.copy(),
                user_index=BiMap.string_index([f"u{i}" for i in range(U)]),
                item_index=BiMap.string_index([f"i{i}" for i in range(I)]),
                seen={},
                loss_history=(),
            )

        algo = TwoTowerAlgorithm(TwoTowerParams())
        m_s, _ = algo.shard_model_for_serving(mk())
        m_h = mk()  # host numpy path as the oracle
        assert serving_state(m_s).shards is not None
        for u in ("u0", "u7", "u29"):
            got = algo.predict(m_s, TTQuery(user=u, num=6))
            want = algo.predict(m_h, TTQuery(user=u, num=6))
            assert [s.item for s in got.item_scores] == [
                s.item for s in want.item_scores
            ], u
        algo.release_pinned_model(m_s)
        assert isinstance(m_s.user_vecs, np.ndarray)
        assert m_s.user_vecs.shape == (U, K)
        np.testing.assert_array_equal(m_s.user_vecs, uv)


# ---------------------------------------------------------------------------
# QueryService integration: reload hot-swap under --shard-factors
# ---------------------------------------------------------------------------


@pytest.fixture()
def trained_variant(memory_storage_env):
    from predictionio_tpu.controller import local_context
    from predictionio_tpu.data.event import DataMap, Event
    from predictionio_tpu.data.storage.base import App
    from predictionio_tpu.workflow import load_engine_variant, run_train

    Storage = memory_storage_env
    app_id = Storage.get_meta_data_apps().insert(App(id=0, name="shard-app"))
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
            "id": "shard-eng",
            "version": "1",
            "engineFactory": "predictionio_tpu.templates."
            "recommendation:engine_factory",
            "datasource": {"params": {"appName": "shard-app"}},
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


class TestQueryServiceSharded:
    def test_sharded_service_matches_plain_service(self, trained_variant):
        from predictionio_tpu.serving import CacheConfig
        from predictionio_tpu.workflow.serving import QueryService

        _, variant = trained_variant
        qs_plain = QueryService(variant)
        qs_shard = QueryService(
            variant, cache=CacheConfig(shard_factors=True)
        )
        assert qs_shard.status_json()["shardFactors"] is True
        assert qs_plain.status_json()["shardFactors"] is False
        assert qs_shard.stats_json()["cache"]["factorShards"] == 8
        for u in ("1", "7", "29"):
            body = {"user": u, "num": 5}
            got = qs_shard.dispatch("POST", "/queries.json", {}, body)
            want = qs_plain.dispatch("POST", "/queries.json", {}, body)
            assert got.status == want.status == 200
            assert [s["item"] for s in got.body["itemScores"]] == [
                s["item"] for s in want.body["itemScores"]
            ], u

    def test_reload_drops_previous_generation_shards(self, trained_variant):
        """Satellite: ``/reload`` under ``--shard-factors`` must leave
        no stale per-device buffers of the superseded generation —
        asserted via weakrefs on the old generation's sharded tables
        (the jax.Array handle owns every device's buffer)."""
        from predictionio_tpu.serving import CacheConfig
        from predictionio_tpu.workflow.serving import QueryService

        _, variant = trained_variant
        qs = QueryService(variant, cache=CacheConfig(shard_factors=True))
        (_algo, model0), = qs._algo_model_pairs
        assert serving_state(model0).shards is not None
        refs = [
            weakref.ref(model0.user_factors),
            weakref.ref(model0.item_factors),
        ]
        old_user_shape = model0.user_factors.shape
        r = qs.dispatch("POST", "/reload", {}, None)
        assert r.status == 200
        (_algo1, model1), = qs._algo_model_pairs
        assert model1 is not model0
        assert serving_state(model1).shards is not None  # new generation re-sharded
        # the released generation fell back to trimmed host arrays...
        assert serving_state(model0).shards is None
        assert isinstance(model0.user_factors, np.ndarray)
        assert model0.user_factors.shape[0] <= old_user_shape[0]
        # ...and its sharded tables are collectable on every device
        del model0
        gc.collect()
        assert all(r() is None for r in refs), (
            "previous generation's shard handles survive /reload — "
            "per-device memory would grow by one catalog per swap"
        )
        # the swapped-in generation still serves
        got = qs.dispatch(
            "POST", "/queries.json", {}, {"user": "1", "num": 4}
        )
        assert got.status == 200 and len(got.body["itemScores"]) == 4
