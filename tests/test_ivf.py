"""IVF approximate-retrieval tests (ISSUE 6).

Covers the ops layer (build/permutation round-trip, tie-stable merge,
``nprobe == nlist`` bit-identity with exact top-K, recall on clustered
factors, cluster balancing), the template hooks (build/release, the
over-fetch filtering contract), and the serving integration (opt-in
default, ``/reload`` hot swap dropping old ANN state, mode-tagged cache
keys so exact and ANN entries never mix, ``/stats.json`` ann section).
"""

import numpy as np
import pytest

import jax.numpy as jnp

from predictionio_tpu.ops import ivf
from predictionio_tpu.ops.als import top_k_items_batch
from predictionio_tpu.ops.topk import top_k_host, top_k_permuted
from predictionio_tpu.serving import AnnConfig
from predictionio_tpu.templates.retrieval import serving_state


#: how far a full-probe IVF score may lie from the exact path's. Both are
#: float32 GEMMs at ``SCORE_PRECISION`` (HIGHEST since PR 21), one over the
#: item table in id order and one over the cluster-major slabs: the same
#: products summed in another order differ in the last place (measured
#: 0.52301013 against 0.5230101; float32 eps is 1.2e-7). Ids and order are
#: held exactly, ties included.
SCORE_RTOL = 1e-6


def clustered_factors(
    n: int, dim: int = 16, n_centers: int = 24, seed: int = 0, sigma: float = 0.15
) -> np.ndarray:
    """Unit-norm mixture-of-Gaussians rows — the clustered shape real
    factor matrices have (and the premise IVF exploits)."""
    rng = np.random.default_rng(seed)
    centers = rng.standard_normal((n_centers, dim)).astype(np.float32)
    x = centers[rng.integers(0, n_centers, n)]
    x = x + sigma * rng.standard_normal((n, dim)).astype(np.float32)
    return (x / np.linalg.norm(x, axis=1, keepdims=True)).astype(np.float32)


# ---------------------------------------------------------------------------
# ops: build
# ---------------------------------------------------------------------------


class TestBuild:
    def test_permutation_round_trip(self):
        x = clustered_factors(1500)
        index, info = ivf.build_ivf(x, nlist=16, seed=0, iters=4)
        ids = np.asarray(index.slab_ids)
        real = ids[ids < 1500]
        # cluster-major -> item id is a bijection over the catalog
        assert sorted(real.tolist()) == list(range(1500))
        # every slab row holds exactly its item's factor vector
        slabs = np.asarray(index.slabs)
        assert np.array_equal(slabs[ids < 1500], x[real])
        # padding rows are zeroed and carry the sentinel
        assert np.all(slabs[ids >= 1500] == 0.0)
        assert info["nlist"] == 16
        assert info["catalogItems"] == 1500
        assert 0 < info["fill"] <= 1.0

    def test_deterministic_per_seed(self):
        x = clustered_factors(800)
        a, _ = ivf.build_ivf(x, nlist=8, seed=3, iters=4)
        b, _ = ivf.build_ivf(x, nlist=8, seed=3, iters=4)
        assert np.array_equal(np.asarray(a.centroids), np.asarray(b.centroids))
        assert np.array_equal(np.asarray(a.slab_ids), np.asarray(b.slab_ids))

    def test_nlist_clamped_to_catalog(self):
        x = clustered_factors(10)
        index, _ = ivf.build_ivf(x, nlist=64, seed=0, iters=2)
        assert index.nlist <= 10
        ids = np.asarray(index.slab_ids)
        assert sorted(ids[ids < 10].tolist()) == list(range(10))

    def test_auto_nlist_is_sqrt(self):
        assert ivf.auto_nlist(10_000) == 100
        assert ivf.auto_nlist(1) == 1

    def test_balance_caps_slab_width(self):
        # everything in ONE tight blob: raw k-means piles most items
        # into few clusters; the balance cap must bound the slab width
        rng = np.random.default_rng(0)
        x = rng.standard_normal((1, 8)).astype(np.float32)
        x = x + 0.01 * rng.standard_normal((2000, 8)).astype(np.float32)
        index, _ = ivf.build_ivf(x, nlist=20, seed=0, iters=3, balance=1.3)
        cap = int(np.ceil(2000 / 20 * 1.3))
        assert index.slab_width <= cap
        ids = np.asarray(index.slab_ids)
        assert sorted(ids[ids < 2000].tolist()) == list(range(2000))

    def test_empty_catalog_rejected(self):
        with pytest.raises(ValueError):
            ivf.build_ivf(np.zeros((0, 8), np.float32))


# ---------------------------------------------------------------------------
# ops: tie-stable merge + query kernel
# ---------------------------------------------------------------------------


class TestMerge:
    @pytest.mark.parametrize("big_ids", [False, True])
    def test_top_k_permuted_tie_stable(self, big_ids):
        rng = np.random.default_rng(1)
        for _ in range(25):
            n = int(rng.integers(10, 120))
            s = rng.choice(
                [-1.5, -0.0, 0.0, 0.25, 0.25, 1.0], size=(3, n)
            ).astype(np.float32)
            ids = np.stack([rng.permutation(n) for _ in range(3)]).astype(
                np.int32
            )
            k = int(rng.integers(1, n))
            ti, ts = top_k_permuted(
                jnp.asarray(s), jnp.asarray(ids), k, big_ids=big_ids
            )
            for r in range(3):
                order = sorted(
                    range(n), key=lambda j: (-s[r, j], ids[r, j])
                )[:k]
                assert np.asarray(ti)[r].tolist() == [
                    int(ids[r, j]) for j in order
                ]
                assert np.asarray(ts)[r].tolist() == [
                    float(s[r, j]) for j in order
                ]

    def test_top_k_host_matches_device_rule(self):
        rng = np.random.default_rng(2)
        s = rng.standard_normal((5, 200)).astype(np.float32)
        s[:, 10:20] = 0.25  # ties
        hi, hv = top_k_host(s, 16)
        import jax.lax

        dv, di = jax.lax.top_k(jnp.asarray(s), 16)
        assert np.array_equal(hi, np.asarray(di))
        assert np.array_equal(hv, np.asarray(dv))
        # 1-D variant
        hi1, hv1 = top_k_host(s[0], 16)
        assert np.array_equal(hi1, np.asarray(di)[0])

    def test_nprobe_eq_nlist_bit_identical_to_exact(self):
        """At full probe the ids and their order are the exact path's;
        the scores agree to ``SCORE_RTOL`` (see there)."""
        x = clustered_factors(1200, dim=16)
        q = clustered_factors(40, dim=16, seed=9)
        index, _ = ivf.build_ivf(x, nlist=12, seed=0, iters=4)
        uidx = np.arange(40, dtype=np.int32)
        ei, es = top_k_items_batch(uidx, jnp.asarray(q), jnp.asarray(x), 17)
        ai, a_s = ivf.ivf_topk_users(uidx, jnp.asarray(q), index, 17, 12)
        assert np.array_equal(np.asarray(ei), np.asarray(ai))
        np.testing.assert_allclose(
            np.asarray(a_s), np.asarray(es), rtol=SCORE_RTOL, atol=0
        )
        # nprobe beyond nlist clamps to the same exact mode
        ai2, _ = ivf.ivf_topk_users(uidx, jnp.asarray(q), index, 17, 99)
        assert np.array_equal(np.asarray(ei), np.asarray(ai2))

    def test_recall_on_clustered_factors(self):
        # deterministic (seeded) recall@10 on clustered factors at an
        # 8/16 probe fraction is ~0.97 here; 0.9 leaves margin for
        # float drift across jax versions. The >= 0.95 product bar is
        # asserted where it belongs: on the bench sweep's measured
        # recall (test_ci_guards smoke guard).
        x = clustered_factors(3000, dim=16, n_centers=64)
        q = clustered_factors(64, dim=16, n_centers=64, seed=5)
        index, _ = ivf.build_ivf(x, nlist=16, seed=0, iters=6)
        uidx = np.arange(64, dtype=np.int32)
        ei, _ = top_k_items_batch(uidx, jnp.asarray(q), jnp.asarray(x), 10)
        ai, _ = ivf.ivf_topk_users(uidx, jnp.asarray(q), index, 10, 8)
        hits = sum(
            len(set(e) & set(a))
            for e, a in zip(
                np.asarray(ei).tolist(), np.asarray(ai).tolist()
            )
        )
        assert hits / (64 * 10) >= 0.9

    def test_sentinel_trimmed_when_candidates_short(self):
        # 1 probed cluster of ~60 items cannot fill k=64 -> sentinel
        # tail, trimmed by query_topk
        x = clustered_factors(600, dim=8, n_centers=10)
        index, info = ivf.build_ivf(x, nlist=10, seed=0, iters=4)
        runtime = ivf.AnnRuntime(index, nprobe=1, build_info=info)
        ids, scores = ivf.query_topk(runtime, x[0], 64)
        assert 0 < len(ids) <= 64
        assert all(i < 600 for i in ids)
        assert len(ids) == len(scores)
        assert all(np.isfinite(scores))

    def test_runtime_counters(self):
        x = clustered_factors(500, dim=8)
        index, info = ivf.build_ivf(x, nlist=8, seed=0, iters=3)
        runtime = ivf.AnnRuntime(index, nprobe=2, build_info=info)
        ivf.query_topk(runtime, x[0], 5)
        ivf.query_topk(runtime, x[1], 5)
        st = runtime.stats_json()
        assert st["queries"] == 2
        assert st["clustersScored"] == 4
        assert 0 < st["fractionOfCatalogScored"] <= 1.0
        assert st["nprobe"] == 2


# ---------------------------------------------------------------------------
# templates + serving integration
# ---------------------------------------------------------------------------


@pytest.fixture()
def rec_variant(memory_storage_env):
    """A trained recommendation engine over a clustered-ish catalog."""
    from predictionio_tpu.controller import local_context
    from predictionio_tpu.data.event import DataMap, Event
    from predictionio_tpu.data.storage.base import App
    from predictionio_tpu.workflow import load_engine_variant, run_train

    Storage = memory_storage_env
    app_id = Storage.get_meta_data_apps().insert(App(id=0, name="ivf-app"))
    rng = np.random.default_rng(7)
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
            for u, i in zip(
                rng.integers(0, 40, 2500), rng.integers(0, 150, 2500)
            )
        ),
        app_id,
    )
    variant = load_engine_variant(
        {
            "id": "ivf-eng",
            "version": "1",
            "engineFactory": "predictionio_tpu.templates."
            "recommendation:engine_factory",
            "datasource": {"params": {"appName": "ivf-app"}},
            "algorithms": [
                {
                    "name": "als",
                    "params": {
                        "rank": 8,
                        "numIterations": 2,
                        "lambda": 0.05,
                        "seed": 5,
                        "serveOnDevice": True,
                        "deviceLatencyBudgetMs": 0,
                    },
                }
            ],
        }
    )
    run_train(variant, local_context())
    return Storage, variant


def _exact_equiv_ann() -> AnnConfig:
    # nprobe >= nlist: ANN answers rank as the exact path does, so
    # integration asserts are deterministic
    return AnnConfig(enabled=True, nlist=8, nprobe=8, kmeans_iters=3)


def assert_same_ranking(got, want):
    """Two ``handle_batch`` answer lists agree: status, item ids and
    their order equal, scores within ``SCORE_RTOL``."""
    assert len(got) == len(want)
    for (g_status, g), (w_status, w) in zip(got, want):
        assert g_status == w_status
        assert [s["item"] for s in g["itemScores"]] == [
            s["item"] for s in w["itemScores"]
        ]
        np.testing.assert_allclose(
            [s["score"] for s in g["itemScores"]],
            [s["score"] for s in w["itemScores"]],
            rtol=SCORE_RTOL, atol=0,
        )


class TestServingIntegration:
    def test_ann_strictly_opt_in(self, rec_variant):
        import inspect

        from predictionio_tpu.workflow.serving import QueryService

        sig = inspect.signature(QueryService.__init__)
        assert sig.parameters["ann"].default is None
        assert AnnConfig().enabled is False
        _, variant = rec_variant
        qs = QueryService(variant)
        assert qs.ann_config is None
        assert qs._cache_mode == "exact"
        model = qs._algo_model_pairs[0][1]
        assert serving_state(model).ann is None
        assert "ann" not in qs.stats_json()
        assert qs.status_json()["ann"] is False
        # a disabled config is treated exactly like none
        qs2 = QueryService(variant, ann=AnnConfig(enabled=False))
        assert qs2.ann_config is None

    def test_ann_batch_matches_exact_at_full_probe(self, rec_variant):
        from predictionio_tpu.workflow.serving import QueryService

        _, variant = rec_variant
        bodies = [{"user": str(u), "num": 5} for u in range(25)]
        exact = QueryService(variant).handle_batch(bodies)
        qs = QueryService(variant, ann=_exact_equiv_ann())
        assert serving_state(qs._algo_model_pairs[0][1]).ann is not None
        assert_same_ranking(qs.handle_batch(bodies), exact)

    def test_ann_single_predict_serves_k_items(self, rec_variant):
        from predictionio_tpu.workflow.serving import QueryService

        _, variant = rec_variant
        qs = QueryService(
            variant, ann=AnnConfig(enabled=True, nlist=8, nprobe=2)
        )
        r = qs.dispatch("POST", "/queries.json", {}, {"user": "1", "num": 7})
        assert r.status == 200
        assert len(r.body["itemScores"]) == 7
        st = qs.stats_json()["ann"]
        assert st["models"][0]["queries"] >= 1
        assert 0 < st["models"][0]["fractionOfCatalogScored"] <= 1.0
        assert st["models"][0]["buildSeconds"] >= 0

    def test_reload_hot_swaps_ann_state(self, rec_variant):
        from predictionio_tpu.workflow.serving import QueryService

        _, variant = rec_variant
        qs = QueryService(variant, ann=_exact_equiv_ann())
        old_model = qs._algo_model_pairs[0][1]
        old_runtime = serving_state(old_model).ann
        assert old_runtime is not None
        qs.reload()
        # the superseded generation's index is dropped (release hook)...
        assert serving_state(old_model).ann is None
        # ...and the new generation carries its own, rebuilt state
        new_model = qs._algo_model_pairs[0][1]
        assert serving_state(new_model).ann is not None
        assert serving_state(new_model).ann is not old_runtime
        assert qs._ann_runtimes == [serving_state(new_model).ann]

    def test_cache_keys_are_mode_tagged(self, rec_variant):
        from predictionio_tpu.serving import CacheConfig
        from predictionio_tpu.workflow.serving import QueryService

        _, variant = rec_variant
        body = {"user": "1", "num": 5}
        qs_exact = QueryService(
            variant, cache=CacheConfig(result_cache=True)
        )
        qs_ann = QueryService(
            variant,
            cache=CacheConfig(result_cache=True),
            ann=AnnConfig(enabled=True, nlist=8, nprobe=2),
        )
        qs_exact.dispatch("POST", "/queries.json", {}, body)
        qs_ann.dispatch("POST", "/queries.json", {}, body)
        (exact_key,) = qs_exact._result_cache._entries.keys()
        (ann_key,) = qs_ann._result_cache._entries.keys()
        # same body, disjoint key namespaces: an exact entry can never
        # satisfy an ANN lookup or vice versa
        assert exact_key.startswith("exact|")
        assert ann_key.startswith("ann[nlist=8,nprobe=2]|")
        assert exact_key != ann_key
        assert exact_key.split("|", 1)[1] == ann_key.split("|", 1)[1]

    def test_ann_composes_with_microbatcher(self, rec_variant):
        from predictionio_tpu.serving import BatcherConfig
        from predictionio_tpu.workflow.serving import QueryService

        _, variant = rec_variant
        exact = QueryService(variant).handle_batch([{"user": "2", "num": 4}])[0]
        qs = QueryService(
            variant,
            batching=BatcherConfig(max_batch_size=4, max_batch_delay_ms=0.0),
            ann=_exact_equiv_ann(),
        )
        try:
            assert_same_ranking(
                [qs.batcher.submit({"user": "2", "num": 4})], [exact]
            )
        finally:
            qs.close()


class TestTemplateHooks:
    def test_similarproduct_blacklist_overfetch(self):
        """Blacklisting the most-similar (popular) items must not shrink
        the result below num: the ANN path over-fetches num + |excluded|
        candidates before the final merge."""
        from predictionio_tpu.data.aggregator import BiMap
        from predictionio_tpu.templates.similarproduct.engine import (
            ALSAlgorithm,
            ALSAlgorithmParams,
            Query,
            SimilarProductModel,
        )

        x = clustered_factors(400, dim=8, n_centers=8, seed=3)
        index = BiMap.string_index([f"i{j}" for j in range(400)])
        model = SimilarProductModel(
            item_factors=x, item_index=index, categories={}
        )
        algo = ALSAlgorithm(ALSAlgorithmParams())
        model, _ = algo.build_ann_for_serving(
            model, AnnConfig(enabled=True, nlist=8, nprobe=8, kmeans_iters=3)
        )
        base = algo.predict(model, Query(items=("i0",), num=8))
        top_items = [s.item for s in base.item_scores]
        assert len(top_items) == 8
        # blacklist the entire top-8: still 8 (different) items
        filtered = algo.predict(
            model, Query(items=("i0",), num=8, black_list=tuple(top_items))
        )
        got = [s.item for s in filtered.item_scores]
        assert len(got) == 8
        assert not set(got) & set(top_items)
        assert "i0" not in got
        # whitelist/categories filters fall back to the exact path
        wl = algo.predict(
            model, Query(items=("i0",), num=3, white_list=("i5", "i9", "i17"))
        )
        assert {s.item for s in wl.item_scores} <= {"i5", "i9", "i17"}
        algo.release_ann_state(model)
        assert serving_state(model).ann is None

    def test_twotower_seen_overfetch_with_ann(self):
        from predictionio_tpu.data.aggregator import BiMap
        from predictionio_tpu.templates.twotower.engine import (
            Query,
            TwoTowerAlgorithm,
            TwoTowerParams,
            TwoTowerServingModel,
        )

        items = clustered_factors(300, dim=8, n_centers=6, seed=4)
        users = clustered_factors(10, dim=8, n_centers=6, seed=5)
        item_index = BiMap.string_index([f"i{j}" for j in range(300)])
        user_index = BiMap.string_index([f"u{j}" for j in range(10)])
        algo = TwoTowerAlgorithm(TwoTowerParams())
        # u0 has "seen" its entire exact top-10
        model = TwoTowerServingModel(
            user_vecs=users,
            item_vecs=items,
            user_index=user_index,
            item_index=item_index,
            seen={},
        )
        base = algo.predict(model, Query(user="u0", num=10))
        seen = {s.item for s in base.item_scores}
        model.seen = {"u0": seen}
        model, _ = algo.build_ann_for_serving(
            model, AnnConfig(enabled=True, nlist=6, nprobe=6, kmeans_iters=3)
        )
        out = algo.predict(model, Query(user="u0", num=10))
        got = [s.item for s in out.item_scores]
        assert len(got) == 10
        assert not set(got) & seen
        algo.release_ann_state(model)
        assert serving_state(model).ann is None


def test_default_import_path_never_touches_ivf():
    """With ANN off nothing may even import ops/ivf — the exact serving
    path must be byte-identical to a build without the module."""
    import subprocess
    import sys

    probe = (
        "import sys; "
        "import predictionio_tpu.workflow.serving; "
        "import predictionio_tpu.templates.recommendation; "
        "import predictionio_tpu.templates.twotower; "
        "import predictionio_tpu.templates.similarproduct; "
        "sys.exit(1 if 'predictionio_tpu.ops.ivf' in sys.modules else 0)"
    )
    import os

    proc = subprocess.run(
        [sys.executable, "-c", probe],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        capture_output=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stderr.decode()[-500:]
