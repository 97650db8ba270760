"""End-to-end test of the Recommendation template — the v1 acceptance gate
(SURVEY.md section 8.2 step 4): events in storage -> train via workflow ->
model blob -> deploy re-hydration -> correct top-N answers."""

import json

import numpy as np
import pytest

from predictionio_tpu.data.aggregator import BiMap
from predictionio_tpu.controller import (
    EngineParams,
    EngineParamsGenerator,
    Evaluation,
    local_context,
    mesh_context,
)
from predictionio_tpu.data.event import DataMap, Event
from predictionio_tpu.data.storage.base import App
from predictionio_tpu.templates.recommendation import (
    ALSAlgorithmParams,
    DataSourceParams,
    Query,
    engine_factory,
)
from predictionio_tpu.templates.recommendation.engine import PrecisionAtK
from predictionio_tpu.workflow import load_engine_variant, run_train


APP = "rec-test-app"

VARIANT = {
    "id": "recommendation",
    "version": "1",
    "engineFactory": "predictionio_tpu.templates.recommendation:engine_factory",
    "datasource": {"params": {"appName": APP}},
    "algorithms": [
        {
            "name": "als",
            "params": {"rank": 8, "numIterations": 10, "lambda": 0.01, "seed": 3},
        }
    ],
}


@pytest.fixture()
def rec_app(memory_storage_env):
    """Two taste clusters: even users love even items (ratings 4-5) and
    dislike odd items (ratings 1-2), and vice versa. Cross-group ratings
    are dense enough (0.5) that explicit ALS without bias terms can learn
    the boundary."""
    Storage = memory_storage_env
    app_id = Storage.get_meta_data_apps().insert(App(id=0, name=APP))
    le = Storage.get_l_events()
    le.init(app_id)
    rng = np.random.default_rng(0)
    for u in range(30):
        for i in range(20):
            same_group = (i % 2) == (u % 2)
            if same_group and rng.random() < 0.9:
                le.insert(
                    Event(
                        event="rate",
                        entity_type="user",
                        entity_id=str(u),
                        target_entity_type="item",
                        target_entity_id=str(i),
                        properties=DataMap({"rating": float(rng.integers(4, 6))}),
                    ),
                    app_id,
                )
            elif not same_group and rng.random() < 0.5:
                le.insert(
                    Event(
                        event="rate",
                        entity_type="user",
                        entity_id=str(u),
                        target_entity_type="item",
                        target_entity_id=str(i),
                        properties=DataMap({"rating": float(rng.integers(1, 3))}),
                    ),
                    app_id,
                )
    return Storage


def _deploy_and_query(Storage, instance, num=5, user="0"):
    eng = engine_factory()
    variant = load_engine_variant(VARIANT)
    ep = variant.engine_params(eng)
    blob = Storage.get_model_data_models().get(instance.id).models
    serving, pairs = eng.prepare_deploy(local_context(), ep, instance.id, blob)
    q = serving.supplement_base(Query(user=user, num=num))
    preds = [algo.predict_base(m, q) for algo, m in pairs]
    return serving.serve_base(q, preds)


class TestRecommendationEndToEnd:
    def test_train_deploy_query(self, rec_app):
        Storage = rec_app
        instance = run_train(load_engine_variant(VARIANT), local_context())
        assert instance.status == "COMPLETED"
        result = _deploy_and_query(Storage, instance, num=5, user="0")
        items = [s.item for s in result.item_scores]
        assert len(items) == 5
        # user 0 is in the even group: top recommendations skew even
        even = sum(1 for i in items if int(i) % 2 == 0)
        assert even >= 4, f"expected mostly even items, got {items}"
        # scores sorted descending
        scores = [s.score for s in result.item_scores]
        assert scores == sorted(scores, reverse=True)
        # the instance says where it ran and which kernels it took, so a
        # reader can tell a device run from a host run without jax
        import jax

        assert json.loads(instance.env["device"]) == {
            "platform": "cpu",
            "deviceKind": jax.devices()[0].device_kind,
            "count": len(jax.devices()),
        }
        kernels = json.loads(instance.env["kernels"])["als"]
        assert kernels["solver"] == "cholesky"
        assert kernels["bucketing"] == "host"
        assert kernels["mesh"] is None and instance.mesh_conf == {}
        algo_params = VARIANT["algorithms"][0]["params"]
        assert len(kernels["sweepSeconds"]) == algo_params["numIterations"]

    def test_auto_mesh_over_one_device_is_mesh_less(self, rec_app):
        """`pio train --mesh auto` on one chip must take the single-device
        kernels: over ONE device the auto mesh is the mesh-less context
        (a 1x1 mesh would route onto the sharded Cholesky/host path). An
        explicit data=1,model=1 keeps the mesh it asked for."""
        import jax

        one = jax.devices()[:1]
        assert mesh_context(devices=one) == local_context()
        instance = run_train(
            load_engine_variant(VARIANT), mesh_context(devices=one)
        )
        assert instance.mesh_conf == {}
        assert json.loads(instance.env["kernels"])["als"]["mesh"] is None
        explicit = mesh_context(axis_sizes=(1, 1), devices=one)
        assert explicit.has_mesh and explicit.num_devices == 1

    def test_unknown_user_returns_empty(self, rec_app):
        Storage = rec_app
        instance = run_train(load_engine_variant(VARIANT), local_context())
        result = _deploy_and_query(Storage, instance, user="nope")
        assert result.item_scores == ()

    def test_train_on_mesh(self, rec_app):
        Storage = rec_app
        ctx = mesh_context()  # 8 virtual CPU devices on the data axis
        instance = run_train(load_engine_variant(VARIANT), ctx)
        assert instance.status == "COMPLETED"
        assert instance.mesh_conf["devices"] == "8"
        result = _deploy_and_query(Storage, instance, num=5, user="1")
        odd = sum(1 for s in result.item_scores if int(s.item) % 2 == 1)
        assert odd >= 4

    def test_eval_precision_at_k(self, rec_app):
        from predictionio_tpu.workflow import run_evaluation

        eng = engine_factory()
        ds = DataSourceParams(app_name=APP, eval_k=3)
        candidates = [
            EngineParams(
                datasource=ds,
                algorithms=(("als", ALSAlgorithmParams(rank=2, num_iterations=10, lambda_=0.1)),),
            ),
            EngineParams(
                datasource=ds,
                algorithms=(("als", ALSAlgorithmParams(rank=4, num_iterations=10, lambda_=0.1)),),
            ),
        ]
        evaluation = Evaluation(engine=eng, metric=PrecisionAtK(5))
        instance, result = run_evaluation(
            evaluation, EngineParamsGenerator(candidates), local_context()
        )
        assert instance.status == "EVALCOMPLETED"
        # clustered data: random precision@5 over unseen items is ~0.23
        # (≈3 held-out positives among ≈13 unseen); the winning model must
        # comfortably beat that.
        assert result.best_score.score > 0.45
        assert len(result.engine_params_scores) == 2


class TestDeviceServingGuardrail:
    """serveOnDevice must probe real per-query latency at deploy time and
    fall back to host serving when it blows the budget (VERDICT r2 weak
    #5), leaving the outcome on the model for ``GET /``."""

    def _algo_and_model(self, budget_ms):
        from predictionio_tpu.templates.recommendation.engine import (
            ALSAlgorithm,
            ALSAlgorithmParams,
            ALSModel,
        )

        rng = np.random.default_rng(0)
        params = ALSAlgorithmParams(
            serve_on_device=True, device_latency_budget_ms=budget_ms
        )
        algo = ALSAlgorithm(params)
        model = ALSModel(
            user_factors=rng.normal(size=(8, 4)).astype(np.float32),
            item_factors=rng.normal(size=(6, 4)).astype(np.float32),
            user_index=BiMap.string_index(str(i) for i in range(8)),
            item_index=BiMap.string_index(str(i) for i in range(6)),
        )
        return algo, model

    def test_over_budget_falls_back_to_host(self):
        # an impossibly tight budget forces the fallback path
        algo, model = self._algo_and_model(budget_ms=1e-9)
        model = algo.prepare_model_for_serving(model)
        assert isinstance(model.item_factors, np.ndarray)
        r = algo.predict(model, Query(user="0", num=3))
        assert len(r.item_scores) == 3

    def test_disabled_probe_stays_on_device(self):
        import jax

        algo, model = self._algo_and_model(budget_ms=0)  # <=0 disables
        model = algo.prepare_model_for_serving(model)
        assert isinstance(model.item_factors, jax.Array)
        r = algo.predict(model, Query(user="0", num=3))
        assert len(r.item_scores) == 3

    def test_generous_budget_stays_on_device(self):
        import jax

        algo, model = self._algo_and_model(budget_ms=60_000.0)
        model = algo.prepare_model_for_serving(model)
        assert isinstance(model.item_factors, jax.Array)
