"""The Engine: a typed DASE composition plus its train/eval/deploy logic.

Parity: ``core/src/main/scala/org/apache/predictionio/controller/Engine.scala``
(``class Engine[TD,EI,PD,Q,P,A]``, ``object Engine.train/eval``,
``makeSerializableModels``, ``prepareDeploy``, ``SimpleEngine``,
``EngineParams``) and ``EngineFactory.scala``.

An engine is data: the component *classes* plus a parallel ``EngineParams``
carrying each component's ``Params``. The workflow layer
(:mod:`predictionio_tpu.workflow`) instantiates and drives it.
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Any, Callable, Mapping, Sequence, Type

from predictionio_tpu.controller.base import create_doer
from predictionio_tpu.controller.components import (
    Algorithm,
    DataSource,
    FirstServing,
    IdentityPreparator,
    Preparator,
    SanityCheck,
    Serving,
)
from predictionio_tpu.controller.context import WorkflowContext
from predictionio_tpu.controller.params import EmptyParams, Params, params_from_json
from predictionio_tpu.controller.persistent import (
    PersistentModel,
    PersistentModelManifest,
    load_persistent_model,
)
from predictionio_tpu.utils.serialization import dumps_model, loads_model
from predictionio_tpu.utils.spans import span

__all__ = [
    "EngineParams",
    "Engine",
    "SimpleEngine",
    "EngineFactory",
    "resolve_engine_factory",
]

logger = logging.getLogger(__name__)


@dataclasses.dataclass(frozen=True)
class EngineParams:
    """Per-component parameters for one engine variant
    (parity: ``EngineParams`` in ``Engine.scala``).

    ``algorithms`` is an ordered list of ``(algorithm_name, params)`` —
    order defines prediction order into ``Serving.serve``.
    """

    datasource: Params = dataclasses.field(default_factory=EmptyParams)
    preparator: Params = dataclasses.field(default_factory=EmptyParams)
    algorithms: tuple = ()  # tuple[tuple[str, Params], ...]
    serving: Params = dataclasses.field(default_factory=EmptyParams)


class Engine:
    """DASE composition (parity: ``class Engine`` in ``Engine.scala``)."""

    def __init__(
        self,
        datasource_class: Type[DataSource],
        preparator_class: Type[Preparator],
        algorithms_class_map: Mapping[str, Type[Algorithm]],
        serving_class: Type[Serving],
    ):
        if not algorithms_class_map:
            raise ValueError("Engine needs at least one algorithm class")
        self.datasource_class = datasource_class
        self.preparator_class = preparator_class
        self.algorithms_class_map = dict(algorithms_class_map)
        self.serving_class = serving_class

    # ------------------------------------------------------------------ params
    def params_from_json(self, obj: Mapping[str, Any]) -> EngineParams:
        """Bind an engine.json ``params`` tree to typed ``EngineParams``
        (the ``JsonExtractor`` duty, done strictly — see
        :func:`predictionio_tpu.controller.params.params_from_json`).

        Expected shape (byte-compatible with reference engine.json)::

            {"datasource": {"params": {...}},
             "preparator": {"params": {...}},
             "algorithms": [{"name": "als", "params": {...}}, ...],
             "serving": {"params": {...}}}
        """

        def block(component: Any, label: str) -> Mapping[str, Any]:
            """Extract a component's ``params`` block, strictly: stray keys
            (e.g. params written without the ``params`` wrapper) raise
            instead of silently training with defaults."""
            if component is None:
                return {}
            if not isinstance(component, Mapping):
                raise ValueError(f"engine.json '{label}' must be an object")
            stray = set(component) - {"params", "name"}
            if stray:
                raise ValueError(
                    f"engine.json '{label}' has unexpected key(s) {sorted(stray)}; "
                    "component params belong under a 'params' block"
                )
            return component.get("params", {})

        def params_cls(cls: type) -> type:
            return getattr(cls, "params_class", EmptyParams)

        algo_entries = obj.get("algorithms") or []
        algorithms = []
        for entry in algo_entries:
            if not isinstance(entry, Mapping):
                raise ValueError(
                    f"engine.json algorithms entries must be objects like "
                    f'{{"name": ..., "params": {{...}}}}; got {entry!r}'
                )
            name = entry.get("name")
            if name not in self.algorithms_class_map:
                raise ValueError(
                    f"engine.json names unknown algorithm '{name}'; "
                    f"available: {sorted(self.algorithms_class_map)}"
                )
            cls = self.algorithms_class_map[name]
            algorithms.append(
                (name, params_from_json(params_cls(cls), block(entry, f"algorithms[{name}]")))
            )
        if not algorithms:
            # Default: first registered algorithm with empty params.
            first = next(iter(self.algorithms_class_map))
            algorithms = [(first, params_from_json(params_cls(self.algorithms_class_map[first]), {}))]

        return EngineParams(
            datasource=params_from_json(
                params_cls(self.datasource_class), block(obj.get("datasource"), "datasource")
            ),
            preparator=params_from_json(
                params_cls(self.preparator_class), block(obj.get("preparator"), "preparator")
            ),
            algorithms=tuple(algorithms),
            serving=params_from_json(
                params_cls(self.serving_class), block(obj.get("serving"), "serving")
            ),
        )

    # ------------------------------------------------------------------ doers
    def _make_algorithms(self, engine_params: EngineParams) -> list[tuple[str, Algorithm]]:
        out = []
        for name, params in engine_params.algorithms:
            if name not in self.algorithms_class_map:
                raise ValueError(f"Unknown algorithm '{name}'")
            out.append((name, create_doer(self.algorithms_class_map[name], params)))
        return out

    @staticmethod
    def _sanity(obj: Any, enabled: bool, label: str) -> None:
        if enabled and isinstance(obj, SanityCheck):
            logger.info("Sanity-checking %s", label)
            obj.sanity_check()

    # ------------------------------------------------------------------ train
    def train(
        self,
        ctx: WorkflowContext,
        engine_params: EngineParams,
        sanity_check: bool = False,
        stop_after_read: bool = False,
        stop_after_prepare: bool = False,
        timings: dict | None = None,
        warm_models: Sequence[tuple[str, Any]] | None = None,
    ) -> list[Any]:
        """Run DASE training; returns one model per algorithm
        (parity: ``object Engine.train``; the ``stop_after_*`` flags mirror
        ``WorkflowParams.stopAfterRead/Prepare``). When ``timings`` is a
        dict, per-phase wall-clock seconds are recorded into it
        (read/prepare/train:<name>, each a span of ``utils/spans.py``:
        ``train.read``, ``train.prepare``, ``train.algorithm``) — the
        EngineInstance timing surface of
        SURVEY.md section 6.1. ``warm_models`` (``models_from_bytes`` of a
        previous COMPLETED instance) hands each algorithm its predecessor
        via ``ctx.warm_model`` for warm-started retrains."""
        import dataclasses as _dc

        def _timed(label: str, fn, span_name: str, enclosing: bool = False):
            with span(span_name, enclosing=enclosing) as phase:
                result = fn()
            if timings is not None:
                timings[label] = round(phase.seconds, 3)
            return result

        # Instantiate algorithms first so a bad engine.json fails before the
        # (expensive) data read — mirrors the reference's early reflection.
        algorithms = self._make_algorithms(engine_params)
        datasource = create_doer(self.datasource_class, engine_params.datasource)
        td = _timed(
            "read", lambda: datasource.read_training_base(ctx), "train.read"
        )
        self._sanity(td, sanity_check, "training data")
        if stop_after_read:
            return []
        preparator = create_doer(self.preparator_class, engine_params.preparator)
        pd = _timed(
            "prepare", lambda: preparator.prepare_base(ctx, td), "train.prepare"
        )
        self._sanity(pd, sanity_check, "prepared data")
        if stop_after_prepare:
            return []
        # pair warm models to algorithms by NAME (position as tie-break for
        # duplicate names): a reordered algorithms list must still seed
        # every algorithm whose predecessor exists
        warm_pool = list(warm_models) if warm_models else []

        def take_warm(i: int, name: str):
            if i < len(warm_pool) and warm_pool[i] is not None and warm_pool[i][0] == name:
                model = warm_pool[i][1]
                warm_pool[i] = None
                return model
            for j, entry in enumerate(warm_pool):
                if entry is not None and entry[0] == name:
                    warm_pool[j] = None
                    return entry[1]
            return None

        models = []
        for i, (name, algo) in enumerate(algorithms):
            logger.info("Training algorithm '%s' (%s)", name, type(algo).__name__)
            a_ctx = ctx
            warm = take_warm(i, name)
            if warm is not None:
                a_ctx = _dc.replace(ctx, warm_model=warm)
            key = f"train:{name}"
            if timings is not None and key in timings:
                key = f"train:{name}#{i}"  # same algorithm listed twice
            models.append(
                # the algorithm's own leaves (transfer, bucketing, sweeps,
                # readback) lie inside this one
                _timed(
                    key, lambda a=algo, c=a_ctx: a.train_base(c, pd),
                    "train.algorithm", enclosing=True,
                )
            )
        return models

    # ------------------------------------------------------------------ eval
    def read_eval_folds(
        self, ctx: WorkflowContext, engine_params: EngineParams
    ) -> list:
        """Materialize the eval folds for these datasource params — split
        out so a parameter sweep whose candidates share datasource params
        reads and splits the events ONCE (the reference re-reads per
        candidate; see MetricEvaluator's fold cache)."""
        datasource = create_doer(self.datasource_class, engine_params.datasource)
        return list(datasource.read_eval_base(ctx))

    def eval(
        self,
        ctx: WorkflowContext,
        engine_params: EngineParams,
        folds: list | None = None,
    ) -> list[tuple[Any, list[tuple[Any, Any, Any]]]]:
        """Per eval fold: train on TD, batch-predict the held-out queries,
        serve, and pair with actuals -> ``[(EI, [(Q, P, A), ...]), ...]``
        (parity: ``object Engine.eval``). ``folds`` short-circuits the
        datasource read (fold reuse across sweep candidates)."""
        preparator = create_doer(self.preparator_class, engine_params.preparator)
        serving = create_doer(self.serving_class, engine_params.serving)
        if folds is None:
            folds = self.read_eval_folds(ctx, engine_params)
        results = []
        for fold_index, (td, eval_info, qa_pairs) in enumerate(folds):
            logger.info("Evaluating fold %d (%d queries)", fold_index, len(qa_pairs))
            pd = preparator.prepare_base(ctx, td)
            algos = self._make_algorithms(engine_params)
            models = [algo.train_base(ctx, pd) for _, algo in algos]
            # Supplement once, then both predict and serve see the
            # supplemented query — identical to the deploy path (SURVEY.md
            # section 4.2), so eval scores reflect served behavior.
            supplemented = [serving.supplement_base(q) for q, _ in qa_pairs]
            indexed_queries = list(enumerate(supplemented))
            # per-algorithm batch predictions, realigned by index
            per_algo: list[dict[int, Any]] = []
            for (name, algo), model in zip(algos, models):
                preds = dict(algo.batch_predict_base(model, indexed_queries))
                per_algo.append(preds)
            qpa = []
            for i, (_, a) in enumerate(qa_pairs):
                sq = supplemented[i]
                served = serving.serve_base(sq, [preds[i] for preds in per_algo])
                qpa.append((sq, served, a))
            results.append((eval_info, qpa))
        return results

    # ---------------------------------------------------------- persistence
    def models_to_bytes(
        self,
        instance_id: str,
        engine_params: EngineParams,
        models: Sequence[Any],
    ) -> bytes:
        """Serialize trained models for the ``Models`` repo
        (parity: ``Engine.makeSerializableModels``): each model is either

        * a :class:`PersistentModel` that saved itself -> store its manifest;
        * anything else -> pytree-pickled inline.
        """
        algos = self._make_algorithms(engine_params)
        if len(models) != len(algos):
            raise ValueError(
                f"Got {len(models)} models for {len(algos)} algorithms; "
                "models must align 1:1 with engine_params.algorithms"
            )
        entries: list[tuple[str, Any]] = []
        for (name, algo), model in zip(algos, models):
            if isinstance(model, PersistentModel):
                if model.save(instance_id, algo.params):
                    entries.append(
                        ("persistent", PersistentModelManifest(type(model).class_path()))
                    )
                    continue
            entries.append(("pickle", model))
        return dumps_model(entries)

    def models_from_bytes(
        self,
        engine_params: EngineParams,
        instance_id: str,
        model_blob: bytes,
        algos: Sequence[tuple[str, Algorithm]] | None = None,
    ) -> list[tuple[str, Any]]:
        """Re-hydrate the raw trained models of a completed instance as
        ``[(algorithm_name, model), ...]`` — no serving preparation. Used
        by deploy (via :meth:`prepare_deploy`) and by warm retrains.
        ``algos`` reuses a caller's already-constructed doers."""
        if algos is None:
            algos = self._make_algorithms(engine_params)
        entries = loads_model(model_blob)
        if len(entries) != len(algos):
            raise ValueError(
                f"Model blob holds {len(entries)} models but engine params "
                f"declare {len(algos)} algorithms"
            )
        out = []
        for (name, algo), (kind, payload) in zip(algos, entries):
            if kind == "persistent":
                model = load_persistent_model(payload, instance_id, algo.params)
            elif kind == "pickle":
                model = payload
            else:
                raise ValueError(f"Unknown model entry kind '{kind}'")
            out.append((name, model))
        return out

    def prepare_deploy(
        self,
        ctx: WorkflowContext,
        engine_params: EngineParams,
        instance_id: str,
        model_blob: bytes,
    ) -> tuple[Serving, list[tuple[Algorithm, Any]]]:
        """Re-hydrate serving components + models from a completed train
        (parity: ``Engine.prepareDeploy``). Runs each algorithm's
        ``prepare_model_for_serving`` (device placement / jit warm-up)."""
        serving = create_doer(self.serving_class, engine_params.serving)
        algos = self._make_algorithms(engine_params)
        named = self.models_from_bytes(
            engine_params, instance_id, model_blob, algos=algos
        )
        return serving, [
            (algo, algo.prepare_model_for_serving(model))
            for (name, algo), (_n, model) in zip(algos, named)
        ]


class SimpleEngine(Engine):
    """Single-datasource, single-algorithm engine with FirstServing
    (parity: ``SimpleEngine`` in ``Engine.scala``)."""

    def __init__(self, datasource_class: Type[DataSource], algorithm_class: Type[Algorithm]):
        super().__init__(
            datasource_class=datasource_class,
            preparator_class=IdentityPreparator,
            algorithms_class_map={"": algorithm_class},
            serving_class=FirstServing,
        )


#: An EngineFactory is any zero-arg callable returning an Engine
#: (parity: ``trait EngineFactory``). engine.json's ``engineFactory`` names
#: one as ``"package.module:attr"`` (or dotted path whose last element is
#: the attribute).
EngineFactory = Callable[[], Engine]


def resolve_engine_factory(path: str) -> EngineFactory:
    """Resolve an ``engineFactory`` string to the factory callable
    (parity: the reflective ``EngineFactory`` lookup in
    ``core/workflow/CreateWorkflow.scala``)."""
    from predictionio_tpu.utils.reflection import resolve_attr

    obj = resolve_attr(path)
    if isinstance(obj, Engine):
        return lambda: obj
    if not callable(obj):
        raise TypeError(f"Engine factory '{path}' is not callable")
    return obj
