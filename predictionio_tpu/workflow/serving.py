"""Query server — deploy a trained engine instance behind HTTP.

Parity: ``core/workflow/CreateServer.scala`` (``MasterActor`` +
``ServerActor``): load the latest COMPLETED ``EngineInstance``, re-hydrate
models (``Engine.prepareDeploy``), answer ``POST /queries.json``, hot-swap
on ``POST /reload``, status on ``GET /``, plugin dispatch, and the
optional feedback loop that writes prediction events back to the event
server. The actor pair collapses into :class:`QueryService` — model state
swaps are a single attribute assignment behind a lock, and jit warm-up
happens at (re)load time so first queries pay no compile.
"""

from __future__ import annotations

import dataclasses
import datetime as _dt
import json
import logging
import queue
import threading
import urllib.error
import urllib.request
import uuid
from typing import Any, Mapping, Sequence

from predictionio_tpu.controller.context import WorkflowContext, local_context
from predictionio_tpu.controller.engine import Engine
from predictionio_tpu.controller.params import params_from_json, params_to_json
from predictionio_tpu.data.storage import Storage
from predictionio_tpu.serving import (
    AnnConfig,
    BatcherConfig,
    CacheConfig,
    MicroBatcher,
)
from predictionio_tpu.serving.cache import (
    CacheStats,
    ResultCache,
    Singleflight,
    canonical_key,
    extract_scope,
)
from predictionio_tpu.api.stats import HttpStats, LockStats
from predictionio_tpu.templates.retrieval import serving_state
from predictionio_tpu.utils.spans import CompileLedger, durations_ms, span
from predictionio_tpu.workflow.engine_json import EngineVariant

__all__ = [
    "EngineServerPlugin",
    "QueryService",
    "FeedbackConfig",
    "QueryServerError",
]

logger = logging.getLogger(__name__)


class QueryServerError(RuntimeError):
    pass


def _token_ok(presented: str, expected: str) -> bool:
    import hmac

    return hmac.compare_digest(str(presented), expected)


class EngineServerPlugin:
    """Serving-side plugin (parity: ``core/workflow/EngineServerPlugin.scala``).

    ``plugin_type`` is ``"outputblocker"`` (may rewrite the response) or
    ``"outputsniffer"`` (observes only). ``process`` receives and returns
    the JSON-ready prediction payload.
    """

    plugin_type = "outputsniffer"
    name = "plugin"

    def start(self, service: "QueryService") -> None:  # lifecycle hook
        pass

    def process(self, query: Any, prediction: Any, service: "QueryService") -> Any:
        return prediction


@dataclasses.dataclass(frozen=True)
class FeedbackConfig:
    """Feedback-loop settings (parity: ``--feedback --event-server-*``).

    Feedback is best-effort telemetry by contract: the defaults never let
    a slow or down event server stall or fail a query. ``block_ms`` opts
    into briefly blocking the query thread for a queue slot when the
    queue is full (higher delivery, bounded latency cost); the breaker
    knobs govern how fast the worker degrades to dropping while the
    event server is unreachable (docs/operations.md).
    """

    event_server_url: str  # e.g. http://127.0.0.1:7070
    access_key: str
    channel: str | None = None
    #: socket timeout for each feedback POST (the worker thread's, never
    #: the query thread's)
    timeout_s: float = 5.0
    #: >0: a full feedback queue blocks the query thread up to this long
    #: before dropping; 0 (default, `--no-feedback-blocking`) never blocks
    block_ms: float = 0.0
    #: consecutive post failures that open the feedback breaker — while
    #: open, events are dropped instantly instead of each paying a full
    #: connect timeout. 0 (default) disables the breaker: like every
    #: resilience knob it is strictly opt-in (`--feedback-breaker-threshold`)
    breaker_threshold: int = 0
    breaker_reset_s: float = 5.0


def _result_to_json(result: Any) -> Any:
    if hasattr(result, "to_json"):
        return result.to_json()
    if dataclasses.is_dataclass(result) and not isinstance(result, type):
        return dataclasses.asdict(result)
    return result


class QueryService:
    """One deployed engine instance (thread-safe; hot-reloadable)."""

    def __init__(
        self,
        variant: EngineVariant,
        ctx: WorkflowContext | None = None,
        plugins: Sequence[EngineServerPlugin] = (),
        feedback: FeedbackConfig | None = None,
        instance_id: str | None = None,
        batching: BatcherConfig | None = None,
        cache: CacheConfig | None = None,
        ann: AnnConfig | None = None,
        online=None,
        explore=None,
        replica_id: str | None = None,
        aot=None,
    ):
        self.variant = variant
        #: fleet identity (``pio deploy --replica-id``, set by the fleet
        #: supervisor): reported on /readyz, /stats.json and — so the
        #: router can tag routed cache keys with the serving generation —
        #: as X-PIO-Replica / X-PIO-Generation headers on query
        #: responses. None (the default, every non-fleet deploy) adds no
        #: headers and leaves responses byte-identical.
        self.replica_id = replica_id
        self.ctx = ctx or local_context()
        self.plugins = list(plugins)
        self.feedback = feedback
        self._requested_instance_id = instance_id
        self._lock = threading.Lock()
        # approximate retrieval (pio deploy --ann; docs/serving.md).
        # Strictly opt-in: ann=None (or a disabled config) leaves every
        # query on the exact scoring path and never imports ops/ivf.
        # Set BEFORE reload() so the index builds with the first load.
        self.ann_config = ann if ann is not None and ann.enabled else None
        #: retrieval-mode tag mixed into cache/singleflight keys so
        #: exact and ANN results can never serve each other — and, with
        #: --quantize, so a quantized deployment's (rescored) results
        #: never serve an f32 deployment's entries or vice versa
        self._cache_mode = (
            self.ann_config.cache_mode if self.ann_config is not None
            else "exact"
        )
        quantize_mode = (
            cache.quantize if cache is not None and cache.enabled else None
        )
        if quantize_mode:
            self._cache_mode = f"{self._cache_mode}+q{quantize_mode}"
        # exploration policies (pio deploy --explore; docs/serving.md).
        # Strictly opt-in: explore=None (or a disabled config) leaves
        # every response byte-identical and never imports
        # predictionio_tpu.experiments (CI-guarded like online/fleet).
        # The policy joins the cache-mode tag so an exploring
        # deployment's re-ranked results never serve a greedy
        # deployment's cache entries or vice versa.
        self.explore_config = (
            explore if explore is not None and explore.enabled else None
        )
        #: live Explorer (None unless --explore): public so the online
        #: runner can feed polled reward events back into the posterior
        self.explorer = None
        if self.explore_config is not None:
            from predictionio_tpu.experiments.explore import Explorer

            self.explorer = Explorer(self.explore_config)
            self._cache_mode = (
                f"{self._cache_mode}+x{self.explore_config.policy}"
            )
        #: AnnRuntime per ANN-built model of the LIVE generation
        #: (swapped with the pairs under the lock on every reload)
        self._ann_runtimes: list = []
        # query-path caching & coalescing (predictionio_tpu.serving.cache;
        # docs/performance.md). Strictly opt-in: cache=None (or an all-off
        # config) leaves /queries.json on the exact prior code path. Built
        # BEFORE reload() so the pin-model tier applies to the first load.
        self.cache_config = cache if cache is not None and cache.enabled else None
        # deploy-time AOT serving (pio deploy --aot; workflow/aot.py).
        # Strictly opt-in: aot=None (or a disabled config) never imports
        # workflow.aot and leaves every query on the exact prior code
        # path (CI-guarded like batching/caching/ann/online). When on,
        # reload() boots by DESERIALIZING the generation's exported
        # serving programs, and the compile ledger's count since boot
        # proves the request path compiles nothing after boot.
        self.aot_config = (
            aot if aot is not None and getattr(aot, "active", False) else None
        )
        #: every deploy counts its compiles (utils/spans.py): per jitted
        #: function, and since the boot mark — the `compile` block of
        #: /stats.json, and `aot.serveTimeCompiles` under --aot
        self._compiles = CompileLedger.install()
        #: what the HTTP threads spend around dispatch() (`http` block)
        self._http_stats = HttpStats()
        #: the interpreter lock as the beat thread sees it (`lock` block);
        #: the thread starts at the boot mark
        self._lock_stats = LockStats()
        self._cache_stats: CacheStats | None = None
        self._result_cache: ResultCache | None = None
        self._singleflight: Singleflight | None = None
        #: monotonically increments on every successful reload; keys the
        #: singleflight namespace and is reported on /stats.json so an
        #: operator can correlate cache flushes with model swaps
        self._model_generation = 0
        if self.cache_config is not None:
            self._cache_stats = CacheStats()
            if self.cache_config.result_cache:
                self._result_cache = ResultCache(
                    self.cache_config, self._cache_stats
                )
            if self.cache_config.coalesce:
                self._singleflight = Singleflight(self._cache_stats)
        self._engine: Engine | None = None
        self._serving = None
        self._algo_model_pairs: list = []
        self.instance = None
        self.start_time = _dt.datetime.now(_dt.timezone.utc)
        self.query_count = 0
        self.feedback_dropped = 0
        self.feedback_sent = 0
        self.feedback_failed = 0
        # graceful degradation (docs/operations.md): a failed /reload
        # keeps serving the last-good model and flags it here
        self.degraded = False
        self.last_reload_error: str | None = None
        self.last_reload_at: _dt.datetime | None = None
        #: set by the transport layer (console deploy): called by
        #: ``GET /stop`` to shut the HTTP server down (parity:
        #: CreateServer's stop route / `pio undeploy`)
        self.stop_server: Any = None
        #: when set, ``GET /stop`` requires ``?token=<stop_token>``
        #: (console deploy generates one and shares it with undeploy
        #: via a basedir token file)
        self.stop_token: str | None = None
        #: callbacks run first by :meth:`close` (and therefore by the
        #: drain path) — e.g. the endpoint-registry withdraw wired by
        #: ``pio deploy --announce-dir``
        self.on_close: list = []
        # one long-lived worker drains feedback posts — per-query threads
        # would grow unboundedly when the event server is slow
        self._feedback_queue: "queue.Queue | None" = None
        self._feedback_breaker = None
        if feedback is not None:
            from predictionio_tpu import resilience

            self._feedback_queue = queue.Queue(maxsize=10_000)
            if feedback.breaker_threshold > 0:
                # event-server unavailability degrades the loop to
                # dropping instantly instead of paying a full connect
                # timeout per event while the server is down
                self._feedback_breaker = resilience.CircuitBreaker(
                    failure_threshold=feedback.breaker_threshold,
                    reset_timeout_s=feedback.breaker_reset_s,
                    name="feedback",
                )
                resilience.register_stats("feedback", self._feedback_breaker)
            threading.Thread(target=self._feedback_worker, daemon=True).start()
        # online learning (pio deploy --online; docs/operations.md).
        # Strictly opt-in: online=None (or a disabled config) starts no
        # follower thread and leaves serving byte-identical — with the
        # flag off, predictionio_tpu.online is never even imported
        # (CI-guarded like batching/caching/ann/resilience)
        self.online_config = (
            online if online is not None and online.enabled else None
        )
        self.online = None
        #: monotonically increments on every applied partial update —
        #: the freshness counter beside the (full-reload) generation
        self._online_updates = 0
        self.reload()
        if self.online_config is not None:
            from predictionio_tpu.online.runner import OnlineRunner

            self.online = OnlineRunner(self, self.online_config)
        # cross-request micro-batching (predictionio_tpu.serving): when
        # enabled, /queries.json routes through the batcher so concurrent
        # requests share one handle_batch dispatch. Created AFTER reload()
        # so a warmup_body compiles against the loaded models.
        self.batcher: MicroBatcher | None = (
            MicroBatcher(self.handle_batch, batching)
            if batching is not None
            else None
        )
        # ready to serve: the batcher's bucket warm-up above compiled (or
        # loaded) its programs as BOOT work, so the mark reload() left
        # moves here; every later reload() marks at its own end
        self._compiles.mark_boot_complete()
        self._beat = self._start_beat()
        for p in self.plugins:
            p.start(self)

    def _start_beat(self):
        """The deploy's one beat thread (``serving/lockbeat.py``): it
        times the interpreter lock and catches the host standing still.
        It holds the stats, not the service, and ends with it (or once
        nobody holds the service any more)."""
        from predictionio_tpu.serving.lockbeat import LockBeat

        http_stats = self._http_stats
        batcher_stats = self.batcher.stats if self.batcher is not None else None

        def cpu_total_ns() -> int:
            workers = batcher_stats.cpu_ns_workers if batcher_stats else 0
            return workers + http_stats.cpu_ns_riders

        return LockBeat(self._lock_stats, cpu_total_ns, owner=self).start()

    def _feedback_worker(self) -> None:
        assert self._feedback_queue is not None
        assert self.feedback is not None
        timeout_s = self.feedback.timeout_s
        breaker = self._feedback_breaker
        while True:
            url, event = self._feedback_queue.get()
            try:
                if breaker is not None and not breaker.acquire():
                    # event server known-down: drop instantly rather than
                    # paying a full connect timeout per queued event
                    with self._lock:
                        self.feedback_dropped += 1
                    continue
                try:
                    req = urllib.request.Request(
                        url,
                        data=json.dumps(event, default=str).encode(),
                        headers={"Content-Type": "application/json"},
                        method="POST",
                    )
                    urllib.request.urlopen(req, timeout=timeout_s).read()
                except Exception as e:
                    if breaker is not None:
                        # 4xx proves the event server is UP (bad access
                        # key, invalid event) — only transport-level
                        # failures may open the breaker, same contract as
                        # the storage RPC
                        if (
                            isinstance(e, urllib.error.HTTPError)
                            and e.code < 500
                        ):
                            breaker.record_success()
                        else:
                            breaker.record_failure()
                    with self._lock:
                        self.feedback_failed += 1
                    # warning, not exception: a down event server logs one
                    # line per attempt, and the breaker bounds attempts
                    logger.warning("Feedback POST failed: %s", e)
                else:
                    if breaker is not None:
                        breaker.record_success()
                    with self._lock:
                        self.feedback_sent += 1
            finally:
                self._feedback_queue.task_done()

    # ---------------------------------------------------------------- load
    def _resolve_instance(self):
        repo = Storage.get_meta_data_engine_instances()
        if self._requested_instance_id:
            inst = repo.get(self._requested_instance_id)
            if inst is None:
                raise QueryServerError(
                    f"Engine instance '{self._requested_instance_id}' not found"
                )
            return inst
        inst = repo.get_latest_completed(
            self.variant.id, self.variant.version, self.variant.id
        )
        if inst is None:
            raise QueryServerError(
                f"No COMPLETED training of engine '{self.variant.id}' "
                f"(version '{self.variant.version}') found — run `pio train` first"
            )
        return inst

    def reload(self) -> None:
        """(Re)hydrate engine + models — the ``/reload`` hot swap
        (parity: MasterActor re-running prepareDeploy).

        Graceful degradation: once a model is serving, a failed reload
        (storage outage, missing blob, broken variant) NEVER wedges the
        service — the last-good model keeps serving, ``GET /`` reports
        ``degraded`` with the error, and the raised
        :class:`QueryServerError` says so. The initial load still raises:
        with nothing loaded there is nothing to degrade to."""
        try:
            instance = self._resolve_instance()
            engine = self.variant.build_engine()
            engine_params = engine.params_from_json(
                {
                    "datasource": {"params": json.loads(instance.datasource_params or "{}")},
                    "preparator": {"params": json.loads(instance.preparator_params or "{}")},
                    "algorithms": json.loads(instance.algorithms_params or "[]"),
                    "serving": {"params": json.loads(instance.serving_params or "{}")},
                }
                if instance.algorithms_params
                else self.variant.raw
            )
            model = Storage.get_model_data_models().get(instance.id)
            if model is None:
                raise QueryServerError(f"No model blob for instance '{instance.id}'")
            serving, pairs = engine.prepare_deploy(
                self.ctx, engine_params, instance.id, model.models
            )
            if (
                self.cache_config is not None
                and (
                    self.cache_config.pin_model
                    or self.cache_config.shard_factors
                    or self.cache_config.quantize is not None
                )
            ) or self.aot_config is not None:
                # device-resident tier: factor state pinned once per model
                # generation (lazy boundary — serving/ stays jax-free;
                # docs/performance.md). --shard-factors pins SHARDS per
                # device instead of replicas so per-device memory scales
                # as catalog / num_devices; --quantize pins int8 codes +
                # per-row scales for another ~4x on top (docs/serving.md).
                # --aot (which implies pinning) additionally boots by
                # deserializing the generation's exported programs, so
                # the request path compiles nothing (workflow/aot.py).
                from predictionio_tpu.workflow import device_state

                pairs, bytes_pinned = device_state.pin_pairs(
                    pairs,
                    shard=(
                        self.cache_config is not None
                        and self.cache_config.shard_factors
                    ),
                    quantize=(
                        self.cache_config.quantize
                        if self.cache_config is not None
                        else None
                    ),
                    aot=self.aot_config,
                    instance_id=instance.id,
                )
                if self._cache_stats is not None:
                    self._cache_stats.set_gauge("bytes_pinned", bytes_pinned)
                    self._cache_stats.set_gauge(
                        "bytes_by_dtype", device_state.bytes_by_dtype(pairs)
                    )
                    if self.cache_config.shard_factors:
                        self._cache_stats.set_gauge(
                            "factor_shards", device_state.shard_count(pairs)
                        )
            if self.ann_config is not None:
                # clustered-retrieval tier: IVF index built once per
                # model generation behind the same lazy jax boundary;
                # hot-swaps with the pairs on /reload (docs/serving.md)
                from predictionio_tpu.workflow import device_state

                pairs, _ann_infos = device_state.build_ann_pairs(
                    pairs, self.ann_config
                )
        except Exception as e:
            with self._lock:
                has_last_good = self._serving is not None
                if has_last_good:
                    self.degraded = True
                    self.last_reload_error = str(e)[:500]
                    self.last_reload_at = _dt.datetime.now(_dt.timezone.utc)
                    last_good = self.instance.id if self.instance else None
            if not has_last_good:
                raise
            # conservative cache contract (docs/serving.md): a degraded
            # server keeps answering from the last-good MODEL but never
            # from the previous generation's RESULT cache — the failed
            # reload proves newer training data exists, so cached results
            # may be stale even though the model is not
            if self._result_cache is not None:
                self._result_cache.invalidate_all()
            logger.warning(
                "Reload failed; still serving last-good instance %s: %s",
                last_good, e,
            )
            raise QueryServerError(
                f"Reload failed (still serving last-good instance "
                f"'{last_good}'): {e}"
            ) from e
        with self._lock:
            old_pairs = self._algo_model_pairs
            self._engine = engine
            self._serving = serving
            self._algo_model_pairs = pairs
            self._ann_runtimes = [
                rt
                for _, model in pairs
                if (rt := serving_state(model).ann) is not None
            ]
            self.instance = instance
            self.degraded = False
            self.last_reload_error = None
            self.last_reload_at = _dt.datetime.now(_dt.timezone.utc)
            self._model_generation += 1
            generation = self._model_generation
        if self._cache_stats is not None:
            self._cache_stats.set_gauge("model_generation", generation)
        if self._result_cache is not None and generation > 1:
            # a new generation must never serve the old generation's
            # results; the singleflight namespace is generation-keyed so
            # in-flight fills die with their generation too
            self._result_cache.invalidate_all()
        if (
            old_pairs
            and old_pairs is not pairs
            and (
                (
                    self.cache_config is not None
                    and (
                        self.cache_config.pin_model
                        or self.cache_config.shard_factors
                        or self.cache_config.quantize is not None
                    )
                )
                or self.ann_config is not None
                or self.aot_config is not None
            )
        ):
            # free the superseded generation's device buffers — pinned
            # factors AND the old IVF index — promptly. Functionally safe
            # against in-flight queries that snapshotted the old pairs:
            # release converts the factor views to host arrays (and the
            # ANN state to None) in place, so a racing query computes
            # exact on host once rather than reading freed memory
            from predictionio_tpu.workflow import device_state

            device_state.release_pairs(old_pairs)
        # everything compiled so far this reload was BOOT work
        # (deserialize warm-ups, or tier-2/3 fallback compiles);
        # compiles counted from here on are serve-time — the number
        # the --aot contract asserts stays ZERO
        self._compiles.mark_boot_complete()
        logger.info(
            "Loaded engine instance %s (generation %d)", instance.id, generation
        )

    # --------------------------------------------------------------- query
    @staticmethod
    def _bind_query(body: Any, pairs: Sequence) -> Any:
        algo = pairs[0][0]
        query_class = getattr(algo, "query_class", None)
        if query_class is None or not isinstance(body, Mapping):
            return body
        return params_from_json(query_class, body)

    def handle_query(self, body: Any, variant: str | None = None) -> tuple[int, Any]:
        # snapshot under the lock so an in-flight query is internally
        # consistent across a concurrent /reload hot-swap
        with self._lock:
            serving = self._serving
            pairs = list(self._algo_model_pairs)
        if serving is None:
            return 503, {"message": "No engine loaded"}
        if body is None:
            return 400, {"message": "Query body is required (JSON)."}
        try:
            query = self._bind_query(body, pairs)
        except Exception as e:
            return 400, {"message": f"Invalid query: {e}"}
        query = serving.supplement_base(query)
        predictions = [algo.predict_base(model, query) for algo, model in pairs]
        return self._finish_query(serving, body, query, predictions, variant)

    def _finish_query(
        self,
        serving,
        body: Any,
        query: Any,
        predictions: Sequence[Any],
        variant: str | None = None,
    ) -> tuple[int, Any]:
        """serve -> explore -> plugins -> feedback -> count, shared by the
        single and batch routes so they cannot diverge."""
        result = serving.serve_base(query, predictions)
        payload = _result_to_json(result)
        pr_id = None
        if self.feedback is not None:
            pr_id = uuid.uuid4().hex
            if isinstance(payload, dict):
                payload = dict(payload, prId=pr_id)
        if self.explorer is not None and isinstance(payload, dict):
            # policy re-rank between scoring and the plugins: plugins and
            # feedback must see the order actually served
            items = payload.get("itemScores")
            if isinstance(items, list) and items:
                payload = dict(payload, itemScores=self.explorer.rerank(items))
        for plugin in self.plugins:
            if plugin.plugin_type == "outputblocker":
                payload = plugin.process(query, payload, self)
            else:
                plugin.process(query, payload, self)
        if self.feedback is not None:
            self._send_feedback(body, payload, pr_id, variant)
        with self._lock:
            self.query_count += 1
        return 200, payload

    # ------------------------------------------------------- cached queries
    def _scored_query(
        self, body: Any, variant: str | None = None
    ) -> tuple[int, Any]:
        """The uncached scoring path — through the micro-batcher when one
        is configured, else the per-request path. The micro-batched path
        drops the per-request variant tag (a batch mixes variants; its
        feedback events carry no variant field — documented limitation,
        docs/serving.md)."""
        if self.batcher is not None:
            return self.batcher.submit(body)
        if variant is None:
            return self.handle_query(body)
        return self.handle_query(body, variant)

    def handle_query_cached(
        self, body: Any, variant: str | None = None
    ) -> tuple[int, Any]:
        """/queries.json with the cache tiers applied (docs/serving.md):

        1. result-LRU lookup (generation-validated, TTL-bounded);
        2. on miss, singleflight — identical in-flight queries collapse
           into one computation, so the micro-batcher downstream never
           scores duplicate work in one batch;
        3. the winning computation's 200 result is committed back to the
           LRU unless an invalidation won the race since the miss
           (:meth:`ResultCache.commit` drops stale fills).

        Uncacheable bodies (non-JSON-serializable) bypass every tier.
        Non-200 results are never cached (errors stay per-request), but
        they do coalesce — N identical failing queries in flight pay one
        computation."""
        if self._result_cache is None and self._singleflight is None:
            return self._scored_query(body, variant)  # pin-model-only config
        key = canonical_key(body)
        if key is None:
            self._cache_stats.incr("uncacheable")
            return self._scored_query(body, variant)
        # retrieval mode is part of the key: an ANN answer is a
        # different (approximate) result for the same body, so exact and
        # ANN entries must never serve each other — not across a config
        # change, and not between deployments sharing a warmed cache
        key = f"{self._cache_mode}|{key}"
        if variant is not None:
            # A/B experiments (ISSUE 16): the router's X-PIO-Variant tag
            # namespaces the result cache AND the singleflight (the
            # flight key embeds this key) so two variants never serve
            # each other's entries — variant names cannot contain the
            # "|" separator (validated by experiments.split)
            key = f"v={variant}|{key}"
        cfg = self.cache_config
        rc = self._result_cache
        scope = extract_scope(body, cfg.scope_field)
        if rc is not None:
            hit, value = rc.get(key)
            if hit:
                return value

        def compute() -> tuple[int, Any]:
            token = rc.reserve(key, scope) if rc is not None else None
            result = self._scored_query(body, variant)
            if rc is not None and result[0] == 200:
                rc.commit(token, result)
            return result

        if self._singleflight is not None:
            # generation-keyed: a flight straddling a /reload never feeds
            # followers a previous generation's result under the new key
            flight_key = f"{self._model_generation}:{key}"
            try:
                value, _led = self._singleflight.do(flight_key, compute)
            except TimeoutError as e:
                return 500, {"message": str(e)}
            return value
        return compute()

    def cache_note_write(
        self, scopes: Sequence[str] | None = None, flush_all: bool = False
    ) -> dict:
        """Event-driven invalidation hook (docs/serving.md): a write
        about ``scopes`` (user/entity ids) makes their cached results
        stale immediately — entries die on write, not only on TTL. Called
        by the ``POST /cache/invalidate.json`` route and by in-process
        ingest pipelines (see ``serving.cache.scopes_from_events`` for
        mapping event bodies to scopes). ``flush_all`` drops everything
        (equivalent to what ``/reload`` does on a generation swap)."""
        if self._result_cache is None:
            return {"invalidated": 0, "flushed": False}
        if flush_all:
            self._result_cache.invalidate_all()
            return {"invalidated": 0, "flushed": True}
        count = 0
        for scope in scopes or ():
            if isinstance(scope, str) and scope:
                self._result_cache.invalidate_scope(scope)
                count += 1
        return {"invalidated": count, "flushed": False}

    # ------------------------------------------------------ online fold-in
    def snapshot_pairs(self) -> tuple[list, int]:
        """Consistent (pairs, model generation) snapshot — what the
        online runner computes updates against; the generation token
        comes back through :meth:`apply_online_update` so updates
        computed against a superseded generation are dropped."""
        with self._lock:
            return list(self._algo_model_pairs), self._model_generation

    def apply_online_update(
        self, updates: Sequence[tuple[int, Any]], generation: int | None = None
    ) -> dict:
        """The partial-update hot swap beside ``/reload`` (ROADMAP item
        3): swap ONLY the touched factor rows of the live models, under
        the same generation lock a full reload uses.

        ``updates`` is ``[(pair index, OnlineUpdate), ...]`` — each
        pair's algorithm applies its own update (row scatters, cold-start
        id injection, incremental IVF maintenance; see the templates'
        ``apply_online_update`` hooks). ``generation`` (from
        :meth:`snapshot_pairs`) guards against a concurrent ``/reload``:
        rows solved against superseded factors are dropped, never folded
        into the new generation.

        Cache contract (docs/serving.md): unlike ``/reload`` — which
        flushes everything because the whole model moved — a partial
        update bumps ONLY the touched per-scope counters, so unrelated
        hot entries survive a fold-in. Untouched users' rankings can
        drift when item rows move; the result-cache TTL bounds that
        staleness, same as any event-driven invalidation miss.

        Locking: the generation check and the pair snapshot happen under
        the lock; the row swaps themselves run OUTSIDE it. Each pair has
        exactly ONE online writer (the runner's cycle lock / its
        trainer thread), every mutation is an atomic whole-object
        attribute swap ordered so racing readers stay consistent, and a
        concurrent ``/reload`` only ever swaps in NEW model objects — a
        hook finishing against the superseded objects is then harmless.
        Holding the serving lock through the (numpy-bound) hooks was
        measured to convoy concurrent queries straight into the p99
        tail on every fold."""
        with self._lock:
            if generation is not None and generation != self._model_generation:
                return {"applied": False, "reason": "superseded generation"}
            pairs = list(self._algo_model_pairs)
        infos: list[dict] = []
        scopes: set[str] = set()
        try:
            for pair_idx, upd in updates:
                if upd is None or getattr(upd, "empty", True):
                    continue
                if not 0 <= pair_idx < len(pairs):
                    continue
                algo, model = pairs[pair_idx]
                hook = getattr(algo, "apply_online_update", None)
                if hook is None:
                    continue
                # scopes BEFORE the hook: if it raises mid-swap, the
                # touched users' cached results may already reflect a
                # partial row swap and must die with it — the finally
                # below invalidates them even on the error path
                scopes.update(upd.touched_scopes())
                infos.append(hook(model, upd))
        finally:
            if infos:
                with self._lock:
                    self._online_updates += 1
            if scopes:
                # per-scope, never a full flush (the fold-in cache
                # satellite)
                self.cache_note_write(sorted(scopes))
        return {"applied": bool(infos), "infos": infos,
                "scopes": len(scopes)}

    def handle_batch(
        self, bodies: Sequence[Any], n_real: int | None = None
    ) -> list[tuple[int, Any]]:
        """Batch-amortized :meth:`handle_query` (ref
        ``core/workflow/BatchPredict.scala``): bind + supplement each query,
        then push ALL of them through each algorithm's ``batch_predict_base``
        — one chunked device dispatch instead of a round trip per query —
        then the shared per-query tail (serve/plugins/feedback). Per-item
        errors isolate: a malformed query gets its own 400, a query whose
        predict/serve raises gets its own 500 (the bulk path falls back to
        per-query prediction if the batched call itself raises); the batch
        never aborts. Returns ``[(status, payload), ...]`` aligned with
        input.

        ``n_real``: when set, slots >= ``n_real`` are bucket-padding added
        by the micro-batcher — they participate in the batched predict
        call (shape stability is their whole purpose) but skip the
        serve/plugin/feedback tail, don't count as queries, and answer
        ``(200, None)``; the batcher discards them."""
        with self._lock:
            serving = self._serving
            pairs = list(self._algo_model_pairs)
        if serving is None:
            return [(503, {"message": "No engine loaded"})] * len(bodies)
        out: list[tuple[int, Any] | None] = [None] * len(bodies)
        queries: list[tuple[int, Any]] = []
        with span("bind"):
            for i, body in enumerate(bodies):
                if body is None:
                    out[i] = (
                        400, {"message": "Query body is required (JSON)."}
                    )
                    continue
                try:
                    query = self._bind_query(body, pairs)
                except Exception as e:
                    out[i] = (400, {"message": f"Invalid query: {e}"})
                    continue
                try:
                    query = serving.supplement_base(query)
                except Exception as e:  # handle_query surfaces a 500 too
                    out[i] = (500, {"message": str(e)})
                    continue
                queries.append((i, query))
        by_slot: dict[int, list[Any]] = {i: [] for i, _ in queries}
        if queries:
            try:
                for algo, model in pairs:
                    for i, pred in algo.batch_predict_base(model, queries):
                        by_slot[i].append(pred)
            except Exception:
                # one poisoned query must not fail the chunk: redo this
                # chunk per query so only the offender gets a 500
                logger.exception(
                    "batch_predict failed; falling back to per-query predict"
                )
                by_slot = {}
                for i, q in queries:
                    try:
                        by_slot[i] = [
                            algo.predict_base(model, q) for algo, model in pairs
                        ]
                    except Exception as e:
                        out[i] = (500, {"message": str(e)})
        limit = len(bodies) if n_real is None else n_real
        with span("format"):
            for i, query in queries:
                if out[i] is not None:  # per-query fallback already failed it
                    continue
                if i >= limit:  # padding slot: no serve tail, no side effects
                    out[i] = (200, None)
                    continue
                try:
                    out[i] = self._finish_query(
                        serving, bodies[i], query, by_slot[i]
                    )
                except Exception as e:
                    out[i] = (500, {"message": str(e)})
        return [
            o if o is not None else (500, {"message": "unprocessed"}) for o in out
        ]

    def handle_batch_jsonlines(
        self, bodies: Sequence[Any]
    ) -> list[str | None] | None:
        """Bulk-file fast path: JSON payload STRINGS straight from the
        algorithm's vectorized scorer, skipping per-query dataclass and
        json.dumps overhead (~3x of `pio batchpredict` on one core).

        Only legal when it is behaviorally identical to
        :meth:`handle_batch`: exactly one algorithm, stock
        :class:`FirstServing` with the default supplement, no plugins, no
        feedback, and the algorithm offers ``batch_predict_json``.
        Returns None when any condition fails (caller uses handle_batch);
        individual None entries mark bodies the fast path would not bind
        bit-identically (caller routes those through handle_batch)."""
        from predictionio_tpu.controller.components import FirstServing, Serving

        with self._lock:
            serving = self._serving
            pairs = list(self._algo_model_pairs)
        if (
            serving is None
            or len(pairs) != 1
            or type(serving) is not FirstServing
            or type(serving).supplement is not Serving.supplement
            or self.plugins
            or self.feedback is not None
            or not hasattr(pairs[0][0], "batch_predict_json")
        ):
            return None
        algo, model = pairs[0]
        try:
            lines = algo.batch_predict_json(model, bodies)
        except Exception:
            # the fast path must never reduce robustness: handle_batch
            # has per-query fallback isolation, so route everything there
            logger.exception(
                "batch_predict_json failed; falling back to handle_batch"
            )
            return None
        with self._lock:
            self.query_count += sum(1 for l in lines if l is not None)
        return lines

    # ------------------------------------------------------------ feedback
    def _send_feedback(
        self,
        query_body: Any,
        payload: Any,
        pr_id: str | None,
        variant: str | None = None,
    ) -> None:
        """Async POST of the prediction as a ``predict`` event
        (parity: the feedback loop in CreateServer)."""
        fb = self.feedback
        assert fb is not None
        properties: dict = {"query": query_body, "prediction": payload}
        # experiment attribution (ISSUE 16): the active A/B variant and
        # exploration policy ride in properties so reward joins are
        # exact. The eventId stays pio_fb_<prId> — a retried POST is
        # still the same event to the store's dedup, stamped or not.
        if variant is not None:
            properties["variant"] = variant
        explore_config = getattr(self, "explore_config", None)
        if explore_config is not None:
            properties["policy"] = explore_config.policy
        event = {
            # deterministic client eventId derived from the prediction id:
            # the worker's POST becomes retry-safe under the event store's
            # client-id dedup — a redelivered feedback event answers
            # "duplicate", never double-counts (docs/eventserver.md)
            "eventId": f"pio_fb_{pr_id}",
            "event": "predict",
            "entityType": "pio_pr",
            "entityId": pr_id or "",
            "properties": properties,
            "prId": pr_id,
            "eventTime": _dt.datetime.now(_dt.timezone.utc).isoformat(),
        }
        url = f"{fb.event_server_url.rstrip('/')}/events.json?accessKey={fb.access_key}"
        if fb.channel:
            url += f"&channel={fb.channel}"
        try:
            if fb.block_ms > 0:
                # opt-in (docs/operations.md): trade a bounded stall for
                # better delivery when the queue is briefly full
                self._feedback_queue.put((url, event), timeout=fb.block_ms / 1000.0)
            else:
                self._feedback_queue.put_nowait((url, event))
        except queue.Full:
            # feedback is best-effort telemetry; never stall the query
            # path — but surface the loss to operators via status_json
            with self._lock:
                self.feedback_dropped += 1
            logger.warning("Feedback queue full; dropping prediction event")

    @property
    def model_generation(self) -> int:
        """Monotonic per-process reload counter (1 after the first load).
        The fleet router gates rolling swaps on every replica converging
        to one value of this."""
        with self._lock:
            return self._model_generation

    # -------------------------------------------------------------- status
    def status_json(self) -> dict:
        from predictionio_tpu.workflow import device_state

        inst = self.instance
        with self._lock:
            pairs = list(self._algo_model_pairs)
        return {
            "status": "alive",
            # where predict computes: device buffers or host arrays, and
            # on which platform/deviceKind (docs/serving.md)
            "device": device_state.serving_device(pairs),
            "replicaId": self.replica_id,
            "generation": self.model_generation,
            "engineId": self.variant.id,
            "engineVersion": self.variant.version,
            "engineFactory": self.variant.engine_factory,
            "engineInstanceId": inst.id if inst else None,
            "startTime": self.start_time.isoformat(),
            "queryCount": self.query_count,
            "feedbackDropped": self.feedback_dropped,
            "batching": self.batcher is not None,
            "caching": self.cache_config is not None,
            "shardFactors": (
                self.cache_config is not None
                and self.cache_config.shard_factors
            ),
            "quantize": (
                self.cache_config.quantize
                if self.cache_config is not None
                else None
            ),
            # per-dtype ledger of the pinned device state (f32 vs int8
            # codes vs their scales) — same served-truth numbers as
            # /stats.json cache.bytesByDtype
            "bytesPinnedByDtype": (
                self._cache_stats.to_json()["bytesByDtype"]
                if self._cache_stats is not None
                else {}
            ),
            "ann": self.ann_config is not None,
            "aot": self.aot_config is not None,
            "online": self.online is not None,
            "explore": (
                self.explore_config.policy
                if self.explore_config is not None
                else None
            ),
            # degraded-mode semantics (docs/operations.md): serving the
            # last-good model after a failed reload
            "degraded": self.degraded,
            "lastReloadError": self.last_reload_error,
            "lastReloadAt": (
                self.last_reload_at.isoformat() if self.last_reload_at else None
            ),
            "plugins": [
                {"name": p.name, "type": p.plugin_type} for p in self.plugins
            ],
        }

    def stats_json(self) -> dict:
        """``GET /stats.json`` payload: query counters plus, when the
        micro-batcher is on, its full gauge/latency decomposition."""
        from predictionio_tpu import resilience

        # one consistent snapshot of every counter
        with self._lock:
            count = self.query_count
            feedback_counts = {
                "sent": self.feedback_sent,
                "failed": self.feedback_failed,
                "dropped": self.feedback_dropped,
            }
            degraded = self.degraded
            generation = self._model_generation
        out: dict = {
            "queryCount": count,
            # fleet identity + model generation (ISSUE 15): the router
            # and `pio status` gate rollouts on the fleet converging to
            # one generation; replicaId is null outside --replicas
            "replicaId": self.replica_id,
            "generation": generation,
            "startTime": self.start_time.isoformat(),
            "batching": self.batcher is not None,
            "degraded": degraded,
            # breaker states + retry/abort counters from every registered
            # transport (storage RPC, feedback loop)
            "resilience": resilience.stats_snapshot(),
            # compiles per jitted function and since the boot mark
            # (sinceBoot: the request path should compile nothing)
            "compile": self._compiles.to_json(),
            # what the HTTP threads spend reading a request and writing
            # its answer, around dispatch()
            "http": self._http_stats.to_json(),
            # the interpreter lock from outside the request path: how
            # late a sleeping thread runs, the share of wall the request
            # path's threads were on a CPU (their CPU time since boot
            # beside it), and the times the host stood still
            "lock": {
                **self._lock_stats.to_json(),
                "cpuNs": {
                    "workers": (
                        self.batcher.stats.cpu_ns_workers
                        if self.batcher is not None else 0
                    ),
                    "riders": self._http_stats.cpu_ns_riders,
                },
            },
        }
        if self.feedback is not None:
            out["feedback"] = feedback_counts
        if self.batcher is not None:
            out["batcher"] = self.batcher.stats.to_json()
        if self._cache_stats is not None:
            # hit/miss/coalesced counters, eviction + invalidation
            # breakdown, bytes pinned (docs/performance.md)
            out["cache"] = self._cache_stats.to_json()
        if self.explorer is not None:
            # per-policy exploration decomposition (docs/serving.md):
            # queries/explored counts, cumulative model-score regret,
            # reward-event posterior feed
            out["explore"] = self.explorer.stats_json()
        if self.online is not None:
            # freshness decomposition (docs/operations.md): events
            # folded, fold latency, watermark lag, and the measured
            # event->reflected-in-recs latency of applied batches
            with self._lock:
                applied = self._online_updates
            out["online"] = dict(
                self.online.stats_json(), updatesApplied=applied
            )
        if (
            self.cache_config is not None
            and self.cache_config.quantize is not None
        ):
            # quantized-serving decomposition (docs/serving.md): dtype,
            # the real byte ledger (codes/scales vs the f32 the same
            # catalog would cost), measured quantization error, and the
            # MEASURED rescore depth the over-fetch actually paid
            with self._lock:
                q_pairs = list(self._algo_model_pairs)
            out["quant"] = {
                "dtype": self.cache_config.quantize,
                "models": [
                    rt.stats_json()
                    for _, model in q_pairs
                    if (rt := serving_state(model).quant) is not None
                ],
            }
        if self.aot_config is not None:
            # AOT-serving decomposition (docs/operations.md): which tier
            # the boot landed on (1 = deserialized artifacts, 2 =
            # persistent-cache fallback, 3 = plain JIT), program/hit
            # counters, and the serve-time compile count the --aot
            # contract asserts stays ZERO after boot
            from predictionio_tpu.workflow import device_state

            with self._lock:
                a_pairs = list(self._algo_model_pairs)
            aot_block = device_state.aot_stats(a_pairs) or {
                "tier": None, "loaded": 0,
            }
            aot_block["serveTimeCompiles"] = self._compiles.since_boot()
            out["aot"] = aot_block
        if self.ann_config is not None:
            # approximate-retrieval decomposition (docs/serving.md):
            # effective nlist/nprobe plus, per built index, clusters
            # scored and the fraction of the catalog each query paid for
            with self._lock:
                runtimes = list(self._ann_runtimes)
            out["ann"] = {
                # nlist 0 means auto (~sqrt(catalog)) — report what the
                # build actually picked, not the sentinel
                "nlist": self.ann_config.nlist
                or (runtimes[0].index.nlist if runtimes else 0),
                "nprobe": self.ann_config.nprobe,
                "cacheMode": self._cache_mode,
                "models": [rt.stats_json() for rt in runtimes],
            }
        return out

    def record_http(
        self, records: Sequence, counts: Mapping[str, int] | None = None
    ) -> None:
        """The HTTP wrapper's hook (``api/http.py`` finds it by name):
        the spans one request closed on its HTTP thread and, where a
        group of that thread's riders is full, the group's counts: the
        wrapper's and the batcher's ``rider.*``."""
        ms = durations_ms(records)
        if "httpRead" in ms and "httpWrite" in ms:
            self._http_stats.record(ms["httpRead"], ms["httpWrite"])
        if counts and counts.get("rider.requests"):
            self._http_stats.record_riders(
                counts["rider.requests"], counts.get("rider.requestNs", 0),
                counts["rider.cpuNs"], counts["rider.queuedNs"],
                counts["rider.giveWayNs"],
            )

    def readiness(self) -> dict:
        """``GET /readyz`` (served by the HTTP wrapper): storage
        reachable, a model loaded, and — when batching is on — the
        dispatcher thread alive. ``degraded`` (serving last-good after a
        failed reload) is reported but does NOT fail readiness: the
        server is still answering queries, which is what readiness
        gates."""
        from predictionio_tpu.api.health import readiness_report, storage_check

        with self._lock:
            model_ok = self._serving is not None
            degraded = self.degraded
            generation = self._model_generation
        batcher_ok = self.batcher is None or self.batcher.dispatcher_alive()
        report = readiness_report(
            storage=storage_check(),
            model_loaded={"ok": model_ok},
            batcher={"ok": batcher_ok},
        )
        report["degraded"] = degraded
        # fleet identity + generation: the router's health probes read
        # these to gate routing and rolling-swap convergence
        report["replicaId"] = self.replica_id
        report["generation"] = generation
        return report

    def close(self) -> None:
        """Release background resources (the beat thread, the batcher's
        dispatcher thread and the online follower/trainer threads) and run the ``on_close``
        callbacks (e.g. the endpoint-registry withdraw the console wires
        under ``--announce-dir``, so a draining replica leaves the ring
        cleanly instead of waiting out its lease). Safe to call more
        than once; queued requests get a 503."""
        callbacks, self.on_close = self.on_close, []
        for cb in callbacks:
            try:
                cb()
            except Exception as e:  # closing must never fail the drain
                logger.warning("on_close callback failed: %s", e)
        if self.online is not None:
            self.online.stop()
            self.online = None
        if self.batcher is not None:
            self.batcher.close()
        self._beat.stop()

    def drain(self) -> None:
        """Graceful-shutdown hook, auto-discovered by the HTTP wrapper
        (``api/lifecycle.py``): runs after in-flight requests completed,
        so closing the batcher here releases its dispatcher thread and
        answers anything still queued with a clean 503 instead of
        abandoning it mid-shutdown."""
        self.close()

    # ------------------------------------------------------------ dispatch
    def dispatch(
        self,
        method: str,
        path: str,
        params: Mapping[str, str],
        body: Any = None,
        headers: Mapping[str, str] | None = None,
        form: Mapping[str, str] | None = None,
    ):
        from predictionio_tpu.api.service import Response

        method = method.upper()

        def tag_replica(resp: "Response") -> "Response":
            # fleet mode only (--replica-id): stamp which replica and
            # model generation answered, so the router can enforce the
            # never-two-generations-per-cache-key contract from served
            # truth instead of probe staleness. replica_id None (every
            # non-fleet deploy) returns the response untouched.
            if self.replica_id is None:
                return resp
            tags = {
                "X-PIO-Replica": self.replica_id,
                "X-PIO-Generation": str(self.model_generation),
            }
            return dataclasses.replace(
                resp, headers={**(resp.headers or {}), **tags}
            )

        if path == "/" and method == "GET":
            return Response(200, self.status_json())
        if path == "/queries.json" and method == "POST":
            def to_response(status: int, payload: Any) -> Response:
                # admission control: tell well-behaved clients when to
                # come back instead of letting them hot-loop. The value
                # is computed once, by the batcher, into the payload —
                # one shaping rule for the cached and uncached branches
                if (
                    status in (429, 503)
                    and isinstance(payload, Mapping)
                    and "retryAfterSeconds" in payload
                ):
                    return Response(
                        status,
                        payload,
                        headers={
                            "Retry-After": str(payload["retryAfterSeconds"])
                        },
                    )
                return Response(status, payload)

            # A/B experiments (ISSUE 16): the fleet router tags routed
            # queries with the assigned variant; the tag namespaces the
            # cache/singleflight keys and stamps feedback events. Absent
            # header (every non-experiment deploy) => variant None and
            # the exact prior code paths.
            variant_tag = None
            if headers:
                variant_tag = next(
                    (
                        v
                        for k, v in headers.items()
                        if k.lower() == "x-pio-variant"
                    ),
                    None,
                ) or None
            if self.cache_config is not None:
                # result cache + singleflight in front of the (possibly
                # batched) scoring path; cache off => the exact branches
                # below, byte-identical to the pre-cache server
                return tag_replica(
                    to_response(*self.handle_query_cached(body, variant_tag))
                )
            if self.batcher is not None:
                return tag_replica(to_response(*self.batcher.submit(body)))
            status, payload = (
                self.handle_query(body)
                if variant_tag is None
                else self.handle_query(body, variant_tag)
            )
            return tag_replica(Response(status, payload))
        if path == "/cache/invalidate.json" and method == "POST":
            # event-driven invalidation hook: {"entityId": "u1"} /
            # {"entityIds": [...]} / {"all": true} / a list of
            # event-server-shaped bodies (entityType/entityId)
            if self._result_cache is None:
                return Response(
                    404,
                    {"message": "No result cache on this deployment "
                                "(enable with pio deploy --result-cache)."},
                )
            scopes: list = []
            flush_all = False
            if isinstance(body, Mapping):
                flush_all = bool(body.get("all"))
                if isinstance(body.get("entityId"), str):
                    scopes.append(body["entityId"])
                ids = body.get("entityIds")
                if isinstance(ids, list):
                    scopes.extend(i for i in ids if isinstance(i, str))
            elif isinstance(body, list):
                from predictionio_tpu.serving.cache import scopes_from_events

                scopes.extend(sorted(scopes_from_events(body)))
            return Response(200, self.cache_note_write(scopes, flush_all))
        if path == "/stats.json" and method == "GET":
            return Response(200, self.stats_json())
        if path == "/online/fold.json" and method == "POST":
            # the partial-update entry point beside /reload: poll the
            # tail and fold whatever landed, synchronously (the daemon
            # keeps its own cadence; this is the operator/test trigger)
            if self.online is None:
                return Response(
                    404,
                    {"message": "Online learning is off on this deployment "
                                "(enable with pio deploy --online)."},
                )
            try:
                return Response(200, self.online.fold_now())
            except Exception as e:
                return Response(500, {"message": str(e)[:300]})
        if path == "/experiments/reward.json" and method == "POST":
            # reward entry point for the explorer's posterior when online
            # learning is off (with --online the PR 7 follower feeds
            # reward events automatically); body is one event dict or a
            # list of them, event-server shaped
            if self.explorer is None:
                return Response(
                    404,
                    {"message": "Exploration is off on this deployment "
                                "(enable with pio deploy --explore)."},
                )
            events = (
                body
                if isinstance(body, list)
                else [body] if isinstance(body, Mapping) else []
            )
            matched = self.explorer.note_reward_events(events)
            return Response(
                200, {"matched": matched, "explore": self.explorer.stats_json()}
            )
        if path == "/reload" and method == "POST":
            try:
                self.reload()
                return Response(200, {"message": "Reloaded"})
            except QueryServerError as e:
                # degraded, not dead: the last-good model is still
                # serving, so this is an unavailability of the *reload*,
                # not of the server — 503 + Retry-After, never a raw 500
                if self.degraded:
                    return Response(
                        503,
                        {"message": str(e), "degraded": True},
                        headers={"Retry-After": "5"},
                    )
                return Response(500, {"message": str(e)})
        if path == "/stop" and method == "GET":
            # parity: CreateServer's stop route. stop_server rides the
            # response as after_send: the transport flushes the answer
            # first and only then shuts the listener down.
            # When stop_token is set (pio deploy always sets one), the
            # caller must present it — otherwise anyone who can reach the
            # port could shut down a production deployment (advisor r3).
            # Preferred carrier is the X-PIO-Stop-Token header (query
            # strings leak into access logs / proxies — advisor r4); the
            # query param stays accepted for older clients.
            presented = ""
            if headers:
                presented = next(
                    (
                        v
                        for k, v in headers.items()
                        if k.lower() == "x-pio-stop-token"
                    ),
                    "",
                )
            presented = presented or params.get("token", "")
            if self.stop_token and not _token_ok(presented, self.stop_token):
                return Response(
                    403, {"message": "Missing or invalid stop token."}
                )
            if self.stop_server is None:
                return Response(
                    501, {"message": "This deployment has no stop hook."}
                )
            # the process goes: no traceback dump of its way out
            self._beat.stop()
            return Response(
                200, {"message": "Shutting down."},
                after_send=self.stop_server,
            )
        if path == "/profiler/start" and method == "POST":
            # jax.profiler trace capture (SURVEY.md section 6.1 rebuild
            # surface); view the dump with TensorBoard/XProf
            import jax

            options = body if isinstance(body, Mapping) else {}
            log_dir = options.get("logDir") or "/tmp/pio-profile"
            # JAX's own host events and the dispatcher's pio.* spans; the
            # Python tracer (every frame of every HTTP thread) stalls a
            # loaded server while it starts and is only on when asked for
            profile = jax.profiler.ProfileOptions()
            profile.host_tracer_level = 2
            profile.python_tracer_level = (
                1 if options.get("pythonTracer") is True else 0
            )
            try:
                jax.profiler.start_trace(log_dir, profiler_options=profile)
            except RuntimeError as e:
                return Response(409, {"message": str(e)})
            return Response(200, {"message": "Profiler started", "logDir": log_dir})
        if path == "/profiler/stop" and method == "POST":
            import jax

            try:
                jax.profiler.stop_trace()
            except RuntimeError as e:
                return Response(409, {"message": str(e)})
            return Response(200, {"message": "Profiler stopped"})
        return Response(404, {"message": "Not Found"})
