"""The ``pio`` console — argparse front-end over the command layer.

Parity: ``tools/console/Console.scala`` + ``console/Pio.scala`` (scopt →
argparse). Subcommand surface mirrors the reference:

    pio version | status
    pio app new|list|show|delete|data-delete|channel-new|channel-delete
    pio accesskey new|list|delete
    pio import|export
    pio train | deploy | eval | eventserver | dashboard | batchpredict

Run as ``python -m predictionio_tpu.tools.console`` or via ``bin/pio``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

from predictionio_tpu.tools import commands
from predictionio_tpu.utils import compile_cache, spans
from predictionio_tpu.version import __version__

__all__ = ["main", "build_parser"]


def _int_at_least(floor: int):
    """argparse ``type=`` validator: int with a lower bound, so a bad
    value fails at parse time with the usual clean ``usage:`` error
    instead of a config-construction traceback."""

    def parse(text: str) -> int:
        try:
            value = int(text)
        except ValueError:
            raise argparse.ArgumentTypeError(f"invalid int value: {text!r}")
        if value < floor:
            raise argparse.ArgumentTypeError(f"must be >= {floor}")
        return value

    return parse


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="pio", description="predictionio_tpu — TPU-native ML server"
    )
    sub = p.add_subparsers(dest="command", required=True)

    sub.add_parser("version", help="print version")
    sub.add_parser("status", help="check storage + device connectivity")

    # ---- app
    app = sub.add_parser("app", help="manage apps")
    app_sub = app.add_subparsers(dest="app_command", required=True)
    ap_new = app_sub.add_parser("new")
    ap_new.add_argument("name")
    ap_new.add_argument("--description")
    ap_new.add_argument("--access-key", default="")
    app_sub.add_parser("list")
    for cmd in ("show", "delete", "data-delete", "compact"):
        sp = app_sub.add_parser(cmd)
        sp.add_argument("name")
        if cmd in ("data-delete", "compact"):
            sp.add_argument("--channel")
    ch_new = app_sub.add_parser("channel-new")
    ch_new.add_argument("name")
    ch_new.add_argument("channel")
    ch_del = app_sub.add_parser("channel-delete")
    ch_del.add_argument("name")
    ch_del.add_argument("channel")

    # ---- accesskey
    ak = sub.add_parser("accesskey", help="manage access keys")
    ak_sub = ak.add_subparsers(dest="accesskey_command", required=True)
    ak_new = ak_sub.add_parser("new")
    ak_new.add_argument("app_name")
    ak_new.add_argument("events", nargs="*")
    ak_list = ak_sub.add_parser("list")
    ak_list.add_argument("app_name", nargs="?")
    ak_del = ak_sub.add_parser("delete")
    ak_del.add_argument("key")

    # ---- import / export
    imp = sub.add_parser("import", help="bulk-load JSON-lines events")
    imp.add_argument("--appname", required=True)
    imp.add_argument("--input", required=True)
    imp.add_argument("--channel")
    exp = sub.add_parser("export", help="dump events to JSON-lines")
    exp.add_argument("--appname", required=True)
    exp.add_argument("--output", required=True)
    exp.add_argument("--channel")
    exp.add_argument(
        "--sharded",
        type=int,
        default=0,
        metavar="N",
        help="write N round-robin shard files into OUTPUT (a directory) "
        "for multi-host training reads",
    )
    exp.add_argument(
        "--format",
        choices=("json", "columnar"),
        default="json",
        help="json = JSON-lines; columnar = dictionary-encoded segment "
        "directory, re-importable and readable at array speed (the "
        "reference's --format parquet role)",
    )

    # ---- train
    train = sub.add_parser("train", help="run the training workflow")
    train.add_argument("--engine-json", default="engine.json")
    train.add_argument("--batch", default="")
    train.add_argument("--skip-sanity-check", action="store_true")
    train.add_argument("--stop-after-read", action="store_true")
    train.add_argument("--stop-after-prepare", action="store_true")
    train.add_argument(
        "--warm-start", action="store_true",
        help="seed algorithms from the latest COMPLETED instance's model "
        "(retrains converge in fewer sweeps)",
    )
    train.add_argument(
        "--mesh",
        default="auto",
        help="'auto' (all devices on the data axis; one device trains "
        "mesh-less, like 'none'), 'none' (local), or 'data=N,model=M' "
        "axis sizes",
    )
    # ---- deploy-time AOT serving (predictionio_tpu.workflow.aot;
    # docs/operations.md AOT runbook). Strictly opt-in: without --aot no
    # program is exported and training output is byte-identical
    # (CI-guarded).
    train.add_argument(
        "--aot", action="store_true",
        help="after training, lower + serialize every budgeted serving "
        "entrypoint per pow2 candidate bucket (jax.export) into "
        "<basedir>/fleet/aot/<instance>/ and stamp the artifact set into "
        "the fleet model registry — `pio deploy --aot` replicas then boot "
        "by deserializing instead of compiling (zero serve-time "
        "compiles; docs/operations.md)",
    )

    def add_ssl_flags(sp):
        sp.add_argument(
            "--cert", default=None,
            help="PEM certificate for https (default: $PIO_SSL_CERT)",
        )
        sp.add_argument(
            "--key", default=None,
            help="PEM private key for https (default: $PIO_SSL_KEY)",
        )

    def add_lifecycle_flags(sp):
        sp.add_argument(
            "--drain-deadline-s", type=float, default=0.0, metavar="S",
            help="graceful drain on SIGTERM/SIGINT: stop accepting (503 + "
            "Retry-After, /readyz flips unready), finish in-flight "
            "requests within S seconds, flush storage, exit 0; a second "
            "signal force-quits. 0 (default) keeps immediate exit "
            "(docs/operations.md)",
        )

    # ---- deploy
    deploy = sub.add_parser("deploy", help="serve the latest trained instance")
    deploy.add_argument("--engine-json", default="engine.json")
    deploy.add_argument("--ip", default="0.0.0.0")
    deploy.add_argument("--port", type=int, default=8000)
    deploy.add_argument("--engine-instance-id")
    # ---- replica fleet (predictionio_tpu.fleet; docs/operations.md).
    # Strictly opt-in: without --replicas no fleet module is imported, no
    # router process exists, and serving is byte-identical (CI-guarded).
    deploy.add_argument(
        "--replicas", type=_int_at_least(1), default=0, metavar="N",
        help="serve through a replica fleet: spawn N query-server "
        "subprocesses (each composing every other deploy flag, e.g. "
        "--shard-factors/--quantize/--ann) plus a router on --port that "
        "load-balances by consistent hash of the cache scope, health-"
        "gates on /readyz + passive failures + a per-replica circuit "
        "breaker, fails idempotent requests over to a peer, and "
        "orchestrates rolling /reload (docs/operations.md fleet runbook)",
    )
    deploy.add_argument(
        "--replica-id", default=None, metavar="ID",
        help="fleet-internal: this process is replica ID of a fleet "
        "(set by the supervisor; exposes replicaId/generation on "
        "/readyz, /stats.json and query response headers)",
    )
    deploy.add_argument(
        "--probe-interval-s", type=float, default=0.25, metavar="S",
        help="router: seconds between /readyz health probes of each "
        "replica — a killed or draining replica is routed around within "
        "one interval (default 0.25)",
    )
    deploy.add_argument(
        "--failover-retries", type=_int_at_least(0), default=1, metavar="N",
        help="router: most times one idempotent request (GETs and "
        "/queries.json) is re-dispatched to a peer after a replica "
        "fails mid-request; non-idempotent routes are never retried "
        "(default 1)",
    )
    deploy.add_argument(
        "--hedge-ms", type=float, default=0.0, metavar="MS",
        help="router: hedge a query to a second replica when the first "
        "has not answered within max(MS, observed p95) — bounds the "
        "tail one slow replica can impose; 0 (default) disables hedging",
    )
    deploy.add_argument(
        "--fleet-breaker-threshold", type=_int_at_least(1), default=2,
        metavar="N",
        help="router: consecutive transport failures that open one "
        "replica's circuit breaker (default 2)",
    )
    deploy.add_argument(
        "--fleet-breaker-reset-s", type=float, default=1.0, metavar="S",
        help="router: seconds an open replica breaker waits before "
        "probing again — the fleet's recovery-time unit (default 1.0)",
    )
    # ---- cross-host elastic fleet (ISSUE 17; docs/operations.md
    # multi-host runbook). All strictly opt-in: a fleet-less deploy
    # imports none of it, and a plain `--replicas N` fleet only gains
    # registry-driven discovery (replicas bind port 0 and self-report —
    # the pick-then-spawn port race is structurally gone).
    deploy.add_argument(
        "--endpoint-registry", default=None, metavar="DIR",
        help="fleet: shared endpoint-registry directory (a shared "
        "filesystem path) through which replicas on ANY host join this "
        "router's consistent-hash ring — lease-stamped atomic entry "
        "files, evicted on lease expiry, readable at GET "
        "/fleet/endpoints.json (default: <basedir>/fleet/endpoints, "
        "i.e. single-host unless pointed at a shared mount)",
    )
    deploy.add_argument(
        "--router-only", action="store_true",
        help="fleet: serve a router WITHOUT spawning replicas — a "
        "second router sharing --endpoint-registry with the primary is "
        "router-tier HA: same registry, same ring, client-visible "
        "failover between the two",
    )
    deploy.add_argument(
        "--autoscale", default="", metavar="MIN:MAX",
        help="fleet: autoscale the replica fleet between MIN and MAX on "
        "the watermarks below; scale-down retires drain-aware (SIGTERM "
        "→ finish in-flight → withdraw registry entry; zero queries "
        "lost). Requires --replicas (the initial size)",
    )
    deploy.add_argument(
        "--scale-up-qps", type=float, default=50.0, metavar="Q",
        help="autoscale: add a replica when per-replica q/s exceeds Q "
        "(default 50)",
    )
    deploy.add_argument(
        "--scale-up-p99-ms", type=float, default=250.0, metavar="MS",
        help="autoscale: add a replica when router p99 exceeds MS "
        "regardless of q/s (default 250)",
    )
    deploy.add_argument(
        "--scale-down-qps", type=float, default=5.0, metavar="Q",
        help="autoscale: drain one replica away when per-replica q/s "
        "falls below Q and p99 is calm (default 5; must be < "
        "--scale-up-qps — the gap is the hysteresis band)",
    )
    deploy.add_argument(
        "--scale-cooldown-s", type=float, default=10.0, metavar="S",
        help="autoscale: seconds between scaling actions (default 10)",
    )
    deploy.add_argument(
        "--stale-cache-ttl-s", type=float, default=0.0, metavar="S",
        help="router: keep each scope's last good answer S seconds and "
        "serve it marked `X-PIO-Stale: true` ONLY when no replica can "
        "serve at all — a fresh-capable scope never sees a stale "
        "answer. 0 (default) disables the stale-while-down cache",
    )
    deploy.add_argument(
        "--lease-ttl-s", type=float, default=5.0, metavar="S",
        help="endpoint registry: seconds a replica's lease lives "
        "between heartbeats; an entry unrenewed past this is evicted "
        "from every router's ring (default 5)",
    )
    deploy.add_argument(
        "--announce-dir", default=None, metavar="DIR",
        help="replica: announce this server's actually-bound address "
        "(use with --port 0) into the endpoint-registry directory and "
        "heartbeat the lease — how a replica on another host joins a "
        "fleet (set automatically by the fleet supervisor)",
    )
    deploy.add_argument(
        "--announce-host", default="127.0.0.1", metavar="HOST",
        help="replica: the address other hosts reach this replica at "
        "(written into the registry entry; default 127.0.0.1)",
    )
    deploy.add_argument("--feedback", action="store_true")
    deploy.add_argument("--event-server-ip", default="127.0.0.1")
    deploy.add_argument("--event-server-port", type=int, default=7070)
    deploy.add_argument("--accesskey", default="")
    # ---- cross-request micro-batching (predictionio_tpu.serving)
    deploy.add_argument(
        "--batching", action="store_true",
        help="coalesce concurrent /queries.json requests into batched "
        "device dispatches (docs/serving.md)",
    )
    deploy.add_argument(
        "--max-batch-size", type=int, default=32,
        help="most queries per batched dispatch (default 32)",
    )
    deploy.add_argument(
        "--max-batch-delay-ms", type=float, default=2.0,
        help="longest wait for batchmates past the oldest queued request; "
        "0 = dispatch immediately, batch only what is already queued",
    )
    deploy.add_argument(
        "--batch-queue", type=int, default=256,
        help="bounded admission queue size (default 256)",
    )
    deploy.add_argument(
        "--admission-policy", choices=("reject", "block"), default="reject",
        help="full queue behavior: reject = 429 + Retry-After (default), "
        "block = wait up to --admission-timeout-ms, then 503",
    )
    deploy.add_argument(
        "--admission-timeout-ms", type=float, default=1000.0,
        help="block policy only: longest wait for a queue slot",
    )
    deploy.add_argument(
        "--batch-buckets", default="",
        help="comma-separated batch sizes to pad to (default: powers of "
        "two up to --max-batch-size); each bucket is one jit shape",
    )
    deploy.add_argument(
        "--batch-warmup-query", default=None, metavar="JSON",
        help="sample query body; every bucket shape is pre-compiled with "
        "it at startup so live traffic never recompiles",
    )
    # ---- query-path caching & coalescing (predictionio_tpu.serving.cache;
    # docs/performance.md). Each tier is individually opt-in; with none of
    # these flags the serving path is byte-identical to a cache-less build.
    deploy.add_argument(
        "--result-cache", action="store_true",
        help="serve repeated identical queries from an in-memory LRU with "
        "TTL and event-driven invalidation (POST /cache/invalidate.json; "
        "/reload flushes)",
    )
    deploy.add_argument(
        "--result-cache-entries", type=int, default=4096,
        help="most entries the result LRU holds (default 4096)",
    )
    deploy.add_argument(
        "--result-cache-ttl-s", type=float, default=30.0,
        help="seconds a cached result may serve before it expires "
        "(<= 0: no TTL — entries die only by eviction or invalidation)",
    )
    deploy.add_argument(
        "--result-cache-max-mb", type=float, default=64.0,
        help="approximate payload-byte budget of the result LRU in MiB "
        "(<= 0: unbounded)",
    )
    deploy.add_argument(
        "--cache-scope-field", default="user", metavar="FIELD",
        help="query field naming the per-entity invalidation scope "
        "(default 'user'); 'none' disables per-scope invalidation",
    )
    deploy.add_argument(
        "--coalesce", action="store_true",
        help="collapse identical in-flight queries into one scored "
        "computation whose result fans out to all waiters (singleflight; "
        "composes with --batching so a batch never holds duplicate work)",
    )
    deploy.add_argument(
        "--pin-model", action="store_true",
        help="pin factor matrices and the jitted score+top-K programs "
        "device-resident across requests (no per-request staging or "
        "re-trace; bytes pinned reported on /stats.json)",
    )
    deploy.add_argument(
        "--shard-factors", action="store_true",
        help="pin factor SHARDS per device instead of a full replica: "
        "tables split row-wise over a one-axis model mesh of the local "
        "devices, so per-device factor memory is table/num_devices and "
        "catalogs bigger than one device's memory serve; exact top-K "
        "stays tie-stable-identical to the replicated path, and --ann "
        "slabs shard over the same axis (docs/serving.md)",
    )
    # ---- quantized serving (predictionio_tpu.ops.quant; docs/serving.md).
    # Strictly opt-in: without --quantize every table serves f32 and the
    # module is never imported.
    deploy.add_argument(
        "--quantize", choices=("int8",), default=None, metavar="DTYPE",
        help="serve factor tables (and --ann IVF slabs) as int8 codes + "
        "per-row f32 scales: ~4x more catalog per device and ~4x less "
        "gather traffic, recall-guarded by a two-stage kernel (int8 "
        "coarse scan over-fetching max(4k, k+64), f32 rescore of only "
        "the gathered candidates). Composes with --shard-factors "
        "(catalog/S/4 bytes per device), --pin-model, --ann and "
        "--online (touched rows re-quantize on fold-in); /stats.json "
        "grows a 'quant' section (docs/serving.md)",
    )
    # ---- deploy-time AOT serving (predictionio_tpu.workflow.aot;
    # docs/operations.md AOT runbook). Strictly opt-in: without --aot no
    # artifact is read and serving is byte-identical (CI-guarded).
    deploy.add_argument(
        "--aot", action="store_true",
        help="boot by deserializing the instance's `pio train --aot` "
        "exported programs instead of compiling: fingerprint-checked "
        "(jaxlib/backend/shape-bucket), warmed before the first query, "
        "ZERO serve-time compiles. A missing/stale/corrupt artifact set "
        "falls back LOUDLY to the persistent compilation cache (tier 2, "
        "$JAX_COMPILATION_CACHE_DIR) and then plain JIT (tier 3) — results "
        "stay bit-identical on every tier; implies --pin-model; "
        "/stats.json grows an 'aot' section with serveTimeCompiles "
        "(docs/operations.md)",
    )
    # ---- approximate retrieval (predictionio_tpu.ops.ivf; docs/serving.md).
    # Strictly opt-in: without --ann every query scores the exact path.
    deploy.add_argument(
        "--ann", action="store_true",
        help="serve top-K through an on-device IVF (clustered) index "
        "built at (re)load time: score nprobe cluster slabs per query "
        "instead of the whole catalog (recall/latency trade-off in "
        "docs/performance.md; /stats.json grows an 'ann' section)",
    )
    deploy.add_argument(
        "--ann-nlist", type=_int_at_least(0), default=0, metavar="N",
        help="k-means cluster count for --ann (default 0 = auto, "
        "~sqrt(catalog items))",
    )
    deploy.add_argument(
        "--ann-nprobe", type=_int_at_least(1), default=8, metavar="N",
        help="clusters scored per query for --ann (default 8); "
        "nprobe >= nlist reproduces exact top-K bit-identically",
    )
    deploy.add_argument(
        "--ann-seed", type=int, default=0,
        help="k-means seed for --ann (index build is deterministic per "
        "(factors, seed))",
    )
    deploy.add_argument(
        "--ann-kmeans-iters", type=_int_at_least(0), default=8, metavar="N",
        help="Lloyd iterations after k-means++ seeding (default 8)",
    )
    # ---- online learning (predictionio_tpu.online; docs/operations.md).
    # Strictly opt-in: without --online no follower thread starts and the
    # serving path is byte-identical to a build without the subsystem.
    deploy.add_argument(
        "--online", action="store_true",
        help="tail the event store and fold fresh events into the live "
        "model without a retrain: incremental ALS fold-in / streaming "
        "two-tower mini-batches, hot-swapped row-by-row with per-scope "
        "cache invalidation and incremental IVF index updates "
        "(/stats.json grows an 'online' section; columnar event store "
        "required)",
    )
    deploy.add_argument(
        "--online-interval-s", type=float, default=1.0, metavar="S",
        help="seconds between watermark polls of the event tail "
        "(default 1.0)",
    )
    deploy.add_argument(
        "--online-batch", type=_int_at_least(1), default=4096, metavar="N",
        help="most events folded per batch; larger bursts fold over "
        "consecutive batches (default 4096)",
    )
    deploy.add_argument(
        "--online-algos", default="", metavar="NAMES",
        help="comma-separated algorithm-class allowlist (e.g. "
        "'als,twotower'); empty (default) = every deployed algorithm "
        "that implements the online hooks",
    )
    deploy.add_argument(
        "--online-prior-weight", type=float, default=1.0, metavar="W",
        help="anchor strength toward each entity's trained row in the "
        "fold-in re-solve; 0 = pure fold-in from online-observed events "
        "(default 1.0)",
    )
    deploy.add_argument(
        "--online-from-start", action="store_true",
        help="fold events already in the store at deploy time too "
        "(default: start at the end of the stream)",
    )
    # ---- experimentation (predictionio_tpu.experiments; docs/serving.md).
    # Strictly opt-in: without --explore/--variants the package is never
    # imported and serving is byte-identical (CI-guarded).
    deploy.add_argument(
        "--explore", choices=("epsilon", "thompson"), default=None,
        metavar="POLICY",
        help="rerank each query's top-K through a bandit exploration "
        "policy (epsilon-greedy or Thompson sampling over per-item "
        "posteriors); reward events fold back through --online's "
        "follower or POST /experiments/reward.json, and /stats.json "
        "grows an 'explore' section with the cumulative regret counter "
        "(docs/serving.md)",
    )
    deploy.add_argument(
        "--explore-epsilon", type=float, default=0.1, metavar="E",
        help="epsilon policy: probability a query serves an exploration "
        "slate instead of the exploit ranking (default 0.1)",
    )
    deploy.add_argument(
        "--explore-seed", type=int, default=0,
        help="PRNG seed of the exploration policy (per-query keys are "
        "folded from a served-query counter; default 0)",
    )
    deploy.add_argument(
        "--explore-reward-event", default="reward", metavar="NAME",
        help="event name counted as bandit reward when folding the event "
        "tail back into the policy posterior (default 'reward')",
    )
    deploy.add_argument(
        "--variants", default="", metavar="NAME[:W],NAME[:W],...",
        help="router-only (requires --replicas): split /queries.json "
        "traffic into weighted A/B variants sticky by cache scope — "
        "assignment is a pure hash of (salt, weights, scope), so it "
        "survives router restarts and replica failover; per-variant "
        "q/s, p50/p99 and reward counters appear on the router's "
        "/stats.json, and POST /experiments/promote.json collapses "
        "traffic onto the winner and rolls it fleet-wide "
        "(docs/operations.md experiment runbook)",
    )
    # ---- resilience (predictionio_tpu.resilience; docs/operations.md).
    # Defaults are the do-nothing configuration: single-attempt storage
    # calls, no breaker — identical to a build without these flags.
    deploy.add_argument(
        "--retry-reads", type=int, default=0, metavar="N",
        help="retry idempotent storage reads up to N extra times with "
        "exponential backoff + full jitter (default 0 = single attempt)",
    )
    deploy.add_argument(
        "--retry-writes", action="store_true",
        help="also retry storage writes; only safe when writes are "
        "idempotent (client-generated ids / upserts)",
    )
    deploy.add_argument(
        "--breaker-threshold", type=int, default=0, metavar="N",
        help="consecutive storage transport failures that open the "
        "circuit breaker (fail fast instead of stacking timeouts); "
        "0 = breaker disabled",
    )
    deploy.add_argument(
        "--breaker-reset-s", type=float, default=5.0,
        help="seconds an open breaker waits before letting one probe "
        "request through (half-open)",
    )
    deploy.add_argument(
        "--rpc-deadline-s", type=float, default=0.0,
        help="overall per-call budget consumed across retries, so a "
        "retried storage call never exceeds it (0 = per-attempt "
        "timeout only)",
    )
    deploy.add_argument(
        "--feedback-timeout", type=float, default=5.0, metavar="S",
        help="socket timeout for feedback event posts (worker thread, "
        "never the query path)",
    )
    deploy.add_argument(
        "--feedback-block-ms", type=float, default=0.0,
        help="when the feedback queue is full, block the query thread up "
        "to this long for a slot before dropping (default 0 = drop "
        "immediately)",
    )
    deploy.add_argument(
        "--no-feedback-blocking", action="store_true",
        help="force the feedback loop to never block the query path "
        "(overrides --feedback-block-ms; this is also the default)",
    )
    deploy.add_argument(
        "--feedback-breaker-threshold", type=int, default=0, metavar="N",
        help="consecutive failed feedback posts that open the feedback "
        "breaker (drop instantly while the event server is down instead "
        "of paying a connect timeout per event); 0 = disabled",
    )
    deploy.add_argument(
        "--feedback-breaker-reset-s", type=float, default=5.0,
        help="seconds an open feedback breaker waits before probing the "
        "event server again",
    )
    add_ssl_flags(deploy)
    add_lifecycle_flags(deploy)

    # ---- undeploy
    und = sub.add_parser(
        "undeploy", help="stop a deployed engine server via GET /stop"
    )
    und.add_argument("--ip", default="127.0.0.1")
    und.add_argument("--port", type=int, default=8000)
    und.add_argument("--https", action="store_true")
    und.add_argument(
        "--insecure", action="store_true",
        help="skip TLS certificate verification (self-signed deployments)",
    )
    und.add_argument(
        "--token", default=None,
        help="deployment stop token (default: read from the basedir token "
        "file written by `pio deploy` for this port)",
    )

    # ---- eval
    ev = sub.add_parser("eval", help="run an evaluation sweep")
    ev.add_argument("evaluation", help="import path of the Evaluation object")
    ev.add_argument(
        "params_generator",
        nargs="?",
        help="import path of the EngineParamsGenerator (optional if the "
        "Evaluation supplies engine_params_list)",
    )
    ev.add_argument("--batch", default="")
    ev.add_argument("--output-path", default="best.json")
    ev.add_argument(
        "--grid", action="store_true",
        help="train and score every candidate in ONE vmapped jit per "
        "fold shape (one compile per sweep, not per candidate) when the "
        "generator sweeps numeric ALS axes (lambda/alpha/seed); any "
        "non-vmappable sweep falls back to the sequential evaluator "
        "with the same output contract (docs/evaluation.md)",
    )
    ev.add_argument(
        "--promote-to", default=None, metavar="URL",
        help="after the sweep, POST the winning candidate's variant to "
        "URL/experiments/promote.json on a fleet router deployed with "
        "--variants — the sweep's candidate order must match the "
        "router's variant order (closing the eval → promote loop "
        "without an operator POST). Example: --promote-to "
        "http://127.0.0.1:8000",
    )

    # ---- eventserver
    es = sub.add_parser("eventserver", help="start the event server")
    es.add_argument("--ip", default="0.0.0.0")
    es.add_argument("--port", type=int, default=7070)
    es.add_argument("--stats", action="store_true")
    # ---- background compaction scheduler (docs/operations.md). Strictly
    # opt-in: 0 (default) starts no scheduler thread — tail compaction
    # stays the manual `pio app compact` it always was (CI-guarded).
    es.add_argument(
        "--compact-interval-s", type=float, default=0.0, metavar="S",
        help="sweep the columnar event store every S seconds and compact "
        "streams past the watermarks below (0 = no background "
        "compaction, the historical default; requires the columnar "
        "EVENTDATA backend)",
    )
    es.add_argument(
        "--compact-tail-mb", type=float, default=32.0, metavar="MB",
        help="tail-size watermark: compact a stream whose live JSONL "
        "tail exceeds MB mebibytes (default 32)",
    )
    es.add_argument(
        "--compact-dead-tombstones", type=int, default=10000, metavar="N",
        help="dead-bytes watermark: compact a stream with >= N "
        "tombstoned tail events (default 10000)",
    )
    es.add_argument(
        "--compact-min-interval-s", type=float, default=30.0, metavar="S",
        help="rate limit: never compact the same stream twice within S "
        "seconds (default 30)",
    )
    add_ssl_flags(es)
    add_lifecycle_flags(es)

    # ---- dashboard
    db = sub.add_parser("dashboard", help="start the evaluation dashboard")
    db.add_argument("--ip", default="127.0.0.1")
    db.add_argument("--port", type=int, default=9000)
    add_ssl_flags(db)
    add_lifecycle_flags(db)

    # ---- adminserver
    adm = sub.add_parser("adminserver", help="start the admin REST server")
    adm.add_argument("--ip", default="127.0.0.1")
    adm.add_argument("--port", type=int, default=7071)
    add_ssl_flags(adm)
    add_lifecycle_flags(adm)

    # ---- template
    tpl = sub.add_parser("template", help="built-in engine templates")
    tpl_sub = tpl.add_subparsers(dest="template_command", required=True)
    tpl_sub.add_parser("list")
    tpl_get = tpl_sub.add_parser("get")
    tpl_get.add_argument("name")
    tpl_get.add_argument("directory")
    tpl_get.add_argument("--appname", default="MyApp")

    # ---- storageserver
    ss = sub.add_parser(
        "storageserver",
        help="expose this host's storage backend over the network "
        "(server side of the TYPE=remote driver)",
    )
    ss.add_argument(
        "--ip", default="127.0.0.1",
        help="bind address; binding beyond loopback requires --secret "
        "(the server grants read/write on apps, keys, events and models)",
    )
    ss.add_argument("--port", type=int, default=7072)
    ss.add_argument(
        "--secret", default=None,
        help="shared secret clients must present (default: $PIO_STORAGE_SERVER_SECRET)",
    )
    add_ssl_flags(ss)
    add_lifecycle_flags(ss)

    # ---- chaos-ingest (predictionio_tpu.resilience.chaos)
    ch = sub.add_parser(
        "chaos-ingest",
        help="crash-safety drill: SIGKILL a real event-server subprocess "
        "under concurrent retrying writers and verify exactly-once "
        "ingestion, clean recovery, and graceful drain",
    )
    ch.add_argument("--cycles", type=int, default=3, help="SIGKILL/restart cycles")
    ch.add_argument("--writers", type=int, default=4, help="concurrent writer threads")
    ch.add_argument(
        "--events", type=int, default=120,
        help="events per writer across the whole run",
    )
    ch.add_argument(
        "--backend", choices=("sqlite", "columnar"), default="sqlite",
        help="EVENTDATA backend under test (columnar runs with FSYNC=true)",
    )
    ch.add_argument("--seed", type=int, default=0, help="kill-schedule RNG seed")
    ch.add_argument(
        "--bulk-events", type=int, default=1000,
        help="events streamed through POST /events/bulk.json in the "
        "bulk-writer phase (SIGKILL lands mid-stream; 0 disables)",
    )
    ch.add_argument(
        "--drain-deadline-s", type=float, default=5.0,
        help="drain deadline for the final SIGTERM-under-load phase",
    )
    ch.add_argument(
        "--partitions", type=_int_at_least(1), default=1,
        help=">1 adds the kill-one-partition drill: a columnar store "
        "with PARTITIONS=P, one partition's appender chaos-killed "
        "mid-bulk-stream plus a whole-server SIGKILL mid-retry — zero "
        "acked loss, zero duplicates, surviving partitions never stall, "
        "the killed partition catches up",
    )
    ch.add_argument(
        "--replication", type=int, default=0,
        help="with --partitions: replicas per partition (0 off, else "
        ">= 2); the drill also kills one non-leader replica and asserts "
        "loud quorum-loss degradation plus replica catch-up",
    )
    ch.add_argument(
        "--ack-quorum", type=int, default=0,
        help="fsync-durable copies required per ack (default: majority "
        "of --replication)",
    )
    ch.add_argument(
        "--keep", action="store_true",
        help="keep the scratch storage directory for inspection",
    )

    # ---- chaos-serve (predictionio_tpu.resilience.chaos; ISSUE 15)
    cs = sub.add_parser(
        "chaos-serve",
        help="serving-fleet drill: train a tiny model, deploy "
        "`--replicas N` behind the router, SIGKILL replicas under >= 16 "
        "concurrent query clients and rolling-/reload the fleet — "
        "verifying ZERO failed queries, zero cross-generation results, "
        "and p99 recovery within one breaker reset",
    )
    cs.add_argument(
        "--replicas", type=_int_at_least(1), default=2,
        help="fleet size for the kill/rolling phases (default 2)",
    )
    cs.add_argument(
        "--clients", type=_int_at_least(1), default=16,
        help="concurrent query clients (default 16)",
    )
    cs.add_argument(
        "--kills", type=_int_at_least(1), default=1,
        help="replica SIGKILLs during the kill phase (default 1)",
    )
    cs.add_argument(
        "--seconds", type=float, default=6.0,
        help="kill-phase duration in seconds (default 6)",
    )
    cs.add_argument(
        "--reloads", type=_int_at_least(0), default=1,
        help="rolling /reload rotations under load (default 1)",
    )
    cs.add_argument(
        "--events", type=int, default=400,
        help="synthetic training events (default 400)",
    )
    cs.add_argument("--seed", type=int, default=0, help="workload RNG seed")
    cs.add_argument(
        "--aot", action="store_true",
        help="run the drill AOT-on: `pio train --aot` exports the "
        "generation's programs, replicas deploy with --aot, and the "
        "rolling-reload phase additionally asserts ZERO serve-time "
        "compiles across the full rotation (reload-p99 gated against "
        "steady-state; docs/operations.md AOT runbook)",
    )
    cs.add_argument(
        "--sharded-point", action="store_true",
        help="also measure one fleet whose replicas serve with "
        "--shard-factors (8-way virtual host mesh)",
    )
    cs.add_argument(
        "--keep", action="store_true",
        help="keep the scratch storage directory for inspection",
    )

    # ---- chaos-fleet (predictionio_tpu.resilience.chaos; ISSUE 17)
    cf = sub.add_parser(
        "chaos-fleet",
        help="cross-host elastic-fleet drill: two 'hosts' (separate "
        "basedirs) share one endpoint registry behind an HA router "
        "pair; SIGKILL an entire host's fleet under concurrent "
        "never-retrying clients (zero failed queries, the survivor "
        "absorbs, the dead host rejoins via the registry), drive the "
        "autoscaler through a watermark scale-up and a drain-aware "
        "scale-down (zero in-flight loss), and prove the "
        "stale-while-down cache serves marked answers only when every "
        "replica is dead",
    )
    cf.add_argument(
        "--replicas-per-host", type=_int_at_least(1), default=1,
        help="replica fleet size on each 'host' (default 1)",
    )
    cf.add_argument(
        "--clients", type=_int_at_least(1), default=16,
        help="concurrent query clients (default 16)",
    )
    cf.add_argument(
        "--seconds", type=float, default=6.0,
        help="host-kill phase duration in seconds (default 6)",
    )
    cf.add_argument(
        "--events", type=int, default=400,
        help="synthetic training events (default 400)",
    )
    cf.add_argument(
        "--lease-ttl-s", type=float, default=1.0,
        help="endpoint-registry lease TTL under test (default 1.0)",
    )
    cf.add_argument("--seed", type=int, default=0, help="workload RNG seed")
    cf.add_argument(
        "--skip-autoscale", action="store_true",
        help="skip the autoscaler phase (host-kill + stale only)",
    )
    cf.add_argument(
        "--keep", action="store_true",
        help="keep the scratch storage directories for inspection",
    )

    # ---- batchpredict
    bp = sub.add_parser("batchpredict", help="bulk predictions from a query file")
    bp.add_argument("--engine-json", default="engine.json")
    bp.add_argument("--input", required=True, help="JSON-lines query file")
    bp.add_argument("--output", required=True, help="JSON-lines results file")
    bp.add_argument("--engine-instance-id")

    # ---- build (no-op parity)
    sub.add_parser(
        "build", help="no-op (Python engines need no compilation; kept for parity)"
    )

    # ---- run: execute a command with the storage/config env injected
    # (parity: Console.scala `pio run <main class>` launching user code
    # against the configured storage; here the subprocess inherits the
    # resolved PIO_* env so ad-hoc scripts see the same storage the CLI
    # does)
    run_p = sub.add_parser(
        "run", help="run a command with the framework environment injected"
    )
    run_p.add_argument(
        "run_args", nargs=argparse.REMAINDER,
        help="command and arguments (e.g. `pio run python myscript.py`)",
    )

    # ---- lint (piolint: predictionio_tpu.analysis; docs/development.md)
    lint = sub.add_parser(
        "lint",
        help="run piolint — AST layering/concurrency/JAX-hygiene analysis "
        "over the source tree (exits 1 on any non-baselined finding)",
    )
    lint.add_argument(
        "--root", default=None,
        help="tree to lint (default: this checkout's repo root)",
    )
    lint.add_argument(
        "--format", choices=("text", "json", "sarif"), default="text",
        help="text = file:line diagnostics; json = machine-readable "
        "summary + findings; sarif = SARIF 2.1.0 for inline code-review "
        "annotations (new findings level=error, baselined level=note)",
    )
    lint.add_argument(
        "--baseline", default=None, metavar="FILE",
        help="baseline file (default: <root>/piolint-baseline.json)",
    )
    lint.add_argument(
        "--update-baseline", action="store_true",
        help="rewrite the baseline to the current findings (keeps "
        "existing justifications); add one-line justifications before "
        "committing",
    )
    lint.add_argument(
        "--prune-baseline", action="store_true",
        help="drop baseline entries no current finding matches (fixed "
        "findings) AND compile-budget.json entries whose entrypoint no "
        "longer exists, without accepting anything new; CI fails on "
        "stale entries, this is the one-command cleanup",
    )
    lint.add_argument(
        "--witness", default=None, metavar="REPORT",
        help="cross-check a recorded lock-witness report (pytest "
        "--lock-witness or pio tsan JSON output) against the static "
        "lock graph, both directions: a witnessed acquisition-order "
        "edge missing from the static digraph (analyzer gap) or a "
        "static cycle that neither manifested nor carries a "
        "lock-witness-waivers.json entry fails the lint",
    )

    # ---- tsan (runtime lock-witness: predictionio_tpu.analysis.witness)
    tsan = sub.add_parser(
        "tsan",
        help="run a pio command under the lock-witness sanitizer: "
        "records the lock acquisition-order digraph, hold-time "
        "percentiles and sleeps-under-lock, reports witnessed "
        "lock-order inversions, and classifies every static PIO207 "
        "cycle as CONFIRMED or PLAUSIBLE (docs/operations.md)",
    )
    tsan.add_argument(
        "--report", default=None, metavar="FILE",
        help="also write the JSON report to FILE",
    )
    tsan.add_argument(
        "--long-hold-ms", type=float, default=50.0,
        help="hold time above which an acquisition counts as a long "
        "hold (default 50)",
    )
    tsan.add_argument(
        "tsan_args", nargs=argparse.REMAINDER,
        help="command to run under the witness, e.g. "
        "`pio tsan -- chaos-ingest --cycles 1`",
    )

    # ---- jitwitness (runtime jit-witness: predictionio_tpu.analysis
    # .jit_witness — the compile/transfer sibling of `pio tsan`)
    jitw = sub.add_parser(
        "jitwitness",
        help="run a pio command under the jit-witness sanitizer: counts "
        "XLA compiles per call site (with first-compile latency), "
        "device->host transfer bytes, and per-call jax.jit "
        "constructions; classifies every static PIO306-308 finding "
        "CONFIRMED or PLAUSIBLE and checks the compile-budget.json "
        "ledger (docs/operations.md)",
    )
    jitw.add_argument(
        "--report", default=None, metavar="FILE",
        help="also write the JSON report to FILE",
    )
    jitw.add_argument(
        "--ledger", default=None, metavar="FILE",
        help="compile-budget ledger (default: <repo>/compile-budget.json)",
    )
    jitw.add_argument(
        "jitwitness_args", nargs=argparse.REMAINDER,
        help="command to run under the witness, e.g. "
        "`pio jitwitness -- batchpredict --input q.json --output o.json`",
    )

    # ---- upgrade (informational parity stub)
    sub.add_parser(
        "upgrade",
        help="print upgrade guidance (pip-managed; no in-place upgrader)",
    )
    return p


def _parse_mesh(spec: str):
    from predictionio_tpu.controller.context import local_context, mesh_context

    if spec == "none":
        return local_context()
    if spec == "auto":
        return mesh_context()  # one device: the mesh-less context
    sizes = {}
    for part in spec.split(","):
        axis, _, n = part.partition("=")
        sizes[axis.strip()] = int(n)
    return mesh_context(
        axis_sizes=list(sizes.values()), axis_names=list(sizes.keys())
    )


def _train_aot_export(variant, ctx, instance) -> None:
    """``pio train --aot``: lower + serialize the just-trained
    instance's serving programs (workflow/aot.py) and stamp the
    artifact set into the fleet model registry beside the generation.

    The instance is re-hydrated exactly the way ``pio deploy`` will
    (``prepare_deploy`` over the stored blob), so what is exported is
    what will serve. A failed export never fails the train — artifacts
    are an optimization and deploy falls back loudly to tier 2/3."""
    from predictionio_tpu.data.storage import Storage
    from predictionio_tpu.fleet.registry import ModelRegistry
    from predictionio_tpu.workflow import aot

    engine = variant.build_engine()
    engine_params = engine.params_from_json(variant.raw)
    model = Storage.get_model_data_models().get(instance.id)
    if model is None:
        print(
            "WARNING: --aot: no model blob stored for this instance; "
            "nothing to export",
            file=sys.stderr,
        )
        return
    _, pairs = engine.prepare_deploy(
        ctx, engine_params, instance.id, model.models
    )
    base_dir = Storage.base_dir()
    root = os.path.join(base_dir, "fleet", "aot")
    manifest = aot.export_instance(pairs, instance.id, root)
    if manifest is None:
        print(
            "WARNING: --aot: no algorithm exported a serving program "
            "(algorithms without the aot_export_for_serving hook "
            "contribute nothing); `pio deploy --aot` will fall back to "
            "tier 2/3",
            file=sys.stderr,
        )
        return
    total = sum(int(e.get("bytes", 0)) for e in manifest.get("entries", []))
    record = ModelRegistry(os.path.join(base_dir, "fleet")).publish(
        instance.id,
        meta={"publisher": "train --aot"},
        artifacts={
            "dir": aot.artifact_dir(root, instance.id),
            "programs": len(manifest.get("entries", [])),
            "bytes": total,
            "fingerprint": manifest.get("fingerprint", {}),
        },
    )
    print(
        f"AOT export: {len(manifest.get('entries', []))} programs "
        f"({total} bytes) for instance {instance.id} "
        f"(fleet generation {record.generation})"
    )


def _replica_argv(args, replica_id: str, announce_dir: str) -> list[str]:
    """Reconstruct a single-replica ``deploy`` argv from the parsed fleet
    args: every non-default deploy flag is carried over (so
    ``--shard-factors``/``--quantize``/``--ann``/... compose per
    replica), while the fleet/router flags, the public bind, and TLS are
    stripped — replicas listen plaintext on loopback (the router
    terminates TLS) with their own identity. Each replica binds **port
    0** and self-reports its actually-bound address through the endpoint
    registry (``--announce-dir``), so no port is ever picked before the
    bind — the pick-then-spawn race is structurally impossible. Derived
    from the parsed namespace, not raw argv, so ``--flag=value``
    spellings and future flags need no special-casing."""
    defaults = build_parser().parse_args(["deploy"])
    skip = {
        "command",
        # fleet/router-only flags never reach a replica
        "replicas", "replica_id", "probe_interval_s", "failover_retries",
        "hedge_ms", "fleet_breaker_threshold", "fleet_breaker_reset_s",
        "variants", "endpoint_registry", "router_only", "autoscale",
        "scale_up_qps", "scale_up_p99_ms", "scale_down_qps",
        "scale_cooldown_s", "stale_cache_ttl_s",
        # rebound below / router-terminated
        "ip", "port", "cert", "key", "announce_dir", "announce_host",
        "lease_ttl_s",
    }
    argv = ["-m", "predictionio_tpu.tools.console", "deploy"]
    for name, value in sorted(vars(args).items()):
        if name in skip or value == getattr(defaults, name, None):
            continue
        if value is None or value is False:
            continue
        flag = "--" + name.replace("_", "-")
        if value is True:
            argv.append(flag)
        else:
            argv.extend([flag, str(value)])
    argv.extend(
        [
            "--ip", "127.0.0.1", "--port", "0",
            "--replica-id", replica_id,
            "--announce-dir", announce_dir,
            "--announce-host", args.announce_host,
            "--lease-ttl-s", str(args.lease_ttl_s),
        ]
    )
    return argv


def _deploy_fleet(args) -> int:
    """``pio deploy --replicas N`` (and ``--router-only``): spawn the
    replica subprocesses under the self-healing supervisor and serve the
    fleet router on the public port. Replicas bind port 0 and join the
    ring by announcing their bound address through the shared endpoint
    registry — the router starts with an EMPTY ring and reconciles
    membership from the registry every probe interval, so replicas on
    other hosts (same ``--endpoint-registry`` directory) join the same
    ring. SIGTERM/SIGINT, ``GET /stop`` (token-gated) and ``pio
    undeploy`` all stop the WHOLE fleet — replicas must never outlive
    their router."""
    import atexit
    import signal as _signal
    import threading

    from predictionio_tpu.api.http import serve
    from predictionio_tpu.data.storage import Storage
    from predictionio_tpu.fleet import (
        EndpointRegistry,
        FleetSupervisor,
        ModelRegistry,
        ReplicaSpec,
        RouterConfig,
        RouterService,
        fleet_state_path,
    )
    from predictionio_tpu.tools import commands

    if args.router_only and args.autoscale:
        raise SystemExit(
            "--router-only serves no supervisor to scale; run --autoscale "
            "on the fleet that owns the replicas"
        )
    base_dir = Storage.base_dir()
    endpoints_dir = args.endpoint_registry or os.path.join(
        base_dir, "fleet", "endpoints"
    )
    endpoint_registry = EndpointRegistry(
        endpoints_dir, lease_ttl_s=args.lease_ttl_s
    )
    # With an EXPLICIT shared registry, many supervisors feed one ring —
    # replica ids minted per-host (r0, scale1, ...) would collide across
    # hosts and silently overwrite each other's registry entries, so
    # each id carries a host-unique token. The default (private)
    # registry keeps the bare ids.
    if args.endpoint_registry:
        import socket

        host_token = f"{socket.gethostname().split('.')[0]}-{os.getpid()}"

        def _rid(base: str) -> str:
            return f"{base}@{host_token}"
    else:
        def _rid(base: str) -> str:
            return base

    specs: list[ReplicaSpec] = []
    if not args.router_only:
        for i in range(args.replicas):
            rid = _rid(f"r{i}")
            specs.append(
                ReplicaSpec(
                    rid, 0, tuple(_replica_argv(args, rid, endpoints_dir))
                )
            )
    config = RouterConfig(
        probe_interval_s=args.probe_interval_s,
        failover_retries=args.failover_retries,
        hedge_ms=args.hedge_ms,
        breaker_threshold=args.fleet_breaker_threshold,
        breaker_reset_s=args.fleet_breaker_reset_s,
        scope_field=(
            None
            if args.cache_scope_field.lower() in ("none", "")
            else args.cache_scope_field
        ),
        stale_cache_ttl_s=args.stale_cache_ttl_s,
    )
    registry = ModelRegistry(os.path.join(base_dir, "fleet"))
    split = None
    if args.variants:
        # lazy: without --variants no experiments module is imported
        from predictionio_tpu.experiments.split import SplitConfig, TrafficSplit

        split = TrafficSplit(SplitConfig.parse(args.variants))
        print(
            "A/B experiment: "
            + ", ".join(
                f"{v.name}:{v.weight:g}" for v in split.config.variants
            )
            + f" (sticky by {config.scope_field or 'whole-body hash'})"
        )
    router = RouterService(
        [], config, registry=registry, split=split,
        endpoint_registry=endpoint_registry,
    )
    supervisor = None
    autoscaler = None
    if not args.router_only:
        supervisor = FleetSupervisor(
            specs, fleet_state_path(base_dir, args.port), args.port
        )
        supervisor.start()
        if args.autoscale:
            from predictionio_tpu.fleet.autoscaler import (
                Autoscaler,
                AutoscalerConfig,
            )

            lo, _, hi = args.autoscale.partition(":")
            try:
                scale_cfg = AutoscalerConfig(
                    min_replicas=int(lo),
                    max_replicas=int(hi or lo),
                    scale_up_qps=args.scale_up_qps,
                    scale_up_p99_ms=args.scale_up_p99_ms,
                    scale_down_qps=args.scale_down_qps,
                    cooldown_s=args.scale_cooldown_s,
                )
            except ValueError as e:
                raise SystemExit(f"--autoscale: {e}")
            autoscaler = Autoscaler(
                router,
                supervisor,
                lambda rid: ReplicaSpec(
                    _rid(rid), 0,
                    tuple(_replica_argv(args, _rid(rid), endpoints_dir)),
                ),
                scale_cfg,
            )
            autoscaler.start()
    router.start()
    stopped = threading.Event()

    def shutdown_fleet():
        if stopped.is_set():
            return
        stopped.set()
        if autoscaler is not None:
            autoscaler.stop()
        router.close()
        if supervisor is not None:
            supervisor.stop()

    atexit.register(shutdown_fleet)

    def wire_stop(server):
        router.stop_token = commands.write_stop_token(args.port)

        def stop_all():
            def run():
                shutdown_fleet()
                server.shutdown()

            threading.Thread(target=run, daemon=True).start()

        router.stop_server = stop_all
        # first signal stops the fleet (replicas get SIGTERM, so each
        # drains per its own --drain-deadline-s, withdraws its registry
        # entry, and only then exits); the router's listener follows
        _signal.signal(_signal.SIGTERM, lambda s, f: stop_all())
        _signal.signal(_signal.SIGINT, lambda s, f: stop_all())

    role = "HA router" if args.router_only else "router"
    print(
        f"Fleet is deployed: {role} on {args.ip}:{args.port}, "
        f"{len(specs)} replica(s) self-reporting via {endpoints_dir}"
        + (f", autoscale {args.autoscale}" if autoscaler else "")
    )
    serve(
        router.dispatch, args.ip, args.port,
        ssl_context=_ssl_from_args(args), ready_callback=wire_stop,
    )
    shutdown_fleet()
    return 0


def _start_announcer(args, service, server) -> None:
    """Replica self-report (ISSUE 17): publish this server's
    *actually-bound* address (``--port 0`` capable — the port is read
    off the live socket, never picked in advance) into the shared
    endpoint registry, heartbeat the lease, and withdraw on drain/exit
    so clean retirement leaves no entry to expire. Lazy import: only
    ``--announce-dir`` pays for the fleet module."""
    import atexit
    import threading

    from predictionio_tpu.fleet.registry import EndpointRegistry

    host, port = args.announce_host, server.server_address[1]
    rid = args.replica_id or f"pid{os.getpid()}"
    registry = EndpointRegistry(
        args.announce_dir, lease_ttl_s=args.lease_ttl_s
    )
    stop = threading.Event()

    def generation() -> int:
        try:
            return int(getattr(service, "model_generation", 0) or 0)
        except (TypeError, ValueError):
            return 0

    registry.announce(rid, host, port, generation=generation())
    print(
        f"Announced replica {rid} at {host}:{port} in "
        f"{args.announce_dir} (lease {args.lease_ttl_s:g}s)"
    )

    def heartbeat() -> None:
        interval = max(0.05, args.lease_ttl_s / 3.0)
        while not stop.wait(interval):
            try:
                registry.heartbeat(rid, host, port, generation=generation())
            except OSError:
                pass  # sharedfs hiccup: the next beat renews the lease

    threading.Thread(
        target=heartbeat, name="endpoint-heartbeat", daemon=True
    ).start()

    def withdraw() -> None:
        stop.set()
        try:
            registry.withdraw(rid)
        except OSError:
            pass

    # drain withdraws FIRST (routers reconcile this replica out before
    # the listener closes); atexit covers non-drain exits
    if hasattr(service, "on_close"):
        service.on_close.append(withdraw)
    atexit.register(withdraw)


def _lifecycle_from_args(args):
    """Opt-in :class:`~predictionio_tpu.api.lifecycle.DrainManager` from
    ``--drain-deadline-s``. 0 (the default) returns None — signals keep
    their historical immediate-exit behavior, guarded by
    tests/test_ci_guards.py. When enabled, SIGTERM/SIGINT handlers are
    installed here (console main runs on the main thread, a signal-API
    requirement) and the process-wide storage flush is registered as the
    final drain hook; the served service's own ``drain`` hook (e.g. the
    query server's batcher close) is discovered by the HTTP wrapper and
    runs before it."""
    deadline = getattr(args, "drain_deadline_s", 0.0)
    if not deadline or deadline <= 0:
        return None
    from predictionio_tpu import resilience
    from predictionio_tpu.api.lifecycle import DrainManager
    from predictionio_tpu.data.storage import Storage

    lifecycle = DrainManager(deadline)
    lifecycle.install_signals()
    lifecycle.add_drain_hook(Storage.close)
    # drain state (in-flight count, rejections) joins the resilience
    # section of GET /stats.json on servers that serve one
    resilience.register_stats("lifecycle", lifecycle)
    return lifecycle


def _promote_winner(router_url: str, result) -> dict:
    """``pio eval --grid --promote-to URL``: close the sweep → promote
    loop (ROADMAP item 4's leftover). Maps the sweep's winning candidate
    INDEX onto the router's variant ORDER — ``GET /experiments.json``
    lists variants in ``--variants`` order, so the operator deploys one
    variant per sweep candidate in the same order — then POSTs the
    promotion (which rolls the fleet). Loud ``SystemExit`` on any
    mismatch: a silently mis-mapped promotion would roll the wrong model
    fleet-wide."""
    import urllib.error
    import urllib.request

    url = router_url.rstrip("/")
    try:
        with urllib.request.urlopen(
            url + "/experiments.json", timeout=10
        ) as r:
            experiments = json.load(r)
    except (urllib.error.URLError, json.JSONDecodeError, OSError) as e:
        raise SystemExit(
            f"--promote-to: cannot read {url}/experiments.json: {e}"
        )
    variants = [v.get("name") for v in experiments.get("variants", [])]
    candidates = len(result.engine_params_scores)
    if len(variants) != candidates:
        raise SystemExit(
            f"--promote-to: the router serves {len(variants)} variant(s) "
            f"{variants} but the sweep scored {candidates} candidate(s) — "
            "refusing to guess the mapping; deploy --variants with one "
            "variant per sweep candidate, in the same order"
        )
    winner = variants[result.best_index]
    req = urllib.request.Request(
        url + "/experiments/promote.json",
        data=json.dumps({"variant": winner}).encode(),
        headers={"Content-Type": "application/json"},
        method="POST",
    )
    try:
        # the promotion rolls every replica through /reload — budget it
        # like a rolling reload, not like a GET
        with urllib.request.urlopen(req, timeout=600) as r:
            payload = json.load(r)
            status = r.status
    except urllib.error.HTTPError as e:
        raise SystemExit(
            f"--promote-to: promotion of {winner!r} failed "
            f"({e.code}): {e.read()[:300]!r}"
        )
    except (urllib.error.URLError, json.JSONDecodeError, OSError) as e:
        raise SystemExit(f"--promote-to: promotion of {winner!r} failed: {e}")
    return {
        "promotedVariant": winner,
        "bestIndex": result.best_index,
        "status": status,
        "router": payload,
    }


def _ssl_from_args(args):
    """TLS context from --cert/--key flags, falling back to the
    PIO_SSL_CERT / PIO_SSL_KEY env vars; None = plain http. A
    half-specified pair is an error — silently starting plain HTTP when
    the operator passed --cert would leak traffic they meant to encrypt."""
    from predictionio_tpu.api.http import make_ssl_context, ssl_context_from_env

    cert = getattr(args, "cert", None)
    key = getattr(args, "key", None)
    if bool(cert) != bool(key):
        raise ValueError("--cert and --key must be given together")
    if cert and key:
        return make_ssl_context(cert, key)
    return ssl_context_from_env()


def main(argv: list[str] | None = None) -> int:
    # process start (the kernel's record) to here: interpreter, imports
    # and, under a wrapper that opens the device first, the device
    startup_s = spans.process_age_s()
    startup_cpu_s = time.process_time()
    # PIO_JAX_PLATFORMS=cpu forces the JAX platform even when the
    # interpreter preloaded jax with a different one (CPU CI runs,
    # multi-host rehearsals on hosts whose default platform is a single
    # accelerator). Must happen before any backend initializes.
    platform_override = os.environ.get("PIO_JAX_PLATFORMS")
    if platform_override:
        import jax

        jax.config.update("jax_platforms", platform_override)
    args = build_parser().parse_args(argv)
    # one rule (utils/compile_cache.py): $JAX_COMPILATION_CACHE_DIR if
    # set, else <checkout>/.jax_cache — a repeat `pio train` on the same
    # shapes skips the compile, and a deploy reuses what train compiled
    compile_cache.configure()
    cmd = args.command
    try:
        if cmd == "version":
            print(__version__)
        elif cmd == "status":
            results = commands.status_check()
            return 0 if results["ok"] else 1
        elif cmd == "app":
            ac = args.app_command
            if ac == "new":
                commands.app_new(args.name, args.description, args.access_key)
            elif ac == "list":
                commands.app_list()
            elif ac == "show":
                commands.app_show(args.name)
            elif ac == "delete":
                commands.app_delete(args.name)
            elif ac == "data-delete":
                commands.app_data_delete(args.name, args.channel)
            elif ac == "compact":
                commands.app_compact(args.name, args.channel)
            elif ac == "channel-new":
                commands.channel_new(args.name, args.channel)
            elif ac == "channel-delete":
                commands.channel_delete(args.name, args.channel)
        elif cmd == "accesskey":
            akc = args.accesskey_command
            if akc == "new":
                commands.accesskey_new(args.app_name, args.events)
            elif akc == "list":
                commands.accesskey_list(args.app_name)
            elif akc == "delete":
                commands.accesskey_delete(args.key)
        elif cmd == "import":
            commands.import_events(args.appname, args.input, args.channel)
        elif cmd == "export":
            commands.export_events(
                args.appname, args.output, args.channel,
                num_shards=args.sharded, format=args.format,
            )
        elif cmd == "train":
            from predictionio_tpu.parallel import initialize_from_env
            from predictionio_tpu.workflow import load_engine_variant, run_train
            from predictionio_tpu.workflow.core import WorkflowParams

            initialize_from_env()  # multi-host when PIO_COORDINATOR_* set
            # the main thread feeds the device: its leaf spans also go
            # into a profiler trace, if someone wraps this job in one,
            # and each records the thread's CPU time beside its wall
            unbound = spans.bind(spans.Collector(annotate=True, cpu=True))
            try:
                with spans.span("train.backend_init") as backend_init:
                    import jax

                    jax.devices()
                phase_timings = {
                    "backend_init": round(backend_init.seconds, 3)
                }
                if startup_s is not None:
                    phase_timings["startup"] = round(startup_s, 3)
                    # the process's CPU seconds over the same stretch
                    # (every thread's; a span's are its own thread's)
                    phase_timings["cpu"] = {"startup": round(startup_cpu_s, 3)}
                variant = load_engine_variant(args.engine_json)
                ctx = _parse_mesh(args.mesh)
                instance = run_train(
                    variant,
                    ctx,
                    WorkflowParams(
                        batch=args.batch,
                        skip_sanity_check=args.skip_sanity_check,
                        stop_after_read=args.stop_after_read,
                        stop_after_prepare=args.stop_after_prepare,
                        warm_start=args.warm_start,
                    ),
                    phase_timings=phase_timings,
                )
            finally:
                spans.bind(unbound)
            if args.aot:
                # lazy: without --aot no AOT module is imported and the
                # train output is byte-identical (CI-guarded)
                _train_aot_export(variant, ctx, instance)
            print(f"Training completed. Engine instance: {instance.id}")
        elif cmd == "deploy":
            if (args.replicas and args.replicas > 0) or args.router_only:
                # replica-fleet path (ISSUE 15/17): router + replica
                # subprocesses (or a bare HA router). Gated here so a
                # fleet-less deploy never imports predictionio_tpu.fleet
                # (CI-guarded).
                return _deploy_fleet(args)
            from predictionio_tpu import resilience
            from predictionio_tpu.api.http import serve
            from predictionio_tpu.serving import BatcherConfig
            from predictionio_tpu.workflow import load_engine_variant
            from predictionio_tpu.workflow.serving import FeedbackConfig, QueryService

            # before any storage client exists: the lazily-built remote
            # driver reads these process-wide defaults (per-source
            # PIO_STORAGE_SOURCES_<ID>_* properties still win)
            resilience.set_rpc_defaults(
                retries=args.retry_reads,
                retry_writes=args.retry_writes,
                breaker_threshold=args.breaker_threshold,
                breaker_reset_s=args.breaker_reset_s,
                deadline_s=args.rpc_deadline_s,
            )
            variant = load_engine_variant(args.engine_json)
            feedback = None
            if args.feedback:
                feedback = FeedbackConfig(
                    event_server_url=(
                        f"http://{args.event_server_ip}:{args.event_server_port}"
                    ),
                    access_key=args.accesskey,
                    timeout_s=args.feedback_timeout,
                    block_ms=(
                        0.0 if args.no_feedback_blocking else args.feedback_block_ms
                    ),
                    breaker_threshold=args.feedback_breaker_threshold,
                    breaker_reset_s=args.feedback_breaker_reset_s,
                )
            batching = None
            if args.batching:
                batching = BatcherConfig(
                    max_batch_size=args.max_batch_size,
                    max_batch_delay_ms=args.max_batch_delay_ms,
                    max_queue=args.batch_queue,
                    admission=args.admission_policy,
                    block_timeout_ms=args.admission_timeout_ms,
                    buckets=tuple(
                        int(x) for x in args.batch_buckets.split(",") if x.strip()
                    ),
                    warmup_body=(
                        json.loads(args.batch_warmup_query)
                        if args.batch_warmup_query
                        else None
                    ),
                )
            cache = None
            if (
                args.result_cache or args.coalesce or args.pin_model
                or args.shard_factors or args.quantize
            ):
                from predictionio_tpu.serving import CacheConfig

                cache = CacheConfig(
                    result_cache=args.result_cache,
                    result_cache_entries=args.result_cache_entries,
                    result_cache_ttl_s=args.result_cache_ttl_s,
                    result_cache_max_bytes=int(
                        args.result_cache_max_mb * 1024 * 1024
                    ),
                    coalesce=args.coalesce,
                    pin_model=args.pin_model,
                    shard_factors=args.shard_factors,
                    quantize=args.quantize,
                    scope_field=(
                        None
                        if args.cache_scope_field.lower() in ("none", "")
                        else args.cache_scope_field
                    ),
                )
            ann = None
            if args.ann:
                from predictionio_tpu.serving import AnnConfig

                ann = AnnConfig(
                    enabled=True,
                    nlist=args.ann_nlist,
                    nprobe=args.ann_nprobe,
                    seed=args.ann_seed,
                    kmeans_iters=args.ann_kmeans_iters,
                )
            online = None
            if args.online:
                from predictionio_tpu.online import OnlineConfig

                online = OnlineConfig(
                    enabled=True,
                    interval_s=args.online_interval_s,
                    batch_size=args.online_batch,
                    algorithms=tuple(
                        t.strip()
                        for t in args.online_algos.split(",")
                        if t.strip()
                    ),
                    prior_weight=args.online_prior_weight,
                    from_start=args.online_from_start,
                )
            explore = None
            if args.explore:
                # lazy: without --explore no experiments module is imported
                from predictionio_tpu.experiments.explore import ExploreConfig

                explore = ExploreConfig(
                    policy=args.explore,
                    epsilon=args.explore_epsilon,
                    seed=args.explore_seed,
                    reward_event=args.explore_reward_event,
                )
            aot = None
            if args.aot:
                # lazy: without --aot no AOT module is imported and the
                # serving path is byte-identical (CI-guarded)
                from predictionio_tpu.data.storage import Storage
                from predictionio_tpu.workflow.aot import AotConfig

                aot = AotConfig(
                    enabled=True,
                    root=os.path.join(Storage.base_dir(), "fleet", "aot"),
                )
            service = QueryService(
                variant, feedback=feedback, instance_id=args.engine_instance_id,
                batching=batching, cache=cache, ann=ann, online=online,
                explore=explore, replica_id=args.replica_id, aot=aot,
            )

            def wire_stop(server):
                # GET /stop answers first, then the server shuts down on a
                # helper thread (shutdown() from a handler would deadlock).
                # The stop token is written only after a successful bind so
                # a failed re-deploy on a busy port cannot clobber the live
                # deployment's token file. Keyed by the BOUND port, so
                # --port 0 deployments get a usable token too.
                import threading

                bound_port = server.server_address[1]
                service.stop_token = commands.write_stop_token(bound_port)
                service.stop_server = lambda: threading.Thread(
                    target=server.shutdown, daemon=True
                ).start()
                if args.port == 0:
                    print(f"Bound port {bound_port}")
                if args.announce_dir:
                    _start_announcer(args, service, server)

            print(f"Engine is deployed and running. Listening on {args.ip}:{args.port}")
            serve(
                service.dispatch, args.ip, args.port,
                ssl_context=_ssl_from_args(args), ready_callback=wire_stop,
                lifecycle=_lifecycle_from_args(args),
            )
        elif cmd == "undeploy":
            commands.undeploy(
                args.ip, args.port, args.https, args.insecure, token=args.token
            )
        elif cmd == "eval":
            from predictionio_tpu.controller import local_context
            from predictionio_tpu.controller.evaluation import EngineParamsGenerator
            from predictionio_tpu.utils.reflection import resolve_attr
            from predictionio_tpu.workflow.core import WorkflowParams, run_evaluation

            evaluation = resolve_attr(args.evaluation)
            if callable(evaluation) and not hasattr(evaluation, "engine"):
                evaluation = evaluation()
            if args.params_generator:
                generator = resolve_attr(args.params_generator)
                if callable(generator) and not hasattr(generator, "engine_params_list"):
                    generator = generator()
            else:
                generator = EngineParamsGenerator(
                    getattr(evaluation, "engine_params_list", ())
                )
            if args.grid:
                # lazy: without --grid no experiments module is imported
                from predictionio_tpu.experiments.sweep import (
                    run_grid_evaluation,
                )

                instance, result = run_grid_evaluation(
                    evaluation,
                    generator,
                    local_context(),
                    WorkflowParams(batch=args.batch),
                    evaluation_class=args.evaluation,
                    generator_class=args.params_generator or "",
                )
            else:
                instance, result = run_evaluation(
                    evaluation,
                    generator,
                    local_context(),
                    WorkflowParams(batch=args.batch),
                    evaluation_class=args.evaluation,
                    generator_class=args.params_generator or "",
                )
            print(result.leaderboard())
            with open(args.output_path, "w") as f:
                json.dump(result.to_json(), f, indent=2, default=str)
            print(f"Best params written to {args.output_path}")
            if args.promote_to:
                report = _promote_winner(args.promote_to, result)
                print(json.dumps(report, indent=2, default=str))
        elif cmd == "eventserver":
            from predictionio_tpu.api import EventService
            from predictionio_tpu.api.http import serve

            service = EventService(stats=args.stats)
            if args.compact_interval_s and args.compact_interval_s > 0:
                from predictionio_tpu.data.storage import Storage
                from predictionio_tpu.data.storage.compaction import (
                    CompactionConfig,
                    CompactionScheduler,
                )

                le = Storage.get_l_events()
                if not (
                    hasattr(le, "stream_stats") and hasattr(le, "compact")
                ):
                    raise SystemExit(
                        "--compact-interval-s needs an EVENTDATA backend "
                        "with a tail to compact (TYPE=columnar)"
                    )
                service.compaction_scheduler = CompactionScheduler(
                    le,
                    CompactionConfig(
                        interval_s=args.compact_interval_s,
                        tail_bytes_high=int(
                            args.compact_tail_mb * 1024 * 1024
                        ),
                        dead_tombstones_high=args.compact_dead_tombstones,
                        min_interval_s=args.compact_min_interval_s,
                    ),
                )
                service.compaction_scheduler.start()
                print(
                    "Background compaction: every "
                    f"{args.compact_interval_s:g}s, tail >= "
                    f"{args.compact_tail_mb:g} MiB or >= "
                    f"{args.compact_dead_tombstones} dead tombstones"
                )
            print(f"Event Server is listening on {args.ip}:{args.port}")
            serve(
                service.dispatch, args.ip, args.port,
                ssl_context=_ssl_from_args(args),
                lifecycle=_lifecycle_from_args(args),
            )
        elif cmd == "dashboard":
            from predictionio_tpu.api.http import serve
            from predictionio_tpu.tools.dashboard import DashboardService

            print(f"Dashboard is listening on {args.ip}:{args.port}")
            serve(
                DashboardService().dispatch, args.ip, args.port,
                ssl_context=_ssl_from_args(args),
                lifecycle=_lifecycle_from_args(args),
            )
        elif cmd == "adminserver":
            from predictionio_tpu.api.http import serve
            from predictionio_tpu.tools.adminserver import AdminService

            print(f"Admin server is listening on {args.ip}:{args.port}")
            serve(
                AdminService().dispatch, args.ip, args.port,
                ssl_context=_ssl_from_args(args),
                lifecycle=_lifecycle_from_args(args),
            )
        elif cmd == "template":
            if args.template_command == "list":
                commands.template_list()
            elif args.template_command == "get":
                commands.template_get(args.name, args.directory, args.appname)
        elif cmd == "storageserver":
            from predictionio_tpu.api.http import serve
            from predictionio_tpu.data.storage.remote import StorageRpcService

            secret = args.secret or os.environ.get("PIO_STORAGE_SERVER_SECRET")
            loopback = args.ip.startswith("127.") or args.ip in ("localhost", "::1")
            if not loopback and not secret:
                raise SystemExit(
                    "storageserver grants unauthenticated read/write of apps, "
                    "access keys, events and model blobs; refusing to bind "
                    f"non-loopback address {args.ip!r} without --secret / "
                    "$PIO_STORAGE_SERVER_SECRET"
                )
            print(f"Storage server is listening on {args.ip}:{args.port}")
            serve(
                StorageRpcService(secret=secret).dispatch, args.ip, args.port,
                ssl_context=_ssl_from_args(args),
                lifecycle=_lifecycle_from_args(args),
            )
        elif cmd == "batchpredict":
            from predictionio_tpu.tools.batchpredict import run_batch_predict

            n = run_batch_predict(
                args.engine_json, args.input, args.output, args.engine_instance_id
            )
            print(f"Wrote {n} predictions to {args.output}")
        elif cmd == "build":
            print(
                "Nothing to build: Python engines are imported directly. "
                "(kept for command-line parity with the reference)"
            )
        elif cmd == "run":
            import subprocess

            cmdline = list(args.run_args)
            if cmdline and cmdline[0] == "--":
                cmdline = cmdline[1:]
            if not cmdline:
                print("ERROR: pio run needs a command to execute",
                      file=sys.stderr)
                return 1
            env = dict(os.environ)
            from predictionio_tpu.data.storage import Storage

            env.setdefault("PIO_FS_BASEDIR", Storage.base_dir())
            repo_root = os.path.dirname(
                os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
            )
            env["PYTHONPATH"] = (
                repo_root + os.pathsep + env.get("PYTHONPATH", "")
            ).rstrip(os.pathsep)
            return subprocess.run(cmdline, env=env).returncode
        elif cmd == "lint":
            # stdlib-only AST analysis: imports nothing it lints, never
            # initializes jax — safe and fast on any CI host. Exit code
            # contract (docs/development.md): 0 clean, 1 findings (or a
            # failed witness crosscheck), 2 internal error — so a CI job
            # can tell "the tree is dirty" from "the linter broke".
            try:
                from predictionio_tpu.analysis import run_lint

                res = run_lint(
                    root=args.root,
                    baseline_path=args.baseline,
                    update_baseline=args.update_baseline,
                    prune_stale=args.prune_baseline,
                )
                pruned_ledger = 0
                if args.prune_baseline:
                    # the compile-budget ledger prunes alongside the
                    # finding baseline: an entrypoint whose file or
                    # function is gone is the same class of stale debt
                    # (still stdlib-only — the prune is an AST existence
                    # check)
                    from predictionio_tpu.analysis import jit_witness

                    pruned_ledger = jit_witness.prune_ledger(
                        jit_witness.default_ledger_path(res.root), res.root
                    )
                xcheck = None
                if args.witness:
                    from predictionio_tpu.analysis import lock_witness

                    with open(args.witness, encoding="utf-8") as fh:
                        doc = json.load(fh)
                    # accept any recorded shape: a pytest --lock-witness
                    # / pio tsan payload ({"witness": {...}}) or a raw
                    # witness report ({"edges": [...]})
                    wrep = doc.get("witness", doc) if isinstance(
                        doc, dict
                    ) else {}
                    xcheck = lock_witness.crosscheck(wrep, root=res.root)
                ok = res.ok and (xcheck is None or xcheck["ok"])
                if args.format == "json":
                    payload = res.to_json()
                    # the ledger prune rewrites a checked-in file; a CI
                    # job reading the JSON must see that happened, same
                    # as prunedBaselineEntries
                    payload["prunedCompileBudgetEntries"] = pruned_ledger
                    if xcheck is not None:
                        payload["witnessCrosscheck"] = xcheck
                        payload["ok"] = ok
                    print(json.dumps(payload, indent=2))
                elif args.format == "sarif":
                    print(json.dumps(res.to_sarif(), indent=2))
                else:
                    for f in res.new_findings:
                        print(f.render())
                    summary = (
                        f"piolint: {res.files_scanned} files, "
                        f"{len(res.new_findings)} new finding(s), "
                        f"{len(res.baselined)} baselined, "
                        f"{res.suppressed_count} suppressed"
                    )
                    if res.pruned_baseline:
                        summary += (
                            f", {res.pruned_baseline} stale baseline entr"
                            f"{'y' if res.pruned_baseline == 1 else 'ies'} "
                            "pruned"
                        )
                    if pruned_ledger:
                        summary += (
                            f", {pruned_ledger} stale compile-budget entr"
                            f"{'y' if pruned_ledger == 1 else 'ies'} pruned"
                        )
                    if res.stale_baseline:
                        summary += (
                            f", {res.stale_baseline} stale baseline entr"
                            f"{'y' if res.stale_baseline == 1 else 'ies'} "
                            "(fixed findings — prune with --prune-baseline)"
                        )
                    print(summary)
                    if xcheck is not None:
                        print(
                            f"lock-witness crosscheck: "
                            f"{xcheck['dynamicEdges']} dynamic edge(s) vs "
                            f"{xcheck['staticEdges']} static, "
                            f"{len(xcheck['gaps'])} analyzer gap(s), "
                            f"{len(xcheck['unwaivedStaticCycles'])} "
                            f"unwaived static cycle(s), "
                            f"{len(xcheck['staleWaivers'])} stale waiver(s)"
                        )
                        for g in xcheck["gaps"]:
                            print(
                                f"  GAP: witnessed {g['from']} -> "
                                f"{g['to']} (x{g['count']}) has no static "
                                f"edge {g['staticFrom']} -> {g['staticTo']}"
                            )
                        for c in xcheck["unwaivedStaticCycles"]:
                            print(
                                "  UNWAIVED CYCLE: "
                                + " -> ".join(c["cycle"])
                                + f" ({c['witnessedEdges']}/"
                                f"{c['totalEdges']} edges witnessed; add "
                                "a lock-witness-waivers.json entry or "
                                "exercise it)"
                            )
                return 0 if ok else 1
            except Exception as e:  # noqa: BLE001 — exit-code contract
                print(f"piolint: internal error: {e}", file=sys.stderr)
                return 2
        elif cmd == "tsan":
            # run a nested pio command in-process under the lock-witness
            # sanitizer (stdlib-only; docs/operations.md "Lock-witness
            # runbook"). The child's locks allocated AFTER install are
            # recorded; its exit code is combined with the witness
            # verdict (any witnessed inversion fails the run).
            from predictionio_tpu.analysis import witness

            cmdline = list(args.tsan_args)
            if cmdline and cmdline[0] == "--":
                cmdline = cmdline[1:]
            if cmdline and cmdline[0] == "pio":
                cmdline = cmdline[1:]
            if not cmdline:
                print("ERROR: pio tsan needs a command to execute, e.g. "
                      "`pio tsan -- chaos-ingest --cycles 1`",
                      file=sys.stderr)
                return 1
            def run_child() -> int:
                # a nested command may leave via SystemExit (argparse
                # errors, server refusals) — fold that into an exit code
                # so the witness report survives; real witnessed work
                # already happened by then and must not be discarded
                try:
                    return main(cmdline)
                except SystemExit as e:
                    code = e.code
                    if code is None:
                        return 0
                    return code if isinstance(code, int) else 1

            from predictionio_tpu.analysis import lock_witness

            child_rc, payload = lock_witness.run_with_lock_witness(
                run_child,
                long_hold_ms=args.long_hold_ms,
                waivers=lock_witness.load_waivers(),
            )
            payload["command"] = cmdline
            payload["exitCode"] = child_rc
            if args.report:
                witness.write_report(args.report, payload)
            print(json.dumps(payload, indent=2, sort_keys=True))
            return 0 if (payload["ok"] and not child_rc) else 1
        elif cmd == "jitwitness":
            # run a nested pio command in-process under the jit-witness
            # sanitizer (docs/operations.md "Jit-witness runbook"): XLA
            # compiles per call site, transfer bytes, per-call jit
            # constructions; classifies the static PIO306-308 findings
            # and checks the compile-budget ledger. Exit 1 on a budget
            # VIOLATION or child failure — unbudgeted compiles are
            # reported, not fatal (arbitrary commands train/cold-start).
            from predictionio_tpu.analysis import jit_witness

            cmdline = list(args.jitwitness_args)
            if cmdline and cmdline[0] == "--":
                cmdline = cmdline[1:]
            if cmdline and cmdline[0] == "pio":
                cmdline = cmdline[1:]
            if not cmdline:
                print(
                    "ERROR: pio jitwitness needs a command to execute, "
                    "e.g. `pio jitwitness -- deploy ...`",
                    file=sys.stderr,
                )
                return 1

            def run_child_jw() -> int:
                try:
                    return main(cmdline)
                except SystemExit as e:
                    code = e.code
                    if code is None:
                        return 0
                    return code if isinstance(code, int) else 1

            child_rc, rep = jit_witness.run_with_jit_witness(run_child_jw)
            payload = jit_witness.jitwitness_report(
                rep, ledger_path=args.ledger
            )
            payload["command"] = cmdline
            payload["exitCode"] = child_rc
            if args.report:
                jit_witness.write_report(args.report, payload)
            print(json.dumps(payload, indent=2, sort_keys=True))
            return 0 if (payload["ok"] and not child_rc) else 1
        elif cmd == "chaos-ingest":
            # spawns real event-server subprocesses and SIGKILLs them;
            # stdlib-only harness (docs/operations.md "Crash safety")
            from predictionio_tpu.resilience.chaos import (
                ChaosConfig,
                run_chaos_ingest,
            )

            report = run_chaos_ingest(
                ChaosConfig(
                    cycles=args.cycles,
                    writers=args.writers,
                    events_per_writer=args.events,
                    backend=args.backend,
                    seed=args.seed,
                    bulk_events=args.bulk_events,
                    drain_deadline_s=args.drain_deadline_s,
                    partitions=args.partitions,
                    replication=args.replication,
                    ack_quorum=args.ack_quorum,
                    keep_dir=args.keep,
                )
            )
            print(json.dumps(report, indent=2))
            return 0 if report["ok"] else 1
        elif cmd == "chaos-serve":
            # serving-fleet robustness drill (ISSUE 15): SIGKILL replicas
            # under concurrent clients, rolling /reload, zero failed
            # queries (docs/operations.md "Fleet runbook")
            from predictionio_tpu.resilience.chaos import (
                ServeChaosConfig,
                run_chaos_serve,
            )

            report = run_chaos_serve(
                ServeChaosConfig(
                    replicas=args.replicas,
                    clients=args.clients,
                    kills=args.kills,
                    phase_seconds=args.seconds,
                    reloads=args.reloads,
                    train_events=args.events,
                    seed=args.seed,
                    sharded_point=args.sharded_point,
                    aot=args.aot,
                    keep_dir=args.keep,
                )
            )
            print(json.dumps(report, indent=2))
            return 0 if report["ok"] else 1
        elif cmd == "chaos-fleet":
            # cross-host elastic-fleet drill (ISSUE 17): two-"host" kill
            # with HA router failover, autoscaler watermark scale-up +
            # drain-aware scale-down, stale-while-down proof
            # (docs/operations.md "Multi-host fleet runbook")
            from predictionio_tpu.resilience.chaos import (
                FleetChaosConfig,
                run_chaos_fleet,
            )

            report = run_chaos_fleet(
                FleetChaosConfig(
                    replicas_per_host=args.replicas_per_host,
                    clients=args.clients,
                    phase_seconds=args.seconds,
                    train_events=args.events,
                    lease_ttl_s=args.lease_ttl_s,
                    seed=args.seed,
                    autoscale_phase=not args.skip_autoscale,
                    keep_dir=args.keep,
                )
            )
            print(json.dumps(report, indent=2))
            return 0 if report["ok"] else 1
        elif cmd == "upgrade":
            print(
                "predictionio_tpu is a Python package: upgrade with your "
                "package manager (e.g. `pip install -U predictionio_tpu`). "
                "Storage formats are forward-compatible within a major "
                "version; no in-place upgrader is needed."
            )
        return 0
    except Exception as e:
        print(f"ERROR: {e}", file=sys.stderr)
        # DeviceUnavailableError carries its own code (69): the fleet
        # supervisor reads it as "no chip for this replica", not a crash
        return getattr(e, "exit_code", 1)


if __name__ == "__main__":
    sys.exit(main())
