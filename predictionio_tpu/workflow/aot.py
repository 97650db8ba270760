"""Deploy-time AOT serving artifacts — compile NOTHING at serve time.

Every budgeted serving entrypoint (``compile-budget.json``, PR 14) is a
bounded set of XLA programs keyed by pow2 bucket — yet until this module
each replica re-traced, re-lowered, and re-compiled that same set on
every boot and every rolling-swap rotation: the one remaining cold-start
tax on the request path. ALX stages all XLA programs ahead of the data
plane; this module applies the recipe to serving (ROADMAP item 5):

* ``pio train --aot`` (or ``pio deploy --aot`` against artifact-less
  instances) **exports** each algorithm's serving programs per pow2
  bucket via :mod:`jax.export` — the serialized StableHLO is portable
  across processes and hosts with the same jaxlib/backend — into an
  atomic, fsync'd artifact directory under the shared fleet mount,
  beside a ``manifest.json`` carrying the environment **fingerprint**
  (jax/jaxlib versions, backend, device kind) and per-blob SHA-256 +
  argument-shape records.
* Replicas **boot by deserializing**: :func:`load_runtime` (called from
  ``device_state.pin_pairs``) verifies the fingerprint and every blob
  digest, deserializes the programs, and warms each one ONCE — the only
  backend compile left happens at boot, where the persistent
  compilation cache (tier 2, shared across replicas) answers it — then
  attaches an :class:`AotRuntime` the engine's pinned serving path
  consults before its jitted fallbacks.
* Failure is **loud, tiered, and never fatal**: a fingerprint mismatch
  or corrupt blob logs the exact reason and falls back to tier 2 (the
  persistent JAX compilation cache, ``$JAX_COMPILATION_CACHE_DIR``) and
  then tier 3 (today's JIT path) — results stay bit-identical by
  construction, because the exported programs are the SAME jaxprs the
  JIT path traces (CI-guarded parity test).

The proof moves with the mechanism: with AOT on, the jit-witness gate
tightens from "compiles within budget" to **zero serve-time compiles**
(:func:`predictionio_tpu.analysis.jit_witness.zero_compile_gate`),
asserted in the bench ``aot_serving`` section and across the
``pio chaos-serve`` rolling drill.

jax is imported lazily inside functions only — importing this module
costs nothing, and the default (no ``--aot``) deploy never imports it
at all (CI-guarded).
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import logging
import os
import shutil
import tempfile
import threading
from typing import Any, Sequence

# the artifact SCHEMA (manifest name, dir layout, stdlib verification)
# is owned by the stdlib-only fleet registry so the router and `pio
# status` can gate on readiness with nothing installed; this module
# adds the jax halves (export + deserialize) on top of it
from predictionio_tpu.fleet.registry import (
    AOT_MANIFEST_NAME as MANIFEST_NAME,
    aot_artifact_dir as artifact_dir,
    read_aot_manifest as read_manifest,
    verify_aot_artifacts as verify_artifacts,
)

__all__ = [
    "AotConfig",
    "AotRuntime",
    "MANIFEST_NAME",
    "artifact_dir",
    "current_fingerprint",
    "export_instance",
    "fallback_tier",
    "load_runtime",
    "read_manifest",
    "serving_buckets",
    "verify_artifacts",
]

logger = logging.getLogger(__name__)

#: serialized-program filename suffix — anything else is ignored
BLOB_SUFFIX = ".jaxprog"

#: fingerprint fields that must match EXACTLY for tier-1 loads: a
#: serialized StableHLO module is only portable within one
#: jaxlib/backend pair, and device-kind changes (cpu -> TPUv4) change
#: which executables the backend compile would produce anyway
_STRICT_FIELDS = ("jaxVersion", "jaxlibVersion", "backend", "deviceKind")


@dataclasses.dataclass(frozen=True)
class AotConfig:
    """``pio deploy --aot`` / ``pio train --aot`` knobs.

    Strictly opt-in: ``enabled=False`` (or passing no config at all)
    leaves every code path byte-identical to a tree without this
    module — the default deploy never even imports it (CI-guarded)."""

    enabled: bool = False
    #: artifact root (default ``<basedir>/fleet/aot`` — the shared
    #: fleet mount, so every host's replicas deserialize the same set)
    root: str | None = None

    @property
    def active(self) -> bool:
        return self.enabled


def current_fingerprint() -> dict:
    """The environment identity serialized programs are valid within."""
    import jax
    import jaxlib

    # a backend that cannot initialise raises here: an artifact set
    # stamped "unknown x 0" would only fail later, at every deploy
    devices = jax.devices()
    return {
        "jaxVersion": jax.__version__,
        "jaxlibVersion": jaxlib.__version__,
        "backend": jax.default_backend(),
        "deviceKind": devices[0].device_kind,
        "deviceCount": len(devices),
    }


def fingerprint_mismatches(manifest_fp: dict, live_fp: dict) -> list[str]:
    """Human-readable field-level diffs that disqualify a tier-1 load."""
    diffs = []
    for field in _STRICT_FIELDS:
        if manifest_fp.get(field) != live_fp.get(field):
            diffs.append(
                f"{field}: artifact={manifest_fp.get(field)!r} "
                f"live={live_fp.get(field)!r}"
            )
    return diffs


def serving_buckets(
    n_items: int, max_buckets: int = 6, floor: int = 16
) -> list[int]:
    """The pow2 k-bucket set to export per entrypoint — the SAME math
    as ``ops.topk.bucket_k`` (pow2, floor 16, capped at the catalog),
    enumerated instead of discovered: floor, 2*floor, ... up to the
    catalog size, bounded by ``max_buckets`` (derived from the
    entrypoint's ``compile-budget.json`` allowance, so the exported set
    can never exceed what the ledger already budgets the JIT path)."""
    out: list[int] = []
    b = floor
    while len(out) < max_buckets:
        out.append(min(b, int(n_items)))
        if b >= n_items:
            break
        b <<= 1
    # dedupe while preserving order (catalog-capped tail collapses)
    seen: set[int] = set()
    return [k for k in out if not (k in seen or seen.add(k))]


def ledger_max_buckets(
    ledger_path: str | None, entrypoint: str, default: int = 6
) -> int:
    """Bucket-count bound for one entrypoint, read from the
    compile-budget ledger (bucket enumeration is DRIVEN by the ledger:
    an entrypoint budgeted for N compiles never exports more than N
    bucket programs)."""
    try:
        from predictionio_tpu.analysis import jit_witness

        path = ledger_path or jit_witness.default_ledger_path()
        ledger = jit_witness.load_ledger(path)
    except Exception:
        return default
    for entry in ledger.get("entries", []):
        if entry.get("entrypoint") == entrypoint:
            try:
                return max(1, min(default, int(entry["maxCompiles"])))
            except (KeyError, TypeError, ValueError):
                return default
    return default


# ---------------------------------------------------------------------------
# Export (pio train --aot / pio deploy --aot)
# ---------------------------------------------------------------------------


def export_instance(
    pairs: Sequence,
    engine_instance_id: str,
    root: str,
    ledger_path: str | None = None,
) -> dict | None:
    """Lower + serialize every AOT-exportable serving program of the
    deployed (algorithm, model) pairs into an atomic artifact dir.

    Each algorithm opts in by implementing
    ``aot_export_for_serving(model, buckets) -> dict[str, Exported]``
    (duck-typed, exactly like the pin/shard/quantize hooks); pairs
    without the hook contribute nothing. Returns the manifest dict, or
    ``None`` when no pair exported anything.

    Atomicity: programs + manifest are written into a ``.tmp`` sibling,
    every file fsync'd, then the whole directory renamed into place and
    the parent fsync'd — a reader (or a crash) sees the previous whole
    artifact set or the next, never a torn one."""
    import jax  # noqa: F401  (availability probe — export is jax work)

    programs: dict[str, Any] = {}
    for algo, model in pairs:
        hook = getattr(algo, "aot_export_for_serving", None)
        if hook is None:
            continue
        n_items = _catalog_items(algo, model)
        buckets = serving_buckets(
            n_items,
            max_buckets=ledger_max_buckets(
                ledger_path,
                "predictionio_tpu/templates/serving_util.py:chunked_topk",
            ),
        )
        try:
            exported = hook(model, buckets)
        except Exception:
            logger.exception(
                "aot_export_for_serving failed for %s; skipping",
                type(algo).__name__,
            )
            continue
        for key, exp in (exported or {}).items():
            if key in programs:
                # two algorithms of the same class serving one engine:
                # suffix with the pair ordinal so neither set is lost
                key = f"{key}#{len(programs)}"
            programs[key] = exp
    if not programs:
        return None

    final_dir = artifact_dir(root, engine_instance_id)
    os.makedirs(root, exist_ok=True)
    tmp_dir = tempfile.mkdtemp(prefix=".aot.", dir=root)
    entries = []
    try:
        for key, exp in sorted(programs.items()):
            blob = bytes(exp.serialize())
            fname = _blob_filename(key)
            _write_durable(os.path.join(tmp_dir, fname), blob)
            entries.append(
                {
                    "key": key,
                    "file": fname,
                    "bytes": len(blob),
                    "sha256": hashlib.sha256(blob).hexdigest(),
                    "argShapes": [
                        [list(a.shape), str(a.dtype)] for a in exp.in_avals
                    ],
                }
            )
        manifest = {
            "version": 1,
            "engineInstanceId": engine_instance_id,
            "fingerprint": current_fingerprint(),
            "entries": entries,
        }
        _write_durable(
            os.path.join(tmp_dir, MANIFEST_NAME),
            json.dumps(manifest, indent=2, sort_keys=True).encode(),
        )
        _fsync_dir(tmp_dir)
        # atomic publish: retire any previous artifact set for this
        # instance first (rename-then-delete, so a crash mid-publish
        # leaves either the old set or the new one addressable)
        old = None
        if os.path.isdir(final_dir):
            old = f"{final_dir}.old.{os.getpid()}"
            os.rename(final_dir, old)
        os.rename(tmp_dir, final_dir)
        _fsync_dir(root)
        if old is not None:
            shutil.rmtree(old, ignore_errors=True)
    except BaseException:
        shutil.rmtree(tmp_dir, ignore_errors=True)
        raise
    logger.info(
        "Exported %d AOT serving program(s) for instance %s -> %s",
        len(entries), engine_instance_id, final_dir,
    )
    return manifest


def _blob_filename(key: str) -> str:
    safe = "".join(c if c.isalnum() or c in "._-" else "_" for c in key)
    return f"{safe}{BLOB_SUFFIX}"


def _write_durable(path: str, data: bytes) -> None:
    with open(path, "wb") as f:
        f.write(data)
        f.flush()
        os.fsync(f.fileno())


def _fsync_dir(directory: str) -> None:
    fd = os.open(directory, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def _catalog_items(algo, model) -> int:
    """Rows of the item table the algorithm names (``ITEM_TABLE``,
    ``templates/retrieval.py``): what caps the exported k buckets."""
    items = getattr(model, getattr(algo, "ITEM_TABLE", "item_factors"), None)
    if items is not None and hasattr(items, "shape"):
        return int(items.shape[0])
    return 1


def fallback_tier() -> int:
    """Which tier a failed tier-1 load lands on: tier 2 when the
    persistent JAX compilation cache is configured (the backend compile
    the JIT fallback pays is answered from the shared cache dir —
    ``utils/compile_cache.py`` has the rule that places it), else
    tier 3 (full JIT)."""
    import jax

    if jax.config.jax_compilation_cache_dir or os.environ.get(
        "JAX_COMPILATION_CACHE_DIR"
    ):
        return 2
    return 3


# ---------------------------------------------------------------------------
# Load (replica boot: device_state.pin_pairs)
# ---------------------------------------------------------------------------


class AotRuntime:
    """Deserialized serving programs of ONE model generation.

    The engine's pinned serving path asks :meth:`get` per dispatch; a
    program that raises at call time (shape drift after an online
    re-layout, for example) is disabled in place so the very next
    dispatch falls back to the jitted path — serve-time failures
    degrade to tier 2/3, never to an error response."""

    def __init__(self, programs: dict, manifest: dict, tier: int = 1):
        self._programs = programs
        self.manifest = manifest
        self.tier = tier
        # asked from many threads at once: HTTP threads on the per-request
        # path, the batcher's two workers on the batch path
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self._disabled: set[str] = set()

    def get(self, key: str):
        fn = self._programs.get(key)
        with self._lock:
            if fn is None or key in self._disabled:
                self.misses += 1
                return None
            self.hits += 1
        return fn

    def disable(self, key: str, reason: str) -> None:
        with self._lock:
            if key in self._disabled:
                return
            self._disabled.add(key)
        logger.warning(
            "AOT program %s disabled at serve time (%s); the jitted "
            "path serves this shape from now on", key, reason,
        )

    def __len__(self) -> int:
        return len(self._programs) - len(self._disabled)

    def stats(self) -> dict:
        return {
            "tier": self.tier,
            "programs": len(self._programs),
            "disabled": len(self._disabled),
            "hits": self.hits,
            "misses": self.misses,
        }


def load_runtime(
    engine_instance_id: str, root: str, warm: bool = True
) -> tuple[AotRuntime | None, dict]:
    """Deserialize one instance's artifact set into an
    :class:`AotRuntime`. Returns ``(runtime, report)`` — runtime is
    ``None`` on ANY failure (missing dir, fingerprint mismatch, corrupt
    blob, deserialize error), with the report saying which tier serving
    fell back to and exactly why; the caller logs loudly and keeps
    serving through the JIT path, bit-identical by construction.

    ``warm=True`` calls every deserialized program once with zeros, so
    the single backend compile each needs happens HERE (at boot, where
    tier 2's shared persistent cache answers it) — never at serve
    time."""
    report: dict[str, Any] = {
        "tier": 1,
        "instance": engine_instance_id,
        "loaded": 0,
        "problems": [],
    }
    try:
        instance_dir = artifact_dir(root, engine_instance_id)
    except ValueError as e:
        report["problems"].append(str(e))
        return _fallback(report)
    check = verify_artifacts(instance_dir, deep=True)
    if not check["ok"]:
        report["problems"].extend(check["problems"])
        return _fallback(report)
    manifest = read_manifest(instance_dir)
    assert manifest is not None  # verify_artifacts just parsed it
    live_fp = current_fingerprint()
    diffs = fingerprint_mismatches(manifest.get("fingerprint") or {}, live_fp)
    if diffs:
        report["problems"].append("fingerprint mismatch: " + "; ".join(diffs))
        return _fallback(report)

    from jax import export as jax_export

    import numpy as np

    programs: dict[str, Any] = {}
    for entry in manifest.get("entries", []):
        path = os.path.join(instance_dir, entry["file"])
        try:
            with open(path, "rb") as f:
                blob = f.read()
            exported = jax_export.deserialize(bytearray(blob))
        except Exception as e:
            report["problems"].append(
                f"deserialize failed for {entry.get('key')}: "
                f"{type(e).__name__}: {e}"
            )
            return _fallback(report)
        fn = exported.call
        if warm:
            try:
                fn(*(
                    np.zeros(shape, dtype=dtype)
                    for shape, dtype in entry.get("argShapes", [])
                ))
            except Exception as e:
                report["problems"].append(
                    f"warm call failed for {entry.get('key')}: "
                    f"{type(e).__name__}: {e}"
                )
                return _fallback(report)
        programs[entry["key"]] = fn
    report["loaded"] = len(programs)
    report["fingerprint"] = live_fp
    return AotRuntime(programs, manifest, tier=1), report


def _fallback(report: dict) -> tuple[None, dict]:
    tier = fallback_tier()
    report["tier"] = tier
    logger.warning(
        "AOT artifact load failed for instance %s — falling back to "
        "tier %d (%s): %s",
        report.get("instance"),
        tier,
        "persistent compilation cache" if tier == 2 else "JIT",
        "; ".join(report["problems"]) or "unknown",
    )
    return None, report
