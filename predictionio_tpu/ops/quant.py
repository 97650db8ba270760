"""Per-row symmetric int8 quantization — the ``--quantize int8`` tier.

PR 6 measured that gather bandwidth dominates IVF probes on CPU hosts
and PR 9 that per-device factor bytes are the hard catalog ceiling even
after S-way sharding; ALX (arxiv 2112.02194) shows mixed-precision
factorization is the standard TPU answer to both. This module brings
that idiom to the serving path: factor tables (and IVF slabs, see
:mod:`predictionio_tpu.ops.ivf`) are stored as **int8 codes plus one
f32 scale per row**, so a served catalog costs ``rank + 4`` bytes per
row instead of ``4·rank`` — ~4x more catalog per device multiplied on
top of the ``/S`` from sharding, and ~4x less memory traffic per
gathered candidate.

Quality is kept by a **recall-guarded two-stage top-K**:

1. **int8 coarse scan** — the query row is itself quantized and scored
   against the whole table with one int8×int8 GEMM accumulated in
   int32, rescaled by the product of the two scales. This stage
   OVER-FETCHES ``k' = max(4k, k + 64)`` candidates (:func:`overfetch`)
   so quantization noise at the k-th boundary costs candidates, never
   results.
2. **f32 rescore** — only the ``k'`` gathered candidates are
   dequantized and re-scored against the *unquantized* f32 query, then
   merged through the shared tie rule
   (:func:`predictionio_tpu.ops.topk.sort_merge_topk`: descending
   score, ties by ascending id). The final ordering is therefore
   exact-f32-deterministic over the dequantized rows — adversarial
   equal-score rows rank identically to the f32 exact path
   (CI-asserted), replicated and sharded alike.

This is ONE quantization rule in ONE module: piolint PIO305 bans raw
``int8`` construction anywhere else under ``ops/``, ``parallel/`` and
``workflow/``, so every code/scale pair in the repo agrees on the
rounding, the zero-row guard, and the re-quantize-on-scatter rule the
online fold-in relies on. Strictly opt-in: nothing imports this module
until a deploy passes ``--quantize int8`` (CI-guarded like ``--ann`` /
``--shard-factors``).
"""

from __future__ import annotations

import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np

from predictionio_tpu.ops.topk import SCORE_PRECISION, bucket_k, sort_merge_topk

__all__ = [
    "QuantizedTable",
    "QuantRuntime",
    "quantize_rows",
    "quantize_rows_traced",
    "quantize_table_host",
    "quantize_slabs",
    "dequantize",
    "quantize_table",
    "quantization_error",
    "overfetch",
    "int8_matmul",
    "quantized_topk_batch",
    "quantized_topk_users",
    "run_topk",
    "topk_users",
    "table_bytes_f32",
]

#: symmetric code range: [-127, 127] (the -128 slot is unused so the
#: range is symmetric and negation is exact)
_QMAX = 127.0


# ---------------------------------------------------------------------------
# Quantize / dequantize primitives (the ONE rounding rule)
# ---------------------------------------------------------------------------


def quantize_rows_traced(mat: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Traceable core of the per-row symmetric quantizer: ``mat [..., K]
    f32 -> (codes [..., K] int8, scales [...] f32)`` with ``scale =
    amax(|row|)/127`` and ``code = rint(row/scale)`` (round-half-even —
    numpy and XLA agree, which is what keeps the host and device
    quantizers bit-identical). All-zero rows get scale 0 and zero codes,
    so ``dequantize`` reproduces them exactly. Callable from inside
    other traces (the sharded shard_map kernels quantize the resolved
    query rows in-kernel)."""
    amax = jnp.max(jnp.abs(mat), axis=-1)
    # reciprocal MULTIPLY, not division: numpy and XLA round a constant
    # division differently (XLA strength-reduces to a reciprocal), and
    # the host and device quantizers must agree bitwise or the fold-in's
    # host-side re-quantize drifts from the build-time layout
    scales = amax * np.float32(1.0 / _QMAX)
    safe = jnp.where(scales > 0, scales, 1.0)
    codes = jnp.clip(
        jnp.rint(mat / safe[..., None]), -_QMAX, _QMAX
    ).astype(jnp.int8)
    return codes, scales.astype(jnp.float32)


quantize_rows = jax.jit(quantize_rows_traced)


def quantize_table_host(mat: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Numpy mirror of :func:`quantize_rows` (same rounding, same
    zero-row guard) for build-time layout work — sharding a table before
    ``device_put``, and the IVF host mirror's per-lane re-quantize."""
    mat = np.asarray(mat, np.float32)
    amax = np.max(np.abs(mat), axis=-1)
    # same reciprocal-multiply rule as the traced quantizer (bitwise
    # host/device agreement — see quantize_rows_traced)
    scales = (amax * np.float32(1.0 / _QMAX)).astype(np.float32)
    safe = np.where(scales > 0, scales, 1.0)
    codes = np.clip(
        np.rint(mat / safe[..., None]), -_QMAX, _QMAX
    ).astype(np.int8)
    return codes, scales


def quantize_slabs(slabs: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Quantize IVF cluster-major slabs ``[nlist, W, K]`` per LANE row:
    ``(codes [nlist, W, K] int8, scales [nlist, W] f32)``. Zero-padded
    lanes quantize to zero codes + zero scale, so the sentinel masking
    in the query kernel is unchanged."""
    return quantize_table_host(np.asarray(slabs, np.float32))


def dequantize(codes, scales):
    """``codes [..., K] * scales [...]`` -> f32 rows; works on numpy and
    jax arrays alike (the backing of a :class:`QuantizedTable` may be
    either)."""
    if isinstance(codes, np.ndarray):
        return codes.astype(np.float32) * np.asarray(scales, np.float32)[
            ..., None
        ]
    return codes.astype(jnp.float32) * scales[..., None]


def quantization_error(mat: np.ndarray, codes, scales) -> dict:
    """Error accounting for the ``/stats.json`` ``quant`` block: how far
    the dequantized table sits from the f32 original. ``maxRelError`` is
    per-row (error relative to the row's own magnitude — the quantity
    the symmetric scheme bounds at ~0.5/127 per element)."""
    mat = np.asarray(mat, np.float32)
    deq = np.asarray(dequantize(np.asarray(codes), np.asarray(scales)))
    err = np.abs(deq - mat)
    amax = np.maximum(np.max(np.abs(mat), axis=-1, keepdims=True), 1e-12)
    return {
        "maxAbsError": round(float(err.max()) if err.size else 0.0, 6),
        "rmsError": round(
            float(np.sqrt(np.mean(err * err))) if err.size else 0.0, 6
        ),
        "maxRelError": round(
            float((err / amax).max()) if err.size else 0.0, 6
        ),
    }


def overfetch(k: int, limit: int) -> int:
    """Coarse-stage candidate count ``k' = max(4k, k+64)``, clamped to
    the catalog — enough head-room that an int8 ranking error at the
    k-th boundary moves a candidate WITHIN the rescored set instead of
    out of it (docs/serving.md discusses tuning)."""
    return max(1, min(int(limit), max(4 * int(k), int(k) + 64)))


def table_bytes_f32(rows: int, rank: int) -> int:
    """What the same table would cost served f32 — the baseline for the
    ``bytesSaved`` stat."""
    return int(rows) * int(rank) * 4


# ---------------------------------------------------------------------------
# The served container
# ---------------------------------------------------------------------------


class QuantizedTable:
    """An int8-served factor table: ``codes [N, K]`` + per-row
    ``scales [N]``, either host numpy or device (possibly sharded) jax
    arrays. Quacks enough like an ndarray for the serving and online
    fold-in paths — ``shape``/``len``, dequantizing ``__getitem__``, and
    ``__array__`` (full dequantize, used by release/re-layout/ANN-build
    paths that need the f32 values once)."""

    #: duck-type marker (isinstance would force the default serving path
    #: to import this module just to check)
    is_quantized = True

    __slots__ = ("codes", "scales")

    def __init__(self, codes, scales):
        self.codes = codes
        self.scales = scales

    @property
    def shape(self) -> tuple:
        return tuple(self.codes.shape)

    @property
    def dtype(self):
        return self.codes.dtype

    def __len__(self) -> int:
        return int(self.codes.shape[0])

    def __getitem__(self, idx):
        """Dequantized f32 row(s) — the fold-in's prior gather and the
        ANN path's query-row resolve both read through here, so only
        the touched rows are ever dequantized."""
        return dequantize(self.codes[idx], self.scales[idx])

    def __array__(self, dtype=None, copy=None):
        full = np.asarray(dequantize(np.asarray(self.codes),
                                     np.asarray(self.scales)))
        return full.astype(dtype) if dtype is not None else full

    @property
    def nbytes_codes(self) -> int:
        return int(self.codes.size) * self.codes.dtype.itemsize

    @property
    def nbytes_scales(self) -> int:
        return int(self.scales.size) * self.scales.dtype.itemsize


def quantize_table(mat) -> QuantizedTable:
    """Quantize a host f32 table and pin codes + scales on the default
    device — the replicated (non-sharded) ``--quantize`` layout. The
    sharded layout lives in
    :func:`predictionio_tpu.parallel.sharding.shard_quantized_table`."""
    codes, scales = quantize_table_host(np.asarray(mat, np.float32))
    return QuantizedTable(jax.device_put(codes), jax.device_put(scales))


# ---------------------------------------------------------------------------
# Two-stage top-K kernels
# ---------------------------------------------------------------------------


def int8_matmul(q_codes: jax.Array, table_codes: jax.Array) -> jax.Array:
    """``q_codes [B, K] @ table_codes.T [K, N]`` accumulated in int32 —
    the coarse scan's GEMM. int8 operands keep the memory traffic at a
    quarter of f32; on TPU the MXU runs this natively (the ALX
    mixed-precision recipe), on CPU XLA lowers it without VNNI so the
    win here is bandwidth (gathers, HBM), not FLOPs."""
    return jnp.matmul(q_codes, table_codes.T, preferred_element_type=jnp.int32)


def _two_stage_topk(qvecs, codes, scales, k: int, kp: int, num_items):
    """Shared trace body: int8 coarse scan -> ``kp`` over-fetch -> f32
    rescore of the gathered candidates -> tie-stable merge. ``num_items``
    is TRACED (the logical row bound; online fold-ins advance it while
    padding keeps the shapes fixed), ``k``/``kp`` static."""
    q_codes, q_scales = quantize_rows_traced(qvecs)
    acc = int8_matmul(q_codes, codes)  # [B, N] int32
    approx = acc.astype(jnp.float32) * q_scales[:, None] * scales[None, :]
    gid = jnp.arange(codes.shape[0], dtype=jnp.int32)
    approx = jnp.where(gid[None, :] < num_items, approx, -jnp.inf)
    _, cand = jax.lax.top_k(approx, kp)  # positions ARE ids (natural order)
    # keep the rescore gathers out of the top_k fusion — same XLA:CPU
    # TopkDecomposer cliff ops/topk.py documents
    cand = jax.lax.optimization_barrier(cand)
    deq = dequantize(codes[cand], scales[cand])  # [B, kp, K] f32 rows
    exact = jnp.einsum("bpk,bk->bp", deq, qvecs, precision=SCORE_PRECISION)
    valid = cand < num_items
    exact = jnp.where(valid, exact, -jnp.inf)
    ids = jnp.where(valid, cand, num_items)
    return sort_merge_topk(exact, ids, min(int(k), int(kp)))


@functools.partial(jax.jit, static_argnames=("k", "kp"))
def quantized_topk_batch(
    qvecs: jax.Array,
    codes: jax.Array,
    scales: jax.Array,
    k: int,
    kp: int,
    num_items: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """Two-stage top-k for a batch of f32 query VECTORS against an
    int8 table: ``([B, k] ids, [B, k] f32 rescored scores)``, descending
    score, ties by ascending id. Rows past ``num_items`` (growth
    padding) carry the ``num_items`` sentinel at ``-inf``."""
    return _two_stage_topk(qvecs, codes, scales, k, kp, num_items)


@functools.partial(jax.jit, static_argnames=("k", "kp"))
def quantized_topk_users(
    user_idx: jax.Array,
    u_codes: jax.Array,
    u_scales: jax.Array,
    i_codes: jax.Array,
    i_scales: jax.Array,
    k: int,
    kp: int,
    num_items: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """Two-stage top-k for a batch of USER indices: dequantize the user
    rows on device (the f32 queries the rescore stage uses), then the
    shared body — one dispatch per chunk."""
    q = dequantize(u_codes[user_idx], u_scales[user_idx])
    return _two_stage_topk(q, i_codes, i_scales, k, kp, num_items)


# ---------------------------------------------------------------------------
# Host-facing wrappers + runtime accounting
# ---------------------------------------------------------------------------


class QuantRuntime:
    """Per-model serving state of the quantized tier, held as ``quant``
    of the model's ``templates.retrieval.ServingState`` by the
    ``quantize_model_for_serving`` hook: the mode, the real byte ledger (codes/scales vs the f32
    baseline), measured quantization error, and thread-safe counters
    for the ``/stats.json`` ``quant`` block — including the MEASURED
    rescore depth (the ``k'`` each bucket actually paid)."""

    def __init__(self, mode: str, bytes_by_dtype: dict, bytes_f32: int,
                 error: dict | None = None):
        self.mode = str(mode)
        self.bytes_by_dtype = dict(bytes_by_dtype)
        self.bytes_f32 = int(bytes_f32)
        self.error = dict(error or {})
        self._lock = threading.Lock()
        self.queries = 0
        self.rescored = 0  # total candidates rescored (sum of k')
        self.rescore_depth_max = 0

    def note(self, n_queries: int, rescore_depth: int) -> None:
        with self._lock:
            self.queries += int(n_queries)
            self.rescored += int(n_queries) * int(rescore_depth)
            self.rescore_depth_max = max(
                self.rescore_depth_max, int(rescore_depth)
            )

    def stats_json(self) -> dict:
        with self._lock:
            q = self.queries
            rescored = self.rescored
            depth_max = self.rescore_depth_max
        total = sum(self.bytes_by_dtype.values())
        return {
            "dtype": self.mode,
            "bytesByDtype": dict(self.bytes_by_dtype),
            "bytesTotal": total,
            "bytesF32Equivalent": self.bytes_f32,
            "bytesSaved": self.bytes_f32 - total,
            "overfetch": "max(4k, k+64)",
            "queries": q,
            "candidatesRescored": rescored,
            "rescoreDepthMax": depth_max,
            "rescoreDepthMean": round(rescored / q, 1) if q else 0.0,
            "quantizationError": dict(self.error),
        }


def run_topk(
    runtime: QuantRuntime,
    user_qt: QuantizedTable,
    item_qt: QuantizedTable,
    user_idx: np.ndarray,
    k: int,
    shards=None,
) -> tuple[jax.Array, jax.Array]:
    """One chunk of the quantized serving path, results left ON DEVICE
    (callers concatenate chunks and cross the link once, the staging
    discipline every other path uses). ``k`` is the caller's (already
    bucketed) fetch size; the over-fetch derives from it so each bucket
    compiles one program. Routes through the shard_map kernel when the
    tables are model-sharded."""
    idx = jnp.asarray(np.asarray(user_idx, np.int32))
    if shards is not None:
        from predictionio_tpu.parallel import sharding

        num_items = int(shards.rows["item"])
        kp = overfetch(k, num_items)
        ids, scores = sharding.sharded_quantized_topk_users(
            idx, user_qt.codes, user_qt.scales,
            item_qt.codes, item_qt.scales,
            k, kp, num_items, shards.mesh,
        )
    else:
        num_items = int(item_qt.shape[0])
        kp = overfetch(k, num_items)
        ids, scores = quantized_topk_users(
            idx, user_qt.codes, user_qt.scales,
            item_qt.codes, item_qt.scales,
            k, kp, jnp.asarray(num_items, jnp.int32),
        )
    runtime.note(len(np.asarray(user_idx)), kp)
    return ids, scores


def topk_users(
    runtime: QuantRuntime,
    user_qt: QuantizedTable,
    item_qt: QuantizedTable,
    user_idx,
    k: int,
    shards=None,
) -> tuple[np.ndarray, np.ndarray]:
    """Top-``k`` for a batch of user indices as numpy — the single-query
    predict path. ``k`` buckets to a power of two (floor 16) so the
    jitted programs compile once per bucket, like every other tier."""
    num_items = (
        int(shards.rows["item"]) if shards is not None
        else int(item_qt.shape[0])
    )
    k = max(1, min(int(k), num_items))
    kb = bucket_k(k, num_items)
    ids, scores = run_topk(
        runtime, user_qt, item_qt, np.asarray(user_idx, np.int32), kb,
        shards=shards,
    )
    ids = np.asarray(ids)
    scores = np.asarray(scores)
    # growth-padding sentinels (id == num_items at -inf) can reach the
    # tail when a shard holds fewer than kb real rows; trim before k
    out_i, out_s = [], []
    for r in range(ids.shape[0]):
        keep = ids[r] < num_items
        out_i.append(ids[r][keep][:k])
        out_s.append(scores[r][keep][:k])
    return np.asarray(out_i), np.asarray(out_s)
