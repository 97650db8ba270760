"""Alternating Least Squares, TPU-native.

Replaces Spark MLlib's ``org.apache.spark.ml.recommendation.ALS``, the
training kernel behind the reference's Recommendation / Similar-Product /
E-Commerce templates (reached from ``PAlgorithm.train`` — see SURVEY.md
sections 3.9, 8.1). Nothing here is a port: MLlib's block-partitioned
shuffle becomes sharded dense compute + XLA collectives, following the
ALX recipe (PAPERS.md — "ALX: Large Scale Matrix Factorization on TPUs").

Memory-bounded solver design (v2):

* **Segmented bucketing** — every row's ragged rating list is split into
  fixed-width segments (powers of two, 8..512 by default). Rows hotter
  than the max width span multiple max-width segments ("hot" rows), so
  no tensor ever scales with the hottest row.
* **Chunked scans** — each bucket is processed in bounded row-chunks via
  ``lax.scan``: peak HBM is O(chunk_entries · rank), *independent of
  bucket size*. This is what lets a 20M-rating sweep fit in one chip's
  HBM (round-1 materialized whole buckets and OOM'd: VERDICT.md weak #1).
* **Two solve paths** — rows that fit one segment are solved in-chunk
  (batched normal equations + Cholesky) and scattered straight into the
  factor table. Hot rows accumulate partial Gramians ``A += QᵀWQ``,
  ``b += Qᵀr`` across their segments (scatter-add into ``[H, K, K]``,
  where H ≤ nnz / max_width by construction) and are solved once at the
  end of the half-sweep.
* **Mesh sharding** — bucket rows/segments are sharded over the ``data``
  axis; the persistent factor tables are sharded over the ``model`` axis
  (ALX-style — NOT replicated, so catalog size scales with the mesh).
  The opposite table never materializes replicated: under ``shard_map``
  each device gathers only from its LOCAL table shard (out-of-shard
  entries masked to zero) and the partial Gramians ``[C,K,K]`` are
  psum'd over ``model`` — the small normal-equation blocks move over
  ICI instead of the catalog-sized table, so peak per-device HBM is
  O(catalog / model_axis) + O(chunk). Solved rows scatter back to
  their ``model`` shard (GSPMD emits the exchange).
* **Hot-slot grouping** — the hot-row Gramian accumulator is built per
  group of at most ``hot_group_slots`` rows, so its ``[H,K,K]`` buffer
  is bounded by a config knob instead of growing with nnz/max_width.

Supports MLlib's two objectives:

* **explicit** — squared error with ALS-WR regularization (λ scaled by
  each row's rating count, MLlib default).
* **implicit** (Hu-Koren-Volinsky) — confidence ``c = 1 + α·|r|``,
  preference ``p = [r > 0]``, shared ``YᵀY`` Gramian once per half-sweep,
  and λ scaled by the row's positive-rating count (MLlib's
  ``numExplicits`` scaling, so reference ``lambda`` values transfer).
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import types
from typing import Any, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec, reshard

from predictionio_tpu.ops.topk import (
    NO_ITEM,
    SCORE_PRECISION,
    bucket_width,
    select_top_k,
    sort_merge_topk,
    top_k_scores,
)
from predictionio_tpu.utils.spans import span

logger = logging.getLogger(__name__)

__all__ = [
    "ALSConfig",
    "ALSFactors",
    "BucketedRatings",
    "build_buckets",
    "build_buckets_device",
    "factors_to_host",
    "train_als",
    "als_sweep",
    "predict_scores",
    "top_k_items",
    "top_k_items_batch",
]

#: Segment widths: multiples of 8 at ~1.33-1.5x steps, so within-bucket
#: padding is < 1.5x (measured padding efficiency 0.787 vs 0.625 for the
#: former powers-of-two set at the 20M bench; sweep ~1.09x faster).
#: Rows with more ratings than the max width split into hot segments.
_DEFAULT_BUCKET_WIDTHS = (8, 16, 24, 32, 48, 64, 96, 128, 192, 256, 384, 512)

#: Max padded entries (rows × width) processed per scan step. Bounds the
#: per-chunk gather at chunk_entries·rank·4 bytes (1 GB at rank 64).
#: Measured on v5e at 20M nnz rank 64: 2^22 is ~20% faster per sweep
#: than 2^20 (fewer scan steps amortize better); 2^23 adds only ~2%.
_DEFAULT_CHUNK_ENTRIES = 1 << 22

#: Max rows per scan step, independent of width: bounds the batched
#: normal-equation buffers at chunk_rows·K²·4 bytes (512 MB at rank 64)
#: — without it a narrow bucket at large chunk_entries would build a
#: [chunk_entries/width, K, K] solve buffer far bigger than the gather.
_DEFAULT_CHUNK_ROWS = 1 << 15

# read-only: als_sweep (jit) closes over this table, so a mutable dict
# here would be frozen into the compiled program at trace time (piolint
# PIO302) — the proxy makes the immutability the trace assumes explicit
_PRECISIONS = types.MappingProxyType({
    "default": jax.lax.Precision.DEFAULT,
    "high": jax.lax.Precision.HIGH,
    "highest": jax.lax.Precision.HIGHEST,
})


@dataclasses.dataclass(frozen=True)
class ALSConfig:
    """Hyperparameters (parity: MLlib ``ALS`` params ``rank``, ``maxIter``,
    ``regParam``, ``implicitPrefs``, ``alpha``, ``seed``)."""

    rank: int = 10
    iterations: int = 10
    reg: float = 0.1
    implicit: bool = False
    alpha: float = 1.0
    seed: int = 0
    #: pad rank up to a multiple of this for MXU-friendly K (0 = exact rank)
    rank_pad_multiple: int = 0
    #: orbax step-checkpoint directory ("" = off); training resumes from
    #: the latest step found there (resume-on-preemption, SURVEY.md 6.4)
    checkpoint_dir: str = ""
    checkpoint_interval: int = 5
    #: segment widths for bucketing (see build_buckets)
    bucket_widths: tuple = _DEFAULT_BUCKET_WIDTHS
    #: max padded entries per scan chunk — the HBM knob
    chunk_entries: int = _DEFAULT_CHUNK_ENTRIES
    #: max hot rows per Gramian-accumulator group: bounds the [H,K,K]
    #: hot accumulator at hot_group_slots·K² floats per group (extra
    #: groups only cost one more batched solve + scatter each)
    hot_group_slots: int = 2048
    #: where the O(nnz) bucketing work runs: "auto" sorts/fills on the
    #: accelerator when training single-device on TPU/GPU (the host sort
    #: alone costs ~3 s/side at 20M nnz on one core), "host"/"device"
    #: force a path. Mesh and multi-host layouts always bucket on host.
    bucketing: str = "auto"
    #: matmul precision for the normal equations: "highest" (full f32,
    #: MLlib-parity accuracy), "high", or "default" (bf16 passes).
    #: "highest" is the recommended default: the sweep is gather-bound,
    #: so bf16 measured only ~0.6% faster at 20M nnz rank 64 on v5e
    #: while costing ~6% top-10 overlap churn (bench precision_compare).
    precision: str = "highest"
    #: SPD solver for the normal equations: "auto" picks the Pallas
    #: lane-batched Cholesky kernel on a single-device TPU backend (how
    #: it reads against XLA's Cholesky on the chip: ops/solve.py) and
    #: Cholesky elsewhere; explicit "cholesky" / "pallas" /
    #: "pallas_interpret" override.
    solver: str = "auto"


class ALSFactors(NamedTuple):
    """The model: dense factor matrices. Row ``num_rows`` of each is a
    zero sentinel used as the scatter target for padding (stripped by
    :func:`train_als` before returning)."""

    user: jax.Array  # [num_users(+1), K]
    item: jax.Array  # [num_items(+1), K]


class _Chunked(NamedTuple):
    """One bucket in scan layout: ``n_chunks`` steps of ``C`` rows of a
    fixed segment width ``L`` (all shapes static for XLA)."""

    row_id: Any  # [n_chunks, C] int32 — row index (normal) or hot slot (hot);
    #              padding rows carry the sentinel (num_rows / num_hot)
    idx: Any  # [n_chunks, C, L] int32 — column indices into the other side
    val: Any  # [n_chunks, C, L] f32 — ratings (0 where masked)
    mask: Any  # [n_chunks, C, L] f32 — 1 for real entries


class BucketedRatings(NamedTuple):
    """One side of the ratings matrix in solver layout.

    Registered as a custom pytree below: the array fields (``normal``,
    ``hot``, ``hot_rows``) are children; the int metadata travels in the
    treedef so it stays STATIC under jit (a multi-process jit must not
    receive per-host scalar leaves, and the sentinel row index wants to
    be a compile-time constant).

    Hot rows are split into GROUPS of at most ``hot_group_slots`` rows:
    ``hot[g]`` holds group g's segments with group-local slot ids and
    ``hot_rows[g]`` maps those slots back to row ids — so the sweep's
    Gramian accumulator is [H_g, K, K], never [num_hot, K, K]."""

    normal: tuple  # tuple[_Chunked, ...] — rows fitting one segment
    hot: tuple  # tuple[_Chunked, ...] — one per group (row_id = local slot)
    hot_rows: tuple  # tuple of [H_g + 1] int32 — slot -> row id; last = sentinel
    num_rows: int
    num_cols: int
    nnz: int  # real entries
    padded_nnz: int  # entries incl. padding (MXU work actually done)


jax.tree_util.register_pytree_node(
    BucketedRatings,
    lambda b: ((b.normal, b.hot, b.hot_rows),
               (b.num_rows, b.num_cols, b.nnz, b.padded_nnz)),
    lambda aux, ch: BucketedRatings(ch[0], ch[1], ch[2], *aux),
)


def _chunk(arrs: list, n: int, c: int, l: int) -> _Chunked:
    """Reshape flat [B(,L)] bucket arrays into scan layout [n, C(, L)]."""
    row_id, idx, val, mask = arrs
    return _Chunked(
        row_id.reshape(n, c),
        idx.reshape(n, c, l),
        val.reshape(n, c, l),
        mask.reshape(n, c, l),
    )


def _fill_bucket(
    n_seg: int,
    n_pad: int,
    width: int,
    seg_row: np.ndarray,
    seg_start: np.ndarray,
    seg_len: np.ndarray,
    cols_s: np.ndarray,
    vals_s: np.ndarray,
    sentinel: int,
) -> list:
    """Vectorized ragged fill of one bucket's [n_pad, width] arrays from
    sorted COO slices (no per-row Python loop — this runs at full-catalog
    scale before the first TPU step)."""
    row_id = np.full(n_pad, sentinel, dtype=np.int32)
    idx = np.zeros((n_pad, width), dtype=np.int32)
    val = np.zeros((n_pad, width), dtype=np.float32)
    mask = np.zeros((n_pad, width), dtype=np.float32)
    row_id[:n_seg] = seg_row
    if n_seg:
        dst_row = np.repeat(np.arange(n_seg), seg_len)
        lane_end = np.cumsum(seg_len)
        dst_lane = np.arange(int(lane_end[-1])) - np.repeat(lane_end - seg_len, seg_len)
        src = np.repeat(seg_start, seg_len) + dst_lane
        idx[dst_row, dst_lane] = cols_s[src]
        val[dst_row, dst_lane] = vals_s[src]
        mask[dst_row, dst_lane] = 1.0
    return [row_id, idx, val, mask]


class _Segments(NamedTuple):
    """Host-side segmentation of one COO shard (pre-padding layout)."""

    per_width: dict  # width -> (seg_row int32, seg_start, seg_len)
    hot_slot: np.ndarray  # local hot-slot id per hot segment
    hot_start: np.ndarray
    hot_len: np.ndarray
    hot_rows: np.ndarray  # [H_local] row ids of hot rows
    w_max: int
    cols_s: np.ndarray  # row-sorted column ids
    vals_s: np.ndarray  # row-sorted values
    rated: np.ndarray  # bool [num_rows] — rows present in this shard


def _segment(
    rows: np.ndarray,
    cols: np.ndarray,
    vals: np.ndarray,
    num_rows: int,
    num_cols: int,
    widths: Sequence[int],
) -> _Segments:
    """Validate + sort one COO shard and split every row into fixed-width
    segments: rows with <= max(widths) ratings get one segment in the
    smallest fitting width; hotter rows get ceil(count/w_max) segments."""
    rows = np.asarray(rows, dtype=np.int64)
    cols = np.asarray(cols, dtype=np.int64)
    vals = np.asarray(vals, dtype=np.float32)
    if not (rows.shape == cols.shape == vals.shape):
        raise ValueError("rows/cols/vals must be 1-D arrays of equal length")
    if rows.size and (rows.min() < 0 or rows.max() >= num_rows):
        raise ValueError("row index out of range")
    if cols.size and (cols.min() < 0 or cols.max() >= num_cols):
        raise ValueError("column index out of range")

    usable = _usable_widths(widths)
    w_max = usable[-1]

    order = np.argsort(rows, kind="stable")
    cols_s, vals_s = cols[order], vals[order]
    # counts via bincount instead of np.unique: unique re-sorts the 20M+
    # array a second time (2.5 s/side at ML-20M scale) where bincount is a
    # single O(nnz) pass (VERDICT r2 item 2)
    counts_all = np.bincount(rows, minlength=num_rows)
    uniq, starts, counts = _row_offsets(counts_all)
    rated = counts_all > 0

    plan = _plan_segments(uniq, starts, counts, usable)
    return _Segments(
        plan["per_width"], plan["hot_slot"], plan["hot_start"], plan["hot_len"],
        plan["hot_rows"], w_max, cols_s, vals_s, rated,
    )


def _usable_widths(widths: Sequence[int]) -> list:
    usable = sorted({int(w) for w in widths if w >= 1})
    if not usable:
        raise ValueError("widths must contain at least one positive width")
    return usable


def _row_offsets(counts_all: np.ndarray) -> tuple:
    """(uniq row ids, their start offset in the row-sorted layout, their
    counts) from a dense per-row count vector — O(num_rows)."""
    uniq = np.nonzero(counts_all)[0]
    counts = counts_all[uniq]
    starts = (np.cumsum(counts_all) - counts_all)[uniq]
    return uniq, starts, counts


def _plan_segments(
    uniq: np.ndarray, starts: np.ndarray, counts: np.ndarray, usable: list
) -> dict:
    """Split rows into fixed-width segments given per-row counts — the
    O(num_rows) planning shared by the host and device bucketing paths."""
    w_max = usable[-1]
    is_hot = counts > w_max
    per_width: dict = {}
    lo = 0
    for w in usable:
        sel = np.nonzero(~is_hot & (counts > lo) & (counts <= w))[0]
        lo = w
        if sel.size:
            per_width[w] = (uniq[sel].astype(np.int32), starts[sel], counts[sel])

    hot_sel = np.nonzero(is_hot)[0]
    num_hot = int(hot_sel.size)
    if num_hot:
        h_counts = counts[hot_sel]
        n_segs = -(-h_counts // w_max)  # per hot row
        hot_slot = np.repeat(np.arange(num_hot, dtype=np.int32), n_segs)
        # segment k of a row starts at row_start + k*w_max
        seg_k = np.arange(int(n_segs.sum())) - np.repeat(
            np.cumsum(n_segs) - n_segs, n_segs
        )
        hot_start = np.repeat(starts[hot_sel], n_segs) + seg_k * w_max
        hot_len = np.minimum(
            np.repeat(h_counts, n_segs) - seg_k * w_max, w_max
        ).astype(np.int64)
        hot_rows = uniq[hot_sel].astype(np.int32)
    else:
        hot_slot = np.zeros(0, np.int32)
        hot_start = np.zeros(0, np.int64)
        hot_len = np.zeros(0, np.int64)
        hot_rows = np.zeros(0, np.int32)
    return {
        "per_width": per_width, "hot_slot": hot_slot, "hot_start": hot_start,
        "hot_len": hot_len, "hot_rows": hot_rows,
    }


def build_buckets(
    rows: np.ndarray,
    cols: np.ndarray,
    vals: np.ndarray,
    num_rows: int,
    num_cols: int,
    widths: Sequence[int] = _DEFAULT_BUCKET_WIDTHS,
    row_multiple: int = 8,
    chunk_entries: int = _DEFAULT_CHUNK_ENTRIES,
    hot_group_slots: int = 2048,
) -> BucketedRatings:
    """Host-side: COO ratings -> chunked, segmented, padded buckets.

    Rows with at most ``max(widths)`` ratings go to the smallest width
    that fits (normal path). Hotter rows are split into ``max(widths)``-
    wide segments (hot path) so no shape depends on the hottest row.
    Every bucket is laid out as ``[n_chunks, C, L]`` with
    ``C·L ≤ chunk_entries`` and ``C`` a multiple of ``row_multiple``
    (keep that a multiple of the mesh's data-axis size so chunk rows
    shard evenly). Rows with zero ratings are absent — ``train_als``
    zeroes their factors via the rated-row mask.
    """
    seg = _segment(rows, cols, vals, num_rows, num_cols, widths)
    nnz = int(np.asarray(rows).size)
    padded_nnz = 0
    normal_chunks: list = []
    hot_chunks: list = []

    def pack(seg_row, seg_start, seg_len, width, sentinel):
        """Pad segments to chunked layout and append a _Chunked."""
        nonlocal padded_nnz
        n_seg = int(seg_row.size)
        c, n_chunks, n_pad = _chunk_plan(n_seg, width, row_multiple, chunk_entries)
        padded_nnz += n_pad * width
        arrs = _fill_bucket(
            n_seg, n_pad, width, seg_row, seg_start, seg_len,
            seg.cols_s, seg.vals_s, sentinel,
        )
        return _chunk(arrs, n_chunks, c, width)

    plan = {
        "per_width": seg.per_width, "hot_slot": seg.hot_slot,
        "hot_start": seg.hot_start, "hot_len": seg.hot_len,
        "hot_rows": seg.hot_rows,
    }
    hot_rows_groups: list = []
    for seg_row, seg_start, seg_len, width, sentinel, hr in _bucket_defs(
        plan, num_rows, seg.w_max, hot_group_slots
    ):
        chunked = pack(seg_row, seg_start, seg_len, width, sentinel)
        if hr is None:
            normal_chunks.append(chunked)
        else:
            hot_chunks.append(chunked)
            hot_rows_groups.append(hr)

    return BucketedRatings(
        tuple(normal_chunks),
        tuple(hot_chunks),
        tuple(hot_rows_groups),
        num_rows,
        num_cols,
        nnz,
        padded_nnz,
    )


def _chunk_plan(
    n_seg: int, width: int, row_multiple: int, chunk_entries: int
) -> tuple[int, int, int]:
    """(rows per chunk, n_chunks, padded rows) for one bucket. Rows are
    bounded both by entries (the gather buffer) and by _DEFAULT_CHUNK_ROWS
    (the [C, K, K] normal-equation buffers)."""
    c = max(row_multiple, (chunk_entries // width) // row_multiple * row_multiple)
    cap = max(row_multiple, _DEFAULT_CHUNK_ROWS // row_multiple * row_multiple)
    c = min(c, cap, -(-max(n_seg, 1) // row_multiple) * row_multiple)
    n_chunks = -(-max(n_seg, 1) // c)
    return c, n_chunks, n_chunks * c


@functools.partial(jax.jit, static_argnames=("n_max",))
def _sort_coo(
    rows: jax.Array, cols: jax.Array, vals: jax.Array, n_max: int
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Device-side row-sort of the COO + per-row counts. One fused XLA
    program: the 20M-entry sort that costs ~3 s/side single-threaded on
    host runs in well under a second on the chip. ``n_max`` is padded to
    ``max(num_rows, num_cols)`` by the caller so the user- and item-side
    sorts share one compiled program."""
    with jax.named_scope("pio_bucket_sort"):
        _, cols_s, vals_s = jax.lax.sort((rows, cols, vals), num_keys=1)
        counts = jnp.zeros(n_max, jnp.int32).at[rows].add(1)
    return cols_s, vals_s, counts


@jax.jit
def _coo_stats(rows: jax.Array, cols: jax.Array) -> jax.Array:
    """[min(rows), min(cols), max(cols)] — one fused validation readback."""
    return jnp.stack([jnp.min(rows), jnp.min(cols), jnp.max(cols)])


@functools.partial(jax.jit, static_argnames=("shapes",))
def _fill_buckets(cs: jax.Array, vs: jax.Array, meta: jax.Array, shapes: tuple):
    """Gather-based ragged fill: idx[r, l] = cols_s[start[r] + l] for
    l < len[r] — one fused gather per bucket, no host scatter. All bucket
    metadata travels in ONE concatenated operand (remote backends pay a
    round-trip per transfer, not per byte); ``shapes`` is the static
    (width, rows_per_chunk, n_chunks) tuple per bucket. Module-level jit:
    a per-call closure would recompile on every train."""
    out = []
    off = 0
    with jax.named_scope("pio_bucket_fill"):
        for width, c, n_chunks in shapes:
            n_pad = c * n_chunks
            row_id = meta[off : off + n_pad]
            st = meta[off + n_pad : off + 2 * n_pad]
            ln = meta[off + 2 * n_pad : off + 3 * n_pad]
            off += 3 * n_pad
            lane = jnp.arange(width, dtype=jnp.int32)[None, :]
            lm = lane < ln[:, None]
            src = jnp.where(lm, st[:, None] + lane, 0)
            out.append(
                _Chunked(
                    row_id.reshape(n_chunks, c),
                    jnp.where(lm, cs[src], 0).reshape(n_chunks, c, width),
                    jnp.where(lm, vs[src], 0.0).reshape(n_chunks, c, width),
                    lm.astype(jnp.float32).reshape(n_chunks, c, width),
                )
            )
    return tuple(out)


def _bucket_defs(plan: dict, num_rows: int, w_max: int, hot_group_slots: int):
    """Yield ``(seg_row, seg_start, seg_len, width, sentinel, hot_rows_g)``
    per bucket — normal-width buckets first (hot_rows_g None), then hot
    groups of <= hot_group_slots slots. The single source of truth for
    bucket/group structure, shared by the host and device fill paths."""
    for w in sorted(plan["per_width"]):
        seg_row, seg_start, seg_len = plan["per_width"][w]
        yield seg_row, seg_start, seg_len, w, num_rows, None
    num_hot = int(plan["hot_rows"].size)
    if num_hot:
        H = hot_group_slots
        g_of_seg = plan["hot_slot"] // H
        for g in range(-(-num_hot // H)):
            sel = g_of_seg == g
            h_g = min(H, num_hot - g * H)
            hr = np.full(h_g + 1, num_rows, dtype=np.int32)
            hr[:h_g] = plan["hot_rows"][g * H : g * H + h_g]
            yield (
                (plan["hot_slot"][sel] - g * H).astype(np.int32),
                plan["hot_start"][sel], plan["hot_len"][sel],
                w_max, h_g, hr,
            )


def build_buckets_device(
    rows: np.ndarray,
    cols: np.ndarray,
    vals: np.ndarray,
    num_rows: int,
    num_cols: int,
    widths: Sequence[int] = _DEFAULT_BUCKET_WIDTHS,
    row_multiple: int = 8,
    chunk_entries: int = _DEFAULT_CHUNK_ENTRIES,
    hot_group_slots: int = 2048,
) -> tuple[BucketedRatings, np.ndarray]:
    """Device-side bucketing: COO ratings -> chunked, segmented, padded
    buckets, with every O(nnz) step on the accelerator.

    The host transfers the raw COO once, reads back only the O(num_rows)
    per-row counts, and plans segment/chunk shapes from them; the sort
    and the padded gather-fills run on device (VERDICT r2 item 2 — the
    20 s single-threaded host bucketing at 20M nnz drops to the device
    sort + a metadata pass). Single-device layout: the mesh path shards
    host-built buckets; the multi-host path has its own assembler.

    Accepts numpy COO arrays, or ``jax.Array``s already on device (int32
    indices) — the latter skips the host round-trip and validates on
    device instead (explicit min/max reductions plus the bincount sum:
    jax scatters WRAP negative indices, so a sum check alone is not
    enough).

    Returns ``(bucketed ratings with device arrays, rated-row mask)``.
    """
    on_device = all(
        isinstance(a, jax.Array) and not isinstance(a, np.ndarray)
        for a in (rows, cols, vals)
    )
    if not on_device:
        rows = np.asarray(rows)
        cols = np.asarray(cols)
        vals = np.asarray(vals, dtype=np.float32)
    if not (rows.shape == cols.shape == vals.shape):
        raise ValueError("rows/cols/vals must be 1-D arrays of equal length")
    if not on_device:
        if rows.size and (rows.min() < 0 or rows.max() >= num_rows):
            raise ValueError("row index out of range")
        if cols.size and (cols.min() < 0 or cols.max() >= num_cols):
            raise ValueError("column index out of range")
    usable = _usable_widths(widths)
    w_max = usable[-1]
    nnz = int(rows.size)
    if nnz == 0 or nnz >= 2**31 or max(num_rows, num_cols) >= 2**31:
        # int32 device indices would overflow — use the host path
        b = build_buckets(
            np.asarray(rows), np.asarray(cols), np.asarray(vals),
            num_rows, num_cols, widths,
            row_multiple, chunk_entries, hot_group_slots,
        )
        return _device_buckets(b, None), rated_row_mask(b)

    if on_device:
        rows_d, cols_d, vals_d = rows, cols, vals
        if jnp.issubdtype(rows_d.dtype, jnp.int64):
            rows_d = rows_d.astype(jnp.int32)
            cols_d = cols_d.astype(jnp.int32)
    else:
        rows_d = jnp.asarray(rows.astype(np.int32))
        cols_d = jnp.asarray(cols.astype(np.int32))
        vals_d = jnp.asarray(vals)
    # pad the count vector to max(rows, cols) so both transposed sides
    # share one compiled sort (XLA compile is expensive on remote backends)
    n_max = max(num_rows, num_cols)
    cols_s, vals_s, counts_d = _sort_coo(rows_d, cols_d, vals_d, n_max)
    counts_full = np.asarray(counts_d).astype(np.int64)
    counts_all = counts_full[:num_rows]
    if on_device:
        # device-side validation, one readback: negative indices WRAP in
        # jax scatters/gathers (they are not dropped), so min() checks are
        # mandatory; rows >= num_rows land in the padding region of the
        # count vector and make the in-range sum fall short
        stats = np.asarray(_coo_stats(rows_d, cols_d))
        if stats[0] < 0 or int(counts_all.sum()) != nnz:
            raise ValueError("row index out of range")
        if stats[1] < 0 or stats[2] >= num_cols:
            raise ValueError("column index out of range")
    uniq, starts, counts = _row_offsets(counts_all)
    plan = _plan_segments(uniq, starts, counts, usable)

    metas: list = []  # (row_id[n_pad], start[n_pad], len[n_pad], width, c, n_chunks)
    padded_nnz = 0
    n_normal = 0
    hot_rows_groups: list = []
    for seg_row, seg_start, seg_len, width, sentinel, hr in _bucket_defs(
        plan, num_rows, w_max, hot_group_slots
    ):
        n_seg = int(seg_row.size)
        c, n_chunks, n_pad = _chunk_plan(n_seg, width, row_multiple, chunk_entries)
        padded_nnz += n_pad * width
        row_id = np.full(n_pad, sentinel, np.int32)
        row_id[:n_seg] = seg_row
        st = np.zeros(n_pad, np.int32)
        st[:n_seg] = seg_start
        ln = np.zeros(n_pad, np.int32)
        ln[:n_seg] = seg_len
        metas.append((row_id, st, ln, width, c, n_chunks))
        if hr is None:
            n_normal += 1
        else:
            hot_rows_groups.append(hr)

    shapes = tuple((m[3], m[4], m[5]) for m in metas)
    meta_concat = (
        np.concatenate([np.concatenate([m[0], m[1], m[2]]) for m in metas])
        if metas
        else np.zeros(0, np.int32)
    )
    chunks = (
        _fill_buckets(cols_s, vals_s, jnp.asarray(meta_concat), shapes)
        if metas
        else ()
    )
    bucketed = BucketedRatings(
        tuple(chunks[:n_normal]),
        tuple(chunks[n_normal:]),
        tuple(hot_rows_groups),
        num_rows,
        num_cols,
        nnz,
        padded_nnz,
    )
    return bucketed, counts_all > 0


def _solve_systems(b: BucketedRatings) -> int:
    """Systems one half-sweep hands the solver, from the bucket shapes:
    every chunk row (padding rows included) and every hot slot (the
    sentinel's included)."""
    return sum(ch.row_id.size for ch in b.normal) + sum(len(hr) for hr in b.hot_rows)


def rated_row_mask(b: BucketedRatings) -> np.ndarray:
    """Bool [num_rows]: which rows appear in the ratings. Rows outside get
    zero factors (parity: the reference only emits factors for trained
    entities — VERDICT round-1 advisor finding on random unrated scores)."""
    mask = np.zeros(b.num_rows + 1, dtype=bool)
    for ch in b.normal:
        mask[np.asarray(ch.row_id).ravel()] = True
    for hr in b.hot_rows:
        mask[np.asarray(hr)] = True
    mask[b.num_rows] = False
    return mask[: b.num_rows]


# ---------------------------------------------------------------------------
# Solver kernels (pure, jit-compiled)
# ---------------------------------------------------------------------------


def _partials(
    Q: jax.Array,  # [C, L, K] masked gathered factors
    chunk_val: jax.Array,  # [C, L]
    meff: jax.Array,  # [C, L] effective mask (0 where padded / out of shard)
    implicit: bool,
    alpha: float,
    hi: jax.lax.Precision,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Per-chunk partial normal equations (no λ/YᵀY yet). All heavy ops
    are [C,L,K]-shaped einsums -> MXU."""
    if implicit:
        conf_minus_1 = alpha * jnp.abs(chunk_val) * meff  # c - 1
        pref = (chunk_val > 0).astype(Q.dtype) * meff
        A = jnp.einsum("clk,cl,clj->ckj", Q, conf_minus_1, Q, precision=hi)
        b = jnp.einsum("clk,cl->ck", Q, (1.0 + conf_minus_1) * pref, precision=hi)
        n = pref.sum(axis=-1)  # MLlib numExplicits: positive ratings
    else:
        A = jnp.einsum("clk,clj->ckj", Q, Q, precision=hi)
        b = jnp.einsum("clk,cl->ck", Q, chunk_val * meff, precision=hi)
        n = meff.sum(axis=-1)
    return A, b, n


def _gram_chunk(
    other: jax.Array,  # [num_cols+1(+pad), K] — model-sharded on a 2-axis mesh
    chunk_idx: jax.Array,  # [C, L]
    chunk_val: jax.Array,  # [C, L]
    chunk_mask: jax.Array,  # [C, L]
    implicit: bool,
    alpha: float,
    hi: jax.lax.Precision,
    mesh: Mesh | None,
    data_axis: str | None,
    model_axis: str | None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Partial normal equations for one chunk of segments.

    Returns (A [C,K,K], b [C,K], n [C]) WITHOUT the λ/YᵀY terms, so the
    same kernel serves both the in-chunk solve (normal rows) and the
    Gramian accumulation (hot-row segments).

    With a model axis the opposite table stays SHARDED: under shard_map
    each device gathers only from its local [N/S, K] shard (entries
    owned by other shards masked to zero) and the partial Gramians are
    psum'd over ``model``. The catalog-sized table never moves or
    replicates — only O(C·K²) Gramian blocks cross ICI (VERDICT r2
    item 1; the chunk-Gramians-move-not-the-table half of the ALX
    recipe, PAPERS.md).
    """
    if mesh is not None and model_axis is not None:
        S = int(mesh.shape[model_axis])
        rps = other.shape[0] // S  # train_als pads the table to a multiple

        def local(tbl, idx, val, mask):
            me = jax.lax.axis_index(model_axis)
            lidx = idx - me * rps
            inr = (lidx >= 0) & (lidx < rps)
            meff = mask * inr.astype(mask.dtype)
            Q = tbl[jnp.where(inr, lidx, 0)] * meff[..., None]
            A, b, n = _partials(Q, val, meff, implicit, alpha, hi)
            return (
                jax.lax.psum(A, model_axis),
                jax.lax.psum(b, model_axis),
                jax.lax.psum(n, model_axis),
            )

        return jax.shard_map(
            local,
            mesh=mesh,
            in_specs=(
                PartitionSpec(model_axis, None),
                PartitionSpec(data_axis, None),
                PartitionSpec(data_axis, None),
                PartitionSpec(data_axis, None),
            ),
            out_specs=(
                PartitionSpec(data_axis, None, None),
                PartitionSpec(data_axis, None),
                PartitionSpec(data_axis),
            ),
        )(other, chunk_idx, chunk_val, chunk_mask)

    if mesh is not None:
        # data-parallel mesh (tables replicated by construction):
        # segment-sharded gather — each device touches only its rows
        gathered = other.at[chunk_idx].get(
            out_sharding=NamedSharding(
                mesh, PartitionSpec(data_axis, None, None)
            )
        )
    else:
        gathered = other[chunk_idx]
    Q = gathered * chunk_mask[..., None]  # [C, L, K]
    return _partials(Q, chunk_val, chunk_mask, implicit, alpha, hi)


#: rows a block of :func:`_gram_all_rows`
_YTY_BLOCK_ROWS = 1024


def _gram_all_rows(
    table: jax.Array,  # [N, K] — model-sharded on a 2-axis mesh
    hi: jax.lax.Precision,
    mesh: Mesh | None,
    model_axis: str | None,
) -> jax.Array:
    """``table.T @ table`` of a whole factor table in float32: a Gramian a
    block of 1,024 rows, the blocks summed pairwise. One matmul over a
    million rows adds thousands of partial sums into one float32
    accumulator, each add rounded relative to the running sum; that error
    sits in every system of the half-sweep and reaches its solution
    amplified by the system's condition. Pairwise it is a few ulps.

    With a model axis each device sums its own shard's blocks so, and the
    shards' Gramians are psum'd over ``model`` (ICI): [K, K] a device
    crosses, the table never moves. The result is replicated."""

    def local(t):
        n, k = t.shape
        blocks = bucket_width(-(-n // _YTY_BLOCK_ROWS), 1)  # a power of two, to halve
        rows = blocks * _YTY_BLOCK_ROWS
        t = jnp.pad(t, ((0, rows - n), (0, 0))).reshape(blocks, _YTY_BLOCK_ROWS, k)
        g = jnp.einsum("bnk,bnj->bkj", t, t, precision=hi)
        while g.shape[0] > 1:
            g = g.reshape(g.shape[0] // 2, 2, k, k)
            g = g[:, 0] + g[:, 1]
        return g[0]

    if mesh is None or model_axis is None:
        return local(table)  # one device, or a table every device holds whole
    return jax.shard_map(
        lambda t: jax.lax.psum(local(t), model_axis),
        mesh=mesh,
        in_specs=PartitionSpec(model_axis, None),
        out_specs=PartitionSpec(None, None),
    )(table)


def _finish_solve(
    A: jax.Array,  # [.., K, K] accumulated Gramian (no reg / yty yet)
    b: jax.Array,  # [.., K]
    n: jax.Array,  # [..] per-row rating count
    reg: float,
    yty: jax.Array | None,
    solver: str,
) -> jax.Array:
    """Add ALS-WR regularization (λ·max(n,1)·I — MLlib scales λ by the
    rating count in both objectives) and the implicit YᵀY, then solve
    (Pallas lane-batched Cholesky on TPU, XLA's elsewhere — see
    ops/solve.py)."""
    from predictionio_tpu.ops.solve import spd_solve

    K = A.shape[-1]
    eye = jnp.eye(K, dtype=A.dtype)
    A = A + (reg * jnp.maximum(n, 1.0))[..., None, None] * eye
    if yty is not None:
        A = A + yty
    return spd_solve(A, b, method=solver)


def _half_sweep(
    factors: jax.Array,  # [num_rows+1, K] — side being updated (model-sharded)
    other_factors: jax.Array,  # [num_cols+1, K] (model-sharded)
    bucketed: BucketedRatings,
    reg: float,
    implicit: bool,
    alpha: float,
    hi: jax.lax.Precision,
    solver: str,
    mesh: Mesh | None,
    data_axis: str | None,
    model_axis: str | None,
) -> jax.Array:
    model_sharding = None
    if mesh is not None:
        # model_axis=None (axis absent from the mesh): replicated tables —
        # the pure-data-parallel layout of e.g. `pio train --mesh data=8`
        spec = PartitionSpec(model_axis, None) if model_axis else PartitionSpec(None, None)
        model_sharding = NamedSharding(mesh, spec)
    # The opposite table is consumed AS SHARDED: _gram_chunk's shard-map
    # path gathers from each device's local shard and psums the Gramians,
    # so the full table never materializes replicated (VERDICT r2 item 1).
    other = other_factors

    yty = None
    if implicit:
        # Gramian over the other side; sentinel row is zero so it is a
        # no-op term
        with jax.named_scope("pio_als_yty"):
            yty = _gram_all_rows(other_factors, hi, mesh, model_axis)

    # --- normal rows: solve in-chunk, scatter into the factor table ------
    for ch in bucketed.normal:

        def step(fac, xs):
            row_id, idx, val, mask = xs
            with jax.named_scope("pio_als_gram"):
                A, b, n = _gram_chunk(
                    other, idx, val, mask, implicit, alpha, hi,
                    mesh, data_axis, model_axis,
                )
            with jax.named_scope("pio_als_solve"):
                x = _finish_solve(A, b, n, reg, yty, solver)  # [C, K]
            # scatter data-sharded solved rows to their model shard —
            # GSPMD lowers to the ICI exchange replacing MLlib's
            # factor-block shuffle
            with jax.named_scope("pio_als_scatter"):
                fac = fac.at[row_id].set(x, out_sharding=model_sharding)
            return fac, None

        factors, _ = jax.lax.scan(step, factors, tuple(ch))

    # --- hot rows: accumulate Gramians across segments, solve per group --
    # groups of <= hot_group_slots rows bound the accumulator at
    # [H_g, K, K] regardless of how many rows are hot (VERDICT r2 weak #2)
    K = factors.shape[-1]
    replicated = None if mesh is None else NamedSharding(mesh, PartitionSpec())
    for ch, hot_rows_g in zip(bucketed.hot, bucketed.hot_rows):
        num_slots = int(hot_rows_g.shape[0])  # H_g + sentinel
        acc = (
            jnp.zeros((num_slots, K, K), factors.dtype, device=replicated),
            jnp.zeros((num_slots, K), factors.dtype, device=replicated),
            jnp.zeros((num_slots,), factors.dtype, device=replicated),
        )

        def hot_step(carry, xs):
            A_acc, b_acc, n_acc = carry
            slot, idx, val, mask = xs
            with jax.named_scope("pio_als_gram"):
                A, b, n = _gram_chunk(
                    other, idx, val, mask, implicit, alpha, hi,
                    mesh, data_axis, model_axis,
                )
            # scatter-add partial Gramians: segments of one row combine
            # here — the hot-row splitting that bounds memory by
            # nnz/max_width instead of the hottest row's count. The
            # accumulators are replicated (H_g is config-bounded), so
            # on a mesh the adds psum across the data axis.
            with jax.named_scope("pio_als_scatter"):
                A_acc = A_acc.at[slot].add(A, out_sharding=replicated)
                b_acc = b_acc.at[slot].add(b, out_sharding=replicated)
                n_acc = n_acc.at[slot].add(n, out_sharding=replicated)
            return (A_acc, b_acc, n_acc), None

        acc, _ = jax.lax.scan(hot_step, acc, tuple(ch))
        with jax.named_scope("pio_als_solve"):
            x_hot = _finish_solve(*acc, reg, yty, solver)  # [num_slots, K]
        hr = jnp.asarray(hot_rows_g)
        with jax.named_scope("pio_als_scatter"):
            factors = factors.at[hr].set(x_hot, out_sharding=model_sharding)

    # padding rows scattered into the sentinel; re-zero it (array index:
    # the scalar-index path rejects/breaks on out_sharding). The sentinel
    # is row ``num_rows`` — the table may carry extra zero rows beyond it
    # so its length divides the model axis.
    sentinel = jnp.reshape(jnp.asarray(bucketed.num_rows, jnp.int32), (1,))
    zero = jnp.zeros((1, factors.shape[1]), factors.dtype)
    return factors.at[sentinel].set(zero, out_sharding=model_sharding)


@functools.partial(
    jax.jit,
    static_argnames=(
        "reg", "implicit", "alpha", "precision", "solver",
        "mesh", "data_axis", "model_axis",
    ),
    donate_argnums=(0, 1),
)
def als_sweep(
    user_factors: jax.Array,
    item_factors: jax.Array,
    user_bucketed: BucketedRatings,
    item_bucketed: BucketedRatings,
    reg: float,
    implicit: bool,
    alpha: float,
    precision: str = "highest",
    solver: str = "cholesky",
    mesh: Mesh | None = None,
    data_axis: str | None = None,
    model_axis: str | None = None,
) -> tuple[jax.Array, jax.Array]:
    """One full ALS iteration: solve users given items, then items given
    users. Compiled once; buffers donated so factors update in place."""
    hi = _PRECISIONS[precision]
    user_factors = _half_sweep(
        user_factors, item_factors, user_bucketed,
        reg, implicit, alpha, hi, solver, mesh, data_axis, model_axis,
    )
    item_factors = _half_sweep(
        item_factors, user_factors, item_bucketed,
        reg, implicit, alpha, hi, solver, mesh, data_axis, model_axis,
    )
    return user_factors, item_factors


def _device_buckets(
    b: BucketedRatings, mesh: Mesh | None, data_axis: str = "data"
) -> BucketedRatings:
    """Place bucket arrays on device — chunk rows sharded over the mesh's
    data axis when a mesh is given (replaces Spark's RDD partitioning).
    ``hot_rows`` stays a host numpy array (its size is static metadata)."""

    def put(ch: _Chunked) -> _Chunked:
        if mesh is not None:
            s1 = NamedSharding(mesh, PartitionSpec(None, data_axis))
            s2 = NamedSharding(mesh, PartitionSpec(None, data_axis, None))
            return _Chunked(
                jax.device_put(ch.row_id, s1),
                jax.device_put(ch.idx, s2),
                jax.device_put(ch.val, s2),
                jax.device_put(ch.mask, s2),
            )
        return _Chunked(
            jnp.asarray(ch.row_id),
            jnp.asarray(ch.idx),
            jnp.asarray(ch.val),
            jnp.asarray(ch.mask),
        )

    return BucketedRatings(
        tuple(put(ch) for ch in b.normal),
        tuple(put(ch) for ch in b.hot),
        tuple(np.asarray(hr) for hr in b.hot_rows),
        b.num_rows,
        b.num_cols,
        b.nnz,
        b.padded_nnz,
    )


def _multihost_bucketed(
    rows: np.ndarray,
    cols: np.ndarray,
    vals: np.ndarray,
    num_rows: int,
    num_cols: int,
    mesh: Mesh,
    data_axis: str,
    widths: Sequence[int],
    chunk_entries: int,
    hot_group_slots: int = 2048,
) -> tuple[BucketedRatings, np.ndarray]:
    """Multi-host: per-host COO shards -> GLOBAL sharded bucket arrays
    without ever materializing the global rating set on one host
    (VERDICT round-1 missing #3 — replaces :func:`_allgather_coo`).

    1. All-to-all the shard so host ``p`` owns every rating of rows with
       ``row % P == p`` (bounded-memory exchange, O(nnz/P) steady state).
    2. Each host segments its rows locally (complete rows -> correct
       counts), then all hosts agree on per-width block shapes (a tiny
       metadata all-gather) so every host packs an identically-shaped
       block per bucket.
    3. ``jax.make_array_from_process_local_data`` assembles the global
       [n_chunks, P*c_local, L] arrays with the chunk-row axis sharded
       over ``data_axis`` (process-contiguous blocks — the mesh must be
       built over ``jax.devices()`` in process order, which
       ``mesh_context()`` does).

    Returns (bucketed ratings with global device arrays, this-host rated
    mask — OR it across hosts for the global mask).
    """
    from jax.experimental import multihost_utils  # noqa: F401  (doc pointer)

    from predictionio_tpu.parallel.exchange import allgather_objects, exchange_by_owner

    P = jax.process_count()
    me = jax.process_index()
    rows = np.asarray(rows, dtype=np.int64)
    cols = np.asarray(cols, dtype=np.int64)
    vals = np.asarray(vals, dtype=np.float32)
    # validate BEFORE the exchange, then agree on the verdict: a lone
    # raise would strand the peers in the next collective until the
    # distributed timeout, so every host gathers the error flags and they
    # all raise together (round-2 advisor finding)
    err = ""
    if rows.size and (rows.min() < 0 or rows.max() >= num_rows):
        err = f"process {me}: row index out of range"
    elif cols.size and (cols.min() < 0 or cols.max() >= num_cols):
        err = f"process {me}: column index out of range"
    errors = [e for e in allgather_objects(err) if e]
    if errors:
        raise ValueError("; ".join(errors))

    rows, cols, vals = exchange_by_owner([rows, cols, vals], rows % P)
    seg = _segment(rows, cols, vals, num_rows, num_cols, widths)

    data_size = int(mesh.shape[data_axis])
    if data_size % P:
        raise ValueError(
            f"data axis ({data_size}) must divide evenly across {P} processes"
        )
    dpl = data_size // P  # data-axis devices per process
    m = int(np.lcm(8, dpl))

    # --- agree on per-width shapes (tiny metadata gather) -----------------
    local_meta = {
        "widths": {w: int(seg.per_width[w][0].size) for w in seg.per_width},
        "num_hot": int(seg.hot_rows.size),
        "nnz": int(rows.size),
    }
    metas = allgather_objects(local_meta)
    all_widths = sorted({w for mt in metas for w in mt["widths"]})
    hot_counts = [mt["num_hot"] for mt in metas]
    hot_offset = int(np.sum(hot_counts[:me]))
    num_hot_tot = int(np.sum(hot_counts))
    nnz_global = int(np.sum([mt["nnz"] for mt in metas]))

    def plan(width: int, n_seg_max: int) -> tuple[int, int]:
        """(c_local, n_chunks): every host pads its block to the same
        n_chunks * c_local rows; global chunk rows C = P * c_local."""
        budget = max(1, chunk_entries // (width * P))
        c_local = max(m, budget // m * m)
        c_local = min(c_local, -(-max(n_seg_max, 1) // m) * m)
        return c_local, -(-max(n_seg_max, 1) // c_local)

    sharding3 = NamedSharding(mesh, PartitionSpec(None, data_axis, None))
    sharding2 = NamedSharding(mesh, PartitionSpec(None, data_axis))

    padded_global = 0

    def assemble(seg_row, seg_start, seg_len, width, sentinel, n_seg_max):
        """Pack this host's block and build the global sharded arrays."""
        nonlocal padded_global
        c_local, n_chunks = plan(width, n_seg_max)
        n_pad = n_chunks * c_local
        padded_global += n_pad * width * P
        row_id, idx, val, mask = _fill_bucket(
            int(seg_row.size), n_pad, width, seg_row, seg_start, seg_len,
            seg.cols_s, seg.vals_s, sentinel,
        )
        glob3 = (n_chunks, P * c_local, width)
        glob2 = (n_chunks, P * c_local)
        return _Chunked(
            jax.make_array_from_process_local_data(
                sharding2, row_id.reshape(n_chunks, c_local), glob2
            ),
            jax.make_array_from_process_local_data(
                sharding3, idx.reshape(n_chunks, c_local, width), glob3
            ),
            jax.make_array_from_process_local_data(
                sharding3, val.reshape(n_chunks, c_local, width), glob3
            ),
            jax.make_array_from_process_local_data(
                sharding3, mask.reshape(n_chunks, c_local, width), glob3
            ),
        )

    empty_i64 = np.zeros(0, np.int64)
    normal_chunks = []
    for w in all_widths:
        n_seg_max = max(mt["widths"].get(w, 0) for mt in metas)
        seg_row, seg_start, seg_len = seg.per_width.get(
            w, (np.zeros(0, np.int32), empty_i64, empty_i64)
        )
        normal_chunks.append(
            assemble(seg_row, seg_start, seg_len, w, num_rows, n_seg_max)
        )

    hot_chunks = []
    hot_rows_groups = []
    if num_hot_tot:
        # groups of <= hot_group_slots GLOBAL slots bound the sweep's
        # Gramian accumulator; every host packs a (possibly empty) block
        # for every group so global shapes agree
        H = hot_group_slots
        n_groups = -(-num_hot_tot // H)
        g_slot = (seg.hot_slot.astype(np.int64) + hot_offset).astype(np.int64)
        my_counts = [
            int(np.count_nonzero((g_slot >= g * H) & (g_slot < (g + 1) * H)))
            for g in range(n_groups)
        ]
        all_counts = allgather_objects(my_counts)
        gathered_hot = allgather_objects(seg.hot_rows.tolist())
        hot_rows_all = np.concatenate(
            [np.asarray(h, np.int32) for h in gathered_hot]
        )
        rep_sharding = NamedSharding(mesh, PartitionSpec(None))
        for g in range(n_groups):
            sel = (g_slot >= g * H) & (g_slot < (g + 1) * H)
            h_g = min(H, num_hot_tot - g * H)
            n_seg_max = max(c[g] for c in all_counts)
            hot_chunks.append(
                assemble(
                    (g_slot[sel] - g * H).astype(np.int32),
                    seg.hot_start[sel], seg.hot_len[sel],
                    seg.w_max, h_g, n_seg_max,
                )
            )
            hr = np.full(h_g + 1, num_rows, dtype=np.int32)
            hr[:h_g] = hot_rows_all[g * H : g * H + h_g]
            # a raw numpy leaf must not enter a multi-process jit —
            # materialize the (identical-everywhere) slot map replicated
            hot_rows_groups.append(
                jax.make_array_from_callback(
                    hr.shape, rep_sharding,
                    lambda idx, hr=hr: hr[idx],
                )
            )

    bucketed = BucketedRatings(
        tuple(normal_chunks),
        tuple(hot_chunks),
        tuple(hot_rows_groups),
        num_rows,
        num_cols,
        nnz_global,
        padded_global,
    )
    return bucketed, seg.rated


def _allgather_coo(
    rows: np.ndarray, cols: np.ndarray, vals: np.ndarray
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Multi-host: exchange per-host COO shards so every process holds the
    identical global rating set (jax requires globally-consistent values
    for sharded ``device_put``). This one-time DCN gather replaces Spark's
    shuffle-on-read; all per-iteration exchange stays in GSPMD collectives.
    Ragged per-host sizes are padded to the max and masked out after."""
    from jax.experimental import multihost_utils

    n_local = np.array([len(vals)], dtype=np.int64)
    n_all = np.asarray(multihost_utils.process_allgather(n_local)).ravel()
    n_max = int(n_all.max())

    def pad(a, dtype):
        out = np.zeros(n_max, dtype=dtype)
        out[: len(a)] = a
        return out

    stacked = np.stack([pad(rows, np.int64), pad(cols, np.int64)]).astype(np.int64)
    gathered_idx = np.asarray(multihost_utils.process_allgather(stacked))
    gathered_val = np.asarray(multihost_utils.process_allgather(pad(vals, np.float32)))
    # gathered_idx: [P, 2, n_max]; gathered_val: [P, n_max]
    out_r, out_c, out_v = [], [], []
    for p, n in enumerate(n_all):
        out_r.append(gathered_idx[p, 0, :n])
        out_c.append(gathered_idx[p, 1, :n])
        out_v.append(gathered_val[p, :n])
    return (
        np.concatenate(out_r),
        np.concatenate(out_c),
        np.concatenate(out_v).astype(np.float32),
    )


def train_als(
    rows: np.ndarray,
    cols: np.ndarray,
    vals: np.ndarray,
    num_users: int,
    num_items: int,
    config: ALSConfig = ALSConfig(),
    mesh: Mesh | None = None,
    data_axis: str = "data",
    model_axis: str = "model",
    init_user: np.ndarray | None = None,
    init_item: np.ndarray | None = None,
    info: dict | None = None,
) -> ALSFactors:
    """Train factor matrices from COO ratings.

    ``info``, when given, receives the kernel decisions this train took
    (backend, solver, bucketing, ``objective`` -- and under the implicit
    one ``alpha`` and ``positiveEntries`` --, precision, rank, mesh), the
    skew of the data a side (``hotRows``, ``hotGroups``) and its timing
    (``bucketingSeconds``: transfer, sort and fill, of which
    ``transferSeconds`` is the host-to-device copy; ``initSeconds``: the
    two tables seeded; ``sweepSeconds`` — the first sweep carries the
    compile) — what ``pio train`` records in the engine instance. The
    phases are spans (``utils/spans.py``: ``train.transfer``,
    ``train.bucketing``, ``train.init``, one ``train.sweep`` a sweep).

    In a multi-process job, ``rows/cols/vals`` are this host's shard of
    the ratings (the sharded event-reader layout). With a mesh, shards are
    re-partitioned by row through a bounded-memory exchange — per-host
    memory stays O(nnz / num_hosts) (see :func:`_multihost_bucketed`);
    without a mesh they are all-gathered (legacy replicated fallback).

    ``init_user``/``init_item`` (``[num_users, K]`` / ``[num_items, K]``)
    seed the factors instead of the random draw — the warm-retrain path
    (``pio train --warm-start``). Unrated rows are still zeroed, and a
    checkpoint resume (``config.checkpoint_dir``) takes precedence.

    Returns host-strippable ``ALSFactors`` with the sentinel rows removed:
    ``user [num_users, K]``, ``item [num_items, K]``.
    """
    if config.precision not in _PRECISIONS:
        raise ValueError(
            f"ALSConfig.precision must be one of {sorted(_PRECISIONS)}, "
            f"got {config.precision!r}"
        )
    if config.solver not in ("auto", "cholesky", "pallas", "pallas_interpret"):
        raise ValueError(
            "ALSConfig.solver must be 'auto', 'cholesky', 'pallas' or "
            f"'pallas_interpret', got {config.solver!r}"
        )
    if config.bucketing not in ("auto", "host", "device"):
        raise ValueError(
            "ALSConfig.bucketing must be 'auto', 'host' or 'device', "
            f"got {config.bucketing!r}"
        )
    rank = config.rank
    if config.rank_pad_multiple:
        rank = -(-rank // config.rank_pad_multiple) * config.rank_pad_multiple
    multihost = jax.process_count() > 1
    solver = config.solver
    if solver == "auto":
        # the Mosaic kernel is single-device; sharded sweeps keep the
        # portable Cholesky until the kernel is shard_map-wrapped
        on_tpu = jax.default_backend() == "tpu"
        solver = "pallas" if (on_tpu and mesh is None) else "cholesky"
    elif solver.startswith("pallas") and mesh is not None:
        # an explicit kernel request on a sharded sweep would compile the
        # single-device pallas_call under GSPMD — downgrade instead of
        # failing (covers "pallas" and "pallas_interpret" alike)
        logger.warning(
            "solver=%r is single-device; using 'cholesky' on the mesh", solver
        )
        solver = "cholesky"
    use_device_bucketing = mesh is None and not multihost and (
        config.bucketing == "device"
        or (config.bucketing == "auto" and jax.default_backend() != "cpu")
    )
    from predictionio_tpu.ops.solve import pallas_rank_ok, solve_kernel_name

    decisions = {
        "backend": jax.default_backend(),
        # what the sweep will really run: spd_solve sends a rank beyond
        # the kernel's ceiling to Cholesky (and says so)
        "solver": (
            solver
            if not solver.startswith("pallas") or pallas_rank_ok(rank)
            else "cholesky"
        ),
        # the solver as a device trace names it
        "solveKernel": solve_kernel_name(solver, rank),
        "bucketing": "device" if use_device_bucketing else "host",
        "objective": "implicit" if config.implicit else "explicit",
        "precision": config.precision,
        "rank": rank,
        "mesh": None if mesh is None else dict(mesh.shape),
    }
    if config.implicit:
        decisions["alpha"] = float(config.alpha)
        # entries with preference 1 (this process's, in a multi-process
        # job): the terms of the right-hand sides, either side's
        decisions["positiveEntries"] = int(np.count_nonzero(np.asarray(vals) > 0))
    logger.info("ALS kernel decisions: %s", decisions)
    # the timing fields cost a host sync after bucketing and after every
    # sweep: taken only for a caller that asked for them
    timed = info is not None
    info = {} if info is None else info
    info.update(decisions)
    if mesh is not None and model_axis not in mesh.shape:
        # a data-only mesh (e.g. `pio train --mesh data=8`): fall back to
        # replicated factor tables
        model_axis = None
    # bucketing wall (transfer, sort, fill — and their compiles when cold),
    # each span closed by a sync so the next phase is not charged for it
    transfer = None
    if multihost and mesh is not None:
        # bounded-memory path: per-host shards stay sharded; only rows are
        # re-partitioned (VERDICT round-1 missing #3)
        from predictionio_tpu.parallel.exchange import allgather_objects

        with span("train.bucketing") as bucketing:
            user_bucketed, u_rated = _multihost_bucketed(
                rows, cols, vals, num_users, num_items, mesh, data_axis,
                config.bucket_widths, config.chunk_entries,
                config.hot_group_slots,
            )
            item_bucketed, i_rated = _multihost_bucketed(
                cols, rows, vals, num_items, num_users, mesh, data_axis,
                config.bucket_widths, config.chunk_entries,
                config.hot_group_slots,
            )
            # the global rated mask is the OR of the per-host masks
            u_rated = np.bitwise_or.reduce(
                allgather_objects(np.packbits(u_rated))
            )
            i_rated = np.bitwise_or.reduce(
                allgather_objects(np.packbits(i_rated))
            )
            u_rated = np.unpackbits(u_rated, count=num_users).astype(bool)
            i_rated = np.unpackbits(i_rated, count=num_items).astype(bool)
            if timed:
                jax.block_until_ready((user_bucketed, item_bucketed))
    else:
        if multihost:
            # mesh-less multi-process training: legacy replicated path
            rows, cols, vals = _allgather_coo(
                np.asarray(rows), np.asarray(cols), np.asarray(vals)
            )
        row_multiple = 8
        if mesh is not None:
            # chunk rows must divide evenly over the data axis
            row_multiple = int(np.lcm(8, mesh.shape.get(data_axis, 1)))
        bucket_args = dict(
            widths=config.bucket_widths, row_multiple=row_multiple,
            chunk_entries=config.chunk_entries,
            hot_group_slots=config.hot_group_slots,
        )
        if use_device_bucketing:
            with span("train.transfer") as transfer:
                # transfer the COO ONCE and hand device arrays to both
                # sides (each side would otherwise re-upload the same ~12
                # bytes/nnz); validate on host BEFORE the int32 cast so
                # out-of-range int64 values cannot truncate into range
                r_h, c_h = np.asarray(rows), np.asarray(cols)
                v_h = np.asarray(vals, dtype=np.float32)
                if r_h.size and (r_h.min() < 0 or r_h.max() >= num_users):
                    raise ValueError("row index out of range")
                if c_h.size and (c_h.min() < 0 or c_h.max() >= num_items):
                    raise ValueError("column index out of range")
                small = max(num_users, num_items) < 2**31 and r_h.size < 2**31
                if small and r_h.size:
                    rows_x = jnp.asarray(r_h.astype(np.int32))
                    cols_x = jnp.asarray(c_h.astype(np.int32))
                    vals_x = jnp.asarray(v_h)
                    if timed:
                        jax.block_until_ready((rows_x, cols_x, vals_x))
                else:
                    rows_x, cols_x, vals_x = r_h, c_h, v_h
            with span("train.bucketing") as bucketing:
                user_bucketed, u_rated = build_buckets_device(
                    rows_x, cols_x, vals_x, num_users, num_items, **bucket_args
                )
                item_bucketed, i_rated = build_buckets_device(
                    cols_x, rows_x, vals_x, num_items, num_users, **bucket_args
                )
                if timed:
                    jax.block_until_ready((user_bucketed, item_bucketed))
        else:
            with span("train.bucketing") as bucketing:
                user_b = build_buckets(
                    rows, cols, vals, num_users, num_items, **bucket_args
                )
                item_b = build_buckets(
                    cols, rows, vals, num_items, num_users, **bucket_args
                )
                u_rated = rated_row_mask(user_b)
                i_rated = rated_row_mask(item_b)
            with span("train.transfer") as transfer:
                user_bucketed = _device_buckets(user_b, mesh, data_axis)
                item_bucketed = _device_buckets(item_b, mesh, data_axis)
                if timed:
                    jax.block_until_ready((user_bucketed, item_bucketed))

    info["solveSystemsPerSweep"] = _solve_systems(user_bucketed) + _solve_systems(
        item_bucketed
    )
    # the skew: rows wider than the widest bucket, and the groups of
    # hot_group_slots their Gramians are accumulated in, a side
    sides = {"user": user_bucketed, "item": item_bucketed}
    info["hotRows"] = {
        k: sum(len(hr) - 1 for hr in b.hot_rows) for k, b in sides.items()
    }
    info["hotGroups"] = {k: len(b.hot) for k, b in sides.items()}
    if timed:
        info["bucketingSeconds"] = round(
            bucketing.seconds + (transfer.seconds if transfer else 0.0), 3
        )
        if transfer is not None:
            info["transferSeconds"] = round(transfer.seconds, 3)

    # seeding the two tables: a handful of eager programs (random draw,
    # mask, pad), each traced and loaded on its first call
    seeding = span("train.init").start()
    key_u, key_i = jax.random.split(jax.random.PRNGKey(config.seed))
    # Table length: num_rows + 1 sentinel row, padded up so the row axis
    # divides the model-axis size (extra rows stay zero, never written).
    model_size = int(mesh.shape.get(model_axis, 1)) if mesh is not None else 1
    n_u = -(-(num_users + 1) // model_size) * model_size
    n_i = -(-(num_items + 1) // model_size) * model_size
    # MLlib seeds factors with nonnegative abs(normal) rows. On the
    # implicit objective the rows are additionally normalized to unit L2
    # (MLlib's exact init): with confidence weighting, an unlucky
    # small-norm draw parks a row in a slow convergence basin for many
    # sweeps — measurably, the similar-product fixture needs 5x the
    # sweeps to separate its item groups from one such draw. The
    # explicit objective keeps the historical /sqrt(rank) scale (same
    # expected norm) so explicitly-trained models are bit-identical
    # across this change. Unrated rows are zeroed so cold entities never
    # outscore trained ones (round-1 advisor fix).
    u_mask = np.append(u_rated, False)[:, None]
    i_mask = np.append(i_rated, False)[:, None]
    # draw at the canonical (num_rows+1) shape so the init — and therefore
    # the trained factors — are identical across mesh shapes, then zero-pad
    def _seed_table(key, init, num_rows):
        if init is None:
            draw = jnp.abs(
                jax.random.normal(key, (num_rows + 1, rank), jnp.float32)
            )
            if config.implicit:
                norms = jnp.linalg.norm(draw, axis=1, keepdims=True)
                return draw / jnp.maximum(norms, 1e-9)
            # multiply by the precomputed reciprocal (not a divide): the
            # historical op, so explicit inits are bit-identical to it
            return draw * (1.0 / np.sqrt(rank))
        init = np.asarray(init, dtype=np.float32)
        if init.shape[0] != num_rows:
            raise ValueError(
                f"warm init has {init.shape[0]} rows, expected {num_rows}"
            )
        table = np.zeros((num_rows + 1, rank), np.float32)
        k = min(rank, init.shape[1])
        table[:num_rows, :k] = init[:, :k]
        return jnp.asarray(table)

    uf = _seed_table(key_u, init_user, num_users)
    vf = _seed_table(key_i, init_item, num_items)
    uf = jnp.pad(uf * jnp.asarray(u_mask), ((0, n_u - num_users - 1), (0, 0)))
    vf = jnp.pad(vf * jnp.asarray(i_mask), ((0, n_i - num_items - 1), (0, 0)))
    if mesh is not None:
        # persistent tables sharded over the model axis (ALX): catalog
        # memory scales with the mesh instead of being replicated
        model_sharded = NamedSharding(mesh, PartitionSpec(model_axis, None))
        if multihost:
            # every host holds the identical full table; carve out the
            # addressable shards (device_put cannot target a global mesh)
            uf_h, vf_h = np.asarray(uf), np.asarray(vf)
            uf = jax.make_array_from_callback(
                uf_h.shape, model_sharded, lambda idx: uf_h[idx]
            )
            vf = jax.make_array_from_callback(
                vf_h.shape, model_sharded, lambda idx: vf_h[idx]
            )
        else:
            uf = jax.device_put(uf, model_sharded)
            vf = jax.device_put(vf, model_sharded)
    if timed:
        jax.block_until_ready((uf, vf))
    seeding.stop()
    if timed:
        info["initSeconds"] = round(seeding.seconds, 3)

    rep = None if mesh is None else NamedSharding(mesh, PartitionSpec())
    if mesh is not None:

        def _strip(a, b):
            # replicate BEFORE slicing: the canonical length need not
            # divide the model axis, so a sharded-dim slice is illegal
            # (reshard, not with_sharding_constraint — the latter doesn't
            # change the sharded *type* under explicit-sharding meshes)
            a = reshard(a, rep)
            b = reshard(b, rep)
            return a[: num_users + 1], b[: num_items + 1]

        # jitted ONCE per train: the jit cache is keyed on the function
        # object, so a per-save closure would retrace every checkpoint
        _strip_jit = jax.jit(_strip, out_shardings=rep)

    def _to_canonical(u: jax.Array, v: jax.Array) -> dict:
        """Checkpoint state at the canonical (num_rows+1, K) replicated
        shape: the on-disk layout must not depend on the mesh's model-axis
        size, or a resume on a different mesh fails the shape match
        (round-2 advisor finding). Always returns FRESH buffers (copies on
        the mesh-less path) so the async orbax save can overlap the next
        sweep, whose donation would otherwise race the live tables."""
        if mesh is None:
            return {"user": jnp.copy(u), "item": jnp.copy(v)}
        cu, ci = _strip_jit(u, v)
        return {"user": cu, "item": ci}

    def _canonical_like() -> dict:
        """Abstract restore template — no device work, just shapes."""
        return {
            "user": jax.ShapeDtypeStruct(
                (num_users + 1, rank), jnp.float32, sharding=rep
            ),
            "item": jax.ShapeDtypeStruct(
                (num_items + 1, rank), jnp.float32, sharding=rep
            ),
        }

    def _from_canonical(state: dict) -> tuple[jax.Array, jax.Array]:
        """Re-pad restored canonical factors to this mesh's table shape
        and reshard them over the model axis."""
        u, v = state["user"], state["item"]
        if mesh is None:
            return u, v
        return jax.jit(
            lambda a, b: (
                jnp.pad(a, ((0, n_u - (num_users + 1)), (0, 0))),
                jnp.pad(b, ((0, n_i - (num_items + 1)), (0, 0))),
            ),
            out_shardings=NamedSharding(mesh, PartitionSpec(model_axis, None)),
        )(u, v)

    manager = None
    start_step = 0
    if config.checkpoint_dir:
        from predictionio_tpu.utils.checkpoint import CheckpointManager

        manager = CheckpointManager(config.checkpoint_dir)
        latest = manager.latest_step()
        if latest is not None:
            try:
                state = manager.restore(latest, like=_canonical_like())
            except (ValueError, TypeError, KeyError) as exc:
                # shape/structure drift only (e.g. a pre-canonical padded
                # checkpoint, or a different rank); transient I/O errors
                # propagate rather than silently restarting from step 0
                logger.warning(
                    "Checkpoint step %d is incompatible with this run "
                    "(%s); starting fresh", latest, exc,
                )
            else:
                uf, vf = _from_canonical(state)
                # a completed run restores and short-circuits the sweep loop
                start_step = min(latest, config.iterations)
                logger.info(
                    "Resumed ALS from checkpoint step %d", latest
                )

    sweep_seconds, sweep_cpu_seconds = [], []
    for step in range(start_step, config.iterations):
        with span("train.sweep") as sweep:
            uf, vf = als_sweep(
                uf, vf, user_bucketed, item_bucketed,
                reg=config.reg, implicit=config.implicit, alpha=config.alpha,
                precision=config.precision,
                solver=solver,
                mesh=mesh,
                data_axis=data_axis if mesh is not None else None,
                model_axis=model_axis if mesh is not None else None,
            )
            if timed:
                # one sync per sweep: the first entry carries the sweep's
                # compile, the rest are steady state
                jax.block_until_ready(vf)
        if timed:
            sweep_seconds.append(round(sweep.seconds, 3))
            sweep_cpu_seconds.append(round(sweep.cpu_seconds, 3))
        if manager is not None and (
            (step + 1) % config.checkpoint_interval == 0
            or step + 1 == config.iterations
        ):
            # _to_canonical hands the save fresh buffers, so the async
            # write overlaps the next sweep instead of serializing it
            manager.save(step + 1, _to_canonical(uf, vf))
    if timed:
        info["sweepSeconds"] = sweep_seconds
        # the calling thread's CPU time in each (all 0 where its collector
        # takes none): near the wall where the host computes (a trace, a
        # lowering), far under it where it waits for the device
        info["sweepCpuSeconds"] = sweep_cpu_seconds
    if manager is not None:
        manager.wait()
        manager.close()
    if mesh is not None:
        if jax.process_count() > 1:
            # multi-host: replicate before stripping the sentinel row —
            # np.asarray cannot assemble a non-fully-addressable array,
            # and a jitted identity reshards on any topology (device_put
            # cannot retarget a multi-process mesh)
            replicated = NamedSharding(mesh, PartitionSpec())
            uf, vf = jax.jit(
                lambda a, b: (a, b), out_shardings=replicated
            )(uf, vf)
        else:
            # single-host mesh: assemble the tables on HOST straight
            # from the per-device shards. The previous jitted replicate
            # materialized the FULL table on every device right at the
            # finish line — the one step whose peak per-device memory
            # was O(catalog) instead of O(catalog / model_axis), which
            # re-created the BENCH_r01 OOM the sharded sweep avoids.
            return ALSFactors(
                user=np.asarray(uf)[:num_users],
                item=np.asarray(vf)[:num_items],
            )
    # the sentinel rows off: two eager slices, each traced and loaded on
    # its first call (0.18 s each on the chip's host): the first step of
    # bringing the tables out, so the readback's span holds it
    with span("train.readback") as strip:
        factors = ALSFactors(user=uf[:num_users], item=vf[:num_items])
        if timed:
            jax.block_until_ready(factors)
    if timed:
        info["readbackSeconds"] = round(strip.seconds, 3)
    return factors


def factors_to_host(info: dict, *tables: jax.Array) -> tuple[np.ndarray, ...]:
    """The trained tables as host arrays (what a model blob holds), and
    the seconds that took, added to what :func:`train_als` spent stripping
    them, as ``info["readbackSeconds"]`` beside its other timings."""
    with span("train.readback") as readback:
        host = tuple(np.asarray(t) for t in tables)
    info["readbackSeconds"] = round(
        info.get("readbackSeconds", 0.0) + readback.seconds, 3
    )
    return host


# ---------------------------------------------------------------------------
# Inference kernels
# ---------------------------------------------------------------------------


@jax.jit
def predict_scores(user_vec: jax.Array, item_factors: jax.Array) -> jax.Array:
    """Scores of one user against all items: ``item_factors @ user_vec``."""
    return jnp.matmul(item_factors, user_vec, precision=SCORE_PRECISION)


@functools.partial(jax.jit, static_argnames=("k",))
def top_k_items(
    user_vec: jax.Array,
    item_factors: jax.Array,
    k: int,
    exclude_mask: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Top-k item ids + scores for one user. ``exclude_mask`` (bool [I])
    drops items (e.g. already-rated) by sending them to -inf — the
    serving-time filter of the reference's recommendation templates."""
    scores = jnp.matmul(item_factors, user_vec, precision=SCORE_PRECISION)
    if exclude_mask is not None:
        scores = jnp.where(exclude_mask, -jnp.inf, scores)
    return top_k_scores(scores, k)


@functools.partial(jax.jit, static_argnames=("k",))
def top_k_items_batch(
    user_idx: jax.Array,
    user_factors: jax.Array,
    item_factors: jax.Array,
    k: int,
) -> tuple[jax.Array, jax.Array]:
    """Top-k for a BATCH of users in one dispatch: gather the user rows on
    device, score every item with one ``[B, K] @ [K, I]`` GEMM (MXU work,
    not B GEMVs), and select each row's top ``k`` (``ops.topk``
    ``select_top_k``: ``lax.top_k``'s result, over a wide catalog from
    the maxima of 128-column blocks). Returns ``([B, k] item ids,
    [B, k] scores)`` — the only host transfer is the 2·B·k result.

    This is the batch-amortized device serving path (ref
    ``core/workflow/BatchPredict.scala`` ``batchPredictBase``): one
    dispatch per chunk amortizes the per-dispatch cost over the whole
    chunk, where per-query dispatch pays it per prediction."""
    with jax.named_scope("pio_topk_score"):
        user_vecs = user_factors[user_idx]
        scores = jnp.matmul(
            user_vecs, item_factors.T, precision=SCORE_PRECISION
        )
    return top_k_scores(scores, k)
    # NB: donating the user_idx staging buffer was considered for the
    # pinned serving path and rejected: XLA input-output aliasing needs
    # byte-compatible shapes, and the (chunk,) int32 index buffer can
    # never alias the (chunk, k>=16) outputs — the donation would only
    # produce "donated buffers were not usable" warnings.


#: items one tile of :func:`top_k_items_filtered` holds: the ``[rows,
#: tile]`` float32 scores of 32 rows are 64 MB, whatever the catalog
FILTER_TILE = 1 << 19

#: the most bytes those scores may take: what bounds the rows of one
#: dispatch (64 at a full tile)
FILTER_SCORE_BYTES = 1 << 27

#: floor of the pair bucket of :func:`tile_pairs`: the (row, excluded id)
#: pairs of a batch that fall into its fullest tile. 32 rows that leave out
#: 60 ids each put 64 into each of 30 tiles and some 90 into the fullest,
#: so ordinary traffic (histories and black lists of tens of ids) stays in
#: ONE bucket and a deploy compiles one; what a slot costs on a v5e: PERF.md
FILTER_PAIR_FLOOR = 128


@functools.partial(jax.jit, donate_argnums=0)
def _set_tile(tiles: jax.Array, rows: jax.Array, t: jax.Array) -> jax.Array:
    return tiles.at[t].set(rows.T)


def tile_items(table: np.ndarray, fill, tile: int | None = None) -> jax.Array:
    """A per-item host table ``[I, C]`` as the device array ``[tiles, C,
    width]`` that :func:`top_k_items_filtered` scans: cut along the items
    into tiles of ``width`` (``tile``, by default ``FILTER_TILE``, or the
    whole catalog rounded up to 128 lanes when that is less), each
    transposed so the items lie along the lanes, the last padded with
    ``fill``. Tiles cross the link one at a time into a donated buffer:
    the device never holds the table twice and the host never a
    transposed copy."""
    table = np.asarray(table)
    n, c = table.shape
    width = min(int(tile or FILTER_TILE), -(-max(n, 1) // 128) * 128)
    n_tiles = -(-max(n, 1) // width)
    tiles = jnp.full((n_tiles, c, width), fill, table.dtype)
    for t in range(n_tiles):
        rows = table[t * width : (t + 1) * width]
        if rows.shape[0] < width:
            rows = np.concatenate(
                [rows, np.full((width - rows.shape[0], c), fill, table.dtype)]
            )
        # one tile in flight: the loop would else run ahead of the link and
        # park every tile on the device beside the buffer it is bound for
        tiles = _set_tile(tiles, rows, t).block_until_ready()
    return tiles


def tile_pairs(
    excluded: np.ndarray, n_tiles: int, width: int
) -> tuple[np.ndarray, np.ndarray, int]:
    """The item ids a batch's rows leave out (``excluded`` ``i32[rows,
    E]``, padded with ``NO_ITEM``), grouped by the tile of
    :func:`tile_items` each falls into: ``(row, column)``, two ``i32[tiles,
    P]`` arrays that hold, tile by tile, the row and the position within
    the tile of every real pair, and the number of pairs. ``NO_ITEM``,
    ids past the tiles and a row's repeats are dropped; the unused slots of
    a tile hold column ``width``, past the tile, which
    :func:`top_k_items_filtered` discards. ``P`` is the
    ``ops.topk.bucket_width`` bucket of the fullest tile's count (floor
    ``FILTER_PAIR_FLOOR``): an array extent of the program, it follows the
    batch's own lists. Numpy over a few hundred pairs."""
    n_rows = excluded.shape[0]
    real = (excluded >= 0) & (excluded < n_tiles * width)
    # one sort by (id, row) drops a row's repeats and groups by tile
    key = np.unique(excluded[real].astype(np.int64) * n_rows + np.nonzero(real)[0])
    ids, row = np.divmod(key, n_rows)
    tile, col = np.divmod(ids, width)
    per_tile = np.bincount(tile, minlength=n_tiles)
    slot = np.arange(key.size) - np.repeat(np.cumsum(per_tile) - per_tile, per_tile)
    p = bucket_width(int(per_tile.max()), FILTER_PAIR_FLOOR)
    drop_row = np.zeros((n_tiles, p), np.int32)
    drop_col = np.full((n_tiles, p), width, np.int32)
    drop_row[tile, slot] = row
    drop_col[tile, slot] = col
    return drop_row, drop_col, int(key.size)


@functools.partial(jax.jit, static_argnames=("k",))
def top_k_items_filtered(
    user_vecs: jax.Array,
    item_tiles: jax.Array,
    code_tiles: jax.Array,
    blocked: jax.Array,
    wanted: jax.Array,
    drop_row: jax.Array,
    drop_col: jax.Array,
    k: int,
) -> tuple[jax.Array, jax.Array]:
    """:func:`top_k_items_batch` under per-row business rules: the exact
    top ``k`` of the items each row is ALLOWED, by the one tie rule
    (descending score, ascending id), in one dispatch that never holds a
    catalog-wide score matrix.

    ``user_vecs`` ``f32[rows, rank]`` are the batch's user rows, gathered
    by the caller: a row gather from a ``[users, rank]`` table in here
    makes XLA copy the whole table into a row-major layout first, on
    every dispatch (1 GB at 2 M users; compiled for a v5e, PERF.md).
    ``item_tiles`` ``f32[tiles, rank, width]`` and ``code_tiles``
    ``i32[tiles, C, width]`` are :func:`tile_items` of the item factors
    and of the items' category codes (``-1`` = none; an item may carry
    ``C``). ``blocked`` ``bool[tiles, width]`` marks what no row may be
    given: padding past the catalog, and items out of stock. Per row:
    ``wanted`` ``i32[rows, W]`` the category codes asked for (padded
    with ``-2``; a row whose first entry is negative names no category
    and allows all). Per tile, :func:`tile_pairs` of the item ids the
    rows leave out: ``drop_row`` / ``drop_col`` ``i32[tiles, P]``, the
    row and the position within the tile of each (row, id) pair (a
    position of ``width`` pads and is discarded).

    Each tile is scored (``U_b @ V_tile`` at ``SCORE_PRECISION``),
    masked to ``-inf`` where category or ``blocked`` do not allow, cut to
    its own top ``k`` without its own pairs by
    :func:`~predictionio_tpu.ops.topk.select_top_k` (``lax.top_k``'s
    result; at a full tile's width it reads the scores once for the
    maxima of 128-column blocks, mends the ``P`` blocks that hold a pair
    and selects among the ``k`` leading blocks' columns) and merged into
    the carried ``[rows, k]`` by
    :func:`~predictionio_tpu.ops.topk.sort_merge_topk`'s two keys — the
    ``lax.top_k`` of the masked full row. What the rows leave out costs
    by the pairs there are, never by the catalog. A slot no allowed item
    fills comes back as ``(NO_ITEM, -inf)``."""
    n_tiles, _, width = item_tiles.shape
    rows = user_vecs.shape[0]
    names_none = wanted[:, :1] < 0
    kt = min(k, width)

    def one_tile(best, tile):
        t, v_t, codes_t, blocked_t, drop_row_t, drop_col_t = tile
        with jax.named_scope("pio_topk_score"):
            scores = jnp.matmul(user_vecs, v_t, precision=SCORE_PRECISION)
        with jax.named_scope("pio_topk_mask"):
            in_category = jnp.any(
                codes_t[None, :, None, :] == wanted[:, None, :, None],
                axis=(1, 2),
            )
            allowed = (in_category | names_none) & ~blocked_t[None]
            scores = jnp.where(allowed, scores, -jnp.inf)
        with jax.named_scope("pio_topk_select"):
            vals, pos = select_top_k(scores, kt, drop=(drop_row_t, drop_col_t))
        with jax.named_scope("pio_topk_merge"):
            ids = jnp.where(vals > -jnp.inf, pos + t * width, NO_ITEM)
            ids, vals = sort_merge_topk(
                jnp.concatenate([best[1], vals], axis=1),
                jnp.concatenate([best[0], ids], axis=1),
                k,
            )
        return (ids, vals), None

    best = (
        jnp.full((rows, k), NO_ITEM, jnp.int32),
        jnp.full((rows, k), -jnp.inf, jnp.float32),
    )
    (ids, vals), _ = jax.lax.scan(
        one_tile, best,
        (jnp.arange(n_tiles, dtype=jnp.int32), item_tiles, code_tiles,
         blocked, drop_row, drop_col),
    )
    return ids, vals
