"""Model-axis sharded factor serving — ``pio deploy --shard-factors``.

BENCH_r01 died the moment the catalog outgrew one chip
(``f32[64761856,64]`` = 16.6 GB *per table* against 17 GB of HBM)
because serving replicates the factor tables on every device. Training
already shards them ALX-style (``ops/als.py`` keeps the persistent
tables ``PartitionSpec('model', None)`` and moves only O(C·K²) Gramian
blocks over ICI); this module extends the same layout through the
serving path so per-device factor memory is ``O((U+I)·K / S)`` for an
``S``-way model axis — the largest servable catalog scales with the
mesh instead of being capped by a single chip.

Three pieces:

* **Shard placement** — :func:`serving_mesh` builds a one-axis
  (``model``) mesh over the local devices and :func:`shard_table`
  ``device_put``\\ s a factor table row-sharded across it (rows padded
  to a multiple of the axis so every shard is even; padding rows are
  zero and masked out of every kernel by the LOGICAL row count).
  :class:`ShardInfo` carries the mesh plus the logical row counts so
  the padded physical shapes never leak into id spaces.
* **Sharded exact top-K** (:func:`sharded_topk_users`) — a shard_map
  kernel in the MapReduce shape DrJAX frames as a primitive (PAPERS.md):
  each device resolves the query rows from its USER shard (masked
  gather + ``psum`` — the catalog-sized table never moves), scores only
  its ITEM shard with one local GEMM, takes a local top-k (position
  order == global id order within a shard, so ``lax.top_k``'s tie rule
  is already the shared one), and ``all_gather``\\ s ONLY the ``S·k``
  finalists per query; the cross-shard reduce reuses the shared two-key
  tie rule (:func:`~predictionio_tpu.ops.topk.sort_merge_topk`), so the
  merged ranking is tie-stable-identical to the replicated exact kernel.
* **Sharded IVF** (:func:`sharded_ivf_topk`) — PR 6's cluster-major
  slabs shard over the same axis (``ops/ivf.shard_runtime``): centroids
  stay replicated (tiny), every device scores only the probed clusters
  it OWNS, and the same two-level tie-stable merge gathers ``S·k``
  candidates per query.

Strictly opt-in: nothing imports this module until a deploy passes
``--shard-factors`` (CI-guarded like ``--ann``/``--online``).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec

from predictionio_tpu.ops.topk import SCORE_PRECISION, bucket_k, sort_merge_topk

__all__ = [
    "MODEL_AXIS",
    "ShardInfo",
    "serving_mesh",
    "shard_table",
    "shard_quantized_table",
    "gather_rows",
    "row_sharding",
    "take_rows",
    "sharded_topk_users",
    "sharded_quantized_topk_users",
    "sharded_ivf_topk",
    "table_bytes",
    "sharded_table_bytes",
    "per_device_bytes",
    "per_device_bytes_quantized",
]

#: serving-side model axis name (matches the training mesh's axis so the
#: memory model reads the same: per-device rows = rows / S)
MODEL_AXIS = "model"

#: cold-start growth headroom (rows) when a sharded table must be
#: re-laid-out: growing by at least this much amortizes the
#: gather+re-shard over many fold-ins instead of paying it per new
#: entity (same bounded-retrace idea as ops/ivf._CAPACITY_STEP)
GROW_STEP = 1024


@dataclasses.dataclass
class ShardInfo:
    """Per-model sharded-serving state, held as ``shards`` of the model's
    ``templates.retrieval.ServingState`` by the ``shard_model_for_serving``
    hook.

    ``rows`` maps side name (``"user"``/``"item"``) to the LOGICAL row
    count — the physical tables are padded up to a multiple of the mesh
    axis, and every kernel masks by the logical count so padding rows
    can never score or be returned. Mutable on purpose: online
    cold-start fold-ins advance the logical counts (see
    ``workflow/device_state.swap_side_rows``)."""

    mesh: Mesh
    rows: dict

    @property
    def num_shards(self) -> int:
        return int(self.mesh.shape[MODEL_AXIS])


def serving_mesh(shards: int = 0) -> Mesh | None:
    """A one-axis (``model``) mesh over the local devices for sharded
    serving. ``shards`` caps the axis size (0 = all local devices).
    Returns ``None`` on a single-device host — sharding over one device
    is replication, so callers fall back to plain pinning."""
    devs = jax.devices()
    n = len(devs) if shards <= 0 else max(1, min(int(shards), len(devs)))
    if n < 2:
        return None
    # Explicit, like the training mesh (controller/context.py): eager reads
    # of a sharded table go through gather_rows, never ``tbl[idx]``
    return jax.make_mesh(
        (n,), (MODEL_AXIS,), devices=devs[:n],
        axis_types=(AxisType.Explicit,),
    )


def table_spec(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec(MODEL_AXIS, None))


def padded_rows(n: int, shards: int) -> int:
    """Physical row count: logical rows padded up so every shard is even."""
    return -(-max(int(n), 1) // shards) * shards


def shard_table(mat, mesh: Mesh, capacity: int = 0) -> jax.Array:
    """Place a factor table row-sharded over the mesh's model axis.

    Rows are zero-padded to a multiple of the axis size (and up to
    ``capacity`` when given — the cold-start growth headroom), then
    ``device_put`` with ``PartitionSpec('model', None)``: each device
    receives ONLY its ``[rows/S, K]`` shard, which is the whole point —
    the full table never materializes in any single device's memory."""
    mat = np.asarray(mat, dtype=np.float32)
    if mat.ndim != 2:
        raise ValueError(f"factor table must be 2-D, got {mat.shape}")
    S = int(mesh.shape[MODEL_AXIS])
    n_pad = padded_rows(max(mat.shape[0], capacity), S)
    if n_pad != mat.shape[0]:
        mat = np.concatenate(
            [mat, np.zeros((n_pad - mat.shape[0], mat.shape[1]), mat.dtype)]
        )
    return jax.device_put(mat, table_spec(mesh))


def shard_quantized_table(mat, mesh: Mesh, capacity: int = 0):
    """Quantize a host f32 table (``ops/quant``'s one rounding rule) and
    place it int8-sharded over the mesh's model axis: codes
    ``PartitionSpec('model', None)``, per-row scales
    ``PartitionSpec('model')`` — per-device factor memory drops to
    ``rows/S · (rank + 4)`` bytes, the multiplicative composition of the
    sharding and quantization tiers (``pio deploy --shard-factors
    --quantize int8``). Zero padding rows quantize to zero codes + zero
    scale and stay masked by the logical row count like the f32 layout."""
    from predictionio_tpu.ops import quant

    mat = np.asarray(mat, dtype=np.float32)
    if mat.ndim != 2:
        raise ValueError(f"factor table must be 2-D, got {mat.shape}")
    S = int(mesh.shape[MODEL_AXIS])
    n_pad = padded_rows(max(mat.shape[0], capacity), S)
    if n_pad != mat.shape[0]:
        mat = np.concatenate(
            [mat, np.zeros((n_pad - mat.shape[0], mat.shape[1]), mat.dtype)]
        )
    codes, scales = quant.quantize_table_host(mat)
    return quant.QuantizedTable(
        jax.device_put(codes, table_spec(mesh)),
        jax.device_put(
            scales, NamedSharding(mesh, PartitionSpec(MODEL_AXIS))
        ),
    )


# ---------------------------------------------------------------------------
# Byte accounting (the bench's memory model; pure shape math, CPU-safe)
# ---------------------------------------------------------------------------


def table_bytes(rows: int, rank: int, itemsize: int = 4) -> int:
    """Bytes of one replicated factor table — what EVERY device pays
    without sharding."""
    return int(rows) * int(rank) * itemsize


def sharded_table_bytes(
    rows: int, rank: int, shards: int, itemsize: int = 4
) -> int:
    """Per-device bytes of the same table sharded ``shards``-way
    (including the even-shard padding — the only overhead, bounded by
    ``(shards-1)·rank·itemsize``)."""
    return padded_rows(rows, shards) // shards * int(rank) * itemsize


def per_device_bytes(arr) -> int:
    """MEASURED bytes the largest single device holds of ``arr`` — the
    quantity the scale bench asserts against ``table_bytes / S``."""
    per: dict = {}
    for s in arr.addressable_shards:
        per[s.device] = per.get(s.device, 0) + int(s.data.nbytes)
    return max(per.values()) if per else 0


def per_device_bytes_quantized(qt) -> int:
    """Measured per-device bytes of a sharded quantized table — codes
    AND scales, read from the actual array shards so the scale bench
    asserts served truth, not shape math."""
    return per_device_bytes(qt.codes) + per_device_bytes(qt.scales)


# ---------------------------------------------------------------------------
# Kernels
# ---------------------------------------------------------------------------


def _resolve_rows(tbl, idx):
    """Inside shard_map: gather rows ``idx`` (GLOBAL ids, replicated)
    from this device's table shard, masking out-of-shard rows to zero;
    the ``psum`` over the model axis then assembles the true rows on
    every device — only ``[B, K]`` crosses ICI, never the table."""
    rps = tbl.shape[0]  # local shard rows
    me = jax.lax.axis_index(MODEL_AXIS)
    lidx = idx - me * rps
    inr = (lidx >= 0) & (lidx < rps)
    rows = tbl[jnp.where(inr, lidx, 0)]
    # any rank/dtype: int8 codes and 1-D scale vectors resolve here too
    inr = inr.reshape(inr.shape + (1,) * (rows.ndim - 1))
    rows = jnp.where(inr, rows, jnp.zeros((), rows.dtype))
    return jax.lax.psum(rows, MODEL_AXIS)


@functools.partial(jax.jit, static_argnames=("mesh",))
def gather_rows(idx: jax.Array, tbl: jax.Array, mesh: Mesh) -> jax.Array:
    """Rows ``idx`` of a model-sharded table, replicated — the sharded
    analog of ``tbl[idx]`` that moves only the requested rows."""

    def local(i, t):
        return _resolve_rows(t, i)

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(
            PartitionSpec(),
            PartitionSpec(MODEL_AXIS, *([None] * (tbl.ndim - 1))),
        ),
        out_specs=PartitionSpec(),
        check_vma=False,
    )(idx, tbl)


def row_sharding(mat) -> NamedSharding | None:
    """The table's NamedSharding when its rows are partitioned over a
    mesh axis (the --shard-factors layout), else None."""
    s = getattr(mat, "sharding", None)
    if (
        isinstance(s, NamedSharding)
        and len(s.spec) >= 1
        and s.spec[0] is not None
    ):
        return s
    return None


def take_rows(mat, idx):
    """Rows ``idx`` of a factor table wherever it lives — the read twin
    of ``workflow.device_state.set_rows``. Host arrays fancy-index and a
    pinned table gathers on device; a ``--shard-factors`` table resolves
    the rows from their owner shards (:func:`gather_rows`): the serving
    mesh has Explicit axes, where an eager ``tbl[idx]`` on a sharded
    table is a type error rather than a silent all-gather. A quantized
    table dequantizes only the rows asked for."""
    if getattr(mat, "is_quantized", False):
        from predictionio_tpu.ops import quant

        return quant.dequantize(
            take_rows(mat.codes, idx), take_rows(mat.scales, idx)
        )
    if isinstance(mat, np.ndarray):
        return mat[np.asarray(idx, np.int64)]
    idx = jnp.asarray(np.asarray(idx, np.int32))
    sharded = row_sharding(mat)
    if sharded is not None:
        return gather_rows(idx, mat, sharded.mesh)
    return mat[idx]


@functools.partial(jax.jit, static_argnames=("k", "mesh"))
def sharded_topk_users(
    user_idx: jax.Array,
    user_tbl: jax.Array,
    item_tbl: jax.Array,
    k: int,
    num_items: jax.Array,
    mesh: Mesh,
) -> tuple[jax.Array, jax.Array]:
    """Exact top-k over model-sharded factor tables, one dispatch per
    batch: ``([B, k] item ids, [B, k] scores)``, descending score, ties
    by ascending item id — tie-stable-identical to
    :func:`predictionio_tpu.ops.als.top_k_items_batch` on the same
    factors (CI-asserted; within a shard position order IS global id
    order, so the local ``lax.top_k`` already applies the shared rule,
    and the cross-shard reduce is the shared two-key
    :func:`ops.topk.sort_merge_topk` rule).

    ``num_items`` (the LOGICAL catalog bound masking the padding rows)
    is a TRACED scalar on purpose: online cold-start fold-ins advance it
    on every batch while the padding-slot design keeps the table SHAPE
    fixed — static, it would recompile the serving kernel per fold.

    Per-device work: one masked row-resolve + psum for the query rows,
    one ``[B,K]@[K,I/S]`` GEMM over the LOCAL item shard, a local
    top-k, and an all-gather of ``S·k`` finalists per query — per-device
    memory and FLOPs both scale as ``catalog / S``."""
    S = int(mesh.shape[MODEL_AXIS])
    i_rps = item_tbl.shape[0] // S
    kk = min(int(k), i_rps)

    def local(idx, u_l, i_l, n_items):
        q = _resolve_rows(u_l, idx)  # [B, K] true user rows
        me = jax.lax.axis_index(MODEL_AXIS)
        scores = jnp.matmul(q, i_l.T, precision=SCORE_PRECISION)  # [B, I/S]
        base = (me * i_rps).astype(jnp.int32)
        gid = base + jnp.arange(i_rps, dtype=jnp.int32)
        # zero padding rows must never outrank real negative scores
        scores = jnp.where(gid[None, :] < n_items, scores, -jnp.inf)
        v, p = jax.lax.top_k(scores, kk)
        gi = base + p.astype(jnp.int32)
        gv = jax.lax.all_gather(v, MODEL_AXIS, axis=1, tiled=True)
        gids = jax.lax.all_gather(gi, MODEL_AXIS, axis=1, tiled=True)
        # cross-shard reduce: the shared two-key tie rule over S*kk
        # finalists (ops/topk.sort_merge_topk — the fast barrier path
        # is illegal under manual partitioning, see its docstring)
        return sort_merge_topk(gv, gids, min(int(k), S * kk))

    P = PartitionSpec
    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(), P(MODEL_AXIS, None), P(MODEL_AXIS, None), P()),
        out_specs=(P(), P()),
        check_vma=False,
    )(user_idx, user_tbl, item_tbl, jnp.asarray(num_items, jnp.int32))


@functools.partial(jax.jit, static_argnames=("k", "kp", "mesh"))
def sharded_quantized_topk_users(
    user_idx: jax.Array,
    u_codes: jax.Array,
    u_scales: jax.Array,
    i_codes: jax.Array,
    i_scales: jax.Array,
    k: int,
    kp: int,
    num_items: jax.Array,
    mesh: Mesh,
) -> tuple[jax.Array, jax.Array]:
    """Two-stage quantized top-k over model-sharded int8 tables (``pio
    deploy --shard-factors --quantize int8``), one dispatch per batch.

    Per-device work: resolve + DEQUANTIZE the query rows from the local
    user shard (masked gather + psum of ``[B, K]`` f32 rows — codes
    never leave their shard), re-quantize the assembled queries
    in-kernel, one int8×int8 ``[B,K]@[K,I/S]`` coarse GEMM over the
    LOCAL item shard, a per-shard over-fetch of ``kp`` candidates, an
    f32 rescore of ONLY those local candidates (each shard owns its
    finalists, so the rescore gather never crosses the interconnect),
    then the usual two-level tie-stable merge of ``S·k`` rescored
    finalists. Every stage applies the shared
    :func:`ops.topk.sort_merge_topk` rule on f32 rescored scores, so the
    ordering is exact-f32-deterministic — and identical to the
    replicated quantized kernel (and the f32 exact path's tie order)
    whenever the over-fetch covers the true top-k, which is what the
    bench's recall guard measures. (The per-shard over-fetch is a
    SUPERSET of the replicated kernel's global one, so sharding can
    only widen the rescored candidate pool, never narrow it.)"""
    from predictionio_tpu.ops import quant

    S = int(mesh.shape[MODEL_AXIS])
    i_rps = i_codes.shape[0] // S
    kk = min(int(k), i_rps)
    kpp = max(kk, min(int(kp), i_rps))

    def local(idx, uc, us, ic, isc, n_items):
        rps = uc.shape[0]
        me = jax.lax.axis_index(MODEL_AXIS)
        lidx = idx - me * rps
        inr = (lidx >= 0) & (lidx < rps)
        sel = jnp.where(inr, lidx, 0)
        rows = quant.dequantize(uc[sel], us[sel])
        q = jax.lax.psum(jnp.where(inr[:, None], rows, 0.0), MODEL_AXIS)
        q_codes, q_scales = quant.quantize_rows_traced(q)
        acc = quant.int8_matmul(q_codes, ic)  # [B, I/S] int32
        approx = acc.astype(jnp.float32) * q_scales[:, None] * isc[None, :]
        base = (me * i_rps).astype(jnp.int32)
        gid = base + jnp.arange(i_rps, dtype=jnp.int32)
        approx = jnp.where(gid[None, :] < n_items, approx, -jnp.inf)
        _, p = jax.lax.top_k(approx, kpp)  # local over-fetch
        # rescore: gather + dequantize only the local finalists, score
        # against the UNQUANTIZED f32 query
        deq = quant.dequantize(ic[p], isc[p])  # [B, kpp, K]
        exact = jnp.einsum("bpk,bk->bp", deq, q, precision=SCORE_PRECISION)
        gi = base + p.astype(jnp.int32)
        valid = gi < n_items
        exact = jnp.where(valid, exact, -jnp.inf)
        gi = jnp.where(valid, gi, n_items)
        li, lv = sort_merge_topk(exact, gi, kk)
        gv = jax.lax.all_gather(lv, MODEL_AXIS, axis=1, tiled=True)
        gids = jax.lax.all_gather(li, MODEL_AXIS, axis=1, tiled=True)
        return sort_merge_topk(gv, gids, min(int(k), S * kk))

    P = PartitionSpec
    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(
            P(),
            P(MODEL_AXIS, None),
            P(MODEL_AXIS),
            P(MODEL_AXIS, None),
            P(MODEL_AXIS),
            P(),
        ),
        out_specs=(P(), P()),
        check_vma=False,
    )(
        user_idx, u_codes, u_scales, i_codes, i_scales,
        jnp.asarray(num_items, jnp.int32),
    )


@functools.partial(jax.jit, static_argnames=("k", "nprobe", "mesh"))
def sharded_ivf_topk(
    qvecs: jax.Array,
    index,
    k: int,
    nprobe: int,
    mesh: Mesh,
) -> tuple[jax.Array, jax.Array]:
    """IVF retrieval over cluster-major slabs sharded on the model axis
    (``index`` from :func:`predictionio_tpu.ops.ivf.shard_runtime`:
    slabs/slab_ids ``PartitionSpec('model', None, ...)``, centroids
    replicated, ``nlist`` padded to a multiple of the axis with the
    TRUE count in the static metadata).

    Stage 1 (centroid scoring + probe selection) is replicated compute —
    identical on every device, so the probe set needs no exchange.
    Stage 2 each device gathers+scores ONLY the probed clusters it owns
    (out-of-shard probe slots masked), local-merges tie-stably, and
    all-gathers ``S·k`` finalists for the same cross-shard
    :func:`ops.topk.top_k_permuted` reduce the exact path uses. Result
    rows equal the unsharded :func:`ops.ivf.ivf_topk_batch` on the same
    index, including tie order; per-device slab memory is
    ``nlist/S · W · K``."""
    S = int(mesh.shape[MODEL_AXIS])
    nlist_pad = index.slabs.shape[0]  # physical cluster rows (global)
    lists_per = nlist_pad // S
    W = index.slab_width
    nlist_true = index.nlist
    num_items = index.num_items
    quantized = index.slab_scales is not None  # int8 slab codes
    nprobe = max(1, min(int(nprobe), nlist_true))
    kk = max(1, min(int(k), nprobe * W))

    def local(q, cent, slabs_l, ids_l, scales_l):
        me = jax.lax.axis_index(MODEL_AXIS)
        if nprobe >= nlist_true:
            # every cluster probed: skip stage 1 and score this shard's
            # whole cluster-major slab table with ONE GEMM — the same
            # per-item dot shape as the exact path and the unsharded
            # nprobe==nlist mode, which is what keeps this mode
            # bit-identical to exact top-K (scores AND tie order; int8
            # slabs keep determinism over the dequantized table)
            flat = slabs_l.reshape(-1, slabs_l.shape[-1])
            if quantized:
                scores = jnp.matmul(
                    q, flat.T.astype(jnp.float32), precision=SCORE_PRECISION
                ) * scales_l.reshape(1, -1)
            else:
                scores = jnp.matmul(  # [B, lists_per*W]
                    q, flat.T, precision=SCORE_PRECISION
                )
            ids = jnp.broadcast_to(
                ids_l.reshape(1, -1), scores.shape
            )
            scores = jnp.where(ids < num_items, scores, -jnp.inf)
            ids = jnp.where(ids < num_items, ids, num_items)
        else:
            # [B, nlist_pad], replicated compute
            cs = jnp.matmul(q, cent.T, precision=SCORE_PRECISION)
            col = jnp.arange(cs.shape[-1], dtype=jnp.int32)
            cs = jnp.where(col[None, :] < nlist_true, cs, -jnp.inf)
            _, probe = jax.lax.top_k(cs, nprobe)  # global cluster ids
            lp = probe - me * lists_per
            own = (lp >= 0) & (lp < lists_per)
            sc_parts = []
            id_parts = []
            # one gather+einsum per probe SLOT (static unroll, same
            # shape discipline as the unsharded kernel) — slots owned by
            # another shard read slab 0 but are fully masked out
            for j in range(nprobe):
                sel = jnp.where(own[:, j], lp[:, j], 0)
                cand = slabs_l[sel]  # [B, W, K] — int8: 1/4 gather bytes
                ids_j = ids_l[sel]  # [B, W]
                if quantized:
                    s_j = jnp.einsum(
                        "bwk,bk->bw", cand.astype(jnp.float32), q,
                        precision=SCORE_PRECISION,
                    ) * scales_l[sel]
                else:
                    s_j = jnp.einsum(
                        "bwk,bk->bw", cand, q, precision=SCORE_PRECISION
                    )
                valid = own[:, j, None] & (ids_j < num_items)
                sc_parts.append(jnp.where(valid, s_j, -jnp.inf))
                id_parts.append(jnp.where(valid, ids_j, num_items))
            scores = jnp.concatenate(sc_parts, axis=1)
            ids = jnp.concatenate(id_parts, axis=1)
        # local candidate order is (probe slot, lane) — NOT id order —
        # so the local merge must already be tie-stable in id space
        li, lv = sort_merge_topk(scores, ids, kk)
        gv = jax.lax.all_gather(lv, MODEL_AXIS, axis=1, tiled=True)
        gi = jax.lax.all_gather(li, MODEL_AXIS, axis=1, tiled=True)
        return sort_merge_topk(gv, gi, min(int(k), S * kk))

    P = PartitionSpec
    # zero-size scale placeholder when unquantized: shard_map wants a
    # concrete operand per spec, and a dead [S, 0] input costs nothing
    scales_arg = (
        index.slab_scales
        if quantized
        else jnp.zeros((S, 0), jnp.float32)
    )
    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(
            P(),
            P(),
            P(MODEL_AXIS, None, None),
            P(MODEL_AXIS, None),
            P(MODEL_AXIS, None),
        ),
        out_specs=(P(), P()),
        check_vma=False,
    )(qvecs, index.centroids, index.slabs, index.slab_ids, scales_arg)


# ---------------------------------------------------------------------------
# Host-facing wrappers (numpy in, numpy out — what templates call)
# ---------------------------------------------------------------------------


def topk_users(
    info: ShardInfo, user_tbl, item_tbl, user_idx, k: int
) -> tuple[np.ndarray, np.ndarray]:
    """Top-``k`` for a batch of user INDICES through the sharded exact
    kernel; ``k`` buckets to a power of two (floor 16) so the jitted
    program compiles once per bucket, exactly like the exact and ANN
    paths. Returns ``([B, k] ids, [B, k] scores)`` as numpy."""
    num_items = int(info.rows["item"])
    k = max(1, min(int(k), num_items))
    kb = bucket_k(k, num_items)
    idx = jnp.asarray(np.asarray(user_idx, dtype=np.int32))
    ids, scores = sharded_topk_users(
        idx, user_tbl, item_tbl, kb, num_items, info.mesh
    )
    return np.asarray(ids)[:, :k], np.asarray(scores)[:, :k]
