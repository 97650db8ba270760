"""On-device IVF (inverted-file) approximate top-K retrieval.

Every exact query scores the whole catalog, so serving FLOPs per query
grow linearly with catalog size — fine at the 27k-item bench shape,
fatal at "millions of users x millions of items" (ROADMAP item 2). This
module makes per-query cost scale with ``nprobe * (catalog / nlist)``
instead:

* **Build** (model-load time, :func:`build_ivf`) — a jitted k-means
  (k-means++ seeding on a bounded subsample, batched Lloyd iterations
  with chunked assignment so the [n, nlist] distance matrix never
  materializes whole) partitions the item factors into ``nlist``
  clusters, then the factors are reordered **cluster-major**: one
  contiguous ``[nlist, W, K]`` slab tensor (W = the largest cluster,
  smaller clusters padded) plus a ``[nlist, W]`` permutation index back
  to original item ids (padding carries the ``num_items`` sentinel).
  Contiguous slabs are what make the probe stage a dense gather+GEMM
  instead of a sparse scatter walk — the clustered layout half of the
  ALX recipe (PAPERS.md, "Large Scale Matrix Factorization on TPUs").
* **Query** (:func:`ivf_topk_batch` / :func:`ivf_topk_users`) — a
  two-stage jitted kernel in the broadcast-score-reduce shape DrJAX
  frames as a MapReduce primitive (PAPERS.md): score the ``nlist``
  centroids, ``lax.top_k`` the ``nprobe`` best clusters, score ONLY
  those slabs, and merge a global top-K through the permutation index
  with :func:`predictionio_tpu.ops.topk.top_k_permuted` (tie-stable in
  original item id). With ``nprobe == nlist`` the kernel skips the
  gather and scores the full cluster-major table with one GEMM — the
  same dot shape as the exact path — so it reproduces exact top-K
  bit-identically (scores AND tie order); CI asserts this.
* **Filtering** — blacklist/seen-item filters are applied by
  OVER-FETCHING ``K + |excluded|`` candidates before the final merge
  (:func:`query_topk`'s callers), never by post-hoc dropping from an
  exact-K result: a post-hoc filter returns fewer than K items whenever
  popular (high-scoring) items are excluded, and approximate retrieval
  amplifies that hole.

Serving integration: :mod:`predictionio_tpu.workflow.device_state`
builds/releases :class:`AnnRuntime` per model generation (hot-swapped by
``/reload`` exactly like pinned factors); templates route their top-K
through it when present. Everything is strictly opt-in behind
``pio deploy --ann`` — with the flag off this module is never imported
(CI-guarded).
"""

from __future__ import annotations

import functools
import threading
import time
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from predictionio_tpu.ops.topk import SCORE_PRECISION, bucket_k, top_k_permuted

__all__ = [
    "IVFIndex",
    "AnnRuntime",
    "build_ivf",
    "update_ivf",
    "ivf_topk_batch",
    "ivf_topk_users",
    "query_topk",
    "auto_nlist",
    "shard_runtime",
]

#: id-capacity rounding for incrementally grown indexes: ``num_items``
#: is STATIC under jit (it is the sentinel and the mask bound), so every
#: distinct value costs one retrace — growing it in 1024-item jumps
#: means a steady trickle of cold-start items re-traces the query kernel
#: once per ~1024 injections instead of once per fold
_CAPACITY_STEP = 1024

#: rows per chunk of the Lloyd assignment scan — bounds the transient
#: [chunk, nlist] distance block at 64 MB for nlist=1024 instead of
#: materializing the full [n, nlist] matrix (1 GB at 256k items)
_ASSIGN_CHUNK = 16_384

#: k-means++ seeds on at most max(4096, 16 * nlist) subsampled rows:
#: seeding is a scan of nlist O(n*K) steps, so full-catalog seeding would
#: cost nlist/iters times MORE than all Lloyd iterations together
_SEED_SAMPLE_PER_LIST = 16
_SEED_SAMPLE_MIN = 4096


class IVFIndex(NamedTuple):
    """Cluster-major retrieval state. Array fields are pytree children;
    the int metadata travels in the treedef so it stays STATIC under jit
    (the query kernel's shapes and the sentinel id are compile-time
    constants).

    With ``--quantize int8`` (``build_ivf(quantize=True)``) ``slabs``
    holds int8 codes and ``slab_scales`` the per-lane f32 scales
    (``ops/quant``'s one rounding rule) — per-probe gather bytes drop
    ~4x, which is the dominating cost of the probe stage on
    bandwidth-bound hosts (PR 6's measurement). ``slab_scales is None``
    means the classic f32 layout; the treedef difference keeps the two
    modes on separate compiled programs."""

    centroids: Any  # [nlist, K] f32 (ALWAYS f32 — stage 1 stays exact)
    slabs: Any  # [nlist, W, K] f32 (or int8 codes) — zero-padded slabs
    slab_ids: Any  # [nlist, W] int32 — item id per slab row; pad = num_items
    num_items: int
    nlist: int
    slab_width: int
    slab_scales: Any = None  # [nlist, W] f32 per-lane scales (int8 mode)


jax.tree_util.register_pytree_node(
    IVFIndex,
    lambda x: ((x.centroids, x.slabs, x.slab_ids, x.slab_scales),
               (x.num_items, x.nlist, x.slab_width)),
    lambda aux, ch: IVFIndex(ch[0], ch[1], ch[2], *aux, ch[3]),
)


def auto_nlist(num_items: int) -> int:
    """Default cluster count: ~sqrt(catalog) balances the two stage
    costs (stage 1 scores nlist centroids, stage 2 scores ~nprobe * I /
    nlist items), the standard IVF sizing rule of thumb."""
    return max(1, int(round(float(num_items) ** 0.5)))


# ---------------------------------------------------------------------------
# Build: jitted k-means (k-means++ seeding + batched Lloyd iterations)
# ---------------------------------------------------------------------------


def _assign_chunked(x_pad: jax.Array, cents: jax.Array) -> jax.Array:
    """argmin_c ||x - c||^2 per row of ``x_pad [n_chunks, C, K]`` ->
    ``[n_chunks, C]`` int32, one [C, nlist] distance block at a time.
    ||x||^2 is row-constant, so centroid scores reduce to c.c - 2 x.c."""
    c2 = (cents * cents).sum(axis=-1)

    def one(xc: jax.Array) -> jax.Array:
        d = c2[None, :] - 2.0 * (xc @ cents.T)
        return jnp.argmin(d, axis=1).astype(jnp.int32)

    return jax.lax.map(one, x_pad)


@functools.partial(jax.jit, static_argnames=("nlist",))
def _kmeans_pp(key: jax.Array, x: jax.Array, nlist: int) -> jax.Array:
    """k-means++ seeding: first centroid uniform, then D^2 sampling via
    ``categorical(log d2)`` — one fused scan, no host round trips."""
    n = x.shape[0]
    key, k0 = jax.random.split(key)
    c0 = x[jax.random.randint(k0, (), 0, n)]
    cents = jnp.zeros((nlist, x.shape[1]), x.dtype).at[0].set(c0)
    d2 = ((x - c0) ** 2).sum(axis=-1)

    def body(carry, i):
        key, cents, d2 = carry
        key, kc = jax.random.split(key)
        # duplicate points drive d2 to exactly 0; log() sends them to
        # -inf (never re-picked). If EVERY point is already covered the
        # draw degrades to uniform rather than sampling NaNs.
        logits = jnp.where(d2 > 0, jnp.log(jnp.maximum(d2, 1e-30)), -jnp.inf)
        logits = jnp.where(jnp.any(d2 > 0), logits, jnp.zeros_like(logits))
        c = x[jax.random.categorical(kc, logits)]
        cents = cents.at[i].set(c)
        d2 = jnp.minimum(d2, ((x - c) ** 2).sum(axis=-1))
        return (key, cents, d2), None

    (_, cents, _), _ = jax.lax.scan(
        body, (key, cents, d2), jnp.arange(1, nlist)
    )
    return cents


@functools.partial(jax.jit, static_argnames=("iters", "n"))
def _lloyd(
    x: jax.Array, x_pad: jax.Array, cents: jax.Array, iters: int, n: int
) -> jax.Array:
    """``iters`` batched Lloyd iterations: chunked assignment, then a
    scatter-add mean update. Empty clusters keep their old centroid (the
    slab build simply emits an all-sentinel slab for them)."""

    def step(cents, _):
        a = _assign_chunked(x_pad, cents).reshape(-1)[:n]
        sums = jnp.zeros_like(cents).at[a].add(x)
        counts = jnp.zeros((cents.shape[0],), x.dtype).at[a].add(1.0)
        new = sums / jnp.maximum(counts, 1.0)[:, None]
        return jnp.where(counts[:, None] > 0, new, cents), None

    cents, _ = jax.lax.scan(step, cents, None, length=iters)
    return cents


@functools.partial(jax.jit, static_argnames=("n",))
def _final_assign(x_pad: jax.Array, cents: jax.Array, n: int) -> jax.Array:
    return _assign_chunked(x_pad, cents).reshape(-1)[:n]


def _balance_assignment(
    x: np.ndarray, cents: np.ndarray, assign: np.ndarray,
    nlist: int, cap: int,
) -> np.ndarray:
    """Cap every cluster at ``cap`` items: overloaded clusters keep
    their ``cap`` CLOSEST members and spill the rest to the nearest
    cluster with room. The slab width — which every probe pays for in
    gather bytes regardless of which cluster it hits — is bounded by
    ``cap`` instead of the most popular cluster's size (factor models
    concentrate mass on popular regions, so unbalanced widths of 2-3x
    the mean are routine). ``nlist * cap >= items`` by construction, so
    placement always succeeds."""
    counts = np.bincount(assign, minlength=nlist)
    if counts.max() <= cap:
        return assign
    own = cents[assign]
    d_own = ((x - own) ** 2).sum(axis=1)
    spilled: list = []
    for c in np.nonzero(counts > cap)[0]:
        members = np.nonzero(assign == c)[0]
        keep = members[np.argsort(d_own[members], kind="stable")]
        spilled.extend(keep[cap:].tolist())
    counts = np.minimum(counts, cap)
    spill = np.asarray(spilled)
    # nearest-with-room greedy, processed in spill order. Ranking keys
    # come from the GEMM identity ||x-c||^2 = ||x||^2 - 2 x.c + ||c||^2
    # (||x||^2 is row-constant, so c.c - 2 x.c sorts identically): the
    # naive [spill, nlist, K] broadcast would materialize tens of GB at
    # the million-item catalogs this stage exists for. Chunked so the
    # [chunk, nlist] key block stays bounded too.
    c2 = (cents * cents).sum(axis=1)
    for lo in range(0, spill.size, 65_536):
        part = spill[lo : lo + 65_536]
        keys = c2[None, :] - 2.0 * (x[part] @ cents.T)
        prefs = np.argsort(keys, axis=1, kind="stable")
        for item, pref in zip(part, prefs):
            for c in pref:
                if counts[c] < cap:
                    assign[item] = c
                    counts[c] += 1
                    break
    return assign


def build_ivf(
    item_factors: np.ndarray,
    nlist: int = 0,
    seed: int = 0,
    iters: int = 8,
    balance: float = 1.3,
    quantize: bool = False,
) -> tuple[IVFIndex, dict]:
    """Partition ``item_factors [I, K]`` into ``nlist`` clusters and lay
    them out cluster-major. ``nlist <= 0`` picks :func:`auto_nlist`.
    Returns ``(index, build_info)`` — build_info feeds the query
    server's ``/stats.json`` ``ann`` section.

    ``balance`` caps every cluster at ``ceil(items / nlist * balance)``
    members (spill-to-nearest-with-room, :func:`_balance_assignment`),
    bounding the slab width — and with it both probe-stage gather bytes
    and padding waste — near the mean cluster size; ``balance <= 0``
    keeps the raw k-means assignment. The cap only moves BOUNDARY items
    (the ones farthest from an overloaded centroid), so recall impact is
    marginal, and the ``nprobe == nlist`` mode stays bit-identical to
    exact regardless (every slab is scored).

    The O(I*nlist*K) k-means runs jitted on the default backend; the
    final reorder is a single host argsort over the assignment (O(I log
    I) once per model generation, trivial next to the solve that
    produced the factors)."""
    t0 = time.perf_counter()
    x = np.ascontiguousarray(np.asarray(item_factors, dtype=np.float32))
    if x.ndim != 2:
        raise ValueError(f"item_factors must be [I, K], got {x.shape}")
    num_items, dim = x.shape
    if num_items == 0:
        raise ValueError("cannot build an IVF index over an empty catalog")
    nlist = int(nlist) if nlist > 0 else auto_nlist(num_items)
    nlist = max(1, min(nlist, num_items))

    xd = jnp.asarray(x)
    chunk = min(_ASSIGN_CHUNK, max(1, num_items))
    n_chunks = -(-num_items // chunk)
    x_pad = jnp.pad(xd, ((0, n_chunks * chunk - num_items), (0, 0))).reshape(
        n_chunks, chunk, dim
    )
    key = jax.random.PRNGKey(seed)
    if nlist == 1:
        cents = xd.mean(axis=0, keepdims=True)
    else:
        n_seed = min(
            num_items, max(_SEED_SAMPLE_MIN, _SEED_SAMPLE_PER_LIST * nlist)
        )
        if n_seed < num_items:
            key, ks = jax.random.split(key)
            sample = xd[jax.random.choice(
                ks, num_items, (n_seed,), replace=False
            )]
        else:
            sample = xd
        cents = _kmeans_pp(key, sample, nlist)
        cents = _lloyd(xd, x_pad, cents, max(0, int(iters)), num_items)
    # np.array (copy): the balancing pass mutates the assignment, and a
    # zero-copy view of a jax buffer is read-only
    assign = np.array(_final_assign(x_pad, cents, num_items))
    cents_np = np.asarray(cents)
    if balance and balance > 0:
        cap = max(1, int(np.ceil(num_items / nlist * balance)))
        assign = _balance_assignment(x, cents_np, assign, nlist, cap)

    counts = np.bincount(assign, minlength=nlist)
    slab_width = int(max(1, counts.max()))
    # cluster-major reorder; the stable sort keeps items in ascending id
    # order WITHIN each cluster, so the layout is deterministic
    order = np.argsort(assign, kind="stable")
    starts = np.concatenate(([0], np.cumsum(counts)[:-1]))
    lane = np.arange(num_items) - np.repeat(starts, counts)
    slab_ids = np.full((nlist, slab_width), num_items, dtype=np.int32)
    slab_ids[assign[order], lane] = order.astype(np.int32)
    slabs = np.zeros((nlist, slab_width, dim), dtype=np.float32)
    slabs[assign[order], lane] = x[order]

    if quantize:
        # int8 slab storage (--quantize int8): k-means and the reorder
        # above ran on the f32 values; only the SERVED layout quantizes
        # (per-lane codes + scales — ops/quant owns the rounding rule)
        from predictionio_tpu.ops import quant

        codes, lane_scales = quant.quantize_slabs(slabs)
        slab_arr = jnp.asarray(codes)
        scale_arr = jnp.asarray(lane_scales)
    else:
        slab_arr = jnp.asarray(slabs)
        scale_arr = None
    index = IVFIndex(
        centroids=jnp.asarray(cents_np),
        slabs=slab_arr,
        slab_ids=jnp.asarray(slab_ids),
        num_items=num_items,
        nlist=nlist,
        slab_width=slab_width,
        slab_scales=scale_arr,
    )
    info = {
        "nlist": nlist,
        "slabWidth": slab_width,
        "catalogItems": num_items,
        # fraction of slab rows holding real items — 1/fill is the
        # padding overhead the largest cluster imposes on the others
        "fill": round(num_items / float(nlist * slab_width), 4),
        "emptyClusters": int((counts == 0).sum()),
        "balance": float(balance),
        "kmeansIters": int(iters),
        "seed": int(seed),
        "quantized": bool(quantize),
        "bytesIndex": _index_bytes(index),
        "buildSeconds": round(time.perf_counter() - t0, 3),
    }
    return index, info


def _index_bytes(index: IVFIndex) -> int:
    """Real served bytes of the index arrays (dtype-honest: int8 slabs
    count 1 byte/element, their scales 4)."""
    total = (
        index.centroids.size * index.centroids.dtype.itemsize
        + index.slabs.size * index.slabs.dtype.itemsize
        + index.slab_ids.size * index.slab_ids.dtype.itemsize
    )
    if index.slab_scales is not None:
        total += index.slab_scales.size * index.slab_scales.dtype.itemsize
    return int(total)


# ---------------------------------------------------------------------------
# Incremental update: fold-in without a k-means rebuild
# ---------------------------------------------------------------------------


def _host_mirror(index: IVFIndex) -> dict:
    """Mutable host-side view of an index for incremental maintenance:
    numpy slab copies, per-cluster fill counts, and an item -> slab-slot
    map. Built once per index generation, reused across folds. For a
    quantized index the mirror keeps the int8 codes AND the per-lane
    scales — fold-ins then re-quantize only the touched lanes (delta
    cost, never a full-catalog requantization)."""
    slabs = np.array(index.slabs)  # f32 rows, or int8 codes (quantized)
    slab_ids = np.array(index.slab_ids, dtype=np.int32)
    cents = np.asarray(index.centroids, dtype=np.float32)
    sentinel = index.num_items
    pos = np.full(sentinel, -1, np.int64)
    cl, lane = np.nonzero(slab_ids != sentinel)
    pos[slab_ids[cl, lane]] = cl * index.slab_width + lane
    return {
        "slabs": slabs,
        "slab_ids": slab_ids,
        "scales": (
            np.array(index.slab_scales, dtype=np.float32)
            if index.slab_scales is not None
            else None
        ),
        "centroids": cents,
        "c2": (cents * cents).sum(axis=1),
        "fill": (slab_ids != sentinel).sum(axis=1).astype(np.int64),
        "pos": pos,
        "capacity": sentinel,
    }


def _grow_width(state: dict, extra: int) -> None:
    nlist, width, dim = state["slabs"].shape
    pad = max(1, extra, width // 4)
    slabs = np.zeros((nlist, width + pad, dim), state["slabs"].dtype)
    slabs[:, :width] = state["slabs"]
    ids = np.full((nlist, width + pad), state["capacity"], np.int32)
    ids[:, :width] = state["slab_ids"]
    if state.get("scales") is not None:
        scales = np.zeros((nlist, width + pad), np.float32)
        scales[:, :width] = state["scales"]
        state["scales"] = scales
    # re-derive positions: lane arithmetic changed with the width
    pos = np.full(state["capacity"], -1, np.int64)
    cl, lane = np.nonzero(ids != state["capacity"])
    pos[ids[cl, lane]] = cl * (width + pad) + lane
    state["slabs"] = slabs
    state["slab_ids"] = ids
    state["pos"] = pos


def update_ivf(
    index: IVFIndex,
    item_ids: np.ndarray,
    vectors: np.ndarray,
    total_items: int,
    state: dict | None = None,
) -> tuple[IVFIndex, dict, dict]:
    """Fold changed/new item vectors into an existing index WITHOUT a
    k-means rebuild (ROADMAP PR-6 follow-up): each vector is assigned to
    its nearest EXISTING centroid's slab, spilling to the nearest
    cluster with room when the target slab is full (and growing the slab
    width as a last resort). Centroids stay fixed — the point of
    fold-in is that per-update cost scales with the delta, not the
    catalog; a periodic full rebuild (every ``/reload``) re-learns them.

    * an item already in the index whose nearest centroid is unchanged
      updates its slab row in place;
    * an item that MOVED clusters is tombstoned out of its old slab
      (sentinel id, zero row) and re-inserted;
    * a new item (``id >= capacity``) grows the id capacity in
      :data:`_CAPACITY_STEP` jumps — capacity is the jit-static sentinel,
      so stepping it bounds retraces.

    ``state`` is the reusable host mirror from a previous call (pass the
    second return value back in); None builds it from ``index``. Returns
    ``(new index, state, info)``."""
    if state is None:
        state = _host_mirror(index)
    item_ids = np.asarray(item_ids, np.int64)
    vectors = np.ascontiguousarray(np.asarray(vectors, np.float32))
    old_capacity = state["capacity"]
    capacity = old_capacity
    if total_items > capacity:
        capacity = -(-total_items // _CAPACITY_STEP) * _CAPACITY_STEP
        # rewrite the sentinel: padding slots must track the new bound
        # (an item id equal to the OLD capacity is now a real id)
        pad_mask = state["slab_ids"] == old_capacity
        state["slab_ids"][pad_mask] = capacity
        pos = np.full(capacity, -1, np.int64)
        pos[: state["pos"].size] = state["pos"]
        state["pos"] = pos
        state["capacity"] = capacity
    slabs = state["slabs"]
    ids = state["slab_ids"]
    fill = state["fill"]
    pos = state["pos"]
    scales = state.get("scales")
    width = slabs.shape[1]
    if scales is not None:
        # quantized slabs: the mirror stores int8 codes + per-lane
        # scales, so only the TOUCHED lanes re-quantize on scatter —
        # the same delta-cost rule as the factor-table fold-in
        from predictionio_tpu.ops import quant

        lane_vals, lane_scales = quant.quantize_table_host(vectors)
    else:
        lane_vals, lane_scales = vectors, None

    def write_lane(cl, lane, j):
        slabs[cl, lane] = lane_vals[j]
        if scales is not None:
            scales[cl, lane] = lane_scales[j]

    # nearest-centroid preference order per changed item, via the GEMM
    # identity (||x||^2 is row-constant); the delta is small, so the
    # [M, nlist] block is cheap
    keys = state["c2"][None, :] - 2.0 * (vectors @ state["centroids"].T)
    prefs = np.argsort(keys, axis=1, kind="stable")
    moved = inserted = in_place = spilled = 0
    for j, (iid, pref) in enumerate(zip(item_ids.tolist(), prefs)):
        cur = pos[iid]
        target = int(pref[0])
        if cur >= 0:
            cl, lane = divmod(int(cur), width)
            if cl == target:
                write_lane(cl, lane, j)
                in_place += 1
                continue
            ids[cl, lane] = capacity  # tombstone out of the old slab
            slabs[cl, lane] = 0
            if scales is not None:
                scales[cl, lane] = 0.0
            fill[cl] -= 1
            pos[iid] = -1
            moved += 1
        else:
            inserted += 1
        placed = False
        for rank_i, c in enumerate(pref.tolist()):
            if fill[c] >= width:
                continue
            lane = int(np.argmax(ids[c] == capacity))
            ids[c, lane] = iid
            write_lane(c, lane, j)
            fill[c] += 1
            pos[iid] = c * width + lane
            spilled += int(rank_i > 0)
            placed = True
            break
        if not placed:  # every slab full: widen, then retry is trivial
            _grow_width(state, 1)
            slabs = state["slabs"]
            ids = state["slab_ids"]
            pos = state["pos"]
            scales = state.get("scales")
            width = slabs.shape[1]
            lane = int(np.argmax(ids[target] == capacity))
            ids[target, lane] = iid
            write_lane(target, lane, j)
            fill[target] += 1
            pos[iid] = target * width + lane
    new_index = IVFIndex(
        centroids=index.centroids,
        # copies, not views: on CPU backends jnp.asarray adopts aligned
        # numpy buffers zero-copy, and `state` mutates these arrays in
        # place on the NEXT update while in-flight queries may still be
        # scoring this index
        slabs=jnp.asarray(slabs.copy()),
        slab_ids=jnp.asarray(ids.copy()),
        num_items=capacity,
        nlist=index.nlist,
        slab_width=width,
        slab_scales=(
            jnp.asarray(scales.copy()) if scales is not None else None
        ),
    )
    info = {
        "inPlace": in_place,
        "moved": moved,
        "inserted": inserted,
        "spilled": spilled,
        "capacity": capacity,
        "slabWidth": width,
    }
    return new_index, state, info


# ---------------------------------------------------------------------------
# Sharded slabs: the --shard-factors composition (ROADMAP item-2 follow-up)
# ---------------------------------------------------------------------------


def _shard_index(index: IVFIndex, mesh) -> IVFIndex:
    """Lay an index's cluster-major slabs out sharded over the mesh's
    ``model`` axis: ``nlist`` pads to a multiple of the axis (sentinel
    slab ids, zero slabs — the sharded kernel masks padded clusters out
    of stage 1 by the TRUE ``nlist`` in the static metadata), slabs and
    slab ids shard cluster-major, centroids stay replicated (tiny).
    Per-device slab memory drops to ``nlist/S · W · K``."""
    from predictionio_tpu.parallel import sharding  # lazy: avoids a cycle

    from jax.sharding import NamedSharding, PartitionSpec

    S = int(mesh.shape[sharding.MODEL_AXIS])
    nlist_pad = -(-index.nlist // S) * S
    pad = nlist_pad - index.nlist
    cents = np.asarray(index.centroids, np.float32)
    # dtype preserved: int8 codes shard as int8 (the whole point)
    slabs = np.asarray(index.slabs)
    ids = np.asarray(index.slab_ids, np.int32)
    scales = (
        np.asarray(index.slab_scales, np.float32)
        if index.slab_scales is not None
        else None
    )
    if pad:
        cents = np.concatenate(
            [cents, np.zeros((pad, cents.shape[1]), np.float32)]
        )
        slabs = np.concatenate(
            [slabs, np.zeros((pad,) + slabs.shape[1:], slabs.dtype)]
        )
        ids = np.concatenate(
            [ids, np.full((pad, ids.shape[1]), index.num_items, np.int32)]
        )
        if scales is not None:
            scales = np.concatenate(
                [scales, np.zeros((pad, scales.shape[1]), np.float32)]
            )
    ax = sharding.MODEL_AXIS
    return IVFIndex(
        centroids=jnp.asarray(cents),
        slabs=jax.device_put(
            slabs, NamedSharding(mesh, PartitionSpec(ax, None, None))
        ),
        slab_ids=jax.device_put(
            ids, NamedSharding(mesh, PartitionSpec(ax, None))
        ),
        num_items=index.num_items,
        nlist=index.nlist,
        slab_width=index.slab_width,
        slab_scales=(
            jax.device_put(
                scales, NamedSharding(mesh, PartitionSpec(ax, None))
            )
            if scales is not None
            else None
        ),
    )


def shard_runtime(runtime: "AnnRuntime", mesh) -> dict:
    """Re-lay a runtime's index sharded over the serving mesh (``pio
    deploy --shard-factors --ann``). The UNPADDED index is kept on the
    runtime as ``host_index`` so incremental fold-ins
    (:meth:`AnnRuntime.update_items`) keep operating on the clean id
    space and re-shard only the updated layout; queries route through
    :func:`predictionio_tpu.parallel.sharding.sharded_ivf_topk` once
    ``shard_mesh`` is set. Returns the info-dict delta for
    ``/stats.json``."""
    with runtime._lock:
        index = runtime.index
    sharded = _shard_index(index, mesh)
    S = int(mesh.shape["model"])
    sharded_bytes = (
        sharded.slabs.size * sharded.slabs.dtype.itemsize
        + sharded.slab_ids.size * sharded.slab_ids.dtype.itemsize
    )
    if sharded.slab_scales is not None:
        sharded_bytes += (
            sharded.slab_scales.size * sharded.slab_scales.dtype.itemsize
        )
    delta = {
        "shards": S,
        "bytesIndexPerDevice": int(
            sharded.centroids.size * sharded.centroids.dtype.itemsize
            + sharded_bytes // S
        ),
    }
    with runtime._lock:
        runtime.host_index = index
        runtime.index = sharded
        runtime.shard_mesh = mesh
        runtime.build_info.update(delta)  # /stats.json ann section
    return delta


# ---------------------------------------------------------------------------
# Query: two-stage jitted retrieval
# ---------------------------------------------------------------------------


def _ivf_topk(
    qvecs: jax.Array, index: IVFIndex, k: int, nprobe: int
) -> tuple[jax.Array, jax.Array]:
    """Shared kernel body (trace-time ``k``/``nprobe``): score
    centroids, select clusters, score slabs, tie-stable global merge."""
    nlist, width = index.nlist, index.slab_width
    lane_scales = index.slab_scales  # not None => int8 slab codes
    nprobe = max(1, min(int(nprobe), nlist))
    if nprobe >= nlist:
        # every cluster probed: skip stage 1 and the gather entirely and
        # score the whole cluster-major table with ONE [B,K]@[K,n*W]
        # GEMM — the same dot shape as the exact path, which is what
        # makes this mode bit-identical to exact top-K (CI-asserted;
        # in int8 mode the claim is determinism over the DEQUANTIZED
        # table, the strongest statement a lossy layout admits)
        flat = index.slabs.reshape(nlist * width, -1)
        if lane_scales is not None:
            scores = jnp.matmul(
                qvecs, flat.T.astype(jnp.float32), precision=SCORE_PRECISION
            ) * lane_scales.reshape(1, nlist * width)
        else:
            scores = jnp.matmul(qvecs, flat.T, precision=SCORE_PRECISION)
        ids = jnp.broadcast_to(
            index.slab_ids.reshape(1, nlist * width), scores.shape
        )
    else:
        cent_scores = jnp.matmul(  # [B, nlist]
            qvecs, index.centroids.T, precision=SCORE_PRECISION
        )
        _, probe = jax.lax.top_k(cent_scores, nprobe)  # [B, nprobe]
        # one gather+einsum per probe SLOT (static nprobe unroll): the
        # [B, W, K] intermediates stay cache-sized, measured ~25% faster
        # on CPU than the single [B, nprobe, W, K] materialization
        score_l = []
        id_l = []
        for j in range(nprobe):
            sel = probe[:, j]
            cand = index.slabs[sel]  # [B, W, K] — int8: 1/4 the gather bytes
            if lane_scales is not None:
                # dequantize AFTER the dot: one f32 multiply per lane
                # instead of per element (measured faster on CPU, exact
                # same value up to f32 rounding)
                s_j = jnp.einsum(
                    "bwk,bk->bw", cand.astype(jnp.float32), qvecs,
                    precision=SCORE_PRECISION,
                ) * lane_scales[sel]
            else:
                s_j = jnp.einsum(
                    "bwk,bk->bw", cand, qvecs, precision=SCORE_PRECISION
                )
            score_l.append(s_j)
            id_l.append(index.slab_ids[sel])
        scores = jnp.concatenate(score_l, axis=1)  # [B, nprobe*W]
        ids = jnp.concatenate(id_l, axis=1)
    # padding rows are zero vectors (score 0.0, which could outrank real
    # negative scores) — mask by the id sentinel, not by value
    scores = jnp.where(ids < index.num_items, scores, -jnp.inf)
    k = max(1, min(int(k), scores.shape[-1]))
    # item ids below 2^24 are exact in f32, unlocking the fast f32-keyed
    # merge; huge catalogs keep exactness via the sort-based path
    return top_k_permuted(scores, ids, k, big_ids=index.num_items >= (1 << 24))


@functools.partial(jax.jit, static_argnames=("k", "nprobe"))
def ivf_topk_batch(
    qvecs: jax.Array, index: IVFIndex, k: int, nprobe: int
) -> tuple[jax.Array, jax.Array]:
    """Approximate top-k for a batch of query VECTORS ``[B, K]``:
    ``([B, k] item ids, [B, k] scores)``, descending score, ties by
    ascending item id. Rows whose probed clusters hold fewer than ``k``
    real items carry the ``num_items`` sentinel (score ``-inf``) in the
    tail — callers drop it host-side (:func:`trim_row`)."""
    return _ivf_topk(qvecs, index, k, nprobe)


@functools.partial(jax.jit, static_argnames=("k", "nprobe"))
def ivf_topk_users(
    user_idx: jax.Array,
    user_factors: jax.Array,
    index: IVFIndex,
    k: int,
    nprobe: int,
) -> tuple[jax.Array, jax.Array]:
    """Approximate top-k for a batch of USERS: gather the user rows on
    device, then the two-stage kernel — the ANN counterpart of
    :func:`predictionio_tpu.ops.als.top_k_items_batch`, one dispatch per
    chunk."""
    return _ivf_topk(user_factors[user_idx], index, k, nprobe)


def trim_row(ids: np.ndarray, scores: np.ndarray, num_items: int):
    """Drop sentinel padding from one result row; returns plain lists."""
    keep = ids < num_items
    return ids[keep].tolist(), scores[keep].tolist()


class AnnRuntime:
    """Per-model serving state: the index, the deploy-time ``nprobe``,
    build info, and thread-safe query counters for ``/stats.json``.

    Held as ``ann`` of the model's ``templates.retrieval.ServingState``
    by the algorithm's ``build_ann_for_serving`` hook (driven by
    :mod:`predictionio_tpu.workflow.device_state` at (re)load), detached
    by ``release_ann_state`` when the generation is superseded."""

    def __init__(self, index: IVFIndex, nprobe: int, build_info: dict):
        self.index = index
        self.nprobe = max(1, min(int(nprobe), index.nlist))
        self.build_info = dict(build_info)
        self._lock = threading.Lock()
        self.queries = 0
        self.clusters_scored = 0
        self.candidates_scored = 0
        #: incremental-maintenance host mirror (built on first update)
        self._update_state: dict | None = None
        self.incremental_updates = 0
        self.items_folded = 0
        #: --shard-factors state (see :func:`shard_runtime`): when set,
        #: ``index`` holds the PADDED sharded layout queries run on and
        #: ``host_index`` the unpadded one fold-ins update
        self.shard_mesh = None
        self.host_index: IVFIndex | None = None

    def update_items(
        self, item_ids: np.ndarray, vectors: np.ndarray, total_items: int
    ) -> dict:
        """Fold changed/new item vectors into the live index — nearest-
        centroid slab assignment with spill, no k-means rebuild (see
        :func:`update_ivf`). Swaps ``self.index`` atomically; in-flight
        queries that already snapshotted the old index finish against
        it consistently."""
        with self._lock:
            state = self._update_state
            mesh = self.shard_mesh
            index = self.host_index if mesh is not None else self.index
        new_index, state, info = update_ivf(
            index, item_ids, vectors, total_items, state
        )
        # sharded serving: the fold updates the clean unpadded layout,
        # then the whole (delta-sized rebuilt) layout re-shards — queries
        # snapshotting the old sharded index finish against it
        new_sharded = (
            _shard_index(new_index, mesh) if mesh is not None else None
        )
        with self._lock:
            if mesh is not None:
                self.host_index = new_index
                self.index = new_sharded
            else:
                self.index = new_index
            self._update_state = state
            self.incremental_updates += 1
            self.items_folded += len(np.asarray(item_ids))
        return info

    def note_queries(self, n: int) -> None:
        """Account ``n`` queries' worth of scored clusters/candidates."""
        probed = self.nprobe
        if probed >= self.index.nlist:
            candidates = self.index.num_items  # exact-equivalent mode
        else:
            candidates = probed * self.index.slab_width
        with self._lock:
            self.queries += n
            self.clusters_scored += n * probed
            self.candidates_scored += n * candidates

    def stats_json(self) -> dict:
        with self._lock:
            q = self.queries
            clusters = self.clusters_scored
            candidates = self.candidates_scored
        total = q * self.index.num_items
        with self._lock:
            inc = self.incremental_updates
            folded = self.items_folded
        out = {
            "nprobe": self.nprobe,
            "queries": q,
            "clustersScored": clusters,
            "candidatesScored": candidates,
            "incrementalUpdates": inc,
            "itemsFolded": folded,
            # the headline number: what fraction of the catalog each
            # query paid for, vs 1.0 on the exact path
            "fractionOfCatalogScored": (
                round(candidates / total, 4) if total else 0.0
            ),
        }
        out.update(self.build_info)
        return out


def query_topk(
    runtime: AnnRuntime, qvec: np.ndarray, k: int
) -> tuple[list, list]:
    """Single-query retrieval through the index: top-``k`` as
    ``(item id list, score list)`` with sentinel padding trimmed.
    Callers applying blacklist/seen filters must OVER-FETCH here —
    ``k = wanted + len(excluded)`` — and drop excluded ids from the
    returned (longer) list, so the final result still holds ``wanted``
    items (see module docstring). ``k`` is bucketed to a power of two
    (floor 16) so the jitted kernel compiles once per bucket, exactly
    like the exact path's ``chunked_topk``."""
    index = runtime.index
    k = min(int(k), index.num_items)
    if k <= 0:
        return [], []
    kb = bucket_k(k, index.num_items)
    q = jnp.asarray(np.asarray(qvec, dtype=np.float32)[None, :])
    if runtime.shard_mesh is not None:
        from predictionio_tpu.parallel import sharding

        ids, scores = sharding.sharded_ivf_topk(
            q, index, kb, runtime.nprobe, runtime.shard_mesh
        )
    else:
        ids, scores = ivf_topk_batch(q, index, kb, runtime.nprobe)
    runtime.note_queries(1)
    ids_l, scores_l = trim_row(
        np.asarray(ids)[0], np.asarray(scores)[0], index.num_items
    )
    return ids_l[:k], scores_l[:k]
