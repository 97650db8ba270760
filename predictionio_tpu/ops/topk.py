"""Shared top-K selection kernels — one tie-break rule everywhere.

Every ranked surface in the product (recommendation / similarproduct /
twotower / ecommerce serving, ``pio batchpredict``, the IVF retrieval
merge) must order candidates identically, or the exact and approximate
paths diverge on tied scores and host/device results stop being
comparable. The rule is the one ``jax.lax.top_k`` implements natively:

    **descending score, ties broken by ascending item index.**

Three entry points share it:

* :func:`top_k_scores` — jitted :func:`select_top_k` over
  naturally-ordered scores (the exact device path). ``select_top_k``
  returns what ``lax.top_k`` returns, bit for bit (ties -> ascending
  position is the operator's own guarantee); over a wide row it reaches
  it in two stages, from the maxima of contiguous blocks, because the
  chip's ``lax.top_k`` reads a tenth as fast as a max-reduction does.
  :func:`select_plan` names which of the two a shape gets.
* :func:`top_k_permuted` — jitted tie-stable top-K when the score axis
  is NOT in item-id order (the IVF cluster-major merge): a two-key
  ``lax.sort`` on ``(-score, id)`` reproduces the exact rule in the
  *original id space*, which is what makes ``nprobe == nlist`` IVF
  bit-identical to exact retrieval including tie order.
* :func:`top_k_host` — the numpy mirror (argpartition + lexsort) used by
  the host serving paths, so host and device agree wherever the float
  scores do.

Before this module each template carried its own argsort-based variant;
similarproduct/ecommerce used ``argsort(...)[::-1]``, whose reversal
orders TIES by descending index — silently different from every other
path. Hoisting the helper is what fixed that.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "SCORE_PRECISION",
    "bucket_k",
    "bucket_rows",
    "bucket_width",
    "NO_ITEM",
    "SELECT_BLOCK",
    "select_plan",
    "select_top_k",
    "top_k_scores",
    "top_k_permuted",
    "sort_merge_topk",
    "top_k_host",
]

#: matmul precision of every float32 serving score. XLA:CPU multiplies
#: float32 exactly whatever this says, which is where the "identical to
#: the host path" contracts of docs/serving.md were established; a TPU's
#: default is one bf16 pass. Measured on a v5e (PERF.md, PR 21): the batch
#: GEMM [2048,64]@[64,27027] is off by 1.2e-2 against numpy float32 at
#: the default and by 1.9e-6 at HIGHEST — the first reorders near-ties,
#: so every scoring program states the second, as training already does
#: (``ALSConfig.precision = "highest"``).
SCORE_PRECISION = jax.lax.Precision.HIGHEST


def bucket_k(k: int, n_items: int, floor: int = 16) -> int:
    """The ONE pow2 fetch-size bucket every serving tier shares: ``k``
    rounds up to a power of two (``floor`` minimum), capped at the
    catalog. Jitted kernels take the bucketed value as their static
    ``k`` so the compile count is the bucket count, never the request
    cardinality — piolint PIO306 recognizes this helper (its name
    contains "bucket") and ``compile-budget.json``'s entries cite its
    math; changing the floor or rounding here moves every tier's bucket
    set at once instead of drifting per copy."""
    return min(int(n_items), max(floor, 1 << (max(1, int(k)) - 1).bit_length()))


def bucket_rows(n: int, cap: int, floor: int = 8) -> int:
    """The ONE row bucket of every batch scoring program: ``n`` query
    rows round up to a power of two, not under ``floor`` and not over
    ``cap``. A float32 tile has 8 sublanes, so fewer than 8 rows save
    nothing on the chip and only add shapes to compile at boot; ``cap``
    (``serving_util.TOPK_CHUNK``) is the most rows one dispatch may
    score, which bounds the ``[rows, items]`` score matrix. A program's
    row count is an array extent, so it keys the jit cache like
    ``bucket_k``'s static ``k`` does: at most ``log2(cap / floor) + 1``
    shapes ever exist (piolint PIO306 knows this helper by the "bucket"
    in its name)."""
    return min(int(cap), max(floor, 1 << (max(1, int(n)) - 1).bit_length()))


def bucket_width(n: int, floor: int) -> int:
    """The pow2 bucket of a list's padded WIDTH (a tile's excluded pairs
    and a row's wanted categories of a filtered top-K): ``n`` rounds up to
    a power of two, not under ``floor``. The width is an array extent of
    the program, so it keys the jit cache as ``bucket_rows`` does (piolint
    PIO306 knows a bucket step by the "bucket" in its name)."""
    return max(int(floor), 1 << (max(1, int(n)) - 1).bit_length())


#: the id of a result slot that holds no item (its score is ``-inf``): past
#: any catalog, so ``serving_util._drain_staged`` trims it, and the padding
#: of an excluded-id list, which ``ops.als.tile_pairs`` drops
NO_ITEM = np.iinfo(np.int32).max


def sort_merge_topk(
    scores: jax.Array, ids: jax.Array, k: int
) -> tuple[jax.Array, jax.Array]:
    """Tie-stable top-k of an (unordered) candidate list via ONE two-key
    ``lax.sort`` on ``(-score, id)`` — exact for ANY id width, at
    O(n log n) per row. This is :func:`top_k_permuted`'s ``big_ids``
    branch, shared as the cross-shard candidate reduce of the sharded
    serving kernels (``parallel/sharding.py``): there the candidate list
    is only ``S·k`` wide, so the sort is negligible — and the
    barrier-guarded fast path must not run, because XLA:CPU's
    TopkDecomposer aborts on a barrier-fed ``top_k`` under manual
    partitioning (shard_map). Not jitted standalone: it only ever runs
    inside an already-traced kernel."""
    neg, sid = jax.lax.sort((-scores, ids), num_keys=2)
    return sid[..., :k], -neg[..., :k]


#: columns of one block of :func:`select_top_k`'s first stage: one lane
#: row of a float32 tile, so a block's maximum is a reduction inside one
#: vector register and a block is one row of the gather's table
SELECT_BLOCK = 128

#: :func:`select_plan`'s bounds, each from a timing or a reading on a v5e
#: (PERF.md section 6, PR 29). The ``k`` candidate blocks must be this
#: many times narrower than the row (at the least shape that leaves, 8
#: rows of 32,768 columns at k 16, the stages' handful of small device
#: operations cost what ``lax.top_k`` takes for the whole row). ``k`` is
#: at most this, and the rows whole tiles of 8: the chip's ``lax.top_k``
#: breaks ties by ascending position only at some shapes, and at 256 and
#: over, or over one row of candidates, it does not. A width that is no
#: multiple of the block is padded into a second copy of the scores,
#: which may hold no more than this many (1 GiB: a full ``pio
#: batchpredict`` chunk would else hold 5.1 GB twice)
_SELECT_MIN_RATIO = 16
_SELECT_MAX_K = 128
_SELECT_MAX_PADDED = 1 << 28

#: the most positions :func:`select_top_k` strikes from the scores in one
#: scatter; a longer ``drop`` list is a whole number of such chunks. From
#: 1,024 updates on the chip's compiler flattens the scatter's operand,
#: which relays a 64 MB tile of scores twice: 23.4 ms a batch of 30 tiles
#: at 1,024 in one piece against 16.8 in chunks (v5e, PERF.md, PR 36)
_DROP_CHUNK = 512


def select_plan(rows: int, width: int, k: int) -> str:
    """Which selection :func:`select_top_k` builds for ``rows`` score
    rows of ``width`` columns at a static ``k``: ``"blocked"`` (two
    stages, from block maxima) or ``"plain"`` (``lax.top_k`` itself).
    A pure function of the shape, so host code that knows a dispatch's
    shape can count its plan (``/stats.json`` ``batcher.select``)."""
    blocked = (
        k <= _SELECT_MAX_K
        and rows % 8 == 0
        and width >= _SELECT_MIN_RATIO * k * SELECT_BLOCK
        and (width % SELECT_BLOCK == 0 or rows * width <= _SELECT_MAX_PADDED)
    )
    return "blocked" if blocked else "plain"


def select_top_k(
    scores: jax.Array, k: int, drop: tuple[jax.Array, jax.Array] | None = None
) -> tuple[jax.Array, jax.Array]:
    """``jax.lax.top_k(scores, k)``, values and positions bit for bit,
    ties included — by :func:`select_plan`'s ``"blocked"`` plan where
    the row is wide:

    1. cut the last axis into CONTIGUOUS blocks of ``SELECT_BLOCK``
       columns (a ragged end padded with ``-inf``) and take each block's
       maximum;
    2. ``lax.top_k`` over the maxima picks ``k`` blocks; their indices
       are sorted ascending;
    3. those blocks' columns are gathered in that order, so a
       candidate's place is monotone in its column, and ``lax.top_k``
       over the ``k * SELECT_BLOCK`` candidates picks the answer.

    Exact because a top-k element in a block not picked would have ``k``
    blocks ranked before its own (maximum descending, index ascending),
    each holding an element that is larger, or equal at a lower column
    since blocks are contiguous: ``k`` elements outrank it. Selection
    does no arithmetic, so values are the input's own bits.

    The scores are read where they lie: a ``[rows, width]`` float32
    array is stored on the chip in tiles of 8 rows by 128 columns, so
    (``rows`` being a multiple of 8 under the plan) its own bytes are
    ``[row group, block, row in group, lane]``. Both views below say so:
    the maxima reduce the lanes of that order and the gather takes
    ``rows * k`` rows of it as a table of blocks, where a plain
    ``reshape(rows, blocks, 128)`` copies the whole array first and a
    ``lax.reduce_window`` reads it several times slower (compiled for
    and timed on a v5e: PERF.md section 6, PR 29). Not a jitted program
    of its own: it only ever runs inside a caller's trace, as
    :func:`sort_merge_topk`.

    ``drop`` ``(i32[P] row, i32[P] column)`` names positions of the
    (float) scores to leave out: the result is ``lax.top_k``'s of the
    scores with those at ``-inf``, at a cost that follows ``P`` and not
    the width. A column past the row pads the list and is discarded; a
    position named twice is harmless; ``P`` over ``_DROP_CHUNK`` is a
    multiple of it (a ``bucket_width`` bucket is). Under the ``"blocked"`` plan the
    maxima are still taken from the scores as their producer wrote them
    (so they fuse into it), then mended: the ``P`` positions go to
    ``-inf`` where the scores lie, the ``P`` blocks that hold them are
    gathered, and their maxima, taken anew, replace the stale ones
    (``_DROP_CHUNK`` positions at a time)."""
    *lead, width = scores.shape
    rows = math.prod(lead)
    if select_plan(rows, width, k) == "plain" or not jnp.issubdtype(
        scores.dtype, jnp.floating
    ):
        if drop is not None:
            scores = scores.reshape(rows, width).at[drop].set(
                -jnp.inf, mode="drop").reshape(scores.shape)
        return jax.lax.top_k(scores, k)
    b = SELECT_BLOCK
    nb = -(-width // b)
    s = scores.reshape(rows, width)
    if nb * b != width:
        low = jnp.array(-jnp.inf, scores.dtype)
        s = jax.lax.pad(s, low, ((0, 0, 0), (0, nb * b - width, 0)))
    tiles = s.reshape(rows // 8, 8, nb, b)
    maxima = tiles.max(axis=-1).reshape(rows, nb)

    def block_table(tiles, row, block):
        """The scores as a table of blocks, and where ``block`` of ``row``
        lies in it."""
        at = ((row // 8) * nb + block) * 8 + row % 8
        return tiles.transpose(0, 2, 1, 3).reshape(rows * nb, b), at

    if drop is not None:

        def strike(held, pairs):
            s, maxima = held
            drop_row, drop_col = pairs
            s = s.at[drop_row, drop_col].set(-jnp.inf, mode="drop")
            table, at = block_table(
                s.reshape(rows // 8, 8, nb, b), drop_row, drop_col // b)
            mended = table.at[at].get(mode="clip").max(axis=-1)
            return s, maxima.at[drop_row, drop_col // b].set(mended, mode="drop")

        # one form whatever the list's length: a scan over its chunks (a
        # list of one chunk compiles to the chunk's body alone)
        chunk = min(drop[0].shape[0], _DROP_CHUNK)
        if drop[0].shape[0] % chunk:
            raise ValueError(
                f"a drop list of {drop[0].shape[0]} positions is no whole "
                f"number of chunks of {_DROP_CHUNK}"
            )
        (s, maxima), _ = jax.lax.scan(
            lambda held, pairs: (strike(held, pairs), None),
            (s, maxima),
            tuple(a.reshape(-1, chunk) for a in drop),
        )
        tiles = s.reshape(rows // 8, 8, nb, b)
    blocks = jnp.sort(jax.lax.top_k(maxima, k)[1], axis=-1)
    row = jnp.arange(rows, dtype=jnp.int32)[:, None]
    table, at = block_table(tiles, row, blocks)
    candidates = table.at[at.reshape(-1)].get(mode="promise_in_bounds")
    values, place = jax.lax.top_k(candidates.reshape(rows, k * b), k)
    positions = jnp.take_along_axis(blocks, place // b, axis=-1) * b + place % b
    return values.reshape(*lead, k), positions.reshape(*lead, k)


@functools.partial(jax.jit, static_argnames=("k",))
def top_k_scores(scores: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """Top-k of ``scores`` along the last axis: ``(indices, values)``.
    Ties break toward the lower index (``lax.top_k``'s contract, which
    :func:`select_top_k` keeps whichever plan the shape gets)."""
    with jax.named_scope("pio_topk_select"):
        values, indices = select_top_k(scores, k)
    return indices, values


@functools.partial(jax.jit, static_argnames=("k", "big_ids"))
def top_k_permuted(
    scores: jax.Array, ids: jax.Array, k: int, big_ids: bool = False
) -> tuple[jax.Array, jax.Array]:
    """Tie-stable top-k when position != item id: ``scores[..., n]``
    belongs to item ``ids[..., n]`` (any permutation/padding of the id
    space). Returns ``(ids [..., k], scores [..., k])`` ordered by
    descending score, ties by ascending id — the same ranking
    :func:`top_k_scores` produces on the naturally-ordered axis, which
    is what lets the IVF merge reproduce exact top-K bit-identically
    when every cluster is probed.

    A plain ``lax.top_k`` on the scores would break ties by *candidate
    position* (cluster-major order, not id order), and a full two-key
    ``lax.sort`` is O(n log n) per row — measured SLOWER than exact
    full-catalog scoring on CPU at bench shapes (and any non-trivial
    selection pass misses XLA:CPU's fast f32 TopK path by ~10-20x). The
    hot path therefore runs exactly ONE fast f32 ``top_k`` plus an
    O(k log k) sort, and the expensive exact-tie machinery hides behind
    a ``lax.cond`` that only executes when ties actually bite:

    1. ``lax.top_k`` over the (f32) scores selects by exact float order
       — but resolves ties by position.
    2. Position-ties only pick the wrong CANDIDATE SET when ties at the
       k-th-value boundary straddle it (ties strictly above select both
       members either way). A cheap reduce detects that — equality of
       the tied-at-boundary counts inside and across the whole row —
       and the repair branch runs ONLY then: a second ``top_k`` over
       ``-id`` (masked to boundary-tied candidates; ids are exact in
       f32 below 2^24) yields the tied candidates in ascending-id
       order, and pass 1's tie slots are reassigned from it.
    3. The k winners (gathered ids + original scores, bit-exact) are
       ordered by a two-key sort on ``(-score, id)`` — k elements per
       row, negligible next to the selection.

    ``big_ids=True`` (required when ids can reach 2^24, where f32
    spacing exceeds 1) keeps exactness through a full two-key sort —
    correct for any id, at the O(n log n) cost."""
    if big_ids:
        return sort_merge_topk(scores, ids, k)
    t, pos = jax.lax.top_k(scores, k)
    # the barrier keeps downstream slices/compares out of the top_k's
    # fusion: XLA:CPU's fast TopK rewrite bails when the sort's results
    # are consumed by a fused slice, silently falling back to a ~10x
    # slower generic sort (measured; same story for the repair branch)
    t, pos = jax.lax.optimization_barrier((t, pos))
    kth = t[..., -1:]

    def repair(_):
        is_strict = t > kth
        tie_key = jnp.where(scores == kth, -ids.astype(scores.dtype), -jnp.inf)
        tie_pos = jax.lax.optimization_barrier(jax.lax.top_k(tie_key, k))[1]
        # the j-th non-strict slot takes the j-th smallest-id boundary tie
        tie_rank = jnp.cumsum((~is_strict).astype(jnp.int32), axis=-1) - 1
        return jnp.where(
            is_strict,
            pos,
            jnp.take_along_axis(tie_pos, jnp.maximum(tie_rank, 0), axis=-1),
        )

    boundary_ties_bite = jnp.any(
        jnp.sum(scores == kth, axis=-1) > jnp.sum(t == kth, axis=-1)
    )
    final_pos = jax.lax.cond(boundary_ties_bite, repair, lambda _: pos, None)
    sel_ids = jnp.take_along_axis(ids, final_pos, axis=-1)
    sel_scores = jnp.take_along_axis(scores, final_pos, axis=-1)
    neg, out_ids = jax.lax.sort((-sel_scores, sel_ids), num_keys=2)
    return out_ids, -neg


def top_k_host(scores: np.ndarray, k: int) -> tuple[np.ndarray, np.ndarray]:
    """Numpy top-k over the last axis of a 1-D or 2-D score array with
    the shared tie rule; returns ``(indices, values)``. ``argpartition``
    keeps it O(n + k log k) per row — the host serving path at catalog
    sizes below ~10^6 items."""
    k = min(int(k), scores.shape[-1])
    if k <= 0:
        shape = scores.shape[:-1] + (0,)
        return np.zeros(shape, np.int64), np.zeros(shape, scores.dtype)
    if scores.ndim == 1:
        part = np.argpartition(scores, -k)[-k:]
        top = part[np.lexsort((part, -scores[part]))]
        return top, scores[top]
    part = np.argpartition(scores, -k, axis=-1)[..., -k:]
    vals = np.take_along_axis(scores, part, axis=-1)
    # per-row lexsort: primary key descending value, secondary ascending
    # original index — np.lexsort's last key is primary
    order = np.lexsort((part, -vals), axis=-1)
    top = np.take_along_axis(part, order, axis=-1)
    return top, np.take_along_axis(scores, top, axis=-1)
