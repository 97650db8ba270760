"""Batched ALS fold-in — re-solve only the touched rows.

The classic MLlib-era incremental update (PAPERS.md, "MLlib: Machine
Learning in Apache Spark"): with the opposite-side factors ``Y`` held
FIXED, the least-squares optimum for one row is independent of every
other row, so fresh events only require re-solving the rows they
touched::

    x_u = argmin_x  ||r_u - Y_u x||^2  +  reg * n_u * ||x||^2
                    +  prior_weight * ||x - x_old||^2

The ``prior_weight`` anchor keeps a row near its trained optimum while
its *online-observed* history is still thin (the follower only sees
events since deploy, not the training set); as online ratings
accumulate the data term dominates and the solve converges to the pure
fold-in. Cold-start rows (entities the model has never seen) use
``x_old = 0`` with no anchor — exactly the textbook fold-in of a new
user/item from its first events.

The kernel is one jitted program per (batch, width) bucket: gather the
rated opposite rows, form the normal equations with masked einsums, add
the ALS-WR ridge (``reg * max(n,1)`` — the same scaling ``ops.als``
trains with, so fold-in and retrain agree on the objective), and solve
with the shared SPD solver. Batch and width pad to powers of two so
live traffic compiles a handful of programs, then re-traces nothing —
the same bucketing discipline as the serving top-K. Implicit-feedback
models add the ``YtY`` Gramian and confidence weights (MLlib
``implicitPrefs`` fold-in); the caller supplies ``yty`` once per model
generation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from predictionio_tpu.parallel.sharding import take_rows

__all__ = ["foldin_rows", "gram_yty"]

#: floor for the padded per-row rating width buckets
_MIN_BUCKET = 8
#: widest per-row rating window the kernel solves; heavier histories
#: keep their most recent entries (a bounded window is also what keeps
#: one fold's latency flat as an entity's online history grows)
MAX_WIDTH = 512
#: FIXED solve-batch shape: every call chunks its rows into batches of
#: exactly this many (padded), so the kernel compiles ONCE per width
#: bucket instead of once per distinct touched-row count — per-fold
#: retraces were measured to dominate fold latency (and bleed into
#: serving p99 through CPU contention) when the batch dimension floated
B_CHUNK = 128


def _bucket(n: int, floor: int = _MIN_BUCKET) -> int:
    return max(floor, 1 << (max(1, n) - 1).bit_length())


@functools.partial(jax.jit, static_argnames=("implicit",))
def _foldin_kernel(
    Yg: jax.Array,  # [B, L, K] PRE-GATHERED opposite rows (see below)
    val: jax.Array,  # [B, L] f32 ratings
    mask: jax.Array,  # [B, L] f32 1=real
    prior: jax.Array,  # [B, K] f32 anchor rows (0 for cold starts)
    prior_w: jax.Array,  # [B] f32 per-row anchor strength
    reg: jax.Array,  # scalar f32
    alpha: jax.Array,  # scalar f32 (implicit confidence slope)
    yty: jax.Array,  # [K, K] (zeros when explicit)
    implicit: bool,
) -> jax.Array:
    """Solve the anchored normal equations for ``B`` rows at once.

    The gather happens OUTSIDE this jit on purpose: cold-start
    injections grow the factor tables every few folds, and a kernel
    traced against the table would re-compile on every growth — the
    pre-gathered ``[B, L, K]`` operand keeps the trace shape-stable
    regardless of catalog size."""
    Yg = Yg * mask[..., None]  # masked rows zero out
    n = mask.sum(axis=-1)  # [B]
    K = Yg.shape[-1]
    eye = jnp.eye(K, dtype=Yg.dtype)
    if implicit:
        # MLlib implicit fold-in: A = YtY + alpha * sum r y y^T,
        # b = sum (1 + alpha r) y  (preference 1 for every observed pair)
        A = jnp.einsum("blk,blj,bl->bkj", Yg, Yg, alpha * val)
        A = A + yty[None]
        b = jnp.einsum("blk,bl->bk", Yg, (1.0 + alpha * val) * mask)
    else:
        A = jnp.einsum("blk,blj->bkj", Yg, Yg)
        b = jnp.einsum("blk,bl->bk", Yg, val * mask)
    ridge = reg * jnp.maximum(n, 1.0) + prior_w  # ALS-WR + anchor
    A = A + ridge[:, None, None] * eye
    b = b + prior_w[:, None] * prior
    from predictionio_tpu.ops.solve import cholesky_solve

    return cholesky_solve(A, b)


def gram_yty(opposite) -> np.ndarray:
    """``Y^T Y`` of the opposite factors — computed once per model
    generation by implicit-model callers."""
    Y = np.asarray(opposite, dtype=np.float32)
    return Y.T @ Y


def foldin_rows(
    opposite,
    entries: list[tuple[list[int], list[float]]],
    reg: float,
    priors: np.ndarray | None = None,
    prior_weights: np.ndarray | None = None,
    implicit: bool = False,
    alpha: float = 1.0,
    yty: np.ndarray | None = None,
) -> np.ndarray:
    """Re-solve a batch of rows against fixed ``opposite`` factors.

    ``entries[i] = (opposite row indices, ratings)`` is row ``i``'s full
    online-observed history (rows beyond :data:`MAX_WIDTH` keep their
    most recent entries — callers append chronologically). ``priors``
    [B, K] / ``prior_weights`` [B] anchor each solve to its previous row
    (omit or pass weight 0 for pure fold-in / cold starts). Returns the
    solved rows ``[B, K]`` float32.

    The batch dimension is FIXED at :data:`B_CHUNK` (larger batches run
    several chunks) and the width pads to a power-of-two bucket, so the
    jitted kernel compiles once per width bucket and steady-state folds
    re-trace nothing; padding rows solve a trivial identity system and
    are dropped before returning."""
    Y = opposite
    B = len(entries)
    K = int(Y.shape[1])
    if B == 0:
        return np.zeros((0, K), np.float32)
    width = min(MAX_WIDTH, max(len(ix) for ix, _ in entries))
    L = _bucket(width)
    yty_arr = jnp.asarray(
        np.zeros((K, K), np.float32)
        if yty is None
        else np.asarray(yty, np.float32)
    )
    out_parts = []
    for lo in range(0, B, B_CHUNK):
        part = entries[lo : lo + B_CHUNK]
        n = len(part)
        idx = np.zeros((B_CHUNK, L), np.int32)
        val = np.zeros((B_CHUNK, L), np.float32)
        mask = np.zeros((B_CHUNK, L), np.float32)
        for i, (ix, vs) in enumerate(part):
            if len(ix) > L:  # keep the most recent window
                ix, vs = ix[-L:], vs[-L:]
            m = len(ix)
            if m == 0:
                continue
            idx[i, :m] = ix
            val[i, :m] = vs
            mask[i, :m] = 1.0
        pr = np.zeros((B_CHUNK, K), np.float32)
        pw = np.zeros(B_CHUNK, np.float32)
        if priors is not None:
            pr[:n] = np.asarray(priors, np.float32)[lo : lo + B_CHUNK]
        if prior_weights is not None:
            pw[:n] = np.asarray(prior_weights, np.float32)[lo : lo + B_CHUNK]
        # gather OUTSIDE the jit (host fancy-index, or an eager device
        # gather for pinned/sharded tables): the kernel's trace must not
        # depend on the catalog size, which cold-start injections keep
        # growing
        Yg = jnp.asarray(
            take_rows(Y, idx.reshape(-1)).reshape(B_CHUNK, L, K),
            jnp.float32,
        )
        out = _foldin_kernel(
            Yg,
            jnp.asarray(val),
            jnp.asarray(mask),
            jnp.asarray(pr),
            jnp.asarray(pw),
            jnp.float32(reg),
            jnp.float32(alpha),
            yty_arr,
            implicit,
        )
        out_parts.append(np.asarray(out)[:n])
    return np.concatenate(out_parts, axis=0)
