"""Batched SPD solves — the ALS hot op, as a Pallas TPU kernel.

XLA's ``lax.linalg.cholesky`` lowers a batched [B,K,K] factorization to a
K-step sequential loop whose every step round-trips the whole batch
through HBM; at the flagship bench shape ([138k,64,64]) that measures
~1.26 s/solve on a v5e chip — more than a whole ALS sweep with the
kernel. The kernel here
keeps each block of rows **resident in VMEM** and runs *blocked*
Gauss-Jordan elimination vectorized across the batch: pivot blocks of
P=8 columns are inverted with a tiny unrolled in-VMEM GJ, and the rank-P
updates run as batched MXU ``dot_general``s at full f32 precision.
Measured on a v5e (PERF.md, PR 21): 322 ms vs 1260 ms for the XLA
Cholesky at the bench shape (~3.9x), max rel err 1.5e-6 between them.

Gauss-Jordan without pivoting is numerically safe here: every ALS normal
matrix is SPD with an ALS-WR ridge (λ·max(n,1)·I), so diagonal pivots
stay bounded away from zero.

No reference analog — MLlib solves on CPU LAPACK
(``org.apache.spark.ml.recommendation.ALS`` CholeskySolver); this is the
TPU-native replacement for that hot path.
"""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["spd_solve", "gj_solve_pallas", "cholesky_solve", "pallas_rank_ok"]

logger = logging.getLogger(__name__)

#: max rows per kernel block (see _auto_block_rows). Measured standalone
#: at [138000, 64, 64] on a v5e (jax 0.9.0 / libtpu 0.0.34; PERF.md,
#: PR 21): 8 rows 446 ms, 16 rows 323 ms, 32 rows 322 ms, 48 rows 272 ms,
#: while the Mosaic compile grows with the block (6 s, 13 s, 33 s, 65 s —
#: the body unrolls over rows). 32 is kept: 48 buys 15% for twice the
#: compile, and an earlier toolchain measured its ~13 MB footprint
#: slowing the surrounding gather/einsum pipeline inside the sweep.
_BLOCK_ROWS = 32

#: budget for the kernel's whole working set, under the 16 MiB scoped
#: VMEM this libtpu gives a kernel by default (no pallas_call here
#: raises it with compiler_params).
_VMEM_BUDGET = 14 << 20

#: MEASURED total-VMEM multiplier over the [TB, K, K] A-block bytes: at
#: TB=64, K=64 (A block 1 MiB) the compiler refuses with "scoped
#: allocation 17.14M, limit 16.00M" — the loop-carried copy, rank-P
#: operand copies, b/x and pipeline double-buffers multiply the block
#: ~17x, much of it lane padding (a 64-wide last dim occupies 128
#: lanes). At K=128 the factor is smaller: TB=16 (also 1 MiB) compiles.
_KERNEL_VMEM_MULTIPLIER = 17

#: the kernel's ceiling. Block rows must be a multiple of 8 (the [TB, K]
#: right-hand-side block is tiled (8, 128); TB=2 and 4 are refused at
#: lowering), and 8 rows fit the budget only up to K~164 by the model
#: above. Mosaic refuses shapes the interpreter accepts, so the ceiling
#: is what COMPILED: every multiple of 8 from 8 to 128 — each K that
#: spd_solve's padding can produce — compiled on the chip at the block
#: rows _auto_block_rows picks and agreed with Cholesky to 2.1e-6
#: (PERF.md, PR 21; at K=88 the XLA reference itself ran out of scoped
#: VMEM, so that one was checked by residual). Beyond it spd_solve says
#: so and solves with Cholesky.
_MAX_PALLAS_K = 128


def _auto_block_rows(K: int) -> int:
    """Largest multiple-of-8 block_rows whose TOTAL kernel working set
    (~_KERNEL_VMEM_MULTIPLIER x the [TB,K,K] A block) fits the VMEM
    budget: 32 up to K=80, 24 at 88, 16 up to 112, 8 at 120 and 128 —
    each compiled by Mosaic on the chip, not just run in the
    interpreter."""
    tb = _VMEM_BUDGET // (_KERNEL_VMEM_MULTIPLIER * K * K * 4)
    return max(8, min(_BLOCK_ROWS, tb // 8 * 8))

#: pivot-block width: rank-P updates run on the MXU; P=8 keeps the
#: in-VMEM pivot-block inversion tiny while giving the MXU real work.
_PIVOT_BLOCK = 8

_HI = jax.lax.Precision.HIGHEST


def cholesky_solve(A: jax.Array, b: jax.Array) -> jax.Array:
    """Batched SPD solve via XLA's Cholesky: A [.., K, K], b [.., K].
    The portable path (CPU tests, meshes) — slow on TPU at large batch."""
    L = jax.lax.linalg.cholesky(A)
    x = jax.lax.linalg.triangular_solve(L, b[..., None], left_side=True, lower=True)
    x = jax.lax.linalg.triangular_solve(
        L, x, left_side=True, lower=True, transpose_a=True
    )
    return x[..., 0]


def _bdot(a: jax.Array, b: jax.Array) -> jax.Array:
    """Batched matmul [TB,m,k]@[TB,k,n] at full f32 (bf16 MXU passes lose
    ~1e-2 per rank-P update — measured 0.35 rel err over a 64-col sweep)."""
    return jax.lax.dot_general(
        a, b, (((2,), (1,)), ((0,), (0,))), precision=_HI,
        preferred_element_type=jnp.float32,
    )


def _gj_kernel(A_ref, b_ref, x_ref, *, pivot_block: int):
    """Blocked Gauss-Jordan solve of one [TB, K, K] block, fully in VMEM.

    Per pivot block: invert the [TB,P,P] diagonal block with an unrolled
    masked GJ (VPU), then eliminate its P columns from every row with two
    batched MXU matmuls. After all K/P blocks A is the identity and b
    holds the solution. All indices are static (Python-unrolled), so no
    dynamic-gather lowering is involved.
    """
    P = pivot_block
    A = A_ref[:]  # [TB, K, K]
    b = b_ref[:]  # [TB, K]
    K = A.shape[-1]
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, P), 1)
    for blk in range(K // P):
        s = blk * P
        R = A[:, s : s + P, :]  # pivot rows [TB,P,K]
        D = R[:, :, s : s + P]  # diagonal block [TB,P,P]
        rb = b[:, s : s + P]  # [TB,P]
        # --- invert D: P-step masked GJ carrying the inverse ------------
        Di = jnp.broadcast_to(jnp.eye(P, dtype=A.dtype), D.shape)
        M = D
        for j in range(P):
            sel = (iota == j).astype(A.dtype)  # [1,P] one-hot pivot
            prow = jnp.sum(M * sel[:, :, None], 1)  # [TB,P]
            irow = jnp.sum(Di * sel[:, :, None], 1)
            d = jnp.sum(prow * sel, 1)  # [TB]
            inv = 1.0 / d
            prow_s = prow * inv[:, None]
            irow_s = irow * inv[:, None]
            colj = jnp.sum(M * sel[:, None, :], 2)  # [TB,P]
            f = colj * (1.0 - sel)
            M = M - f[:, :, None] * prow_s[:, None, :]
            Di = Di - f[:, :, None] * irow_s[:, None, :]
            M = M * (1.0 - sel[:, :, None]) + sel[:, :, None] * prow_s[:, None, :]
            Di = Di * (1.0 - sel[:, :, None]) + sel[:, :, None] * irow_s[:, None, :]
        # --- rank-P elimination of the pivot columns from all rows ------
        C = A[:, :, s : s + P]  # [TB,K,P]
        F = _bdot(C, Di)
        # pivot rows need G = I - Di so they land on Di @ R (row-reduced
        # form); all other rows use F
        parts = []
        if s:
            parts.append(F[:, :s])
        parts.append(F[:, s : s + P] - Di)
        if s + P < K:
            parts.append(F[:, s + P :])
        G = jnp.concatenate(parts, axis=1) if len(parts) > 1 else parts[0]
        A = A - _bdot(G, R)
        b = b - _bdot(G, rb[..., None])[..., 0]
    x_ref[:] = b  # A reduced to I: b holds the solution


@functools.partial(
    jax.jit, static_argnames=("block_rows", "pivot_block", "interpret")
)
def gj_solve_pallas(
    A: jax.Array,  # [B, K, K]
    b: jax.Array,  # [B, K]
    block_rows: int | None = None,
    pivot_block: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Batched SPD solve, blocked Gauss-Jordan in VMEM. B is padded to a
    multiple of ``block_rows`` (default: auto-sized to the VMEM budget
    for this K); padding rows are identity systems (solve to 0); K must
    be a multiple of ``pivot_block`` (default ``_PIVOT_BLOCK``, read at
    call time so measurements can tune the module knobs)."""
    B, K = b.shape
    if pivot_block is None:
        pivot_block = _PIVOT_BLOCK
    if K % pivot_block:
        raise ValueError(f"K={K} must be a multiple of pivot_block={pivot_block}")
    if block_rows is None:
        block_rows = _auto_block_rows(K)
    n_pad = -(-B // block_rows) * block_rows - B
    if n_pad:
        eye = jnp.broadcast_to(jnp.eye(K, dtype=A.dtype), (n_pad, K, K))
        A = jnp.concatenate([A, eye], axis=0)
        b = jnp.concatenate([b, jnp.zeros((n_pad, K), b.dtype)], axis=0)
    out = pl.pallas_call(
        functools.partial(_gj_kernel, pivot_block=pivot_block),
        grid=(A.shape[0] // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, K, K), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_rows, K), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((block_rows, K), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((A.shape[0], K), b.dtype),
        interpret=interpret,
        # the kernel's name in a device trace, whatever wraps the call
        name="gj_solve_pallas",
    )(A, b)
    return out[:B]


def pallas_rank_ok(K: int) -> bool:
    """Whether the kernel takes a [.., K, K] system (after padding K up
    to the pivot block): beyond ``_MAX_PALLAS_K`` no block size fits the
    scoped VMEM and callers get Cholesky."""
    return -(-K // _PIVOT_BLOCK) * _PIVOT_BLOCK <= _MAX_PALLAS_K


def spd_solve(A: jax.Array, b: jax.Array, method: str = "cholesky") -> jax.Array:
    """Dispatch: ``method`` in {"cholesky", "pallas", "pallas_interpret"}.

    Callers pick "pallas" on a real TPU backend (Mosaic-lowered);
    "pallas_interpret" runs the same kernel logic on CPU for tests;
    "cholesky" is the portable XLA path. A K that is not a multiple of
    the pivot block is embedded in the next multiple — ``[[A, 0], [0,
    I]] x = [b, 0]`` has the same solution in its first K entries — so
    any rank (the templates' default 10 included) runs the kernel. K
    beyond ``_MAX_PALLAS_K`` cannot: that is logged, once per traced
    shape, and solved by Cholesky.
    """
    if method in ("pallas", "pallas_interpret"):
        K = A.shape[-1]
        if pallas_rank_ok(K):
            A2 = A.reshape((-1, K, K))
            b2 = b.reshape((-1, K))
            pad = -K % _PIVOT_BLOCK
            if pad:
                A2 = jnp.pad(A2, ((0, 0), (0, pad), (0, pad)))
                A2 = A2.at[:, K:, K:].set(jnp.eye(pad, dtype=A.dtype))
                b2 = jnp.pad(b2, ((0, 0), (0, pad)))
            x = gj_solve_pallas(A2, b2, interpret=(method == "pallas_interpret"))
            return x[:, :K].reshape(b.shape)
        logger.warning(
            "spd_solve: K=%d exceeds the Pallas kernel's ceiling (%d); "
            "solving with XLA Cholesky", K, _MAX_PALLAS_K,
        )
        method = "cholesky"
    if method == "cholesky":
        return cholesky_solve(A, b)
    raise ValueError(
        f"spd_solve method must be 'cholesky', 'pallas' or 'pallas_interpret', got {method!r}"
    )
