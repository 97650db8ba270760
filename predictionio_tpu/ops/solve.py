"""Batched SPD solves — the ALS hot op, as a Pallas TPU kernel.

XLA's ``lax.linalg.cholesky`` lowers a batched [B,K,K] factorization to a
K-step sequential loop whose every step round-trips the whole batch
through HBM (1.26 s for [138k,64,64] on a v5e: PERF.md, PR 21; not
measured again since). The kernel here puts **the batch along the
lanes**: a vector holds one matrix position of 128 systems, a column of
them is K/8 vregs, and a left-looking Cholesky with both substitutions
runs as full-width float32 VPU operations over blocks resident in VMEM.
There is no MXU product in it, so no bf16 pass and no ``Precision``.

Measured on a v5e inside a whole ``als_ml20m`` train (PERF.md, PR 34):
0.24 s for a job's 3,305,840 systems of 64 x 64, **0.072 us a system**
(the blocked Gauss-Jordan kernel it replaced: 7.45 s, 2.25 us), relative
error to a float64 solve of the same float32 systems 6.4e-7 at the median
(XLA's Cholesky: 1.2e-6). The kernel reads ``A`` as XLA lays it, [B, K, K]
with rows along the lanes, and transposes a row of 128 systems on the XLU
as it goes: an XLA transpose in front of it cost three passes over ``A``
(0.69 s a job) where this costs 0.1 s.

Cholesky without pivoting is numerically safe here: every ALS normal
matrix is SPD with an ALS-WR ridge (λ·max(n,1)·I), so the diagonal stays
bounded away from zero.

No reference analog — MLlib solves on CPU LAPACK
(``org.apache.spark.ml.recommendation.ALS`` CholeskySolver); this is the
TPU-native replacement for that hot path.
"""

from __future__ import annotations

import functools
import logging
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "SOLVE_KERNEL", "spd_solve", "chol_solve_pallas", "cholesky_solve",
    "pallas_rank_ok", "solve_kernel_name",
]

logger = logging.getLogger(__name__)

#: the kernel's name in a device trace and in the instance's
#: ``kernels.als.solveKernel``
SOLVE_KERNEL = "chol_solve_pallas"

#: systems along a vector's lanes: one kernel group
_LANES = 128

#: scoped VMEM a kernel gets by default on this libtpu; the kernel asks
#: for more only when its blocks need it (from K=80)
_DEFAULT_SCOPED_VMEM = 16 << 20

#: bytes of ``A`` a grid step aims for: small ranks take several
#: 128-system groups a step so the step's fixed cost is shared
_STEP_BYTES = 2 << 20

#: columns factored together: their accumulators share each load of an
#: earlier column (4 x K/8 vregs of accumulators at most: half the
#: file). On the chip at K=64 (PERF.md, PR 34): 1 column 3.99 ms a
#: 32,768 systems, 2 columns 2.30, 4 columns 1.95, 8 columns 1.95
_COL_BLOCK = 4

#: earlier columns folded in per loop trip, unrolled by hand (Mosaic
#: unrolls a loop wholly or not at all): 2 trips 2.22 ms, 4 1.95, 8 1.88
_UNROLL = 8

#: the kernel's ceiling: a [128, 128, 128] block is 8 MiB, two pipeline
#: buffers and the factor 24 MiB, asked of the compiler by
#: ``vmem_limit_bytes``. Beyond it spd_solve says so and solves with
#: Cholesky.
_MAX_PALLAS_K = 128


def cholesky_solve(A: jax.Array, b: jax.Array) -> jax.Array:
    """Batched SPD solve via XLA's Cholesky: A [.., K, K], b [.., K].
    The portable path (CPU tests, meshes) — slow on TPU at large batch."""
    L = jax.lax.linalg.cholesky(A)
    x = jax.lax.linalg.triangular_solve(L, b[..., None], left_side=True, lower=True)
    x = jax.lax.linalg.triangular_solve(
        L, x, left_side=True, lower=True, transpose_a=True
    )
    return x[..., 0]


def _block_bytes(K: int) -> int:
    """One group's [128, K, K] block of ``A`` in VMEM: rows of K floats
    lie padded to the 128 lanes."""
    return _LANES * K * max(K, _LANES) * 4


def _groups_per_step(K: int) -> int:
    """128-system groups a grid step takes: as many as fill _STEP_BYTES
    (4 at K=8, 2 at 16, 1 from 24 up)."""
    return max(1, _STEP_BYTES // _block_bytes(K))


@functools.partial(jax.jit, static_argnames=("group",))
def _solve_group(A_ref, b_ref, x_ref, L_ref, r_ref, y_ref, *, group: int):
    """Left-looking Cholesky and both substitutions of one group: 128
    systems a vector.

    ``A_ref`` [TB, K, K], ``b_ref`` / ``x_ref`` [TB, K]: blocks as XLA
    lays them; the group is systems ``128 * group`` onward. Its column
    ``j`` is row ``j`` of each system (``A`` is symmetric) transposed on
    the XLU to [K(row), 128]: K/8 vregs. Scratch: ``L_ref`` [K, K, 128]
    the factor by column, ``r_ref`` [K, 128] the reciprocal diagonal,
    ``y_ref`` [K, 128] ``b``, then ``y`` of ``L y = b`` (riding along the
    factorization), then ``x``. Column ``j`` of the factor is ``A``'s
    less the earlier columns times their row ``j`` (a sublane
    broadcast), scaled by ``rsqrt`` of its own diagonal; only the vregs
    from row ``j // 8 * 8`` down are touched. ``_COL_BLOCK`` columns
    share the loads of the earlier ones. Lanes past the batch's end (a
    ragged last block) compute on whatever the buffer holds and are
    never written back; nothing crosses lanes.

    Jitted on the refs: every solve of a sweep (21 in an ML-20M train,
    one a bucket and hot group) calls it at the same block shapes, so
    the body is traced once a process, not once a ``pallas_call``.
    """
    K = b_ref.shape[1]
    col_block = min(_COL_BLOCK, K)
    lanes = slice(group * _LANES, (group + 1) * _LANES)

    def rows_from(r0):
        return jax.lax.broadcasted_iota(jnp.int32, (K - r0, _LANES), 0) + r0

    y_ref[...] = b_ref[lanes, :].T
    for j0 in range(0, K, col_block):
        r0 = j0 // 8 * 8
        rows = rows_from(r0)
        accs = tuple(A_ref[lanes, j0 + c, :].T[r0:, :] for c in range(col_block))
        if j0:
            u = math.gcd(j0, _UNROLL)

            def fold(t, accs, j0=j0, r0=r0, u=u):
                for k in range(u):  # unrolled by hand: Mosaic takes 1 or all
                    Lk = L_ref[t * u + k, r0:, :]
                    accs = tuple(
                        acc - Lk * L_ref[t * u + k, j0 + c : j0 + c + 1, :]
                        for c, acc in enumerate(accs)
                    )
                return accs

            accs = jax.lax.fori_loop(0, j0 // u, fold, accs)
        done = []  # this block's finished columns, still in registers
        for c, acc in enumerate(accs):
            j = j0 + c
            o = j - r0
            for Lc in done:
                acc = acc - Lc * Lc[o : o + 1, :]
            r = jax.lax.rsqrt(acc[o : o + 1, :])
            Lc = jnp.where(rows >= j, acc * r, 0.0)
            L_ref[j, r0:, :] = Lc
            r_ref[j : j + 1, :] = r
            done.append(Lc)
            y = y_ref[r0:, :]
            yj = y[o : o + 1, :] * r
            y_ref[r0:, :] = jnp.where(
                rows > j, y - Lc * yj, jnp.where(rows == j, yj, y)
            )
    for j in range(K - 1, -1, -1):
        r0 = j // 8 * 8
        o = j - r0
        rows = rows_from(r0)
        x = y_ref[r0:, :]
        s = jnp.sum(
            jnp.where(rows > j, L_ref[j, r0:, :] * x, 0.0), axis=0, keepdims=True
        )
        xj = (x[o : o + 1, :] - s) * r_ref[j : j + 1, :]
        y_ref[r0 : r0 + 8, :] = jnp.where(rows[:8] == j, xj, x[:8])
    x_ref[lanes, :] = y_ref[...].T


def _chol_kernel(A_ref, b_ref, x_ref, *scratch):
    """One grid step: the block's groups of 128 systems, one after the
    other (a Python loop: they are few, and index statically)."""
    for g in range(b_ref.shape[0] // _LANES):
        _solve_group(A_ref, b_ref, x_ref, *scratch, group=g)


@functools.partial(jax.jit, static_argnames=("interpret",))
def chol_solve_pallas(
    A: jax.Array,  # [B, K, K]
    b: jax.Array,  # [B, K]
    interpret: bool = False,
) -> jax.Array:
    """Batched SPD solve by Cholesky, the batch along the lanes. ``K`` is
    a multiple of 8 (the sublane tile); any B (the last block may be
    ragged: nothing is padded or copied in front of the kernel). Only one
    triangle of ``A`` is read."""
    B, K = b.shape
    if K % 8:
        raise ValueError(f"K={K} must be a multiple of 8")
    tb = _LANES * min(_groups_per_step(K), -(-B // _LANES))
    need = 2 * (tb // _LANES) * _block_bytes(K) + K * K * _LANES * 4 + (4 << 20)
    return pl.pallas_call(
        _chol_kernel,
        grid=(-(-B // tb),),
        in_specs=[
            pl.BlockSpec((tb, K, K), lambda i: (i, 0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((tb, K), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((tb, K), lambda i: (i, 0), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((B, K), b.dtype),
        scratch_shapes=[
            pltpu.VMEM((K, K, _LANES), jnp.float32),
            pltpu.VMEM((K, _LANES), jnp.float32),
            pltpu.VMEM((K, _LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=max(_DEFAULT_SCOPED_VMEM, need),
        ),
        interpret=interpret,
        # the kernel's name in a device trace, whatever wraps the call
        name=SOLVE_KERNEL,
    )(A, b)


def pallas_rank_ok(K: int) -> bool:
    """Whether the kernel takes a [.., K, K] system (after padding K up
    to the sublane tile): beyond ``_MAX_PALLAS_K`` callers get Cholesky."""
    return -(-K // 8) * 8 <= _MAX_PALLAS_K


def solve_kernel_name(method: str, K: int) -> str:
    """What ``spd_solve(.., method)`` runs at rank ``K``, as a device
    trace names it: the Pallas kernel's name or ``cholesky_xla``."""
    if method.startswith("pallas") and pallas_rank_ok(K):
        return SOLVE_KERNEL
    return "cholesky_xla"


def spd_solve(A: jax.Array, b: jax.Array, method: str = "cholesky") -> jax.Array:
    """Dispatch: ``method`` in {"cholesky", "pallas", "pallas_interpret"}.

    Callers pick "pallas" on a real TPU backend (Mosaic-lowered);
    "pallas_interpret" runs the same kernel logic on CPU for tests;
    "cholesky" is the portable XLA path. A K that is not a multiple of
    the sublane tile (8) is embedded in the next multiple — ``[[A, 0],
    [0, I]] x = [b, 0]`` has the same solution in its first K entries —
    so any rank (the templates' default 10 included) runs the kernel. K
    beyond ``_MAX_PALLAS_K`` cannot: that is logged, once per traced
    shape, and solved by Cholesky.
    """
    if method in ("pallas", "pallas_interpret"):
        K = A.shape[-1]
        if pallas_rank_ok(K):
            A2 = A.reshape((-1, K, K))
            b2 = b.reshape((-1, K))
            pad = -K % 8
            if pad:
                A2 = jnp.pad(A2, ((0, 0), (0, pad), (0, pad)))
                A2 = A2.at[:, K:, K:].set(jnp.eye(pad, dtype=A.dtype))
                b2 = jnp.pad(b2, ((0, 0), (0, pad)))
            x = chol_solve_pallas(A2, b2, interpret=(method == "pallas_interpret"))
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
