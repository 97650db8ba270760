"""REAL-TPU correctness for the Pallas lane-batched Cholesky solver.

Tier-1 runs the kernel only through ``interpret=True`` (CPU). These
tests run the REAL Mosaic-compiled kernel on a TPU backend at the
flagship bench shape ([138k, 64, 64]), at K=128, and at the small padded
ranks `spd_solve` sends it (rank 10 -> K=16, 5 -> 8, 20 -> 24), comparing
against XLA Cholesky. Everything — SPD generation, both solves, and the error
reduction — happens on device, so only scalars come back to the host.
``chip_smoke.py`` (leg 6) makes the same checks on every chip run.

Skipped cleanly off-TPU; on the chip:
``PIO_TEST_TPU=1 python -m pytest tests/test_pallas_tpu.py -q``.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")


def _on_tpu() -> bool:
    try:
        return jax.default_backend() == "tpu"
    except Exception:
        return False


pytestmark = pytest.mark.skipif(
    not _on_tpu(), reason="requires a real TPU backend (Mosaic lowering)"
)


def _device_spd_batch(batch: int, k: int, seed: int):
    """SPD systems generated ON DEVICE (ALS-shaped: Gramian + ridge)."""
    import jax.numpy as jnp

    @jax.jit
    def make(key):
        kb, kr = jax.random.split(key)
        Q = jax.random.normal(kb, (batch, k, k), jnp.float32)
        A = jnp.einsum("bij,bkj->bik", Q, Q) / k + 0.1 * jnp.eye(k)
        b = jax.random.normal(kr, (batch, k), jnp.float32)
        return A, b

    return make(jax.random.PRNGKey(seed))


@pytest.mark.parametrize(
    "batch,k",
    [
        (138_000, 64),  # the flagship bench shape
        (8_000, 128),  # the ceiling: 8 MiB blocks, vmem_limit_bytes raised
    ],
)
def test_chol_solve_matches_cholesky_on_tpu(batch, k):
    import jax.numpy as jnp

    from predictionio_tpu.ops.solve import chol_solve_pallas, cholesky_solve

    A, b = _device_spd_batch(batch, k, seed=k)
    x_pl = chol_solve_pallas(A, b)  # REAL Mosaic lowering (no interpret)
    x_ch = cholesky_solve(A, b)

    @jax.jit
    def rel_err(xa, xb):
        num = jnp.max(jnp.abs(xa - xb), axis=-1)
        den = jnp.maximum(jnp.max(jnp.abs(xb), axis=-1), 1e-6)
        return jnp.max(num / den)

    err = float(rel_err(x_pl, x_ch))
    assert np.isfinite(err)
    assert err < 1e-4, f"pallas vs cholesky rel err {err} at [{batch},{k},{k}]"


@pytest.mark.parametrize(
    "batch,rank",
    [
        (138_000, 10),  # the templates' default rank: padded to K=16
        (27_027, 5),  # K=8, one vreg a column
        (27_027, 20),  # K=24
    ],
)
def test_spd_solve_pads_small_ranks_into_the_kernel_on_tpu(batch, rank):
    """`spd_solve(.., "pallas")` — the call the ALS sweep makes — embeds a
    rank that is not a multiple of 8 in the next one. Each padded K is
    its own Mosaic compile ([128 * groups, K, K] blocks, K/8 vregs a
    column), and Mosaic refuses shapes the interpreter accepts: compile
    them here."""
    import jax.numpy as jnp

    from predictionio_tpu.ops.solve import (
        cholesky_solve,
        pallas_rank_ok,
        spd_solve,
    )

    assert pallas_rank_ok(rank)
    A, b = _device_spd_batch(batch, rank, seed=rank)
    x_pl = jax.jit(lambda A, b: spd_solve(A, b, "pallas"))(A, b)
    x_ch = cholesky_solve(A, b)
    assert x_pl.shape == (batch, rank)

    @jax.jit
    def rel_err(xa, xb):
        num = jnp.max(jnp.abs(xa - xb), axis=-1)
        den = jnp.maximum(jnp.max(jnp.abs(xb), axis=-1), 1e-6)
        return jnp.max(num / den)

    err = float(rel_err(x_pl, x_ch))
    assert np.isfinite(err)
    assert err < 1e-4, f"pallas vs cholesky rel err {err} at rank {rank}"


def test_chol_solve_residual_on_tpu():
    """Independent ground truth: the kernel's solution must satisfy the
    system itself (not just agree with another solver)."""
    import jax.numpy as jnp

    from predictionio_tpu.ops.solve import chol_solve_pallas

    A, b = _device_spd_batch(4_096, 64, seed=7)
    x = chol_solve_pallas(A, b)

    @jax.jit
    def resid(A, x, b):
        # full f32: the default einsum precision runs bf16 MXU passes on
        # TPU, which would bound this measurement at ~1e-2 by itself
        r = (
            jnp.einsum(
                "bij,bj->bi", A, x, precision=jax.lax.Precision.HIGHEST
            )
            - b
        )
        return jnp.max(
            jnp.linalg.norm(r, axis=-1)
            / jnp.maximum(jnp.linalg.norm(b, axis=-1), 1e-6)
        )

    assert float(resid(A, x, b)) < 1e-4


class TestFusedInbatchCE:
    """Mosaic-compiled fused softmax-CE (ops/fused_ce.py) vs the XLA
    reference at the flagship two-tower bench shape — the kernel is
    default-ON for single-device TPU training, so its compiled path (not
    just interpret mode) must be pinned here."""

    def _towers(self, b, d, seed=0):
        import jax.numpy as jnp

        rng = np.random.default_rng(seed)
        ue = rng.normal(size=(b, d)).astype(np.float32)
        ie = rng.normal(size=(b, d)).astype(np.float32)
        ue /= np.linalg.norm(ue, axis=1, keepdims=True)
        ie /= np.linalg.norm(ie, axis=1, keepdims=True)
        return jnp.asarray(ue), jnp.asarray(ie)

    def _reference(self, ue, ie, inv_temp):
        import jax.numpy as jnp
        import optax

        labels = jnp.arange(ue.shape[0])

        def lg(a, b):
            return (
                jnp.matmul(
                    a.astype(jnp.bfloat16),
                    b.astype(jnp.bfloat16).T,
                    preferred_element_type=jnp.float32,
                )
                * inv_temp
            )

        l1 = optax.softmax_cross_entropy_with_integer_labels(
            lg(ue, ie), labels
        )
        l2 = optax.softmax_cross_entropy_with_integer_labels(
            lg(ie, ue), labels
        )
        return 0.5 * (l1.mean() + l2.mean())

    @pytest.mark.parametrize("b,d", [(8192, 64), (1024, 32)])
    def test_loss_and_grads_match_xla_on_device(self, b, d):
        from predictionio_tpu.ops.fused_ce import fused_inbatch_ce

        ue, ie = self._towers(b, d)
        inv_temp = 10.0
        got = float(fused_inbatch_ce(ue, ie, inv_temp))
        want = float(jax.jit(lambda u, i: self._reference(u, i, inv_temp))(ue, ie))
        assert abs(got - want) < 5e-3 * max(1.0, abs(want)), (got, want)
        g_got = jax.jit(
            jax.grad(
                lambda u, i: fused_inbatch_ce(u, i, inv_temp), argnums=(0, 1)
            )
        )(ue, ie)
        g_want = jax.jit(
            jax.grad(
                lambda u, i: self._reference(u, i, inv_temp), argnums=(0, 1)
            )
        )(ue, ie)
        for got_a, want_a in zip(g_got, g_want):
            scale = float(np.abs(np.asarray(want_a)).max())
            np.testing.assert_allclose(
                np.asarray(got_a), np.asarray(want_a),
                rtol=5e-2, atol=5e-3 * max(scale, 1e-6),
            )
