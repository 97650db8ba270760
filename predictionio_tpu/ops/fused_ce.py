"""Flash-style fused in-batch softmax cross-entropy (Pallas/TPU).

The two-tower training step's cost is NOT its GEMMs: at B=8192, D=64 the
logits matrix is [B, B] = 268 MB fp32, and the unfused XLA pipeline
(2 logits GEMMs -> 2 softmax-CE fwd -> softmax recompute + 4 GEMMs bwd)
streams it through HBM ~10 times per step. Arithmetic intensity of that
chain is ~D FLOPs/byte = 64, far under the v5e roofline crossover (~240),
capping MFU near 7% no matter how fast the MXU is.

This kernel never materializes the logits in HBM. One row-block sweep
computes logit tiles in VMEM, exponentiates in place, and reduces:

* forward: row sums ``rs`` (user->item denominators), column sums ``cs``
  (item->user denominators — the symmetric loss is the SAME matrix read
  down columns), and the diagonal (the positive-pair logits). The loss
  closes on the host side: ``0.5/B * (sum log rs + sum log cs - 2 sum d)``.
* backward: recomputes each tile (flash-attention-style rematerialization
  — a second 2*B*B*D FLOPs buys removing ~5 GB/step of HBM traffic),
  forms ``dL`` in VMEM, and feeds TWO grad GEMMs per tile:
  ``d_ue = dL @ ie`` written per block and ``d_ie += dL^T @ ue_blk``
  accumulated in a VMEM-resident output (consecutive revisits).

No running max is carried (vs. true flash softmax): tower vectors are
L2-normalized so logits are bounded by ``inv_temp`` (~10), and
``exp(10) * 8192`` sits comfortably inside fp32 — the max subtraction
would cost an extra pass for nothing.

HBM traffic per step collapses to O(B*D). FLOP accounting (matching the
MFU math in ``bench.py``): **useful** work is 6*B^2*D per step — the
forward logits GEMM (2*B^2*D) plus the two backward grad GEMMs
(4*B^2*D); the backward tile recompute adds 2*B^2*D of
**rematerialization** overhead that buys the HBM savings and is
deliberately excluded from the MFU numerator. Total executed is
8*B^2*D, so the step turns compute-bound — the condition MFU needs.
GEMM operands are cast to bf16 (fp32 accumulation), riding the MXU at
full rate.

No reference counterpart (the reference has no deep-retrieval template);
design per /opt/skills/guides/pallas_guide.md.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["fused_inbatch_ce", "fused_ce_supported"]

#: rows of the logits computed per grid step. The live tile set (L, E, dL
#: at [TI, B] fp32) is 12.6 MB at B=8192 beside the two [B, D] operands.
#: Compiled by Mosaic on a v5e under the default scoped VMEM at B=8192
#: and B=16384, D=64 (PERF.md, PR 21): forward+backward 1.65 ms / 2.60 ms
#: against 4.66 ms / 13.3 ms for the XLA path. 256 rows measured 1.38 ms
#: / 2.72 ms and 64 rows 1.85 ms / 3.16 ms, so 128 stays. Larger batches
#: are not compiled yet.
_TI = 128


#: the kernel carries no running max (logits are bounded by inv_temp for
#: L2-normalized towers), so exp(inv_temp) * B must stay finite in fp32:
#: inv_temp <= 60 leaves exp(60)*2^20 ~ 1.2e32 << fp32 max. Beyond that
#: (temperature < ~0.017) callers must use the max-subtracted XLA path.
MAX_INV_TEMP = 60.0


def fused_ce_supported(B: int, D: int, inv_temp: float = 1.0) -> bool:
    """Shapes/scales the kernel handles: full row blocks, lane-aligned D,
    and a temperature that cannot overflow the max-free exp."""
    return (
        B % _TI == 0
        and D % 8 == 0
        and B >= _TI
        and 0.0 < inv_temp <= MAX_INV_TEMP
    )


# ---------------------------------------------------------------------------
# Kernels
# ---------------------------------------------------------------------------


def _fwd_kernel(ue_ref, ie_ref, rs_ref, cs_ref, *, inv_temp, ti):
    i = pl.program_id(0)
    logits = (
        jnp.dot(
            ue_ref[:].astype(jnp.bfloat16),
            ie_ref[:].astype(jnp.bfloat16).T,
            preferred_element_type=jnp.float32,
        )
        * inv_temp
    )  # [TI, B]
    # exp in bf16: 2x the VPU transcendental rate, and the kernel's exp
    # only feeds the softmax DENOMINATORS (rs/cs sums) — the positive-pair
    # numerator term is computed exactly in fp32 by the caller. Sums
    # accumulate in fp32.
    e = jnp.exp(logits.astype(jnp.bfloat16)).astype(jnp.float32)
    rs_ref[:] = jnp.sum(e, axis=1, keepdims=True)  # [TI, 1]
    cs = jnp.sum(e, axis=0, keepdims=True)  # [1, B]

    @pl.when(i == 0)
    def _():
        cs_ref[:] = jnp.zeros_like(cs_ref)

    cs_ref[:] = cs_ref[:] + cs
    # NOTE: the diagonal (positive-pair logits) is deliberately NOT read
    # here — L_ii is just rowsum(ue*ie)*inv_temp, an O(B*D) elementwise
    # the caller computes outside; a masked in-kernel extraction costs
    # [TI, B] iota+select work per tile for nothing


def _bwd_kernel(
    ue_ref, ie_ref, rs_ref, cs_ref, due_ref, die_ref, *, inv_temp, ti, b
):
    i = pl.program_id(0)
    ue16 = ue_ref[:].astype(jnp.bfloat16)
    ie16 = ie_ref[:].astype(jnp.bfloat16)
    logits = (
        jnp.dot(ue16, ie16.T, preferred_element_type=jnp.float32) * inv_temp
    )
    # bf16 exp (see _fwd_kernel); the fwd pass computed rs/cs from the
    # SAME rounding, so the softmax here is self-consistent
    e = jnp.exp(logits.astype(jnp.bfloat16)).astype(jnp.float32)
    c = 0.5 * inv_temp / b
    # softmax terms of both CE directions share the tile. The positive
    # pair's -delta_ij correction is NOT applied here: it is a rowwise
    # subtraction (due_i -= 2c*ie_i, die_i -= 2c*ue_i) the caller does
    # outside — keeping the tile free of iota/select masks
    dl = (e * (c / rs_ref[:]) + e * (c / cs_ref[:])).astype(jnp.bfloat16)
    due_ref[:] = jnp.dot(dl, ie16, preferred_element_type=jnp.float32)

    @pl.when(i == 0)
    def _():
        die_ref[:] = jnp.zeros_like(die_ref)

    die_ref[:] = die_ref[:] + jnp.dot(
        dl.T, ue16, preferred_element_type=jnp.float32
    )


# ---------------------------------------------------------------------------
# pallas_call wrappers
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("inv_temp", "interpret"))
def _fwd_call(ue, ie, inv_temp: float, interpret: bool):
    B, D = ue.shape
    grid = (B // _TI,)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, inv_temp=inv_temp, ti=_TI),
        grid=grid,
        in_specs=[
            pl.BlockSpec((_TI, D), lambda i: (i, 0)),
            pl.BlockSpec((B, D), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((_TI, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, B), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, B), jnp.float32),
        ],
        cost_estimate=pl.CostEstimate(
            flops=2 * B * B * D,
            bytes_accessed=2 * B * D * 4 * (B // _TI),
            transcendentals=B * B,
        ),
        interpret=interpret,
    )(ue, ie)


@functools.partial(jax.jit, static_argnames=("inv_temp", "interpret"))
def _bwd_call(ue, ie, rs, cs, inv_temp: float, interpret: bool):
    B, D = ue.shape
    grid = (B // _TI,)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, inv_temp=inv_temp, ti=_TI, b=B),
        grid=grid,
        in_specs=[
            pl.BlockSpec((_TI, D), lambda i: (i, 0)),
            pl.BlockSpec((B, D), lambda i: (0, 0)),
            pl.BlockSpec((_TI, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, B), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((_TI, D), lambda i: (i, 0)),
            pl.BlockSpec((B, D), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, D), jnp.float32),
            jax.ShapeDtypeStruct((B, D), jnp.float32),
        ],
        cost_estimate=pl.CostEstimate(
            flops=6 * B * B * D,
            bytes_accessed=4 * B * D * 4 * (B // _TI),
            transcendentals=B * B,
        ),
        interpret=interpret,
    )(ue, ie, rs, cs)


# ---------------------------------------------------------------------------
# custom-vjp entry point
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def fused_inbatch_ce(
    ue: jax.Array, ie: jax.Array, inv_temp: float, interpret: bool = False
) -> jax.Array:
    """Mean symmetric in-batch softmax CE of L2-normalized tower outputs.

    Equals ``0.5 * (ce(ue@ie.T * t, arange) + ce(ie@ue.T * t, arange))``
    (the XLA reference path in ``ops/twotower.py``) without materializing
    either [B, B] matrix."""
    loss, _ = _fused_fwd(ue, ie, inv_temp, interpret)
    return loss


#: the device scope of both kernel calls: a trace's reduction finds the
#: forward and the backward sweep under it, whatever Mosaic names them
SCOPE = "pio_tt_ce"


def _fused_fwd(ue, ie, inv_temp, interpret):
    with jax.named_scope(SCOPE):
        rs, cs = _fwd_call(ue, ie, inv_temp, interpret)
    B = ue.shape[0]
    # positive-pair logits: the [B, B] diagonal is just the rowwise dot
    diag = jnp.sum(ue * ie, axis=1) * inv_temp
    loss = (
        0.5
        * (jnp.sum(jnp.log(rs)) + jnp.sum(jnp.log(cs)) - 2.0 * jnp.sum(diag))
        / B
    )
    return loss, (ue, ie, rs, cs)


def _fused_bwd(inv_temp, interpret, res, g):
    ue, ie, rs, cs = res
    with jax.named_scope(SCOPE):
        due, die = _bwd_call(ue, ie, rs, cs, inv_temp, interpret)
    # the positive pair's -delta correction, hoisted out of the kernel:
    # d/due_i of (-diag terms) = -(2 * 0.5/B) * inv_temp * ie_i
    c2 = inv_temp / ue.shape[0]
    return (due - c2 * ie) * g, (die - c2 * ue) * g


fused_inbatch_ce.defvjp(_fused_fwd, _fused_bwd)
