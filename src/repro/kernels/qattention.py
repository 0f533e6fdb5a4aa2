"""Fused int8 attention Pallas TPU kernel.

One kernel realizes the PQ-IR attention region the token path codifies
(see ``repro.core.patterns.emit_qattention``):

    MatMulInteger (int8 Q × int8 K^T → int32, on the MXU)
      → Cast f32 → Mul qk_scale                      (combined QK rescale)
      → masked shift: s·mask + (mask-1)·big          (additive {0,-big} mask)
      → ReduceMax / Sub                              (softmax max-shift)
      → QuantizeLinear(lut_scale)                    (int8 score deltas)
      → exp via 256-entry LUT lookup                 (VPU, no transcendentals)
      → ReduceSum int32 / Div / Mul(p_scale) / QL    (int8 probabilities)
      → MatMulInteger (int8 P × int8 V → int32)      (MXU again)
      → Cast f32 → Mul rescale → QuantizeLinear      (int8 context)

TPU mapping: grid is ``(B, Sp/bq)`` — one query row-block per step with the
full-length K/V blocks resident in VMEM (their block specs index on the
batch dim only), the masked LUT-softmax runs on the VPU over the int32
score tile while it is live in VMEM, and both contractions drive the MXU at
its double-rate int8 throughput.  Nothing round-trips to HBM between the
two matmuls — that is the whole point of fusing the region.

Bit-exactness: every step is integer arithmetic or an IEEE-exact f32
elementwise op in the artifact's codified order (the TPU's inexact f32
divide is corrected to IEEE by :func:`repro.kernels.ref.div_rn`), so
``reference runtime == qattention_ref == qattention`` bit-for-bit, in
interpret mode and on the chip.  Zero padding is exact end-to-end: padded keys carry a zero
mask, which drives their score to ``-big`` and their LUT weight to exactly
``lut[0] == 0`` (asserted by ``repro.core.patterns.build_exp_lut``), so they
contribute nothing to the denominator or the context; padded query rows are
sliced away.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .qact_lut import LUT_SPEC, lut_lookup, lut_table
from .qmatmul import MIN_LANE, MIN_SUBLANE
from .ref import div_rn

#: Default query row-block.
BQ = 128


def _ceil_to(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def choose_bq(s, *, bq: int = BQ) -> int:
    """Per-bucket query tile: shrink the default toward the (sublane-aligned)
    query count so decode (S=1) runs a 32-row block instead of padding 1→128.
    ``s`` may be None/0 (unknown extent) — the default then stands."""
    return min(bq, _ceil_to(int(s), MIN_SUBLANE)) if s else bq


def bq_aligned(bq: int) -> bool:
    """The autotuner's validity predicate for a query-tile candidate."""
    return bq > 0 and bq % MIN_SUBLANE == 0


def _qattention_kernel(
    q_ref, k_ref, v_ref, m_ref, lut_ref, o_ref,
    *, qk_scale, big, lut_scale, p_scale, rescale, out_dtype,
):
    q = q_ref[0]  # (bq, dp) int8
    k = k_ref[0]  # (tp, dp) int8
    v = v_ref[0]  # (tp, dp) int8
    mask = m_ref[0]  # (bq, tp) f32

    # int8 Q × K^T → int32 scores on the MXU.
    acc = jax.lax.dot_general(
        q,
        k,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    s_f = acc.astype(jnp.float32) * qk_scale
    masked = s_f * mask + (mask - 1.0) * big
    mx = jnp.max(masked, axis=-1, keepdims=True)
    d_q = jnp.clip(jnp.rint((masked - mx) / lut_scale), -128, 127).astype(jnp.int32)
    w = lut_lookup(d_q + 128, lut_ref)  # uint8 values; masked keys hit lut[0] == 0
    den = jnp.sum(w, axis=-1, keepdims=True)
    p = div_rn(w.astype(jnp.float32), den.astype(jnp.float32))  # IEEE on the TPU too
    # p_q is clipped to [-128, 127], so the int8 cast is exact.
    p_q = jnp.clip(jnp.rint(p * p_scale), -128, 127).astype(jnp.int8)
    # int8 P × V → int32 context on the MXU.
    ctx = jax.lax.dot_general(
        p_q,
        v,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    f = ctx.astype(jnp.float32) * rescale
    info = jnp.iinfo(out_dtype)
    o_ref[0] = jnp.clip(jnp.rint(f), info.min, info.max).astype(out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "qk_scale", "big", "lut_scale", "p_scale", "rescale",
        "out_dtype", "bq", "interpret",
    ),
)
def qattention(
    q_q: jax.Array,  # (B, S, dh) int8
    k_q: jax.Array,  # (B, T, dh) int8
    v_q: jax.Array,  # (B, T, dh) int8
    mask: jax.Array,  # (B, S, T) f32 {0, 1}
    lut: jax.Array,  # (256,) uint8 exp table, lut[0] == 0
    *,
    qk_scale: float,
    big: float,
    lut_scale: float,
    p_scale: float,
    rescale: float,
    out_dtype=jnp.int8,
    bq: int = BQ,
    interpret: bool = False,
) -> jax.Array:
    """Fused int8 attention over a stacked batch of heads.

    Pads S to the ``bq`` row-block, and T/dh to lane multiples — all three
    paddings are exact (see module docstring) — runs the ``(B, Sp/bq)``
    grid, and slices back to the true extents."""
    b, s, dh = q_q.shape
    t = k_q.shape[1]
    bq = choose_bq(s, bq=bq)
    sp, tp, dp = _ceil_to(s, bq), _ceil_to(t, MIN_LANE), _ceil_to(dh, MIN_LANE)
    if (sp, dp) != (s, dh):
        q_q = jnp.pad(q_q, ((0, 0), (0, sp - s), (0, dp - dh)))
    if (tp, dp) != (t, dh):
        k_q = jnp.pad(k_q, ((0, 0), (0, tp - t), (0, dp - dh)))
        v_q = jnp.pad(v_q, ((0, 0), (0, tp - t), (0, dp - dh)))
    if (sp, tp) != (s, t):
        mask = jnp.pad(mask, ((0, 0), (0, sp - s), (0, tp - t)))  # 0 = masked
    kernel = functools.partial(
        _qattention_kernel,
        qk_scale=qk_scale, big=big, lut_scale=lut_scale,
        p_scale=p_scale, rescale=rescale, out_dtype=out_dtype,
    )
    out = pl.pallas_call(
        kernel,
        grid=(b, sp // bq),
        in_specs=[
            pl.BlockSpec((1, bq, dp), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, tp, dp), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, tp, dp), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, bq, tp), lambda i, j: (i, j, 0)),
            LUT_SPEC,
        ],
        out_specs=pl.BlockSpec((1, bq, dp), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b, sp, dp), out_dtype),
        interpret=interpret,
    )(q_q, k_q, v_q, mask, lut_table(lut))
    return out[:, :s, :dh]
