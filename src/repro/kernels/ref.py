"""Pure-jnp oracles for the Pallas kernels (and the dry-run lowering path).

These implement the artifact's op chain exactly — int32 accumulation, f32
rescale in codified order, round-half-even, clip — so that
``kernel(interpret=True) == ref == reference_runtime`` bit-for-bit.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def qmatmul_ref(
    x_q: jax.Array,  # (..., M, K) int8/uint8
    w_q: jax.Array,  # (K, N) int8
    bias_q: jax.Array | None,  # (N,) or (1, N) int32
    quant_scale: jax.Array,  # scalar or (N,) f32
    quant_shift: jax.Array,  # scalar or (N,) f32
    *,
    out_dtype=jnp.int8,
    relu: bool = False,
    two_mul: bool = True,
) -> jax.Array:
    """MatMulInteger → Add → Cast → Mul(→Mul) → [Relu] → QuantizeLinear."""
    acc = jax.lax.dot_general(
        x_q.astype(jnp.int32),
        w_q.astype(jnp.int32),
        dimension_numbers=(((x_q.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    if bias_q is not None:
        acc = acc + bias_q.reshape((1,) * (acc.ndim - 1) + (-1,)).astype(jnp.int32)
    f = acc.astype(jnp.float32)
    f = f * quant_scale.reshape((1,) * (f.ndim - 1) + (-1,)) if quant_scale.ndim else f * quant_scale
    if two_mul:
        f = f * (quant_shift.reshape((1,) * (f.ndim - 1) + (-1,)) if quant_shift.ndim else quant_shift)
    if relu:
        f = jnp.maximum(f, 0.0)
    r = jnp.rint(f)
    info = jnp.iinfo(out_dtype)
    return jnp.clip(r, info.min, info.max).astype(out_dtype)


def _split(x: jax.Array):
    """Veltkamp split: ``x == hi + lo`` exactly, each half with at most 12
    significant bits, so a product of two halves is exact in f32."""
    c = x * jnp.float32(4097.0)
    hi = c - (c - x)
    return hi, x - hi


def _nearest(a: jax.Array, b: jax.Array, q: jax.Array) -> jax.Array:
    """Move the positive quotient ``q`` of ``a / b`` one ulp toward the
    round-to-nearest-even result where it is not already that result.  The
    residual ``a - q·b`` is exact (Dekker's product, no FMA), and so are the
    half-gaps it is compared with (an ulp times ``b``)."""
    bits = jax.lax.bitcast_convert_type(q, jnp.int32)
    up = jax.lax.bitcast_convert_type(bits + 1, jnp.float32)
    dn = jax.lax.bitcast_convert_type(bits - 1, jnp.float32)
    p = q * b
    qh, ql = _split(q)
    bh, bl = _split(b)
    r2 = ((a - p) - (((qh * bh - p) + qh * bl + ql * bh) + ql * bl)) * 2.0
    odd = (bits & 1) == 1
    gap_up, gap_dn = (up - q) * b, (q - dn) * b
    go_up = (r2 > gap_up) | ((r2 == gap_up) & odd)
    go_dn = (-r2 > gap_dn) | ((-r2 == gap_dn) & odd)
    return jnp.where(go_up, up, jnp.where(go_dn, dn, q))


def div_rn(a: jax.Array, b: jax.Array) -> jax.Array:
    """IEEE ``a / b`` (round to nearest even) for integer-valued f32
    ``0 <= a`` and ``1 <= b < 2**24``, on any backend.

    TPU f32 division, in XLA and in Mosaic alike, is not correctly rounded
    (on a TPU v5e about a third of the quotients of such integers differ
    from IEEE in the last bit), so the hardware quotient is corrected twice against the exact
    residual: enough for one within two ulps.  Where division is IEEE the
    corrections change nothing."""
    q = _nearest(a, b, _nearest(a, b, a / b))
    return jnp.where(a == 0, jnp.float32(0.0), q)


def qact_lut_ref(x_q: jax.Array, lut: jax.Array) -> jax.Array:
    """256-entry LUT gather oracle."""
    return jnp.take(lut, x_q.astype(jnp.int32) + 128)


def qattention_ref(
    q_q: jax.Array,  # (..., S, dh) int8
    k_q: jax.Array,  # (..., T, dh) int8
    v_q: jax.Array,  # (..., T, dh) int8
    mask: jax.Array,  # (..., S, T) f32 {0, 1} validity/causality mask
    qk_scale: jax.Array,  # scalar f32: s_q * s_k / sqrt(dh)
    big: jax.Array,  # scalar f32: the additive mask penalty
    lut_scale: jax.Array,  # scalar f32: score-delta quantization step
    lut: jax.Array,  # (256,) uint8 exp table (lut[0] must be 0)
    p_scale: jax.Array,  # scalar f32: probability quantization (127.0)
    rescale: jax.Array,  # scalar f32: s_v / (p_scale * s_out)
    *,
    out_dtype=jnp.int8,
) -> jax.Array:
    """Fused int8 attention oracle: the exact op chain the PQ-IR attention
    region codifies (see ``repro.core.patterns.emit_qattention``), so that
    ``reference runtime == ref == kernel(interpret=True)`` bit-for-bit.

    Every step is either integer arithmetic or an IEEE-exact f32 elementwise
    op (the Div through :func:`div_rn`), so the chain is deterministic across
    numpy / XLA / Pallas and CPU / TPU:

        MatMulInteger(Q, K^T) → ×qk_scale → additive {0,-big} mask →
        ReduceMax/Sub (running-max-free softmax shift) → QuantizeLinear(ls) →
        exp via 256-entry LUT gather → ReduceSum (int32) → Div →
        ×p_scale → QuantizeLinear → MatMulInteger(P, V) → ×rescale →
        QuantizeLinear(out_dtype)
    """
    acc = jnp.matmul(q_q.astype(jnp.int32), jnp.swapaxes(k_q.astype(jnp.int32), -1, -2))
    s_f = acc.astype(jnp.float32) * qk_scale
    masked = s_f * mask + (mask - 1.0) * big
    mx = jnp.max(masked, axis=-1, keepdims=True)
    d = masked - mx  # ≤ 0 everywhere
    d_q = jnp.clip(jnp.rint(d / lut_scale), -128, 127).astype(jnp.int32)
    w = jnp.take(lut, d_q + 128)  # uint8 weights; masked positions hit lut[0] == 0
    den = jnp.sum(w.astype(jnp.int32), axis=-1, keepdims=True)
    p = div_rn(w.astype(jnp.float32), den.astype(jnp.float32))
    p_q = jnp.clip(jnp.rint(p * p_scale), -128, 127).astype(jnp.int32)
    ctx = jnp.matmul(p_q, v_q.astype(jnp.int32))
    f = ctx.astype(jnp.float32) * rescale
    info = jnp.iinfo(out_dtype)
    return jnp.clip(jnp.rint(f), info.min, info.max).astype(out_dtype)
