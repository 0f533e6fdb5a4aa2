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
    if jnp.issubdtype(out_dtype, jnp.floating):
        return f.astype(out_dtype)
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


def div_signed(a: jax.Array, b: jax.Array) -> jax.Array:
    """IEEE ``a / b`` for any sign of ``a`` and ``b > 0`` (:func:`div_rn` of
    the magnitude; rounding to nearest is symmetric)."""
    return jnp.where(a < 0, -div_rn(-a, b), div_rn(a, b))


def sqrt_rn(a: jax.Array) -> jax.Array:
    """The f32 root of ``a >= 0`` that is nearest ``sqrt(a)`` in the sense
    ``|a - c·c|`` least (ties: even), on any backend: the hardware root and
    its neighbours up to two ulps away, compared by their exact residuals.
    TPU square roots are not correctly rounded; this makes the result a
    function of ``a`` alone, within one ulp of the true root."""
    return _nearest_root(a, jnp.sqrt(a))


def _nearest_root(a: jax.Array, s: jax.Array) -> jax.Array:
    """:func:`sqrt_rn` from a root ``s`` within two ulps of the true one."""
    bits = jax.lax.bitcast_convert_type(s, jnp.int32)

    def residual(c):
        p = c * c
        ch, cl = _split(c)
        return jnp.abs((a - p) - (((ch * ch - p) + 2.0 * (ch * cl)) + cl * cl))

    best, best_r = s, residual(s)
    for d in (-2, -1, 1, 2):
        cb = jnp.maximum(bits + d, 0)
        c = jax.lax.bitcast_convert_type(cb, jnp.float32)
        r = residual(c)
        better = (r < best_r) | ((r == best_r) & ((cb & 1) == 0))
        best, best_r = jnp.where(better, c, best), jnp.where(better, r, best_r)
    return jnp.where(a == 0, jnp.float32(0.0), best)


def softmax_rn(x: jax.Array) -> jax.Array:
    """Softmax over the last axis whose value depends on ``x`` alone, not on
    the array's shape: ``exp(x - max)`` summed in index order, each quotient
    the nearest f32 (:func:`div_rn`).  XLA's own softmax on a TPU reduces in
    a shape-dependent order (the same router logits gave different weights
    in a ``(32, 1, 60)`` decode and a ``(2, 1024, 60)`` batch)."""
    e = jnp.exp(x - jnp.max(x, axis=-1, keepdims=True))
    s = e[..., 0]
    for i in range(1, e.shape[-1]):
        s = s + e[..., i]
    return div_rn(e, s[..., None])


def rmsnorm_ref(x_q: jax.Array, gain: jax.Array, inv_d, eps) -> jax.Array:
    """RMSNorm of int8 codes ``(..., D)`` as the artifact codifies it
    (``repro.core.patterns.emit_rmsnorm``): the int32 sum of squares, its
    mean plus ``eps`` in f32, ``x / sqrt(.)`` and ``× gain``, rounded to int8.
    The root and the quotient are the nearest f32 on every backend
    (:func:`sqrt_rn`, :func:`div_signed`)."""
    xi = x_q.astype(jnp.int32)
    ss = jnp.sum(xi * xi, axis=-1, keepdims=True, dtype=jnp.int32)
    den = sqrt_rn(ss.astype(jnp.float32) * inv_d + eps)
    y = div_signed(x_q.astype(jnp.float32), den) * gain
    return jnp.clip(jnp.rint(y), -128, 127).astype(jnp.int8)


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


#: Fixed-point step of the expert combine: each weighted expert output is
#: rounded to ``1 / MOE_FIXED`` of an activation code and summed in int32,
#: which is exact in any order.  ``MOE_CLIP`` bounds it before the cast.
MOE_FIXED = 256.0
MOE_CLIP = 2.0**30


def _round_clip8(f: jax.Array) -> jax.Array:
    """QuantizeLinear(scale=1, zp=int8 0) as an f32 value: round half to even
    and clip to the int8 range."""
    return jnp.clip(jnp.rint(f), -128.0, 127.0)


def swiglu_ref(g_acc, u_acc, r_g, s_g, r_u, r_h) -> jax.Array:
    """The codified SwiGLU product of one expert, from its gate and up int32
    accumulators: both rescaled and rounded to int8 codes, the gate
    dequantized (``× s_g``) through an f32 SiLU ``x · sigmoid(x)``, times the
    up code, rescaled by ``r_h`` and rounded to int8."""
    gx = _round_clip8(g_acc.astype(jnp.float32) * r_g) * s_g
    si = gx * jax.nn.sigmoid(gx)
    u = _round_clip8(u_acc.astype(jnp.float32) * r_u)
    return _round_clip8((si * u) * r_h).astype(jnp.int8)


def combine_ref(d_acc, weight, r_d) -> jax.Array:
    """One expert's down-projection accumulator as its weighted fixed-point
    contribution: ``rint(clip(acc · r_d · weight · MOE_FIXED))`` in int32."""
    c = (d_acc.astype(jnp.float32) * r_d) * weight
    c = jnp.clip(c * jnp.float32(MOE_FIXED), -MOE_CLIP, MOE_CLIP)
    return jnp.rint(c).astype(jnp.int32)


def route_weights(idx: jax.Array, probs: jax.Array) -> jax.Array:
    """Dense per-expert weights ``(..., E)``: the softmax probability of each
    chosen expert (``idx (..., K)``), zero for every other one."""
    chosen = jax.nn.one_hot(idx, probs.shape[-1], dtype=jnp.float32).sum(axis=-2)
    return probs * chosen


def qmoe_ref(
    x_q: jax.Array,  # (T, D) int8 rows
    idx: jax.Array,  # (T, K) chosen experts
    probs: jax.Array,  # (T, E) f32 router softmax
    w_gate: jax.Array,  # (E, D, F) int8
    w_up: jax.Array,  # (E, D, F) int8
    w_down: jax.Array,  # (E, F, D) int8 (int4 values on the w4 lane)
    *,
    r_g, s_g, r_u, r_h, r_d,
) -> jax.Array:
    """Routed-expert oracle: the region's semantic form, every expert on
    every row with weight zero where it was not chosen, contributions summed
    in int32.  Returns ``(T, D)`` int32 in ``1 / MOE_FIXED`` code units."""
    x = x_q.astype(jnp.int32)
    g = jnp.einsum("td,edf->tef", x, w_gate.astype(jnp.int32), preferred_element_type=jnp.int32)
    u = jnp.einsum("td,edf->tef", x, w_up.astype(jnp.int32), preferred_element_type=jnp.int32)
    h = swiglu_ref(g, u, r_g, s_g, r_u, r_h).astype(jnp.int32)
    d = jnp.einsum("tef,efd->ted", h, w_down.astype(jnp.int32), preferred_element_type=jnp.int32)
    w = route_weights(idx, probs)[..., None]
    return combine_ref(d, w, r_d).sum(axis=1, dtype=jnp.int32)
