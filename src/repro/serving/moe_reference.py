"""Plain reference of the sparse-expert (Qwen2-MoE) decoder block.

The published ``Qwen2MoeDecoderLayer`` equations, applied to the artifact's
quantized semantics (int8 weight and activation codes, fixed-point
rescales), as one straightforward ``jax.numpy`` forward pass over whole
sequences: no kernels, no KV cache, no batching of requests, no graph.  It
reads the same :class:`~repro.serving.token_path.TokenPathParams` the
compiled path embeds, and :mod:`tests.test_moe_block` holds the compiled
prefill + decode to its logits.

Per layer::

    h   = x + o(attention(rope(q), rope(k), v))      q, k, v = qkv(rmsnorm(x))
    p   = softmax(h_n · W_router)                     h_n = rmsnorm(h)
    y   = Σ_{e in top4(h_n · W_router)} p_e · E_e(h_n) + σ(h_n · w_sg) · S(h_n)
    out = h + y                                       E, S = down(silu(gate) ⊙ up)

Departures from the float model, each as the artifact codifies it:

- every activation is an int8 code on the shared ``act_scale``, except the
  SwiGLU products (``glu_scale``); residual adds saturate;
- RMSNorm divides int8 codes by ``sqrt(mean(x²) + eps / s²)`` in f32;
- the rotary cos/sin tables are int16 codes of ``2**-14`` and q/k are
  rounded back to int8 after the rotation;
- attention's softmax is the int8 region's: score deltas quantized to steps
  of 0.125, exp from a 256-entry uint8 table, int8 probabilities;
- the top 4 experts are chosen on the int32 router logits (equal logits keep
  the lower expert index), their weights taken from the f32 softmax (its sum
  in expert order), not renormalised;
- each weighted expert output is rounded to ``1/256`` of a code and the four
  are summed in int32; the shared expert's gated output is added in f32 and
  the sum rounded once to int8.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from .token_path import TokenPathConfig, TokenPathParams

#: Fixed-point step of the expert combine and of the rotary tables.
FIXED = 256.0
ROPE_ONE = float(1 << 14)
#: The int8 attention region's constants.
LUT_SCALE, PENALTY, P_SCALE = 0.125, 30000.0, 127.0


def _q8(f):
    return jnp.clip(jnp.rint(f), -128, 127)


def _mm(spec, a, b):
    """An integer matmul of int-valued operands, exact in int32."""
    return jnp.einsum(spec, jnp.asarray(a).astype(jnp.int32), jnp.asarray(b).astype(jnp.int32)).astype(jnp.float32)


def _linear(x, p, *, f32: bool = False):
    """int8 codes times int8 weights, int32 bias, then the rescale: the
    fixed-point ``quant_scale · 2**-shift`` with rounding to int8 codes, or
    the f32 multiplier and no rounding where ``f32``."""
    acc = jnp.einsum("...k,kn->...n", x.astype(jnp.int32), jnp.asarray(p.weight_q, jnp.int32))
    if p.bias_q is not None:
        acc = acc + jnp.asarray(p.bias_q, jnp.int32)
    if f32:
        return acc.astype(jnp.float32) * np.float32(p.rescale.multiplier)
    return _q8(acc.astype(jnp.float32) * np.float32(p.rescale.quant_scale * 2.0 ** -p.rescale.shift))


def _rmsnorm(x, gamma, cfg: TokenPathConfig):
    xi = x.astype(jnp.int32)
    ms = jnp.sum(xi * xi, axis=-1, keepdims=True).astype(jnp.float32) * np.float32(1.0 / x.shape[-1])
    rms = jnp.sqrt(ms + np.float32(cfg.rms_eps / cfg.act_scale**2))
    return _q8(x / rms * (np.asarray(gamma, np.float32) / np.float32(cfg.act_scale)))


def _rope(x, positions, cfg: TokenPathConfig):
    """Rotate-half rotary embedding of each head at its token's position."""
    half = cfg.d_head // 2
    inv = 1.0 / (cfg.rope_theta ** (np.arange(half) * 2.0 / cfg.d_head))
    ang = np.arange(cfg.max_pos)[:, None] * inv[None, :]
    cos = jnp.asarray(np.rint(np.cos(ang) * ROPE_ONE), jnp.float32)[positions]  # (L, half)
    sin = jnp.asarray(np.rint(np.sin(ang) * ROPE_ONE), jnp.float32)[positions]
    xh = x.reshape(x.shape[:-1] + (cfg.n_heads, cfg.d_head))
    x1, x2 = xh[..., :half], xh[..., half:]
    c, s = cos[:, None, :], sin[:, None, :]
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return _q8(out * np.float32(1.0 / ROPE_ONE)).reshape(x.shape)


def _attention(q, k, v, cfg: TokenPathConfig):
    """Causal multi-head attention with the int8 region's LUT softmax."""
    length = q.shape[0]
    h, dh = cfg.n_heads, cfg.d_head
    q, k, v = (t.reshape(length, h, dh) for t in (q, k, v))
    scores = _mm("qhd,khd->hqk", q, k) * np.float32(cfg.act_scale**2 / math.sqrt(dh))
    causal = jnp.tril(jnp.ones((length, length), jnp.float32))
    masked = scores * causal + (causal - 1.0) * np.float32(PENALTY)
    delta = jnp.clip(jnp.rint((masked - masked.max(-1, keepdims=True)) / np.float32(LUT_SCALE)), -128, 127)
    table = np.clip(np.rint(np.exp(np.minimum(np.arange(256) - 128.0, 0.0) * LUT_SCALE) * 255.0), 0, 255)
    w = jnp.asarray(table, jnp.float32)[(delta + 128).astype(jnp.int32)]
    p = _q8(w / w.sum(-1, keepdims=True) * np.float32(P_SCALE))
    ctx = _mm("hqk,khd->qhd", p, v) * np.float32(1.0 / P_SCALE)
    return _q8(ctx).reshape(length, h * dh)


def _swiglu(g, u, cfg: TokenPathConfig):
    x = g * np.float32(cfg.act_scale)
    return _q8(x * jax.nn.sigmoid(x) * u * np.float32(cfg.act_scale / cfg.glu_scale))


def _softmax(f):
    """exp(f - max) over the experts, summed in expert order, divided."""
    e = jnp.exp(f - f.max(-1, keepdims=True))
    s = e[:, 0]
    for i in range(1, e.shape[-1]):
        s = s + e[:, i]
    return e / s[:, None]


def _moe(x, p, cfg: TokenPathConfig):
    """Top-k routed experts plus the gated shared expert, rounded to int8."""
    ex = p["experts"]
    logits = jnp.einsum("ld,de->le", x.astype(jnp.int32), jnp.asarray(ex.router, jnp.int32))
    probs = _softmax(logits.astype(jnp.float32) * np.float32(ex.router_scale))
    top = jax.lax.top_k(logits, cfg.top_k)[1]
    routed = jnp.zeros(x.shape, jnp.int32)
    for e in range(cfg.n_experts):
        weight = jnp.where((top == e).any(-1), probs[:, e], 0.0)[:, None]
        g = _q8(_mm("ld,df->lf", x, ex.gate[e]) * np.float32(ex.r_gate))
        u = _q8(_mm("ld,df->lf", x, ex.up[e]) * np.float32(ex.r_up))
        y = _mm("lf,fd->ld", _swiglu(g, u, cfg), ex.down[e]) * np.float32(ex.r_down)
        routed = routed + jnp.rint(jnp.clip(y * weight * np.float32(FIXED), -2.0**30, 2.0**30)).astype(jnp.int32)
    hs = _swiglu(_linear(x, p["shared_gate"]), _linear(x, p["shared_up"]), cfg)
    shared = _linear(hs, p["shared_down"], f32=True) * jax.nn.sigmoid(_linear(x, p["shared_router"], f32=True))
    return _q8(routed.astype(jnp.float32) * np.float32(1.0 / FIXED) + shared)


def forward(cfg: TokenPathConfig, params: TokenPathParams, tokens) -> jax.Array:
    """Logits ``(L, V)`` f32 of one causal pass over ``tokens (L,)``."""
    with jax.default_matmul_precision("highest"):
        tokens = jnp.asarray(tokens, jnp.int32)
        positions = jnp.arange(tokens.shape[0])
        x = jnp.asarray(params.embedding, jnp.float32)[tokens]
        d = cfg.d_model
        for p in params.layers:
            qkv = _linear(_rmsnorm(x, p["attn_norm"], cfg), p["qkv"])
            q = _rope(qkv[:, :d], positions, cfg)
            k = _rope(qkv[:, d:2 * d], positions, cfg)
            h = _q8(x + _linear(_attention(q, k, qkv[:, 2 * d:], cfg), p["o"]))
            x = _q8(h + _moe(_rmsnorm(h, p["ffn_norm"], cfg), p, cfg))
        xn = _rmsnorm(x, params.final_norm, cfg)
        acc = jnp.einsum("ld,dv->lv", xn.astype(jnp.int32), jnp.asarray(params.lm_head, jnp.int32))
        return acc.astype(jnp.float32) * np.float32(params.lm_scale)
