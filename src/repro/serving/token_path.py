"""The transformer token path codified in PQ-IR: prefill + decode artifacts
with the int8 KV cache as persistent plan state.

This module is the paper's co-design story applied to serving: the whole
transformer block — joint QKV projection, per-head fused int8 attention,
output projection, saturating residuals, MLP — is *codified* as two PQ-IR
graphs and compiled once each:

* **prefill** — ``tokens ("N","S")`` + causal ``mask ("N","S","S")`` in,
  f32 logits and the per-layer int8 K/V rows out.  Compiles to a two-axis
  ``("N","S")`` artifact; prompts run at their (batch, prompt-bucket) cell.
* **decode** — ``tokens ("N",1)`` + scatter ``onehot ("N","S",1)`` + validity
  ``mask ("N",1,"S")`` in, with the per-layer KV caches declared as
  :class:`repro.core.pqir.StateSpec` **state slots**: the lowering pins their
  buffers across invocations and ``specialize_plan`` binds their seq extent
  per bucket.  One token per step, zero re-lowering per step.

The KV update is itself codified — int8 elementwise, exact under padding::

    new_kv = kv * (1 - onehot) + kv_new * onehot

Both graphs share one :class:`~repro.backend.plan.PlanCache` (graph-qualified
keys), so a serving engine holds exactly one specialization per visited
(batch × seq-bucket) cell across prefill *and* decode.

Every layer's projections ride the fused qlinear lane (sub-8-bit weights
included — ``bits_*`` config fields), attention rides the fused ``qattention``
kernel, and the jnp mirrors (:func:`prefill_jax` / :func:`decode_jax`) are
bit-exact against the compiled artifacts — the differential sweep in
``tests/test_token_path.py`` pins all three runtimes against each other.

:class:`CompiledTokenAdapter` plugs the compiled pair into
:class:`repro.serving.engine.ServeEngine` behind its four-method adapter
seam.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..backend.plan import PlanCache
from ..core import pqir
from ..core.compile import CompiledModel, compile_model
from ..core.patterns import (
    ATTN_BIG,
    ATTN_LUT_SCALE,
    ATTN_P_SCALE,
    ROPE_FRAC_BITS,
    build_exp_lut,
    emit_moe_experts,
    emit_qattention,
    emit_rmsnorm,
    emit_rope,
    emit_rope_tables,
    emit_round_clip,
    emit_router,
    emit_swiglu,
    fc_layer,
    fc_layer_f32,
    rope_tables,
)
from ..core.quant import QuantizedLinearParams, quantize_linear_layer
from ..kernels import ref as _ref
from ..kernels.ref import MOE_FIXED
from ..obs import trace as _trace
from ..obs.metrics import default_registry

__all__ = [
    "TokenPathConfig",
    "TokenPathParams",
    "make_token_params",
    "build_prefill_model",
    "build_decode_model",
    "prefill_jax",
    "decode_jax",
    "CompiledTokenPath",
    "CompiledTokenAdapter",
    "causal_mask",
]


@dataclasses.dataclass(frozen=True)
class TokenPathConfig:
    """Shape + precision config for the codified transformer block.

    Activations live on one shared int8 scale (``act_scale``) — residual adds
    are then plain saturating code-domain adds, and the attention rescale
    collapses to ``1 / p_scale``.  ``bits_*`` select the weight lane per
    projection (4 ⇒ QONNX-style ``weight_bits`` attribute, packed-int4 kernel
    on the tiled backends), so one model mixes w4 and w8 layers."""

    vocab: int = 128
    d_model: int = 64
    n_heads: int = 2
    d_ff: int = 128
    n_layers: int = 2
    act_scale: float = 0.05
    lm_scale: float = 0.01
    bits_qkv: int = 4
    bits_o: int = 8
    bits_up: int = 8
    bits_down: int = 4
    #: ``"toy"``: the codified block above (MHA, ReLU MLP, no norm or
    #: positions).  ``"moe"``: a sparse-expert decoder block (Qwen2-MoE):
    #: RMSNorm, rotary positions, ``n_experts`` routed SwiGLU experts of width
    #: ``d_expert`` with top-``top_k`` routing, and a sigmoid-gated shared
    #: SwiGLU expert of width ``d_ff``; positions become a graph input.
    block: str = "toy"
    n_experts: int = 0
    top_k: int = 0
    d_expert: int = 0
    bits_expert_down: int = 4
    rms_eps: float = 1e-6
    rope_theta: float = 1e6
    max_pos: int = 1024
    #: Scale of the SwiGLU products (routed and shared): ``silu(g)·u`` is
    #: quadratic in the activations and gets its own int8 scale.
    glu_scale: float = 0.025

    @property
    def d_head(self) -> int:
        return self.d_model // self.n_heads

    @property
    def positions(self) -> bool:
        """Whether the graphs take token positions (rotary blocks do)."""
        return self.block != "toy"

    @property
    def qk_scale(self) -> float:
        return float(self.act_scale * self.act_scale / np.sqrt(self.d_head))

    @property
    def att_rescale(self) -> float:
        # s_v / (p_scale * s_out) with s_v == s_out == act_scale
        return float(1.0 / ATTN_P_SCALE)


@dataclasses.dataclass
class TokenPathParams:
    """Pre-quantized parameters of the token path (what the artifact embeds).

    A ``"moe"`` layer's dict holds ``qkv``/``o`` and the shared expert's
    ``shared_gate``/``shared_up``/``shared_down``/``shared_router`` as
    :class:`QuantizedLinearParams`, the norm gains ``attn_norm``/``ffn_norm``
    (γ, f32 ``(D,)``) and the routed experts as :class:`RoutedExperts`."""

    embedding: np.ndarray  # (vocab, d_model) int8 codes; row 0 all-zero
    layers: List[Dict[str, object]]
    lm_head: np.ndarray  # (d_model, vocab) int8
    lm_scale: float
    final_norm: Optional[np.ndarray] = None  # γ of the last RMSNorm ("moe")


@dataclasses.dataclass
class RoutedExperts:
    """One sparse-expert layer's router and stacked expert weights.

    The router's int32 logits times ``router_scale`` are its f32 logits; each
    expert projection has one f32 rescale across the experts."""

    router: np.ndarray  # (D, E) int8
    router_scale: float
    gate: np.ndarray  # (E, D, F) int8
    up: np.ndarray  # (E, D, F) int8
    down: np.ndarray  # (E, F, D) int8 container; int4 values when bits_down == 4
    r_gate: float
    r_up: float
    r_down: float
    bits_down: int = 4


def make_token_params(cfg: TokenPathConfig, seed: int = 0) -> TokenPathParams:
    """Deterministic pre-quantized parameters.  Weights are drawn small enough
    that activations stay inside int8 on typical inputs (bit-exactness never
    depends on this — saturation is itself exact — it just keeps the logits
    informative)."""
    if cfg.block == "moe":
        return _make_moe_params(cfg, seed)
    rng = np.random.default_rng(seed)
    emb = rng.integers(-40, 41, (cfg.vocab, cfg.d_model)).astype(np.int8)
    emb[0] = 0  # token 0 doubles as padding: zero embedding
    s = cfg.act_scale

    def lin(n_in: int, n_out: int, bits: int) -> QuantizedLinearParams:
        w = rng.normal(size=(n_in, n_out)).astype(np.float32) * (0.6 / np.sqrt(n_in))
        b = rng.normal(size=(n_out,)).astype(np.float32) * 0.02
        return quantize_linear_layer(w, b, s, s, bits=bits)

    layers = []
    for _ in range(cfg.n_layers):
        layers.append(
            {
                "qkv": lin(cfg.d_model, 3 * cfg.d_model, cfg.bits_qkv),
                "o": lin(cfg.d_model, cfg.d_model, cfg.bits_o),
                "up": lin(cfg.d_model, cfg.d_ff, cfg.bits_up),
                "down": lin(cfg.d_ff, cfg.d_model, cfg.bits_down),
            }
        )
    head = rng.integers(-64, 65, (cfg.d_model, cfg.vocab)).astype(np.int8)
    return TokenPathParams(emb, layers, head, cfg.lm_scale)


def _make_moe_params(cfg: TokenPathConfig, seed: int) -> TokenPathParams:
    """Seeded parameters of the sparse-expert block: per-tensor weight
    scales (one across all experts of a projection), γ near 1."""
    rng = np.random.default_rng(seed)
    s, glu = cfg.act_scale, cfg.glu_scale
    D, E, F = cfg.d_model, cfg.n_experts, cfg.d_expert
    emb = rng.integers(-40, 41, (cfg.vocab, D)).astype(np.int8)
    emb[0] = 0

    def lin(n_in, n_out, bits, *, bias=False, s_in=s, s_out=s):
        w = rng.normal(size=(n_in, n_out)).astype(np.float32) * (0.6 / np.sqrt(n_in))
        b = rng.normal(size=(n_out,)).astype(np.float32) * 0.02 if bias else None
        return quantize_linear_layer(w, b, s_in, s_out, bits=bits)

    def stack(shape, bits):
        w = rng.normal(size=shape).astype(np.float32) * (0.6 / np.sqrt(shape[1]))
        qmax = 7 if bits == 4 else 127
        scale = float(np.abs(w).max()) / qmax
        return np.clip(np.rint(w / scale), -qmax, qmax).astype(np.int8), scale

    def gain():
        return (1.0 + 0.1 * rng.normal(size=(D,))).astype(np.float32)

    layers = []
    for _ in range(cfg.n_layers):
        router, sr = stack((1, D, E), 8)
        gate, sg = stack((E, D, F), 8)
        up, su = stack((E, D, F), 8)
        down, sd = stack((E, F, D), cfg.bits_expert_down)
        layers.append({
            "attn_norm": gain(),
            "qkv": lin(D, 3 * D, cfg.bits_qkv, bias=True),
            "o": lin(D, D, cfg.bits_o),
            "ffn_norm": gain(),
            "experts": RoutedExperts(
                # four times the router codes' scale: logits of std about 2, a decisive top-k
                router=router[0], router_scale=float(np.float32(s * sr * 4.0)),
                gate=gate, up=up, down=down,
                r_gate=float(np.float32(sg)), r_up=float(np.float32(su)),
                r_down=float(np.float32(glu * sd / s)), bits_down=cfg.bits_expert_down,
            ),
            "shared_gate": lin(D, cfg.d_ff, cfg.bits_up),
            "shared_up": lin(D, cfg.d_ff, cfg.bits_up),
            "shared_down": lin(cfg.d_ff, D, cfg.bits_down, s_in=glu),
            "shared_router": lin(D, 1, 8, s_out=1.0),
        })
    head = rng.integers(-64, 65, (D, cfg.vocab)).astype(np.int8)
    return TokenPathParams(emb, layers, head, cfg.lm_scale, final_norm=gain())


# ---------------------------------------------------------------------------
# PQ-IR emission
# ---------------------------------------------------------------------------

def _slice_feat(gb: pqir.GraphBuilder, x: str, lo: int, hi: int, prefix: str) -> str:
    """Slice [lo, hi) of the trailing feature axis (axis 2)."""
    st = gb.add_initializer(f"{prefix}_starts", np.array([lo], np.int64))
    en = gb.add_initializer(f"{prefix}_ends", np.array([hi], np.int64))
    ax = gb.add_initializer(f"{prefix}_axes", np.array([2], np.int64))
    return gb.op("Slice", [x, st, en, ax], out_hint=f"{prefix}_out")


def _residual(gb: pqir.GraphBuilder, a: str, b: str, prefix: str) -> str:
    """Saturating int8 residual: both operands share act_scale, so the add is
    code-domain — Cast f32 (exact for int8), Add, round+clip back to int8."""
    fa = gb.op("Cast", [a], out_hint=f"{prefix}_a_f", to="float32")
    fb = gb.op("Cast", [b], out_hint=f"{prefix}_b_f", to="float32")
    sm = gb.op("Add", [fa, fb], out_hint=f"{prefix}_sum")
    return emit_round_clip(gb, sm, prefix)


def _kv_update(gb: pqir.GraphBuilder, state: str, new: str, onehot: str, prefix: str) -> str:
    """``new_kv = kv·(1-onehot) + kv_new·onehot`` — int8 elementwise (codes are
    bounded by ±127·1, so no overflow), exact under zero padding: padded rows
    have onehot 0 and state 0, contributing 0."""
    one = gb.add_initializer(f"{prefix}_one", np.int8(1))
    keep = gb.op("Sub", [one, onehot], out_hint=f"{prefix}_keep")
    kept = gb.op("Mul", [state, keep], out_hint=f"{prefix}_kept")
    put = gb.op("Mul", [new, onehot], out_hint=f"{prefix}_put")
    return gb.op("Add", [kept, put], out_hint=f"{prefix}_new")


def _attention(
    gb: pqir.GraphBuilder,
    cfg: TokenPathConfig,
    q_full: str,
    k_full: str,
    v_full: str,
    mask: str,
    prefix: str,
) -> str:
    """Per-head fused attention regions + head concat over the feature axis."""
    dh = cfg.d_head
    heads = []
    for h in range(cfg.n_heads):
        qh = _slice_feat(gb, q_full, h * dh, (h + 1) * dh, f"{prefix}_q{h}")
        kh = _slice_feat(gb, k_full, h * dh, (h + 1) * dh, f"{prefix}_k{h}")
        vh = _slice_feat(gb, v_full, h * dh, (h + 1) * dh, f"{prefix}_v{h}")
        heads.append(
            emit_qattention(
                gb, qh, kh, vh, mask, f"{prefix}_att{h}",
                qk_scale=cfg.qk_scale, rescale=cfg.att_rescale,
            )
        )
    if len(heads) == 1:
        return heads[0]
    return gb.op("Concat", heads, out_hint=f"{prefix}_ctx", axis=2)


def _mlp(gb, x: str, p: Dict[str, QuantizedLinearParams], prefix: str) -> str:
    up = fc_layer(gb, x, p["up"], f"{prefix}_up", activation="Relu")
    return fc_layer(gb, up, p["down"], f"{prefix}_down")


def _lm_head(gb, cfg: TokenPathConfig, params: TokenPathParams, x: str) -> str:
    """Unfused f32 logits: MatMulInteger → Cast → Mul(lm_scale)."""
    w = gb.add_initializer("lm_head_q", params.lm_head)
    acc = gb.op("MatMulInteger", [x, w], out_hint="lm_acc")
    f = gb.op("Cast", [acc], out_hint="lm_f", to="float32")
    sc = gb.add_initializer("lm_scale", np.float32(params.lm_scale))
    return gb.op("Mul", [f, sc], out_hint="logits")


def build_prefill_model(cfg: TokenPathConfig, params: TokenPathParams) -> pqir.Model:
    """The two-axis prefill artifact: logits + per-layer K/V cache rows.

    Outputs: ``logits ("N","S",V) f32`` first, then the K and V cache rows
    ``("N","S",D) int8`` per layer, in the same (k, v) × layer order as the
    decode graph's declared states — :class:`CompiledTokenPath` zips the two,
    so a prefilled cache feeds decode directly."""
    if cfg.block == "moe":
        return _build_moe_model(cfg, params, decode=False)
    D, V = cfg.d_model, cfg.vocab
    gb = pqir.GraphBuilder("token_prefill")
    gb.add_input("tokens", "int32", ("N", "S"))
    gb.add_input("mask", "float32", ("N", "S", "S"))
    table = gb.add_initializer("embedding_q", params.embedding)
    x = gb.op("Gather", [table, "tokens"], out_hint="emb", axis=0)
    kv_outs: List[Tuple[str, str]] = []
    for l, p in enumerate(params.layers):
        pfx = f"l{l}"
        qkv = fc_layer(gb, x, p["qkv"], f"{pfx}_qkv")
        qf = _slice_feat(gb, qkv, 0, D, f"{pfx}_qs")
        kf = _slice_feat(gb, qkv, D, 2 * D, f"{pfx}_ks")
        vf = _slice_feat(gb, qkv, 2 * D, 3 * D, f"{pfx}_vs")
        ctx = _attention(gb, cfg, qf, kf, vf, "mask", pfx)
        o = fc_layer(gb, ctx, p["o"], f"{pfx}_o")
        x1 = _residual(gb, x, o, f"{pfx}_res1")
        x = _residual(gb, x1, _mlp(gb, x1, p, pfx), f"{pfx}_res2")
        kv_outs.append((kf, vf))
    logits = _lm_head(gb, cfg, params, x)
    gb.add_output(logits, "float32", ("N", "S", V))
    for l, (kf, vf) in enumerate(kv_outs):
        # renamed via identity-free aliasing: the Slice outputs *are* the
        # cache rows; expose them under the decode state-input names
        gb.add_output(kf, "int8", ("N", "S", D))
        gb.add_output(vf, "int8", ("N", "S", D))
    return gb.build(opset=17)


def build_decode_model(cfg: TokenPathConfig, params: TokenPathParams) -> pqir.Model:
    """The one-token decode artifact with KV state slots.

    Inputs: ``tokens ("N",1)``, ``onehot ("N","S",1) int8`` (scatter position
    of the new K/V row), ``mask ("N",1,"S")`` (validity: positions ≤ current),
    plus per-layer state inputs ``k_cache_l`` / ``v_cache_l ("N","S",D)``.
    Each state's updated tensor is both a graph output and a declared
    :class:`~repro.core.pqir.StateSpec`, so the lowering pins its buffers."""
    if cfg.block == "moe":
        return _build_moe_model(cfg, params, decode=True)
    D, V = cfg.d_model, cfg.vocab
    gb = pqir.GraphBuilder("token_decode")
    gb.add_input("tokens", "int32", ("N", 1))
    gb.add_input("onehot", "int8", ("N", "S", 1))
    gb.add_input("mask", "float32", ("N", 1, "S"))
    for l in range(cfg.n_layers):
        gb.add_input(f"k_cache_{l}", "int8", ("N", "S", D))
        gb.add_input(f"v_cache_{l}", "int8", ("N", "S", D))
    table = gb.add_initializer("embedding_q", params.embedding)
    x = gb.op("Gather", [table, "tokens"], out_hint="emb", axis=0)
    updates: List[Tuple[str, str]] = []
    for l, p in enumerate(params.layers):
        pfx = f"l{l}"
        qkv = fc_layer(gb, x, p["qkv"], f"{pfx}_qkv")
        qf = _slice_feat(gb, qkv, 0, D, f"{pfx}_qs")
        kn = _slice_feat(gb, qkv, D, 2 * D, f"{pfx}_ks")
        vn = _slice_feat(gb, qkv, 2 * D, 3 * D, f"{pfx}_vs")
        k_upd = _kv_update(gb, f"k_cache_{l}", kn, "onehot", f"{pfx}_kupd")
        v_upd = _kv_update(gb, f"v_cache_{l}", vn, "onehot", f"{pfx}_vupd")
        ctx = _attention(gb, cfg, qf, k_upd, v_upd, "mask", pfx)
        o = fc_layer(gb, ctx, p["o"], f"{pfx}_o")
        x1 = _residual(gb, x, o, f"{pfx}_res1")
        x = _residual(gb, x1, _mlp(gb, x1, p, pfx), f"{pfx}_res2")
        updates.append((k_upd, v_upd))
    logits = _lm_head(gb, cfg, params, x)
    gb.add_output(logits, "float32", ("N", 1, V))
    for l, (k_upd, v_upd) in enumerate(updates):
        gb.add_output(k_upd, "int8", ("N", "S", D))
        gb.add_output(v_upd, "int8", ("N", "S", D))
        gb.add_state(f"kv{l}_k", input=f"k_cache_{l}", output=k_upd)
        gb.add_state(f"kv{l}_v", input=f"v_cache_{l}", output=v_upd)
    return gb.build(opset=17)


def _moe_block(gb, cfg: TokenPathConfig, p: Dict[str, object], x: str, rope, attend, pfx: str):
    """One sparse-expert decoder layer on int8 codes ``x``: pre-norm
    attention with rotary q/k, then pre-norm routed + shared experts.
    ``attend(q, k, v)`` returns ``(ctx, k_state, v_state)``.  Returns the
    layer's output, its K/V states and the router's chosen experts."""
    s, D = cfg.act_scale, cfg.d_model
    eps = cfg.rms_eps / (s * s)
    cos, sin, perm = rope
    xn = emit_rmsnorm(gb, x, p["attn_norm"] / np.float32(s), eps, f"{pfx}_ln1")
    qkv = fc_layer(gb, xn, p["qkv"], f"{pfx}_qkv")
    q = emit_rope(gb, _slice_feat(gb, qkv, 0, D, f"{pfx}_qs"), cos, sin, perm, f"{pfx}_qrope")
    k = emit_rope(gb, _slice_feat(gb, qkv, D, 2 * D, f"{pfx}_ks"), cos, sin, perm, f"{pfx}_krope")
    ctx, k_st, v_st = attend(q, k, _slice_feat(gb, qkv, 2 * D, 3 * D, f"{pfx}_vs"))
    x1 = _residual(gb, x, fc_layer(gb, ctx, p["o"], f"{pfx}_o"), f"{pfx}_res1")
    h = emit_rmsnorm(gb, x1, p["ffn_norm"] / np.float32(s), eps, f"{pfx}_ln2")
    ex: RoutedExperts = p["experts"]
    idx, probs = emit_router(gb, h, ex.router, ex.router_scale, cfg.top_k, f"{pfx}_router")
    routed = emit_moe_experts(
        gb, h, idx, probs, ex.gate, ex.up, ex.down, f"{pfx}_moe",
        r_g=ex.r_gate, s_g=s, r_u=ex.r_up, r_h=s / cfg.glu_scale, r_d=ex.r_down,
        bits_down=ex.bits_down,
    )
    g = fc_layer(gb, h, p["shared_gate"], f"{pfx}_sh_gate")
    u = fc_layer(gb, h, p["shared_up"], f"{pfx}_sh_up")
    hs = emit_swiglu(gb, g, u, s, s / cfg.glu_scale, f"{pfx}_sh_glu")
    ys = fc_layer_f32(gb, hs, p["shared_down"], f"{pfx}_sh_down")
    gate = gb.op("Sigmoid", [fc_layer_f32(gb, h, p["shared_router"], f"{pfx}_sh_router")], out_hint=f"{pfx}_sh_sig")
    shared = gb.op("Mul", [ys, gate], out_hint=f"{pfx}_sh_out")
    rf = gb.op("Cast", [routed], out_hint=f"{pfx}_routed_f", to="float32")
    unit = gb.add_initializer(f"{pfx}_fixed_unit", np.float32(1.0 / MOE_FIXED))
    total = gb.op("Add", [gb.op("Mul", [rf, unit], out_hint=f"{pfx}_routed"), shared], out_hint=f"{pfx}_ffn")
    out = emit_round_clip(gb, total, f"{pfx}_ffn")
    return _residual(gb, x1, out, f"{pfx}_res2"), k_st, v_st, idx


def _build_moe_model(cfg: TokenPathConfig, params: TokenPathParams, *, decode: bool) -> pqir.Model:
    """The sparse-expert block's prefill or decode artifact.  As the toy
    graphs, plus ``positions`` (``("N","S")`` / ``("N",1)`` int32) in, and
    each layer's chosen experts ``("N","S",K)`` int32 out after the K/V
    outputs."""
    D, V, K = cfg.d_model, cfg.vocab, cfg.top_k
    s_axis = 1 if decode else "S"
    gb = pqir.GraphBuilder("token_decode" if decode else "token_prefill")
    gb.add_input("tokens", "int32", ("N", s_axis))
    gb.add_input("positions", "int32", ("N", s_axis))
    if decode:
        gb.add_input("onehot", "int8", ("N", "S", 1))
        gb.add_input("mask", "float32", ("N", 1, "S"))
        for l in range(cfg.n_layers):
            gb.add_input(f"k_cache_{l}", "int8", ("N", "S", D))
            gb.add_input(f"v_cache_{l}", "int8", ("N", "S", D))
    else:
        gb.add_input("mask", "float32", ("N", "S", "S"))
    cos_t, sin_t, perm = rope_tables(cfg.max_pos, cfg.n_heads, cfg.d_head, cfg.rope_theta)
    cos, sin = emit_rope_tables(gb, "positions", cos_t, sin_t, "rope")
    table = gb.add_initializer("embedding_q", params.embedding)
    x = gb.op("Gather", [table, "tokens"], out_hint="emb", axis=0)
    states, experts = [], []
    for l, p in enumerate(params.layers):
        pfx = f"l{l}"

        def attend(q, k, v, l=l, pfx=pfx):
            if decode:
                k = _kv_update(gb, f"k_cache_{l}", k, "onehot", f"{pfx}_kupd")
                v = _kv_update(gb, f"v_cache_{l}", v, "onehot", f"{pfx}_vupd")
            return _attention(gb, cfg, q, k, v, "mask", pfx), k, v

        x, k_st, v_st, idx = _moe_block(gb, cfg, p, x, (cos, sin, perm), attend, pfx)
        states.append((k_st, v_st))
        experts.append(idx)
    xn = emit_rmsnorm(gb, x, params.final_norm / np.float32(cfg.act_scale),
                      cfg.rms_eps / cfg.act_scale**2, "final_norm")
    gb.add_output(_lm_head(gb, cfg, params, xn), "float32", ("N", s_axis, V))
    for l, (k_st, v_st) in enumerate(states):
        seq = "S"
        gb.add_output(k_st, "int8", ("N", seq, D))
        gb.add_output(v_st, "int8", ("N", seq, D))
        if decode:
            gb.add_state(f"kv{l}_k", input=f"k_cache_{l}", output=k_st)
            gb.add_state(f"kv{l}_v", input=f"v_cache_{l}", output=v_st)
    for idx in experts:
        gb.add_output(idx, "int32", ("N", s_axis, K))
    return gb.build(opset=17)


# ---------------------------------------------------------------------------
# jnp mirrors — the opaque-JAX twin the compiled artifacts are pinned against
# ---------------------------------------------------------------------------

def _fc_jax(x_q, p: QuantizedLinearParams, *, relu: bool = False):
    r = p.rescale
    return _ref.qmatmul_ref(
        jnp.asarray(x_q), jnp.asarray(p.weight_q),
        None if p.bias_q is None else jnp.asarray(p.bias_q),
        jnp.float32(r.quant_scale), jnp.float32(r.quant_shift),
        relu=relu, two_mul=True,
    )


def _residual_jax(a, b):
    s = a.astype(jnp.float32) + b.astype(jnp.float32)
    return jnp.clip(jnp.rint(s), -128, 127).astype(jnp.int8)


def _attention_jax(cfg: TokenPathConfig, q, k, v, mask, lut):
    dh = cfg.d_head
    heads = []
    for h in range(cfg.n_heads):
        sl = slice(h * dh, (h + 1) * dh)
        heads.append(
            _ref.qattention_ref(
                q[..., sl], k[..., sl], v[..., sl], mask,
                jnp.float32(cfg.qk_scale), jnp.float32(ATTN_BIG),
                jnp.float32(ATTN_LUT_SCALE), jnp.asarray(lut),
                jnp.float32(ATTN_P_SCALE), jnp.float32(cfg.att_rescale),
                out_dtype=jnp.int8,
            )
        )
    return jnp.concatenate(heads, axis=-1)


def _block_jax(cfg, p, x, k_full, v_full, q_full, mask, lut):
    ctx = _attention_jax(cfg, q_full, k_full, v_full, mask, lut)
    o = _fc_jax(ctx, p["o"])
    x1 = _residual_jax(x, o)
    up = _fc_jax(x1, p["up"], relu=True)
    down = _fc_jax(up, p["down"])
    return _residual_jax(x1, down)


def _logits_jax(params: TokenPathParams, x):
    acc = jnp.matmul(x.astype(jnp.int32), jnp.asarray(params.lm_head).astype(jnp.int32))
    return acc.astype(jnp.float32) * jnp.float32(params.lm_scale)


def _round_clip_jax(f):
    return jnp.clip(jnp.rint(f), -128, 127).astype(jnp.int8)


def _rmsnorm_jax(x, gamma, cfg: TokenPathConfig):
    return _ref.rmsnorm_ref(
        x, jnp.asarray(gamma / np.float32(cfg.act_scale)), np.float32(1.0 / x.shape[-1]),
        np.float32(cfg.rms_eps / cfg.act_scale**2),
    )


def _rope_jax(x, cos, sin, perm):
    xi = x.astype(jnp.int32)
    f = (xi * cos + jnp.take(xi, perm, axis=2) * sin).astype(jnp.float32)
    return _round_clip_jax(f * np.float32(2.0 ** -ROPE_FRAC_BITS))


def _fc_f32_jax(x_q, p: QuantizedLinearParams):
    return _ref.qmatmul_ref(
        jnp.asarray(x_q), jnp.asarray(p.weight_q), None,
        jnp.float32(p.rescale.multiplier), jnp.float32(1.0), out_dtype=jnp.float32, two_mul=False,
    )


def _swiglu_jax(g, u, s_g, r_h):
    gx = g.astype(jnp.float32) * np.float32(s_g)
    return _round_clip_jax(((gx * jax.nn.sigmoid(gx)) * u.astype(jnp.float32)) * np.float32(r_h))


def _moe_ffn_jax(cfg: TokenPathConfig, p, h):
    """Router, routed experts (the ``qmoe`` oracle) and the gated shared
    expert of one layer; returns (int8 output, chosen experts)."""
    s, r_h = cfg.act_scale, cfg.act_scale / cfg.glu_scale
    ex: RoutedExperts = p["experts"]
    acc = jax.lax.dot_general(
        h.astype(jnp.int32), jnp.asarray(ex.router, jnp.int32), (((2,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    idx = jax.lax.top_k(acc, cfg.top_k)[1]
    probs = _ref.softmax_rn(acc.astype(jnp.float32) * np.float32(ex.router_scale))
    n, t, d = h.shape
    routed = _ref.qmoe_ref(
        h.reshape(n * t, d), idx.reshape(n * t, -1), probs.reshape(n * t, -1),
        jnp.asarray(ex.gate), jnp.asarray(ex.up), jnp.asarray(ex.down),
        r_g=np.float32(ex.r_gate), s_g=np.float32(s), r_u=np.float32(ex.r_up),
        r_h=np.float32(r_h), r_d=np.float32(ex.r_down),
    ).reshape(n, t, d)
    hs = _swiglu_jax(_fc_jax(h, p["shared_gate"]), _fc_jax(h, p["shared_up"]), s, r_h)
    shared = _fc_f32_jax(hs, p["shared_down"]) * jax.nn.sigmoid(_fc_f32_jax(h, p["shared_router"]))
    total = routed.astype(jnp.float32) * np.float32(1.0 / MOE_FIXED) + shared
    return _round_clip_jax(total), idx


def _moe_forward_jax(cfg: TokenPathConfig, params: TokenPathParams, tokens, positions, mask, lut, kv):
    """jnp mirror of a sparse-expert artifact; ``kv(l, k, v)`` returns the
    K/V that attention reads (prefill: the rows; decode: the updated
    cache).  Returns (logits, [(k, v)], [chosen experts]) per layer."""
    D = cfg.d_model
    cos_t, sin_t, perm = rope_tables(cfg.max_pos, cfg.n_heads, cfg.d_head, cfg.rope_theta)
    pos = jnp.asarray(positions, jnp.int32)
    cos = jnp.take(jnp.asarray(cos_t), pos, axis=0).astype(jnp.int32)
    sin = jnp.take(jnp.asarray(sin_t), pos, axis=0).astype(jnp.int32)
    perm = jnp.asarray(perm, jnp.int32)
    x = jnp.take(jnp.asarray(params.embedding), jnp.asarray(tokens, jnp.int32), axis=0)
    states, experts = [], []
    for l, p in enumerate(params.layers):
        qkv = _fc_jax(_rmsnorm_jax(x, p["attn_norm"], cfg), p["qkv"])
        q = _rope_jax(qkv[..., :D], cos, sin, perm)
        k, v = kv(l, _rope_jax(qkv[..., D : 2 * D], cos, sin, perm), qkv[..., 2 * D :])
        states.append((k, v))
        x1 = _residual_jax(x, _fc_jax(_attention_jax(cfg, q, k, v, mask, lut), p["o"]))
        out, idx = _moe_ffn_jax(cfg, p, _rmsnorm_jax(x1, p["ffn_norm"], cfg))
        experts.append(idx)
        x = _residual_jax(x1, out)
    return _logits_jax(params, _rmsnorm_jax(x, params.final_norm, cfg)), states, experts


def prefill_jax(cfg: TokenPathConfig, params: TokenPathParams, tokens, mask, lut=None, positions=None):
    """jnp mirror of the prefill artifact: op-for-op the same integer/f32
    chain, so the result is bit-identical.  Returns (logits, [(k, v)] per
    layer).  A rotary block also needs ``positions`` ``(N, S)``."""
    lut = build_exp_lut() if lut is None else lut
    if cfg.block == "moe":
        logits, states, _ = _moe_forward_jax(
            cfg, params, tokens, positions, mask, lut, lambda l, k, v: (k, v)
        )
        return logits, states
    D = cfg.d_model
    x = jnp.take(jnp.asarray(params.embedding), jnp.asarray(tokens, jnp.int32), axis=0)
    caches = []
    for p in params.layers:
        qkv = _fc_jax(x, p["qkv"])
        qf, kf, vf = qkv[..., :D], qkv[..., D : 2 * D], qkv[..., 2 * D :]
        caches.append((kf, vf))
        x = _block_jax(cfg, p, x, kf, vf, qf, mask, lut)
    return _logits_jax(params, x), caches


def decode_jax(cfg: TokenPathConfig, params: TokenPathParams, tokens, onehot, mask, states, lut=None,
               positions=None):
    """jnp mirror of the decode artifact.  ``states`` is [(k, v)] per layer;
    returns (logits, new_states) with the codified int8 scatter update.  A
    rotary block also needs ``positions`` ``(N, 1)``."""
    lut = build_exp_lut() if lut is None else lut
    D = cfg.d_model
    oh = jnp.asarray(onehot, jnp.int8)
    keep = (jnp.int8(1) - oh).astype(jnp.int8)
    if cfg.block == "moe":
        def kv(l, kn, vn):
            k_st, v_st = states[l]
            return ((jnp.asarray(k_st) * keep + kn * oh).astype(jnp.int8),
                    (jnp.asarray(v_st) * keep + vn * oh).astype(jnp.int8))

        logits, new_states, _ = _moe_forward_jax(cfg, params, tokens, positions, mask, lut, kv)
        return logits, new_states
    x = jnp.take(jnp.asarray(params.embedding), jnp.asarray(tokens, jnp.int32), axis=0)
    new_states = []
    for p, (k_st, v_st) in zip(params.layers, states):
        qkv = _fc_jax(x, p["qkv"])
        qf, kn, vn = qkv[..., :D], qkv[..., D : 2 * D], qkv[..., 2 * D :]
        k_upd = (jnp.asarray(k_st) * keep + kn * oh).astype(jnp.int8)
        v_upd = (jnp.asarray(v_st) * keep + vn * oh).astype(jnp.int8)
        new_states.append((k_upd, v_upd))
        x = _block_jax(cfg, p, x, k_upd, v_upd, qf, mask, lut)
    return _logits_jax(params, x), new_states


# ---------------------------------------------------------------------------
# compiled pair + engine adapter
# ---------------------------------------------------------------------------

class CompiledTokenPath:
    """The prefill/decode artifact pair compiled onto one shared PlanCache.

    Keys in the shared cache are graph-qualified, so the pair holds exactly
    one specialization per visited (graph, batch-bucket, seq-bucket) cell —
    ``cache_stats()`` makes that observable."""

    def __init__(
        self,
        cfg: Optional[TokenPathConfig] = None,
        params: Optional[TokenPathParams] = None,
        *,
        backend: str = "ref",
        seed: int = 0,
        s_granularity: int = 32,
        plan_cache_capacity: int = 32,
        autotune=None,
    ) -> None:
        self.cfg = cfg if cfg is not None else TokenPathConfig()
        self.params = params if params is not None else make_token_params(self.cfg, seed)
        self.plan_cache = PlanCache(plan_cache_capacity, scope="plan")
        self.prefill_model = build_prefill_model(self.cfg, self.params)
        self.decode_model = build_decode_model(self.cfg, self.params)
        kw = dict(
            backend=backend,
            batch="dynamic",
            dynamic_axes={"N": None, "S": s_granularity},
            plan_cache=self.plan_cache,
            autotune=autotune,
        )
        self.prefill_cm: CompiledModel = compile_model(self.prefill_model, **kw)
        self.decode_cm: CompiledModel = compile_model(self.decode_model, **kw)
        self._logits_prefill = self.prefill_model.graph.outputs[0].name
        self._logits_decode = self.decode_model.graph.outputs[0].name
        self.state_specs = list(self.decode_model.graph.states)
        # prefill outputs [1:] are the per-layer (k, v) rows in state order
        pre_kv = [t.name for t in self.prefill_model.graph.outputs[1:]]
        self._prefill_kv = {s.input: n for s, n in zip(self.state_specs, pre_kv)}
        # a sparse-expert block's graphs end with each layer's chosen experts
        n_kv = 1 + len(self.state_specs)
        self._experts_prefill = [t.name for t in self.prefill_model.graph.outputs[n_kv:]]
        self._experts_decode = [t.name for t in self.decode_model.graph.outputs[n_kv:]]
        #: chosen experts of the last prefill (host) or decode (device) call,
        #: ``(layers, N, S, K)`` int32; None for the toy block
        self.last_routing = None
        #: the decode steps' routing counts not counted yet, ``[rows,
        #: experts_hit, layer_calls]`` int32 on the device (None: nothing),
        #: and how many steps they hold
        self._routing_counts = None
        self._routing_steps = 0
        # jitted one-dispatch decode steps, keyed by exact (N, S) cell
        self._step_fns: Dict[Tuple[int, int], object] = {}
        # jitted one-dispatch prefill steps, keyed by prompt bucket S
        self._prefill_fns: Dict[int, object] = {}

    # -- direct run API -------------------------------------------------------
    def prefill(self, tokens: np.ndarray, mask: np.ndarray, positions: Optional[np.ndarray] = None):
        """Returns (logits (N,S,V) f32, {state-input name: (N,S,D) int8}).
        A rotary block takes ``positions`` (default ``0..S-1`` per row)."""
        feeds = {"tokens": np.asarray(tokens, np.int32), "mask": mask}
        if self.cfg.positions:
            if positions is None:
                positions = np.broadcast_to(np.arange(np.shape(tokens)[1]), np.shape(tokens))
            feeds["positions"] = np.asarray(positions, np.int32)
        outs = self.prefill_cm.run(feeds)
        cache = {inp: np.asarray(outs[name]) for inp, name in self._prefill_kv.items()}
        if self._experts_prefill:
            self.last_routing = np.stack([outs[n] for n in self._experts_prefill])
            self._count_routing(self.last_routing, decode=False)
        return np.asarray(outs[self._logits_prefill]), cache

    def decode(self, tokens, onehot, mask, cache: Dict[str, np.ndarray], positions=None):
        """One decode step.  Returns (logits (N,1,V), next cache dict).  A
        rotary block takes ``positions`` ``(N, 1)``."""
        feeds = {
            "tokens": np.asarray(tokens, np.int32),
            "onehot": np.asarray(onehot, np.int8),
            "mask": mask,
        }
        if self.cfg.positions:
            feeds["positions"] = np.asarray(positions, np.int32).reshape(-1, 1)
        feeds.update(cache)
        outs = self.decode_cm.run(feeds)
        nxt = {s.input: np.asarray(outs[s.output]) for s in self.state_specs}
        if self._experts_decode:
            self.last_routing = np.stack([outs[n] for n in self._experts_decode])
            self._count_routing(self.last_routing, decode=True)
        return np.asarray(outs[self._logits_decode]), nxt

    # -- routing counters ----------------------------------------------------
    def _count_routing(self, routing: np.ndarray, *, decode: bool) -> None:
        """Count one call's routing ``(layers, N, S, K)`` into the registry
        (see :meth:`_count`)."""
        layers = routing.shape[0]
        rows = int(np.prod(routing.shape[1:-1])) * layers
        hit = sum(int(np.unique(routing[l]).size) for l in range(layers))
        self._count(rows, hit, layers, decode=decode)

    @staticmethod
    def _count(rows: int, hit: int, layers: int, *, decode: bool) -> None:
        """``tokenpath.moe.rows`` (rows routed, per layer), ``.experts_hit``
        (distinct experts with a row, per layer) and ``.layer_calls``; the
        decode calls also under ``tokenpath.moe.decode.*``."""
        with _trace.span("tokenpath.moe.route"):
            reg = default_registry()
            for scope in ("tokenpath.moe",) + (("tokenpath.moe.decode",) if decode else ()):
                reg.counter(f"{scope}.rows").inc(rows)
                reg.counter(f"{scope}.experts_hit").inc(hit)
                reg.counter(f"{scope}.layer_calls").inc(layers)

    def flush_routing(self) -> None:
        """Count the routing of the decode steps not counted yet.  The
        jitted decode adds each step's counts to one int32 triple on the
        device, so that an untraced step neither waits for its routing nor
        keeps it; a tracer flushes after each step."""
        counts, self._routing_counts, self._routing_steps = self._routing_counts, None, 0
        if counts is not None:
            rows, hit, layers = (int(v) for v in np.asarray(counts))
            self._count(rows, hit, layers, decode=True)

    def decode_step(self, tokens, pos, cache):
        """The decode hot loop: one step at *exact* bucket extents, keeping
        the KV state as device arrays across steps.

        ``decode()`` round-trips every feed and output through host numpy —
        correct, and what the differential tests pin — but on the serving
        steady state those conversions dominate: the jitted executor itself
        is an order of magnitude cheaper than the per-feed device puts and
        per-output host syncs.  Here the position onehot and causal mask
        are built *inside* one jitted step function (host→device traffic
        per token = the sampled tokens and positions, nothing else), the
        state dict flows back in untouched as device arrays, and only the
        logits are materialized on host.  The specialized entry is still
        fetched from the shared PlanCache on every call, so cell accounting
        is that of :meth:`decode`: one miss per first-visited cell, hits
        thereafter.  ``(N, S)`` must be a cell of the decode plan; other
        extents are refused with a ``ValueError`` naming the bucket
        (:meth:`CompiledTokenAdapter.init_cache` allocates the engine's
        cache there).  Returns (logits (N, V) ndarray, next cache of device
        arrays).  A cache that comes in on the host counts one
        ``tokenpath.cache.host_trips``."""
        n = int(np.shape(tokens)[0])
        s = int(np.shape(next(iter(cache.values())))[1])
        cm = self.decode_cm
        if cm.bucket_for("N", n) != n or cm.bucket_for("S", s) != s:
            raise ValueError(
                f"decode_step runs at the decode plan's buckets: (N, S) = ({n}, {s}) needs "
                f"({cm.bucket_for('N', n)}, {cm.bucket_for('S', s)})"
            )
        on_host = any(isinstance(v, np.ndarray) for v in cache.values())
        if on_host:
            _count_host_trip()
        plan, _ = cm.specialized({"N": n, "S": s})  # per-step cell accounting
        entry = self._step_fns.get((n, s))
        if entry is None:
            logits_name, specs = self._logits_decode, self.state_specs
            experts, rotary = self._experts_decode, self.cfg.positions

            n_experts = self.cfg.n_experts

            def step(params, toks, pos, cache, counts):
                onehot = (jnp.arange(s)[None, :, None] == pos[:, None, None]).astype(jnp.int8)
                mask = (jnp.arange(s)[None, None, :] <= pos[:, None, None]).astype(jnp.float32)
                feeds = {"tokens": toks, "onehot": onehot, "mask": mask}
                if rotary:
                    feeds["positions"] = pos[:, None]
                feeds.update(cache)
                outs = plan.execute(feeds, params)
                nxt = {sp.input: outs[sp.output] for sp in specs}
                if experts:
                    routing = jnp.stack([outs[name] for name in experts])
                    nxt = (nxt, routing, counts + _routing_counts(routing, n_experts))
                return outs[logits_name][:, 0, :], nxt

            entry = self._step_fns[(n, s)] = (jax.jit(step), plan.params())
        fn, params = entry
        if _trace.enabled and on_host:
            # traced only: the host cache goes to the device here, not inside
            # the jitted call's dispatch, so that its own span can time it
            with _trace.span("tokenpath.decode.put"):
                cache = jax.block_until_ready(jax.device_put(cache))
        counts = self._routing_counts
        if counts is None and self._experts_decode:
            counts = np.zeros(3, np.int32)
        with _trace.span("tokenpath.decode.dispatch"):
            logits, nxt = fn(
                params, jnp.asarray(tokens, jnp.int32), jnp.asarray(np.asarray(pos), jnp.int32), cache, counts
            )
        if self._experts_decode:
            nxt, self.last_routing, self._routing_counts = nxt
            self._routing_steps += 1
        with _trace.span("tokenpath.decode.fetch"):
            logits = np.asarray(logits)
        if self._routing_counts is not None and (_trace.enabled or self._routing_steps >= ROUTING_FLUSH_STEPS):
            self.flush_routing()
        return logits, nxt

    def prefill_step(self, tokens, plen: int):
        """One prompt's prefill, with nothing brought to the host but the
        logits row the next token is chosen from.

        ``tokens`` is the prompt padded to its bucket, ``(1, S)``, and
        ``plen`` its true length; where ``S`` is not a bucket of the plan,
        the ids are padded further, with zeros, to ``bucket_for("S", S)``
        (the padding :meth:`prefill` applies too).  :meth:`prefill` fetches
        every output: the ``(1, S, V)`` f32 logits and each layer's K/V rows.
        Here one jitted function builds the causal mask (and a rotary
        block's positions ``0..S-1``) on the device, runs the plan at the
        bucket, and takes the logits row
        at ``plen - 1`` with a dynamic index: ``plen`` is traced, so there is
        one program per bucket, not one per prompt length.  The plan comes
        from the shared PlanCache on every call, so cell accounting is that
        of :meth:`prefill`.  Returns (logits row ``(V,)`` ndarray, {state-input
        name: ``(1, bucket, D)`` int8 device array}); a sparse-expert block's
        routing comes to the host as well and is counted as by
        :meth:`prefill`.  ``tokenpath.prefill.fetched_bytes`` counts the
        bytes brought to the host."""
        tokens = np.asarray(tokens, np.int32)
        if tokens.shape[0] != 1 or not 1 <= plen <= tokens.shape[1]:
            raise ValueError(f"prefill_step takes one prompt (1, S) with 1 <= plen <= S, got "
                             f"{tokens.shape} and plen {plen}")
        cm = self.prefill_cm
        s = cm.bucket_for("S", tokens.shape[1])
        tokens = np.pad(tokens, ((0, 0), (0, s - tokens.shape[1])))
        fetched = default_registry().counter("tokenpath.prefill.fetched_bytes")
        plan, _ = cm.specialized({"N": 1, "S": s})  # per-call cell accounting
        entry = self._prefill_fns.get(s)
        if entry is None:
            logits_name, kv, experts = self._logits_prefill, self._prefill_kv, self._experts_prefill
            rotary = "positions" in cm.input_names

            def step(params, toks, plen):
                feeds = {"tokens": toks, "mask": jnp.tril(jnp.ones((s, s), jnp.float32))[None]}
                if rotary:
                    feeds["positions"] = jnp.arange(s, dtype=jnp.int32)[None]
                outs = plan.execute(feeds, params)
                row = jax.lax.dynamic_index_in_dim(outs[logits_name][0], plen - 1, keepdims=False)
                routing = jnp.stack([outs[name] for name in experts]) if experts else None
                return row, {inp: outs[name] for inp, name in kv.items()}, routing

            entry = self._prefill_fns[s] = (jax.jit(step), plan.params())
        fn, params = entry
        with _trace.span("tokenpath.prefill.dispatch"):
            row, rows, routing = fn(params, jnp.asarray(tokens), np.int32(plen))
        with _trace.span("tokenpath.prefill.fetch"):
            row, routing = jax.device_get((row, routing))
        fetched.inc(row.nbytes + (0 if routing is None else routing.nbytes))
        if routing is not None:
            self.last_routing = routing
            self._count_routing(routing, decode=False)
        return row, rows

    def init_cache(self, n: int, s: int) -> Dict[str, np.ndarray]:
        D = self.cfg.d_model
        return {spec.input: np.zeros((n, s, D), np.int8) for spec in self.state_specs}

    def cache_stats(self) -> Dict[str, float]:
        return self.plan_cache.stats


#: Untraced decode steps whose routing counts stay on the device before they
#: are counted: the int32 triple cannot wrap, and the counters lag a long
#: run by at most this many steps (about half an hour at 30 steps a second).
ROUTING_FLUSH_STEPS = 1 << 16


def _routing_counts(routing: jax.Array, n_experts: int) -> jax.Array:
    """``[rows, experts_hit, layer_calls]`` int32 of one call's routing
    ``(layers, N, S, K)``, on the device: the rows routed and the distinct
    experts with a row, each summed over the layers."""
    layers = routing.shape[0]
    flat = routing.reshape(layers, -1)
    hit = jnp.any(flat[:, :, None] == jnp.arange(n_experts, dtype=flat.dtype), axis=1).sum(dtype=jnp.int32)
    rows = layers * int(np.prod(routing.shape[1:-1]))
    return jnp.stack([jnp.int32(rows), hit, jnp.int32(layers)])


def _count_host_trip() -> None:
    """One whole KV cache moved between host and device by the token path."""
    default_registry().counter("tokenpath.cache.host_trips").inc()


def causal_mask(n: int, s: int) -> np.ndarray:
    """The prefill graph's causal ``mask`` ``(n, s, s)`` f32: 1 where a
    position may attend (keys at or before it), 0 elsewhere."""
    return np.broadcast_to(np.tril(np.ones((s, s), np.float32)), (n, s, s)).copy()


def _write_rows(cache, rows, slot):
    """Each cache array with ``rows[name]`` ``(1, b, D)`` written over
    positions ``[0, b)`` of ``slot``; every other row is left as it was."""
    return {
        name: jax.lax.dynamic_update_slice(buf, rows[name], (slot, 0, 0))
        for name, buf in cache.items()
    }


class CompiledTokenAdapter:
    """ServeEngine adapter for the compiled token path.

    ``init_cache``/``prefill``/``scatter``/``decode`` are the engine's seam.
    Every served call runs a jitted step over a pre-specialized ExecutionPlan
    out of the shared PlanCache, at the plans' bucket extents: the cache is
    allocated at the decode plan's ``(N, S)`` buckets, a prompt is padded to
    its ``S`` bucket and the decode batch to the cache's ``N`` with dead
    rows.  After the first step per cell there is zero lowering work per
    token."""

    def __init__(self, tp: CompiledTokenPath) -> None:
        self.tp = tp
        self.cfg = tp.cfg
        # the slot is traced: one program per prompt bucket, not per slot;
        # the cache is donated, so the rows are written in place
        self._write_rows = jax.jit(_write_rows, donate_argnums=0)

    def init_cache(self, slots: int, max_len: int):
        """The zero cache at the decode plan's ``(N, S)`` buckets for
        ``slots`` and ``max_len``, on the device: admission and decode keep
        it there."""
        cm = self.tp.decode_cm
        return jax.device_put(self.tp.init_cache(cm.bucket_for("N", slots), cm.bucket_for("S", max_len)))

    def prefill(self, padded: np.ndarray, plen: int, max_len: int):
        """The prompt's logits row at ``plen - 1`` on the host and its K/V
        rows on the device, for :meth:`scatter`
        (:meth:`CompiledTokenPath.prefill_step`)."""
        return self.tp.prefill_step(padded, plen)

    def scatter(self, cache, slot: int, pcache):
        """Write one prompt's K/V rows ``(1, bucket, D)`` into ``slot`` of the
        device cache, in place (the cache passed in is donated).  Positions at
        or beyond the bucket keep what the slot held before (zeros, or a
        previous occupant's rows): they are masked until the decode onehot
        overwrites them position by position."""
        if not 0 <= slot < np.shape(next(iter(cache.values())))[0]:
            raise IndexError(f"slot {slot} out of range")
        with _trace.span("tokenpath.scatter.put"):
            if any(isinstance(v, np.ndarray) for v in cache.values()):
                _count_host_trip()
                cache = jax.device_put(cache)
            rows = jax.device_put({name: pcache[name][:, : buf.shape[1]] for name, buf in cache.items()})
            if _trace.enabled:
                rows = jax.block_until_ready(rows)
        with _trace.span("tokenpath.scatter.dispatch"):
            return self._write_rows(cache, rows, np.int32(slot))

    def decode(self, toks: np.ndarray, pos: np.ndarray, cache):
        """One decode step over the engine's ``slots`` rows: ``toks``/``pos``
        are padded with dead rows (token 0 at position 0) up to the cache's
        ``N``, and the first ``slots`` rows of logits come back
        (:meth:`CompiledTokenPath.decode_step`)."""
        n, dead = len(toks), np.shape(next(iter(cache.values())))[0] - len(toks)
        if dead:
            toks = np.pad(np.asarray(toks), ((0, dead), (0, 0)))
            pos = np.pad(np.asarray(pos), (0, dead))
        logits, nxt = self.tp.decode_step(toks, pos, cache)
        return logits[:n], nxt
