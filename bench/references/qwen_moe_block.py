"""Plain reference of the codified sparse-expert (Qwen2-MoE) block, and its
weights.

The block is the published ``Qwen2MoeDecoderLayer`` applied to int8 codes:
RMSNorm before attention, before the experts and at the end; causal
multi-head attention with rotate-half rotary positions on q and k; a router
that takes the top 4 of 60 experts, weighted by its softmax and not
renormalised; every expert a SwiGLU MLP ``down(silu(gate x) * up x)``; a
shared SwiGLU expert gated by ``sigmoid(x w_sg)``.  Departures from the
float model, as the configuration's ``departures`` list them:

- every activation is an int8 code on one scale (``act_scale``), except the
  SwiGLU products (``glu_scale``); projections are int8 x int8 -> int32 with
  a fixed-point rescale ``quant_scale · 2**-shift``; residual adds saturate;
- RMSNorm divides the codes by ``sqrt(mean(x²) + eps / act_scale²)``, root
  and quotient the nearest f32;
- the rotary cos/sin are int16 codes of ``2**-14``; q and k are rounded back
  to int8 after the rotation;
- attention's softmax is the int8 region's (exp from a 256-entry table,
  int8 probabilities), as ``token_block`` computes it;
- the experts are chosen on the int32 router logits (equal logits: the lower
  index), weighted by the softmax summed in expert order, each quotient the
  nearest f32; each weighted expert output is rounded to 1/256 of a code and
  summed in int32; the shared expert's gated f32 output is added and the
  sum rounded once to int8;
- every product is taken in the artifact's order, each rescaled
  accumulator before the weight or gate that multiplies it (``_fixed``).

This file imports nothing of the program.  It makes the weights from the
seed (on the device, in one jitted call) and hands the same codes to the
program and to :func:`forward`, a straightforward jax.numpy pass over whole
sequences under ``jax.default_matmul_precision("highest")``: every expert on
every position, one expert at a time, with weight zero where it was not
chosen.  ``act_bits=4`` is the control: every activation cut to 4 bits.

What the harness calls: :func:`make_weights` and :func:`checks`;
``bench/calibrate.py`` also calls :func:`readings`.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import math
import sys
from pathlib import Path
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _token_block():
    """``bench/references/token_block.py``, once, under the name the
    harness gives it."""
    path = Path(__file__).with_name("token_block.py")
    key = f"bench:{path}"
    if key not in sys.modules:
        spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        spec.loader.exec_module(mod)
    return sys.modules[key]


tb = _token_block()

#: Sequences per call of the reference's forward pass.
BATCH = 2
CONTROL_BITS = tb.CONTROL_BITS
#: Fixed-point steps of the expert combine and the rotary tables.
FIXED = 256.0
ROPE_ONE = float(1 << 14)


@dataclasses.dataclass(frozen=True)
class Block:
    """Widths and fixed-point constants of one configuration."""

    d_model: int
    n_heads: int
    vocab: int
    n_layers: int
    n_experts: int
    top_k: int
    d_expert: int
    d_shared: int
    rms_eps: float
    rope_theta: float
    max_pos: int
    bits_items: Tuple[Tuple[str, int], ...]
    std_items: Tuple[Tuple[str, float], ...]
    act_scale: float
    glu_scale: float
    lm_scale: float
    lut_scale: float
    mask_penalty: float
    p_scale: float
    bias_seed: int

    @property
    def d_head(self) -> int:
        return self.d_model // self.n_heads

    @property
    def bits(self) -> Dict[str, int]:
        return dict(self.bits_items)

    @property
    def code_std(self) -> Dict[str, float]:
        return dict(self.std_items)

    def shape(self, name: str):
        d, e, f, fs = self.d_model, self.n_experts, self.d_expert, self.d_shared
        return {
            "qkv": (d, 3 * d), "o": (d, d), "router": (d, e), "gate": (e, d, f), "up": (e, d, f),
            "down": (e, f, d), "shared_gate": (d, fs), "shared_up": (d, fs), "shared_down": (fs, d),
            "shared_router": (d, 1),
        }[name]

    @classmethod
    def from_config(cls, cfg: dict) -> "Block":
        q, a = cfg["quant"], cfg["attention"]
        return cls(
            d_model=int(cfg["hidden_size"]),
            n_heads=int(cfg["num_attention_heads"]),
            vocab=int(cfg["vocab_size"]),
            n_layers=int(cfg["num_hidden_layers"]),
            n_experts=int(cfg["num_experts"]),
            top_k=int(cfg["num_experts_per_tok"]),
            d_expert=int(cfg["moe_intermediate_size"]),
            d_shared=int(cfg["shared_expert_intermediate_size"]),
            rms_eps=float(cfg["rms_norm_eps"]),
            rope_theta=float(cfg["rope_theta"]),
            max_pos=int(cfg["max_position_embeddings"]),
            bits_items=tuple(sorted((k, int(v)) for k, v in q["weight_bits"].items())),
            std_items=tuple(sorted((k, float(v)) for k, v in q["code_std"].items())),
            act_scale=float(q["act_scale"]),
            glu_scale=float(q["glu_scale"]),
            lm_scale=float(q["lm_scale"]),
            lut_scale=float(a["lut_scale"]),
            mask_penalty=float(a["mask_penalty"]),
            p_scale=float(a["p_scale"]),
            bias_seed=int(q["bias_seed"]),
        )


PROJECTIONS = ("qkv", "o", "router", "gate", "up", "down",
               "shared_gate", "shared_up", "shared_down", "shared_router")


def _draw(key, blk: Block):
    """Every weight code and norm gain, drawn on the device."""
    keys = iter(jax.random.split(key, 3 + blk.n_layers * (len(PROJECTIONS) + 2)))

    def codes(shape, std, lo, hi):
        x = jax.random.normal(next(keys), shape, jnp.float32) * std
        return jnp.clip(jnp.rint(x), lo, hi).astype(jnp.int8)

    def gain():
        return 1.0 + blk.code_std["gain"] * jax.random.normal(next(keys), (blk.d_model,), jnp.float32)

    emb = codes((blk.vocab, blk.d_model), blk.code_std["embedding"], -127, 127)
    emb = emb.at[0].set(0)  # token 0 pads prompts: its embedding is zero
    layers = []
    for _ in range(blk.n_layers):
        layer = {"attn_norm": gain(), "ffn_norm": gain()}
        for name in PROJECTIONS:
            bits = blk.bits[name]
            layer[name] = codes(blk.shape(name), blk.code_std[f"w{bits}"], *tb.weight_range(bits))
        layers.append(layer)
    head = codes((blk.d_model, blk.vocab), blk.code_std["lm_head"], -127, 127)
    return {"embedding": emb, "layers": layers, "lm_head": head, "final_norm": gain()}


def _targets(blk: Block) -> Dict[str, Tuple[int, float, float]]:
    """Per projection: (contraction length, input code std, output std).
    The output std is in codes of its scale, or in real units for the
    router and the shared expert's gate logits (their f32 results)."""
    s = blk.code_std
    d, f, fs = blk.d_model, blk.d_expert, blk.d_shared
    norm, act, glu = s["normed"], s["activation"], s["glu"]
    return {
        "qkv": (d, norm, act), "o": (d, act, act), "router": (d, norm, s["router_logit"]),
        "gate": (d, norm, act), "up": (d, norm, act), "down": (f, glu, act),
        "shared_gate": (d, norm, act), "shared_up": (d, norm, act), "shared_down": (fs, glu, act),
        "shared_router": (d, norm, s["router_logit"]),
    }


def make_weights(cfg: dict, seed: int) -> dict:
    """The weights of one run of ``cfg`` from ``seed``: int8 codes and norm
    gains drawn on the device in one jitted call and brought to the host
    once, plus the qkv biases and the fixed-point rescales, which depend on
    the configuration alone (every seed compiles to the same programs).

    Returns ``{"embedding", "lm_head", "final_norm", "layers": [{"attn_norm",
    "ffn_norm", proj: {"w", "b", "quant_scale", "shift", "bits"}}]}``."""
    blk = Block.from_config(cfg)
    drawn = jax.device_get(jax.jit(functools.partial(_draw, blk=blk))(jax.random.key(seed)))
    bias_rng = np.random.default_rng(blk.bias_seed)
    targets = _targets(blk)
    layers = []
    for layer in drawn["layers"]:
        out = {"attn_norm": np.asarray(layer["attn_norm"]), "ffn_norm": np.asarray(layer["ffn_norm"])}
        for name in PROJECTIONS:
            k, x_std, y_std = targets[name]
            bits = blk.bits[name]
            acc_std = math.sqrt(k) * blk.code_std[f"w{bits}"] * x_std
            qs, shift = tb.fixed_point(y_std / acc_std)
            n = blk.shape(name)[-1]
            bias = np.rint(bias_rng.normal(size=(n,)) * 0.1 * acc_std).astype(np.int32) if name == "qkv" else None
            out[name] = {"w": np.asarray(layer[name]), "b": bias, "quant_scale": qs, "shift": shift, "bits": bits}
        layers.append(out)
    return {"embedding": np.asarray(drawn["embedding"]), "lm_head": np.asarray(drawn["lm_head"]),
            "final_norm": np.asarray(drawn["final_norm"]), "layers": layers}


def _scale(p) -> np.float32:
    return np.float32(p["quant_scale"] * 2.0 ** -p["shift"])


def _q8(f):
    return jnp.clip(jnp.rint(f), -128, 127).astype(jnp.int8)


def _mm(spec, a, b):
    return jnp.einsum(spec, a, b, preferred_element_type=jnp.int32)


def _linear(x, p, *, f32=False):
    acc = _mm("...k,kn->...n", x, p["w"])
    if p["b"] is not None:
        acc = acc + p["b"]
    f = acc.astype(jnp.float32) * _scale(p)
    return f if f32 else _q8(f)


def _sqrt(a):
    """The f32 root of ``a >= 0`` with the least exact residual ``|a - c·c|``
    (ties: even) among the hardware root and its neighbours up to two ulps
    away: the nearest root, where a TPU's own is not correctly rounded."""
    s = jnp.sqrt(a)
    bits = jax.lax.bitcast_convert_type(s, jnp.int32)

    def residual(c):
        p = c * c
        ch, cl = tb._split(c)
        return jnp.abs((a - p) - (((ch * ch - p) + 2.0 * (ch * cl)) + cl * cl))

    best, best_r = s, residual(s)
    for d in (-2, -1, 1, 2):
        cb = jnp.maximum(bits + d, 0)
        c = jax.lax.bitcast_convert_type(cb, jnp.float32)
        r = residual(c)
        better = (r < best_r) | ((r == best_r) & ((cb & 1) == 0))
        best, best_r = jnp.where(better, c, best), jnp.where(better, r, best_r)
    return jnp.where(a == 0, jnp.float32(0.0), best)


def _rmsnorm(x, gain, blk: Block):
    """``gain`` is γ over the output scale, computed on the host; the root
    and the quotient are the nearest f32 (the artifact's Sqrt and Div)."""
    xi = x.astype(jnp.int32)
    ms = jnp.sum(xi * xi, axis=-1, keepdims=True).astype(jnp.float32) * np.float32(1.0 / blk.d_model)
    rms = _sqrt(ms + np.float32(blk.rms_eps / blk.act_scale**2))
    xf = x.astype(jnp.float32)
    unit = jnp.where(xf < 0, -tb.divide(-xf, rms), tb.divide(xf, rms))
    return _q8(unit * gain)


def _rope(x, positions, blk: Block):
    """Rotate-half rotary positions of each head, at ``positions (L,)``."""
    half = blk.d_head // 2
    inv = 1.0 / (blk.rope_theta ** (np.arange(half) * 2.0 / blk.d_head))
    ang = np.arange(blk.max_pos)[:, None] * inv[None, :]
    cos = jnp.asarray(np.rint(np.cos(ang) * ROPE_ONE), jnp.float32)[positions][None, :, None, :]
    sin = jnp.asarray(np.rint(np.sin(ang) * ROPE_ONE), jnp.float32)[positions][None, :, None, :]
    b, length, _ = x.shape
    xh = x.astype(jnp.float32).reshape(b, length, blk.n_heads, blk.d_head)
    x1, x2 = xh[..., :half], xh[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return _q8(out * np.float32(1.0 / ROPE_ONE)).reshape(x.shape)


def _swiglu(g, u, blk: Block):
    x = g.astype(jnp.float32) * np.float32(blk.act_scale)
    return _q8(x * jax.nn.sigmoid(x) * u.astype(jnp.float32) * np.float32(blk.act_scale / blk.glu_scale))


def _fixed(f):
    """``f`` as computed, before it meets its next factor.  XLA on a TPU
    moves a constant factor onto the smaller operand of the next product,
    ``(acc · r) · w`` into ``acc · (r · w)``, which rounds differently from
    the artifact's order; the barrier keeps ``acc · r`` a value of its own."""
    return jax.lax.optimization_barrier(f)


def _softmax(f):
    """exp(f - max) over the experts, summed in expert order, each quotient
    the nearest f32: the artifact's Softmax (XLA's own sums and divides
    differently for different shapes on a TPU)."""
    e = jnp.exp(f - f.max(-1, keepdims=True))
    s = e[..., 0]
    for i in range(1, e.shape[-1]):
        s = s + e[..., i]
    return tb.divide(e, jnp.broadcast_to(s[..., None], e.shape))


def _experts(x, p, blk: Block, act_bits: int):
    """Routed experts plus the gated shared expert of ``x (B, L, D)``,
    rounded to int8: each expert on every position, weight zero where the
    router did not choose it, one expert at a time."""
    cut = functools.partial(tb._cut, act_bits=act_bits)
    logits = _mm("bld,de->ble", x, p["router"]["w"])
    probs = _softmax(logits.astype(jnp.float32) * _scale(p["router"]))
    top = jax.lax.top_k(logits, blk.top_k)[1]
    chosen = jax.nn.one_hot(top, blk.n_experts, dtype=jnp.float32).sum(axis=-2)
    weights = probs * chosen  # (B, L, E)
    g, u, d = p["gate"], p["up"], p["down"]

    def one(acc, expert):
        wg, wu, wd, weight = expert
        ge = cut(_q8(_mm("bld,df->blf", x, wg).astype(jnp.float32) * _scale(g)))
        ue = cut(_q8(_mm("bld,df->blf", x, wu).astype(jnp.float32) * _scale(u)))
        y = _fixed(_mm("blf,fd->bld", cut(_swiglu(ge, ue, blk)), wd).astype(jnp.float32) * _scale(d))
        c = jnp.clip(y * weight[..., None] * np.float32(FIXED), -2.0**30, 2.0**30)
        return acc + jnp.rint(c).astype(jnp.int32), None

    per_expert = (g["w"], u["w"], d["w"], jnp.moveaxis(weights, -1, 0))
    routed, _ = jax.lax.scan(one, jnp.zeros(x.shape, jnp.int32), per_expert)
    hs = cut(_swiglu(cut(_linear(x, p["shared_gate"])), cut(_linear(x, p["shared_up"])), blk))
    shared = _fixed(_linear(hs, p["shared_down"], f32=True)) * jax.nn.sigmoid(_linear(x, p["shared_router"], f32=True))
    return _q8(routed.astype(jnp.float32) * np.float32(1.0 / FIXED) + shared)


def _forward(w, tokens, positions, *, blk: Block, act_bits: int):
    cut = functools.partial(tb._cut, act_bits=act_bits)
    table = jnp.asarray(tb.exp_table(blk))
    d = blk.d_model
    pos = jnp.arange(tokens.shape[1])
    x = cut(jnp.take(w["embedding"], tokens, axis=0))
    for p in w["layers"]:
        qkv = cut(_linear(cut(_rmsnorm(x, p["attn_norm"], blk)), p["qkv"]))
        q = cut(_rope(qkv[..., :d], pos, blk))
        k = cut(_rope(qkv[..., d:2 * d], pos, blk))
        ctx = cut(tb._attention(blk, q, k, qkv[..., 2 * d:], table, act_bits))
        h = cut(tb._residual(x, cut(_linear(ctx, p["o"]))))
        x = cut(tb._residual(h, cut(_experts(cut(_rmsnorm(h, p["ffn_norm"], blk)), p, blk, act_bits))))
    xn = cut(_rmsnorm(x, w["final_norm"], blk))
    rows = jnp.take_along_axis(xn, positions[..., None], axis=1)
    acc = _mm("bpd,dv->bpv", rows, w["lm_head"])
    return acc.astype(jnp.float32) * np.float32(blk.lm_scale)


def device_weights(weights: dict, blk: Block) -> dict:
    """The codes of :func:`make_weights` as device arrays for :func:`forward`
    (the rescales stay numbers); each norm's γ as its gain ``γ / act_scale``,
    divided on the host as the program divides it."""
    def proj(p):
        return {"w": jnp.asarray(p["w"]), "b": None if p["b"] is None else jnp.asarray(p["b"]),
                "quant_scale": p["quant_scale"], "shift": p["shift"]}

    def gain(gamma):
        return jnp.asarray(np.asarray(gamma, np.float32) / np.float32(blk.act_scale))

    layers = [{"attn_norm": gain(layer["attn_norm"]), "ffn_norm": gain(layer["ffn_norm"]),
               **{name: proj(layer[name]) for name in PROJECTIONS}} for layer in weights["layers"]]
    return {"embedding": jnp.asarray(weights["embedding"]), "lm_head": jnp.asarray(weights["lm_head"]),
            "final_norm": gain(weights["final_norm"]), "layers": layers}


def _split(weights: dict):
    """(arrays, static rescales) of :func:`device_weights`."""
    arrays = jax.tree.map(lambda a: a, weights)
    rescales = []
    for layer in arrays["layers"]:
        row = []
        for name in PROJECTIONS:
            p = layer[name]
            row.append((p.pop("quant_scale"), p.pop("shift")))
        rescales.append(tuple(row))
    return arrays, tuple(rescales)


@functools.lru_cache(maxsize=None)
def _jitted(blk: Block, act_bits: int, rescales: tuple):
    def fn(arrays, tokens, positions):
        layers = []
        for i, layer in enumerate(arrays["layers"]):
            layer = dict(layer)
            for name, (qs, shift) in zip(PROJECTIONS, rescales[i]):
                layer[name] = {**layer[name], "quant_scale": qs, "shift": shift}
            layers.append(layer)
        with jax.default_matmul_precision("highest"):
            return _forward({**arrays, "layers": layers}, tokens, positions, blk=blk, act_bits=act_bits)

    return jax.jit(fn)


def forward(blk: Block, weights: dict, tokens, positions, *, act_bits: int = 8):
    """Logits ``(B, P, V)`` f32 at ``positions (B, P)`` of the sequences
    ``tokens (B, L)``, from one causal pass over the whole sequences.
    ``weights`` as :func:`device_weights` gives them."""
    arrays, rescales = _split(weights)
    fn = _jitted(blk, act_bits, rescales)
    return fn(arrays, jnp.asarray(tokens, jnp.int32), jnp.asarray(positions, jnp.int32))


def readings(cell, weights: dict, sample: List[dict], *, control: bool = False) -> Dict[str, np.ndarray]:
    """As ``token_block.readings``: per served token of ``sample``, the gap
    of its logit below the reference's best (``"program"``), and with
    ``control`` the gap of the token the 4-bit-activation reference puts
    first (``"control"``); batches of :data:`BATCH` sequences padded to the
    cell's ``max_len``."""
    blk = Block.from_config(cell.config)
    length = int(cell.spec["engine"]["max_len"])
    width = int(cell.traffic["output"]["max"])
    dw = device_weights(weights, blk)
    out = {"program": [], "control": []}
    for i in range(0, len(sample), BATCH):
        batch = sample[i:i + BATCH]
        batch = batch + [batch[0]] * (BATCH - len(batch))
        real = min(BATCH, len(sample) - i)
        tokens, positions, served, valid = tb.sequences(batch, length, width)
        valid[real:] = False
        logits = np.asarray(forward(blk, dw, tokens, positions))
        out["program"].append(tb.greedy_gaps(logits, served, valid))
        if control:
            low = np.asarray(forward(blk, dw, tokens, positions, act_bits=CONTROL_BITS))
            out["control"].append(tb.greedy_gaps(logits, low.argmax(-1).astype(np.int32), valid))
    return {k: (np.concatenate(v) if v else np.zeros(0)) for k, v in out.items()}


def checks(cell, weights: dict, sample: List[dict]):
    """``logit_gap`` (at most the cell's ``logit_gap_limit``) and
    ``tokens_compared`` (at least its ``min_tokens``), as ``token_block``."""
    spec = cell.spec["check"]
    gaps = readings(cell, weights, sample)["program"]
    widest = float(gaps.max()) if gaps.size else float("inf")
    out = {
        "logit_gap": {"value": widest, "limit": float(spec["logit_gap_limit"])},
        "tokens_compared": {"value": int(gaps.size), "limit": int(spec["min_tokens"])},
    }
    ok = widest <= out["logit_gap"]["limit"] and gaps.size >= out["tokens_compared"]["limit"]
    return out, bool(ok)
