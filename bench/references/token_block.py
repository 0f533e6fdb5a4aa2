"""Plain reference of the codified transformer block, and its weights.

The block: token embedding, then per layer a joint QKV projection, causal
multi-head attention with an int8 exp table, an output projection, a
saturating residual, a ReLU MLP and a second residual; then an LM head.
Every activation is an int8 code on one shared scale, every projection is
an int8 x int8 -> int32 matmul with an int32 bias and a fixed-point rescale
(``acc · quant_scale · 2**-shift``, rounded half to even and clipped).

This file imports nothing of the program.  It makes the weights from the
seed (on the device, in one jitted call) and hands the same codes to the
program and to :func:`forward`, a straightforward jax.numpy forward pass over
whole sequences.  ``act_bits=4`` computes the same pass with every
activation cut to 4 bits: the control that a comparison must fail.

What the harness calls: :func:`make_weights` (config, seed) and
:func:`checks` (the comparison that decides ``correct``); ``bench/calibrate.py``
also calls :func:`readings` with the control.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

PROJECTIONS = ("qkv", "o", "up", "down")
#: Sequences per call of the reference's forward pass.
BATCH = 2
#: Activation bits of the control, the precision below the configuration's int8.
CONTROL_BITS = 4


@dataclasses.dataclass(frozen=True)
class Block:
    """Widths and fixed-point constants of one configuration."""

    d_model: int
    n_heads: int
    d_ff: int
    vocab: int
    n_layers: int
    bits_items: Tuple[Tuple[str, int], ...]
    act_scale: float
    lm_scale: float
    std_items: Tuple[Tuple[str, float], ...]
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

    def projection(self, name: str):
        d, f = self.d_model, self.d_ff
        return {"qkv": (d, 3 * d), "o": (d, d), "up": (d, f), "down": (f, d)}[name]

    @classmethod
    def from_config(cls, cfg: dict) -> "Block":
        q, a = cfg["quant"], cfg["attention"]
        return cls(
            d_model=int(cfg["hidden_size"]),
            n_heads=int(cfg["num_attention_heads"]),
            d_ff=int(cfg["intermediate_size"]),
            vocab=int(cfg["vocab_size"]),
            n_layers=int(cfg["num_hidden_layers"]),
            bits_items=tuple(sorted((k, int(v)) for k, v in q["weight_bits"].items())),
            act_scale=float(q["act_scale"]),
            lm_scale=float(q["lm_scale"]),
            std_items=tuple(sorted((k, float(v)) for k, v in q["code_std"].items())),
            lut_scale=float(a["lut_scale"]),
            mask_penalty=float(a["mask_penalty"]),
            p_scale=float(a["p_scale"]),
            bias_seed=int(q["bias_seed"]),
        )


def fixed_point(multiplier: float, scale_bits: int = 24):
    """(quant_scale, shift) with ``quant_scale · 2**-shift`` just below
    ``multiplier`` and ``quant_scale < 2**scale_bits`` as large as it goes."""
    shift = 0
    while math.floor(multiplier * 2.0 ** (shift + 1)) < (1 << scale_bits):
        shift += 1
    return int(math.floor(multiplier * 2.0**shift)), shift


def weight_range(bits: int):
    return (-8, 7) if bits == 4 else (-127, 127)


def _draw(key, blk: Block):
    """Every weight code, drawn on the device."""
    keys = iter(jax.random.split(key, 2 + 4 * blk.n_layers))

    def codes(shape, std, lo, hi):
        x = jax.random.normal(next(keys), shape, jnp.float32) * std
        return jnp.clip(jnp.rint(x), lo, hi).astype(jnp.int8)

    emb = codes((blk.vocab, blk.d_model), blk.code_std["embedding"], -127, 127)
    emb = emb.at[0].set(0)  # token 0 pads prompts: its embedding is zero
    layers = []
    for _ in range(blk.n_layers):
        layer = {}
        for name in PROJECTIONS:
            bits = blk.bits[name]
            layer[name] = codes(blk.projection(name), blk.code_std[f"w{bits}"], *weight_range(bits))
        layers.append(layer)
    head = codes((blk.d_model, blk.vocab), blk.code_std["lm_head"], -127, 127)
    return {"embedding": emb, "layers": layers, "lm_head": head}


def make_weights(cfg: dict, seed: int) -> dict:
    """The weights of one run of the configuration ``cfg``, from ``seed``:
    int8 codes drawn on the device in one jitted call and brought to the
    host once, plus the biases and the fixed-point rescales, which depend on
    the configuration alone (so that every seed compiles to the same
    programs).

    Returns ``{"embedding", "lm_head", "layers": [{proj: {"w", "b",
    "quant_scale", "shift", "bits"}}]}`` of numpy arrays and numbers."""
    blk = Block.from_config(cfg)
    drawn = jax.device_get(jax.jit(functools.partial(_draw, blk=blk))(jax.random.key(seed)))
    bias_rng = np.random.default_rng(blk.bias_seed)
    act_std = blk.code_std["activation"]
    layers = []
    for layer in drawn["layers"]:
        out = {}
        for name in PROJECTIONS:
            k, n = blk.projection(name)
            bits = blk.bits[name]
            w_std = blk.code_std[f"w{bits}"]
            acc_std = math.sqrt(k) * w_std * act_std
            qs, shift = fixed_point(act_std / acc_std)
            bias = np.rint(bias_rng.normal(size=(n,)) * 0.1 * acc_std).astype(np.int32)
            out[name] = {"w": np.asarray(layer[name]), "b": bias,
                         "quant_scale": qs, "shift": shift, "bits": bits}
        layers.append(out)
    return {"embedding": np.asarray(drawn["embedding"]),
            "lm_head": np.asarray(drawn["lm_head"]), "layers": layers}


def exp_table(blk: Block) -> np.ndarray:
    """``t[i] = round(exp(min(i - 128, 0) · lut_scale) · 255)``, uint8."""
    i = np.arange(256, dtype=np.float64)
    return np.clip(np.rint(np.exp(np.minimum(i - 128.0, 0.0) * blk.lut_scale) * 255.0), 0, 255).astype(np.int32)


def _round_clip(f):
    return jnp.clip(jnp.rint(f), -128, 127).astype(jnp.int8)


def _cut(x, act_bits: int):
    """Activation codes at ``act_bits`` (8: unchanged)."""
    if act_bits == 8:
        return x
    step = 2 ** (8 - act_bits)
    lo, hi = -(2 ** (act_bits - 1)), 2 ** (act_bits - 1) - 1
    q = jnp.clip(jnp.rint(x.astype(jnp.float32) / step), lo, hi) * step
    return jnp.clip(q, -128, 127).astype(jnp.int8)


def _linear(x, p, *, relu=False):
    acc = jax.lax.dot_general(
        x, p["w"], (((x.ndim - 1,), (0,)), ((), ())), preferred_element_type=jnp.int32
    ) + p["b"]
    f = acc.astype(jnp.float32) * jnp.float32(p["quant_scale"]) * jnp.float32(2.0 ** -p["shift"])
    if relu:
        f = jnp.maximum(f, 0.0)
    return _round_clip(f)


def _split(x):
    """Veltkamp's split: ``x == hi + lo`` with at most 12 significant bits
    in each part, so that a product of two parts is exact in f32."""
    c = x * jnp.float32(4097.0)
    hi = c - (c - x)
    return hi, x - hi


def _remainder(a, q, b):
    """``a - q·b``, exact for ``q`` within a few ulps of ``a / b`` (Dekker's
    product, no fused multiply-add needed)."""
    p = q * b
    qh, ql = _split(q)
    bh, bl = _split(b)
    err = ((qh * bh - p) + qh * bl + ql * bh) + ql * bl  # q·b == p + err
    return (a - p) - err


def divide(a, b):
    """``a / b`` rounded to the nearest f32, ties to even, for integer-valued
    ``0 <= a`` and ``1 <= b < 2**24``.  The artifact's ``Div`` is IEEE, and a
    TPU's f32 division is not: so the hardware quotient and its
    neighbours up to two ulps away are compared by their exact residuals."""
    bits = jax.lax.bitcast_convert_type(a / b, jnp.int32)
    best = jax.lax.bitcast_convert_type(bits, jnp.float32)
    best_res = jnp.abs(_remainder(a, best, b))
    for d in (-2, -1, 1, 2):
        c = jax.lax.bitcast_convert_type(bits + d, jnp.float32)
        r = jnp.abs(_remainder(a, c, b))
        better = (r < best_res) | ((r == best_res) & (((bits + d) & 1) == 0))
        best, best_res = jnp.where(better, c, best), jnp.where(better, r, best_res)
    return jnp.where(a == 0, jnp.float32(0.0), best)


def _attention(blk: Block, q, k, v, table, act_bits):
    b, s, _ = q.shape
    h, dh = blk.n_heads, blk.d_head
    q, k, v = (t.reshape(b, s, h, dh) for t in (q, k, v))
    acc = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.int32)
    qk_scale = jnp.float32(blk.act_scale * blk.act_scale / math.sqrt(dh))
    causal = jnp.tril(jnp.ones((s, s), jnp.float32))
    scores = acc.astype(jnp.float32) * qk_scale
    masked = scores * causal + (causal - 1.0) * jnp.float32(blk.mask_penalty)
    delta = masked - jnp.max(masked, axis=-1, keepdims=True)
    idx = jnp.clip(jnp.rint(delta / jnp.float32(blk.lut_scale)), -128, 127).astype(jnp.int32)
    w = jnp.take(table, idx + 128)
    p = divide(w.astype(jnp.float32), jnp.sum(w, axis=-1, keepdims=True).astype(jnp.float32))
    p_q = _cut(_round_clip(p * jnp.float32(blk.p_scale)), act_bits)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", p_q, v, preferred_element_type=jnp.int32)
    out = ctx.astype(jnp.float32) * jnp.float32(1.0 / blk.p_scale)
    return _round_clip(out).reshape(b, s, h * dh)


def _residual(a, b):
    return _round_clip(a.astype(jnp.float32) + b.astype(jnp.float32))


def _forward(weights, tokens, positions, *, blk: Block, act_bits: int):
    table = jnp.asarray(exp_table(blk))
    d = blk.d_model
    x = _cut(jnp.take(weights["embedding"], tokens, axis=0), act_bits)
    for p in weights["layers"]:
        qkv = _cut(_linear(x, p["qkv"]), act_bits)
        ctx = _cut(_attention(blk, qkv[..., :d], qkv[..., d:2 * d], qkv[..., 2 * d:], table, act_bits), act_bits)
        x1 = _cut(_residual(x, _cut(_linear(ctx, p["o"]), act_bits)), act_bits)
        up = _cut(_linear(x1, p["up"], relu=True), act_bits)
        x = _cut(_residual(x1, _cut(_linear(up, p["down"]), act_bits)), act_bits)
    rows = jnp.take_along_axis(x, positions[..., None], axis=1)  # (B, P, D)
    acc = jax.lax.dot_general(
        rows, weights["lm_head"], (((2,), (0,)), ((), ())), preferred_element_type=jnp.int32
    )
    return acc.astype(jnp.float32) * jnp.float32(blk.lm_scale)


def device_weights(weights: dict) -> dict:
    """The codes of :func:`make_weights` as device arrays for :func:`forward`."""
    layers = [
        {name: {"w": jnp.asarray(p["w"]), "b": jnp.asarray(p["b"]),
                "quant_scale": p["quant_scale"], "shift": p["shift"]}
         for name, p in layer.items()}
        for layer in weights["layers"]
    ]
    return {"embedding": jnp.asarray(weights["embedding"]),
            "lm_head": jnp.asarray(weights["lm_head"]), "layers": layers}


@functools.lru_cache(maxsize=None)
def _jitted(blk: Block, act_bits: int, rescales: tuple):
    """The jitted pass; the fixed-point rescales are compile-time constants."""

    def fn(arrays, tokens, positions):
        layers = [
            {n: {**arrays["layers"][i][n], "quant_scale": qs, "shift": shift}
             for n, (qs, shift) in zip(PROJECTIONS, rescales[i])}
            for i in range(blk.n_layers)
        ]
        w = {"embedding": arrays["embedding"], "lm_head": arrays["lm_head"], "layers": layers}
        return _forward(w, tokens, positions, blk=blk, act_bits=act_bits)

    return jax.jit(fn)


def forward(blk: Block, weights: dict, tokens, positions, *, act_bits: int = 8):
    """Logits ``(B, P, V)`` f32 at ``positions (B, P)`` of the sequences
    ``tokens (B, L)``, from one causal pass over the whole sequences.
    ``weights`` as :func:`device_weights` gives them."""
    arrays = {
        "embedding": weights["embedding"], "lm_head": weights["lm_head"],
        "layers": [{n: {"w": p[n]["w"], "b": p[n]["b"]} for n in PROJECTIONS} for p in weights["layers"]],
    }
    rescales = tuple(
        tuple((p[n]["quant_scale"], p[n]["shift"]) for n in PROJECTIONS) for p in weights["layers"]
    )
    fn = _jitted(blk, act_bits, rescales)
    return fn(arrays, jnp.asarray(tokens, jnp.int32), jnp.asarray(positions, jnp.int32))


def greedy_gaps(logits: np.ndarray, tokens: np.ndarray, valid: np.ndarray) -> np.ndarray:
    """For each valid position, how far the logit of ``tokens`` lies below
    the best logit there."""
    logits = np.asarray(logits, np.float64)
    picked = np.take_along_axis(logits, tokens[..., None], axis=-1)[..., 0]
    return (logits.max(axis=-1) - picked)[valid]


def sequences(requests: List[dict], length: int, width: int):
    """Teacher-forced batch of served requests: each ``{"prompt",
    "generated"}`` becomes the tokens ``prompt + generated[:-1]`` padded to
    ``length``, the positions that produced each served token (padded to
    ``width``), the served tokens, and a validity mask."""
    b = len(requests)
    tokens = np.zeros((b, length), np.int32)
    positions = np.zeros((b, width), np.int32)
    served = np.zeros((b, width), np.int32)
    valid = np.zeros((b, width), bool)
    for i, r in enumerate(requests):
        prompt, gen = np.asarray(r["prompt"]), np.asarray(r["generated"])
        seq = np.concatenate([prompt, gen[:-1]])
        tokens[i, : len(seq)] = seq
        n = len(gen)
        positions[i, :n] = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
        served[i, :n] = gen
        valid[i, :n] = True
    return tokens, positions, served, valid


def readings(cell, weights: dict, sample: List[dict], *, control: bool = False) -> Dict[str, np.ndarray]:
    """Per served token of ``sample`` (``{"prompt", "generated"}`` each), the
    gap of its logit below the reference's best (``"program"``); with
    ``control`` also the gap of the token that the reference at
    :data:`CONTROL_BITS`-bit activations puts first (``"control"``).  Runs
    in batches of :data:`BATCH` sequences padded to the cell's ``max_len``."""
    blk = Block.from_config(cell.config)
    length = int(cell.spec["engine"]["max_len"])
    width = int(cell.traffic["output"]["max"])
    dw = device_weights(weights)
    out = {"program": [], "control": []}
    for i in range(0, len(sample), BATCH):
        batch = sample[i:i + BATCH]
        batch = batch + [batch[0]] * (BATCH - len(batch))  # one compiled shape
        real = min(BATCH, len(sample) - i)
        tokens, positions, served, valid = sequences(batch, length, width)
        valid[real:] = False
        logits = np.asarray(forward(blk, dw, tokens, positions))
        out["program"].append(greedy_gaps(logits, served, valid))
        if control:
            low = np.asarray(forward(blk, dw, tokens, positions, act_bits=CONTROL_BITS))
            out["control"].append(greedy_gaps(logits, low.argmax(-1).astype(np.int32), valid))
    return {k: (np.concatenate(v) if v else np.zeros(0)) for k, v in out.items()}


def checks(cell, weights: dict, sample: List[dict]):
    """The numbers compared, each beside its limit, and whether all hold:
    ``logit_gap``, the widest gap of a served token's logit below the
    reference's best (at most the cell's ``logit_gap_limit``), and
    ``tokens_compared`` (at least its ``min_tokens``)."""
    spec = cell.spec["check"]
    gaps = readings(cell, weights, sample)["program"]
    widest = float(gaps.max()) if gaps.size else float("inf")
    out = {
        "logit_gap": {"value": widest, "limit": float(spec["logit_gap_limit"])},
        "tokens_compared": {"value": int(gaps.size), "limit": int(spec["min_tokens"])},
    }
    ok = widest <= out["logit_gap"]["limit"] and gaps.size >= out["tokens_compared"]["limit"]
    return out, bool(ok)
