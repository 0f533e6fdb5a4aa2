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
:class:`repro.serving.engine.ServeEngine` behind the same adapter seam the
opaque-JAX model uses.
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
    build_exp_lut,
    emit_qattention,
    emit_round_clip,
    fc_layer,
)
from ..core.quant import QuantizedLinearParams, quantize_linear_layer
from ..kernels import ref as _ref

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

    @property
    def d_head(self) -> int:
        return self.d_model // self.n_heads

    @property
    def qk_scale(self) -> float:
        return float(self.act_scale * self.act_scale / np.sqrt(self.d_head))

    @property
    def att_rescale(self) -> float:
        # s_v / (p_scale * s_out) with s_v == s_out == act_scale
        return float(1.0 / ATTN_P_SCALE)


@dataclasses.dataclass
class TokenPathParams:
    """Pre-quantized parameters of the token path (what the artifact embeds)."""

    embedding: np.ndarray  # (vocab, d_model) int8 codes; row 0 all-zero
    layers: List[Dict[str, QuantizedLinearParams]]
    lm_head: np.ndarray  # (d_model, vocab) int8
    lm_scale: float


def make_token_params(cfg: TokenPathConfig, seed: int = 0) -> TokenPathParams:
    """Deterministic pre-quantized parameters.  Weights are drawn small enough
    that activations stay inside int8 on typical inputs (bit-exactness never
    depends on this — saturation is itself exact — it just keeps the logits
    informative)."""
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


def prefill_jax(cfg: TokenPathConfig, params: TokenPathParams, tokens, mask, lut=None):
    """jnp mirror of the prefill artifact: op-for-op the same integer/f32
    chain, so the result is bit-identical.  Returns (logits, [(k, v)] per
    layer)."""
    lut = build_exp_lut() if lut is None else lut
    D = cfg.d_model
    x = jnp.take(jnp.asarray(params.embedding), jnp.asarray(tokens, jnp.int32), axis=0)
    caches = []
    for p in params.layers:
        qkv = _fc_jax(x, p["qkv"])
        qf, kf, vf = qkv[..., :D], qkv[..., D : 2 * D], qkv[..., 2 * D :]
        caches.append((kf, vf))
        x = _block_jax(cfg, p, x, kf, vf, qf, mask, lut)
    return _logits_jax(params, x), caches


def decode_jax(cfg: TokenPathConfig, params: TokenPathParams, tokens, onehot, mask, states, lut=None):
    """jnp mirror of the decode artifact.  ``states`` is [(k, v)] per layer;
    returns (logits, new_states) with the codified int8 scatter update."""
    lut = build_exp_lut() if lut is None else lut
    D = cfg.d_model
    oh = jnp.asarray(onehot, jnp.int8)
    keep = (jnp.int8(1) - oh).astype(jnp.int8)
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
        # jitted one-dispatch decode steps, keyed by exact (N, S) cell
        self._step_fns: Dict[Tuple[int, int], object] = {}

    # -- direct run API -------------------------------------------------------
    def prefill(self, tokens: np.ndarray, mask: np.ndarray):
        """Returns (logits (N,S,V) f32, {state-input name: (N,S,D) int8})."""
        outs = self.prefill_cm.run({"tokens": np.asarray(tokens, np.int32), "mask": mask})
        cache = {inp: np.asarray(outs[name]) for inp, name in self._prefill_kv.items()}
        return np.asarray(outs[self._logits_prefill]), cache

    def decode(self, tokens, onehot, mask, cache: Dict[str, np.ndarray]):
        """One decode step.  Returns (logits (N,1,V), next cache dict)."""
        feeds = {
            "tokens": np.asarray(tokens, np.int32),
            "onehot": np.asarray(onehot, np.int8),
            "mask": mask,
        }
        feeds.update(cache)
        outs = self.decode_cm.run(feeds)
        nxt = {s.input: np.asarray(outs[s.output]) for s in self.state_specs}
        return np.asarray(outs[self._logits_decode]), nxt

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
        is identical to the slow path: one miss per first-visited cell,
        hits thereafter.  Falls back to :meth:`decode` when the extents are
        not bucket-aligned (then padding/slicing is required and the slow
        path is the correct one).  Returns (logits (N, V) ndarray, next
        cache of device arrays)."""
        n = int(np.shape(tokens)[0])
        s = int(np.shape(next(iter(cache.values())))[1])
        cm = self.decode_cm
        if cm.bucket_for("N", n) != n or cm.bucket_for("S", s) != s:
            pos = np.asarray(pos, np.int64)
            onehot = np.zeros((n, s, 1), np.int8)
            onehot[np.arange(n), np.clip(pos, 0, s - 1), 0] = 1
            mask = (np.arange(s)[None, None, :] <= pos[:, None, None]).astype(np.float32)
            logits, nxt = self.decode(tokens, onehot, mask, cache)
            return logits[:, 0, :], nxt
        plan, _ = cm.specialized({"N": n, "S": s})  # per-step cell accounting
        entry = self._step_fns.get((n, s))
        if entry is None:
            logits_name, specs = self._logits_decode, self.state_specs

            def step(params, toks, pos, cache):
                onehot = (jnp.arange(s)[None, :, None] == pos[:, None, None]).astype(jnp.int8)
                mask = (jnp.arange(s)[None, None, :] <= pos[:, None, None]).astype(jnp.float32)
                feeds = {"tokens": toks, "onehot": onehot, "mask": mask}
                feeds.update(cache)
                outs = plan.execute(feeds, params)
                return outs[logits_name][:, 0, :], {sp.input: outs[sp.output] for sp in specs}

            entry = self._step_fns[(n, s)] = (jax.jit(step), plan.params())
        fn, params = entry
        logits, nxt = fn(
            params, jnp.asarray(tokens, jnp.int32), jnp.asarray(np.asarray(pos), jnp.int32), cache
        )
        return np.asarray(logits), nxt

    def init_cache(self, n: int, s: int) -> Dict[str, np.ndarray]:
        D = self.cfg.d_model
        return {spec.input: np.zeros((n, s, D), np.int8) for spec in self.state_specs}

    def cache_stats(self) -> Dict[str, float]:
        return self.plan_cache.stats


class CompiledTokenAdapter:
    """ServeEngine adapter for the compiled token path.

    ``init_cache``/``prefill``/``decode``/``scatter`` mirror
    :class:`repro.serving.engine.OpaqueModelAdapter`'s seam, but every call
    executes a pre-specialized ExecutionPlan out of the shared PlanCache —
    after the first step per cell there is zero lowering work per token."""

    def __init__(self, tp: CompiledTokenPath) -> None:
        self.tp = tp
        self.cfg = tp.cfg
        self.max_len = 0
        # no per-bucket jitted-fn cache here — plan specialization IS the
        # per-bucket discipline, surfaced via tp.cache_stats()
        self.prefill_cache = None

    def init_cache(self, slots: int, max_len: int):
        self.max_len = max_len
        return self.tp.init_cache(slots, max_len)

    @staticmethod
    def _causal_mask(n: int, s: int) -> np.ndarray:
        return np.broadcast_to(
            np.tril(np.ones((s, s), np.float32)), (n, s, s)
        ).copy()

    def prefill(self, padded: np.ndarray, plen: int, max_len: int):
        bucket = padded.shape[1]
        logits, cache = self.tp.prefill(padded, self._causal_mask(1, bucket))
        return logits[0, plen - 1], cache

    def scatter(self, cache, slot: int, pcache):
        # cache values may be device arrays (the decode fast path keeps them
        # there between steps); np.array materializes either kind
        out = {}
        for name, buf in cache.items():
            rows = np.asarray(pcache[name])
            dst = np.array(buf, copy=True)
            n = min(rows.shape[1], dst.shape[1])
            dst[slot, :n] = rows[0, :n]
            # rows ≥ prompt bucket keep their zeros: masked until the decode
            # onehot overwrites them position by position
            out[name] = dst
        return out

    def decode(self, toks: np.ndarray, pos: np.ndarray, cache):
        return self.tp.decode_step(toks, pos, cache)
