"""The served path ``moe_token_path``: the token path's sparse-expert block
(``TokenPathConfig(block="moe")``) behind the same ``ServeEngine`` and
``CompiledTokenAdapter`` as ``token_path``.

Each layer runs RMSNorm, rotary attention on the ``qattention`` kernel, a
router, the routed experts on the grouped ``qmoe`` kernel and a gated
shared expert on ``qmatmul``.  Every adapter call is timed as in
``token_path``, and also logs the experts its rows were routed to, so that
:func:`work` counts the ``qmoe`` calls at their true shapes: operations for
the routed rows of the prompt or of the live slots only, bytes for the
weights of the experts those rows hit, plus the rows.

What the harness calls: :func:`build`, :func:`work` and :data:`KERNELS`;
``bench/rehearse_compile.py`` calls :func:`rehearsal_programs`.
"""
from __future__ import annotations

from pathlib import Path
from typing import Dict

import jax.numpy as jnp
import numpy as np

from repro.backend.plan import bucket_multiple
from repro.core.quant import QuantizedLinearParams, Rescale
from repro.obs.metrics import default_registry
from repro.serving.engine import EngineConfig
from repro.serving.token_path import (
    CompiledTokenAdapter,
    CompiledTokenPath,
    RoutedExperts,
    TokenPathConfig,
    TokenPathParams,
)

import harness  # bench/harness.py, on the path the harness sets
import work as yardstick  # bench/work.py

base = harness.module("paths", "token_path", Path(__file__).resolve().parents[1])

KERNELS = {
    "qmatmul": ("qmatmul", "qmatmul_packed"),
    "qattention": ("qattention",),
    "qmoe": ("qmoe",),
}
#: The counters ``counters()`` reports, from ``repro.obs``'s registry.
COUNTERS = ("tokenpath.moe.decode.experts_hit", "tokenpath.moe.decode.layer_calls",
            "tokenpath.moe.rows", "tokenpath.moe.experts_hit")


def moe_config(cfg: dict) -> TokenPathConfig:
    q = cfg["quant"]
    bits = q["weight_bits"]
    return TokenPathConfig(
        vocab=int(cfg["vocab_size"]),
        d_model=int(cfg["hidden_size"]),
        n_heads=int(cfg["num_attention_heads"]),
        d_ff=int(cfg["shared_expert_intermediate_size"]),
        n_layers=int(cfg["num_hidden_layers"]),
        act_scale=float(q["act_scale"]),
        lm_scale=float(q["lm_scale"]),
        bits_qkv=int(bits["qkv"]),
        bits_o=int(bits["o"]),
        bits_up=int(bits["shared_up"]),
        bits_down=int(bits["shared_down"]),
        block="moe",
        n_experts=int(cfg["num_experts"]),
        top_k=int(cfg["num_experts_per_tok"]),
        d_expert=int(cfg["moe_intermediate_size"]),
        bits_expert_down=int(bits["down"]),
        rms_eps=float(cfg["rms_norm_eps"]),
        rope_theta=float(cfg["rope_theta"]),
        max_pos=int(cfg["max_position_embeddings"]),
        glu_scale=float(q["glu_scale"]),
    )


def _multiplier(p: dict) -> float:
    return float(np.float32(p["quant_scale"] * 2.0 ** -p["shift"]))


def _linear(p: dict, scale: float) -> QuantizedLinearParams:
    mult = _multiplier(p)
    return QuantizedLinearParams(
        weight_q=p["w"], bias_q=p["b"], scale_x=scale, scale_w=np.float32(mult), scale_y=scale,
        rescale=Rescale(quant_scale=p["quant_scale"], shift=p["shift"], multiplier=mult), bits=p["bits"],
    )


def moe_params(tcfg: TokenPathConfig, weights: dict) -> TokenPathParams:
    """The benchmark's weight codes in the program's parameter types."""
    s = tcfg.act_scale
    layers = []
    for layer in weights["layers"]:
        router, gate, up, down = (layer[k] for k in ("router", "gate", "up", "down"))
        out = {name: _linear(layer[name], s) for name in
               ("qkv", "o", "shared_gate", "shared_up", "shared_down", "shared_router")}
        out["attn_norm"], out["ffn_norm"] = layer["attn_norm"], layer["ffn_norm"]
        out["experts"] = RoutedExperts(
            router=router["w"], router_scale=_multiplier(router),
            gate=gate["w"], up=up["w"], down=down["w"],
            r_gate=_multiplier(gate), r_up=_multiplier(up), r_down=_multiplier(down),
            bits_down=int(down["bits"]),
        )
        layers.append(out)
    return TokenPathParams(weights["embedding"], layers, weights["lm_head"], tcfg.lm_scale,
                           final_norm=weights["final_norm"])


class RoutedAdapter(base.TimedAdapter):
    """The timed adapter; each logged call's shape also holds the experts
    its rows were routed to: ``(plen, routing)`` of a prefill,
    ``((positions, slots), routing)`` of a decode (routing on the device
    until :func:`work` reads it, after the window)."""

    def prefill(self, padded, plen: int, max_len: int):
        out = super().prefill(padded, plen, max_len)
        self._route(plen)
        return out

    def decode(self, toks, pos, cache):
        out = super().decode(toks, pos, cache)
        self._route((self.log[-1][3], tuple(int(s) for s in self.live())))
        return out

    def _route(self, shape) -> None:
        kind, t, dt, _ = self.log[-1]
        self.log[-1] = (kind, t, dt, (shape, self.inner.tp.last_routing))


class Served(base.Served):
    """One built sparse-expert token path behind its engine."""

    def __init__(self, cell, weights: dict) -> None:
        cfg, engine_cfg = cell.config, cell.spec["engine"]
        self.traffic = cell.traffic
        self.tcfg = moe_config(cfg)
        self.tp = CompiledTokenPath(self.tcfg, moe_params(self.tcfg, weights), backend=cfg.get("backend", "pallas"))
        self.adapter = RoutedAdapter(CompiledTokenAdapter(self.tp))
        self.ecfg = EngineConfig(
            slots=int(engine_cfg["slots"]),
            max_len=int(engine_cfg["max_len"]),
            prefill_bucket=int(engine_cfg["prefill_bucket"]),
            greedy=True,
        )
        self._since = {}
        self.reset()

    def _registry(self) -> Dict[str, int]:
        self.tp.flush_routing()
        reg = default_registry()
        return {name: int(reg.counter(name).value) for name in COUNTERS}

    def warm_up(self) -> int:
        n = super().warm_up()
        self._since = self._registry()
        return n

    def counters(self) -> Dict[str, int]:
        """The engine's counts, and the routing counters since the warm-up."""
        now = self._registry()
        out = super().counters()
        out.update({name: now[name] - self._since.get(name, 0) for name in COUNTERS})
        return out


def build(cell, weights: dict) -> Served:
    return Served(cell, weights)


class MoeWork(yardstick.TokenPathWork):
    """True-shape work of the sparse-expert block: the fused projections
    (qkv, o and the shared expert's gate, up, down and gate logit), causal
    attention, and the routed experts of the rows routed (``qmoe``)."""

    def __init__(self, tcfg: TokenPathConfig, peaks: Dict[str, float]) -> None:
        shapes = yardstick.BlockShapes(tcfg.d_model, tcfg.n_heads, tcfg.d_ff, tcfg.vocab, tcfg.n_layers,
                                       {"qkv": tcfg.bits_qkv, "o": tcfg.bits_o, "up": tcfg.bits_up,
                                        "down": tcfg.bits_down})
        super().__init__(shapes, peaks)
        self.tcfg = tcfg
        self.kernels["qmoe"] = yardstick.Work()

    def _block(self, m: int, attn) -> float:
        t = self.tcfg
        d, fs = t.d_model, t.d_ff
        ops_total = 0.0
        for k, n, bits in ((d, 3 * d, t.bits_qkv), (d, d, t.bits_o), (d, fs, t.bits_up), (d, fs, t.bits_up),
                           (fs, d, t.bits_down), (d, 1, 8)):
            ops, nbytes = yardstick.qmatmul(m, k, n, bits)
            self.kernels["qmatmul"].add(ops, nbytes, self.peaks, count=t.n_layers)
            ops_total += t.n_layers * ops
        calls = t.n_layers * t.n_heads
        self.kernels["qattention"].add(attn[0], attn[1], self.peaks, count=calls)
        return ops_total + calls * attn[0]

    def experts(self, routing: np.ndarray) -> float:
        """One ``qmoe`` call per layer over ``routing (layers, rows, K)``;
        returns the operations."""
        t = self.tcfg
        d, f = t.d_model, t.d_expert
        per_expert = 2 * d * f + f * d * t.bits_expert_down / 8.0
        total = 0.0
        for chosen in routing:
            rows = chosen.shape[0]
            ops = 6.0 * d * f * chosen.size
            nbytes = np.unique(chosen).size * per_expert + chosen.size * d + 4.0 * rows * d
            self.kernels["qmoe"].add(ops, nbytes, self.peaks)
            total += ops
        return total

    def routed_prefill(self, plen: int, routing) -> None:
        self.prefill(plen)
        self.needed_ops += self.experts(np.asarray(routing)[:, 0, :plen, :])

    def routed_decode(self, positions, slots, routing) -> None:
        if not slots:
            return
        self.decode(positions)
        self.needed_ops += self.experts(np.asarray(routing)[:, list(slots), 0, :])


def work(calls, cfg: dict, peaks: Dict[str, float]) -> MoeWork:
    """The operations and bytes the logged calls needed, at true shapes."""
    w = MoeWork(moe_config(cfg), peaks)
    for kind, _, _, shape in calls:
        if kind == "prefill":
            w.routed_prefill(*shape)
        elif kind == "decode":
            (positions, slots), routing = shape
            w.routed_decode(positions, slots, routing)
    return w


def rehearsal_programs(cell, weights: dict) -> Dict[str, tuple]:
    """The largest prefill bucket's and the decode step's plans, each with
    its feeds as ``{name: (shape, dtype)}``."""
    tcfg = moe_config(cell.config)
    tp = CompiledTokenPath(tcfg, moe_params(tcfg, weights), backend="pallas")
    eng = cell.spec["engine"]
    slots, max_len, g = int(eng["slots"]), int(eng["max_len"]), int(eng["prefill_bucket"])
    bucket = bucket_multiple(int(cell.traffic["prompt"]["max"]), g)
    return {
        f"prefill (1, {bucket})": (
            tp.prefill_cm.specialized({"N": 1, "S": bucket})[0],
            {"tokens": ((1, bucket), jnp.int32), "positions": ((1, bucket), jnp.int32),
             "mask": ((1, bucket, bucket), jnp.float32)},
        ),
        f"decode ({slots}, {max_len})": (
            tp.decode_cm.specialized({"N": slots, "S": max_len})[0],
            {"tokens": ((slots, 1), jnp.int32), "positions": ((slots, 1), jnp.int32),
             "onehot": ((slots, max_len, 1), jnp.int8), "mask": ((slots, 1, max_len), jnp.float32),
             **{s.input: ((slots, max_len, tcfg.d_model), jnp.int8) for s in tp.state_specs}},
        ),
    }
