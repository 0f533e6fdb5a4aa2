"""Run the system's main paths once on one TPU and check what comes out.

    python chip_smoke.py

Phase A serves a few requests through ``ServeEngine`` on the compiled token
path (``CompiledTokenPath(backend="pallas")``) at the published widths of
Ouro-2.6B, depth cut to 2 layers.  Every prefill and decode step is checked
against the jnp mirrors (``prefill_jax`` / ``decode_jax``) run on the same
chip: logits and K/V rows must be bit-equal.  The first prefill and the
first decode step are also checked against ``ReferenceRuntime``, the numpy
executor of the artifact's own semantics, on the host.  The compiled decode
step must hold one Pallas kernel per fused matmul and per attention head.

Phase B compiles the paper's quickstart MLP plus an int8 tanh layer with
``backend="pallas", batch="dynamic"`` and serves a few batches through
``CompiledModelServer``; every output must be bit-equal to
``ReferenceRuntime``.

Weights and inputs come from fixed seeds.  The script exits non-zero and
prints no result line when JAX finds no TPU or any check fails.  The last
line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from repro.core import quant  # noqa: E402
from repro.core.compile import compile_model  # noqa: E402
from repro.core.runtime import ReferenceRuntime  # noqa: E402
from repro.core.toolchain import MLPSpec, quantize_mlp  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.serving.compiled import CompiledModelServer, CompiledServerConfig  # noqa: E402
from repro.serving.engine import EngineConfig, Request, ServeEngine  # noqa: E402
from repro.serving.token_path import (  # noqa: E402
    CompiledTokenAdapter,
    CompiledTokenPath,
    TokenPathConfig,
    causal_mask,
    decode_jax,
    prefill_jax,
)

#: The token path's block at the published widths of Ouro-2.6B
#: (https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json: hidden
#: 2048, 16 heads of 128, FFN 5632, vocabulary 49152), depth cut to 2 layers,
#: default w4/w8 lanes.
OURO_WIDTHS = TokenPathConfig(vocab=49152, d_model=2048, n_heads=16, d_ff=5632, n_layers=2)
SLOTS, MAX_LEN, NEW_TOKENS = 4, 1024, 16
PROMPT_LENS = (40, 130, 300, 700)


class SmokeFailure(AssertionError):
    pass


def require(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def require_equal(what: str, got, want) -> None:
    got, want = np.asarray(got), np.asarray(want)
    require(got.shape == want.shape and got.dtype == want.dtype,
            f"{what}: {got.dtype}{got.shape} vs {want.dtype}{want.shape}")
    bad = got != want
    if bad.any():
        first = tuple(int(i) for i in np.argwhere(bad)[0])
        raise SmokeFailure(
            f"{what}: {int(bad.sum())} of {bad.size} elements differ; first at "
            f"{first}: {got[first]!r} vs {want[first]!r}"
        )


def expected_custom_calls(cfg: TokenPathConfig) -> int:
    """One Pallas kernel per fused matmul (qkv, o, up, down) and per head."""
    return cfg.n_layers * (4 + cfg.n_heads)


class MirrorCheckedAdapter(CompiledTokenAdapter):
    """The engine's adapter for the compiled token path, with every prefill
    and decode step checked against the jnp mirrors on the same device, and
    the first of each against ``ReferenceRuntime`` on the host (the mirrors
    share the device's arithmetic; the runtime is the artifact's)."""

    def __init__(self, tp: CompiledTokenPath) -> None:
        super().__init__(tp)
        self.prefills = 0
        self.decode_steps = 0

    @staticmethod
    def _reference_logits(model, feeds) -> np.ndarray:
        feeds = {k: np.asarray(v) for k, v in feeds.items()}
        return ReferenceRuntime(model).run(feeds)[model.graph.outputs[0].name]

    def _require_kv(self, what: str, cache, ref_kv) -> None:
        for l, (k, v) in enumerate(ref_kv):
            require_equal(f"{what} K rows, layer {l}", cache[f"k_cache_{l}"], k)
            require_equal(f"{what} V rows, layer {l}", cache[f"v_cache_{l}"], v)

    def prefill(self, padded, plen, max_len):
        row, cache = super().prefill(padded, plen, max_len)
        mask = causal_mask(1, padded.shape[1])
        ref_logits, ref_kv = prefill_jax(self.cfg, self.tp.params, padded, mask)
        what = f"prefill {self.prefills} ({plen} tokens)"
        self._require_kv(what, cache, ref_kv)
        require_equal(f"{what} logits", row, ref_logits[0, plen - 1])
        if self.prefills == 0:
            want = self._reference_logits(self.tp.prefill_model, {"tokens": padded, "mask": mask})
            require_equal(f"{what} logits vs ReferenceRuntime", row, want[0, plen - 1])
        self.prefills += 1
        return row, cache

    def decode(self, toks, pos, cache):
        logits, nxt = super().decode(toks, pos, cache)
        s = np.shape(next(iter(cache.values())))[1]
        p = np.asarray(pos)[:, None, None]
        onehot = (np.arange(s)[None, :, None] == p).astype(np.int8)
        mask = (np.arange(s)[None, None, :] <= p).astype(np.float32)
        states = [(cache[f"k_cache_{l}"], cache[f"v_cache_{l}"]) for l in range(self.cfg.n_layers)]
        ref_logits, ref_states = decode_jax(self.cfg, self.tp.params, toks, onehot, mask, states)
        what = f"decode step {self.decode_steps}"
        self._require_kv(what, nxt, ref_states)
        require_equal(f"{what} logits", logits, ref_logits[:, 0, :])
        if self.decode_steps == 0:
            feeds = {"tokens": toks, "onehot": onehot, "mask": mask, **cache}
            want = self._reference_logits(self.tp.decode_model, feeds)
            require_equal(f"{what} logits vs ReferenceRuntime", logits, want[:, 0, :])
        self.decode_steps += 1
        return logits, nxt


def phase_a(
    cfg: TokenPathConfig = OURO_WIDTHS,
    *,
    backend: str = "pallas",
    slots: int = SLOTS,
    max_len: int = MAX_LEN,
    prompt_lens=PROMPT_LENS,
    new_tokens: int = NEW_TOKENS,
    seed: int = 0,
) -> dict:
    """Serve ``prompt_lens`` through the engine on the compiled token path,
    every step mirror-checked.  Heuristic tiles only (no autotuning)."""
    t0 = time.perf_counter()
    tp = CompiledTokenPath(cfg, backend=backend, seed=seed)
    adapter = MirrorCheckedAdapter(tp)
    engine = ServeEngine(adapter=adapter, ecfg=EngineConfig(slots=slots, max_len=max_len))
    t1 = time.perf_counter()
    rng = np.random.default_rng(seed)
    reqs = [
        Request(uid=i, prompt=rng.integers(1, cfg.vocab, (n,)).astype(np.int32), max_new_tokens=new_tokens)
        for i, n in enumerate(prompt_lens)
    ]
    for r in reqs:
        engine.submit(r)
    engine.run_until_drained()
    t2 = time.perf_counter()
    for r in reqs:
        require(r.done and len(r.generated) == new_tokens,
                f"request {r.uid}: {len(r.generated)} of {new_tokens} tokens")
    require(adapter.prefills == len(reqs), f"{adapter.prefills} prefills for {len(reqs)} requests")
    require(adapter.decode_steps >= new_tokens - 1, f"only {adapter.decode_steps} decode steps")
    return {
        "tp": tp,
        "setup_s": t1 - t0,
        "serve_s": t2 - t1,
        "prompt_tokens": int(sum(prompt_lens)),
        "generated_tokens": sum(len(r.generated) for r in reqs),
        "prefills": adapter.prefills,
        "decode_steps": adapter.decode_steps,
    }


def decode_custom_calls(tp: CompiledTokenPath, slots: int, max_len: int, device=None) -> int:
    """Pallas kernels (``tpu_custom_call`` call sites) in the decode step of
    the ``(slots, max_len)`` cell, compiled for ``device`` (default: the
    first device)."""
    plan, _ = tp.decode_cm.specialized({"N": slots, "S": max_len})
    on = SingleDeviceSharding(device if device is not None else jax.devices()[0])
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=on)  # noqa: E731
    feeds = {
        "tokens": spec((slots, 1), jnp.int32),
        "onehot": spec((slots, max_len, 1), jnp.int8),
        "mask": spec((slots, 1, max_len), jnp.float32),
    }
    for s in tp.state_specs:
        feeds[s.input] = spec((slots, max_len, tp.cfg.d_model), jnp.int8)
    params = jax.tree.map(lambda a: spec(a.shape, a.dtype), plan.params())
    text = jax.jit(plan.execute).lower(feeds, params).compile().as_text()
    return text.count('custom_call_target="tpu_custom_call"')


def quickstart_tanh_spec(rng: np.random.Generator) -> MLPSpec:
    """examples/quickstart.py's MLP with an int8 tanh layer before the head."""
    w = lambda i, o, s: rng.normal(size=(i, o)).astype(np.float32) * s  # noqa: E731
    b = lambda o: rng.normal(size=(o,)).astype(np.float32) * 0.1  # noqa: E731
    return MLPSpec(
        weights=[w(64, 128, 0.2), w(128, 128, 0.15), w(128, 128, 0.15), w(128, 10, 0.2)],
        biases=[b(128), b(128), b(128), b(10)],
        activations=["Relu", "Relu", "Tanh", None],
    )


def phase_b(*, backend: str = "pallas", waves=(8, 3, 1), seed: int = 0) -> dict:
    """Serve ``waves`` of requests through ``CompiledModelServer`` on the
    quickstart MLP with a tanh LUT layer; bit-equal to ``ReferenceRuntime``."""
    t0 = time.perf_counter()
    rng = np.random.default_rng(seed)
    spec = quickstart_tanh_spec(rng)
    model = quantize_mlp(spec, rng.normal(size=(512, 64)).astype(np.float32),
                         observer="percentile", name="quickstart_mlp_tanh")
    cm = compile_model(model, backend=backend, batch="dynamic")
    require(cm.stats["fused_qlinear"] == 4 and cm.stats["fused_lut"] == 1,
            f"fusion stats {cm.stats}")
    server = CompiledModelServer(cm, CompiledServerConfig(max_batch=max(waves)))
    ref = ReferenceRuntime(model)
    out = model.graph.outputs[0].name
    s_in = float(model.metadata["input_scale"])
    t1 = time.perf_counter()
    for n in waves:
        xq = quant.quantize(rng.normal(size=(n, 64)).astype(np.float32), s_in, "int8")
        reqs = [server.submit(x) for x in xq]
        require(len(server.step()) == n, f"a wave of {n} requests was not served in one batch")
        want = ref.run({"input_q": xq})[out]
        for i, r in enumerate(reqs):
            require_equal(f"phase B request {r.uid} ({n}-request wave)", r.outputs[out], want[i])
    t2 = time.perf_counter()
    return {
        "setup_s": t1 - t0,
        "serve_s": t2 - t1,
        "requests": sum(waves),
        "cells": sorted(server.metrics["bucket_batches"]),
    }


def main() -> int:
    devices = jax.devices()
    print(f"devices: {devices}")
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}", file=sys.stderr)
        return 1
    print(f"compile cache: {enable_compile_cache()}")
    tag = f"[{dev.platform} {dev.device_kind} x{len(devices)}]"

    cfg = OURO_WIDTHS
    print(f"{tag} phase A: the token path's own block at Ouro-2.6B widths (d_model "
          f"{cfg.d_model}, {cfg.n_heads} heads x {cfg.d_head}, FFN {cfg.d_ff}, vocab "
          f"{cfg.vocab}, {cfg.n_layers} layers). Not Ouro: no RMSNorm, RoPE, SwiGLU or looping.")
    a = phase_a(cfg)
    print(f"{tag} phase A: set-up {a['setup_s']:.1f} s; served {a['generated_tokens']} tokens "
          f"for {len(PROMPT_LENS)} prompts ({a['prompt_tokens']} prompt tokens) in "
          f"{a['serve_s']:.1f} s, compiles and mirror checks included")
    print(f"{tag} phase A: bit-equal to prefill_jax/decode_jax: logits and K/V rows of "
          f"{a['prefills']} prefills and {a['decode_steps']} decode steps; logits of the "
          f"first prefill and the first decode step bit-equal to ReferenceRuntime")
    calls = decode_custom_calls(a["tp"], SLOTS, MAX_LEN)
    want = expected_custom_calls(cfg)
    require(calls == want, f"decode step holds {calls} Pallas custom calls, expected {want}")
    print(f"{tag} phase A: {calls} Pallas custom calls in the decode step "
          f"({cfg.n_layers} layers x (4 fused matmuls + {cfg.n_heads} heads))")

    b = phase_b()
    print(f"{tag} phase B: set-up {b['setup_s']:.1f} s; {b['requests']} requests over batch "
          f"cells {b['cells']} in {b['serve_s']:.1f} s, bit-equal to ReferenceRuntime")
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
