"""The served path ``token_path``: ``ServeEngine`` over
``CompiledTokenAdapter`` over ``CompiledTokenPath``.

The engine admits each request with one prefill of its prompt, padded to a
multiple of ``prefill_bucket``, scatters the prompt's K/V rows into a free
slot, and runs one batched decode step over all ``slots`` per cycle.  Every
call into the adapter is wrapped in a host span (``bench.prefill``,
``bench.scatter``, ``bench.decode``) that also writes into the profiler's
trace, and ends only when its results are ready, so the span is the call's
whole cost.  Each call is logged with the true shapes it served, which
:func:`work` turns into operations and bytes (``bench/work.py``).

What the harness calls: :func:`build`, :func:`work` and :data:`KERNELS`;
``bench/rehearse_compile.py`` calls :func:`rehearsal_programs`.
"""
from __future__ import annotations

import time
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.backend.plan import bucket_multiple
from repro.core.quant import QuantizedLinearParams, Rescale
from repro.serving.engine import EngineConfig, Request, ServeEngine
from repro.serving.token_path import (
    CompiledTokenAdapter,
    CompiledTokenPath,
    TokenPathConfig,
    TokenPathParams,
)

import work as yardstick  # bench/work.py, on the path the harness sets

#: Kernel families, and the HLO names of the Pallas calls of each: the
#: jitted wrappers ``kernels.qmatmul.qmatmul`` / ``qmatmul_packed`` and
#: ``kernels.qattention.qattention`` name their custom calls.
KERNELS = {
    "qmatmul": ("qmatmul", "qmatmul_packed"),
    "qattention": ("qattention",),
}


def token_path_config(cfg: dict) -> TokenPathConfig:
    q = cfg["quant"]
    bits = q["weight_bits"]
    return TokenPathConfig(
        vocab=int(cfg["vocab_size"]),
        d_model=int(cfg["hidden_size"]),
        n_heads=int(cfg["num_attention_heads"]),
        d_ff=int(cfg["intermediate_size"]),
        n_layers=int(cfg["num_hidden_layers"]),
        act_scale=float(q["act_scale"]),
        lm_scale=float(q["lm_scale"]),
        bits_qkv=int(bits["qkv"]),
        bits_o=int(bits["o"]),
        bits_up=int(bits["up"]),
        bits_down=int(bits["down"]),
    )


def token_path_params(tcfg: TokenPathConfig, weights: dict) -> TokenPathParams:
    """The benchmark's weight codes in the program's parameter types."""
    s = tcfg.act_scale
    layers = []
    for layer in weights["layers"]:
        out = {}
        for name, p in layer.items():
            mult = p["quant_scale"] * 2.0 ** -p["shift"]
            out[name] = QuantizedLinearParams(
                weight_q=p["w"], bias_q=p["b"], scale_x=s, scale_w=np.float32(mult), scale_y=s,
                rescale=Rescale(quant_scale=p["quant_scale"], shift=p["shift"], multiplier=mult),
                bits=p["bits"],
            )
        layers.append(out)
    return TokenPathParams(weights["embedding"], layers, weights["lm_head"], tcfg.lm_scale)


class TimedAdapter:
    """``CompiledTokenAdapter`` with a host span around every call.

    ``log`` holds one ``(kind, start, seconds, shape)`` per call, where
    ``shape`` is the prompt length of a prefill, the live slots' positions
    of a decode, and ``None`` for a scatter."""

    def __init__(self, inner: CompiledTokenAdapter) -> None:
        self.inner = inner
        self.cfg = inner.cfg
        self.prefill_cache = None
        self.live = lambda: ()
        self.log: List[Tuple[str, float, float, object]] = []

    def _timed(self, kind: str, shape, fn, *args):
        t = time.monotonic()
        with jax.profiler.TraceAnnotation(f"bench.{kind}"):
            out = jax.block_until_ready(fn(*args))
        self.log.append((kind, t, time.monotonic() - t, shape))
        return out

    def init_cache(self, slots: int, max_len: int):
        return self.inner.init_cache(slots, max_len)

    def prefill(self, padded, plen: int, max_len: int):
        return self._timed("prefill", plen, self.inner.prefill, padded, plen, max_len)

    def scatter(self, cache, slot: int, pcache):
        return self._timed("scatter", None, self.inner.scatter, cache, slot, pcache)

    def decode(self, toks, pos, cache):
        positions = tuple(int(p) for p in np.asarray(pos)[self.live()])
        return self._timed("decode", positions, self.inner.decode, toks, pos, cache)


class Served:
    """One built token path behind its engine."""

    def __init__(self, cell, weights: dict) -> None:
        cfg, engine_cfg = cell.config, cell.spec["engine"]
        self.traffic = cell.traffic
        self.tcfg = token_path_config(cfg)
        params = token_path_params(self.tcfg, weights)
        self.tp = CompiledTokenPath(self.tcfg, params, backend=cfg.get("backend", "pallas"))
        self.adapter = TimedAdapter(CompiledTokenAdapter(self.tp))
        self.ecfg = EngineConfig(
            slots=int(engine_cfg["slots"]),
            max_len=int(engine_cfg["max_len"]),
            prefill_bucket=int(engine_cfg["prefill_bucket"]),
            greedy=True,
        )
        self.reset()

    def reset(self) -> None:
        """A fresh engine (empty queue and slots) over the same compiled path."""
        self.engine = ServeEngine(adapter=self.adapter, ecfg=self.ecfg)
        self.adapter.live = lambda: np.flatnonzero(self.engine.slot_live)

    @property
    def calls(self) -> List[Tuple[str, float, float, object]]:
        """Every adapter call since the warm-up: ``(kind, start, seconds, shape)``."""
        return self.adapter.log

    def buckets(self) -> List[int]:
        """Every prefill bucket a prompt of the traffic reaches."""
        g = self.ecfg.prefill_bucket
        lo, hi = int(self.traffic["prompt"]["min"]), int(self.traffic["prompt"]["max"])
        return sorted({bucket_multiple(n, g) for n in range(lo, hi + 1)})

    def warm_up(self) -> int:
        """Run one request per reachable prefill bucket, each with one
        decode step, so that every program the window uses is compiled or
        loaded; returns the number of buckets."""
        buckets = self.buckets()
        rng = np.random.default_rng(0)
        for i, b in enumerate(buckets):
            prompt = rng.integers(1, self.tcfg.vocab, (b,)).astype(np.int32)
            self.engine.submit(Request(uid=-1 - i, prompt=prompt, max_new_tokens=2))
        self.engine.run_until_drained()
        self.adapter.log.clear()
        return len(buckets)

    def submit(self, item) -> Request:
        """Queue one generated request (``loadgen.Item``)."""
        req = Request(uid=item.index, prompt=item.prompt, max_new_tokens=item.max_new_tokens)
        self.engine.submit(req)
        return req

    def busy(self) -> bool:
        return bool(self.engine.queue) or bool(self.engine.active)

    def queue_len(self) -> int:
        return len(self.engine.queue)

    def step(self) -> None:
        self.engine.step()

    def counters(self) -> Dict[str, int]:
        """The engine's own counts (``repro.obs`` registry)."""
        reg = self.engine.registry
        return {k: int(reg.counter(f"engine.{k}").value) for k in ("prefills", "decode_steps", "completed")}

    def close(self) -> None:
        """Drop every device buffer the path holds."""
        self.engine = None
        self.adapter = None
        self.tp = None


def build(cell, weights: dict) -> Served:
    return Served(cell, weights)


def shapes(cfg: dict) -> "yardstick.BlockShapes":
    """The true widths of the configuration, for ``bench/work.py``."""
    t = token_path_config(cfg)
    bits = {"qkv": t.bits_qkv, "o": t.bits_o, "up": t.bits_up, "down": t.bits_down}
    return yardstick.BlockShapes(t.d_model, t.n_heads, t.d_ff, t.vocab, t.n_layers, bits)


def work(calls, cfg: dict, peaks: Dict[str, float]) -> "yardstick.TokenPathWork":
    """The operations and bytes the logged calls needed, at true shapes."""
    w = yardstick.TokenPathWork(shapes(cfg), peaks)
    for kind, _, _, shape in calls:
        if kind == "prefill":
            w.prefill(shape)
        elif kind == "decode":
            w.decode(shape)
    return w


def rehearsal_programs(cell, weights: dict) -> Dict[str, tuple]:
    """The largest prefill bucket's and the decode step's plans, each with
    its feeds as ``{name: (shape, dtype)}``: what ``bench/rehearse_compile.py``
    compiles for a described chip."""
    tcfg = token_path_config(cell.config)
    tp = CompiledTokenPath(tcfg, token_path_params(tcfg, weights), backend="pallas")
    eng = cell.spec["engine"]
    slots, max_len, g = int(eng["slots"]), int(eng["max_len"]), int(eng["prefill_bucket"])
    bucket = bucket_multiple(int(cell.traffic["prompt"]["max"]), g)
    return {
        f"prefill (1, {bucket})": (
            tp.prefill_cm.specialized({"N": 1, "S": bucket})[0],
            {"tokens": ((1, bucket), jnp.int32), "mask": ((1, bucket, bucket), jnp.float32)},
        ),
        f"decode ({slots}, {max_len})": (
            tp.decode_cm.specialized({"N": slots, "S": max_len})[0],
            {"tokens": ((slots, 1), jnp.int32), "onehot": ((slots, max_len, 1), jnp.int8),
             "mask": ((slots, 1, max_len), jnp.float32),
             **{s.input: ((slots, max_len, tcfg.d_model), jnp.int8) for s in tp.state_specs}},
        ),
    }
