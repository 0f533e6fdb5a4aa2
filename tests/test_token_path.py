"""Token-path differential suite: the codified transformer block (PR 10).

Pins, bit-for-bit, the three runtimes of the prefill/decode pair against each
other over a (batch × prompt-len) grid:

  numpy ReferenceRuntime == compiled ref backend == compiled interpret backend
  == the jnp mirrors (prefill_jax / decode_jax)

with int8 KV-cache state slots and mixed w4/w8 projection weights, plus unit
coverage for the state machinery (StateSpec round-trip, pinned plan slots,
per-bucket seq-extent binding, artifact round-trip, plan_diff state records,
shared-PlanCache one-specialization-per-cell) and the fused attention lane
(matcher, kernel, autotuner branch).
"""
import json
import os
import re
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.backend.artifact import load_artifact, save_artifact
from repro.backend.autotune import Autotuner, attention_candidates, is_attention_shape
from repro.backend.plan import PlanCache
from repro.core import pqir
from repro.core.compile import compile_model
from repro.core.patterns import build_exp_lut, emit_qattention
from repro.core.runtime import ReferenceRuntime
from repro.obs import trace as obs_trace
from repro.obs.metrics import default_registry
from repro.serving import token_path as token_path_mod
from repro.serving.engine import EngineConfig, Request, ServeEngine
from repro.serving.token_path import (
    CompiledTokenAdapter,
    CompiledTokenPath,
    TokenPathConfig,
    build_decode_model,
    causal_mask,
    decode_jax,
    make_token_params,
    prefill_jax,
)

CFG = TokenPathConfig()  # defaults: mixed w4 (qkv, down) / w8 (o, up)
PARAMS = make_token_params(CFG, seed=3)


def _tokens(rng, n, s):
    return rng.integers(1, CFG.vocab, (n, s)).astype(np.int32)


def _tp(backend="ref", **kw):
    kw.setdefault("s_granularity", 8)
    return CompiledTokenPath(CFG, PARAMS, backend=backend, **kw)


def _states_list(tp, cache):
    return [
        (cache[tp.state_specs[2 * l].input], cache[tp.state_specs[2 * l + 1].input])
        for l in range(tp.cfg.n_layers)
    ]


class TestDifferentialSweep:
    """Compiled prefill+decode bit-exact vs the jnp mirror over a grid."""

    @pytest.mark.parametrize("backend", ["ref", "interpret"])
    @pytest.mark.parametrize("n,plen", [(1, 3), (2, 7), (3, 8), (2, 12)])
    def test_prefill_grid(self, backend, n, plen):
        tp = _tp(backend)
        rng = np.random.default_rng(100 * n + plen)
        toks = _tokens(rng, n, plen)
        mask = causal_mask(n, plen)
        logits, cache = tp.prefill(toks, mask)
        jl, jcaches = prefill_jax(CFG, PARAMS, toks, mask)
        np.testing.assert_array_equal(logits, np.asarray(jl))
        for (k_j, v_j), (k_c, v_c) in zip(jcaches, _states_list(tp, cache)):
            np.testing.assert_array_equal(k_c, np.asarray(k_j))
            np.testing.assert_array_equal(v_c, np.asarray(v_j))

    @pytest.mark.parametrize("backend", ["ref", "interpret"])
    @pytest.mark.parametrize("n,plen", [(1, 3), (2, 5)])
    def test_decode_steps_follow_prefill(self, backend, n, plen):
        tp = _tp(backend)
        rng = np.random.default_rng(7 * n + plen)
        s_max = 16
        toks = _tokens(rng, n, plen)
        _, pcache = tp.prefill(toks, causal_mask(n, plen))
        cache = tp.init_cache(n, s_max)
        for k in cache:
            cache[k][:, :plen] = pcache[k][:, :plen]
        jstates = _states_list(tp, {k: v.copy() for k, v in cache.items()})
        for step in range(3):
            pos = plen + step
            tok = _tokens(rng, n, 1)
            onehot = np.zeros((n, s_max, 1), np.int8)
            onehot[:, pos, 0] = 1
            mask = np.broadcast_to(
                (np.arange(s_max)[None, None, :] <= pos), (n, 1, s_max)
            ).astype(np.float32)
            logits, cache = tp.decode(tok, onehot, mask, cache)
            jl, jstates = decode_jax(CFG, PARAMS, tok, onehot, mask, jstates)
            np.testing.assert_array_equal(logits, np.asarray(jl))
            for (k_j, v_j), (k_c, v_c) in zip(jstates, _states_list(tp, cache)):
                np.testing.assert_array_equal(k_c, np.asarray(k_j))
                np.testing.assert_array_equal(v_c, np.asarray(v_j))

    def test_prefill_matches_numpy_runtime(self):
        tp = _tp("ref")
        rng = np.random.default_rng(0)
        toks = _tokens(rng, 2, 6)
        mask = causal_mask(2, 6)
        logits, _ = tp.prefill(toks, mask)
        want = ReferenceRuntime(tp.prefill_model).run({"tokens": toks, "mask": mask})
        np.testing.assert_array_equal(
            logits, want[tp.prefill_model.graph.outputs[0].name]
        )

    def test_decode_matches_numpy_runtime(self):
        tp = _tp("ref")
        rng = np.random.default_rng(1)
        n, s = 2, 8
        cache = tp.init_cache(n, s)
        tok = _tokens(rng, n, 1)
        onehot = np.zeros((n, s, 1), np.int8)
        onehot[:, 0, 0] = 1
        mask = np.broadcast_to(
            (np.arange(s)[None, None, :] <= 0), (n, 1, s)
        ).astype(np.float32)
        logits, _ = tp.decode(tok, onehot, mask, cache)
        want = ReferenceRuntime(tp.decode_model).run(
            {"tokens": tok, "onehot": onehot, "mask": mask, **cache}
        )
        np.testing.assert_array_equal(
            logits, want[tp.decode_model.graph.outputs[0].name]
        )

    def test_mixed_bitwidths_render_in_plan(self):
        tp = _tp("ref")
        pretty = tp.decode_cm.plan.pretty()
        assert "weight_bits=4" in pretty  # qkv / down projections
        assert tp.decode_cm.stats["fused_qlinear"] == 4 * CFG.n_layers
        assert tp.decode_cm.stats["fused_qattention"] == CFG.n_heads * CFG.n_layers


class TestStateSpecs:
    def test_round_trip_and_validation(self):
        m = build_decode_model(CFG, PARAMS)
        doc = m.to_json()
        m2 = pqir.Model.from_json(doc)
        m2.validate()
        assert [s.name for s in m2.graph.states] == [s.name for s in m.graph.states]
        assert all(
            (s.input, s.output) == (t.input, t.output)
            for s, t in zip(m2.graph.states, m.graph.states)
        )

    def test_stateless_json_unchanged(self):
        gb = pqir.GraphBuilder("plain")
        gb.add_input("x", "int8", (2, 4))
        y = gb.op("Relu", ["x"], out_hint="y")
        gb.add_output(y, "int8", (2, 4))
        doc = gb.build(opset=17).to_json()
        assert "states" not in doc["graph"]

    def test_duplicate_state_name_rejected(self):
        gb = pqir.GraphBuilder("dup")
        gb.add_input("a", "int8", (2, 4))
        gb.add_input("b", "int8", (2, 4))
        ya = gb.op("Relu", ["a"], out_hint="ya")
        yb = gb.op("Relu", ["b"], out_hint="yb")
        gb.add_output(ya, "int8", (2, 4))
        gb.add_output(yb, "int8", (2, 4))
        gb.add_state("s", input="a", output=ya)
        gb.add_state("s", input="b", output=yb)
        with pytest.raises(ValueError, match="state"):
            gb.build(opset=17)


class TestPlanStates:
    def test_pinned_slots_and_seq_binding(self):
        tp = _tp("ref")
        plan = tp.decode_cm.plan
        assert len(plan.states) == 2 * CFG.n_layers
        for sb in plan.states:
            assert sb.dtype == "int8"
            assert sb.shape == ("N", "S", CFG.d_model)
        # state input slots are pinned and mutually distinct
        in_slots = [sb.in_slot for sb in plan.states]
        assert len(set(in_slots)) == len(in_slots)
        assert "states:" in plan.pretty()
        # per-bucket specialization binds the seq extent
        spec, _ = tp.decode_cm.specialized({"N": 2, "S": 16})
        for sb in spec.states:
            assert sb.shape == (2, 16, CFG.d_model)

    def test_next_state_feeds(self):
        tp = _tp("ref")
        plan = tp.decode_cm.plan
        outs = {sb.output: f"v{i}" for i, sb in enumerate(plan.states)}
        feeds = plan.next_state_feeds(outs)
        assert feeds == {sb.input: f"v{i}" for i, sb in enumerate(plan.states)}


class TestArtifactStates:
    def test_states_round_trip(self, tmp_path):
        tp = _tp("ref")
        n, s = 1, 8
        cache = tp.init_cache(n, s)
        tok = np.ones((n, 1), np.int32)
        onehot = np.zeros((n, s, 1), np.int8)
        onehot[:, 0, 0] = 1
        mask = (np.arange(s)[None, None, :] <= 0).astype(np.float32)
        logits, _ = tp.decode(tok, onehot, mask, cache)
        path = str(tmp_path / "decode.json")
        save_artifact(tp.decode_cm, path)
        doc = json.load(open(path))
        assert len(doc["plan"]["states"]) == 2 * CFG.n_layers
        cm2 = load_artifact(path)
        assert [sb.name for sb in cm2.plan.states] == [
            sb.name for sb in tp.decode_cm.plan.states
        ]
        got = cm2.run({"tokens": tok, "onehot": onehot, "mask": mask, **cache})
        np.testing.assert_array_equal(
            logits, np.asarray(got[tp.decode_model.graph.outputs[0].name])
        )
        # the pre-seeded cell serves without a new specialization
        assert cm2.plan_cache.stats["misses"] == 0


class TestPlanDiffStates:
    def test_stateful_never_diffs_clean_vs_stateless(self, tmp_path):
        tp = _tp("ref")
        a = str(tmp_path / "prefill.json")
        b = str(tmp_path / "decode.json")
        save_artifact(tp.prefill_cm, a)
        save_artifact(tp.decode_cm, b)
        script = os.path.join(os.path.dirname(__file__), "..", "scripts", "plan_diff.py")
        r = subprocess.run(
            [sys.executable, script, a, b], capture_output=True, text=True
        )
        assert r.returncode == 1
        assert "state slots" in r.stdout
        assert "kv0_k" in r.stdout

    def test_same_plan_diffs_clean(self, tmp_path):
        tp = _tp("ref")
        a = str(tmp_path / "a.json")
        save_artifact(tp.decode_cm, a)
        script = os.path.join(os.path.dirname(__file__), "..", "scripts", "plan_diff.py")
        r = subprocess.run(
            [sys.executable, script, a, a], capture_output=True, text=True
        )
        assert r.returncode == 0, r.stdout


class TestSharedCacheServing:
    # (slots, max_len, prefill_bucket): on-bucket, then off-bucket N (3 slots
    # run at N = 4), off-bucket S (20 positions run at S = 24) and a prompt
    # bucket (4) off the S granularity (8)
    EXTENTS = [(1, 16, 8), (3, 16, 8), (1, 20, 4), (3, 20, 4)]

    @pytest.mark.parametrize("slots,max_len,prefill_bucket", [(2, 16, 8), (3, 20, 4)])
    def test_one_specialization_per_visited_cell(self, slots, max_len, prefill_bucket):
        tp = _tp("ref")
        trips = default_registry().counter("tokenpath.cache.host_trips")
        before = trips.value
        eng = ServeEngine(
            ecfg=EngineConfig(slots=slots, max_len=max_len, prefill_bucket=prefill_bucket),
            adapter=CompiledTokenAdapter(tp),
        )
        rng = np.random.default_rng(5)
        for i in range(4):
            eng.submit(
                Request(
                    uid=i,
                    prompt=rng.integers(1, CFG.vocab, (int(rng.integers(2, 8)),)).astype(np.int32),
                    max_new_tokens=4,
                )
            )
        eng.run_until_drained()
        stats = tp.cache_stats()
        # one prefill cell (N=1, S=8) + one decode cell at the buckets of
        # (slots, max_len): every other prefill/decode step is a cache hit —
        # zero re-lowering
        assert set(tp.plan_cache.keys()) == {
            (tp.prefill_model.graph.name, (("N", 1), ("S", 8))),
            (tp.decode_model.graph.name, (("N", tp.decode_cm.bucket_for("N", slots)),
                                          ("S", tp.decode_cm.bucket_for("S", max_len)))),
        }
        assert stats["misses"] == 2
        assert stats["size"] == 2
        assert stats["hits"] == eng.metrics["prefills"] + eng.metrics["decode_steps"] - 2
        assert all(r.done for r in eng.active.values()) or not eng.active
        assert trips.value == before

    @pytest.mark.parametrize("slots,max_len,prefill_bucket", EXTENTS)
    def test_engine_matches_mirror_generation(self, slots, max_len, prefill_bucket):
        """Greedy generation through the engine == hand-rolled jnp-mirror
        loop, one request per slot, at on- and off-bucket engine extents."""
        tp = _tp("ref")
        eng = ServeEngine(
            ecfg=EngineConfig(slots=slots, max_len=max_len, prefill_bucket=prefill_bucket),
            adapter=CompiledTokenAdapter(tp),
        )
        prompts = [np.array([5, 9, 2], np.int32), np.array([7, 1, 3, 3, 8], np.int32), np.array([11], np.int32)]
        reqs = [Request(uid=i, prompt=p, max_new_tokens=4) for i, p in enumerate(prompts[:slots])]
        for req in reqs:
            eng.submit(req)
        eng.run_until_drained()

        # mirror: prefill at the bucket length, then decode token by token
        s_max = max_len
        for req in reqs:
            prompt = req.prompt
            plen = len(prompt)
            bucket = -(-plen // prefill_bucket) * prefill_bucket
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :plen] = prompt
            jl, jcaches = prefill_jax(CFG, PARAMS, padded, causal_mask(1, bucket))
            states = []
            for k, v in jcaches:
                ks = np.zeros((1, s_max, CFG.d_model), np.int8)
                vs = np.zeros((1, s_max, CFG.d_model), np.int8)
                ks[:, :bucket] = np.asarray(k)
                vs[:, :bucket] = np.asarray(v)
                states.append((ks, vs))
            toks = [int(np.asarray(jl)[0, plen - 1].argmax())]
            pos = plen
            for _ in range(3):
                onehot = np.zeros((1, s_max, 1), np.int8)
                onehot[0, pos, 0] = 1
                mask = (np.arange(s_max)[None, None, :] <= pos).astype(np.float32)
                jl, states = decode_jax(
                    CFG, PARAMS, np.array([[toks[-1]]], np.int32), onehot, mask, states
                )
                toks.append(int(np.asarray(jl)[0, 0].argmax()))
                pos += 1
            assert req.generated == toks
        # every decode ran the jitted step at the buckets of (slots, max_len)
        cm = tp.decode_cm
        assert list(tp._step_fns) == [(cm.bucket_for("N", slots), cm.bucket_for("S", max_len))]

    @pytest.mark.parametrize("n,s,need", [(3, 16, "(4, 16)"), (2, 20, "(2, 24)")],
                             ids=["off-bucket-N", "off-bucket-S"])
    def test_decode_step_refuses_an_off_bucket_cache(self, n, s, need):
        tp = _tp("ref")
        cache = jax.device_put(tp.init_cache(n, s))
        with pytest.raises(ValueError, match=re.escape(f"needs {need}")):
            tp.decode_step(np.ones((n, 1), np.int32), np.zeros((n,), np.int32), cache)
        assert not tp._step_fns and tp.cache_stats()["misses"] == 0


#: Each token-path span and the span it opens inside.
SPAN_PARENTS = {
    "engine.step": None,
    "engine.admit": "engine.step",
    "engine.prefill": "engine.admit",
    "tokenpath.prefill.dispatch": "engine.prefill",
    "tokenpath.prefill.fetch": "engine.prefill",
    "engine.scatter": "engine.admit",
    "tokenpath.scatter.put": "engine.scatter",
    "tokenpath.scatter.dispatch": "engine.scatter",
    "engine.decode": "engine.step",
    "tokenpath.decode.put": "engine.decode",
    "tokenpath.decode.dispatch": "engine.decode",
    "tokenpath.decode.fetch": "engine.decode",
    "engine.select": "engine.step",
}


class TestTokenPathSpans:
    """The serving loop's spans over the compiled token path, traced."""

    @staticmethod
    def _serve(tracer=None, n=4, check=None):
        """Staggered requests over two slots, served cycle by cycle, with
        ``tracer`` installed where given; returns the engine and its requests.
        ``check(engine)``, where given, is read after every cycle into
        ``engine.checked``."""
        tp = _tp("ref")
        eng = ServeEngine(
            ecfg=EngineConfig(slots=2, max_len=16, prefill_bucket=8),
            adapter=CompiledTokenAdapter(tp),
        )
        rng = np.random.default_rng(11)
        reqs = [
            Request(uid=i, prompt=_tokens(rng, 1, int(rng.integers(2, 8)))[0], max_new_tokens=3 + i)
            for i in range(n)
        ]
        eng.checked = []
        if tracer is not None:
            obs_trace.install(tracer)
        try:
            for r in reqs:
                eng.submit(r)
            for _ in range(64):
                if not eng.queue and not eng.active:
                    break
                eng.step()
                if check is not None:
                    eng.checked.append(check(eng))
        finally:
            if tracer is not None:
                obs_trace.uninstall()
        return eng, reqs

    def test_spans_nest_as_documented(self):
        tracer = obs_trace.Tracer()
        eng, reqs = self._serve(tracer)
        by_sid = {r.sid: r for r in tracer.spans()}
        # first visits of a cell also open backend.specialize spans
        spans = [r for r in by_sid.values() if r.name in SPAN_PARENTS]
        # the cache stays on the device, so the decode never puts it there
        assert {r.name for r in spans} == set(SPAN_PARENTS) - {"tokenpath.decode.put"}
        # the prefill runs as one jitted step: no host mask, no CompiledModel.run
        for name in ("tokenpath.prefill.mask", "run.pad", "run.execute", "run.slice"):
            assert not tracer.spans(name), name
        for r in spans:
            parent = by_sid[r.parent].name if r.parent is not None else None
            assert parent == SPAN_PARENTS[r.name], r
        assert len(tracer.spans("engine.step")) == eng.metrics["decode_steps"]
        assert len(tracer.spans("engine.prefill")) == eng.metrics["prefills"] == len(reqs)
        # request spans carry their uid
        assert sorted(s.attrs["uid"] for s in tracer.spans("engine.prefill")) == [r.uid for r in reqs]
        assert {s.attrs["uid"] for s in tracer.spans("engine.scatter")} == {r.uid for r in reqs}
        # one put and one dispatch per admission, one dispatch and fetch per prefill
        assert len(tracer.spans("tokenpath.prefill.dispatch")) == len(reqs)
        assert len(tracer.spans("tokenpath.prefill.fetch")) == len(reqs)
        assert len(tracer.spans("tokenpath.scatter.put")) == len(reqs)
        assert len(tracer.spans("tokenpath.scatter.dispatch")) == len(reqs)

    def test_the_cache_stays_on_the_device(self):
        tracer = obs_trace.Tracer()
        trips = default_registry().counter("tokenpath.cache.host_trips")
        before = trips.value
        eng, _ = self._serve(tracer, check=lambda eng: all(isinstance(v, jax.Array) for v in eng.cache.values()))
        assert eng.checked and all(eng.checked)
        assert tracer.spans("engine.scatter") and not tracer.spans("tokenpath.decode.put")
        assert trips.value == before

    def test_tokens_equal_with_and_without_the_tracer(self):
        _, plain = self._serve()
        _, traced = self._serve(obs_trace.Tracer())
        assert [r.generated for r in plain] == [r.generated for r in traced]
        assert all(r.done for r in plain)

    def test_queue_wait_is_stamped_at_admission(self):
        eng, reqs = self._serve()
        for r in reqs:
            assert r.t_submit <= r.t_admit <= r.t_first
        wait = eng.registry.histogram("engine.queue_wait_ms")
        assert wait.count == len(reqs)
        assert wait.max == pytest.approx(max(1e3 * (r.t_admit - r.t_submit) for r in reqs))


class TestMoeRoutingCounters:
    """The sparse-expert block's routing counters (``tokenpath.moe.*``) and
    span, served through the engine.  Zero router weights make every router
    logit 0, so every row of every layer takes experts 0 and 1 (equal logits
    keep the lower index): a known routing."""

    MOE = TokenPathConfig(vocab=96, d_model=64, n_heads=4, d_ff=96, n_layers=2, block="moe",
                          n_experts=8, top_k=2, d_expert=32, max_pos=64)
    NAMES = ("tokenpath.moe.rows", "tokenpath.moe.experts_hit", "tokenpath.moe.layer_calls",
             "tokenpath.moe.decode.rows", "tokenpath.moe.decode.experts_hit",
             "tokenpath.moe.decode.layer_calls")

    @classmethod
    def _tp(cls):
        params = make_token_params(cls.MOE, seed=4)
        for layer in params.layers:
            layer["experts"].router[:] = 0
        return CompiledTokenPath(cls.MOE, params, backend="ref", s_granularity=8)

    @classmethod
    def _counts(cls):
        return {n: default_registry().counter(n).value for n in cls.NAMES}

    @classmethod
    def _serve(cls, tp, tracer=None, check=None):
        eng = ServeEngine(ecfg=EngineConfig(slots=2, max_len=16, prefill_bucket=8),
                          adapter=CompiledTokenAdapter(tp))
        reqs = [Request(uid=i, prompt=np.arange(1, 4 + i, dtype=np.int32), max_new_tokens=3 + i)
                for i in range(3)]
        checked = []
        if tracer is not None:
            obs_trace.install(tracer)
        try:
            for r in reqs:
                eng.submit(r)
            while eng.queue or eng.active:
                eng.step()
                if check is not None:
                    checked.append(check())
        finally:
            if tracer is not None:
                obs_trace.uninstall()
        return eng, reqs, checked

    def test_counters_count_rows_and_experts_of_a_known_routing(self):
        tp = self._tp()
        before = self._counts()
        eng, _, _ = self._serve(tp)
        tp.flush_routing()
        got = {n: v - before[n] for n, v in self._counts().items()}
        layers, steps, prefills = self.MOE.n_layers, eng.metrics["decode_steps"], eng.metrics["prefills"]
        assert got["tokenpath.moe.decode.layer_calls"] == layers * steps
        assert got["tokenpath.moe.decode.rows"] == layers * steps * 2  # every slot's row
        assert got["tokenpath.moe.decode.experts_hit"] == layers * steps * 2  # experts 0 and 1
        assert got["tokenpath.moe.layer_calls"] == layers * (steps + prefills)
        assert got["tokenpath.moe.rows"] == layers * (steps * 2 + prefills * 8)  # a prefill routes its bucket
        assert got["tokenpath.moe.experts_hit"] == 2 * got["tokenpath.moe.layer_calls"]
        assert tp.last_routing.shape == (layers, 2, 1, self.MOE.top_k)
        assert set(np.unique(np.asarray(tp.last_routing)).tolist()) == {0, 1}

    def test_untraced_decode_leaves_the_routing_on_the_device(self, monkeypatch):
        """No tracer: nothing waits on the device, and the decode routing is
        not fetched (its counters stand still) until it is flushed."""
        tp = self._tp()
        calls = []
        for name in ("block_until_ready", "device_put"):
            real = getattr(jax, name)
            monkeypatch.setattr(jax, name, lambda *a, _real=real, _name=name, **k: (
                calls.append(_name), _real(*a, **k))[1])
        before = self._counts()
        eng, _, _ = self._serve(tp)
        assert calls == ["device_put"] * (1 + 3)  # the zero cache once, each admission's rows
        assert self._counts()["tokenpath.moe.decode.layer_calls"] == before["tokenpath.moe.decode.layer_calls"]
        assert isinstance(tp.last_routing, jax.Array)
        tp.flush_routing()
        assert (self._counts()["tokenpath.moe.decode.layer_calls"]
                == before["tokenpath.moe.decode.layer_calls"] + self.MOE.n_layers * eng.metrics["decode_steps"])

    def test_untraced_steps_keep_one_count_not_one_array_per_step(self):
        """A long untraced run holds the same device buffers after 40 more
        steps: the routing is summed into one int32 triple on the device,
        not kept per step, and the flush counts every step."""
        tp = self._tp()
        layers = self.MOE.n_layers
        toks, pos = np.ones((2, 1), np.int32), np.array([3, 5])
        cache = jax.device_put(tp.init_cache(2, 16))
        before = self._counts()["tokenpath.moe.decode.layer_calls"]

        def run(steps, cache):
            for _ in range(steps):
                _, cache = tp.decode_step(toks, pos, cache)
            return cache

        cache = run(5, cache)
        jax.block_until_ready(cache)
        held = len(jax.live_arrays())
        cache = run(40, cache)
        jax.block_until_ready(cache)
        assert len(jax.live_arrays()) == held
        assert tp._routing_counts.shape == (3,)
        tp.flush_routing()
        assert tp._routing_counts is None
        assert self._counts()["tokenpath.moe.decode.layer_calls"] == before + 45 * layers

    def test_untraced_counts_are_flushed_every_so_many_steps(self, monkeypatch):
        """Without a tracer or a flush, the counts are taken every
        ``ROUTING_FLUSH_STEPS`` decode steps and held on the device between."""
        monkeypatch.setattr(token_path_mod, "ROUTING_FLUSH_STEPS", 4)
        tp = self._tp()
        layers = self.MOE.n_layers
        toks, pos = np.ones((2, 1), np.int32), np.array([3, 5])
        cache = jax.device_put(tp.init_cache(2, 16))
        calls = default_registry().counter("tokenpath.moe.decode.layer_calls")
        before, seen = calls.value, []
        for _ in range(9):
            _, cache = tp.decode_step(toks, pos, cache)
            seen.append(calls.value - before)
        assert seen == [0, 0, 0, 4 * layers, 4 * layers, 4 * layers, 4 * layers, 8 * layers, 8 * layers]

    def test_a_tracer_counts_each_step_and_tokens_stay_bit_equal(self):
        tp = self._tp()
        _, plain, _ = self._serve(tp)
        tp.flush_routing()
        tracer = obs_trace.Tracer()
        calls = default_registry().counter("tokenpath.moe.decode.layer_calls")
        seen = []
        eng, traced, _ = self._serve(tp, tracer, check=lambda: seen.append(calls.value))
        # counted as each traced step ends: one count per layer per decode
        assert seen[-1] - seen[0] == self.MOE.n_layers * (eng.metrics["decode_steps"] - 1)
        assert [r.generated for r in plain] == [r.generated for r in traced]
        routes = tracer.spans("tokenpath.moe.route")
        assert len(routes) == eng.metrics["decode_steps"] + eng.metrics["prefills"]


class TestDeviceScatter:
    """``CompiledTokenAdapter.scatter`` writes a prompt's rows into its slot
    of the device cache, in place."""

    S = 16

    def _stale_cache(self, tp, on_device):
        """A two-slot cache full of earlier occupants' rows."""
        rng = np.random.default_rng(21)
        cache = {k: rng.integers(-128, 128, v.shape).astype(np.int8) for k, v in tp.init_cache(2, self.S).items()}
        return cache, (jax.device_put(cache) if on_device else {k: v.copy() for k, v in cache.items()})

    @pytest.mark.parametrize("on_device", [True, False], ids=["device", "host"])
    def test_equals_the_host_row_write_bit_for_bit(self, on_device):
        tp = _tp("ref")
        ad = CompiledTokenAdapter(tp)
        plen, bucket, slot = 5, 8, 1
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :plen] = _tokens(np.random.default_rng(22), 1, plen)[0]
        _, rows = ad.prefill(padded, plen, self.S)
        want, cache = self._stale_cache(tp, on_device)
        for name in want:
            want[name][slot, :bucket] = rows[name][0]
        trips = default_registry().counter("tokenpath.cache.host_trips")
        before = trips.value
        got = ad.scatter(cache, slot, rows)
        assert trips.value - before == (0 if on_device else 1)
        assert set(got) == set(want)
        for name, buf in got.items():
            assert isinstance(buf, jax.Array)
            # the other slot, and this slot's stale rows beyond the bucket, survive
            np.testing.assert_array_equal(np.asarray(buf), want[name])

    def test_one_program_per_bucket_not_per_slot(self):
        tp = _tp("ref")
        ad = CompiledTokenAdapter(tp)
        # every jit of ``_write_rows`` shares one cache, so the shapes here are
        # this test's own: four slots (a bucket) of 40 positions
        cache = ad.init_cache(4, 40)
        rng = np.random.default_rng(23)
        programs = []
        for slot in (0, 1, 2, 3):
            rows = {k: rng.integers(-128, 128, (1, 8, CFG.d_model)).astype(np.int8) for k in cache}
            cache = ad.scatter(cache, slot, rows)
            programs.append(ad._write_rows._cache_size())
        assert programs == programs[:1] * 4
        with pytest.raises(IndexError):  # never clamped onto the last slot
            ad.scatter(cache, 4, rows)


class TestDevicePrefill:
    """``CompiledTokenAdapter.prefill`` through ``CompiledTokenPath.prefill_step``:
    only the logits row at ``plen - 1`` comes to the host, and the K/V rows
    stay on the device for ``scatter``."""

    S = 16
    FETCHED = "tokenpath.prefill.fetched_bytes"

    @staticmethod
    def _padded(plen, bucket, seed=31):
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :plen] = _tokens(np.random.default_rng(seed + plen), 1, plen)[0]
        return padded

    @pytest.mark.parametrize("backend", ["ref", "interpret"])
    @pytest.mark.parametrize("bucket,plen", [(8, 1), (8, 7), (8, 8), (16, 1), (16, 15), (16, 16)])
    def test_equals_the_host_prefill_bit_for_bit(self, backend, bucket, plen):
        tp = _tp(backend)
        padded = self._padded(plen, bucket)
        row, rows = CompiledTokenAdapter(tp).prefill(padded, plen, self.S)
        logits, cache = tp.prefill(padded, causal_mask(1, bucket))
        assert isinstance(row, np.ndarray) and row.shape == (CFG.vocab,)
        np.testing.assert_array_equal(row, logits[0, plen - 1])
        assert set(rows) == set(cache)
        for name, got in rows.items():
            assert isinstance(got, jax.Array) and got.shape == (1, bucket, CFG.d_model)
            np.testing.assert_array_equal(np.asarray(got), cache[name])

    def test_one_program_per_bucket_whatever_plen(self):
        tp = _tp("ref")
        ad = CompiledTokenAdapter(tp)
        served = [(8, p) for p in (8, 1, 3, 5, 7)] + [(16, p) for p in (16, 9, 2)]
        for bucket, plen in served:
            ad.prefill(self._padded(plen, bucket), plen, self.S)
        assert sorted(tp._prefill_fns) == [8, 16]
        assert [tp._prefill_fns[b][0]._cache_size() for b in (8, 16)] == [1, 1]
        # the plan cache counts the prefill cells as prefill() does: one miss each
        stats = tp.cache_stats()
        assert stats["misses"] == 2 and stats["hits"] == len(served) - 2

    def test_scatter_takes_the_device_rows_as_they_are(self):
        tp = _tp("ref")
        ad = CompiledTokenAdapter(tp)
        plen, bucket, slot = 5, 8, 1
        _, rows = ad.prefill(self._padded(plen, bucket), plen, self.S)
        cache = ad.init_cache(2, self.S)
        handed = []
        write = ad._write_rows
        ad._write_rows = lambda c, r, sl: (handed.append(r), write(c, r, sl))[1]
        trips = default_registry().counter("tokenpath.cache.host_trips")
        before = trips.value
        got = ad.scatter(cache, slot, rows)
        assert trips.value == before
        # the very arrays prefill returned: no copy to the host and back
        assert all(handed[0][name] is rows[name] for name in rows)
        for name, buf in got.items():
            want = np.zeros((2, self.S, CFG.d_model), np.int8)
            want[slot, :bucket] = np.asarray(rows[name])[0]
            np.testing.assert_array_equal(np.asarray(buf), want)

    def test_fetched_bytes_are_one_logits_row_per_prefill(self):
        tp = _tp("ref")
        fetched = default_registry().counter(self.FETCHED)
        before = fetched.value
        eng = ServeEngine(ecfg=EngineConfig(slots=2, max_len=self.S, prefill_bucket=8),
                          adapter=CompiledTokenAdapter(tp))
        rng = np.random.default_rng(32)
        for i in range(4):
            eng.submit(Request(uid=i, prompt=_tokens(rng, 1, int(rng.integers(1, 16)))[0], max_new_tokens=3))
        eng.run_until_drained()
        assert eng.metrics["prefills"] == 4
        assert fetched.value - before == eng.metrics["prefills"] * CFG.vocab * 4

    def test_an_off_bucket_prompt_runs_the_jitted_step_at_its_bucket(self):
        tp = _tp("ref")  # S buckets of 8: a prompt padded to 4 is off bucket
        fetched = default_registry().counter(self.FETCHED)
        before = fetched.value
        padded = self._padded(3, 4)
        row, rows = CompiledTokenAdapter(tp).prefill(padded, 3, self.S)
        logits, cache = tp.prefill(padded, causal_mask(1, 4))
        np.testing.assert_array_equal(row, logits[0, 2])
        for name in cache:
            assert isinstance(rows[name], jax.Array) and rows[name].shape == (1, 8, CFG.d_model)
            np.testing.assert_array_equal(np.asarray(rows[name])[:, :4], cache[name])
        assert list(tp._prefill_fns) == [8]
        # one logits row came to the host, nothing else
        assert fetched.value - before == CFG.vocab * 4

    @pytest.mark.parametrize("plen", [0, 9])
    def test_plen_outside_the_bucket_is_refused(self, plen):
        with pytest.raises(ValueError, match="plen"):
            _tp("ref").prefill_step(self._padded(1, 8), plen)


class TestAttentionLane:
    def test_matcher_counts_regions(self):
        tp = _tp("ref")
        assert tp.prefill_cm.stats["fused_qattention"] == CFG.n_heads * CFG.n_layers

    def test_single_region_interpret_matches_ref(self):
        gb = pqir.GraphBuilder("attn_one")
        gb.add_input("q", "int8", ("N", "S", 32))
        gb.add_input("k", "int8", ("N", "S", 32))
        gb.add_input("v", "int8", ("N", "S", 32))
        gb.add_input("mask", "float32", ("N", "S", "S"))
        out = emit_qattention(gb, "q", "k", "v", "mask", "a0", qk_scale=0.01, rescale=0.02)
        gb.add_output(out, "int8", ("N", "S", 32))
        m = gb.build(opset=17)
        rng = np.random.default_rng(0)
        feeds = {
            "q": rng.integers(-128, 128, (2, 7, 32)).astype(np.int8),
            "k": rng.integers(-128, 128, (2, 7, 32)).astype(np.int8),
            "v": rng.integers(-128, 128, (2, 7, 32)).astype(np.int8),
            "mask": causal_mask(2, 7),
        }
        dyn = {"N": None, "S": None}
        ref = compile_model(m, backend="ref", batch="dynamic", dynamic_axes=dyn)
        itp = compile_model(m, backend="interpret", batch="dynamic", dynamic_axes=dyn)
        assert ref.stats["fused_qattention"] == 1
        want = ReferenceRuntime(m).run(feeds)
        for cm in (ref, itp):
            got = cm.run(feeds)
            for kk in want:
                np.testing.assert_array_equal(np.asarray(got[kk]), want[kk])

    def test_exp_lut_zero_floor(self):
        lut = build_exp_lut()
        assert lut.shape == (256,)
        assert lut[0] == 0  # padding exactness hinges on this
        assert lut[128] == 255  # exp(0) at full scale


class TestAutotuneAttention:
    def test_shape_predicate(self):
        assert is_attention_shape({"b": 2, "s": 8, "t": 8, "dh": 32, "bq": 32})
        assert not is_attention_shape({"m": 8, "k": 16, "n": 32})

    def test_candidates_respect_alignment(self):
        cands = attention_candidates(100, 128, 32)
        assert all(bq % 32 == 0 for bq in cands)
        assert all(bq <= 128 for bq in cands)  # never exceeds rounded-up S
        assert len(cands) >= 2  # a real lattice to search

    def test_measured_search_tags_tuned(self):
        gb = pqir.GraphBuilder("attn_tuned")
        gb.add_input("q", "int8", ("N", "S", 32))
        gb.add_input("k", "int8", ("N", "S", 32))
        gb.add_input("v", "int8", ("N", "S", 32))
        gb.add_input("mask", "float32", ("N", "S", "S"))
        out = emit_qattention(gb, "q", "k", "v", "mask", "a0", qk_scale=0.01, rescale=0.02)
        gb.add_output(out, "int8", ("N", "S", 32))
        m = gb.build(opset=17)
        calls = []

        def measure(fn, *a, **kw):
            calls.append(1)
            return float(len(calls))  # first candidate (the heuristic) wins

        cm = compile_model(
            m, backend="interpret", batch="dynamic",
            dynamic_axes={"N": None, "S": None}, autotune=Autotuner(measure_fn=measure),
        )
        spec, _ = cm.specialized({"N": 2, "S": 100})
        assert len(calls) >= 2
        assert "bq" in spec.steps[0].params["shape"]
        recs = [
            rec
            for ev in cm.plan.provenance.specializations
            for _, rec in ev.tiles
        ]
        assert any("[tuned]" in r for r in recs)


class TestExactDivision:
    """``kernels.ref.div_rn``: the attention softmax's Div, IEEE on any
    backend.  A TPU's divide is off by an ulp for many such quotients; the
    correction is pinned here against quotients moved off by up to two."""

    @pytest.mark.parametrize("off", [0, 1, -1, 2, -2])
    def test_corrects_an_inexact_quotient(self, off):
        import jax
        import jax.numpy as jnp

        from repro.kernels import ref

        rng = np.random.default_rng(off + 7)
        b = rng.integers(1, 255 * 1024, 200_000).astype(np.float32)
        b[:50_000] = rng.integers(1, 600, 50_000)
        a = np.minimum(np.floor(rng.random(b.size) * (b + 1)), b).astype(np.float32)
        a[:100] = 0
        ieee = a / b
        bits = jax.lax.bitcast_convert_type(jnp.asarray(ieee), jnp.int32) + off
        q = jnp.where(jnp.asarray(a) == 0, 0.0, jax.lax.bitcast_convert_type(bits, jnp.float32))
        fix = jax.jit(lambda a, b, q: ref._nearest(a, b, ref._nearest(a, b, q)))
        got = np.asarray(fix(jnp.asarray(a), jnp.asarray(b), q))
        np.testing.assert_array_equal(got[a > 0], ieee[a > 0])
        np.testing.assert_array_equal(np.asarray(ref.div_rn(jnp.asarray(a), jnp.asarray(b))), ieee)
