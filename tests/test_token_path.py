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
import subprocess
import sys

import numpy as np
import pytest

from repro.backend.artifact import load_artifact, save_artifact
from repro.backend.autotune import Autotuner, attention_candidates, is_attention_shape
from repro.backend.plan import PlanCache
from repro.core import pqir
from repro.core.compile import compile_model
from repro.core.patterns import build_exp_lut, emit_qattention
from repro.core.runtime import ReferenceRuntime
from repro.serving.engine import EngineConfig, Request, ServeEngine
from repro.serving.token_path import (
    CompiledTokenAdapter,
    CompiledTokenPath,
    TokenPathConfig,
    build_decode_model,
    decode_jax,
    make_token_params,
    prefill_jax,
)

CFG = TokenPathConfig()  # defaults: mixed w4 (qkv, down) / w8 (o, up)
PARAMS = make_token_params(CFG, seed=3)


def _causal(n, s):
    return np.broadcast_to(np.tril(np.ones((s, s), np.float32)), (n, s, s)).copy()


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
        mask = _causal(n, plen)
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
        _, pcache = tp.prefill(toks, _causal(n, plen))
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
        mask = _causal(2, 6)
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
    def test_one_specialization_per_visited_cell(self):
        tp = _tp("ref")
        eng = ServeEngine(
            ecfg=EngineConfig(slots=2, max_len=16, prefill_bucket=8),
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
        # one prefill cell (N=1, S=8) + one decode cell (N=2, S=16): every
        # other prefill/decode step is a cache hit — zero re-lowering
        assert stats["misses"] == 2
        assert stats["size"] == 2
        assert stats["hits"] == eng.metrics["prefills"] + eng.metrics["decode_steps"] - 2
        assert all(r.done for r in eng.active.values()) or not eng.active

    def test_engine_matches_mirror_generation(self):
        """Greedy generation through the engine == hand-rolled jnp-mirror loop."""
        tp = _tp("ref")
        eng = ServeEngine(
            ecfg=EngineConfig(slots=1, max_len=16, prefill_bucket=8),
            adapter=CompiledTokenAdapter(tp),
        )
        prompt = np.array([5, 9, 2], np.int32)
        req = Request(uid=0, prompt=prompt, max_new_tokens=4)
        eng.submit(req)
        eng.run_until_drained()

        # mirror: prefill at the bucket length, then decode token by token
        bucket, s_max, plen = 8, 16, len(prompt)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :plen] = prompt
        jl, jcaches = prefill_jax(CFG, PARAMS, padded, _causal(1, bucket))
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
            "mask": _causal(2, 7),
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
