"""Benchmark harness — one entry per paper figure/table + system-level extras.

Prints ``name,us_per_call,derived`` CSV rows:

  fig1_fc_two_mul        — Fig 1 pattern: reference runtime vs fused compile
  fig2_fc_relu_one_mul   — Fig 2
  fig3_conv              — Fig 3
  fig4_int8_tanh         — Fig 4 (derived: max int8 ULP error vs fp32 tanh)
  fig5_fp16_tanh         — Fig 5
  fig6_fp16_sigmoid      — Fig 6
  tbl_rescale_decompose  — §3.1 decomposition (derived: worst rel. error)
  sys_pass_pipeline      — repro.passes optimized vs raw compile of a 3-layer
                           MLP (derived: folded/eliminated pipeline stats)
  sys_plan_overhead      — slot-indexed ExecutionPlan execution vs the old
                           name-keyed dict-env interpretation of the same
                           kernels (derived: slot/tensor counts)
  sys_per_channel_overhead — per-channel vs scalar fused requant on the same
                           FC layer (derived: ratio; pinned at near-parity)
  sys_serving_compiled   — micro-batched serving of one batch-polymorphic
                           compiled artifact: requests/s at batch buckets
                           1/8/32 + plan-cache hit rate (≥2 buckets must be
                           served from cache after warmup)
  sys_seq_buckets        — one two-axis (named N × S) compiled artifact over
                           a (batch ∈ {1,8}) × (seq ∈ {32,128}) scenario
                           grid: requests/s per cell + specialization
                           counts (asserts at most one per grid cell)
  sys_autotune           — measured per-cell tile autotuning: tuned vs
                           heuristic executor per batch cell (tuned must not
                           lose beyond noise), plus the persisted-tile-cache
                           round trip (a warm-started second session must
                           measure nothing)
  sys_fleet              — fleet serving from one AOT plan artifact: a
                           3-replica ShardedRouter (warm-started, cell
                           affinity) vs a single warm server on the same
                           mixed-cell traffic (derived: rps both ways,
                           per-replica plan-cache hit rates pinned ≥ the
                           single-server baseline, warm vs cold first-wave
                           latency, lost/dup request counters)
  sys_int4_decode        — sub-8-bit weight lane on the decode path: one
                           MLP quantized at weight_bits 8 vs 4 on the tiled
                           interpret backend, decode-shaped cells M ∈ {1,8}
                           (derived: tokens/s both ways per cell, cost-model
                           HBM-byte ratio; asserts packed-int4 bit-exact vs
                           the unpacked reference and w4 weight bytes ≤
                           0.55× w8)
  sys_attn_decode        — the compiled token path (docs/token_path.md):
                           transformer decode through the specialized
                           ("N",1,…) ExecutionPlan with int8 KV state slots
                           and fused attention, decode cells M ∈ {1,8}
                           (derived: tokens/s per cell; asserts
                           compiled decode bit-exact vs the jnp mirror and
                           exactly one specialization per visited cell)
  sys_w8a8_decode        — reduced-arch decode step: bf16 vs W8A8+int8-KV
  sys_grad_compress      — int8 cross-pod gradient all-reduce (derived: wire-
                           bytes ratio vs f32)

Run:  PYTHONPATH=src python -m benchmarks.run [--smoke] [--json PATH]

``--smoke`` runs the fast subset (fig1, pass pipeline, plan overhead,
per-channel overhead, serving-compiled, seq buckets, autotune, fleet,
int4 decode, attn decode) for CI.  ``--json BENCH_<n>.json``
additionally persists the rows as JSON so the perf trajectory survives
across PRs (CI uploads the file as a build artifact).
"""
from __future__ import annotations

import argparse
import json
import platform
import time

import numpy as np

#: Rows accumulated by ``row()`` for the optional --json dump.
_ROWS: list = []


def _timeit(fn, *args, repeat: int = 20, warmup: int = 3) -> float:
    for _ in range(warmup):
        fn(*args)
    t0 = time.perf_counter()
    for _ in range(repeat):
        fn(*args)
    return (time.perf_counter() - t0) / repeat * 1e6  # us


def row(name: str, us: float, derived: str) -> None:
    _ROWS.append({"name": name, "us_per_call": round(us, 1), "derived": derived})
    print(f"{name},{us:.1f},{derived}")


def _fc_pattern(activation, two_mul, act_builder=None):
    from repro.core import patterns, pqir, quant

    rng = np.random.default_rng(0)
    scale_w = 0.02 if act_builder is not None else 0.1  # keep preacts in the
    w = rng.normal(size=(256, 256)).astype(np.float32) * scale_w  # act range
    b = rng.normal(size=(256,)).astype(np.float32) * 0.1
    scale_y = (patterns.TANH_INPUT_ABSMAX / 127.0) if act_builder else 0.1
    p = quant.quantize_linear_layer(w, b, 0.05, scale_y)
    gb = pqir.GraphBuilder("bench")
    xi = gb.add_input("x", "int8", (None, 256))
    if act_builder is not None:
        y = act_builder(gb, xi, p, "fc0")
        out_dtype = "uint8" if act_builder is patterns.fc_fp16_sigmoid else "int8"
    else:
        y = patterns.fc_layer(gb, xi, p, "fc0", two_mul=two_mul, activation=activation)
        out_dtype = "int8"
    gb.add_output(y, out_dtype, (None, 256))
    model = gb.build()
    xq = rng.integers(-128, 128, (64, 256)).astype(np.int8)
    return model, xq, y, w, b


def bench_pattern(name, activation=None, two_mul=True, act_builder=None, derived_fn=None):
    from repro.core.compile import compile_model
    from repro.core.runtime import ReferenceRuntime

    model, xq, yname, w, b = _fc_pattern(activation, two_mul, act_builder)
    rt = ReferenceRuntime(model)
    # optimize=False: the fig rows measure the paper's codified chains as-is
    # (mul_fold would collapse fig1's two-Mul rescale into fig2's one-Mul
    # kernel config); sys_pass_pipeline below covers the optimized path.
    cm = compile_model(model, optimize=False)
    ref_out = rt.run({"x": xq})[yname]
    fused_out = cm.run({"x": xq})[yname]
    exact = np.array_equal(ref_out, fused_out)
    us_ref = _timeit(lambda: rt.run({"x": xq}))
    us_fused = _timeit(lambda: cm.run({"x": xq}))
    derived = f"fused_us={us_fused:.1f};speedup={us_ref / us_fused:.2f}x;bitexact={exact};{_stats_derived(cm)}"
    if derived_fn is not None:
        derived += ";" + derived_fn(model, xq, ref_out, w, b)
    row(name, us_ref, derived)


def _stats_derived(cm) -> str:
    """Compile/pass stats for the report: fused-vs-fallback step counts plus
    what the repro.passes pipeline folded/eliminated before codegen."""
    s = cm.stats
    fused = s["fused_qlinear"] + s["fused_qconv"] + s["fused_lut"]
    return f"fused={fused};fallback={s['generic']};folded={s['folded']};eliminated={s['eliminated']}"


def _tanh_err(model, xq, out, w, b):
    ref = np.tanh(xq.astype(np.float32) * 0.05 @ w + b)
    err = np.abs(out.astype(np.float32) / 127.0 - ref).max()
    return f"max_err_vs_fp32={err:.4f}"


def _sigmoid_err(model, xq, out, w, b):
    ref = 1.0 / (1.0 + np.exp(-(xq.astype(np.float32) * 0.05 @ w + b)))
    err = np.abs(out.astype(np.float32) / 255.0 - ref).max()
    return f"max_err_vs_fp32={err:.4f}"


def bench_fig3_conv():
    from repro.core import patterns, pqir, quant
    from repro.core.compile import compile_model
    from repro.core.runtime import ReferenceRuntime

    rng = np.random.default_rng(1)
    w = rng.integers(-128, 128, (16, 8, 3, 3)).astype(np.int8)
    b = rng.integers(-100, 100, (16,)).astype(np.int32)
    r = quant.decompose_multiplier(0.002)
    gb = pqir.GraphBuilder("bench_conv")
    xi = gb.add_input("x", "int8", (None, 8, 16, 16))
    y = patterns.conv_layer(gb, xi, w, b, r, "c0", pads=(1, 1, 1, 1))
    gb.add_output(y, "int8", (None, 16, 16, 16))
    model = gb.build()
    xq = rng.integers(-128, 128, (8, 8, 16, 16)).astype(np.int8)
    rt = ReferenceRuntime(model)
    cm = compile_model(model)
    exact = np.array_equal(rt.run({"x": xq})[y], cm.run({"x": xq})[y])
    us_ref = _timeit(lambda: rt.run({"x": xq}), repeat=5)
    us_fused = _timeit(lambda: cm.run({"x": xq}))
    row("fig3_conv", us_ref, f"fused_us={us_fused:.1f};speedup={us_ref / us_fused:.2f}x;bitexact={exact};{_stats_derived(cm)}")


def bench_rescale_table():
    from repro.core import quant

    rng = np.random.default_rng(2)
    worst = 0.0
    for m in np.concatenate([[0.25, 1 / 3, 1.0, 2**-20], rng.uniform(1e-5, 50.0, 5000)]):
        r = quant.decompose_multiplier(float(m))
        worst = max(worst, abs(r.realized - m) / m)
    us = _timeit(lambda: quant.decompose_multiplier(0.123456), repeat=200)
    anchors = quant.decompose_multiplier(1 / 3)
    row(
        "tbl_rescale_decompose",
        us,
        f"worst_rel_err={worst:.2e};anchor_1/3=({anchors.quant_scale},{anchors.shift});max_exact_int={quant.MAX_EXACT_FLOAT_INT}",
    )


def bench_w8a8_decode():
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.core.convert import convert_params_w8a8
    from repro.models import model as M

    cfg = get_config("qwen3_1_7b", reduced=True)
    cfg8 = dataclasses.replace(cfg, kv_cache_dtype="int8")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    pq = convert_params_w8a8(params)
    B, S = 4, 64
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, 1)).astype(np.int32))
    pos = jnp.full((B,), S // 2, jnp.int32)

    d16 = jax.jit(lambda p, t, ps, c: M.decode_step(p, t, ps, c, cfg, compute_dtype=jnp.float32))
    d8 = jax.jit(lambda p, t, ps, c: M.decode_step(p, t, ps, c, cfg8, compute_dtype=jnp.float32))
    c16 = M.init_cache(cfg, B, S)
    c8 = M.init_cache(cfg8, B, S)
    l16, _ = d16(params, toks, pos, c16)
    l8, _ = d8(pq, toks, pos, c8)
    agree = float((jnp.argmax(l16, -1) == jnp.argmax(l8, -1)).mean())
    us16 = _timeit(lambda: jax.block_until_ready(d16(params, toks, pos, c16)), repeat=10)
    us8 = _timeit(lambda: jax.block_until_ready(d8(pq, toks, pos, c8)), repeat=10)
    # derived: HBM bytes that matter on TPU — weight + cache footprint ratio
    import jax.tree_util as jtu

    bytes_of = lambda t: sum(x.size * x.dtype.itemsize for x in jtu.tree_leaves(t))
    ratio = bytes_of(params) / bytes_of(pq)
    row("sys_w8a8_decode", us16, f"w8a8_us={us8:.1f};argmax_agree={agree:.2f};weight_bytes_ratio={ratio:.2f}x")


def bench_pass_pipeline():
    """repro.passes pipeline on a 3-layer MLP artifact: optimized vs raw
    compile, with the pipeline's folded/eliminated stats in the derived
    column (the two-Mul rescales fold, dead initializers get pruned)."""
    from repro.core.compile import compile_model

    model, xq = _mlp_artifact()
    cm_raw = compile_model(model, optimize=False)
    cm_opt = compile_model(model)
    exact = all(
        np.array_equal(a, b)
        for a, b in zip(cm_raw.run({"input_q": xq}).values(), cm_opt.run({"input_q": xq}).values())
    )
    us_raw = _timeit(lambda: cm_raw.run({"input_q": xq}))
    us_opt = _timeit(lambda: cm_opt.run({"input_q": xq}))
    row(
        "sys_pass_pipeline",
        us_raw,
        f"optimized_us={us_opt:.1f};speedup={us_raw / us_opt:.2f}x;bitexact={exact};{_stats_derived(cm_opt)}",
    )


def _mlp_artifact(layers: int = 3, width: int = 256):
    from repro.core import quant
    from repro.core.toolchain import MLPSpec, quantize_mlp

    rng = np.random.default_rng(4)
    spec = MLPSpec(
        weights=[rng.normal(size=(width, width)).astype(np.float32) * 0.05 for _ in range(layers)],
        biases=[rng.normal(size=(width,)).astype(np.float32) * 0.1 for _ in range(layers)],
        activations=["Relu"] * (layers - 1) + [None],
    )
    calib = rng.normal(size=(width, width)).astype(np.float32)
    model = quantize_mlp(spec, calib)
    xq = quant.quantize(
        rng.normal(size=(64, width)).astype(np.float32), eval(model.metadata["input_scale"]), "int8"
    )
    return model, xq


def bench_plan_overhead():
    """Typed slot-indexed ExecutionPlan vs the old name-keyed dict-env
    interpretation — same registry kernels, only the storage discipline
    differs, so this row isolates the plan layer's overhead (it should be
    ~1.0x: under jit both lower to the same XLA program)."""
    import jax
    import jax.numpy as jnp

    from repro.core.compile import compile_model

    model, xq = _mlp_artifact()
    cm = compile_model(model)
    plan = cm.plan
    feeds = {"input_q": jnp.asarray(xq)}
    run_slots = jax.jit(plan.execute)
    run_dict = jax.jit(plan.execute_dict_env)
    a, b = run_slots(feeds), run_dict(feeds)
    exact = all(np.array_equal(np.asarray(a[k]), np.asarray(b[k])) for k in a)
    us_dict = _timeit(lambda: jax.block_until_ready(run_dict(feeds)))
    us_plan = _timeit(lambda: jax.block_until_ready(run_slots(feeds)))
    n_tensors = len({t for s in plan.steps for t in s.outputs}) + len(plan.inputs)
    row(
        "sys_plan_overhead",
        us_dict,
        f"plan_us={us_plan:.1f};ratio={us_plan / us_dict:.2f}x;bitexact={exact};"
        f"slots={plan.num_slots};tensors={n_tensors};steps={len(plan.steps)}",
    )


def bench_per_channel_overhead():
    """Per-channel fused requant vs scalar: the epilogue multiplies by a
    pre-padded (1, np) vector either way (scalars are broadcast at plan
    time), so per-channel quantization should ride the fused kernels at
    (near-)parity — this row pins that."""
    from repro.core import patterns, pqir, quant
    from repro.core.compile import compile_model

    rng = np.random.default_rng(6)
    w = rng.normal(size=(256, 256)).astype(np.float32) * 0.05
    b = rng.normal(size=(256,)).astype(np.float32) * 0.1
    xq = rng.integers(-128, 128, (64, 256)).astype(np.int8)

    def build(per_channel):
        p = quant.quantize_linear_layer(w, b, 0.05, 0.1, per_channel=per_channel)
        gb = pqir.GraphBuilder("bench_pc")
        xi = gb.add_input("x", "int8", (None, 256))
        y = patterns.fc_layer(gb, xi, p, "fc0", two_mul=True, activation="Relu")
        gb.add_output(y, "int8", (None, 256))
        return compile_model(gb.build())

    cm_scalar, cm_pc = build(False), build(True)
    assert cm_scalar.stats["fused_qlinear"] == 1 and cm_pc.stats["fused_qlinear"] == 1
    us_scalar = _timeit(lambda: cm_scalar.run({"x": xq}))
    us_pc = _timeit(lambda: cm_pc.run({"x": xq}))
    row(
        "sys_per_channel_overhead",
        us_scalar,
        f"per_channel_us={us_pc:.1f};ratio={us_pc / us_scalar:.2f}x;"
        f"fused_scalar={cm_scalar.stats['fused_qlinear']};fused_pc={cm_pc.stats['fused_qlinear']}",
    )


def bench_serving_compiled():
    """One batch-polymorphic compiled artifact served through the
    micro-batching layer at three batch buckets.  After a warmup wave per
    bucket, every timed wave must be served from the plan cache (no
    re-specialization) — the derived column carries requests/s per bucket
    and the cache hit rate, and asserts ≥2 buckets came from cache."""
    from repro.core.compile import compile_model
    from repro.serving import CompiledModelServer, CompiledServerConfig

    from repro.obs.metrics import default_registry

    model, _ = _mlp_artifact(layers=2, width=128)
    cm = compile_model(model, backend="interpret", batch="dynamic")
    # publish serve.* / cache.plan.* into the process registry so a
    # --metrics run snapshots real serving traffic
    srv = CompiledModelServer(
        cm, CompiledServerConfig(max_batch=32), registry=default_registry()
    )
    rng = np.random.default_rng(9)
    xs = rng.integers(-128, 128, (32, 128)).astype(np.int8)

    def serve_wave(n):
        for i in range(n):
            srv.submit(xs[i])
        srv.run_until_drained()

    buckets = (1, 8, 32)
    rps = {}
    buckets_from_cache = 0
    for n in buckets:
        serve_wave(n)  # warmup: specialize + jit this bucket once
        misses_before = cm.cache_stats["misses"]
        repeat = 10
        t0 = time.perf_counter()
        for _ in range(repeat):
            serve_wave(n)
        dt = time.perf_counter() - t0
        rps[n] = n * repeat / dt
        # cache-served: the timed waves for THIS bucket triggered no new
        # specialization (a re-specialization after eviction would show here)
        if cm.cache_stats["misses"] == misses_before:
            buckets_from_cache += 1
    s = srv.summary()
    cache = s["plan_cache"]
    assert buckets_from_cache >= 2, (cache, srv.metrics)
    assert cache["misses"] == len(buckets), cache  # one specialization per bucket
    us = 1e6 / rps[8]  # per-request cost at the mid bucket
    row(
        "sys_serving_compiled",
        us,
        f"rps_b1={rps[1]:.0f};rps_b8={rps[8]:.0f};rps_b32={rps[32]:.0f};"
        f"cache_hit_rate={s['plan_cache_hit_rate']:.2f};"
        f"specializations={cache['misses']};cache_size={cache['size']};"
        f"buckets_from_cache={buckets_from_cache};"
        f"lat_avg_ms={s['latency_avg_ms']:.2f}",
    )


def bench_seq_buckets():
    """One two-axis compiled artifact (named batch N + sequence S) across a
    (batch ∈ {1,8}) × (seq ∈ {32,128}) scenario grid.  Each cell is warmed
    once (specialize + jit), then timed; at most one plan specialization per
    visited grid cell is asserted — the multi-axis generalization of the
    one-specialization-per-bucket serving contract."""
    from repro.core import patterns, pqir, quant
    from repro.core.compile import compile_model

    rng = np.random.default_rng(10)
    p = quant.quantize_linear_layer(
        rng.normal(size=(64, 64)).astype(np.float32) * 0.05,
        rng.normal(size=(64,)).astype(np.float32) * 0.1, 0.05, 0.1,
    )
    gb = pqir.GraphBuilder("bench_seq")
    x = gb.add_input("x", "int8", ("N", "S", 64))
    y = patterns.fc_layer(gb, x, p, "fc0", two_mul=True, activation="Relu")
    gb.add_output(y, "int8", ("N", "S", 64))
    cm = compile_model(gb.build(), backend="interpret", dynamic_axes={"N": None, "S": 32})

    grid = [(b, s) for b in (1, 8) for s in (32, 128)]
    feeds = {
        (b, s): {"x": rng.integers(-128, 128, (b, s, 64)).astype(np.int8)}
        for b, s in grid
    }
    rps = {}
    for b, s in grid:
        cm.run(feeds[(b, s)])  # warmup: specialize + jit this cell once
        misses_before = cm.cache_stats["misses"]
        repeat = 10
        t0 = time.perf_counter()
        for _ in range(repeat):
            cm.run(feeds[(b, s)])
        dt = time.perf_counter() - t0
        rps[(b, s)] = b * repeat / dt
        assert cm.cache_stats["misses"] == misses_before, (
            f"grid cell ({b},{s}) re-specialized during the timed waves"
        )
    cache = cm.cache_stats
    assert cache["misses"] == len(grid), cache  # ≤1 specialization per cell
    us = 1e6 / rps[(8, 32)]
    cells = ";".join(f"rps_b{b}_s{s}={rps[(b, s)]:.0f}" for b, s in grid)
    row(
        "sys_seq_buckets",
        us,
        f"{cells};specializations={cache['misses']};grid_cells={len(grid)};"
        f"cache_hit_rate={cache['hit_rate']:.2f}",
    )


def bench_autotune():
    """Measured per-cell tile autotuning closing the co-design loop: one
    batch-polymorphic 2-layer MLP on the interpret backend, two batch cells.
    Each cell is specialized twice — heuristic tiles vs the budgeted measured
    search — and both jitted executors are timed with the shared median-of-k
    helper.  Tuned must never lose to heuristic beyond CI noise on any
    measured cell (the heuristic is always candidate #0 of the search, so a
    regression means the measurement itself is broken), and a second tuner
    session warm-started from the persisted tile cache must resolve every
    cell with zero new measurements."""
    import os
    import tempfile

    import jax
    import jax.numpy as jnp

    from repro.backend.autotune import Autotuner, measure_median
    from repro.backend.lowering import specialize_plan
    from repro.core.compile import compile_model

    model, xq = _mlp_artifact(layers=2, width=256)
    cache_path = os.path.join(tempfile.mkdtemp(prefix="repro-autotune-"), "tiles.json")
    tuner = Autotuner(budget=4, repeat=3, warmup=1, cache=cache_path)
    cm = compile_model(model, backend="interpret", batch="dynamic", autotune=tuner)

    cells = (8, 64)
    us_h, us_t, ratios = {}, {}, {}
    for cell in cells:
        feeds = {"input_q": jnp.asarray(xq[:cell])}
        plan_h = specialize_plan(cm.plan, cell)  # static heuristic tiles
        plan_t, run_t = cm.specialized(cell)  # the measured search runs here
        run_h = jax.jit(plan_h.execute)
        a, b = run_h(feeds), run_t(feeds)
        assert all(np.array_equal(np.asarray(a[k]), np.asarray(b[k])) for k in a)
        us_h[cell] = measure_median(
            lambda run=run_h, f=feeds: jax.block_until_ready(run(f))
        ) * 1e6
        us_t[cell] = measure_median(
            lambda run=run_t, f=feeds: jax.block_until_ready(run(f))
        ) * 1e6
        ratios[cell] = us_t[cell] / us_h[cell]
        assert ratios[cell] <= 1.35, (
            f"tuned tiles lost to the heuristic at cell N={cell}: "
            f"{us_t[cell]:.1f}us vs {us_h[cell]:.1f}us"
        )
    measured = tuner.measurements

    # warm-start round trip: a brand-new session on the same artifact file
    # specializes every known cell without timing a single candidate
    warm = Autotuner(budget=4, cache=cache_path)
    cm2 = compile_model(model, backend="interpret", batch="dynamic", autotune=warm)
    for cell in cells:
        cm2.specialized(cell)
    assert warm.measurements == 0, (
        f"warm-started session re-measured {warm.measurements} candidate(s)"
    )
    cells_s = ";".join(f"tuned_vs_heur_b{c}={ratios[c]:.2f}x" for c in cells)
    row(
        "sys_autotune",
        us_t[cells[0]],
        f"{cells_s};measurements={measured};warm_measurements={warm.measurements};"
        f"cache_entries={len(warm.cache)}",
    )


def bench_fleet():
    """Fleet-scale serving from one AOT plan artifact: a 3-replica
    ShardedRouter (each replica warm-started by ``load_artifact`` — plan
    cache pre-seeded with the recorded hot cells, jit traces primed) vs a
    single warm-started server on the same mixed-cell traffic.  Cell
    affinity must keep every replica's plan-cache hit rate at least the
    single-server baseline (sharding must not divide cache locality by N),
    and the warm start must serve its first wave faster than a cold
    compile-specialize-jit does.  Zero lost, zero duplicated requests."""
    import os
    import tempfile

    from repro.backend.artifact import load_artifact, save_artifact
    from repro.core import patterns, pqir, quant
    from repro.core.compile import compile_model
    from repro.serving import CompiledModelServer, CompiledServerConfig, ShardedRouter

    rng = np.random.default_rng(11)
    p = quant.quantize_linear_layer(
        rng.normal(size=(64, 64)).astype(np.float32) * 0.05,
        rng.normal(size=(64,)).astype(np.float32) * 0.1, 0.05, 0.1,
    )

    def build_model():
        gb = pqir.GraphBuilder("bench_fleet")
        x = gb.add_input("x", "int8", ("N", "S", 64))
        y = patterns.fc_layer(gb, x, p, "fc0", two_mul=True, activation="Relu")
        gb.add_output(y, "int8", ("N", "S", 64))
        return gb.build()

    # the traffic mix: three seq-bucket cells (S ∈ {8, 16, 24}), waves of 4
    seq_lens, wave = (4, 12, 20), 4
    cfg = CompiledServerConfig(max_batch=wave)

    def serve_waves(submit, drain, n_waves):
        for _ in range(n_waves):
            for s in seq_lens:
                for _ in range(wave):
                    submit(rng.integers(-128, 128, (s, 64)).astype(np.int8))
            drain()

    # record the hot cells once and save the artifact the whole fleet shares
    cm_rec = compile_model(build_model(), backend="interpret", dynamic_axes={"N": None, "S": 8})
    srv_rec = CompiledModelServer(cm_rec, cfg)
    serve_waves(srv_rec.submit, srv_rec.run_until_drained, 1)
    path = os.path.join(tempfile.mkdtemp(prefix="repro-fleet-"), "fleet.json")
    save_artifact(cm_rec, path)

    # warm-start value: first wave on a pre-seeded + jit-primed replica vs a
    # cold compile (specialize + jit on first touch, per cell)
    cm_cold = compile_model(build_model(), backend="interpret", dynamic_axes={"N": None, "S": 8})
    srv_cold = CompiledModelServer(cm_cold, cfg)
    t0 = time.perf_counter()
    serve_waves(srv_cold.submit, srv_cold.run_until_drained, 1)
    cold_ms = (time.perf_counter() - t0) * 1e3

    srv_single = CompiledModelServer(load_artifact(path, warm=True), cfg)
    t0 = time.perf_counter()
    serve_waves(srv_single.submit, srv_single.run_until_drained, 1)
    warm_ms = (time.perf_counter() - t0) * 1e3

    # single warm-started server baseline throughput + hit rate
    n_waves = 10
    t0 = time.perf_counter()
    serve_waves(srv_single.submit, srv_single.run_until_drained, n_waves)
    single_rps = len(seq_lens) * wave * n_waves / (time.perf_counter() - t0)
    single_summary = srv_single.summary()
    single_rate = single_summary["plan_cache_hit_rate"]

    # the fleet: 3 replicas, one front door, cell-affinity sharding
    router = ShardedRouter.from_artifact(path, replicas=3, server_cfg=cfg)
    serve_waves(router.submit, router.run_until_drained, 1)  # route the cells
    t0 = time.perf_counter()
    serve_waves(router.submit, router.run_until_drained, n_waves)
    fleet_rps = len(seq_lens) * wave * n_waves / (time.perf_counter() - t0)
    s = router.summary()
    assert s["lost"] == 0 and s["duplicates"] == 0, s
    assert len(set(s["cell_owners"].values())) == 3, s["cell_owners"]
    rates = s["plan_cache_hit_rates"]
    for name, rate in rates.items():
        assert rate >= single_rate - 1e-9, (
            f"replica {name} hit rate {rate:.3f} fell below the single-server "
            f"baseline {single_rate:.3f}: sharding broke cache locality"
        )
    us = 1e6 / single_rps
    row(
        "sys_fleet",
        us,
        f"fleet_rps={fleet_rps:.0f};single_rps={single_rps:.0f};replicas=3;"
        f"cells={len(seq_lens)};hit_rate_single={single_rate:.2f};"
        f"hit_rate_replicas_min={min(rates.values()):.2f};"
        f"warm_first_wave_ms={warm_ms:.0f};cold_first_wave_ms={cold_ms:.0f};"
        f"warm_speedup={cold_ms / warm_ms:.1f}x;"
        f"lost={s['lost']};dup={s['duplicates']}",
    )


def bench_int4_decode():
    """Sub-8-bit weight lane on the decode path: one 1-layer MLP quantized
    twice from identical float weights (``weight_bits`` 8 vs 4), compiled on
    the tiled interpret backend, timed at decode-shaped cells M ∈ {1, 8}
    (one token per sequence → tokens/s = M / step time).  The packed-int4
    output must be bit-exact against the *unpacked* int4 reference runtime
    (the lane's oracle — see docs/quantization.md) at every cell, and the
    shared cost model must price the w4 weight stream at ≤ 0.55× the w8
    bytes (it is exactly 0.5×: two nibbles per byte)."""
    from repro.backend import cost
    from repro.core.compile import compile_model
    from repro.core.runtime import ReferenceRuntime
    from repro.core.toolchain import MLPSpec, quantize_mlp
    from repro.kernels.qmatmul import choose_tiles

    d = 1024

    def build(bits):
        rng = np.random.default_rng(7)  # identical float weights both ways
        spec = MLPSpec(
            weights=[rng.normal(size=(d, d)).astype(np.float32) * 0.05],
            biases=[rng.normal(size=(d,)).astype(np.float32) * 0.1],
            activations=[None],
        )
        calib = rng.normal(size=(64, d)).astype(np.float32)
        return quantize_mlp(spec, calib, weight_bits=bits, name=f"decode_w{bits}")

    m8, m4 = build(8), build(4)
    cm8 = compile_model(m8, backend="interpret", batch="dynamic")
    cm4 = compile_model(m4, backend="interpret", batch="dynamic")
    rt4 = ReferenceRuntime(m4)

    rng = np.random.default_rng(8)
    cells = (1, 8)
    parts, best_speedup = [], 0.0
    for M in cells:
        feeds = {"input_q": rng.integers(-128, 128, (M, d)).astype(np.int8)}
        out4, ref4 = cm4.run(feeds), rt4.run(feeds)
        exact = all(np.array_equal(out4[k], ref4[k]) for k in out4)
        assert exact, f"packed int4 diverged from the unpacked reference at M={M}"
        us8 = _timeit(lambda: cm8.run(feeds))
        us4 = _timeit(lambda: cm4.run(feeds))
        best_speedup = max(best_speedup, us8 / us4)
        parts.append(
            f"tok_s_b{M}_w8={M / (us8 * 1e-6):.0f};tok_s_b{M}_w4={M / (us4 * 1e-6):.0f};"
            f"speedup_b{M}={us8 / us4:.2f}x"
        )
    # analytic HBM accounting from the single source of truth (backend.cost),
    # at the tiles the decode cell actually specializes to
    bm, bk, bn = choose_tiles(cells[0], d, d)
    hbm8 = cost.qmatmul_hbm_bytes(cells[0], d, d, bm, bk, bn, weight_bits=8)
    hbm4 = cost.qmatmul_hbm_bytes(cells[0], d, d, bm, bk, bn, weight_bits=4)
    w4_w = hbm8 - hbm4  # the packed stream: exactly the halved weight term
    weight_ratio = w4_w / (2.0 * w4_w)
    assert weight_ratio <= 0.55, f"w4 weight bytes {weight_ratio:.2f}x of w8"
    us_ref4 = _timeit(lambda: rt4.run({"input_q": rng.integers(-128, 128, (1, d)).astype(np.int8)}), repeat=5)
    row(
        "sys_int4_decode",
        us_ref4,
        ";".join(parts)
        + f";weight_bytes_ratio={weight_ratio:.2f}x;hbm_est_ratio={hbm4 / hbm8:.2f}x;"
        f"best_speedup={best_speedup:.2f}x;bitexact=True;width={d}",
    )


def bench_attn_decode():
    """The compiled token path on the decode hot loop: the transformer block
    (QKV/O + int8-KV update + fused attention + MLP) executing through the
    specialized ``("N",1,…)`` ExecutionPlan — int8 KV cache in state slots,
    mixed w4/w8 projections — at decode cells M ∈ {1, 8}.  Before timing:
    the compiled step must be bit-exact against the jnp mirror
    (``decode_jax``) at every cell, and the shared PlanCache must hold
    exactly one specialization per visited cell (zero per-step
    re-lowering).  See docs/token_path.md."""
    from repro.serving.token_path import (
        CompiledTokenAdapter,
        CompiledTokenPath,
        TokenPathConfig,
        decode_jax,
        make_token_params,
    )

    cfg = TokenPathConfig()
    params = make_token_params(cfg, seed=3)
    tp = CompiledTokenPath(cfg, params, backend="ref", s_granularity=8)
    ad = CompiledTokenAdapter(tp)

    rng = np.random.default_rng(11)
    s_max, pos0 = 32, 10
    cells = (1, 8)
    parts = []
    for m in cells:
        toks = rng.integers(1, cfg.vocab, (m, 1)).astype(np.int32)
        pos = np.full((m,), pos0, np.int64)
        cache = ad.init_cache(m, s_max)
        for k in cache:  # a warm, non-trivial KV state
            cache[k] = rng.integers(-128, 128, cache[k].shape).astype(np.int8)

        # bit-exactness gate: compiled decode == jnp mirror, logits + states
        onehot = np.zeros((m, s_max, 1), np.int8)
        onehot[:, pos0, 0] = 1
        mask = np.broadcast_to(
            np.arange(s_max)[None, None, :] <= pos0, (m, 1, s_max)
        ).astype(np.float32)
        logits_c, nxt = tp.decode(toks, onehot, mask, cache)
        states = [
            (cache[tp.state_specs[2 * l].input], cache[tp.state_specs[2 * l + 1].input])
            for l in range(cfg.n_layers)
        ]
        logits_j, jstates = decode_jax(cfg, params, toks, onehot, mask, states)
        assert np.array_equal(logits_c, np.asarray(logits_j)), (
            f"compiled decode diverged from the jnp mirror at M={m}"
        )
        for l, (kj, vj) in enumerate(jstates):
            assert np.array_equal(nxt[tp.state_specs[2 * l].input], np.asarray(kj))
            assert np.array_equal(nxt[tp.state_specs[2 * l + 1].input], np.asarray(vj))

        us_c = _timeit(lambda: ad.decode(toks, pos, cache))
        parts.append(f"tok_s_b{m}_compiled={m / (us_c * 1e-6):.0f}")

    # exactly one specialization per visited decode cell, all hits after
    stats = tp.cache_stats()
    assert stats["misses"] == len(cells), stats
    us_c1 = _timeit(
        lambda: ad.decode(
            np.ones((1, 1), np.int32), np.full((1,), pos0, np.int64), ad.init_cache(1, s_max)
        )
    )
    row(
        "sys_attn_decode",
        us_c1,
        ";".join(parts)
        + f";plan_misses={stats['misses']};cells={len(cells)};bitexact=True;"
        f"d={cfg.d_model};layers={cfg.n_layers};heads={cfg.n_heads}",
    )


def bench_grad_compress():
    import jax
    import jax.numpy as jnp

    from repro.optim.grad_compress import _compress_leaf

    # single-device emulation of the wire format economics
    rng = np.random.default_rng(3)
    g = jnp.asarray(rng.normal(size=(1024, 1024)).astype(np.float32))
    res = jnp.zeros_like(g)

    def one(g, res):
        g_eff = g + res
        s = jnp.abs(g_eff).max() / 127.0 + 1e-20
        q = jnp.clip(jnp.rint(g_eff / s), -128, 127)
        return (s * q), g_eff - s * q

    fn = jax.jit(one)
    fn(g, res)
    us = _timeit(lambda: jax.block_until_ready(fn(g, res)), repeat=10)
    deq, _ = fn(g, res)
    err = float(jnp.abs(deq - g).max() / jnp.abs(g).max())
    row("sys_grad_compress", us, f"wire_bytes_ratio=4.00x_vs_f32;one_round_rel_err={err:.4f}")


def main(argv=None) -> None:
    import jax

    from repro.core import patterns
    from repro.launch.compile_cache import enable_compile_cache

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="fast CI subset")
    ap.add_argument(
        "--json", metavar="PATH",
        help="also write the rows as JSON (e.g. BENCH_42.json) so the perf "
        "trajectory persists across PRs; CI uploads it as an artifact",
    )
    ap.add_argument(
        "--trace", metavar="PATH",
        help="install a repro.obs tracer for the whole run and dump the "
        "Chrome-trace JSON (load it at chrome://tracing or ui.perfetto.dev): "
        "compile/pass spans, per-cell specializations, serving steps",
    )
    ap.add_argument(
        "--metrics", metavar="PATH",
        help="dump the process MetricsRegistry snapshot (serve.*, engine.*, "
        "cache.*) as JSON after the run",
    )
    args = ap.parse_args(argv)
    enable_compile_cache()

    tracer = None
    if args.trace:
        from repro.obs import trace as _trace

        tracer = _trace.install()

    print("name,us_per_call,derived")
    bench_pattern("fig1_fc_two_mul", activation=None, two_mul=True)
    if not args.smoke:
        bench_pattern("fig2_fc_relu_one_mul", activation="Relu", two_mul=False)
        bench_fig3_conv()
        bench_pattern("fig4_int8_tanh", act_builder=patterns.fc_int8_tanh, derived_fn=_tanh_err)
        bench_pattern("fig5_fp16_tanh", act_builder=patterns.fc_fp16_tanh, derived_fn=_tanh_err)
        bench_pattern("fig6_fp16_sigmoid", act_builder=patterns.fc_fp16_sigmoid, derived_fn=_sigmoid_err)
        bench_rescale_table()
    bench_pass_pipeline()
    bench_plan_overhead()
    bench_per_channel_overhead()
    bench_serving_compiled()
    bench_seq_buckets()
    bench_autotune()
    bench_fleet()
    bench_int4_decode()
    bench_attn_decode()
    if not args.smoke:
        bench_w8a8_decode()
        bench_grad_compress()

    if tracer is not None:
        from repro.obs import trace as _trace

        _trace.uninstall()
        tracer.dump(args.trace)
        print(f"# wrote {len(tracer.records)} trace events to {args.trace} (trace_id={tracer.trace_id})")
    if args.metrics:
        from repro.obs.metrics import default_registry

        with open(args.metrics, "w") as f:
            json.dump(default_registry().snapshot(), f, indent=2)
            f.write("\n")
        print(f"# wrote metrics snapshot to {args.metrics}")

    if args.json:
        payload = {
            "schema": "repro-bench-v1",
            "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
            "smoke": bool(args.smoke),
            "platform": platform.platform(),
            "device": {
                "platform": jax.devices()[0].platform,
                "kind": jax.devices()[0].device_kind,
                "count": len(jax.devices()),
            },
            "python": platform.python_version(),
            "rows": _ROWS,
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
            f.write("\n")
        print(f"# wrote {len(_ROWS)} rows to {args.json}")


if __name__ == "__main__":
    main()
