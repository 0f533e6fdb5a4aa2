"""Observability plane: tracing round-trips, unified metrics, provenance.

Covers the repro.obs contract the rest of the stack leans on:

* trace export round-trip — nested spans land with correct depth, the
  Chrome-trace JSON loads back and is monotonic, async request pairs link;
* metrics snapshot determinism — concurrent publishers produce exact
  counts and byte-stable snapshots;
* no-tracer overhead — with nothing installed the instrumentation sites
  get one shared no-op span (no allocation, no recording);
* end-to-end — compile/serve with a tracer installed and find the pass,
  fusion, specialization and serving spans the ISSUE contract names.
"""
import json
import threading
import time

import numpy as np
import pytest

from repro.core.cache import LruCache
from repro.obs import trace as obs_trace
from repro.obs.metrics import Histogram, MetricsRegistry, cache_key
from repro.obs.provenance import PlanProvenance


@pytest.fixture
def tracer():
    t = obs_trace.install()
    try:
        yield t
    finally:
        obs_trace.uninstall()


def _mlp():
    from repro.core.toolchain import MLPSpec, quantize_mlp

    rng = np.random.default_rng(0)
    spec = MLPSpec(
        weights=[rng.normal(size=(32, 32)).astype(np.float32) * 0.1 for _ in range(2)],
        biases=[rng.normal(size=(32,)).astype(np.float32) * 0.1 for _ in range(2)],
        activations=["Relu", None],
    )
    calib = rng.normal(size=(64, 32)).astype(np.float32)
    return quantize_mlp(spec, calib)


# -- tracing ------------------------------------------------------------------


class TestTracer:
    def test_nested_spans_record_depth_and_attrs(self, tracer):
        with obs_trace.span("outer", a=1):
            with obs_trace.span("inner") as s:
                s.set(tile="bm=32")
        outer, = tracer.spans("outer")
        inner, = tracer.spans("inner")
        assert outer.depth == 0 and inner.depth == 1
        assert outer.attrs == {"a": 1} and inner.attrs == {"tile": "bm=32"}
        # the child interval nests inside the parent interval
        assert outer.ts <= inner.ts
        assert inner.ts + inner.dur <= outer.ts + outer.dur + 1e-9

    def test_chrome_trace_json_round_trip(self, tracer):
        with obs_trace.span("compile.fuse", nodes=3):
            obs_trace.event("cache.plan.miss", key="8")
        obs_trace.async_begin("serve.request", 7, shape="(32,)")
        obs_trace.async_end("serve.request", 7)
        payload = json.loads(json.dumps(tracer.to_chrome_trace()))
        evs = payload["traceEvents"]
        assert evs[0]["ph"] == "M"  # process metadata
        body = evs[1:]
        # monotonic, non-negative microsecond timestamps
        ts = [e["ts"] for e in body]
        assert all(t >= 0 for t in ts) and ts == sorted(ts)
        by_ph = {e["ph"]: e for e in body}
        assert set(by_ph) == {"X", "i", "b", "e"}
        assert by_ph["X"]["name"] == "compile.fuse" and "dur" in by_ph["X"]
        assert by_ph["X"]["cat"] == "compile"
        assert by_ph["b"]["id"] == by_ph["e"]["id"] == 7
        assert payload["otherData"]["trace_id"] == tracer.trace_id

    def test_render_tree_nests(self, tracer):
        with obs_trace.span("outer"):
            with obs_trace.span("inner", k=2):
                pass
        tree = tracer.render_tree()
        assert tracer.trace_id in tree
        out_line, = [l for l in tree.splitlines() if "outer" in l]
        in_line, = [l for l in tree.splitlines() if "inner" in l]
        indent = lambda l: len(l) - len(l.lstrip())  # noqa: E731
        assert indent(in_line) > indent(out_line)
        assert "k=2" in in_line

    def test_exception_inside_span_still_records(self, tracer):
        with pytest.raises(RuntimeError):
            with obs_trace.span("boom"):
                raise RuntimeError("x")
        rec, = tracer.spans("boom")
        assert rec.attrs["error"] == "RuntimeError"

    def test_threads_get_distinct_tids(self, tracer):
        # barrier keeps all workers alive at once — otherwise the OS may
        # reuse a finished thread's ident and collapse tids
        barrier = threading.Barrier(3)

        def work():
            barrier.wait()
            with obs_trace.span("worker"):
                pass

        ts = [threading.Thread(target=work) for _ in range(3)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        with obs_trace.span("main"):
            pass
        tids = {r.tid for r in tracer.spans()}
        assert len(tids) == 4
        # every worker span is depth 0 in its own thread
        assert all(r.depth == 0 for r in tracer.spans("worker"))


class _Hook:
    """A profiler hook that logs when each annotation is entered and left."""

    def __init__(self):
        self.log = []

    def __call__(self, name):
        hook = self

        class Annotation:
            def __enter__(self):
                hook.log.append(("enter", name))

            def __exit__(self, *exc):
                hook.log.append(("exit", name))

        return Annotation()


class TestProfilerHook:
    def test_annotations_enter_and_leave_in_nesting_order(self):
        hook = _Hook()
        tracer = obs_trace.install(obs_trace.Tracer(annotate=hook))
        try:
            with obs_trace.span("outer", uid=3):
                with obs_trace.span("inner"):
                    pass
                with obs_trace.span("second"):
                    pass
        finally:
            obs_trace.uninstall()
        assert hook.log == [("enter", "outer"), ("enter", "inner"), ("exit", "inner"),
                            ("enter", "second"), ("exit", "second"), ("exit", "outer")]
        # the attributes stay in the tracer's record, not in the annotation
        outer, = tracer.spans("outer")
        assert outer.attrs == {"uid": 3}

    def test_an_exception_still_leaves_the_annotation(self):
        hook = _Hook()
        obs_trace.install(obs_trace.Tracer(annotate=hook))
        try:
            with pytest.raises(RuntimeError):
                with obs_trace.span("boom"):
                    raise RuntimeError("x")
        finally:
            obs_trace.uninstall()
        assert hook.log == [("enter", "boom"), ("exit", "boom")]

    def test_parent_links_each_record_to_the_open_span(self, tracer):
        with obs_trace.span("step"):
            with obs_trace.span("decode"):
                obs_trace.event("cache.plan.hit")
            with obs_trace.span("select"):
                pass
        with obs_trace.span("next"):
            pass
        by_name = {r.name: r for r in tracer.records}
        step = by_name["step"]
        assert step.parent is None and by_name["next"].parent is None
        assert by_name["decode"].parent == step.sid
        assert by_name["select"].parent == step.sid
        assert by_name["cache.plan.hit"].parent == by_name["decode"].sid
        sids = [r.sid for r in tracer.spans()]
        assert None not in sids and len(set(sids)) == len(sids)

    def test_threads_keep_their_own_parents(self, tracer):
        def work():
            with obs_trace.span("worker"):
                pass

        with obs_trace.span("main"):
            t = threading.Thread(target=work)
            t.start()
            t.join()
        worker, = tracer.spans("worker")
        assert worker.parent is None


class TestNoTracer:
    def test_span_is_shared_noop_singleton(self):
        assert obs_trace.current() is None and not obs_trace.enabled
        assert obs_trace.span("x", a=1) is obs_trace.span("y")
        assert obs_trace.span("x") is obs_trace.NULL_SPAN
        with obs_trace.span("x") as s:
            assert s.set(anything=1) is s
        obs_trace.event("x")  # no-ops, no error
        obs_trace.async_begin("x", 1)
        obs_trace.async_end("x", 1)

    def test_token_path_records_nothing_and_calls_no_hook(self, monkeypatch):
        """Served with no tracer installed, the token path opens no span,
        calls no profiler hook, never waits on the device, and puts only what
        it must: the zero cache once, and each admission's K/V rows."""
        import jax

        from repro.serving.engine import EngineConfig, Request, ServeEngine
        from repro.serving.token_path import CompiledTokenAdapter, CompiledTokenPath, TokenPathConfig

        calls = []
        for name in ("block_until_ready", "device_put"):
            real = getattr(jax, name)
            monkeypatch.setattr(jax, name, lambda *a, _real=real, _name=name, **k: (
                calls.append(_name), _real(*a, **k))[1])
        tp = CompiledTokenPath(TokenPathConfig(), backend="ref", seed=1, s_granularity=8)

        def serve():
            eng = ServeEngine(ecfg=EngineConfig(slots=2, max_len=16, prefill_bucket=8),
                              adapter=CompiledTokenAdapter(tp))
            for i in range(3):
                eng.submit(Request(uid=i, prompt=np.arange(1, 4 + i, dtype=np.int32), max_new_tokens=3))
            eng.run_until_drained()

        hook = _Hook()
        tracer = obs_trace.Tracer(annotate=hook)
        serve()  # no tracer installed
        assert obs_trace.current() is None
        assert hook.log == [] and tracer.records == [] and calls == ["device_put"] * (1 + 3)
        # the same serving, traced, does call the hook and wait for the rows
        obs_trace.install(tracer)
        try:
            serve()
        finally:
            obs_trace.uninstall()
        assert hook.log and tracer.spans("engine.step") and "block_until_ready" in calls

    def test_uninstrumented_overhead_smoke(self):
        """The no-tracer fast path is a global read + a shared singleton;
        generous bound, this guards against accidental allocation storms."""
        n = 20_000
        t0 = time.perf_counter()
        for _ in range(n):
            with obs_trace.span("hot"):
                pass
        dt = time.perf_counter() - t0
        assert dt < 1.0, f"{n} no-op spans took {dt:.3f}s"


# -- metrics ------------------------------------------------------------------


class TestMetrics:
    def test_concurrent_publish_is_exact_and_deterministic(self):
        reg = MetricsRegistry()
        n_threads, n_ops = 8, 1000

        def work(i):
            c = reg.counter("serve.requests")
            h = reg.histogram("serve.latency_ms")
            for k in range(n_ops):
                c.inc()
                h.observe((k % 17) + 0.5)

        ts = [threading.Thread(target=work, args=(i,)) for i in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        snap = reg.snapshot()
        assert snap["serve.requests"] == n_threads * n_ops
        assert snap["serve.latency_ms"]["count"] == n_threads * n_ops
        # deterministic: repeated snapshots of identical state are byte-equal
        assert json.dumps(snap) == json.dumps(reg.snapshot())
        json.loads(json.dumps(snap))  # JSON-able throughout

    def test_histogram_bounded_memory_and_quantiles(self):
        h = Histogram()
        for v in range(1, 10_001):
            h.observe(float(v))
        assert h.count == 10_000
        # log-bucketed: far fewer buckets than samples
        assert len(h.buckets) < 100
        assert h.quantile(0.0) == 1.0 and h.quantile(1.0) == 10_000.0
        # mid quantiles within the documented growth-factor error
        assert h.quantile(0.5) == pytest.approx(5000, rel=0.16)
        assert h.quantile(0.95) == pytest.approx(9500, rel=0.16)
        s = Histogram().stats()
        assert s["count"] == 0 and s["p99"] is None and s["avg"] is None

    def test_kind_mismatch_is_an_error(self):
        reg = MetricsRegistry()
        reg.counter("a.b")
        with pytest.raises(TypeError, match="a.b"):
            reg.gauge("a.b")

    def test_prometheus_export(self):
        reg = MetricsRegistry()
        reg.counter("serve.requests").inc(3)
        reg.gauge("cache.plan.size").set(2)
        reg.histogram("serve.latency_ms").observe(4.0)
        text = reg.to_prometheus()
        assert "# TYPE repro_serve_requests counter\nrepro_serve_requests 3" in text
        assert "repro_cache_plan_size 2" in text
        assert 'repro_serve_latency_ms{quantile="0.5"}' in text
        assert "repro_serve_latency_ms_count 1" in text

    def test_cache_attach_publishes_canonical_live_gauges(self):
        reg = MetricsRegistry()
        cache = LruCache(2, scope="plan")
        cache.attach_metrics(reg)
        cache.get("k")  # miss
        cache.put("k", 1)
        cache.get("k")  # hit
        snap = reg.snapshot()
        assert snap[cache_key("plan", "hits")] == 1.0
        assert snap[cache_key("plan", "misses")] == 1.0
        assert snap[cache_key("plan", "hit_rate")] == 0.5
        # live callback gauges: later cache activity shows without re-attach
        cache.get("k")
        assert reg.snapshot()[cache_key("plan", "hits")] == 2.0
        # the alias dict is untouched by the registry route
        assert set(cache.stats) == {"size", "capacity", "hits", "misses", "evictions", "hit_rate"}

    def test_scoped_cache_emits_trace_events(self, tracer):
        cache = LruCache(1, scope="plan")
        cache.get("a")
        cache.put("a", 1)
        cache.put("b", 2)  # evicts a
        cache.get("b")
        names = [e.name for e in tracer.events()]
        assert names.count("cache.plan.miss") == 1
        assert names.count("cache.plan.evict") == 1
        assert names.count("cache.plan.hit") == 1


# -- provenance ---------------------------------------------------------------


class TestProvenance:
    def test_record_and_render(self):
        p = PlanProvenance(nodes_before=10, nodes_after=7, pass_iterations=2)
        p.add_pass(0, "const_fold", {"folded": 3, "noise": 0})
        p.add_pass(0, "noop", {"x": 0})  # all-zero: not recorded
        p.add_fusion("qlinear", "fc0_matmul", ("fc0_matmul", "fc0_add"), "y")
        p.add_specialization({"N": 8}, {"fc0": "m=8,bm=32"})
        assert len(p.passes) == 1 and p.pass_totals == {"folded": 3}
        text = p.render()
        assert "passes: nodes 10->7 in 2 iteration(s) (folded=3)" in text
        assert "qlinear @ fc0_matmul: fc0_matmul+fc0_add -> y" in text
        assert "(N=8): fc0 m=8,bm=32" in text
        assert "trace" not in text  # only rendered when a tracer was installed
        d = json.loads(json.dumps(p.to_dict()))
        assert d["fusions"][0]["pattern"] == "qlinear"
        assert d["specializations"][0]["bindings"] == {"N": 8}

    def test_compiled_plan_carries_provenance(self):
        from repro.core.compile import compile_model

        cm = compile_model(_mlp(), backend="interpret", batch="dynamic")
        prov = cm.plan.provenance
        assert prov is not None
        assert len(prov.fusions) == cm.stats["fused_qlinear"] == 2
        assert prov.trace_id is None  # no tracer at compile time
        assert "provenance:" not in cm.plan.pretty()
        verbose = cm.plan.pretty(verbose=True)
        assert "provenance:" in verbose and "fusions: 2 matched" in verbose
        # lazy per-cell specialization appends to the shared record and the
        # specialized plan shows the same history
        x = np.zeros((3, 32), np.int8)
        cm.run({cm.input_names[0]: x})
        plan8, _ = cm.specialized(8)
        assert len(prov.specializations) == 2
        assert plan8.provenance is prov
        assert "specializations: 2" in cm.plan.pretty(verbose=True)


# -- end-to-end ---------------------------------------------------------------


class TestEndToEnd:
    def test_compile_and_serve_spans(self, tracer):
        from repro.core.compile import compile_model
        from repro.serving import CompiledModelServer, CompiledServerConfig

        cm = compile_model(_mlp(), backend="interpret", batch="dynamic")
        assert cm.plan.provenance.trace_id == tracer.trace_id
        srv = CompiledModelServer(cm, CompiledServerConfig(max_batch=4))
        rng = np.random.default_rng(1)
        reqs = [srv.submit(rng.integers(-128, 128, (32,)).astype(np.int8)) for _ in range(6)]
        srv.run_until_drained()
        assert all(r.done for r in reqs)

        assert tracer.spans("compile") and tracer.spans("compile.fuse")
        assert tracer.spans("compile.lower") and tracer.spans("passes.pipeline")
        assert any(s.name.startswith("pass.") for s in tracer.spans())
        # one specialization span per visited scenario cell (buckets 4 and 2)
        specs = tracer.spans("backend.specialize")
        assert len(specs) == 2
        assert {s.attrs["bindings"] for s in specs} == {"N=4", "N=2"}
        # each specialization span carries the chosen tiles per fused step
        assert all(
            any("bm=" in str(v) for v in s.attrs.values()) for s in specs
        )
        # serving: step spans with coalesce/compute children, request pairs
        steps = tracer.spans("serve.step")
        assert len(steps) == 2 and len(tracer.spans("serve.compute")) == 2
        recs = tracer.records
        begins = {r.aid for r in recs if r.kind == "async_b" and r.name == "serve.request"}
        ends = {r.aid for r in recs if r.kind == "async_e" and r.name == "serve.request"}
        assert begins == ends == {r.uid for r in reqs}
        # run phases inside the compiled model
        assert tracer.spans("run.pad") and tracer.spans("run.execute") and tracer.spans("run.slice")

    def test_server_registry_unifies_cache_and_serve_metrics(self):
        from repro.core.compile import compile_model
        from repro.serving import CompiledModelServer, CompiledServerConfig

        cm = compile_model(_mlp(), backend="interpret", batch="dynamic")
        reg = MetricsRegistry()
        srv = CompiledModelServer(cm, CompiledServerConfig(max_batch=4), registry=reg)
        rng = np.random.default_rng(2)
        for _ in range(5):
            srv.submit(rng.integers(-128, 128, (32,)).astype(np.int8))
        srv.run_until_drained()
        snap = reg.snapshot()
        assert snap["serve.requests"] == srv.metrics["requests"] == 5
        assert snap["serve.completed"] == 5
        assert snap["serve.latency_ms"]["count"] == 5
        assert snap["serve.queue_wait_ms"]["count"] == 5
        # canonical cache keys mirror the alias dict exactly
        for field, v in cm.cache_stats.items():
            assert snap[cache_key("plan", field)] == pytest.approx(float(v))
