"""The benchmark harness: one run of one cell.

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration and a traffic mix; everything else is found by name, so that
a new cell, path or metric is a new file and no edit here:

- ``bench/configs/<config>.json``: widths, fixed-point constants, the served
  path (``bench/paths/<path>.py``) and the plain reference
  (``bench/references/<reference>.py``);
- ``bench/traffic/<mix>.json``: prompt and output lengths, arrival kind
  (``bench/arrivals/<kind>.py``);
- ``bench/cells/<cell>.json``: the offered load, the engine's sizes, and
  what the correctness check samples and its limit;
- ``bench/metrics/<metric>.py``: one reader per metric, end-to-end or
  per-layer, each ``read(run)`` of a :class:`RunData`.

A path module gives ``build(cell, weights)`` (the served system, with
``warm_up``, ``submit``, ``busy``, ``step``, ``queue_len``, ``calls``,
``counters`` and ``close``), ``work(calls, config, peaks)`` (the true-shape
work of its calls) and ``KERNELS`` (its Pallas calls by family).  A
reference module gives ``make_weights(config, seed)`` and ``checks(cell,
weights, sample)``.

A run makes the weights from the seed, builds the path, warms up every
shape the traffic reaches (that is ``setup_s``), serves the traffic for
``--seconds``, then checks a seeded sample of the finished requests against
the reference and prints one JSON line.  ``--trace 1`` records the window
with the profiler and reports the per-layer metrics instead of the
end-to-end ones.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import loadgen  # noqa: E402
import stats  # noqa: E402
import trace_reduce  # noqa: E402
import work  # noqa: E402

#: How long after the window the run keeps serving, at most, for the first
#: tokens of requests that were due inside it.
DRAIN_S = 60.0
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class SetupError(RuntimeError):
    """The run cannot start here (no chip, unknown device, unknown cell)."""


def module(kind: str, name: str, bench: Path = BENCH):
    """``<bench>/<kind>/<name>.py``, imported by file path (once)."""
    path = bench / kind / f"{name}.py"
    key = f"bench:{path}"
    if key in sys.modules:
        return sys.modules[key]
    if not path.is_file():
        raise SetupError(f"no {kind} module {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    spec: dict  # bench/cells/<name>.json
    end_to_end: List[dict]
    per_layer: List[dict]
    bench: Path = BENCH  # where its modules are found

    def module(self, kind: str, name: str):
        return module(kind, name, self.bench)

    def path(self):
        return self.module("paths", self.config["path"])

    def reference(self):
        return self.module("references", self.config["reference"])


def _read_json(path: Path) -> dict:
    return json.loads(path.read_text())


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = _read_json(root / "BENCHMARK.json")
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise SetupError(f"no workload {name!r} in BENCHMARK.json")
    w = by_name[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]

    def applies(metric: dict) -> bool:
        return name in metric.get("workloads", [name])

    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=_read_json(root / conf["file"]),
        traffic=_read_json(root / "bench" / "traffic" / f"{w['traffic']}.json"),
        spec=_read_json(root / "bench" / "cells" / f"{name}.json"),
        end_to_end=[m for m in bench["end_to_end"] if applies(m)],
        per_layer=[m for m in bench["per_layer"] if applies(m)],
        bench=root / "bench",
    )


# ---------------------------------------------------------------------------
# serving the traffic
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Record:
    item: loadgen.Item
    due: float
    submitted: float
    req: object  # .generated, .done, .t_first, .t_done
    times: List[float] = dataclasses.field(default_factory=list)


class Compiles:
    """Counts XLA compilations (``jax.monitoring``) while ``on``."""

    def __init__(self) -> None:
        self.on = False
        self.count = 0

    def __call__(self, event: str, duration: float, **kwargs) -> None:
        if self.on and event == BACKEND_COMPILE_EVENT:
            self.count += 1


COMPILES = Compiles()


@dataclasses.dataclass
class Window:
    t0: float
    t_end: float  # t0 + seconds: the window the end-to-end metrics cover
    t_closed: float  # end of the last step begun inside the window
    records: List[Record]
    queue_samples: List[tuple]  # (time, requests waiting) as each step begins
    steps: List[tuple]  # (start, seconds) of each engine step


def serve(served, arrivals, pool: loadgen.Pool, seconds: float, *, drain_s: float = DRAIN_S) -> Window:
    """Offer the traffic for ``seconds`` and keep serving until every
    request due inside the window has its first token (at most ``drain_s``
    more).  Each token is stamped when the engine step that made it ends;
    a first token at the request's own ``t_first``.  Python's garbage
    collector is off while it serves, so that no collection pauses a step."""
    import jax

    records: List[Record] = []
    inflight: List[Record] = []
    queue_samples: List[tuple] = []
    steps: List[tuple] = []
    gc.collect()
    gc.disable()
    t0 = time.monotonic()
    t_end = t0 + seconds
    t_closed = None
    window = jax.profiler.TraceAnnotation("bench.window")
    window.__enter__()
    COMPILES.on = True
    arrivals.start(t0)
    try:
        while True:
            now = time.monotonic()
            # every arrival due by now is recorded before the window may
            # close, so a request that fell due during a long step counts
            for due in arrivals.due(now):
                item = pool.next()
                req = served.submit(item)
                rec = Record(item, due, time.monotonic(), req)
                records.append(rec)
                inflight.append(rec)
            if t_closed is None and now >= t_end:
                t_closed = now
                COMPILES.on = False
                window.__exit__(None, None, None)
            if t_closed is not None:
                waiting = any(not r.times for r in records if r.due < t_end)
                if not waiting or now >= t_end + drain_s:
                    break
            if not served.busy():
                wake = min(arrivals.next_time(), t_end if t_closed is None else now + 0.01)
                time.sleep(max(0.0, wake - time.monotonic()))
                continue
            queue_samples.append((now, served.queue_len()))
            with jax.profiler.TraceAnnotation("bench.step"):
                served.step()
            t = time.monotonic()
            steps.append((now, t - now))
            still = []
            for rec in inflight:
                n = len(rec.req.generated)
                if n > len(rec.times):
                    if not rec.times:
                        rec.times.append(rec.req.t_first)
                    rec.times.extend([t] * (n - len(rec.times)))
                if rec.req.done:
                    arrivals.done(rec.req.t_done)
                else:
                    still.append(rec)
            inflight = still
    finally:
        gc.enable()
        COMPILES.on = False
        if t_closed is None:
            window.__exit__(None, None, None)
    return Window(t0, t_end, t_closed if t_closed is not None else time.monotonic(), records,
                  queue_samples, steps)


def sample_finished(win: Window, count: int, seed: int) -> List[dict]:
    """A seeded sample of the finished requests, the longest among them:
    ``{"prompt", "generated"}`` each."""
    done = [r for r in win.records if r.req.done]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.item.prompt) + len(r.req.generated), -r.item.index))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([seed, 7])
    pick = [rest[i] for i in rng.permutation(len(rest))[: max(0, count - 1)]]
    return [{"prompt": np.asarray(r.item.prompt), "generated": list(r.req.generated)}
            for r in [longest] + pick]


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class RunData:
    """Everything a metric reader may read of one run."""

    cell: Cell
    seconds: float
    setup_s: float
    window: Window
    calls: List[tuple]  # (kind, start, seconds, shape) of path calls begun in the window
    counters: Dict[str, int]  # the path's own counts
    compiles: int  # XLA compilations inside the window
    device: dict  # platform, kind, count, memory_peak_bytes
    peaks: Dict[str, float]  # bench/peaks.json's row of this device
    work: object  # the path's true-shape work of ``calls`` (``path.work``)
    events: Optional[dict] = None  # traced runs: trace_reduce.events of the window
    trace: Optional[dict] = None  # traced runs: trace_reduce.reduce with the path's KERNELS

    def spans(self, kind: str) -> List[float]:
        return [c[2] for c in self.calls if c[0] == kind]


def device_info(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devices)}


def memory_peak(devices) -> Optional[int]:
    peaks = []
    for d in devices:
        memory = d.memory_stats() or {}
        if "peak_bytes_in_use" in memory:
            peaks.append(int(memory["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def build(cell: Cell, seed: int):
    """Weights from the seed, the served path, every shape warmed up."""
    ref = cell.reference()
    weights = ref.make_weights(cell.config, seed)
    served = cell.path().build(cell, weights)
    served.warm_up()
    return ref, weights, served


def arrivals_for(cell: Cell, seconds: float, load: Optional[dict] = None):
    kind = cell.traffic["arrival"]
    order = np.random.default_rng([loadgen.ORDER, 1])  # every seed: the same arrivals
    return cell.module("arrivals", kind).Arrivals(load or cell.spec["load"], seconds, order)


def pool_for(cell: Cell, arrivals, seed: int) -> loadgen.Pool:
    """The cell's ``pool`` of distinct requests, else one period's arrivals."""
    size = int(cell.spec["pool"]) if "pool" in cell.spec else int(arrivals.n)
    return loadgen.Pool(cell.traffic, size, int(cell.config["vocab_size"]), [seed, 0])


def _trace_window(trace: bool):
    """Start the profiler where ``trace``; returns a function that stops it
    and returns the window's events (``None`` untraced)."""
    import jax

    if not trace:
        return lambda: None
    tracedir = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(tracedir, profiler_options=opts)

    def stop():
        jax.profiler.stop_trace()
        try:
            files = sorted(Path(tracedir).rglob("*.xplane.pb"))
            if not files:
                raise RuntimeError("the profiler wrote no trace")
            return trace_reduce.events(str(files[-1]))
        finally:
            shutil.rmtree(tracedir, ignore_errors=True)

    return stop


def diagnostics(data: RunData) -> str:
    """One line on what the window held, for reading a far-off run."""
    win = data.window
    due = stats.due(win)
    ttft, gaps, late = stats.ttft(win), stats.token_gaps(win), stats.lateness(win)
    parts = [
        f"{len(due)} requests due in the window, {sum(1 for r in due if not r.times)} without a first token",
        f"{stats.tokens(win)} output tokens, {len(gaps)} token gaps",
        f"ttft p50 {1e3 * stats.percentile(ttft, 50):.1f} ms, itl p50 {1e3 * stats.percentile(gaps, 50):.1f} ms",
        f"generator lateness p50 {1e3 * stats.percentile(late, 50):.3f} ms, max {1e3 * max(late, default=0.0):.3f} ms",
    ]
    for kind in sorted({c[0] for c in data.calls}):
        s = data.spans(kind)
        parts.append(f"{kind} {len(s)} calls, {sum(s):.3f} s, mean {1e3 * sum(s) / len(s):.2f} ms, "
                     f"max {1e3 * max(s):.2f} ms")
    inside = [d for t, d in win.steps if t < win.t_end]
    if inside:
        parts.append(f"{len(inside)} steps, longest {1e3 * max(inside):.1f} ms, "
                     f"queue max {max((q for _, q in win.queue_samples), default=0)}")
    parts.append(f"compiles {data.compiles}; path {data.counters}")
    return f"bench: {data.cell.name}: " + "; ".join(parts)


def run(cell: Cell, seed: int, seconds: float, trace: bool, *, t_start: float,
        peaks: Dict[str, float], log=print) -> dict:
    import jax

    devices = jax.devices()[: cell.chips]
    ref, weights, served = build(cell, seed)
    path = cell.path()
    arrivals = arrivals_for(cell, seconds)
    pool = pool_for(cell, arrivals, seed)
    setup_s = time.monotonic() - t_start
    stop_trace = _trace_window(trace)
    win = serve(served, arrivals, pool, seconds)
    events = stop_trace()
    calls = [c for c in served.calls if win.t0 <= c[1] < win.t_closed]
    compiles, COMPILES.count = COMPILES.count, 0
    data = RunData(
        cell=cell, seconds=seconds, setup_s=setup_s, window=win, calls=calls,
        counters=served.counters(), compiles=compiles,
        device={**device_info(devices), "memory_peak_bytes": memory_peak(devices)},
        peaks=peaks, work=path.work(calls, cell.config, peaks), events=events,
        trace=trace_reduce.reduce(events, path.KERNELS) if events is not None else None,
    )
    log(diagnostics(data))

    # the program's state goes before the reference runs
    sample = sample_finished(win, int(cell.spec["check"]["requests"]), seed)
    served.close()
    del served
    gc.collect()
    checks, agrees = ref.checks(cell, weights, sample)
    due = stats.due(win)
    failed = sum(1 for r in due if not r.times)

    result = {
        "correct": bool(agrees and failed == 0),
        "attempted": len(due),
        "failed": failed,
        "metrics": {},
        "device": dict(data.device),
    }
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = cell.module("metrics", m["name"]).read(data)
        if value is not None:
            result["metrics"][m["name"]] = {"value": float(value), "unit": m["unit"]}
    if trace:
        result["device"]["busy_s"] = data.trace["busy_s"]
        result["device"]["window_s"] = data.trace["window_s"]
        result["breakdown"] = {"device_ops": data.trace["device_ops"], "idle_gaps": data.trace["idle_gaps"]}
    result["checks"] = checks
    return result


def print_checks(result: dict) -> None:
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} limit {c['limit']}", file=sys.stderr)


def setup_device(cell: Cell):
    """The devices and peaks of this machine; a run needs TPUs, as many as
    the cell asks for, of a kind in ``bench/peaks.json``."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SetupError(f"needs a TPU; JAX found {devices[0].platform!r}")
    if len(devices) < cell.chips:
        raise SetupError(f"the cell needs {cell.chips} chips; JAX found {len(devices)}")
    try:
        peaks = work.load_peaks(devices[0].device_kind)
    except work.UnknownDevice as e:
        raise SetupError(str(e)) from e
    return devices, peaks


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache where the program keeps it
    (``repro.launch.compile_cache``: ``$JAX_COMPILATION_CACHE_DIR``, else
    ``<checkout>/.jax_cache``), keeping every program, however small."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache as program_cache

    path = program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def start(name: str):
    """The cell, this machine's peaks, the compile listener and cache: what
    every entry point does before it builds anything."""
    import jax

    cell = load_cell(name)
    jax.monitoring.register_event_duration_secs_listener(COMPILES)
    _, peaks = setup_device(cell)
    enable_compile_cache()
    return cell, peaks


def main(argv=None, *, t_start: float) -> int:
    args = parse_args(argv)
    try:
        cell, peaks = start(args.workload)
    except (SetupError, FileNotFoundError, KeyError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    result = run(cell, args.seed, args.seconds, bool(args.trace), t_start=t_start, peaks=peaks)
    print_checks(result)
    print(json.dumps(result))
    return 0
