"""The program's own spans in a traced run, on the profiler's clock.

``repro.obs`` times the token path from inside.  With a tracer installed
that carries the profiler hook (``Tracer(annotate=jax.profiler.
TraceAnnotation)``), each of its spans also lands in the profiler's trace as
an annotation of the span's name, beside the device operations.  This
module reads those annotations and puts the device's idle time down to the
innermost program span open at each instant:

- :func:`events`: the program's spans in one ``.xplane.pb``, as ``[name,
  start, end]`` in nanoseconds on the trace's one clock, the list that goes
  beside ``trace_reduce.events``' ``"host"`` and ``"device"`` as
  ``"program"``;
- :func:`idle_by_span`: the window's idle seconds by innermost program span.
  A stretch with no device operation can run through several spans (a
  scatter's fetches and writes, then the next decode's put), so each idle
  instant is put down to the span open then, not each gap to the span at
  its midpoint;
- :func:`readings`: the per-layer readings these spans give, and how much
  of the benchmark's own ``bench.*`` spans and of the idle time they cover;
- :func:`longest`: from the tracer's own records, the longest span of each
  kind, and the path from the longest step down to the part that made it
  long, by each span's ``parent``.

It is also a tool.  ``bench/run.py`` installs no program tracer; this runs
one cell as the harness does, with the program's tracer installed for the
window (``--tracer 1``) and the profiler on (``--profile 1``), and prints the
end-to-end metrics and, profiled, the readings, as one JSON line last::

    python3 bench/program_spans.py --workload <cell> --seed <n> --seconds <s> \\
        [--tracer 0|1] [--profile 0|1]

Run from the root of a checkout on a machine with the chips the cell asks
for.  ``--profile 0`` with ``--tracer 0`` and ``--tracer 1`` on the same
seeds gives what the program's tracing costs end to end.
"""
from __future__ import annotations

import time

T_START = time.monotonic()  # set-up is timed from here

import argparse  # noqa: E402
import bisect  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from collections import defaultdict  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional, Sequence, Tuple  # noqa: E402

import harness  # noqa: E402
import stats  # noqa: E402
import trace_reduce  # noqa: E402

#: The token path's spans (``docs/observability.md``).
NAMES = (
    "engine.step", "engine.admit", "engine.prefill", "engine.scatter", "engine.decode", "engine.select",
    "tokenpath.prefill.mask", "run.pad", "run.execute", "run.slice",
    "tokenpath.scatter.fetch", "tokenpath.scatter.write",
    "tokenpath.decode.put", "tokenpath.decode.dispatch", "tokenpath.decode.fetch",
)
#: The parts of one adapter call: what each ``bench.*`` span should be made of.
PARTS = tuple(n for n in NAMES if not n.startswith("engine."))
#: Spans that are a copy between host and device, or host work on the data
#: of one: the device idles through them unless other work is queued.
HOST_COPIES = (
    "tokenpath.prefill.mask", "run.pad", "run.slice", "tokenpath.scatter.fetch",
    "tokenpath.scatter.write", "tokenpath.decode.put", "tokenpath.decode.fetch",
)
#: The benchmark's own spans around each adapter call.
WRAPPERS = ("bench.prefill", "bench.scatter", "bench.decode")
#: Idle time under no program span.
OUTSIDE = "outside"

Span = Tuple[str, float, float]


def events(path: str) -> List[list]:
    """``[name, start, end]`` of every program span in one ``.xplane.pb``."""
    import jax

    names = set(NAMES)
    out: List[list] = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in names:
                        out.append([ev.name, ev.start_ns, ev.start_ns + ev.duration_ns])
    return out


def innermost(spans: Sequence[Span]) -> List[Span]:
    """The time under ``spans`` cut into ``(start, end, name)`` pieces, in
    order, each under the innermost span open then.  One thread's spans nest;
    a child that outlasts its parent is cut at the parent's end."""
    out: List[Span] = []
    stack: List[list] = []
    t = -math.inf

    def close(until: float) -> None:
        nonlocal t
        while stack and stack[-1][2] <= until:
            name, _, end = stack.pop()
            if end > t:
                out.append((t, end, name))
                t = end

    for name, s, e in sorted(spans, key=lambda h: (h[1], -h[2])):
        close(s)
        if stack:
            if s > t:
                out.append((t, s, stack[-1][0]))
            e = min(e, stack[-1][2])
        stack.append([name, s, e])
        t = s
    close(math.inf)
    return out


def _overlap(a: Sequence[Tuple[float, float]], b: Sequence[Span]) -> Dict[str, float]:
    """Time shared by two ordered lists of disjoint intervals, by ``b``'s names."""
    out: Dict[str, float] = defaultdict(float)
    i = j = 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out[b[j][2]] += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _window(ev: dict) -> Tuple[float, float]:
    windows = [(s, e) for name, s, e in ev["host"] if name == "bench.window"]
    if not windows:
        raise ValueError("the trace holds no bench.window span")
    return windows[0]


def _idle(ops: Sequence[list], w0: float, w1: float) -> List[Tuple[float, float]]:
    """The stretches of ``[w0, w1]`` in which none of ``ops`` runs."""
    busy = trace_reduce._union((max(s, w0), min(e, w1)) for _, s, e, _ in ops if e > w0 and s < w1)
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    return [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]


def idle_by_span(ev: dict) -> Dict[str, float]:
    """Seconds of the window in which the device is idle, by the innermost
    program span open then (:data:`OUTSIDE` where none is), averaged over the
    devices.  A trace with no device plane counts as one idle device."""
    w0, w1 = _window(ev)
    pieces = innermost([(n, max(s, w0), min(e, w1)) for n, s, e in ev["program"] if e > w0 and s < w1])
    planes = list(ev["device"].values()) or [[]]
    out: Dict[str, float] = defaultdict(float)
    for ops in planes:
        gaps = _idle(ops, w0, w1)
        by_name = _overlap(gaps, pieces)
        for name, ns in by_name.items():
            out[name] += ns * 1e-9 / len(planes)
        out[OUTSIDE] += (sum(b - a for a, b in gaps) - sum(by_name.values())) * 1e-9 / len(planes)
    return dict(out)


def _inside(spans: Sequence[list], w0: float, w1: float, name: str) -> List[list]:
    return sorted((h for h in spans if h[0] == name and w0 <= h[1] < w1), key=lambda h: h[1])


def _children(parents: Sequence[list], children: Sequence[list]) -> List[List[list]]:
    """For each parent span, the child spans that start inside it."""
    starts = [c[1] for c in children]
    return [children[bisect.bisect_left(starts, s):bisect.bisect_right(starts, e)] for _, s, e in parents]


def _mean_ms(durations_ns: Sequence[float]) -> Optional[float]:
    return 1e-6 * sum(durations_ns) / len(durations_ns) if durations_ns else None


def queue_wait_p90_ms(win) -> Optional[float]:
    """Nearest-rank p90, over the requests due in the window, of admission
    minus submission (``Request.t_admit - t_submit``).  A request never
    admitted counts as beyond any value; where the p90 falls on one, the
    least it can be: the wait from the earliest such submission to the end
    of serving.  ``None`` where the program stamps no admission."""
    due = stats.due(win)
    if not due or not hasattr(due[0].req, "t_admit"):
        return None
    waits = [(r.req.t_admit - r.req.t_submit) if r.req.t_admit is not None else math.inf for r in due]
    p = stats.percentile(waits, 90)
    if math.isinf(p):
        p = max(win.t_closed, win.t_end) - min(r.req.t_submit for r in due if r.req.t_admit is None)
    return 1e3 * p


def readings(ev: dict, win) -> dict:
    """The readings of one traced window: ``ev`` holds ``"host"``,
    ``"device"`` and ``"program"`` (:func:`events`), ``win`` is the
    ``harness.Window``.

    - ``metrics``: ``scatter_fetch_ms``, the mean per admission of its summed
      ``tokenpath.scatter.fetch`` spans; ``cache_put_ms``, the mean
      ``tokenpath.decode.put`` span; ``prefill_fetch_ms``, the mean
      ``run.slice`` span inside an ``engine.prefill``; ``queue_wait_p90_ms``;
      ``host_copy_idle``, the share of the window (%) in which the device is
      idle under one of :data:`HOST_COPIES`;
    - ``idle_s``: :func:`idle_by_span`, by seconds, largest first;
    - ``cover``: the share of each ``bench.*`` wrapper's time that the
      :data:`PARTS` spans cover, and of the idle time that any program span
      and that the parts or ``engine.select`` cover.
    """
    w0, w1 = _window(ev)
    window_s = (w1 - w0) * 1e-9
    prog = ev["program"]
    idle = idle_by_span(ev)
    scatters = _children(_inside(prog, w0, w1, "engine.scatter"), _inside(prog, w0, w1, "tokenpath.scatter.fetch"))
    slices = _children(_inside(prog, w0, w1, "engine.prefill"), _inside(prog, w0, w1, "run.slice"))
    metrics = {
        "scatter_fetch_ms": _mean_ms([sum(e - s for _, s, e in kids) for kids in scatters]),
        "cache_put_ms": _mean_ms([e - s for _, s, e in _inside(prog, w0, w1, "tokenpath.decode.put")]),
        "prefill_fetch_ms": _mean_ms([e - s for kids in slices for _, s, e in kids]),
        "queue_wait_p90_ms": queue_wait_p90_ms(win),
        "host_copy_idle": 100.0 * sum(idle.get(n, 0.0) for n in HOST_COPIES) / window_s if window_s > 0 else None,
    }
    parts = [(s, e) for n, s, e in prog if n in PARTS]
    part_union = [(s, e, "part") for s, e in trace_reduce._union(parts)]
    cover = {}
    for name in WRAPPERS:
        spans = [(s, e) for n, s, e in ev["host"] if n == name and w0 <= s < w1]
        total = sum(e - s for s, e in spans)
        if total > 0:
            cover[name] = _overlap(sorted(spans), part_union).get("part", 0.0) / total
    idle_total = sum(idle.values())
    if idle_total > 0:
        cover["idle"] = 1.0 - idle.get(OUTSIDE, 0.0) / idle_total
        cover["idle_in_parts"] = sum(v for n, v in idle.items() if n in PARTS or n == "engine.select") / idle_total
    return {"metrics": metrics, "idle_s": dict(sorted(idle.items(), key=lambda kv: -kv[1])), "cover": cover}


def longest(records) -> dict:
    """From a tracer's records: ``longest_ms``, the longest span of each
    name, and ``longest_step``, ``[name, ms]`` from the longest top-level
    span down through the longest child at each level."""
    spans = [r for r in records if r.kind == "span"]
    by_name: Dict[str, float] = {}
    kids: Dict[Optional[int], list] = defaultdict(list)
    for r in spans:
        by_name[r.name] = max(by_name.get(r.name, 0.0), 1e3 * r.dur)
        kids[r.parent].append(r)
    chain, level = [], kids[None]
    while level:
        top = max(level, key=lambda r: r.dur)
        chain.append([top.name, 1e3 * top.dur])
        level = kids[top.sid]
    return {"longest_ms": dict(sorted(by_name.items(), key=lambda kv: -kv[1])), "longest_step": chain}


# ---------------------------------------------------------------------------
# the tool: one cell with the program's tracer installed
# ---------------------------------------------------------------------------

def _profile(on: bool):
    """Start the profiler where ``on``, as the harness does; returns a
    function that stops it and returns the window's events, the program's
    spans among them (``None`` unprofiled)."""
    import jax

    if not on:
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
            ev = trace_reduce.events(str(files[-1]))
            ev["program"] = events(str(files[-1]))
            return ev
        finally:
            shutil.rmtree(tracedir, ignore_errors=True)

    return stop


def run(cell, seed: int, seconds: float, *, tracer: bool, profile: bool, t_start: float,
        peaks: Dict[str, float], log=print) -> dict:
    """Serve one window as ``harness.run`` does, with the program's tracer
    installed where ``tracer``: the end-to-end metrics; traced, the
    :func:`longest` spans of the window and its drain; profiled, the
    window's :func:`readings` beside the busy time and the idle time by
    benchmark span that ``trace_reduce.reduce`` gives."""
    import jax

    from repro.obs import trace as program_trace

    devices = jax.devices()[: cell.chips]
    _, _, served = harness.build(cell, seed)
    path = cell.path()
    arrivals = harness.arrivals_for(cell, seconds)
    pool = harness.pool_for(cell, arrivals, seed)
    setup_s = time.monotonic() - t_start
    stop = _profile(profile)
    if tracer:
        program_trace.install(program_trace.Tracer(annotate=jax.profiler.TraceAnnotation))
    try:
        win = harness.serve(served, arrivals, pool, seconds)
    finally:
        installed = program_trace.uninstall() if tracer else None
    ev = stop()
    calls = [c for c in served.calls if win.t0 <= c[1] < win.t_closed]
    compiles, harness.COMPILES.count = harness.COMPILES.count, 0
    data = harness.RunData(
        cell=cell, seconds=seconds, setup_s=setup_s, window=win, calls=calls,
        counters=served.counters(), compiles=compiles, device=harness.device_info(devices),
        peaks=peaks, work=path.work(calls, cell.config, peaks), events=ev,
    )
    log(harness.diagnostics(data))
    served.close()
    result = {"tracer": tracer, "profile": profile, "end_to_end": {}}
    for m in cell.end_to_end:
        value = cell.module("metrics", m["name"]).read(data)
        if value is not None:
            result["end_to_end"][m["name"]] = float(value)
    if installed is not None:
        result.update(longest(installed.records))
    if ev is not None:
        bench = trace_reduce.reduce(ev, path.KERNELS)
        result.update(readings(ev, win))
        result["busy_s"], result["window_s"] = bench["busy_s"], bench["window_s"]
        result["bench_idle_s"] = dict(bench["idle_gaps"])
    return result


def main(argv=None, *, t_start: float) -> int:
    ap = argparse.ArgumentParser(description="Run one cell with the program's tracer installed.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--tracer", type=int, choices=(0, 1), default=1)
    ap.add_argument("--profile", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    try:
        cell, peaks = harness.start(args.workload)
    except (harness.SetupError, FileNotFoundError, KeyError) as e:
        print(f"program_spans: {e}", file=sys.stderr)
        return 2
    result = run(cell, args.seed, args.seconds, tracer=bool(args.tracer), profile=bool(args.profile),
                 t_start=t_start, peaks=peaks)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))
    sys.exit(main(sys.argv[1:], t_start=T_START))
