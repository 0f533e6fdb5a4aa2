"""From the profiler's trace to device busy time, kernel time and idle gaps.

``events(path)`` reads an ``.xplane.pb`` into plain lists: the benchmark's
host spans (``bench.*``) and the device operations of every TPU, each with
its start and end in nanoseconds on the trace's one clock.  ``reduce`` turns
those lists into numbers; it is what the tests pin on a recorded trace.

- busy: the union of the intervals in which some operation runs on a
  device, inside the ``bench.window`` span, averaged over the devices used;
- kernel time: the summed durations of the Pallas calls (``tpu_custom_call``)
  whose instruction a family names;
- idle gaps: every stretch of the window in which no operation runs,
  attributed to the innermost benchmark span that covers its midpoint:
  ``bench.prefill``, ``bench.scatter``, ``bench.decode``; inside
  ``bench.step`` but outside those, ``bench.select`` (the engine's token
  choice and bookkeeping); inside the window alone, ``bench.loadgen``.

    python bench/trace_reduce.py <file.xplane.pb>    # what the trace holds
"""
from __future__ import annotations

import bisect
import re
import sys
from collections import defaultdict
from typing import Dict, Iterable, List, Sequence, Tuple

#: Lines of a device plane that summarize rather than run operations.
_SUMMARY_LINES = {"XLA Modules", "Steps", "XLA TraceMe", "Framework Ops", "Framework Name Scope",
                  "Source code", "Launch Stats"}
#: ``%qattention.57 = s8[...] custom-call(...)``: an HLO instruction's name.
_INSTRUCTION = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)?\s*=")
#: Host spans by depth: a deeper one covering a gap names it.
_DEPTH = {"bench.window": 0, "bench.step": 1, "bench.prefill": 2, "bench.scatter": 2, "bench.decode": 2}
_NAME = {"bench.window": "bench.loadgen", "bench.step": "bench.select"}


def _is_device_plane(name: str) -> bool:
    return name.startswith("/device:TPU:") and name[len("/device:TPU:"):].isdigit()


def events(path: str) -> Dict[str, list]:
    """``{"host": [[name, start, end]], "device": {plane: [[name, start,
    end, kind]]}}`` from one ``.xplane.pb``, times in nanoseconds; ``name``
    and ``kind`` as :func:`op_name` gives them."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    host: List[list] = []
    device: Dict[str, List[list]] = {}
    for plane in data.planes:
        if _is_device_plane(plane.name):
            lines = [ln for ln in plane.lines if ln.name == "XLA Ops"]
            if not lines:
                lines = [ln for ln in plane.lines if ln.name not in _SUMMARY_LINES]
            ops = []
            for line in lines:
                for ev in line.events:
                    name, kind = op_name(ev.name)
                    ops.append([name, ev.start_ns, ev.start_ns + ev.duration_ns, kind])
            if ops:
                device[plane.name] = ops
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in _DEPTH:
                        host.append([ev.name, ev.start_ns, ev.start_ns + ev.duration_ns])
    return {"host": host, "device": device}


def _union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


class _Spans:
    """Host spans by depth; spans of one depth do not overlap."""

    def __init__(self, host: Sequence[list]) -> None:
        by_depth: Dict[int, List[list]] = defaultdict(list)
        for h in host:
            by_depth[_DEPTH[h[0]]].append(h)
        self.levels = []
        for depth in sorted(by_depth, reverse=True):
            spans = sorted(by_depth[depth], key=lambda h: h[1])
            self.levels.append(([h[1] for h in spans], spans))

    def activity(self, t: float) -> str:
        """The innermost span's activity at time ``t``."""
        for starts, spans in self.levels:
            i = bisect.bisect_right(starts, t) - 1
            if i >= 0 and spans[i][2] >= t:
                return _NAME.get(spans[i][0], spans[i][0])
        return "bench.loadgen"


def op_name(text: str) -> Tuple[str, str]:
    """(instruction name without its number, ``"tpu_custom_call"`` for a
    Pallas kernel or ``""``) of a device operation's event name, which on a
    TPU is the HLO instruction's text.  A Pallas call's instruction is
    named after the jitted function that wraps it (``qattention``,
    ``qmatmul``, ``qmatmul_packed``): the kernels pass no ``name=``."""
    m = _INSTRUCTION.match(text)
    kind = "tpu_custom_call" if 'custom_call_target="tpu_custom_call"' in text else ""
    return (m.group(1) if m else text[:80]), kind


def kernel_of(name: str, kind: str, kernels: Dict[str, Sequence[str]]) -> str:
    """The family whose Pallas kernel the operation runs, or ``""``."""
    if kind != "tpu_custom_call":
        return ""
    for family, names in kernels.items():
        if name in names:
            return family
    return ""


def reduce(ev: Dict[str, list], kernels: Dict[str, Sequence[str]]) -> Dict[str, object]:
    """Busy and window seconds, seconds per kernel family, and the top-10
    device operations and idle-gap activities by time."""
    windows = [(s, e) for name, s, e in ev["host"] if name == "bench.window"]
    if not windows:
        raise ValueError("the trace holds no bench.window span")
    w0, w1 = windows[0]
    spans = _Spans([h for h in ev["host"] if h[2] >= w0 and h[1] <= w1])
    busy_total = 0.0
    family_s: Dict[str, float] = defaultdict(float)
    op_s: Dict[str, float] = defaultdict(float)
    gap_s: Dict[str, float] = defaultdict(float)
    planes = 0
    for ops in ev["device"].values():
        inside = [(max(s, w0), min(e, w1), n, d) for n, s, e, d in ops if e > w0 and s < w1]
        if not inside:
            continue
        planes += 1
        for s, e, n, d in inside:
            fam = kernel_of(n, d, kernels)
            if fam:
                family_s[fam] += (e - s) * 1e-9
            op_s[n] += (e - s) * 1e-9
        merged = _union((s, e) for s, e, _, _ in inside)
        busy_total += sum(e - s for s, e in merged) * 1e-9
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gap_s[spans.activity((a + b) / 2)] += (b - a) * 1e-9
    top = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:10]]  # noqa: E731
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy_total / planes if planes else 0.0,
        "devices": planes,
        "kernel_s": dict(family_s),
        "device_ops": top(op_s),
        "idle_gaps": top(gap_s),
    }


def _summary(path: str) -> None:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            print(f"  LINE {line.name!r}: {len(evs)} events")
            for ev in evs[:12]:
                stats = {k: (str(v)[:160]) for k, v in ev.stats}
                print(f"    {ev.name!r} start={ev.start_ns} dur={ev.duration_ns} {stats}")


if __name__ == "__main__":
    _summary(sys.argv[1])
