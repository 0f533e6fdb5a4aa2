"""Statistics of a served window, shared by the metric readers.

A window (``harness.Window``) holds one record per request offered: its due
time and the time each of its output tokens was stamped.  The end-to-end
metrics cover ``[t0, t_end)``: the requests due in it, and the tokens and
token gaps that end in it.
"""
from __future__ import annotations

import math
from typing import List


def percentile(values: List[float], q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q``% of
    the values at or below it (``nan`` of no values)."""
    if not values:
        return float("nan")
    s = sorted(values)
    return s[max(0, math.ceil(q / 100.0 * len(s)) - 1)]


def due(win) -> list:
    """The records of every request due inside the window."""
    return [r for r in win.records if win.t0 <= r.due < win.t_end]


def ttft(win) -> List[float]:
    """First token minus due time, in seconds, of every request due inside
    the window; ``inf`` for a request that never got a first token."""
    return [(r.times[0] - r.due) if r.times else math.inf for r in due(win)]


def ttft_percentile(win, q: float) -> float:
    """The ``q``-th percentile of :func:`ttft`.  Where it falls on a request
    with no first token, the least it can be: the wait from the earliest such
    request's due time until the run stopped serving."""
    p = percentile(ttft(win), q)
    if math.isinf(p):
        p = max(win.t_closed, win.t_end) - min(r.due for r in due(win) if not r.times)
    return p


def tokens(win) -> int:
    """Output tokens stamped inside the window."""
    return sum(1 for r in win.records for t in r.times if win.t0 <= t <= win.t_end)


def token_gaps(win) -> List[float]:
    """Every gap between consecutive tokens of a request whose later token
    falls inside the window, in seconds."""
    return [b - a for r in win.records for a, b in zip(r.times, r.times[1:])
            if win.t0 <= b <= win.t_end]


def lateness(win) -> List[float]:
    """Submission minus due time of the requests due inside the window: how
    late the generator offered them (the engine is synchronous, so an
    arrival that falls due during a step waits for it)."""
    return [r.submitted - r.due for r in due(win)]
