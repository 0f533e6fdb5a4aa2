"""Open-loop arrivals at a fixed rate: independent users who send on a
schedule whatever the server does.

The schedule is fixed and stratified, not a random realization of a
Poisson process: the gaps are the exponential distribution's quantiles at
``(j + 0.5) / n``, placed in one fixed order (``loadgen.ORDER``), and each
period of ``seconds`` holds exactly ``round(rate · seconds)`` arrivals, the
first at the period's start.  Every seed therefore offers the same arrivals;
the seed draws the tokens.  The cell's load file gives ``{"rate_per_s": r}``.
"""
from __future__ import annotations

import math
from typing import List


class Arrivals:
    closed = False

    def __init__(self, load: dict, seconds: float, rng) -> None:
        rate = float(load["rate_per_s"])
        self.n = max(1, int(round(rate * seconds)))  # arrivals per period
        self.period = float(seconds)
        self.gaps = [-math.log(1.0 - (j + 0.5) / self.n) / rate for j in range(self.n)]
        self.rng = rng
        self.pending: List[float] = []
        self.next_period = 0.0

    def _extend(self) -> None:
        gaps = [self.gaps[i] for i in self.rng.permutation(self.n)]
        total, acc = sum(gaps), 0.0
        for g in gaps:
            self.pending.append(self.next_period + self.period * acc / total)
            acc += g
        self.next_period += self.period

    def start(self, t0: float) -> None:
        self.next_period = t0
        self._extend()

    def due(self, now: float) -> List[float]:
        """Due times of every arrival at or before ``now``, oldest first."""
        out = []
        while True:
            if not self.pending:
                self._extend()
            if self.pending[0] > now:
                return out
            out.append(self.pending.pop(0))

    def next_time(self) -> float:
        if not self.pending:
            self._extend()
        return self.pending[0]

    def done(self, t_done: float) -> None:
        """An open loop takes no notice of completions."""
