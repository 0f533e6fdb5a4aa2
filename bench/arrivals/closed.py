"""Closed-loop arrivals: a fixed pool of clients, each of which sends its
next request when its previous one has completed, after a think time.

The cell's load file gives ``{"clients": n, "think_s": t}``.  All clients
send their first request at the window's start.  A request's due time is
its client's previous completion plus the think time.
"""
from __future__ import annotations

import heapq
from typing import List


class Arrivals:
    closed = True

    def __init__(self, load: dict, seconds: float, rng) -> None:
        self.clients = int(load["clients"])
        self.think = float(load.get("think_s", 0.0))
        self.pending: List[float] = []

    def start(self, t0: float) -> None:
        self.pending = [t0] * self.clients
        heapq.heapify(self.pending)

    def due(self, now: float) -> List[float]:
        out = []
        while self.pending and self.pending[0] <= now:
            out.append(heapq.heappop(self.pending))
        return out

    def next_time(self) -> float:
        return self.pending[0] if self.pending else float("inf")

    def done(self, t_done: float) -> None:
        heapq.heappush(self.pending, t_done + self.think)
