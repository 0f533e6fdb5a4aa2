"""The general traffic generator: a mix file of sizes and a seed in, requests out.

A mix (``bench/traffic/<mix>.json``) gives the distribution of prompt and
output lengths.  The pool holds the distribution's quantiles at
``(j + 0.5) / n``, shuffled and paired by a fixed stream (:data:`ORDER`),
so every seed gets the same sizes in the same order and a run's work does
not depend on the seed: in a window of fixed length, the order decides how
many tokens of the late requests fall inside it, which would otherwise
move the metrics from seed to seed by more than from run to run.  The seed
draws the prompt tokens.  When a run uses more requests than the pool
holds, the pool is shuffled again and reused.

Size specs::

    {"dist": "lognormal", "median": 192, "sigma": 0.8, "min": 16, "max": 640}
    {"dist": "uniform", "min": 16, "max": 48}
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Dict, List

import numpy as np

#: The fixed stream that orders sizes and arrival gaps, for every seed.
ORDER = 20261016


def quantiles(spec: Dict, n: int) -> List[int]:
    """``n`` lengths at the quantiles ``(j + 0.5) / n`` of ``spec``."""
    lo, hi = int(spec["min"]), int(spec["max"])
    us = [(j + 0.5) / n for j in range(n)]
    kind = spec["dist"]
    if kind == "lognormal":
        norm = NormalDist()
        vals = [float(spec["median"]) * math.exp(float(spec["sigma"]) * norm.inv_cdf(u)) for u in us]
    elif kind == "uniform":
        vals = [lo + math.floor(u * (hi - lo + 1)) for u in us]
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return [min(hi, max(lo, int(round(v)))) for v in vals]


@dataclasses.dataclass
class Item:
    """One request as the generator makes it."""

    index: int
    prompt: np.ndarray  # (plen,) int32 token ids in [1, vocab)
    max_new_tokens: int


class Pool:
    """The seeded stream of requests of one run."""

    def __init__(self, mix: Dict, size: int, vocab: int, seed: int) -> None:
        self.prompts = quantiles(mix["prompt"], size)
        self.outputs = quantiles(mix["output"], size)
        self.vocab = vocab
        self.rng = np.random.default_rng(seed)
        self.order = np.random.default_rng(ORDER)
        self.size = size
        self._order: List[tuple] = []
        self.issued = 0

    def _refill(self) -> None:
        p = self.order.permutation(self.size)
        o = self.order.permutation(self.size)
        self._order = [(self.prompts[i], self.outputs[j]) for i, j in zip(p, o)]

    def next(self) -> Item:
        if self.issued % self.size == 0:
            self._refill()
        plen, out = self._order[self.issued % self.size]
        prompt = self.rng.integers(1, self.vocab, (plen,), dtype=np.int64).astype(np.int32)
        item = Item(self.issued, prompt, out)
        self.issued += 1
        return item
