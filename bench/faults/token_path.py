"""Faults a cell served by the token path can have, planted under the timed
path: each wraps ``CompiledTokenAdapter.decode``, the call that every
decode step of the window goes through.

- ``state_unchanged``: the step returns the K/V cache it was given, so no
  new row is ever written;
- ``half_batch``: the odd slots' rows are never computed (each takes its
  even neighbour's logits);
- ``token_altered``: every third step, slot 0's token is changed where it is
  made (its least logit raised above the best).

``plant(name)`` applies one and returns the function that takes it out.
"""
from __future__ import annotations

import numpy as np

from repro.serving.token_path import CompiledTokenAdapter


def _state_unchanged(decode):
    def broken(self, toks, pos, cache):
        logits, _ = decode(self, toks, pos, cache)
        return logits, cache
    return broken


def _half_batch(decode):
    def broken(self, toks, pos, cache):
        logits, nxt = decode(self, toks, pos, cache)
        logits = np.array(logits)
        logits[1::2] = logits[0::2][: logits[1::2].shape[0]]
        return logits, nxt
    return broken


def _token_altered(decode):
    calls = {"n": 0}

    def broken(self, toks, pos, cache):
        logits, nxt = decode(self, toks, pos, cache)
        calls["n"] += 1
        if calls["n"] % 3 == 0:
            logits = np.array(logits)
            logits[0, int(np.argmin(logits[0]))] = logits[0].max() + 1.0
        return logits, nxt
    return broken


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch, "token_altered": _token_altered}


def plant(name: str):
    original = CompiledTokenAdapter.decode
    CompiledTokenAdapter.decode = FAULTS[name](original)
    return lambda: setattr(CompiledTokenAdapter, "decode", original)
