"""Faults a cell served by the sparse-expert token path can have, planted
under the timed path: ``token_path``'s three (each wraps
``CompiledTokenAdapter.decode``), and one of the routed experts:

- ``expert_dropped``: the grouped expert kernel leaves out each row's last
  chosen expert, as a capacity limit that drops rows would (its weight is
  zeroed before the kernel; planted before the path is built, since the
  kernel is traced into every program).

``plant(name)`` applies one and returns the function that takes it out.
"""
from __future__ import annotations

from pathlib import Path

import jax

from repro.backend import registry

import harness  # bench/harness.py, on the path the harness sets

_base = harness.module("faults", "token_path", Path(__file__).resolve().parents[1])


def _expert_dropped(impl):
    def broken(step, args):
        x, idx, probs = args
        drop = jax.nn.one_hot(idx[..., -1], probs.shape[-1], dtype=probs.dtype)
        return impl(step, [x, idx, probs * (1.0 - drop)])
    return broken


def plant(name: str):
    if name in _base.FAULTS:
        return _base.plant(name)
    if name != "expert_dropped":
        raise KeyError(f"no fault {name!r}")
    keys = [(b, "qmoe") for b in registry.backends_for("qmoe")]
    originals = {k: registry._REGISTRY[k] for k in keys}
    for k, impl in originals.items():
        registry._REGISTRY[k] = _expert_dropped(impl)
    return lambda: registry._REGISTRY.update(originals)


FAULTS = {**_base.FAULTS, "expert_dropped": _expert_dropped}
