"""Production meshes.

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the ``pod`` axis is pure
data parallelism whose gradient all-reduce crosses DCN (and is therefore the
int8-compression target, repro.optim.grad_compress).

Defined as functions (never module-level constants) so importing this module
never touches jax device state — the dry-run must set XLA_FLAGS before any
device query.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """A mesh whose axes are all ``Auto``: the sharding annotations in this
    package are GSPMD hints, and JAX's default ``Explicit`` axes would make
    every gather and scan over a sharded operand a type error."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for CPU multi-device tests (forced host devices)."""
    return _auto_mesh(shape, axes)


def describe(mesh) -> str:
    return f"mesh{dict(mesh.shape)} over {mesh.devices.size} devices"
