"""int8 activation as an exact 256-entry lookup table (Pallas TPU kernel).

TPU-native adaptation of the paper's §6 activation flows (DESIGN.md §3): the
artifact codifies ``QuantizeLinear → DequantizeLinear → [Cast f16] →
Tanh/Sigmoid → [Cast f32] → QuantizeLinear``.  Because the chain's input is
int8, it is a pure function of 256 possible values — the compiler evaluates
the chain once with *reference-runtime semantics* (including the fp16 casts of
Figs 5/6) into a 256-entry table, making the kernel bit-exact against the
reference interpreter by construction while eliminating all transcendental
work on-chip.

Mosaic lowers no 1-D gather, so the lookup (:func:`lut_lookup`, shared with
the fused attention kernel) is a compare-and-select chain over the table,
held 2-D as ``(1, 256)`` int32 in SMEM: each step broadcasts one scalar
entry into the lanes whose index matches it.  It is exact for any int8 or
uint8 table, since every value passes through int32 untouched.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .qmatmul import MIN_LANE, _ceil_to, choose_bm

LUT_SIZE = 256

#: Default (rows, lanes) block of the activation kernel.
BM, BN = 256, 1024


def build_lut(fn, in_scale: float, out_scale: float, out_dtype: str = "int8", compute_dtype: str = "float32") -> np.ndarray:
    """Evaluate DQL→[cast]→fn→[cast]→QL over all 256 int8 codes with numpy
    reference semantics.  ``fn`` maps a float array to a float array."""
    codes = np.arange(-128, 128, dtype=np.int32)
    x = codes.astype(np.float32) * np.float32(in_scale)
    if compute_dtype == "float16":
        y = fn(x.astype(np.float16)).astype(np.float16).astype(np.float32)
    else:
        y = fn(x.astype(np.float32)).astype(np.float32)
    q = np.rint(y / np.float32(out_scale))
    info = np.iinfo(out_dtype)
    return np.clip(q, info.min, info.max).astype(out_dtype)


def lut_table(lut: jax.Array) -> jax.Array:
    """A ``(256,)`` int8/uint8 table as the ``(1, 256)`` int32 operand that
    :func:`lut_lookup` reads."""
    return jnp.asarray(lut).astype(jnp.int32).reshape(1, LUT_SIZE)


#: BlockSpec of the table operand: the whole ``(1, 256)`` array in SMEM.
LUT_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)


def lut_lookup(idx: jax.Array, lut_ref) -> jax.Array:
    """``lut[idx]`` for int32 ``idx`` in [0, 256), inside a kernel: a
    compare-and-select chain over the SMEM table ref (see :data:`LUT_SPEC`).
    Returns int32; every index matches exactly one step."""

    def step(c, acc):
        return jnp.where(idx == c, lut_ref[0, c], acc)

    return jax.lax.fori_loop(0, LUT_SIZE, step, jnp.zeros_like(idx))


def _lut_kernel(x_ref, lut_ref, o_ref):
    idx = x_ref[...].astype(jnp.int32) + 128
    o_ref[...] = lut_lookup(idx, lut_ref).astype(o_ref.dtype)


def choose_blocks(m: int, n: int, *, bm: int = BM, bn: int = BN):
    """(bm, bn) for an ``(m, n)`` activation: rows shrink toward ``m`` in
    sublane steps, lanes take the largest 128-multiple up to ``bn`` that
    divides ``n`` padded to 128.  The caller pads to the blocks."""
    return choose_bm(max(m, 1), bm=bm), int(np.gcd(_ceil_to(n, MIN_LANE), bn))


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def qact_lut(
    x_q: jax.Array,  # (M, N) int8, M % bm == 0, N % bn == 0
    lut: jax.Array,  # (256,) int8/uint8
    *,
    bm: int = BM,
    bn: int = BN,
    interpret: bool = False,
) -> jax.Array:
    """Apply an int8→int8/uint8 LUT activation over ``(bm, bn)`` blocks
    (:func:`repro.kernels.ops.quantized_activation` pads to them)."""
    m, n = x_q.shape
    assert m % bm == 0 and n % bn == 0, (m, n, bm, bn)
    return pl.pallas_call(
        _lut_kernel,
        grid=(m // bm, n // bn),
        in_specs=[pl.BlockSpec((bm, bn), lambda i, j: (i, j)), LUT_SPEC],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), lut.dtype),
        interpret=interpret,
    )(x_q, lut_table(lut))
