"""Fused pre-quantized matmul Pallas TPU kernel.

One kernel realizes the paper's entire Fig.1/2 pattern:

    MatMulInteger (int8×int8 → int32, on the MXU)
      → Add int32 bias
      → Cast f32 → Mul quant_scale → Mul quant_shift   (§3.1 integer rescale)
      → optional ReLU
      → QuantizeLinear(scale=1, zp=0)                   (round-half-even + clip)

TPU mapping (DESIGN.md §3): the int8×int8→int32 product drives the MXU at its
double-rate int8 throughput; the rescale epilogue runs on the VPU over the
int32 accumulator while it is still resident in VMEM — the Cast/Mul/Mul/QL
chain of the artifact never round-trips to HBM.  Grid is (M/bm, N/bn, K/bk)
with a VMEM int32 accumulator scratch carried across the k dimension
(innermost, sequential on TPU).

Tile constraints: int8 operands want (32, 128)-aligned tiles, the int32
accumulator (8, 128); the default 128/256/128 blocks satisfy both and keep the
MXU busy (128×128 systolic array).  Shape padding is handled by
:mod:`repro.kernels.ops`, zero padding being exact for integer matmul.

Bit-exactness: the epilogue performs the *same f32 operations in the same
order* as the ONNX-dialect ops, so results match the reference runtime
bit-for-bit (asserted over shape/dtype sweeps in tests/test_kernels_qmatmul.py).

The packed-int4 variant (:func:`qmatmul_packed`) streams weights 2-per-byte
from HBM and unpacks per tile on the VPU before the same MXU product —
halving weight traffic for the bandwidth-bound decode path (see
docs/quantization.md and tests/test_int4.py for the bit-exactness pin).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Default MXU-aligned tile sizes.
BM, BK, BN = 128, 256, 128

# Minimum tile granularity: int8 operands want (32, 128)-aligned tiles and the
# int32 accumulator (8, 128) — 32-multiple sublanes × 128-lane last dims
# satisfy both.  Public: the autotuner's candidate lattice is built from these.
MIN_SUBLANE, MIN_LANE = 32, 128
_MIN_SUBLANE, _MIN_LANE = MIN_SUBLANE, MIN_LANE


def tile_aligned(bm: int, bk: int, bn: int) -> bool:
    """True iff (bm, bk, bn) satisfies the kernel's tile constraints: positive
    blocks, bm a 32-multiple (int8 sublane minimum, which also covers the
    int32 accumulator's 8), bk and bn 128-lane multiples."""
    return (
        min(bm, bk, bn) > 0
        and bm % MIN_SUBLANE == 0
        and bk % MIN_LANE == 0
        and bn % MIN_LANE == 0
    )


def _ceil_to(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def choose_bm(m, *, bm: int = BM) -> int:
    """Per-batch-bucket tile choice for the M dimension.

    The K/N tiles are a property of the *weights* (fixed at template-build
    time); ``bm`` is the one tile that depends on the batch, so it is the
    piece re-chosen per bucket by the batch-polymorphic specialization:
    a bucket of 1 runs with bm=32 (the int8 sublane minimum) instead of
    padding 1→128.  ``m`` may be None/0 (unknown batch) — the default
    ``bm`` then stands."""
    return min(bm, _ceil_to(int(m), _MIN_SUBLANE)) if m else bm


def choose_tiles(m, k: int, n: int, *, bm: int = BM, bk: int = BK, bn: int = BN):
    """Pick (bm, bk, bn) for a *static* problem shape at plan time.

    Shrinks the default blocks toward the (hardware-minimum-aligned) problem
    size so small layers don't pad 33→256; ``m`` may be None when the batch
    dimension is dynamic, in which case the default ``bm`` stands (see
    :func:`choose_bm` for the per-bucket M choice)."""
    bk_ = min(bk, _ceil_to(int(k), _MIN_LANE))
    bn_ = min(bn, _ceil_to(int(n), _MIN_LANE))
    return choose_bm(m, bm=bm), bk_, bn_


def _epilogue(acc, bias, qscale, qshift, *, relu: bool, two_mul: bool, out_dtype):
    """The artifact's rescale chain, op-for-op (order matters for bit-exactness)."""
    acc = acc + bias  # int32 + int32
    f = acc.astype(jnp.float32)
    f = f * qscale
    if two_mul:
        f = f * qshift
    if relu:
        f = jnp.maximum(f, 0.0)
    r = jnp.rint(f)  # round half to even, as ONNX QuantizeLinear
    info = jnp.iinfo(out_dtype)
    return jnp.clip(r, info.min, info.max).astype(out_dtype)


def _qmatmul_kernel(x_ref, w_ref, b_ref, qs_ref, qsh_ref, o_ref, acc_ref, *, relu, two_mul, out_dtype):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # int8 × int8 → int32 on the MXU.
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...],
        w_ref[...],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(k == pl.num_programs(2) - 1)
    def _finish():
        o_ref[...] = _epilogue(
            acc_ref[...], b_ref[...], qs_ref[...], qsh_ref[...],
            relu=relu, two_mul=two_mul, out_dtype=out_dtype,
        )


def _unpack_int4_rows(p):
    """(rows, bn) uint8 nibble-pairs → (2·rows, bn) int8, K-interleaved.

    Mirrors :func:`repro.kernels.pack.unpack_int4` with pure VPU shift
    arithmetic on 32-bit lanes (Mosaic has no 8-bit shifts): the low nibble
    sign-extends via ``(p << 28) >> 28``, the high nibble via
    ``(p << 24) >> 28`` (the arithmetic right shift carries the sign).  The
    stack-reshape interleaves along the sublane axis only — the 128-lane
    layout is untouched."""
    w = p.astype(jnp.int32)
    lo = (w << 28) >> 28
    hi = (w << 24) >> 28
    return jnp.stack([lo, hi], axis=1).reshape(2 * p.shape[0], p.shape[1]).astype(jnp.int8)


def _qmatmul_packed_kernel(x_ref, wp_ref, b_ref, qs_ref, qsh_ref, o_ref, acc_ref, *, relu, two_mul, out_dtype):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Unpack the (bk//2, bn) packed tile to (bk, bn) int8 in VMEM, then the
    # same int8 MXU product as the unpacked kernel — HBM only ever streamed
    # half the weight bytes.
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...],
        _unpack_int4_rows(wp_ref[...]),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(k == pl.num_programs(2) - 1)
    def _finish():
        o_ref[...] = _epilogue(
            acc_ref[...], b_ref[...], qs_ref[...], qsh_ref[...],
            relu=relu, two_mul=two_mul, out_dtype=out_dtype,
        )


@functools.partial(
    jax.jit,
    static_argnames=("out_dtype", "relu", "two_mul", "bm", "bk", "bn", "interpret"),
)
def qmatmul_packed(
    x_q: jax.Array,  # (M, K) int8
    w_p: jax.Array,  # (K // 2, N) uint8 — int4 nibble pairs along K
    bias_q: jax.Array,  # (1, N) int32
    quant_scale: jax.Array,  # (1, N) f32
    quant_shift: jax.Array,  # (1, N) f32
    *,
    out_dtype=jnp.int8,
    relu: bool = False,
    two_mul: bool = True,
    bm: int = BM,
    bk: int = BK,
    bn: int = BN,
    interpret: bool = False,
) -> jax.Array:
    """Packed-int4 variant of :func:`qmatmul`: weights arrive 2-per-byte
    (packed once at plan time by :func:`repro.kernels.pack.pack_int4`) and
    are unpacked per (bk, bn) tile inside the kernel.  Same grid, same
    epilogue, bit-exact with the unpacked kernel on int4-range weights."""
    m, k = x_q.shape
    kp2, n = w_p.shape
    assert k == 2 * kp2, (x_q.shape, w_p.shape)
    assert m % bm == 0 and k % bk == 0 and n % bn == 0, (m, k, n, bm, bk, bn)
    assert bk % 2 == 0, bk

    kernel = functools.partial(_qmatmul_packed_kernel, relu=relu, two_mul=two_mul, out_dtype=out_dtype)
    return pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn, k // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk // 2, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(x_q, w_p, bias_q, quant_scale, quant_shift)


@functools.partial(
    jax.jit,
    static_argnames=("out_dtype", "relu", "two_mul", "bm", "bk", "bn", "interpret"),
)
def qmatmul(
    x_q: jax.Array,  # (M, K) int8
    w_q: jax.Array,  # (K, N) int8
    bias_q: jax.Array,  # (1, N) int32
    quant_scale: jax.Array,  # (1, N) f32 — integer values stored as FLOAT
    quant_shift: jax.Array,  # (1, N) f32 — 2**-N
    *,
    out_dtype=jnp.int8,
    relu: bool = False,
    two_mul: bool = True,
    bm: int = BM,
    bk: int = BK,
    bn: int = BN,
    interpret: bool = False,
) -> jax.Array:
    """Fused pre-quantized matmul.  All dims must already be tile-multiples
    (see :func:`repro.kernels.ops.quantized_matmul` for the padded wrapper)."""
    m, k = x_q.shape
    k2, n = w_q.shape
    assert k == k2, (x_q.shape, w_q.shape)
    assert m % bm == 0 and k % bk == 0 and n % bn == 0, (m, k, n, bm, bk, bn)

    kernel = functools.partial(_qmatmul_kernel, relu=relu, two_mul=two_mul, out_dtype=out_dtype)
    return pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn, k // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(x_q, w_q, bias_q, quant_scale, quant_shift)
