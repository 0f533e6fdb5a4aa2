"""Grouped int8 routed-expert Pallas TPU kernel.

One kernel realizes the routed-expert region the token path codifies for a
sparse-expert block (see ``repro.core.patterns.emit_moe_experts``): each
token row goes to the ``K`` experts its router chose, every expert is a
SwiGLU MLP with w8 gate/up and w8 or packed-int4 down projections, and each
row's expert outputs are weighted by the router's probabilities and summed
in fixed point::

    g, u = round(x·Wg[e] · r_g), round(x·Wu[e] · r_u)       (int8 codes)
    h    = round(silu(g · s_g) · u · r_h)                    (int8 code)
    out[row] += rint(clip(h·Wd[e] · r_d · p[row, e] · 256))  (int32)

TPU mapping: the ``T·K`` (row, expert) assignments are sorted by expert and
each expert's rows are laid out in whole ``bm``-row tiles.  The grid walks
those tiles, ``(tiles, F / bf)``, with the F blocks innermost; scalar
prefetch hands every tile its expert, so the weight blocks' index maps pick
that expert's slab.  Consecutive tiles of one expert keep the same weight
blocks, so the pipeline fetches each hit expert's weights once; an expert
that no row chose owns no tile, so its weights are never read and no work
is done for it.  The grid has a static upper bound of tiles; the tiles past
the real ones repeat the last real tile's block indices (nothing is
fetched) and skip their body.  No row is dropped: there is no capacity.

Bit-exactness: every step is an integer matmul or an f32 elementwise op in
the artifact's codified order (:func:`repro.kernels.ref.swiglu_ref`,
:func:`repro.kernels.ref.combine_ref`), and the per-row sum over experts is
int32, so the kernel equals the dense semantic form
(:func:`repro.kernels.ref.qmoe_ref`) bit for bit in interpret mode.  On the
chip the SiLU's sigmoid is Mosaic's, not XLA's.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .qmatmul import MIN_LANE, MIN_SUBLANE, _ceil_to, _unpack_int4_rows
from .ref import combine_ref, swiglu_ref

#: Largest row tile.
BM = 128
#: Weight bytes one grid step may stream (gate + up + down blocks); the
#: pipeline holds two of each.
BLOCK_BYTES = 8 << 20
#: Scoped VMEM the kernel asks for: two buffers of the weight blocks plus the
#: row tiles, the accumulator and the SwiGLU temporaries.
VMEM_LIMIT = 64 << 20


def choose_bm(assignments: int, experts: int, *, bm: int = BM) -> int:
    """Row tile: the mean rows per expert, in sublane steps, at least 32 and
    at most ``bm`` (decode routes a few rows to each expert)."""
    mean = -(-assignments // max(experts, 1))
    return max(MIN_SUBLANE, min(bm, _ceil_to(mean, MIN_SUBLANE)))


def choose_bf(dp: int, fp: int, down_bits: int) -> int:
    """F block: the largest 128-multiple dividing ``fp`` whose gate, up and
    down blocks stream at most :data:`BLOCK_BYTES` per step."""
    per_lane = 2 * dp + dp * down_bits // 8
    best = MIN_LANE
    for bf in range(MIN_LANE, fp + 1, MIN_LANE):
        if fp % bf == 0 and bf * per_lane <= BLOCK_BYTES:
            best = bf
    return best


def group_layout(idx: jax.Array, experts: int, bm: int):
    """Sort the ``(row, k)`` assignments of ``idx (T, K)`` by expert and lay
    each expert's rows out in whole ``bm``-row tiles.

    Returns ``(slot, tile_expert, tile_src, tiles)``: the padded row each
    assignment lands on ``(T·K,)``; per tile (static upper bound) its expert
    and the tile whose blocks it reads (itself if real, else the last real
    tile); and the number of real tiles."""
    flat = idx.reshape(-1).astype(jnp.int32)
    a = flat.shape[0]
    counts = jnp.bincount(flat, length=experts).astype(jnp.int32)
    padded = (counts + bm - 1) // bm * bm
    pstart = jnp.cumsum(padded) - padded  # each expert's first padded row
    start = jnp.cumsum(counts) - counts  # its first row in sorted order
    order = jnp.argsort(flat, stable=True)
    ranked = jnp.zeros((a,), jnp.int32).at[order].set(jnp.arange(a, dtype=jnp.int32))
    slot = pstart[flat] + ranked - start[flat]
    tiles = jnp.sum(padded) // bm
    max_tiles = -(-a // bm) + min(experts, a)
    t = jnp.arange(max_tiles, dtype=jnp.int32)
    src = jnp.minimum(t, tiles - 1)
    tile_expert = jnp.searchsorted(jnp.cumsum(padded), src * bm, side="right").astype(jnp.int32)
    return slot, tile_expert, src.astype(jnp.int32), tiles.astype(jnp.int32)


def _qmoe_kernel(
    te_ref, src_ref, n_ref,  # scalar prefetch
    x_ref, p_ref, wg_ref, wu_ref, wd_ref, o_ref, acc_ref,
    *, r_g, s_g, r_u, r_h, r_d, down_bits,
):
    t, j = pl.program_id(0), pl.program_id(1)

    @pl.when(t < n_ref[0])
    def _body():
        @pl.when(j == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        x = x_ref[...]
        dims = (((1,), (0,)), ((), ()))
        g = jax.lax.dot_general(x, wg_ref[0], dims, preferred_element_type=jnp.int32)
        u = jax.lax.dot_general(x, wu_ref[0], dims, preferred_element_type=jnp.int32)
        h = swiglu_ref(g, u, r_g, s_g, r_u, r_h)
        wd = _unpack_int4_rows(wd_ref[0]) if down_bits == 4 else wd_ref[0]
        acc_ref[...] += jax.lax.dot_general(h, wd, dims, preferred_element_type=jnp.int32)

        @pl.when(j == pl.num_programs(1) - 1)
        def _finish():
            o_ref[...] = combine_ref(acc_ref[...], p_ref[...], r_d)


@functools.partial(
    jax.jit,
    static_argnames=("d", "r_g", "s_g", "r_u", "r_h", "r_d", "down_bits", "bm", "bf", "interpret"),
)
def qmoe(
    x_q: jax.Array,  # (T, D) int8 rows
    idx: jax.Array,  # (T, K) chosen experts
    probs: jax.Array,  # (T, E) f32 router softmax
    w_gate: jax.Array,  # (E, Dp, Fp) int8, zero-padded
    w_up: jax.Array,  # (E, Dp, Fp) int8, zero-padded
    w_down: jax.Array,  # (E, Fp // 2, Dp) uint8 packed int4, or (E, Fp, Dp) int8
    *,
    d: int,
    r_g: float,
    s_g: float,
    r_u: float,
    r_h: float,
    r_d: float,
    down_bits: int = 4,
    bm: int = 0,
    bf: int = 0,
    interpret: bool = False,
) -> jax.Array:
    """The routed experts of ``T`` rows, summed per row: ``(T, d)`` int32 in
    ``1 / MOE_FIXED`` code units.  ``bm``/``bf`` of 0 are chosen here."""
    t_rows, k = idx.shape
    e, dp, fp = w_gate.shape
    a = t_rows * k
    bm = bm or choose_bm(a, e)
    bf = bf or choose_bf(dp, fp, down_bits)
    assert fp % bf == 0 and dp % MIN_LANE == 0, (fp, bf, dp)
    slot, tile_expert, tile_src, tiles = group_layout(idx, e, bm)
    rows = tile_expert.shape[0] * bm
    flat_rows = jnp.arange(a, dtype=jnp.int32) // k
    x = jnp.pad(x_q, ((0, 0), (0, dp - x_q.shape[1])))
    xs = jnp.zeros((rows, dp), jnp.int8).at[slot].set(x[flat_rows])
    p = jnp.take_along_axis(probs, idx.astype(jnp.int32), axis=1).reshape(-1)
    ps = jnp.zeros((rows, 1), jnp.float32).at[slot, 0].set(p)
    nf = fp // bf
    last = nf - 1

    def fblock(tt, j, n_ref):
        # a tile past the real ones stays on the last real tile's F block
        live = (tt < n_ref[0]).astype(jnp.int32)
        return j * live + last * (1 - live)

    wd_rows = bf // 2 if down_bits == 4 else bf
    kernel = functools.partial(
        _qmoe_kernel, r_g=r_g, s_g=s_g, r_u=r_u, r_h=r_h, r_d=r_d, down_bits=down_bits
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(tile_expert.shape[0], nf),
            in_specs=[
                pl.BlockSpec((bm, dp), lambda tt, j, te, src, n: (src[tt], 0)),
                pl.BlockSpec((bm, 1), lambda tt, j, te, src, n: (src[tt], 0)),
                pl.BlockSpec((1, dp, bf), lambda tt, j, te, src, n: (te[tt], 0, fblock(tt, j, n))),
                pl.BlockSpec((1, dp, bf), lambda tt, j, te, src, n: (te[tt], 0, fblock(tt, j, n))),
                pl.BlockSpec((1, wd_rows, dp), lambda tt, j, te, src, n: (te[tt], fblock(tt, j, n), 0)),
            ],
            out_specs=pl.BlockSpec((bm, dp), lambda tt, j, te, src, n: (src[tt], 0)),
            scratch_shapes=[pltpu.VMEM((bm, dp), jnp.int32)],
        ),
        out_shape=jax.ShapeDtypeStruct((rows, dp), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"), vmem_limit_bytes=VMEM_LIMIT
        ),
        interpret=interpret,
        name="qmoe",
    )(tile_expert, tile_src, tiles.reshape(1), xs, ps, w_gate, w_up, w_down)
    contrib = out[slot, :d].reshape(t_rows, k, d)
    return contrib.sum(axis=1, dtype=jnp.int32)
