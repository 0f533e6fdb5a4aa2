"""Public jit'd wrappers around the Pallas kernels.

Handle here (so kernels stay tile-pure):
  * arbitrary leading batch dims (flattened into M),
  * shape padding to tile multiples (zero padding is exact for int matmul),
  * uint8 activations — folded to int8 by the compiler identity
        x_u8 @ W = (x_s8 + 128) @ W = x_s8 @ W + 128·colsum(W)
    i.e. a bias correction computed once at compile time, keeping the MXU on
    its signed-int8 fast path (a HW/SW co-design move the artifact's
    *expressiveness* makes possible: the compiler sees the true dtypes),
  * scalar vs per-channel rescale broadcasting,
  * backend dispatch: pallas (TPU) / pallas-interpret (CPU validation) /
    pure-jnp reference (dry-run lowering).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import pack as _pack
from . import qact_lut as _qact
from . import qmatmul as _qmm
from . import ref as _ref


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def fold_uint8_input(w_q: jax.Array, bias_q: Optional[jax.Array]):
    """Return the bias correction that converts a uint8-activation matmul into
    a signed-int8 one: bias' = bias + 128 * sum_k W[k, :]."""
    colsum = jnp.sum(w_q.astype(jnp.int32), axis=0)
    corr = 128 * colsum
    return corr if bias_q is None else bias_q.astype(jnp.int32) + corr


# ---------------------------------------------------------------------------
# plan-time shape specialization (repro.backend lowering)
# ---------------------------------------------------------------------------


def template_qmatmul_params(
    w_q: np.ndarray,  # (K, N) int8 (unpacked; values in [-8, 7] when weight_bits=4)
    bias_q: Optional[np.ndarray],  # (N,) int32
    quant_scale: np.ndarray,  # scalar or (N,) f32
    quant_shift: np.ndarray,  # scalar or (N,) f32
    *,
    weight_bits: int = 8,
):
    """The batch-*independent* half of qmatmul shape specialization.

    Everything here is a property of the weights alone: the K/N tile choice,
    and the parameter pre-padding to tile multiples (kp = K and N rounded up
    to bk/bn).  None of it depends on the batch, so a batch-polymorphic plan
    template builds — and pays for — it exactly once, and every per-bucket
    specialization *shares* these padded arrays (binding a bucket copies no
    parameter data, see :func:`bind_qmatmul_batch`).

    Returns ``(consts, shape)``: ``consts = (w2, b2, qs2, qsh2)`` jnp arrays
    already shaped ``(kp, np)/(1, np)`` for the kernel, and ``shape`` the
    batch-open record ``{k, n, kp, np, bk, bn}`` (no ``m``/``bm`` yet).
    Zero padding is exact for integer matmul; scale/shift pad with 1.0 so the
    padded epilogue stays finite.

    ``weight_bits=4`` packs the padded weight 2-per-byte along K *here, once
    per template* (kp is always even — bk is a 128-multiple): ``w2`` becomes
    a uint8 ``(kp // 2, np)`` nibble array and the shape record carries
    ``bits: 4``; backends dispatch on it (the ref backend keeps the unpacked
    consts as the oracle — see ``repro.backend.fused``)."""
    if weight_bits not in (4, 8):
        raise ValueError(f"unsupported weight_bits: {weight_bits!r}")
    k, n = int(w_q.shape[0]), int(w_q.shape[1])
    _, bk, bn = _qmm.choose_tiles(None, k, n)
    kp, np_ = _round_up(k, bk), _round_up(n, bn)
    w2 = np.zeros((kp, np_), np.int8)
    w2[:k, :n] = np.asarray(w_q, np.int8)
    if weight_bits == 4:
        w2 = _pack.pack_int4(w2)  # (kp // 2, np) uint8, zero rows pack to 0x00
    b2 = np.zeros((1, np_), np.int32)
    if bias_q is not None:
        b2[0, :n] = np.asarray(bias_q, np.int32).reshape(-1)
    qs2 = np.ones((1, np_), np.float32)
    qs2[0, :n] = np.broadcast_to(np.asarray(quant_scale, np.float32).reshape(1, -1), (1, n))
    qsh2 = np.ones((1, np_), np.float32)
    qsh2[0, :n] = np.broadcast_to(np.asarray(quant_shift, np.float32).reshape(1, -1), (1, n))
    consts = (jnp.asarray(w2), jnp.asarray(b2), jnp.asarray(qs2), jnp.asarray(qsh2))
    shape = {"k": k, "n": n, "kp": kp, "np": np_, "bk": bk, "bn": bn}
    if weight_bits != 8:
        shape["bits"] = weight_bits  # omitted at 8: int8 records stay byte-identical
    return consts, shape


def bind_qmatmul_axes(shape: dict, bindings: Optional[dict], *, partial: bool = False) -> dict:
    """The scenario-*dependent* half: close a template shape record over
    concrete per-axis buckets.

    ``shape["lead"]`` is the activation's leading dims as inferred at
    template-build time: concrete ints, named symbolic axes (strings such as
    ``"N"``/``"S"``), ``None`` in the leading position for the legacy
    implicit batch, and the whole tuple ``None`` when inference knew nothing
    — M then stays unknown and the default bm stands.  The flat matmul M is
    the product of the lead dims with ``bindings`` substituted per axis name
    (an unnamed leading ``None`` binds to the batch axis ``"N"``, matching
    :func:`repro.passes.analysis.bind`).  Only ``m`` and the bm tile choice
    are computed here — the padded parameter arrays and K/N tiles come from
    the template unchanged, so a bucket specialization is O(1) (no
    re-lowering, no array copies).

    ``partial=True`` substitutes the given axes into ``lead`` but keeps the
    record *open* (no m/bm yet) — used when a template is specialized over a
    subset of its axes and must stay a template for the rest."""
    bindings = bindings or {}
    lead = shape.get("lead")
    if partial:
        if lead is None:
            return dict(shape)
        new_lead = []
        for i, d in enumerate(lead):
            if isinstance(d, str) and d in bindings:
                d = int(bindings[d])
            elif d is None and i == 0 and "N" in bindings:
                d = int(bindings["N"])
            new_lead.append(d)
        out = dict(shape)
        out["lead"] = tuple(new_lead)
        return out
    if lead is None:
        m: Optional[int] = None  # inference knew nothing: keep the default bm
    else:
        m = 1
        for i, d in enumerate(lead):
            if isinstance(d, str):
                d = bindings.get(d)
            elif d is None and i == 0:
                d = bindings.get("N")  # legacy implicit batch
            if not isinstance(d, int):
                m = None  # still-unknown dim: fall back to the default bm
                break
            m *= int(d)
    bound = {key: v for key, v in shape.items() if key != "lead"}
    bound["m"] = m
    bound["bm"] = _qmm.choose_bm(m)
    return bound


def with_tiles(
    shape: dict,
    *,
    bm: Optional[int] = None,
    bk: Optional[int] = None,
    bn: Optional[int] = None,
) -> dict:
    """A copy of a *bound* qmatmul shape record with tile overrides — the
    autotuner's way of re-tiling a cell without touching the template.

    Overrides are validated against the kernel's alignment constraints
    (:func:`repro.kernels.qmatmul.tile_aligned`), and ``bk``/``bn`` must
    additionally *divide* the template's padded ``kp``/``np`` — the padded
    parameter arrays were built once at template time and every tuned
    specialization shares them zero-copy, so a tile that would change the
    padding is not a legal candidate.  ``bm`` is free (any 32-multiple): the
    activation is padded per call, not baked into the template."""
    out = dict(shape)
    if bm is not None:
        if bm <= 0 or bm % _qmm.MIN_SUBLANE:
            raise ValueError(f"bm={bm} is not a positive {_qmm.MIN_SUBLANE}-multiple")
        out["bm"] = int(bm)
    if bk is not None:
        if bk <= 0 or bk % _qmm.MIN_LANE:
            raise ValueError(f"bk={bk} is not a positive {_qmm.MIN_LANE}-multiple")
        if shape["kp"] % bk:
            raise ValueError(
                f"bk={bk} does not divide the template's padded kp={shape['kp']} "
                "(tuned tiles must reuse the pre-padded parameter arrays)"
            )
        out["bk"] = int(bk)
    if bn is not None:
        if bn <= 0 or bn % _qmm.MIN_LANE:
            raise ValueError(f"bn={bn} is not a positive {_qmm.MIN_LANE}-multiple")
        if shape["np"] % bn:
            raise ValueError(
                f"bn={bn} does not divide the template's padded np={shape['np']} "
                "(tuned tiles must reuse the pre-padded parameter arrays)"
            )
        out["bn"] = int(bn)
    return out


def bind_qmatmul_batch(shape: dict, batch: Optional[int]) -> dict:
    """Single-axis sugar over :func:`bind_qmatmul_axes` (the PR 4 calling
    convention): bind the implicit batch axis only."""
    return bind_qmatmul_axes(shape, {} if batch is None else {"N": int(batch)})


def _bind_dim(d, bindings: dict):
    """One dim of an attention shape record: named axes substitute from
    ``bindings``, ints pass through, still-symbolic names stay as-is."""
    if isinstance(d, str) and d in bindings:
        return int(bindings[d])
    return d


def bind_qattention_axes(shape: dict, bindings: Optional[dict], *, partial: bool = False) -> dict:
    """Close a fused-attention template shape record over concrete buckets.

    The template record is ``{"b": lead-dims, "s": S, "t": T, "dh": int}``
    where ``b`` is the stacked batch×heads leading dims tuple and any entry
    (or ``s``/``t``) may be a named symbolic axis (``"N"``, ``"S"``).  A full
    bind substitutes the bindings, flattens ``b`` to its product, and picks
    the query row-tile ``bq`` via :func:`repro.kernels.qattention.choose_bq`
    (the autotuner may override it afterwards).  ``partial=True`` substitutes
    the given axes but keeps the record open — the step then stays a
    template for the remaining axes."""
    from . import qattention as _qatt

    bindings = bindings or {}
    out = dict(shape)
    lead = tuple(_bind_dim(d, bindings) for d in shape.get("b", ()))
    out["s"] = _bind_dim(shape.get("s"), bindings)
    out["t"] = _bind_dim(shape.get("t"), bindings)
    if partial:
        out["b"] = lead
        return out
    b = 1
    for d in lead:
        if not isinstance(d, int):
            raise ValueError(f"unbound attention batch dim {d!r} in {shape!r}")
        b *= int(d)
    if not isinstance(out["s"], int) or not isinstance(out["t"], int):
        raise ValueError(f"unbound attention seq dims in {out!r}")
    out["b"] = b
    out.setdefault("bq", _qatt.choose_bq(out["s"]))
    return out


def specialize_qmatmul_params(
    w_q: np.ndarray,  # (K, N) int8
    bias_q: Optional[np.ndarray],  # (N,) int32
    quant_scale: np.ndarray,  # scalar or (N,) f32
    quant_shift: np.ndarray,  # scalar or (N,) f32
    *,
    m: Optional[int] = None,  # static M if known, else None (dynamic batch)
    weight_bits: int = 8,
):
    """Fully-static specialization (the ``batch="static"`` compile path):
    template + immediate batch binding in one step.  Returns the same
    ``(consts, params)`` contract as before the template split — ``params``
    is the closed record ``{m, k, n, kp, np, bm, bk, bn}``."""
    consts, shape = template_qmatmul_params(
        w_q, bias_q, quant_scale, quant_shift, weight_bits=weight_bits
    )
    params = bind_qmatmul_batch({**shape, "lead": (m,)}, None)
    return consts, params


def quantized_matmul_planned(
    x_q: jax.Array,  # (..., K) int8 (uint8 already folded at plan time)
    w2: jax.Array,  # (kp, np) int8 — pre-padded
    b2: jax.Array,  # (1, np) int32 — pre-padded
    qs2: jax.Array,  # (1, np) f32 — pre-padded
    qsh2: jax.Array,  # (1, np) f32 — pre-padded
    shape: dict,  # the params record from specialize_qmatmul_params
    *,
    out_dtype=jnp.int8,
    relu: bool = False,
    two_mul: bool = True,
    interpret: bool = False,
) -> jax.Array:
    """Shape-specialized fused matmul: parameters arrive pre-padded, so the
    per-call work is at most an activation pad (skipped entirely when the
    traced shape is already a tile multiple).

    ``shape["bits"] == 4`` selects the packed-int4 kernel: ``w2`` is then the
    uint8 ``(kp // 2, np)`` nibble array the template packed once."""
    k, n, kp = shape["k"], shape["n"], shape["kp"]
    bm, bk, bn = shape["bm"], shape["bk"], shape["bn"]
    bits = shape.get("bits", 8)
    orig_shape = x_q.shape
    assert orig_shape[-1] == k, (orig_shape, k)
    x2 = x_q.reshape(-1, k)
    m = x2.shape[0]
    mp = _round_up(max(m, 1), bm)
    if mp != m or kp != k:
        x2 = jnp.pad(x2, ((0, mp - m), (0, kp - k)))
    if bits == 4:
        assert w2.dtype == jnp.uint8 and w2.shape[0] * 2 == kp, (w2.dtype, w2.shape, kp)
        out = _qmm.qmatmul_packed(
            x2, w2, b2, qs2, qsh2,
            out_dtype=out_dtype, relu=relu, two_mul=two_mul,
            bm=bm, bk=bk, bn=bn, interpret=interpret,
        )
    else:
        out = _qmm.qmatmul(
            x2, w2, b2, qs2, qsh2,
            out_dtype=out_dtype, relu=relu, two_mul=two_mul,
            bm=bm, bk=bk, bn=bn, interpret=interpret,
        )
    return out[:m, :n].reshape(orig_shape[:-1] + (n,))


def quantized_matmul(
    x_q: jax.Array,  # (..., K) int8 or uint8
    w_q: jax.Array,  # (K, N) int8
    bias_q: Optional[jax.Array],  # (N,) int32
    quant_scale,  # python float/int, or (N,) array — integer values as FLOAT
    quant_shift,  # python float, or (N,) array — 2**-N
    *,
    out_dtype=jnp.int8,
    relu: bool = False,
    two_mul: bool = True,
    backend: str = "ref",  # "pallas" | "interpret" | "ref"
    bm: int = _qmm.BM,
    bk: int = _qmm.BK,
    bn: int = _qmm.BN,
) -> jax.Array:
    """Fused pre-quantized matmul over arbitrary leading dims."""
    orig_shape = x_q.shape
    k, n = w_q.shape
    assert orig_shape[-1] == k, (orig_shape, w_q.shape)

    if x_q.dtype == jnp.uint8:
        bias_q = fold_uint8_input(w_q, bias_q)
        x_q = (x_q.astype(jnp.int32) - 128).astype(jnp.int8)

    qs = jnp.asarray(quant_scale, jnp.float32)
    qsh = jnp.asarray(quant_shift, jnp.float32)

    if backend == "ref":
        return _ref.qmatmul_ref(
            x_q, w_q, bias_q, qs, qsh, out_dtype=out_dtype, relu=relu, two_mul=two_mul
        ).reshape(orig_shape[:-1] + (n,))

    x2 = x_q.reshape(-1, k)
    m = x2.shape[0]
    mp, kp, np_ = _round_up(max(m, 1), bm), _round_up(k, bk), _round_up(n, bn)
    x2 = jnp.pad(x2, ((0, mp - m), (0, kp - k)))
    w2 = jnp.pad(w_q, ((0, kp - k), (0, np_ - n)))
    b2 = jnp.zeros((1, np_), jnp.int32) if bias_q is None else jnp.pad(
        bias_q.reshape(1, n).astype(jnp.int32), ((0, 0), (0, np_ - n))
    )
    qs2 = jnp.pad(jnp.broadcast_to(qs.reshape(1, -1), (1, n)), ((0, 0), (0, np_ - n)), constant_values=1.0)
    qsh2 = jnp.pad(jnp.broadcast_to(qsh.reshape(1, -1), (1, n)), ((0, 0), (0, np_ - n)), constant_values=1.0)
    out = _qmm.qmatmul(
        x2, w2, b2, qs2, qsh2,
        out_dtype=out_dtype, relu=relu, two_mul=two_mul,
        bm=bm, bk=bk, bn=bn, interpret=(backend == "interpret"),
    )
    return out[:m, :n].reshape(orig_shape[:-1] + (n,))


def quantized_activation(
    x_q: jax.Array,  # (...,) int8
    lut: jax.Array | np.ndarray,  # (256,) int8/uint8
    *,
    backend: str = "ref",
) -> jax.Array:
    """int8 LUT activation over arbitrary shape: flattened to ``(M, N)`` and
    zero-padded to the kernel's blocks (any code is a valid index; the
    padding is sliced away)."""
    lut = jnp.asarray(lut)
    if backend == "ref":
        return _ref.qact_lut_ref(x_q, lut)
    orig_shape = x_q.shape
    n = orig_shape[-1]
    x2 = x_q.reshape(-1, n)
    m = x2.shape[0]
    bm, bn = _qact.choose_blocks(m, n)
    mp, np_ = _round_up(max(m, 1), bm), _round_up(n, bn)
    if (mp, np_) != (m, n):
        x2 = jnp.pad(x2, ((0, mp - m), (0, np_ - n)))
    out = _qact.qact_lut(x2, lut, bm=bm, bn=bn, interpret=(backend == "interpret"))
    return out[:m, :n].reshape(orig_shape)


@functools.partial(jax.jit, static_argnames=("out_dtype", "relu", "two_mul", "strides", "pads"))
def quantized_conv2d(
    x_q: jax.Array,  # (N, C, H, W) int8/uint8
    w_q: jax.Array,  # (M, C, kH, kW) int8
    bias_q: Optional[jax.Array],  # (M,) int32
    quant_scale,
    quant_shift,
    *,
    strides=(1, 1),
    pads=(0, 0, 0, 0),
    out_dtype=jnp.int8,
    relu: bool = False,
    two_mul: bool = True,
) -> jax.Array:
    """ConvInteger + epilogue.  Lowers to XLA's int8 conv (which maps onto the
    MXU via implicit im2col on TPU); the epilogue matches the artifact chain
    bit-for-bit.  Symmetric quantization ⇒ zero padding is exact."""
    if x_q.dtype == jnp.uint8:
        # Same signed-offset fold as matmul: correction = 128 * sum over C,kh,kw.
        corr = 128 * jnp.sum(w_q.astype(jnp.int32), axis=(1, 2, 3))
        bias_q = corr if bias_q is None else bias_q.astype(jnp.int32) + corr
        x_q = (x_q.astype(jnp.int32) - 128).astype(jnp.int8)
    acc = jax.lax.conv_general_dilated(
        x_q.astype(jnp.int8),
        w_q.astype(jnp.int8),
        window_strides=tuple(strides),
        padding=((pads[0], pads[2]), (pads[1], pads[3])),
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        preferred_element_type=jnp.int32,
    )
    if bias_q is not None:
        acc = acc + bias_q.reshape(1, -1, 1, 1).astype(jnp.int32)
    f = acc.astype(jnp.float32)
    qs = jnp.asarray(quant_scale, jnp.float32)
    qsh = jnp.asarray(quant_shift, jnp.float32)
    f = f * (qs.reshape(1, -1, 1, 1) if qs.ndim else qs)
    if two_mul:
        f = f * (qsh.reshape(1, -1, 1, 1) if qsh.ndim else qsh)
    if relu:
        f = jnp.maximum(f, 0.0)
    r = jnp.rint(f)
    info = jnp.iinfo(out_dtype)
    return jnp.clip(r, info.min, info.max).astype(out_dtype)
