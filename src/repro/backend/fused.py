"""Fused-kernel implementations for the backend registry.

Four kernel ids cover the paper's fusion patterns:

* ``qlinear_matmul`` — MatMulInteger→…→QuantizeLinear chain.  The ``ref``
  backend runs the pure-jnp oracle on the *unpadded* parameters; the
  ``interpret``/``pallas`` backends run the Pallas tile kernel on parameters
  the lowering already padded to tile multiples
  (:func:`repro.kernels.ops.specialize_qmatmul_params`), so nothing but the
  activation is ever padded per call.
* ``qlinear_conv2d`` — ConvInteger chain on XLA's int8 conv (shared impl:
  the epilogue is plain jnp on every backend).
* ``qact_lut`` — the exact 256-entry int8 activation LUT.
* ``qattention`` — the fused int8 attention region (score MatMulInteger,
  additive masking, max-shifted LUT-softmax, context MatMulInteger).  The
  ``ref`` backend runs the jnp oracle; ``interpret``/``pallas`` run the
  tiled kernel (:mod:`repro.kernels.qattention`).  Scalar constants ride in
  ``step.params`` (static under jit); the LUT is the one array const.
* ``rmsnorm`` — the RMSNorm region (shared impl): its square root and
  division corrected to the nearest f32, so that the chip computes the
  artifact's IEEE semantics and not its own approximations.
* ``softmax_rn`` — a sparse-expert router's softmax over its int32 logits
  (shared impl): summed in expert order, the quotients the nearest f32.
* ``qmoe`` — the routed-expert region of a sparse-expert block: rows,
  chosen experts and router probabilities in, the fixed-point sum of the
  chosen experts' weighted SwiGLU outputs out.  The ``ref`` backend runs the
  dense oracle (every expert, zero weights where not chosen);
  ``interpret``/``pallas`` run the grouped kernel
  (:mod:`repro.kernels.qmoe`), which touches only the chosen experts.

Step contract (see :mod:`repro.backend.plan`): ``args = [x]`` (the single
graph-tensor input), parameters in ``step.consts``, static config in
``step.params``.  ``params["x_uint8"]`` marks a uint8 activation whose +128
offset was folded into the bias *at plan time* — the impl only applies the
signed shift to x.
"""
from __future__ import annotations

import jax.numpy as jnp

from ..core.pqir import DTYPES
from ..kernels import ops as kops
from ..kernels import ref as _ref
from .registry import register


def _as_signed(x, params):
    """uint8 activation → signed int8 (bias correction already folded)."""
    if params.get("x_uint8"):
        return (x.astype(jnp.int32) - 128).astype(jnp.int8)
    return x


@register("qlinear_matmul", backend="ref")
def _qlinear_matmul_ref(step, args):
    x = _as_signed(args[0], step.params)
    w, b, qs, qsh = step.consts
    p = step.params
    y = _ref.qmatmul_ref(
        x, w, b, qs, qsh,
        out_dtype=DTYPES[p["out_dtype"]], relu=p["relu"], two_mul=p["two_mul"],
    )
    return [y]


def _qlinear_matmul_tiled(step, args, *, interpret: bool):
    x = _as_signed(args[0], step.params)
    w2, b2, qs2, qsh2 = step.consts
    p = step.params
    if p.get("dynamic_batch"):
        raise RuntimeError(
            "batch-polymorphic template plan cannot execute directly: bind it "
            "to a bucket first (repro.backend.lowering.specialize_plan, or run "
            "through CompiledModel which caches specializations per bucket)"
        )
    y = kops.quantized_matmul_planned(
        x, w2, b2, qs2, qsh2, p["shape"],
        out_dtype=DTYPES[p["out_dtype"]], relu=p["relu"], two_mul=p["two_mul"],
        interpret=interpret,
    )
    return [y]


@register("qlinear_matmul", backend="interpret")
def _qlinear_matmul_interpret(step, args):
    return _qlinear_matmul_tiled(step, args, interpret=True)


@register("qlinear_matmul", backend="pallas")
def _qlinear_matmul_pallas(step, args):
    return _qlinear_matmul_tiled(step, args, interpret=False)


@register("qlinear_conv2d")
def _qlinear_conv2d(step, args):
    w, b, qs, qsh = step.consts
    p = step.params
    y = kops.quantized_conv2d(
        args[0], w, b, qs, qsh,
        strides=p["strides"], pads=p["pads"],
        out_dtype=DTYPES[p["out_dtype"]], relu=p["relu"], two_mul=p["two_mul"],
    )
    return [y]


@register("qattention", backend="ref")
def _qattention_ref(step, args):
    q, k, v, mask = args
    (lut,) = step.consts
    p = step.params
    y = _ref.qattention_ref(
        q, k, v, mask,
        jnp.float32(p["qk_scale"]), jnp.float32(p["big"]),
        jnp.float32(p["lut_scale"]), lut,
        jnp.float32(p["p_scale"]), jnp.float32(p["rescale"]),
        out_dtype=DTYPES[p["out_dtype"]],
    )
    return [y]


def _qattention_tiled(step, args, *, interpret: bool):
    from ..kernels import qattention as _qatt

    q, k, v, mask = args
    (lut,) = step.consts
    p = step.params
    if p.get("dynamic_attn"):
        raise RuntimeError(
            "axis-open attention template cannot execute directly: bind it to "
            "a bucket first (repro.backend.lowering.specialize_plan, or run "
            "through CompiledModel which caches specializations per bucket)"
        )
    y = _qatt.qattention(
        q, k, v, mask, lut,
        qk_scale=p["qk_scale"], big=p["big"], lut_scale=p["lut_scale"],
        p_scale=p["p_scale"], rescale=p["rescale"],
        out_dtype=DTYPES[p["out_dtype"]],
        bq=p["shape"].get("bq", _qatt.BQ),
        interpret=interpret,
    )
    return [y]


@register("qattention", backend="interpret")
def _qattention_interpret(step, args):
    return _qattention_tiled(step, args, interpret=True)


@register("qattention", backend="pallas")
def _qattention_pallas(step, args):
    return _qattention_tiled(step, args, interpret=False)


def _qact_lut(step, args, *, backend: str):
    (lut,) = step.consts
    return [kops.quantized_activation(args[0], lut, backend=backend)]


@register("qact_lut", backend="ref")
def _qact_lut_ref(step, args):
    return _qact_lut(step, args, backend="ref")


@register("qact_lut", backend="interpret")
def _qact_lut_interpret(step, args):
    return _qact_lut(step, args, backend="interpret")


@register("qact_lut", backend="pallas")
def _qact_lut_pallas(step, args):
    return _qact_lut(step, args, backend="pallas")


def _moe_rows(args):
    """``(x (T, D), idx (T, K), probs (T, E))`` from the step's ``(N, S, ·)``
    operands, and the leading shape to restore."""
    x, idx, probs = args
    lead = x.shape[:-1]
    return (x.reshape(-1, x.shape[-1]), idx.reshape(-1, idx.shape[-1]),
            probs.reshape(-1, probs.shape[-1]), lead)


@register("rmsnorm")
def _rmsnorm(step, args):
    (gain,) = step.consts
    p = step.params
    return [_ref.rmsnorm_ref(args[0], gain, jnp.float32(p["inv_d"]), jnp.float32(p["eps"]))]


@register("softmax_rn")
def _softmax_rn(step, args):
    return [_ref.softmax_rn(args[0].astype(jnp.float32) * jnp.float32(step.params["scale"]))]


@register("qmoe", backend="ref")
def _qmoe_ref(step, args):
    x, idx, probs, lead = _moe_rows(args)
    wg, wu, wd = step.consts
    p = step.params
    y = _ref.qmoe_ref(
        x, idx, probs, wg, wu, wd,
        r_g=jnp.float32(p["r_g"]), s_g=jnp.float32(p["s_g"]), r_u=jnp.float32(p["r_u"]),
        r_h=jnp.float32(p["r_h"]), r_d=jnp.float32(p["r_d"]),
    )
    return [y.reshape(lead + (p["d"],))]


def _qmoe_tiled(step, args, *, interpret: bool):
    from ..kernels import qmoe as _qmoe

    x, idx, probs, lead = _moe_rows(args)
    wg, wu, wd = step.consts
    p = step.params
    y = _qmoe.qmoe(
        x, idx, probs, wg, wu, wd,
        d=p["d"], r_g=p["r_g"], s_g=p["s_g"], r_u=p["r_u"], r_h=p["r_h"], r_d=p["r_d"],
        down_bits=p["down_bits"], interpret=interpret,
    )
    return [y.reshape(lead + (p["d"],))]


@register("qmoe", backend="interpret")
def _qmoe_interpret(step, args):
    return _qmoe_tiled(step, args, interpret=True)


@register("qmoe", backend="pallas")
def _qmoe_pallas(step, args):
    return _qmoe_tiled(step, args, interpret=False)
