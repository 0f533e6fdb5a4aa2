"""The hardware-specific compilation stage: PQ-IR → typed ExecutionPlan →
JAX/Pallas kernels.

This is the *other side* of the paper's co-design contract, structured as a
three-level flow (QNN / onnx-mlir style multi-level lowering):

1. **Optimize** — the artifact first runs through the
   :mod:`repro.passes` pipeline (constant folding, identity/dead-node
   elimination, Reshape/Transpose/Flatten sinking, §3.1 two-Mul rescale and
   integer Add-bias folding, Quantize/Dequantize round-trip cancellation).
   Every pass is semantics-preserving — bit-exact on integer paths — and the
   caller's artifact is never mutated (the pipeline clones it).

2. **Fuse** — fusion candidates are *declarative pattern specs*
   (:class:`repro.passes.rewrite.Pattern`): an op chain with
   dtype/arity/constness preconditions and capture names, matched along
   single-consumer edges by the shared pattern-rewrite engine.  The specs in
   this module describe the paper's kernels:

     QLINEAR_PATTERN: {MatMulInteger|ConvInteger → [Add] → Cast(f32) →
                       Mul [→ Mul] → [Relu] → QuantizeLinear(1,0)}
         ⇒ one fused int8 MXU kernel (repro.kernels.qmatmul), or XLA int8
           conv + fused epilogue (repro.kernels.ops.quantized_conv2d).
           The rescale Mul constants may be scalar or per-channel vectors
           along the output-feature axis; per-channel multiplier/shift
           arrays ride through plan-time specialization pre-padded to tile
           multiples like every other qmatmul parameter.
     GEMM_PATTERN:    same epilogue anchored on an integer Gemm (the form
                      Gemm-based MLP exports emit) ⇒ same fused kernel;
                      transB and the C bias operand fold at plan time.
     LUT_PATTERN:     {DequantizeLinear(int8) → [Cast f16] → Tanh|Sigmoid →
                       [Cast f32] → QuantizeLinear}
         ⇒ exact 256-entry VMEM LUT (repro.kernels.qact_lut), built with
           reference-runtime semantics (incl. the fp16 casts) ⇒ bit-exact.
     QLINEAR_F32_PATTERN: {MatMulInteger → [Add] → Cast(f32) → Mul} whose
                      f32 result feeds more f32 arithmetic ⇒ the fused
                      kernel with an unrounded epilogue.

   DAG regions (``REGIONS``, matched at their sinks by
   :func:`repro.passes.rewrite.match_region`): ``QMOE_REGION``, the routed
   experts of a sparse-expert block (every expert, zero weights where not
   chosen) ⇒ the grouped ``qmoe`` kernel over the chosen experts only;
   ``RMSNORM_REGION`` ⇒ one step with the nearest-f32 root and quotient;
   ``ROUTER_SOFTMAX_REGION`` ⇒ one step summing in expert order.

3. **Lower** — matches and fallback nodes become
   :class:`repro.backend.StepDraft`\\ s, and :func:`repro.backend.build_plan`
   turns them into a typed, liveness-planned :class:`ExecutionPlan`
   (integer buffer slots, per-step kernel ids resolved through the backend
   registry, shapes/dtypes from :mod:`repro.passes.analysis`).  Shape
   specialization happens *here*, at plan time: fused-qmatmul parameters are
   pre-padded to tile multiples and (bm, bk, bn) chosen per static shape, so
   the hot path never pads weights/bias/scales per call.  uint8 activations
   fold to the signed-int8 MXU fast path at plan time too (bias correction
   computed once).  ``CompiledModel.plan`` is printable — the artifact a
   hardware designer reads.

4. **Specialize (late)** — with ``dynamic_axes={...}`` (or its single-axis
   sugar ``batch="dynamic"``) the lowering stops one step earlier: the plan
   is a shape-generic *template* open over the artifact's **named symbolic
   axes** (``("N", "S", 64)`` input signatures; legacy ``(None, …)`` inputs
   contribute the implicit batch axis ``"N"``).  Fusion, slot liveness,
   dtype inference, and the axis-independent parameter padding are all done
   once; the axis-dependent M/bm stay symbolic.  Executing the artifact then
   binds the template to a per-axis *bucket* combination on demand
   (:func:`repro.backend.specialize_plan` with a bindings dict — tile choice
   for the flattened lead dims, nothing re-lowered) through a bounded
   :class:`repro.backend.PlanCache` keyed on the sorted bindings, so one
   compiled artifact serves a whole (batch × sequence × …) scenario grid
   with at most one specialization — and one jit trace — per visited bucket
   combination.  Each axis carries its own bucketing policy (power-of-two
   default; an int granularity rounds up to multiples, matching the serving
   engine's prefill buckets).  Zero padding along an axis is only exact when
   no op mixes information across it, so dynamic compilation *proves* each
   requested axis elementwise-safe independently
   (:func:`repro.passes.analysis.axis_mixing_nodes`) and rejects the graph
   otherwise.  This is the serving-side contract
   :mod:`repro.serving.compiled` builds its micro-batching server on.

Adding a fusion means adding a Pattern + a builder; adding a backend means
registering kernels — there is no hand-written chain-walking or backend
conditional left here.  Anything unmatched falls back to the generic jnp op
mirror (:mod:`repro.backend.generic`), so *every* valid artifact compiles.
Conformance: integer paths are bit-exact vs :mod:`repro.core.runtime`; float
fallbacks are allclose.
"""
from __future__ import annotations

import weakref
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..backend import StepDraft, build_plan, const_arg, none_arg, specialize_plan, tensor_arg
from ..backend.generic import _JOPS  # noqa: F401  (re-export; conformance sweep)
from ..backend.plan import ExecutionPlan, PlanCache, bindings_key, resolve_bucketing
from ..kernels import ops as kops
from ..kernels import pack as _pack
from ..kernels.qact_lut import build_lut
from ..kernels.ref import MOE_CLIP, MOE_FIXED
from ..obs import trace as _trace
from ..obs.metrics import MetricsRegistry
from ..obs.provenance import PlanProvenance
from ..passes import PassManager, PipelineReport
from ..passes.analysis import (
    BATCH_AXIS,
    GraphAnalysis,
    axis_inputs,
    axis_mixing_nodes,
    axis_positions,
    graph_axes,
    implicit_batch_graph,
)
from ..passes.rewrite import (
    Match,
    NodeSpec,
    OpSpec,
    Pattern,
    Region,
    RegionMatch,
    match_chain,
    match_region,
    ql_params,
)
from . import runtime
from .pqir import Model, Node

# ---------------------------------------------------------------------------
# fusion: declarative pattern specs + plan-step builders
# ---------------------------------------------------------------------------

# activation references the LUT builder bakes; Sigmoid uses the same
# overflow-safe form as the reference runtime so LUTs stay bit-exact vs it
_NP_ACT = {"Tanh": np.tanh, "Sigmoid": runtime.stable_sigmoid}


def _is_round_clip_ql(ga: GraphAnalysis, node: Node) -> bool:
    """QuantizeLinear(scale=1, zp=0) — the paper's pure rounding+clipping
    stage whose zp dtype selects the output dtype."""
    scale, zp = ql_params(ga, node)
    return (
        scale is not None and zp is not None
        and scale.size == 1 and np.asarray(zp).size == 1
        and float(scale) == 1.0 and int(np.asarray(zp)) == 0
    )


def _is_sym_scalar_q(ga: GraphAnalysis, node: Node) -> bool:
    """Scalar-scale, zero-zero-point (symmetric) quantize/dequantize."""
    scale, zp = ql_params(ga, node)
    return (
        scale is not None and zp is not None
        and scale.size == 1 and np.asarray(zp).size == 1
        and int(np.asarray(zp)) == 0
    )


def _dql_int8_sym(ga: GraphAnalysis, node: Node) -> bool:
    return ga.dtype(node.inputs[0]) == "int8" and _is_sym_scalar_q(ga, node)


def _gemm_q_anchor(ga: GraphAnalysis, node: Node) -> bool:
    """Integer Gemm usable as a fused-qlinear core: int8/uint8 activation,
    constant 2-D int8 weight, optional constant integer bias, default
    alpha/beta, no transA (transB folds into the constant at plan time)."""
    if ga.dtype(node.inputs[0]) not in ("int8", "uint8"):
        return False
    if node.attrs.get("transA", 0):
        return False
    if float(node.attrs.get("alpha", 1.0)) != 1.0 or float(node.attrs.get("beta", 1.0)) != 1.0:
        return False
    w = ga.const(node.inputs[1])
    if w is None or w.ndim != 2 or w.dtype != np.int8:
        return False
    if len(node.inputs) > 2 and node.inputs[2]:
        c = ga.const(node.inputs[2])
        if c is None or not np.issubdtype(c.dtype, np.integer):
            return False
    return True


#: The Fig 1/2 epilogue every qlinear core shares:
#: [Add bias] → Cast(f32) → Mul [→ Mul] → [Relu] → QuantizeLinear(1, 0).
#: The Mul constants may be scalars or per-channel vectors along the
#: output-feature axis — the builder validates the broadcast direction.
_QL_EPILOGUE = (
    OpSpec("Add", capture="bias", optional=True, const_operand="bias_c"),
    OpSpec("Cast", attrs={"to": "float32"}),
    OpSpec("Mul", capture="mul1", const_operand="mul1_c"),
    OpSpec("Mul", capture="mul2", optional=True, const_operand="mul2_c"),
    OpSpec("Relu", capture="relu", optional=True),
    OpSpec("QuantizeLinear", capture="ql", where=_is_round_clip_ql),
)


def _plain_weight(ga: GraphAnalysis, node: Node) -> bool:
    """A matmul weight is one 2-D matrix (a stack of expert weights is the
    routed-expert region's), a conv weight one 4-D kernel."""
    w = ga.const(node.inputs[1])
    return w is not None and w.ndim == (4 if node.op_type == "ConvInteger" else 2)


QLINEAR_PATTERN = Pattern(
    "qlinear",
    (OpSpec(("MatMulInteger", "ConvInteger"), capture="core", arity=2, const_inputs={1: "weight"},
            where=_plain_weight),)
    + _QL_EPILOGUE,
)

#: An int8 projection whose rescaled accumulator stays f32 and feeds more f32
#: arithmetic (a sparse-expert block's shared expert and its sigmoid gate):
#: the same fused kernel with an unrounded f32 epilogue.
QLINEAR_F32_PATTERN = Pattern(
    "qlinear_f32",
    (
        OpSpec("MatMulInteger", capture="core", arity=2, const_inputs={1: "weight"}, where=_plain_weight),
        OpSpec("Add", capture="bias", optional=True, const_operand="bias_c"),
        OpSpec("Cast", attrs={"to": "float32"}),
        OpSpec("Mul", capture="mul1", const_operand="mul1_c"),
    ),
)

#: Gemm-codified FC chains (some MLP exporters emit one integer Gemm instead
#: of MatMulInteger + Add) lower onto the same fused qlinear kernel.
GEMM_PATTERN = Pattern(
    "qlinear_gemm",
    (OpSpec("Gemm", capture="core", const_inputs={1: "weight"}, where=_gemm_q_anchor),)
    + _QL_EPILOGUE,
)

LUT_PATTERN = Pattern(
    "qact_lut",
    (
        OpSpec("DequantizeLinear", capture="dql", where=_dql_int8_sym),
        OpSpec("Cast", capture="to16", optional=True, attrs={"to": "float16"}),
        OpSpec(("Tanh", "Sigmoid"), capture="act"),
        OpSpec("Cast", capture="to32", optional=True, attrs={"to": "float32"}),
        OpSpec("QuantizeLinear", capture="ql", where=_is_sym_scalar_q),
    ),
    # the fp16 down-cast and up-cast appear together or not at all
    where=lambda m: (m.node("to16") is None) == (m.node("to32") is None),
)


def _channel_const(c, n_out: int, tail: int, acc_ndim: Optional[int]) -> Optional[np.ndarray]:
    """Normalize a captured epilogue constant to a scalar ``()`` or an
    ``(n_out,)`` vector that broadcasts along the accumulator's
    output-feature axis (``tail`` = trailing spatial singleton dims: 0 for
    the (..., N) matmul layout, 2 for conv's NCHW).  Any other broadcast
    direction (per-row constants, rank-expanding constants whose extra
    leading dims would grow the output shape) returns None — the chain then
    stays unfused rather than fusing incorrectly.  ``acc_ndim`` is the
    accumulator rank when statically known (None ⇒ only rank ≤ 1 constants
    are provably non-expanding)."""
    c = np.asarray(c)
    if c.ndim > (acc_ndim if acc_ndim is not None else 1):
        return None  # broadcasting would prepend dims to the output
    if c.size == 1:
        return c.reshape(())
    shape = c.shape
    if tail:
        if len(shape) <= tail or any(d != 1 for d in shape[len(shape) - tail:]):
            return None
        shape = shape[: len(shape) - tail]
    if not shape or shape[-1] != c.size or c.size != n_out:
        return None
    return c.reshape(-1)


def _static_m(shape) -> Optional[int]:
    """Product of the leading (batch) dims if fully known, else None (a
    symbolic dim — named or unknown — makes the flat M unknowable here)."""
    if shape is None or len(shape) < 1:
        return None
    lead = shape[:-1]
    m = 1
    for d in lead:
        if not isinstance(d, int):
            return None
        m *= int(d)
    return m


def _symbolic_lead(shape) -> Optional[tuple]:
    """The activation's leading dims for an axis-open shape record: named
    axes (strings) mark the symbolic dims — or, on legacy graphs, ``None``
    in the leading position marks the implicit batch; other dims stay
    concrete so late binding can compute the flat M as their product with
    the axis bindings substituted.  A wholly unknown shape returns None —
    binding then leaves M unknown and keeps the default bm rather than
    stamping a flat M it cannot actually know."""
    if shape is None or len(shape) < 2:
        return None
    return tuple(shape[:-1])


def _build_qlinear(compiler: "Compiler", m: Match) -> Optional[StepDraft]:
    """Lower a QLINEAR/GEMM_PATTERN match onto the fused int8 matmul / conv,
    shape-specializing the matmul parameters at plan time.  Returns None
    (fall back unfused) when an epilogue constant does not broadcast along
    the output-feature axis."""
    core = m.anchor
    is_conv = core.op_type == "ConvInteger"
    is_gemm = core.op_type == "Gemm"
    ga = compiler.analysis
    # QONNX-style sub-8-bit weights: the bitwidth rides as a node attribute
    # on the integer core op (weights stay an unpacked int8 initializer, so
    # the reference runtime needs no change); the tiled lowering packs on it.
    weight_bits = int(core.attrs.get("weight_bits", 8))
    ql = m.node("ql")
    if ql is None:
        # the f32 lane: only where every reader continues in f32 arithmetic
        if m.out_tensor in ga.out_names or not all(
            c.op_type in ("Mul", "Sigmoid") for c in ga.consumers.get(m.out_tensor, [])
        ):
            return None
        out_dtype = "float32"
    else:
        zp = ga.const(ql.inputs[2]) if len(ql.inputs) > 2 else np.zeros((), np.int8)
        out_dtype = str(np.asarray(zp).dtype)
    relu = m.node("relu") is not None

    w = np.asarray(m.consts["weight"])
    if is_gemm and core.attrs.get("transB", 0):
        w = np.ascontiguousarray(w.T)
    n_out = int(w.shape[0]) if is_conv else int(w.shape[1])
    tail = 2 if is_conv else 0
    # conv accumulators are NCHW by construction; matmul/Gemm rank comes from
    # shape inference (unknown ⇒ _channel_const only admits rank ≤ 1 consts)
    acc_shape = ga.shape(core.outputs[0])
    acc_ndim = 4 if is_conv else (len(acc_shape) if acc_shape is not None else None)

    two_mul = "mul2" in m
    qs = _channel_const(np.asarray(m.consts["mul1_c"], np.float32), n_out, tail, acc_ndim)
    qsh = (
        _channel_const(np.asarray(m.consts["mul2_c"], np.float32), n_out, tail, acc_ndim)
        if two_mul else np.float32(1.0)
    )
    if qs is None or qsh is None:
        return None

    b = None
    if is_gemm and len(core.inputs) > 2 and core.inputs[2]:
        b = _channel_const(ga.const(core.inputs[2]), n_out, 0, acc_ndim)
        if b is None:
            return None
        b = b.astype(np.int32)
    add_c = m.consts.get("bias_c")
    if add_c is not None:
        bc = _channel_const(add_c, n_out, tail, acc_ndim)
        if bc is None:
            return None
        # int32 addition wraps associatively, so folding the Gemm C operand
        # and a trailing Add into one bias is exact even under overflow
        with np.errstate(over="ignore"):
            b = bc.astype(np.int32) if b is None else b + bc.astype(np.int32)
    x_name = core.inputs[0]
    params = {"out_dtype": out_dtype, "relu": relu, "two_mul": two_mul}

    if is_conv:
        attrs = core.attrs
        params.update(
            strides=tuple(attrs.get("strides", (1, 1))),
            pads=tuple(attrs.get("pads", (0, 0, 0, 0))),
        )
        if weight_bits != 8:
            # conv has no packed lane — the bitwidth still renders in the plan
            params["weight_bits"] = weight_bits
        consts = (
            jnp.asarray(w),
            None if b is None else jnp.asarray(b),
            jnp.asarray(qs),
            jnp.asarray(np.asarray(qsh, np.float32)),
        )
        return StepDraft(
            "qlinear_conv2d", [tensor_arg(x_name)], [m.out_tensor],
            params=params, consts=consts, kind="fused_qconv", name=core.name,
        )

    if compiler.backend == "ref":
        # pure-jnp oracle: unpadded params, uint8 handled by int32 widening;
        # int4 stays *unpacked* here — this path is what the packed kernels
        # are pinned bit-exact against
        if weight_bits != 8:
            params["weight_bits"] = weight_bits
        consts = (
            jnp.asarray(w),
            None if b is None else jnp.asarray(b),
            jnp.asarray(qs),
            jnp.asarray(np.asarray(qsh, np.float32)),
        )
        return StepDraft(
            "qlinear_matmul", [tensor_arg(x_name)], [m.out_tensor],
            params=params, consts=consts, kind="fused_qlinear", name=core.name,
        )

    # tiled Pallas path: fold uint8 → signed int8 and pre-pad at plan time
    # (the uint8 bias fold and the K/N padding are both batch-independent,
    # so they belong to the template either way)
    if ga.dtype(x_name) == "uint8":
        b = np.asarray(kops.fold_uint8_input(jnp.asarray(w), None if b is None else jnp.asarray(b)))
        params["x_uint8"] = True
    if compiler.batch == "dynamic":
        # axis-open template: leave the axis-dependent (m, bm) binding to
        # per-bucket-combination specialization (specialize_plan / PlanCache)
        consts, shape = kops.template_qmatmul_params(
            w, b, qs, np.asarray(qsh, np.float32), weight_bits=weight_bits
        )
        shape["lead"] = _symbolic_lead(ga.shape(x_name))
        params["shape"] = shape
        params["dynamic_batch"] = True
    else:
        consts, shape = kops.specialize_qmatmul_params(
            w, b, qs, np.asarray(qsh, np.float32),
            m=_static_m(ga.shape(x_name)), weight_bits=weight_bits,
        )
        params["shape"] = shape
    return StepDraft(
        "qlinear_matmul", [tensor_arg(x_name)], [m.out_tensor],
        params=params, consts=consts, kind="fused_qlinear", name=core.name,
    )


def _build_lut(compiler: "Compiler", m: Match) -> StepDraft:
    """Lower a LUT_PATTERN match onto the exact 256-entry VMEM LUT."""
    ga = compiler.analysis
    in_scale, _ = ql_params(ga, m.node("dql"))
    out_scale, out_zp = ql_params(ga, m.node("ql"))
    compute_dtype = "float16" if m.node("to16") is not None else "float32"
    out_dtype = str(np.asarray(out_zp).dtype)
    act = m.node("act").op_type

    lut = build_lut(_NP_ACT[act], float(in_scale), float(out_scale), out_dtype, compute_dtype)
    return StepDraft(
        "qact_lut", [tensor_arg(m.node("dql").inputs[0])], [m.out_tensor],
        params={"act": act, "out_dtype": out_dtype}, consts=(jnp.asarray(lut),),
        kind="fused_lut", name=m.node("act").name,
    )


#: The compiler's fusion table: (declarative pattern, plan-step builder).
#: New fusions plug in here — describe the chain as data, lower in a builder.
FUSIONS = (
    (QLINEAR_PATTERN, _build_qlinear),
    (GEMM_PATTERN, _build_qlinear),
    (LUT_PATTERN, _build_lut),
    (QLINEAR_F32_PATTERN, _build_qlinear),
)


# ---------------------------------------------------------------------------
# declarative DAG regions (repro.passes.rewrite.Region): routed experts, RMSNorm
# ---------------------------------------------------------------------------


def _is_round_clip_i8(ga: GraphAnalysis, node: Node) -> bool:
    return _is_round_clip_ql(ga, node) and ga.dtype(node.outputs[0]) == "int8"


def _stacked_weight(ga: GraphAnalysis, node: Node) -> bool:
    w = ga.const(node.inputs[1])
    return w is not None and w.ndim == 3 and w.dtype == np.int8


def _expert_proj(name: str, rows: str):
    return (
        NodeSpec(f"{name}_acc", "MatMulInteger", (rows, f"#w_{name}"), where=_stacked_weight),
        NodeSpec(f"{name}_f", "Cast", (f"@{name}_acc",), attrs={"to": "float32"}),
        NodeSpec(f"{name}_s", "Mul", (f"@{name}_f", f"#r_{name}")),
    )


#: The routed-expert region ``repro.core.patterns.emit_moe_experts`` emits:
#: every expert's SwiGLU on every row, weighted by the router's probability
#: where chosen, contributions in fixed point summed over the experts.
#: Fused onto the grouped ``qmoe`` kernel, which computes only the chosen
#: experts — the same result, since an unchosen expert adds exactly 0.
QMOE_REGION = Region(
    "qmoe",
    (
        NodeSpec("rows", "Unsqueeze", ("$x", "#rows_axes")),
        *_expert_proj("gate", "@rows"),
        NodeSpec("gate_q", "QuantizeLinear", ("@gate_s", "#gate_ql_s", "#gate_ql_zp"), where=_is_round_clip_i8),
        *_expert_proj("up", "@rows"),
        NodeSpec("up_q", "QuantizeLinear", ("@up_s", "#up_ql_s", "#up_ql_zp"), where=_is_round_clip_i8),
        NodeSpec("gf", "Cast", ("@gate_q",), attrs={"to": "float32"}),
        NodeSpec("gx", "Mul", ("@gf", "#s_g")),
        NodeSpec("sig", "Sigmoid", ("@gx",)),
        NodeSpec("silu", "Mul", ("@gx", "@sig")),
        NodeSpec("uf", "Cast", ("@up_q",), attrs={"to": "float32"}),
        NodeSpec("prod", "Mul", ("@silu", "@uf")),
        NodeSpec("prod_s", "Mul", ("@prod", "#r_h")),
        NodeSpec("h", "QuantizeLinear", ("@prod_s", "#h_ql_s", "#h_ql_zp"), where=_is_round_clip_i8),
        *_expert_proj("down", "@h"),
        NodeSpec("hot", "OneHot", ("$idx", "#depth", "#hot_values"), attrs={"axis": -1}),
        NodeSpec("chosen", "ReduceSum", ("@hot",), attrs={"axes": [2], "keepdims": 0}),
        NodeSpec("weights", "Mul", ("$probs", "@chosen")),
        NodeSpec("weights5", "Unsqueeze", ("@weights", "#weights_axes")),
        NodeSpec("weighted", "Mul", ("@down_s", "@weights5")),
        NodeSpec("fixed", "Mul", ("@weighted", "#fixed")),
        NodeSpec("clipped", "Clip", ("@fixed", "#lo", "#hi")),
        NodeSpec("rounded", "Round", ("@clipped",)),
        NodeSpec("contrib", "Cast", ("@rounded",), attrs={"to": "int32"}),
        NodeSpec("sum", "ReduceSum", ("@contrib",), attrs={"axes": [2, 3], "keepdims": 0}),
    ),
)


def _scalar(c) -> Optional[float]:
    c = np.asarray(c)
    return float(c.reshape(())) if c.size == 1 and c.dtype == np.float32 else None


#: Device copies of stacked expert weights, keyed by the ids of their host
#: arrays (and the lane): a prefill and a decode plan compiled from the same
#: weights hold one copy.  An entry goes when its host gate array does.
_EXPERT_CONSTS: Dict[tuple, Tuple[tuple, tuple]] = {}


def _expert_consts(sources, dense: bool, bits: int) -> tuple:
    """The ``qmoe`` step's weight consts from the host ``(gate, up, down)``:
    as they are for the dense ``ref`` oracle, else zero-padded to 128-lane
    multiples (the down projection packed two per byte on the w4 lane)."""
    key = tuple(id(a) for a in sources) + (dense, bits)
    hit = _EXPERT_CONSTS.get(key)
    if hit is not None and all(r() is a for r, a in zip(hit[0], sources)):
        return hit[1]
    wg, wu, wd = (np.asarray(a) for a in sources)
    if dense:
        consts = (jnp.asarray(wg), jnp.asarray(wu), jnp.asarray(wd))
    else:
        e, d, f = wg.shape
        dp, fp = -(-d // 128) * 128, -(-f // 128) * 128
        gp = np.zeros((e, dp, fp), np.int8)
        up = np.zeros((e, dp, fp), np.int8)
        dn = np.zeros((e, fp, dp), np.int8)
        gp[:, :d, :f], up[:, :d, :f], dn[:, :f, :d] = wg, wu, wd
        if bits == 4:
            dn = np.stack([_pack.pack_int4(w) for w in dn])
        consts = (jnp.asarray(gp), jnp.asarray(up), jnp.asarray(dn))
    try:
        refs = tuple(weakref.ref(a) for a in sources)
    except TypeError:  # not weakly referable: no sharing
        return consts
    _EXPERT_CONSTS[key] = (refs, consts)
    weakref.finalize(sources[0], _EXPERT_CONSTS.pop, key, None)
    return consts


def _build_qmoe(compiler: "Compiler", m: RegionMatch) -> Optional[StepDraft]:
    """Lower a matched routed-expert region onto the grouped ``qmoe``
    kernel.  The region's constants must be the codified ones (axes, the
    {0, 1} one-hot, the fixed-point step and clip); the rescales ride in
    ``params`` (static under jit), the stacked weights in ``consts``: as
    they are for the ``ref`` oracle, zero-padded to 128-lane multiples
    (with the down projection packed two per byte on the w4 lane) for the
    tiled kernel."""
    c = m.consts
    wg, wu, wd = (np.asarray(c[k]) for k in ("w_gate", "w_up", "w_down"))
    e, d, f = wg.shape
    if wu.shape != (e, d, f) or wd.shape != (e, f, d) or wd.dtype != np.int8:
        return None
    if [int(a) for a in np.asarray(c["rows_axes"]).reshape(-1)] != [2, 3]:
        return None
    if [int(a) for a in np.asarray(c["weights_axes"]).reshape(-1)] != [3, 4]:
        return None
    if int(np.asarray(c["depth"]).reshape(-1)[0]) != e:
        return None
    if np.asarray(c["hot_values"]).tolist() != [0.0, 1.0]:
        return None
    if (_scalar(c["fixed"]), _scalar(c["lo"]), _scalar(c["hi"])) != (MOE_FIXED, -MOE_CLIP, MOE_CLIP):
        return None
    scales = {k: _scalar(c[k]) for k in ("r_gate", "s_g", "r_up", "r_h", "r_down")}
    if any(v is None for v in scales.values()):
        return None
    bits = int(m.nodes["down_acc"].attrs.get("weight_bits", 8))
    params = {
        "d": int(d), "r_g": scales["r_gate"], "s_g": scales["s_g"], "r_u": scales["r_up"],
        "r_h": scales["r_h"], "r_d": scales["r_down"], "down_bits": bits,
    }
    sources = (c["w_gate"], c["w_up"], c["w_down"])
    consts = _expert_consts(sources, compiler.backend == "ref", bits)
    return StepDraft(
        "qmoe",
        [tensor_arg(m.inputs["x"]), tensor_arg(m.inputs["idx"]), tensor_arg(m.inputs["probs"])],
        [m.out_tensor],
        params=params, consts=consts, kind="fused_qmoe", name=m.nodes["gate_acc"].name,
    )


def _reduces_last_axis(ga: GraphAnalysis, node: Node) -> bool:
    shape = ga.shape(node.inputs[0])
    axes = node.attrs.get("axes")
    return shape is not None and axes is not None and [int(a) % len(shape) for a in axes] == [len(shape) - 1]


#: The RMSNorm region ``repro.core.patterns.emit_rmsnorm`` emits, fused onto
#: one step whose square root and division are the nearest f32 on every
#: backend (a TPU's own are not correctly rounded).
RMSNORM_REGION = Region(
    "rmsnorm",
    (
        NodeSpec("xi", "Cast", ("$x",), attrs={"to": "int32"}),
        NodeSpec("sq", "Mul", ("@xi", "@xi")),
        NodeSpec("ss", "ReduceSum", ("@sq",), attrs={"keepdims": 1}, where=_reduces_last_axis),
        NodeSpec("ssf", "Cast", ("@ss",), attrs={"to": "float32"}),
        NodeSpec("ms", "Mul", ("@ssf", "#inv_d")),
        NodeSpec("var", "Add", ("@ms", "#eps")),
        NodeSpec("rms", "Sqrt", ("@var",)),
        NodeSpec("xf", "Cast", ("$x",), attrs={"to": "float32"}),
        NodeSpec("unit", "Div", ("@xf", "@rms")),
        NodeSpec("scaled", "Mul", ("@unit", "#gain")),
        NodeSpec("out", "QuantizeLinear", ("@scaled", "#ql_s", "#ql_zp"), where=_is_round_clip_i8),
    ),
)


def _build_rmsnorm(compiler: "Compiler", m: RegionMatch) -> Optional[StepDraft]:
    c = m.consts
    inv_d, eps = _scalar(c["inv_d"]), _scalar(c["eps"])
    gain = np.asarray(c["gain"])
    d = (compiler.analysis.shape(m.inputs["x"]) or (None,))[-1]
    if inv_d is None or eps is None or gain.dtype != np.float32 or gain.shape != (d,):
        return None
    if compiler.analysis.dtype(m.inputs["x"]) != "int8":
        return None
    return StepDraft(
        "rmsnorm", [tensor_arg(m.inputs["x"])], [m.out_tensor],
        params={"inv_d": inv_d, "eps": eps}, consts=(jnp.asarray(gain),),
        kind="fused_norm", name=m.nodes["xi"].name,
    )


def _softmax_last_axis(ga: GraphAnalysis, node: Node) -> bool:
    shape = ga.shape(node.inputs[0])
    return shape is not None and int(node.attrs.get("axis", -1)) in (-1, len(shape) - 1)


#: The router's softmax ``repro.core.patterns.emit_router`` emits (its int32
#: logits in f32, times the router's scale, normalized over the experts),
#: fused onto one step that sums in expert order and takes the nearest f32
#: quotients (:func:`repro.kernels.ref.softmax_rn`): the same weights at
#: every shape the plan is specialized to, where XLA's own softmax reduces
#: in a shape-dependent order.
ROUTER_SOFTMAX_REGION = Region(
    "router_softmax",
    (
        NodeSpec("f", "Cast", ("$logits",), attrs={"to": "float32"}),
        NodeSpec("scaled", "Mul", ("@f", "#scale")),
        NodeSpec("probs", "Softmax", ("@scaled",), where=_softmax_last_axis),
    ),
)


def _build_router_softmax(compiler: "Compiler", m: RegionMatch) -> Optional[StepDraft]:
    scale = _scalar(m.consts["scale"])
    if scale is None or compiler.analysis.dtype(m.inputs["logits"]) != "int32":
        return None
    return StepDraft(
        "softmax_rn", [tensor_arg(m.inputs["logits"])], [m.out_tensor],
        params={"scale": scale}, kind="fused_router", name=m.nodes["f"].name,
    )


#: DAG regions fused at their sinks: (region, step builder).
REGIONS = (
    (QMOE_REGION, _build_qmoe),
    (RMSNORM_REGION, _build_rmsnorm),
    (ROUTER_SOFTMAX_REGION, _build_router_softmax),
)


# ---------------------------------------------------------------------------
# fused int8 attention: a DAG region, matched programmatically
# ---------------------------------------------------------------------------
#
# The ~25-node attention region emitted by repro.core.patterns.emit_qattention
# is a DAG, not a single-consumer chain (the mask fans into three nodes, the
# masked scores fan into ReduceMax and Sub, the LUT weights fan into the
# numerator and denominator branches), so the declarative chain matcher
# cannot describe it.  _match_qattention walks the emitted structure
# explicitly, anchored on the score MatMulInteger — the only MatMulInteger
# whose *both* operands are non-const, which is also what keeps it disjoint
# from QLINEAR_PATTERN's constant-weight anchor.


def _f32_scalar(ga: GraphAnalysis, name: str) -> Optional[float]:
    c = ga.const(name)
    if c is None:
        return None
    c = np.asarray(c)
    if c.size != 1 or c.dtype != np.float32:
        return None
    return float(c.reshape(()))


def _scalar_operand(ga: GraphAnalysis, node: Node, data: str) -> Optional[float]:
    """The f32 scalar constant operand of a binary node whose other operand
    is ``data`` (either position)."""
    ins = list(node.inputs)
    if data not in ins:
        return None
    other = ins[1] if ins[0] == data else ins[0]
    return _f32_scalar(ga, other)


def _is_zero_zp_ql(ga: GraphAnalysis, node: Node, scale: Optional[float], dtype: str = "int8") -> bool:
    """QuantizeLinear with the given scalar scale (None = any scalar) and a
    zero zero-point of the given dtype."""
    s, zp = ql_params(ga, node)
    if s is None or zp is None or np.asarray(s).size != 1 or np.asarray(zp).size != 1:
        return False
    if scale is not None and float(np.asarray(s)) != scale:
        return False
    return str(np.asarray(zp).dtype) == dtype and int(np.asarray(zp)) == 0


def _match_qattention(ga: GraphAnalysis, anchor: Node) -> Optional[dict]:
    """Match the codified int8 attention region rooted at its score
    MatMulInteger.  Strict by construction: every internal tensor must be
    consumed only inside the region (single_consumer, or the exact expected
    fan-out for the mask / masked-scores / LUT-weight tensors), every
    epilogue constant must be the expected scalar, and the LUT must satisfy
    ``lut[0] == 0`` — the property zero-padding exactness rests on.  Returns
    the capture dict for :func:`_build_qattention`, or None."""

    def nxt(tensor: str, op: str) -> Optional[Node]:
        n = ga.single_consumer(tensor)
        return n if n is not None and n.op_type == op else None

    if anchor.op_type != "MatMulInteger" or len(anchor.inputs) != 2:
        return None
    q, kt = anchor.inputs
    if ga.is_const(q) or ga.is_const(kt):
        return None
    tr = ga.producers.get(kt)
    if tr is None or tr.op_type != "Transpose" or ga.single_consumer(kt) is not anchor:
        return None
    if list(tr.attrs.get("perm", [])) != [0, 2, 1]:
        return None
    k = tr.inputs[0]
    if ga.dtype(q) != "int8" or ga.dtype(k) != "int8":
        return None

    cast1 = nxt(anchor.outputs[0], "Cast")
    if cast1 is None or cast1.attrs.get("to") != "float32":
        return None
    mul_c = nxt(cast1.outputs[0], "Mul")
    if mul_c is None:
        return None
    qk_scale = _scalar_operand(ga, mul_c, cast1.outputs[0])
    if qk_scale is None:
        return None
    sm = nxt(mul_c.outputs[0], "Mul")
    if sm is None:
        return None
    mask = sm.inputs[1] if sm.inputs[0] == mul_c.outputs[0] else sm.inputs[0]
    if ga.is_const(mask) or ga.dtype(mask) != "float32":
        return None
    masked = nxt(sm.outputs[0], "Add")
    if masked is None:
        return None
    pen_t = masked.inputs[1] if masked.inputs[0] == sm.outputs[0] else masked.inputs[0]
    pen = ga.producers.get(pen_t)
    if pen is None or pen.op_type != "Mul" or ga.single_consumer(pen_t) is not masked:
        return None
    sub1_t = pen.inputs[0] if _f32_scalar(ga, pen.inputs[1]) is not None else pen.inputs[1]
    big = _scalar_operand(ga, pen, sub1_t)
    sub1 = ga.producers.get(sub1_t)
    if big is None or sub1 is None or sub1.op_type != "Sub":
        return None
    if ga.single_consumer(sub1_t) is not pen:
        return None
    if sub1.inputs[0] != mask or _f32_scalar(ga, sub1.inputs[1]) != 1.0:
        return None

    # masked scores fan into exactly {ReduceMax, Sub}
    mt = masked.outputs[0]
    cons = ga.consumers.get(mt, [])
    if mt in ga.out_names or len(cons) != 2:
        return None
    mx = next((n for n in cons if n.op_type == "ReduceMax"), None)
    d = next((n for n in cons if n.op_type == "Sub"), None)
    if mx is None or d is None:
        return None
    if list(mx.attrs.get("axes", [])) != [2] or not mx.attrs.get("keepdims", 1):
        return None
    if ga.single_consumer(mx.outputs[0]) is not d or list(d.inputs) != [mt, mx.outputs[0]]:
        return None

    dq = nxt(d.outputs[0], "QuantizeLinear")
    if dq is None or not _is_zero_zp_ql(ga, dq, None, "int8"):
        return None
    lut_scale = float(np.asarray(ga.const(dq.inputs[1])))
    idx32 = nxt(dq.outputs[0], "Cast")
    if idx32 is None or idx32.attrs.get("to") != "int32":
        return None
    idxadd = nxt(idx32.outputs[0], "Add")
    if idxadd is None:
        return None
    off_t = idxadd.inputs[1] if idxadd.inputs[0] == idx32.outputs[0] else idxadd.inputs[0]
    off = ga.const(off_t)
    if off is None or np.asarray(off).size != 1 or int(np.asarray(off)) != 128:
        return None
    gather = nxt(idxadd.outputs[0], "Gather")
    if gather is None or int(gather.attrs.get("axis", 0)) != 0:
        return None
    lut = ga.const(gather.inputs[0])
    if lut is None or lut.shape != (256,) or lut.dtype != np.uint8 or lut[0] != 0:
        return None

    # LUT weights fan into exactly the int32 (denominator) and f32
    # (numerator) casts
    wt = gather.outputs[0]
    wcons = ga.consumers.get(wt, [])
    if wt in ga.out_names or len(wcons) != 2 or any(n.op_type != "Cast" for n in wcons):
        return None
    wi = next((n for n in wcons if n.attrs.get("to") == "int32"), None)
    wf = next((n for n in wcons if n.attrs.get("to") == "float32"), None)
    if wi is None or wf is None:
        return None
    den = nxt(wi.outputs[0], "ReduceSum")
    if den is None or list(den.attrs.get("axes", [])) != [2] or not den.attrs.get("keepdims", 1):
        return None
    denf = nxt(den.outputs[0], "Cast")
    if denf is None or denf.attrs.get("to") != "float32":
        return None
    p = nxt(wf.outputs[0], "Div")
    if p is None or ga.single_consumer(denf.outputs[0]) is not p:
        return None
    if list(p.inputs) != [wf.outputs[0], denf.outputs[0]]:
        return None
    pmul = nxt(p.outputs[0], "Mul")
    if pmul is None:
        return None
    p_scale = _scalar_operand(ga, pmul, p.outputs[0])
    if p_scale is None:
        return None
    pq = nxt(pmul.outputs[0], "QuantizeLinear")
    if pq is None or not _is_zero_zp_ql(ga, pq, 1.0, "int8"):
        return None

    ctx = nxt(pq.outputs[0], "MatMulInteger")
    if ctx is None or ctx.inputs[0] != pq.outputs[0]:
        return None
    v = ctx.inputs[1]
    if ga.is_const(v) or ga.dtype(v) != "int8":
        return None
    cf = nxt(ctx.outputs[0], "Cast")
    if cf is None or cf.attrs.get("to") != "float32":
        return None
    cmul = nxt(cf.outputs[0], "Mul")
    if cmul is None:
        return None
    rescale = _scalar_operand(ga, cmul, cf.outputs[0])
    if rescale is None:
        return None
    out_ql = ga.single_consumer(cmul.outputs[0])
    if out_ql is None or out_ql.op_type != "QuantizeLinear":
        return None
    s_out, zp_out = ql_params(ga, out_ql)
    if (
        s_out is None or zp_out is None or np.asarray(s_out).size != 1
        or float(np.asarray(s_out)) != 1.0 or int(np.asarray(zp_out)) != 0
    ):
        return None

    sq, sk = ga.shape(q), ga.shape(k)
    if sq is None or sk is None or len(sq) != 3 or len(sk) != 3:
        return None
    if not isinstance(sq[2], int):
        return None
    nodes = (
        tr, anchor, cast1, mul_c, sm, sub1, pen, masked, mx, d, dq, idx32,
        idxadd, gather, wi, den, denf, wf, p, pmul, pq, ctx, cf, cmul, out_ql,
    )
    return {
        "nodes": nodes,
        "q": q, "k": k, "v": v, "mask": mask,
        "out": out_ql.outputs[0],
        "out_dtype": str(np.asarray(zp_out).dtype),
        "qk_scale": qk_scale, "big": big, "lut_scale": lut_scale,
        "p_scale": p_scale, "rescale": rescale, "lut": lut,
        "b": tuple(sq[:1]), "s": sq[1], "t": sk[1], "dh": int(sq[2]),
        "anchor": anchor,
    }


def qattention_exempt_nodes(ga: GraphAnalysis) -> frozenset:
    """Names of every node inside a matched attention region — the regions
    the per-axis elementwise proof skips (see
    :func:`repro.passes.analysis.axis_mixing_nodes`).  The skip is sound
    because the region's own masking semantics make zero padding exact along
    any axis: a zero-padded key carries a zero mask, its score is driven to
    −big, and its LUT weight is exactly ``lut[0] == 0`` (the matcher checks
    this), so padded positions contribute nothing to the softmax denominator
    or the context; padded query rows produce finite garbage (the
    denominator can never be 0) that run-time slicing discards."""
    exempt = set()
    for node in ga.graph.nodes:
        if node.op_type != "MatMulInteger":
            continue
        m = _match_qattention(ga, node)
        if m is not None:
            exempt.update(n.name for n in m["nodes"])
    return frozenset(exempt)


def _build_qattention(compiler: "Compiler", m: dict) -> Optional[StepDraft]:
    """Lower a matched attention region onto the fused ``qattention`` kernel.
    Scalar constants ride in ``params`` (static under jit); the LUT is the
    step's one array const.  With dynamic axes the shape record stays open
    (``dynamic_attn``) and is bound per bucket by ``specialize_plan``; a
    static compile with symbolic dims falls back unfused instead."""
    shape = {"b": m["b"], "s": m["s"], "t": m["t"], "dh": m["dh"]}
    params = {
        "out_dtype": m["out_dtype"],
        "qk_scale": m["qk_scale"], "big": m["big"],
        "lut_scale": m["lut_scale"], "p_scale": m["p_scale"],
        "rescale": m["rescale"],
    }
    if compiler.batch == "dynamic":
        params["shape"] = shape
        params["dynamic_attn"] = True
    else:
        dims = list(m["b"]) + [m["s"], m["t"]]
        if not all(isinstance(d, int) for d in dims):
            return None  # symbolic dims without dynamic axes: stay unfused
        params["shape"] = kops.bind_qattention_axes(shape, {})
    return StepDraft(
        "qattention",
        [tensor_arg(m["q"]), tensor_arg(m["k"]), tensor_arg(m["v"]), tensor_arg(m["mask"])],
        [m["out"]],
        params=params, consts=(jnp.asarray(m["lut"]),),
        kind="fused_qattention", name=m["anchor"].name,
    )


class Compiler:
    def __init__(
        self,
        model: Model,
        *,
        backend: str = "ref",
        fuse: bool = True,
        optimize: bool = True,
        verify_passes: bool = False,
        batch: str = "static",
        dynamic_axes: Optional[Dict[str, object]] = None,
        plan_cache_capacity: int = PlanCache.DEFAULT_CAPACITY,
        plan_cache: Optional[PlanCache] = None,
        autotune=None,
    ) -> None:
        model.validate()
        self.autotuner = _resolve_autotuner(autotune)
        if batch not in ("static", "dynamic"):
            raise ValueError(f"batch must be 'static' or 'dynamic', got {batch!r}")
        if batch == "dynamic" and dynamic_axes is None:
            # PR 4 sugar: dynamic over the (implicit or named) batch axis
            dynamic_axes = {BATCH_AXIS: None}
        if dynamic_axes:
            batch = "dynamic"
        available = graph_axes(model.graph)
        if batch == "dynamic":
            missing = sorted(set(dynamic_axes) - set(available))
            if missing:
                raise ValueError(
                    f"dynamic axes {missing} are not symbolic in any graph input "
                    f"signature (available: {list(available) or 'none'}) — "
                    "declare them as named dims, e.g. ('N', 'S', 64), or use a "
                    "(None, ...) leading dim for the implicit batch axis"
                )
            # an axis may appear at several positions of one signature (an
            # attention mask is ("N", "S", "S")): run-time padding/slicing
            # handles every occurrence (axis_input_positions below)
        if optimize:
            model, self.pass_report = PassManager(verify=verify_passes).run(model)
        else:
            self.pass_report = PipelineReport(
                nodes_before=len(model.graph.nodes), nodes_after=len(model.graph.nodes)
            )
        # provenance: the how-this-plan-came-to-be record the plan will carry
        tracer = _trace.current()
        self.provenance = PlanProvenance(
            nodes_before=self.pass_report.nodes_before,
            nodes_after=self.pass_report.nodes_after,
            pass_iterations=self.pass_report.iterations,
            trace_id=tracer.trace_id if tracer is not None else None,
        )
        for e in self.pass_report.entries:
            if e.changed:
                self.provenance.add_pass(e.iteration, e.name, e.counters)
        self.model = model
        self.graph = model.graph
        self.backend = backend
        self.fuse = fuse
        self.batch = batch
        # preserve the graph's axis declaration order for stable plan axes
        if batch == "dynamic":
            self.dynamic_axes = {
                a: resolve_bucketing(dynamic_axes.get(a)) for a in available if a in dynamic_axes
            }
            # raw (pre-resolution) bucketing specs: what an AOT artifact
            # serializes, since the resolved policies are callables
            self.axis_specs = {
                a: dynamic_axes.get(a) for a in available if a in dynamic_axes
            }
        else:
            self.dynamic_axes = {}
            self.axis_specs = {}
        self.plan_cache_capacity = plan_cache_capacity
        self.plan_cache = plan_cache
        self.inits = {k: v for k, v in self.graph.initializers.items()}
        self.analysis = GraphAnalysis(self.graph)
        if batch == "dynamic":
            # zero padding along a dynamic axis is only exact when no op
            # mixes information across it — prove each requested axis
            # independently and reject (rather than silently mis-serve)
            # graphs with e.g. a global ReduceMean or an axis-folding Reshape.
            # Matched attention regions are exempt: their masking semantics
            # make zero padding exact by construction (the region reduces
            # over keys whose padded LUT weight is exactly 0 — see
            # qattention_exempt_nodes), which the per-op proof cannot see.
            implicit = implicit_batch_graph(self.graph)
            exempt = qattention_exempt_nodes(self.analysis)
            for axis in self.dynamic_axes:
                problems = axis_mixing_nodes(
                    self.analysis, axis, implicit=implicit, exempt=exempt
                )
                if problems:
                    raise ValueError(
                        f"dynamic axis {axis!r} needs every op to be "
                        "batch-elementwise along it; cannot prove that for:\n  "
                        + "\n  ".join(problems)
                        + "\ncompile with batch='static' instead"
                    )
        self.stats = {
            "fused_qlinear": 0,
            "fused_qconv": 0,
            "fused_lut": 0,
            "fused_qattention": 0,
            "fused_qmoe": 0,
            "fused_norm": 0,
            "fused_router": 0,
            "generic": 0,
            "folded": self.pass_report.total("folded"),
            "eliminated": self.pass_report.total("eliminated"),
        }

    # -- main ---------------------------------------------------------------
    def compile(self) -> "CompiledModel":
        order = self.graph.toposorted()
        consumed = set()
        drafts: List[StepDraft] = []
        # attention regions are DAGs whose members straddle the anchor in
        # topo order (the K-Transpose precedes it, V may be produced after
        # it): match them up front, skip members as they stream past, and
        # emit the fused step at the region's sink — the one position where
        # every region input is guaranteed already produced
        attn_emit, attn_skip = ({}, set())
        if self.fuse:
            attn_emit, attn_skip = self._qattention_regions()
            region_emit, region_skip = self._dag_regions()
            attn_emit.update(region_emit)
            attn_skip |= region_skip
        with _trace.span("compile.fuse", nodes=len(order)) as fuse_span:
            for node in order:
                if id(node) in consumed or id(node) in attn_skip:
                    continue
                if id(node) in attn_emit:
                    draft = attn_emit[id(node)]
                else:
                    draft = self._fused_draft(node, consumed) if self.fuse else None
                    if draft is None:
                        draft = self._generic_draft(node)
                drafts.append(draft)
                self.stats[draft.kind] += 1
            fuse_span.set(
                fused=len(self.provenance.fusions),
                generic=self.stats["generic"],
            )
        with _trace.span("compile.lower", steps=len(drafts)) as lower_span:
            plan = build_plan(
                self.graph, self.analysis, drafts, self.backend,
                batch=self.batch, axes=tuple(self.dynamic_axes),
                provenance=self.provenance,
            )
            lower_span.set(slots=plan.num_slots)
        self.stats["plan_slots"] = plan.num_slots
        return CompiledModel(
            self.model, plan, self.stats, self.pass_report,
            plan_cache_capacity=self.plan_cache_capacity,
            plan_cache=self.plan_cache,
            dynamic_axes=self.dynamic_axes,
            axis_specs=self.axis_specs,
            autotuner=self.autotuner,
        )

    def _qattention_regions(self):
        """Match every attention region once, up front.  Returns
        ``(emit, skip)``: ``emit`` maps the id of each region's sink node
        (its final QuantizeLinear — last in any topo order, since every
        other member is its ancestor) to the fused StepDraft; ``skip`` holds
        the ids of all other member nodes."""
        emit: Dict[int, StepDraft] = {}
        skip: set = set()
        for node in self.graph.nodes:
            if node.op_type != "MatMulInteger":
                continue
            qm = _match_qattention(self.analysis, node)
            if qm is None:
                continue
            draft = _build_qattention(self, qm)
            if draft is None:
                continue
            sink = qm["nodes"][-1]
            emit[id(sink)] = draft
            skip.update(id(n) for n in qm["nodes"] if n is not sink)
            self.provenance.add_fusion(
                "qattention", node.name,
                tuple(n.name for n in qm["nodes"]), qm["out"],
            )
        return emit, skip

    def _dag_regions(self):
        """Match every declarative region of :data:`REGIONS` at its sink,
        as :meth:`_qattention_regions` does for attention: ``emit`` maps the
        sink's id to the fused step, ``skip`` holds the other members."""
        emit: Dict[int, StepDraft] = {}
        skip: set = set()
        for region, builder in REGIONS:
            for node in self.graph.nodes:
                if node.op_type != region.sink.op or id(node) in emit or id(node) in skip:
                    continue
                rm = match_region(self.analysis, node, region)
                if rm is None:
                    continue
                draft = builder(self, rm)
                if draft is None:
                    continue
                emit[id(node)] = draft
                members = rm.members()
                skip.update(id(n) for n in members if n is not node)
                self.provenance.add_fusion(
                    region.name, draft.name, tuple(n.name for n in members), rm.out_tensor,
                )
        return emit, skip

    def _fused_draft(self, node: Node, consumed: set) -> Optional[StepDraft]:
        for pattern, builder in FUSIONS:
            if node.op_type not in pattern.anchor_ops:
                continue
            m = match_chain(self.analysis, node, pattern)
            if m is None:
                continue
            draft = builder(self, m)
            if draft is None:
                continue
            consumed.update(id(n) for n in m.nodes)
            self.provenance.add_fusion(
                pattern.name, m.anchor.name,
                tuple(n.name for n in m.nodes), m.out_tensor,
            )
            return draft
        return None

    def _generic_draft(self, node: Node) -> StepDraft:
        if node.op_type not in _JOPS:
            raise NotImplementedError(f"compiler has no lowering for op {node.op_type!r}")
        args = []
        for name in node.inputs:
            if not name:
                args.append(none_arg())
            elif name in self.inits:
                args.append(const_arg(np.asarray(self.inits[name])))
            else:
                args.append(tensor_arg(name))
        return StepDraft(
            f"op.{node.op_type}", args, list(node.outputs),
            params={"attrs": node.attrs}, kind="generic", name=node.name,
        )


class CompiledModel:
    """A compiled artifact: typed ExecutionPlan + jitted slot-indexed
    executor + fusion report.  ``print(cm.plan)`` shows the full lowering.

    With dynamic axes the held plan is a shape-generic *template*:
    :meth:`run` reads each dynamic axis's true extent off the feeds, pads
    every axis-carrying feed to that axis's bucket (per-axis bucketing
    policy — power-of-two by default), binds the template to the bucket
    combination through a bounded :class:`~repro.backend.plan.PlanCache`
    keyed on the sorted bindings (at most one specialization and one jit
    trace per resident combination), executes, and slices results back to
    the true extents along every axis position they carry.  Zero padding is
    exact because dynamic compilation *proves* it per axis: the compiler
    rejects any graph with an op it cannot show to be elementwise along each
    requested axis (:func:`repro.passes.analysis.axis_mixing_nodes`), and
    the conformance sweep pins dynamic == per-shape-static == reference,
    bit for bit, over the whole bucket grid."""

    def __init__(
        self,
        model: Model,
        plan: ExecutionPlan,
        stats: Dict[str, int],
        pass_report: Optional[PipelineReport] = None,
        *,
        plan_cache_capacity: int = PlanCache.DEFAULT_CAPACITY,
        plan_cache: Optional[PlanCache] = None,
        dynamic_axes: Optional[Dict[str, object]] = None,
        axis_specs: Optional[Dict[str, object]] = None,
        autotuner=None,
    ) -> None:
        self.model = model
        self.plan = plan
        #: per-axis raw bucketing specs (None / int / callable) as declared at
        #: compile time — the serializable counterpart of ``dynamic_axes``,
        #: whose values are already-resolved policy callables
        self.plan_cache_capacity = plan_cache_capacity
        if plan.batch == "dynamic":
            self.axis_specs: Dict[str, object] = (
                dict(axis_specs) if axis_specs is not None else {a: None for a in plan.axes}
            )
        else:
            self.axis_specs = {}
        #: optional repro.backend.autotune.Autotuner — when set, every lazy
        #: specialization routes its tile choice through the measured search
        self.autotuner = autotuner
        self.steps = plan.steps
        self.stats = stats
        self.pass_report = pass_report if pass_report is not None else PipelineReport()
        self.input_names = [t.name for t in model.graph.inputs]
        self.output_names = [t.name for t in model.graph.outputs]
        if plan.batch == "dynamic":
            # a shared cache (plan_cache=) pools specializations across
            # several artifacts (e.g. a prefill and a decode plan serving one
            # token path); cache_key() then prefixes the graph name so the
            # artifacts never collide on identical bindings
            self._shared_cache = plan_cache is not None
            self.plan_cache: Optional[PlanCache] = (
                plan_cache if plan_cache is not None
                else PlanCache(plan_cache_capacity, scope="plan")
            )
            self.dynamic_axes: Dict[str, object] = {
                a: resolve_bucketing(None) for a in plan.axes
            }
            if dynamic_axes:
                self.dynamic_axes.update(dynamic_axes)
            implicit = implicit_batch_graph(model.graph)
            # where each dynamic axis sits in each input: axis -> {input:
            # (pos, ...)} — every occurrence (a mask signature like
            # ("N", "S", "S") carries an axis twice and every position must
            # be padded); the single-int *_pos views keep the first position
            # for backward compatibility
            self.axis_input_positions: Dict[str, Dict[str, tuple]] = {}
            for axis in self.dynamic_axes:
                by_input = {}
                for t in model.graph.inputs:
                    pos = axis_positions(tuple(t.shape), axis, implicit=implicit)
                    if pos:
                        by_input[t.name] = pos
                self.axis_input_positions[axis] = by_input
            self.axis_input_pos: Dict[str, Dict[str, int]] = {
                axis: {name: pos[0] for name, pos in by_input.items()}
                for axis, by_input in self.axis_input_positions.items()
            }
            # axis-carrying outputs get sliced back to the true extents;
            # positions come from the declared signature with the plan's
            # inferred value shapes as fallback, so an output mis-declared
            # with a concrete dim is still recognized as axis-carrying
            inferred = {
                name: info.shape
                for step in plan.steps
                for name, info in zip(step.outputs, step.out_info)
            }
            self.output_axis_positions: Dict[str, Dict[str, tuple]] = {}
            for t in model.graph.outputs:
                by_axis = {}
                for axis in self.dynamic_axes:
                    pos = axis_positions(tuple(t.shape), axis, implicit=implicit)
                    if not pos:
                        pos = axis_positions(inferred.get(t.name), axis, implicit=implicit)
                    if pos:
                        by_axis[axis] = pos
                if by_axis:
                    self.output_axis_positions[t.name] = by_axis
            self.output_axis_pos: Dict[str, Dict[str, int]] = {
                name: {axis: pos[0] for axis, pos in by_axis.items()}
                for name, by_axis in self.output_axis_positions.items()
            }
            self._jitted = None  # a template is only executable once bound
        else:
            self._shared_cache = False
            self.plan_cache = None
            self.dynamic_axes = {}
            self.axis_input_positions = {}
            self.axis_input_pos = {}
            self.output_axis_positions = {}
            self.output_axis_pos = {}
            self._jitted = self.plan.jit()

    @property
    def backend(self) -> str:
        return self.plan.backend

    @property
    def is_dynamic(self) -> bool:
        return self.plan.batch == "dynamic"

    # -- PR 4 single-axis views (the batch axis) ----------------------------
    @property
    def batch_input_names(self) -> List[str]:
        """Inputs carrying the batch axis (PR 4 compat view)."""
        return list(self.axis_input_pos.get(BATCH_AXIS, {}))

    @property
    def batch_output_names(self) -> set:
        """Outputs carrying the batch axis (PR 4 compat view)."""
        return {k for k, v in self.output_axis_pos.items() if BATCH_AXIS in v}

    def run(self, feeds: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
        if self.is_dynamic:
            return self._run_dynamic(feeds)
        with _trace.span("run.execute"):
            res = self._jitted({k: jnp.asarray(v) for k, v in feeds.items()})
            return {k: np.asarray(v) for k, v in res.items()}

    def __call__(self, **feeds) -> Dict[str, np.ndarray]:
        return self.run(feeds)

    def lower(self, feeds: Dict[str, jax.ShapeDtypeStruct]):
        if self.is_dynamic:
            raise NotImplementedError(
                "lower() needs a bound plan — use specialized(bindings) and "
                "inspect/lower the per-bucket executor instead"
            )
        return self._jitted.lower(feeds)

    # -- scenario-specialized execution -------------------------------------
    def bucket_for(self, axis: str, extent: int) -> int:
        """The padded bucket for a true extent along ``axis`` under that
        axis's bucketing policy."""
        return int(self.dynamic_axes[axis](int(extent)))

    def cache_key(self, bindings) -> tuple:
        """The plan-cache key for a bucket combination.  On a private cache
        this is exactly :func:`~repro.backend.plan.bindings_key` (existing
        keys, artifacts and tests stay valid); on a shared cache the graph
        name is prefixed so two artifacts pooling one cache (prefill +
        decode) never collide on identical bindings."""
        if not isinstance(bindings, dict):
            bindings = {BATCH_AXIS: int(bindings)}
        key = bindings_key(bindings)
        return (self.model.graph.name, key) if self._shared_cache else key

    def specialized(self, bindings):
        """The (plan, jitted executor) pair for a bucket combination,
        specializing lazily through the bounded plan cache.  ``bindings`` is
        an axis→bucket dict (a bare int is sugar for the batch axis).
        ``cache_stats`` counts a miss (== one specialization) only on first
        use of a resident combination; binding order never splits cache
        entries (keys are the sorted bindings)."""
        if not self.is_dynamic:
            raise ValueError("specialized() is only meaningful on a dynamic compile")
        if not isinstance(bindings, dict):
            bindings = {BATCH_AXIS: int(bindings)}
        unknown = sorted(set(bindings) - set(self.dynamic_axes))
        if unknown:
            raise ValueError(
                f"unknown dynamic axes {unknown}: this artifact is open over "
                f"{list(self.dynamic_axes)}"
            )
        key = self.cache_key(bindings)
        entry = self.plan_cache.get(key)
        if entry is None:
            plan = specialize_plan(self.plan, bindings, tuner=self.autotuner)
            entry = (plan, plan.jit())
            self.plan_cache.put(key, entry)
        return entry

    @property
    def cache_stats(self) -> Dict[str, int]:
        """Plan-cache counters (size/capacity/hits/misses/evictions/
        hit_rate); misses double as the number of specializations.  These
        legacy flat keys stay for one release — the canonical scheme is
        ``cache.plan.<field>`` in a :class:`~repro.obs.metrics.
        MetricsRegistry` (see :meth:`attach_metrics`)."""
        if self.plan_cache is None:
            return {}
        return self.plan_cache.stats

    def attach_metrics(self, registry: MetricsRegistry) -> None:
        """Publish this artifact's plan-cache stats into ``registry`` under
        the canonical ``cache.plan.*`` keys (live callback gauges)."""
        if self.plan_cache is not None:
            self.plan_cache.attach_metrics(registry)

    def _run_dynamic(self, feeds: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
        extents: Dict[str, int] = {}
        for axis, by_input in self.axis_input_positions.items():
            vals = {
                int(np.asarray(feeds[name]).shape[pos])
                for name, positions in by_input.items()
                if name in feeds
                for pos in positions
            }
            if len(vals) != 1:
                raise ValueError(
                    f"inputs {sorted(by_input)} carrying dynamic axis {axis!r} "
                    f"must all be fed with one common extent, got {sorted(vals)}"
                )
            extents[axis] = vals.pop()
        bindings = {axis: self.bucket_for(axis, ext) for axis, ext in extents.items()}
        _, fn = self.specialized(bindings)
        with _trace.span("run.pad"):
            padded: Dict[str, jax.Array] = {}
            for name, v in feeds.items():
                v = np.asarray(v)
                widths = [(0, 0)] * v.ndim
                grow = False
                for axis, by_input in self.axis_input_positions.items():
                    for pos in by_input.get(name, ()):
                        if v.shape[pos] != bindings[axis]:
                            # zero slabs are exact: dynamic compilation proved
                            # every op elementwise along the axis (or the
                            # region's masking makes padding inert), and the
                            # padding is sliced away below
                            widths[pos] = (0, bindings[axis] - v.shape[pos])
                            grow = True
                padded[name] = jnp.asarray(np.pad(v, widths) if grow else v)
        with _trace.span("run.execute") as ex_span:
            if _trace.enabled:
                ex_span.set(**{f"bucket_{a}": b for a, b in sorted(bindings.items())})
            res = fn(padded)
        with _trace.span("run.slice"):
            out: Dict[str, np.ndarray] = {}
            for k, v in res.items():
                v = np.asarray(v)
                by_axis = self.output_axis_positions.get(k)
                if by_axis:
                    slicer = [slice(None)] * v.ndim
                    for axis, positions in by_axis.items():
                        for pos in positions:
                            slicer[pos] = slice(0, extents[axis])
                    v = v[tuple(slicer)]
                out[k] = v
            return out


def _resolve_autotuner(autotune):
    """Normalize the ``compile_model(autotune=...)`` sugar to an Autotuner
    (or None): True → in-memory session, a path → persistent tile cache,
    a tuner instance → as-is.  Tuners are duck-typed on the ``tune_step``
    contract (not ``isinstance``) so injected test doubles — and the module
    run under ``python -m``, where the class exists twice — both work."""
    if not autotune:
        return None
    from ..backend.autotune import Autotuner

    if autotune is True:
        return Autotuner()
    if hasattr(autotune, "tune_step"):
        return autotune
    return Autotuner(cache=str(autotune))


def compile_model(
    model: Model,
    *,
    backend: str = "ref",
    fuse: bool = True,
    optimize: bool = True,
    verify_passes: bool = False,
    batch: str = "static",
    dynamic_axes: Optional[Dict[str, object]] = None,
    plan_cache_capacity: int = PlanCache.DEFAULT_CAPACITY,
    plan_cache: Optional[PlanCache] = None,
    autotune=None,
) -> CompiledModel:
    """Compile a PQ-IR artifact for the TPU backend.

    backend:       "pallas" (real TPU lowering), "interpret" (Pallas
                   interpreter — CPU-validatable), "ref" (pure-jnp fused ops;
                   what the dry-run lowers).
    optimize:      run the :mod:`repro.passes` pipeline first (the caller's
                   artifact is cloned, never mutated).
    verify_passes: turn on the pipeline's reference-runtime conformance hook
                   (asserts each pass is semantics-preserving on probe
                   inputs before the backend ever sees the graph).
    batch:         "static" specializes shapes once at plan time (classic
                   behavior); "dynamic" is single-axis sugar for
                   ``dynamic_axes={"N": None}`` — a batch-polymorphic plan
                   *template* bound lazily to power-of-two batch buckets at
                   run time.
    dynamic_axes:  named symbolic axes to leave open in the plan template,
                   mapped to per-axis bucketing specs: ``None`` →
                   power-of-two buckets, an int g → round up to multiples of
                   g (sequence-length style), a callable → custom policy.
                   Axes must appear in the graph's input signatures (named
                   dims like ``("N", "S", 64)``; a legacy ``(None, …)``
                   leading dim is the implicit batch axis ``"N"``).  One
                   artifact then serves the whole scenario grid with at most
                   one specialization per visited bucket combination.
    plan_cache_capacity:
                   bound on resident per-bucket specializations (dynamic
                   mode; LRU-evicted beyond this).
    plan_cache:    an existing :class:`~repro.backend.plan.PlanCache` to
                   share across artifacts (e.g. one cache serving a prefill
                   and a decode plan of the same token path).  Keys are then
                   prefixed with the graph name (``cm.cache_key``), so pooled
                   artifacts never collide; capacity/accounting are the
                   shared cache's.
    autotune:      measured per-cell tile search (dynamic mode, tiled
                   backends): ``True`` → an in-memory
                   :class:`repro.backend.autotune.Autotuner` session, a path
                   → a session persisted to that JSON tile cache (warm
                   starts perform zero measurements), an Autotuner instance
                   → shared/injected (tests pass one with a deterministic
                   ``measure_fn``).  Each lazy specialization then measures
                   a budgeted, cost-model-seeded candidate list and the plan
                   provenance tags every cell's tile source.
    """
    with _trace.span(
        "compile", graph=model.graph.name, backend=backend,
        batch="dynamic" if (dynamic_axes or batch == "dynamic") else batch,
    ):
        return Compiler(
            model, backend=backend, fuse=fuse, optimize=optimize,
            verify_passes=verify_passes, batch=batch, dynamic_axes=dynamic_axes,
            plan_cache_capacity=plan_cache_capacity, plan_cache=plan_cache,
            autotune=autotune,
        ).compile()
