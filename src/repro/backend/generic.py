"""Generic jnp op mirror — the fallback lowering for every standard op.

Each entry implements one ONNX-dialect operator as a jnp expression with the
same semantics as :mod:`repro.core.runtime` (the conformance oracle): exact
on integer paths, allclose on float paths.  The table is registered wholesale
in the backend registry under kernel ids ``op.<OpType>`` for the shared
``"*"`` backend, so any op the fusion patterns don't consume still compiles
on every backend.

Implementations take ``(attrs, ins)`` — the node's attribute dict and its
operand list (``None`` for absent optional inputs).  Shape-parameter
operands (Reshape target, Slice starts/ends, Squeeze axes, …) must be
compile-time constants: the lowering bakes initializers in as numpy arrays,
and :func:`_static_ints` rejects traced values with a clear error instead of
letting ``np.asarray`` fail on a tracer.
"""
from __future__ import annotations

from typing import Callable, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from ..core.pqir import DTYPES
from .registry import register

_JOPS: Dict[str, Callable] = {}


def _jop(name):
    def deco(fn):
        _JOPS[name] = fn
        return fn

    return deco


def _static_ints(v, op: str, what: str) -> List[int]:
    """Concrete int list from a shape-parameter operand; rejects tracers."""
    if isinstance(v, jax.core.Tracer):
        raise NotImplementedError(
            f"compiler requires a constant {what} for {op} (got a traced value); "
            "the reference runtime supports the dynamic form"
        )
    return [int(s) for s in np.asarray(v).reshape(-1)]


@_jop("MatMulInteger")
def _j_matmuli(attrs, ins):
    a, b = ins[0], ins[1]
    a32 = a.astype(jnp.int32) - (ins[2].astype(jnp.int32) if len(ins) > 2 and ins[2] is not None else 0)
    b32 = b.astype(jnp.int32) - (ins[3].astype(jnp.int32) if len(ins) > 3 and ins[3] is not None else 0)
    if b32.ndim > 2:
        # stacked (batched) matmul — e.g. the attention QK^T / PV contractions;
        # jnp.matmul broadcasts leading dims with int32 accumulation (exact)
        return [jnp.matmul(a32, b32)]
    return [jax.lax.dot_general(a32, b32, (((a32.ndim - 1,), (0,)), ((), ())), preferred_element_type=jnp.int32)]


@_jop("ConvInteger")
def _j_convi(attrs, ins):
    x, w = ins[0], ins[1]
    pads = tuple(attrs.get("pads", (0, 0, 0, 0)))
    acc = jax.lax.conv_general_dilated(
        x.astype(jnp.int8) if x.dtype != jnp.uint8 else x.astype(jnp.int32),
        w.astype(jnp.int8),
        window_strides=tuple(attrs.get("strides", (1, 1))),
        padding=((pads[0], pads[2]), (pads[1], pads[3])),
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        feature_group_count=int(attrs.get("group", 1)),
        preferred_element_type=jnp.int32,
    )
    return [acc]


@_jop("QuantizeLinear")
def _j_ql(attrs, ins):
    x, scale = ins[0], ins[1]
    zp = ins[2] if len(ins) > 2 else jnp.zeros((), jnp.int8)
    info = jnp.iinfo(zp.dtype)
    y = jnp.rint(x.astype(jnp.float32) / scale.astype(jnp.float32)) + zp.astype(jnp.float32)
    return [jnp.clip(y, info.min, info.max).astype(zp.dtype)]


@_jop("DequantizeLinear")
def _j_dql(attrs, ins):
    x, scale = ins[0], ins[1]
    zp = ins[2].astype(jnp.int32) if len(ins) > 2 else 0
    return [(x.astype(jnp.int32) - zp).astype(jnp.float32) * scale.astype(jnp.float32)]


@_jop("Cast")
def _j_cast(attrs, ins):
    return [ins[0].astype(DTYPES[attrs["to"]])]


@_jop("Reshape")
def _j_reshape(attrs, ins):
    return [ins[0].reshape(tuple(_static_ints(ins[1], "Reshape", "target shape")))]


@_jop("Slice")
def _j_slice(attrs, ins):
    x = ins[0]
    starts = _static_ints(ins[1], "Slice", "starts")
    ends = _static_ints(ins[2], "Slice", "ends")
    axes = _static_ints(ins[3], "Slice", "axes") if len(ins) > 3 and ins[3] is not None else list(range(len(starts)))
    steps = _static_ints(ins[4], "Slice", "steps") if len(ins) > 4 and ins[4] is not None else [1] * len(starts)
    sl = [slice(None)] * x.ndim
    for s, e, a, st in zip(starts, ends, axes, steps):
        sl[a] = slice(s, e, st)
    return [x[tuple(sl)]]


@_jop("Squeeze")
def _j_squeeze(attrs, ins):
    axes = tuple(_static_ints(ins[1], "Squeeze", "axes")) if len(ins) > 1 and ins[1] is not None else None
    return [jnp.squeeze(ins[0], axis=axes)]


@_jop("Unsqueeze")
def _j_unsqueeze(attrs, ins):
    x = ins[0]
    for a in sorted(_static_ints(ins[1], "Unsqueeze", "axes")):
        x = jnp.expand_dims(x, a)
    return [x]


for _name, _fn in {
    "Mul": lambda attrs, ins: [ins[0] * ins[1]],
    "Add": lambda attrs, ins: [ins[0] + ins[1]],
    "Sub": lambda attrs, ins: [ins[0] - ins[1]],
    "Div": lambda attrs, ins: [ins[0] // ins[1] if jnp.issubdtype(ins[0].dtype, jnp.integer) else ins[0] / ins[1]],
    "Relu": lambda attrs, ins: [jnp.maximum(ins[0], jnp.zeros((), ins[0].dtype))],
    "Tanh": lambda attrs, ins: [jnp.tanh(ins[0]).astype(ins[0].dtype)],
    "Sigmoid": lambda attrs, ins: [jax.nn.sigmoid(ins[0].astype(jnp.float32)).astype(ins[0].dtype)],
    "Erf": lambda attrs, ins: [jax.lax.erf(ins[0].astype(jnp.float32)).astype(ins[0].dtype)],
    "Sqrt": lambda attrs, ins: [jnp.sqrt(ins[0])],
    "Pow": lambda attrs, ins: [jnp.power(ins[0], ins[1])],
    "Clip": lambda attrs, ins: [jnp.clip(ins[0], ins[1] if len(ins) > 1 else None, ins[2] if len(ins) > 2 else None)],
    "Softmax": lambda attrs, ins: [jax.nn.softmax(ins[0].astype(jnp.float32), axis=int(attrs.get("axis", -1))).astype(ins[0].dtype)],
    "MatMul": lambda attrs, ins: [ins[0] @ ins[1]],
    "Transpose": lambda attrs, ins: [jnp.transpose(ins[0], attrs.get("perm"))],
    "Flatten": lambda attrs, ins: [ins[0].reshape((int(np.prod(ins[0].shape[: int(attrs.get("axis", 1))])) if int(attrs.get("axis", 1)) else 1, -1))],
    "Concat": lambda attrs, ins: [jnp.concatenate(ins, axis=int(attrs["axis"]))],
    "Gather": lambda attrs, ins: [jnp.take(ins[0], ins[1].astype(jnp.int32), axis=int(attrs.get("axis", 0)))],
    "GlobalAveragePool": lambda attrs, ins: [ins[0].mean(axis=(2, 3), keepdims=True).astype(ins[0].dtype)],
    "ReduceMean": lambda attrs, ins: [ins[0].mean(axis=tuple(attrs.get("axes")) if attrs.get("axes") else None, keepdims=bool(attrs.get("keepdims", 1))).astype(ins[0].dtype)],
    "ReduceMax": lambda attrs, ins: [ins[0].max(axis=tuple(attrs.get("axes")) if attrs.get("axes") else None, keepdims=bool(attrs.get("keepdims", 1))).astype(ins[0].dtype)],
    "ReduceSum": lambda attrs, ins: [ins[0].sum(axis=tuple(attrs.get("axes")) if attrs.get("axes") else None, keepdims=bool(attrs.get("keepdims", 1)), dtype=ins[0].dtype)],
}.items():
    _JOPS[_name] = _fn


@_jop("Round")
def _j_round(attrs, ins):
    return [jnp.rint(ins[0])]


@_jop("TopK")
def _j_topk(attrs, ins):
    """``jax.lax.top_k`` (equal values keep the lower index first) on the
    last axis; indices come out int32 (the reference runtime's are int64)."""
    x, k = ins[0], _static_ints(ins[1], "TopK", "k")[0]
    if int(attrs.get("axis", -1)) not in (-1, x.ndim - 1) or not int(attrs.get("largest", 1)):
        raise NotImplementedError("TopK lowers only largest-k along the last axis")
    vals, idx = jax.lax.top_k(x, k)
    return [vals, idx]


@_jop("OneHot")
def _j_one_hot(attrs, ins):
    idx, depth, values = ins
    if int(attrs.get("axis", -1)) != -1:
        raise NotImplementedError("OneHot lowers only axis=-1")
    d = _static_ints(depth, "OneHot", "depth")[0]
    hot = idx.astype(jnp.int32)[..., None] == jnp.arange(d, dtype=jnp.int32)
    return [jnp.where(hot, values[1], values[0]).astype(values.dtype)]


@_jop("Gemm")
def _j_gemm(attrs, ins):
    a, b = ins[0], ins[1]
    if attrs.get("transA", 0):
        a = a.T
    if attrs.get("transB", 0):
        b = b.T
    if jnp.issubdtype(a.dtype, jnp.integer):
        # integer Gemm: int32 accumulation, alpha/beta fixed at 1 (dialect
        # rule mirrored from repro.core.runtime)
        if float(attrs.get("alpha", 1.0)) != 1.0 or float(attrs.get("beta", 1.0)) != 1.0:
            raise NotImplementedError("integer Gemm requires alpha == beta == 1")
        y = jax.lax.dot_general(
            a.astype(jnp.int32), b.astype(jnp.int32),
            (((a.ndim - 1,), (0,)), ((), ())), preferred_element_type=jnp.int32,
        )
        if len(ins) > 2 and ins[2] is not None:
            y = y + ins[2].astype(jnp.int32)
        return [y]
    y = float(attrs.get("alpha", 1.0)) * (a @ b)
    if len(ins) > 2 and ins[2] is not None:
        y = y + float(attrs.get("beta", 1.0)) * ins[2]
    return [y.astype(ins[0].dtype)]


@_jop("MaxPool")
def _j_maxpool(attrs, ins):
    x = ins[0]
    kh, kw = attrs["kernel_shape"]
    sh, sw = tuple(attrs.get("strides", (kh, kw)))
    pads = tuple(attrs.get("pads", (0, 0, 0, 0)))
    init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
    y = jax.lax.reduce_window(
        x, init, jax.lax.max, (1, 1, kh, kw), (1, 1, sh, sw),
        ((0, 0), (0, 0), (pads[0], pads[2]), (pads[1], pads[3])),
    )
    return [y]


@_jop("AveragePool")
def _j_avgpool(attrs, ins):
    x = ins[0].astype(jnp.float32)
    kh, kw = attrs["kernel_shape"]
    sh, sw = tuple(attrs.get("strides", (kh, kw)))
    pads = tuple(attrs.get("pads", (0, 0, 0, 0)))
    y = jax.lax.reduce_window(
        x, 0.0, jax.lax.add, (1, 1, kh, kw), (1, 1, sh, sw),
        ((0, 0), (0, 0), (pads[0], pads[2]), (pads[1], pads[3])),
    ) / (kh * kw)
    return [y.astype(ins[0].dtype)]


# ---------------------------------------------------------------------------
# registry hookup: every generic op is a shared-backend kernel "op.<Name>"
# ---------------------------------------------------------------------------


def _make_impl(fn):
    def impl(step, args):
        return fn(step.params.get("attrs", {}), args)

    return impl


for _name, _fn in _JOPS.items():
    register(f"op.{_name}")(_make_impl(_fn))
