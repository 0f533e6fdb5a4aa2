"""E6: compiler-vs-reference conformance sweep over every generic lowering,
plus the per-channel differential sweep over every *fused* kernel.

One randomized case per op in the compiler's ``_JOPS`` table, executed by
both :mod:`repro.core.runtime` (the oracle) and the compiled generic path
(``fuse=False, optimize=False`` — pure ``op.<Name>`` registry kernels).
Integer outputs must match bit-exactly; float outputs allclose.  The
parametrization is driven by ``_JOPS`` itself, so adding a generic lowering
without a sweep case fails loudly.

``TestPerChannelFusedSweep`` is the differential conformance harness for the
axis-aware lowering: per-channel variants of every fused requant kernel
(qlinear matmul two-Mul/one-Mul, uint8 activations, the Gemm-codified form,
conv, and the LUT composition) compiled on every registered backend — ``ref``
and ``interpret``; ``interpret`` *is* the Pallas kernel run in interpret mode,
so the ``pallas`` backend differs only by ``interpret=False`` at dispatch —
and asserted bit-exact against the reference runtime.
"""
import numpy as np
import pytest

from repro.core import patterns, pqir, quant
from repro.core.compile import _JOPS, compile_model
from repro.core.runtime import ReferenceRuntime

#: Backends every fused case is swept across.  "interpret" executes the same
#: Pallas tile kernels as "pallas", in the Pallas interpreter (CPU-hosted
#: CI); real-TPU pallas coverage is the ROADMAP CI-lane follow-up.
BACKENDS = ("ref", "interpret")


def _g(name):
    return pqir.GraphBuilder(name)


def _finish(gb, y, dtype, shape=None):
    gb.add_output(y, dtype, shape if shape is not None else (None,))
    return gb.build()


def _rngf(rng, shape, scale=1.0):
    return (rng.normal(size=shape) * scale).astype(np.float32)


def _rng8(rng, shape, lo=-128, hi=128):
    return rng.integers(lo, hi, shape).astype(np.int8)


# Each case: rng → (model, feeds).  Inputs are random graph inputs; shape
# parameters (Reshape target, Slice starts/ends, axes, …) are initializers,
# matching how real artifacts codify them.


def _case_matmul_integer(rng):
    gb = _g("m")
    a = gb.add_input("a", "int8", (4, 8))
    b = gb.add_input("b", "int8", (8, 6))
    azp = gb.add_initializer("azp", np.int8(3))
    bzp = gb.add_initializer("bzp", np.int8(-2))
    y = gb.op("MatMulInteger", [a, b, azp, bzp])
    return _finish(gb, y, "int32"), {"a": _rng8(rng, (4, 8)), "b": _rng8(rng, (8, 6))}


def _case_conv_integer(rng):
    gb = _g("c")
    x = gb.add_input("x", "int8", (2, 3, 8, 8))
    w = gb.add_input("w", "int8", (4, 3, 3, 3))
    y = gb.op("ConvInteger", [x, w], pads=(1, 1, 1, 1), strides=(1, 1))
    return _finish(gb, y, "int32"), {"x": _rng8(rng, (2, 3, 8, 8)), "w": _rng8(rng, (4, 3, 3, 3))}


def _case_quantize_linear(rng):
    gb = _g("q")
    x = gb.add_input("x", "float32", (4, 8))
    s = gb.add_initializer("s", np.float32(0.05))
    zp = gb.add_initializer("zp", np.int8(5))
    y = gb.op("QuantizeLinear", [x, s, zp])
    return _finish(gb, y, "int8"), {"x": _rngf(rng, (4, 8))}


def _case_dequantize_linear(rng):
    gb = _g("dq")
    x = gb.add_input("x", "int8", (4, 8))
    s = gb.add_initializer("s", np.float32(0.05))
    zp = gb.add_initializer("zp", np.int8(3))
    y = gb.op("DequantizeLinear", [x, s, zp])
    return _finish(gb, y, "float32"), {"x": _rng8(rng, (4, 8))}


def _case_cast(rng):
    gb = _g("cast")
    x = gb.add_input("x", "float32", (4, 8))
    y = gb.op("Cast", [x], to="float16")
    return _finish(gb, y, "float16"), {"x": _rngf(rng, (4, 8))}


def _binary(op):
    def build(rng):
        gb = _g(op.lower())
        a = gb.add_input("a", "float32", (4, 8))
        b = gb.add_input("b", "float32", (4, 8))
        y = gb.op(op, [a, b])
        return _finish(gb, y, "float32"), {"a": _rngf(rng, (4, 8)), "b": _rngf(rng, (4, 8))}

    return build


def _case_div(rng):
    gb = _g("div")  # integer path: floor division must match exactly
    a = gb.add_input("a", "int32", (4, 8))
    b = gb.add_input("b", "int32", (4, 8))
    y = gb.op("Div", [a, b])
    return _finish(gb, y, "int32"), {
        "a": rng.integers(-100, 100, (4, 8)).astype(np.int32),
        "b": rng.integers(1, 6, (4, 8)).astype(np.int32),
    }


def _unary(op, positive=False):
    def build(rng):
        gb = _g(op.lower())
        x = gb.add_input("x", "float32", (4, 8))
        y = gb.op(op, [x])
        xv = _rngf(rng, (4, 8))
        if positive:
            xv = np.abs(xv) + 0.1
        return _finish(gb, y, "float32"), {"x": xv}

    return build


def _case_pow(rng):
    gb = _g("pow")
    a = gb.add_input("a", "float32", (4, 8))
    e = gb.add_initializer("e", np.float32(1.7))
    y = gb.op("Pow", [a, e])
    return _finish(gb, y, "float32"), {"a": np.abs(_rngf(rng, (4, 8))) + 0.1}


def _case_clip(rng):
    gb = _g("clip")
    x = gb.add_input("x", "float32", (4, 8))
    lo = gb.add_initializer("lo", np.float32(-0.5))
    hi = gb.add_initializer("hi", np.float32(0.5))
    y = gb.op("Clip", [x, lo, hi])
    return _finish(gb, y, "float32"), {"x": _rngf(rng, (4, 8))}


def _case_softmax(rng):
    gb = _g("sm")
    x = gb.add_input("x", "float32", (4, 8))
    y = gb.op("Softmax", [x], axis=-1)
    return _finish(gb, y, "float32"), {"x": _rngf(rng, (4, 8))}


def _case_matmul(rng):
    gb = _g("mm")
    a = gb.add_input("a", "float32", (4, 8))
    b = gb.add_input("b", "float32", (8, 6))
    y = gb.op("MatMul", [a, b])
    return _finish(gb, y, "float32"), {"a": _rngf(rng, (4, 8)), "b": _rngf(rng, (8, 6))}


def _case_gemm(rng):
    gb = _g("gemm")
    a = gb.add_input("a", "float32", (4, 8))
    b = gb.add_input("b", "float32", (6, 8))
    c = gb.add_initializer("c", _rngf(rng, (6,)))
    y = gb.op("Gemm", [a, b, c], transB=1, alpha=0.5, beta=1.5)
    return _finish(gb, y, "float32"), {"a": _rngf(rng, (4, 8)), "b": _rngf(rng, (6, 8))}


def _case_reshape(rng):
    gb = _g("rs")
    x = gb.add_input("x", "float32", (4, 6))
    t = gb.add_initializer("t", np.asarray([2, 12], np.int64))
    y = gb.op("Reshape", [x, t])
    return _finish(gb, y, "float32"), {"x": _rngf(rng, (4, 6))}


def _case_transpose(rng):
    gb = _g("tp")
    x = gb.add_input("x", "float32", (4, 6))
    y = gb.op("Transpose", [x], perm=[1, 0])
    return _finish(gb, y, "float32"), {"x": _rngf(rng, (4, 6))}


def _case_flatten(rng):
    gb = _g("fl")
    x = gb.add_input("x", "float32", (2, 3, 4))
    y = gb.op("Flatten", [x], axis=1)
    return _finish(gb, y, "float32"), {"x": _rngf(rng, (2, 3, 4))}


def _case_concat(rng):
    gb = _g("cc")
    a = gb.add_input("a", "float32", (2, 3))
    b = gb.add_input("b", "float32", (2, 5))
    y = gb.op("Concat", [a, b], axis=1)
    return _finish(gb, y, "float32"), {"a": _rngf(rng, (2, 3)), "b": _rngf(rng, (2, 5))}


def _case_slice(rng):
    gb = _g("sl")
    x = gb.add_input("x", "int32", (4, 10))
    st = gb.add_initializer("st", np.asarray([1], np.int64))
    en = gb.add_initializer("en", np.asarray([9], np.int64))
    ax = gb.add_initializer("ax", np.asarray([1], np.int64))
    sp = gb.add_initializer("sp", np.asarray([2], np.int64))
    y = gb.op("Slice", [x, st, en, ax, sp])
    return _finish(gb, y, "int32"), {"x": rng.integers(-50, 50, (4, 10)).astype(np.int32)}


def _case_gather(rng):
    gb = _g("ga")
    x = gb.add_input("x", "float32", (5, 4))
    idx = gb.add_initializer("idx", np.asarray([[0, 3], [2, 1]], np.int64))
    y = gb.op("Gather", [x, idx], axis=0)
    return _finish(gb, y, "float32"), {"x": _rngf(rng, (5, 4))}


def _case_squeeze(rng):
    gb = _g("sq")
    x = gb.add_input("x", "int8", (2, 1, 3, 1))
    ax = gb.add_initializer("ax", np.asarray([1, 3], np.int64))
    y = gb.op("Squeeze", [x, ax])
    return _finish(gb, y, "int8"), {"x": _rng8(rng, (2, 1, 3, 1))}


def _case_unsqueeze(rng):
    gb = _g("us")
    x = gb.add_input("x", "int8", (2, 3))
    ax = gb.add_initializer("ax", np.asarray([0, 2], np.int64))
    y = gb.op("Unsqueeze", [x, ax])
    return _finish(gb, y, "int8"), {"x": _rng8(rng, (2, 3))}


def _pool(op):
    def build(rng):
        gb = _g(op.lower())
        x = gb.add_input("x", "float32", (2, 3, 8, 8))
        y = gb.op(op, [x], kernel_shape=(2, 2), strides=(2, 2))
        return _finish(gb, y, "float32"), {"x": _rngf(rng, (2, 3, 8, 8))}

    return build


def _case_gap(rng):
    gb = _g("gap")
    x = gb.add_input("x", "float32", (2, 3, 5, 5))
    y = gb.op("GlobalAveragePool", [x])
    return _finish(gb, y, "float32"), {"x": _rngf(rng, (2, 3, 5, 5))}


def _case_reduce_mean(rng):
    gb = _g("rm")
    x = gb.add_input("x", "float32", (2, 3, 5))
    y = gb.op("ReduceMean", [x], axes=(1,), keepdims=1)
    return _finish(gb, y, "float32"), {"x": _rngf(rng, (2, 3, 5))}


def _case_reduce_max(rng):
    # keepdims on the last axis — the attention max-subtract shape
    gb = _g("rmax")
    x = gb.add_input("x", "float32", (2, 4, 7))
    y = gb.op("ReduceMax", [x], axes=(2,), keepdims=1)
    return _finish(gb, y, "float32"), {"x": _rngf(rng, (2, 4, 7))}


def _case_reduce_sum(rng):
    # int32 accumulator reduction, as the attention probability normalizer
    gb = _g("rsum")
    x = gb.add_input("x", "int32", (2, 4, 7))
    y = gb.op("ReduceSum", [x], axes=(2,), keepdims=1)
    feeds = {"x": rng.integers(0, 255, (2, 4, 7)).astype(np.int32)}
    return _finish(gb, y, "int32"), feeds


def _case_round(rng):
    # halves round to even, as QuantizeLinear does
    gb = _g("round")
    x = gb.add_input("x", "float32", (4, 8))
    y = gb.op("Round", [x])
    xv = _rngf(rng, (4, 8), 4.0)
    xv[0, :4] = [0.5, 1.5, -2.5, -0.5]
    return _finish(gb, y, "float32"), {"x": xv}


def _case_top_k(rng):
    # int32 router logits with ties: equal values keep the lower index first
    gb = _g("topk")
    x = gb.add_input("x", "int32", (3, 5, 12))
    k = gb.add_initializer("k", np.asarray([4], np.int64))
    vals, idx = gb.fresh("vals"), gb.fresh("idx")
    gb.add_node("TopK", [x, k], [vals, idx], axis=-1, largest=1, sorted=1)
    gb.add_output(vals, "int32", (3, 5, 4))
    y = gb.op("Cast", [idx], to="int32")
    feeds = {"x": rng.integers(-3, 4, (3, 5, 12)).astype(np.int32)}
    return _finish(gb, y, "int32"), feeds


def _case_one_hot(rng):
    gb = _g("onehot")
    idx = gb.add_input("idx", "int32", (3, 4, 2))
    depth = gb.add_initializer("depth", np.asarray([6], np.int64))
    values = gb.add_initializer("values", np.asarray([0.0, 1.0], np.float32))
    y = gb.op("OneHot", [idx, depth, values], axis=-1)
    return _finish(gb, y, "float32"), {"idx": rng.integers(0, 6, (3, 4, 2)).astype(np.int32)}


CASES = {
    "MatMulInteger": _case_matmul_integer,
    "ConvInteger": _case_conv_integer,
    "QuantizeLinear": _case_quantize_linear,
    "DequantizeLinear": _case_dequantize_linear,
    "Cast": _case_cast,
    "Mul": _binary("Mul"),
    "Add": _binary("Add"),
    "Sub": _binary("Sub"),
    "Div": _case_div,
    "Relu": _unary("Relu"),
    "Tanh": _unary("Tanh"),
    "Sigmoid": _unary("Sigmoid"),
    "Erf": _unary("Erf"),
    "Sqrt": _unary("Sqrt", positive=True),
    "Pow": _case_pow,
    "Clip": _case_clip,
    "Softmax": _case_softmax,
    "MatMul": _case_matmul,
    "Gemm": _case_gemm,
    "Reshape": _case_reshape,
    "Transpose": _case_transpose,
    "Flatten": _case_flatten,
    "Concat": _case_concat,
    "Slice": _case_slice,
    "Gather": _case_gather,
    "Squeeze": _case_squeeze,
    "Unsqueeze": _case_unsqueeze,
    "MaxPool": _pool("MaxPool"),
    "AveragePool": _pool("AveragePool"),
    "GlobalAveragePool": _case_gap,
    "ReduceMean": _case_reduce_mean,
    "ReduceMax": _case_reduce_max,
    "ReduceSum": _case_reduce_sum,
    "Round": _case_round,
    "TopK": _case_top_k,
    "OneHot": _case_one_hot,
}


@pytest.mark.parametrize("op", sorted(_JOPS))
def test_generic_lowering_matches_reference(op):
    assert op in CASES, f"op {op!r} has a generic lowering but no sweep case — add one"
    rng = np.random.default_rng(abs(hash(op)) % (2**31))
    model, feeds = CASES[op](rng)
    ref = ReferenceRuntime(model).run(feeds)
    cm = compile_model(model, fuse=False, optimize=False)
    assert cm.stats["generic"] >= 1
    got = cm.run(feeds)
    for k, want in ref.items():
        have = got[k]
        assert have.shape == want.shape, (op, have.shape, want.shape)
        assert have.dtype == want.dtype, (op, have.dtype, want.dtype)
        if np.issubdtype(want.dtype, np.integer) or want.dtype == np.bool_:
            np.testing.assert_array_equal(have, want, err_msg=op)
        else:
            np.testing.assert_allclose(have, want, rtol=1e-5, atol=1e-6, err_msg=op)


def _pc_params(rng, n_in, n_out, *, bias=True, out_dtype="int8"):
    """A per-channel-quantized FC layer with a deliberately hot channel, so
    per-tensor and per-channel scales genuinely differ."""
    w = rng.normal(size=(n_in, n_out)).astype(np.float32) * 0.2
    w[:, rng.integers(0, n_out)] *= 25.0
    b = rng.normal(size=(n_out,)).astype(np.float32) * 0.1 if bias else None
    return quant.quantize_linear_layer(w, b, 0.05, 0.1, per_channel=True, out_dtype=out_dtype)


def _pc_fc(rng, *, two_mul=True, activation=None, bias=True, in_dtype="int8", out_dtype="int8"):
    p = _pc_params(rng, 32, 24, bias=bias, out_dtype=out_dtype)
    gb = _g("pc_fc")
    x = gb.add_input("x", in_dtype, (None, 32))
    y = patterns.fc_layer(gb, x, p, "fc0", two_mul=two_mul, activation=activation)
    gb.add_output(y, out_dtype, (None, 24))
    lo, hi = (0, 256) if in_dtype == "uint8" else (-128, 128)
    return gb.build(), {"x": rng.integers(lo, hi, (8, 32)).astype(in_dtype)}, {"fused_qlinear": 1}


def _pc_gemm(rng, *, trans_b=False):
    p = _pc_params(rng, 32, 24)
    gb = _g("pc_gemm")
    x = gb.add_input("x", "int8", (None, 32))
    y = patterns.fc_layer_gemm(gb, x, p, "fc0", activation="Relu", trans_b=trans_b)
    gb.add_output(y, "int8", (None, 24))
    return gb.build(), {"x": _rng8(rng, (8, 32))}, {"fused_qlinear": 1}


def _pc_conv(rng, *, two_mul=False, activation="Relu", bias=True):
    m, c = 6, 3
    w = rng.normal(size=(m, c, 3, 3)).astype(np.float32) * 0.4
    w[rng.integers(0, m)] *= 20.0
    absmax = np.maximum(np.abs(w).max(axis=(1, 2, 3)), 1e-12)
    scale_w = (absmax / 127.0).astype(np.float32)
    w_q = quant.quantize(w, scale_w.reshape(-1, 1, 1, 1), "int8")
    b_q = quant.quantize_bias(rng.normal(size=(m,)).astype(np.float32) * 0.1, scale_w, 0.05) if bias else None
    rescale = quant.decompose_multipliers(scale_w.astype(np.float64) * 0.05 / 0.1)
    gb = _g("pc_conv")
    x = gb.add_input("x", "int8", (None, c, 8, 8))
    y = patterns.conv_layer(
        gb, x, w_q, b_q, rescale, "c0", pads=(1, 1, 1, 1), two_mul=two_mul, activation=activation
    )
    gb.add_output(y, "int8", (None, m, 8, 8))
    return gb.build(), {"x": _rng8(rng, (2, c, 8, 8))}, {"fused_qconv": 1}


def _pc_fc_then_lut(rng):
    """Per-channel FC feeding the int8-tanh LUT: the vector rescale composes
    with the (scalar-scale) LUT fusion — both chains still fuse."""
    p = _pc_params(rng, 32, 16)
    p = quant.quantize_linear_layer(
        p.weight_q.astype(np.float32) * 0.01, None, 0.05, patterns.TANH_INPUT_ABSMAX / 127.0, per_channel=True
    )
    gb = _g("pc_lut")
    x = gb.add_input("x", "int8", (None, 32))
    y = patterns.fc_int8_tanh(gb, x, p, "fc0")
    gb.add_output(y, "int8", (None, 16))
    return gb.build(), {"x": _rng8(rng, (8, 32))}, {"fused_qlinear": 1, "fused_lut": 1}


PER_CHANNEL_CASES = {
    "fc_two_mul": lambda rng: _pc_fc(rng, two_mul=True, bias=True),
    "fc_one_mul_relu": lambda rng: _pc_fc(rng, two_mul=False, activation="Relu"),
    "fc_no_bias": lambda rng: _pc_fc(rng, two_mul=True, bias=False),
    "fc_uint8_in": lambda rng: _pc_fc(rng, two_mul=True, in_dtype="uint8"),
    "fc_uint8_out": lambda rng: _pc_fc(rng, two_mul=True, activation="Relu", out_dtype="uint8"),
    "gemm": lambda rng: _pc_gemm(rng),
    "gemm_transB": lambda rng: _pc_gemm(rng, trans_b=True),
    "conv_one_mul": lambda rng: _pc_conv(rng, two_mul=False),
    "conv_two_mul": lambda rng: _pc_conv(rng, two_mul=True),
    "conv_no_bias": lambda rng: _pc_conv(rng, two_mul=True, bias=False, activation=None),
    "fc_then_lut": _pc_fc_then_lut,
}


class TestPerChannelFusedSweep:
    """Differential conformance: per-channel variants of every fused kernel,
    every backend, bit-exact against the reference runtime — and actually
    *fused* (no silent scalar-only fallback)."""

    @pytest.mark.parametrize("backend", BACKENDS)
    @pytest.mark.parametrize("case", sorted(PER_CHANNEL_CASES))
    def test_per_channel_fused_matches_reference(self, case, backend):
        rng = np.random.default_rng(abs(hash(case)) % (2**31))
        model, feeds, want_fused = PER_CHANNEL_CASES[case](rng)
        ref = ReferenceRuntime(model).run(feeds)
        cm = compile_model(model, backend=backend, verify_passes=True)
        for kind, count in want_fused.items():
            assert cm.stats[kind] == count, (case, cm.stats)
        assert cm.stats["generic"] == 0, (case, cm.stats)
        got = cm.run(feeds)
        for k, want in ref.items():
            np.testing.assert_array_equal(got[k], want, err_msg=f"{case}/{backend}")


class TestShapePlumbingEndToEnd:
    def test_slice_squeeze_unsqueeze_through_full_pipeline(self):
        """The satellite case: a valid artifact using Slice/Squeeze/Unsqueeze
        compiles through the *default* path (optimize + fuse on) and matches
        the reference runtime bit-exactly."""
        rng = np.random.default_rng(7)
        gb = _g("plumb")
        x = gb.add_input("x", "int8", (4, 1, 10))
        sq_ax = gb.add_initializer("sq_ax", np.asarray([1], np.int64))
        st = gb.add_initializer("st", np.asarray([2], np.int64))
        en = gb.add_initializer("en", np.asarray([10], np.int64))
        ax = gb.add_initializer("ax", np.asarray([1], np.int64))
        us_ax = gb.add_initializer("us_ax", np.asarray([2], np.int64))
        s = gb.op("Squeeze", [x, sq_ax])  # (4, 10)
        sl = gb.op("Slice", [s, st, en, ax])  # (4, 8)
        u = gb.op("Unsqueeze", [sl, us_ax])  # (4, 8, 1)
        gb.add_output(u, "int8", (4, 8, 1))
        model = gb.build()
        feeds = {"x": _rng8(rng, (4, 1, 10))}
        ref = ReferenceRuntime(model).run(feeds)[u]
        for backend in ("ref", "interpret"):
            got = compile_model(model, backend=backend).run(feeds)[u]
            np.testing.assert_array_equal(got, ref)
