"""E3: compiled TPU backend ≡ reference runtime — bit-exact on integer paths.

This is the conformance test that makes the co-design separation real: the
quantizer's artifact runs identically on the "standard tool" (reference
runtime) and on the hardware-specific compiled backend.
"""
import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import patterns, pqir, quant
from repro.core.compile import compile_model
from repro.core.runtime import ReferenceRuntime
from repro.core.toolchain import CNNSpec, ConvLayerSpec, MLPSpec, quantize_cnn, quantize_mlp


def _fc_model(rng, two_mul=True, activation=None, n_in=64, n_out=32):
    x = rng.normal(size=(8, n_in)).astype(np.float32)
    w = rng.normal(size=(n_in, n_out)).astype(np.float32) * 0.1
    b = rng.normal(size=(n_out,)).astype(np.float32) * 0.2
    scale_x = quant.choose_scale(float(np.abs(x).max()), "int8")
    p = quant.quantize_linear_layer(w, b, scale_x, 0.1)
    xq = quant.quantize(x, scale_x, "int8")
    gb = pqir.GraphBuilder("m")
    xi = gb.add_input("input_q", "int8", (None, n_in))
    y = patterns.fc_layer(gb, xi, p, "fc0", two_mul=two_mul, activation=activation)
    gb.add_output(y, "int8", (None, n_out))
    return gb.build(), xq, y


class TestFusionBitExact:
    @pytest.mark.parametrize("two_mul", [True, False])
    @pytest.mark.parametrize("activation", [None, "Relu"])
    def test_fig12_fused_equals_runtime(self, two_mul, activation):
        rng = np.random.default_rng(0)
        model, xq, yname = _fc_model(rng, two_mul, activation)
        ref_out = ReferenceRuntime(model).run({"input_q": xq})[yname]
        for backend in ("ref", "interpret"):
            cm = compile_model(model, backend=backend)
            assert cm.stats["fused_qlinear"] == 1, cm.stats
            assert cm.stats["generic"] == 0  # the whole chain fused
            got = cm.run({"input_q": xq})[yname]
            np.testing.assert_array_equal(got, ref_out)

    @pytest.mark.parametrize("fn,name", [
        (patterns.fc_int8_tanh, "int8_tanh"),
        (patterns.fc_fp16_tanh, "fp16_tanh"),
        (patterns.fc_fp16_sigmoid, "fp16_sigmoid"),
    ])
    def test_fig456_lut_fused_bitexact(self, fn, name):
        """The compiled LUT reproduces the DQL→[f16]→act→QL chain bit-exactly —
        including the fp16 rounding of Figs 5/6."""
        rng = np.random.default_rng(1)
        x = rng.normal(size=(16, 32)).astype(np.float32)
        w = rng.normal(size=(32, 16)).astype(np.float32) * 0.3
        b = rng.normal(size=(16,)).astype(np.float32) * 0.1
        scale_x = quant.choose_scale(float(np.abs(x).max()), "int8")
        absmax = patterns.SIGMOID_INPUT_ABSMAX if "sigmoid" in name else patterns.TANH_INPUT_ABSMAX
        p = quant.quantize_linear_layer(w, b, scale_x, absmax / 127.0)
        xq = quant.quantize(x, scale_x, "int8")
        gb = pqir.GraphBuilder("m")
        xi = gb.add_input("input_q", "int8", (None, 32))
        y = fn(gb, xi, p, "fc0")
        out_dtype = "uint8" if "sigmoid" in name else "int8"
        gb.add_output(y, out_dtype, (None, 16))
        model = gb.build()
        ref_out = ReferenceRuntime(model).run({"input_q": xq})[y]
        cm = compile_model(model, backend="ref")
        assert cm.stats["fused_lut"] == 1, cm.stats
        assert cm.stats["fused_qlinear"] == 1
        got = cm.run({"input_q": xq})[y]
        np.testing.assert_array_equal(got, ref_out)

    def test_conv_chain_fused(self):
        rng = np.random.default_rng(2)
        w = rng.integers(-128, 128, (8, 3, 3, 3)).astype(np.int8)
        b = rng.integers(-100, 100, (8,)).astype(np.int32)
        r = quant.decompose_multiplier(0.002)
        gb = pqir.GraphBuilder("c")
        xi = gb.add_input("x", "int8", (None, 3, 10, 10))
        y = patterns.conv_layer(gb, xi, w, b, r, "c0", pads=(1, 1, 1, 1), activation="Relu")
        gb.add_output(y, "int8", (None, 8, 10, 10))
        model = gb.build()
        x = rng.integers(-128, 128, (2, 3, 10, 10)).astype(np.int8)
        ref_out = ReferenceRuntime(model).run({"x": x})[y]
        cm = compile_model(model)
        assert cm.stats["fused_qconv"] == 1
        np.testing.assert_array_equal(cm.run({"x": x})[y], ref_out)

    def test_per_row_mul_constant_falls_back_unfused(self):
        """A Mul constant broadcasting along the *batch* axis is not a
        per-channel rescale — the chain must not fuse (the fused kernel only
        knows output-feature vectors) but must still compile correctly via
        the generic mirror."""
        rng = np.random.default_rng(9)
        gb = pqir.GraphBuilder("m")
        xi = gb.add_input("x", "int8", (4, 16))
        w = gb.add_initializer("w", rng.integers(-128, 128, (16, 8)).astype(np.int8))
        acc = gb.op("MatMulInteger", [xi, w], out_hint="acc")
        f = gb.op("Cast", [acc], out_hint="f", to="float32")
        per_row = gb.add_initializer("per_row", np.full((4, 1), 2.0**-9, np.float32))
        m = gb.op("Mul", [f, per_row], out_hint="m")
        y = patterns.emit_round_clip(gb, m, "out")
        gb.add_output(y, "int8", (4, 8))
        model = gb.build()
        xq = rng.integers(-128, 128, (4, 16)).astype(np.int8)
        ref_out = ReferenceRuntime(model).run({"x": xq})[y]
        cm = compile_model(model)
        assert cm.stats["fused_qlinear"] == 0 and cm.stats["generic"] > 0, cm.stats
        np.testing.assert_array_equal(cm.run({"x": xq})[y], ref_out)

    def test_rank_expanding_mul_constant_falls_back(self):
        """A (1, 1, N) rescale constant broadcasts the 2-D accumulator up to
        rank 3 in the reference runtime — fusing it would silently drop that
        dim, so the chain must compile via the generic mirror instead."""
        rng = np.random.default_rng(11)
        gb = pqir.GraphBuilder("m")
        xi = gb.add_input("x", "int8", (4, 16))
        w = gb.add_initializer("w", rng.integers(-128, 128, (16, 8)).astype(np.int8))
        acc = gb.op("MatMulInteger", [xi, w], out_hint="acc")
        f = gb.op("Cast", [acc], out_hint="f", to="float32")
        c = gb.add_initializer("c", np.full((1, 1, 8), 2.0**-9, np.float32))
        m = gb.op("Mul", [f, c], out_hint="m")
        y = patterns.emit_round_clip(gb, m, "out")
        gb.add_output(y, "int8", (1, 4, 8))
        model = gb.build()
        xq = rng.integers(-128, 128, (4, 16)).astype(np.int8)
        ref_out = ReferenceRuntime(model).run({"x": xq})[y]
        assert ref_out.shape == (1, 4, 8)
        cm = compile_model(model, optimize=False)
        assert cm.stats["fused_qlinear"] == 0 and cm.stats["generic"] > 0, cm.stats
        got = cm.run({"x": xq})[y]
        np.testing.assert_array_equal(got, ref_out)

    def test_gemm_codified_fc_fuses(self):
        """ROADMAP follow-up #2: a Gemm-based MLP export hits the fused
        qlinear path (transB and the C bias fold at plan time)."""
        rng = np.random.default_rng(10)
        w = rng.normal(size=(48, 24)).astype(np.float32) * 0.1
        b = rng.normal(size=(24,)).astype(np.float32) * 0.2
        for per_channel in (False, True):
            p = quant.quantize_linear_layer(w, b, 0.05, 0.1, per_channel=per_channel)
            for trans_b in (False, True):
                gb = pqir.GraphBuilder("g")
                xi = gb.add_input("input_q", "int8", (None, 48))
                y = patterns.fc_layer_gemm(gb, xi, p, "fc0", activation="Relu", trans_b=trans_b)
                gb.add_output(y, "int8", (None, 24))
                model = gb.build()
                xq = rng.integers(-128, 128, (8, 48)).astype(np.int8)
                ref_out = ReferenceRuntime(model).run({"input_q": xq})[y]
                for backend in ("ref", "interpret"):
                    cm = compile_model(model, backend=backend)
                    assert cm.stats["fused_qlinear"] == 1 and cm.stats["generic"] == 0, cm.stats
                    np.testing.assert_array_equal(cm.run({"input_q": xq})[y], ref_out)

    def test_unfused_fallback_still_exact(self):
        """fuse=False exercises the generic jnp mirror — still bit-exact on
        this all-integer graph."""
        rng = np.random.default_rng(3)
        model, xq, yname = _fc_model(rng)
        ref_out = ReferenceRuntime(model).run({"input_q": xq})[yname]
        cm = compile_model(model, fuse=False)
        assert cm.stats["fused_qlinear"] == 0 and cm.stats["generic"] > 0
        np.testing.assert_array_equal(cm.run({"input_q": xq})[yname], ref_out)


class TestEndToEndArtifacts:
    def test_mlp_artifact_compiles_and_matches(self):
        rng = np.random.default_rng(4)
        spec = MLPSpec(
            weights=[rng.normal(size=(32, 64)).astype(np.float32) * 0.2,
                     rng.normal(size=(64, 64)).astype(np.float32) * 0.2,
                     rng.normal(size=(64, 10)).astype(np.float32) * 0.2],
            biases=[rng.normal(size=(64,)).astype(np.float32) * 0.1,
                    rng.normal(size=(64,)).astype(np.float32) * 0.1,
                    rng.normal(size=(10,)).astype(np.float32) * 0.1],
            activations=["Relu", "Tanh", None],
        )
        calib = rng.normal(size=(128, 32)).astype(np.float32)
        model = quantize_mlp(spec, calib)
        xq = quant.quantize(rng.normal(size=(8, 32)).astype(np.float32), eval(model.metadata["input_scale"]), "int8")
        ref_out = ReferenceRuntime(model).run({"input_q": xq})
        cm = compile_model(model)
        assert cm.stats["fused_qlinear"] == 3
        assert cm.stats["fused_lut"] == 1  # the tanh
        got = cm.run({"input_q": xq})
        for k in ref_out:
            np.testing.assert_array_equal(got[k], ref_out[k])

    def test_cnn_artifact_compiles_and_matches(self):
        rng = np.random.default_rng(5)
        spec = CNNSpec(
            convs=[ConvLayerSpec(rng.normal(size=(4, 1, 3, 3)).astype(np.float32) * 0.3,
                                 rng.normal(size=(4,)).astype(np.float32) * 0.1,
                                 activation="Relu")],
            head=MLPSpec(weights=[rng.normal(size=(4 * 6 * 6, 10)).astype(np.float32) * 0.1],
                         biases=[rng.normal(size=(10,)).astype(np.float32) * 0.1],
                         activations=[None]),
        )
        calib = rng.normal(size=(64, 1, 8, 8)).astype(np.float32)
        model = quantize_cnn(spec, calib)
        xq = quant.quantize(calib[:4], eval(model.metadata["input_scale"]), "int8")
        ref_out = ReferenceRuntime(model).run({"input_q": xq})
        cm = compile_model(model)
        assert cm.stats["fused_qconv"] == 1 and cm.stats["fused_qlinear"] == 1
        got = cm.run({"input_q": xq})
        for k in ref_out:
            np.testing.assert_array_equal(got[k], ref_out[k])

    def test_pallas_interpret_end_to_end(self):
        rng = np.random.default_rng(6)
        model, xq, yname = _fc_model(rng, n_in=256, n_out=128)
        ref_out = ReferenceRuntime(model).run({"input_q": xq})[yname]
        cm = compile_model(model, backend="interpret")
        np.testing.assert_array_equal(cm.run({"input_q": xq})[yname], ref_out)


class TestLutKernel:
    def test_lut_kernel_paths(self):
        from repro.kernels import ops as kops
        from repro.kernels.qact_lut import build_lut

        lut = build_lut(np.tanh, 4.0 / 127.0, 1.0 / 127.0, "int8")
        assert lut.shape == (256,) and lut.dtype == np.int8
        x = np.random.default_rng(0).integers(-128, 128, (64, 128)).astype(np.int8)
        expect = lut[x.astype(np.int32) + 128]
        for backend in ("ref", "interpret"):
            got = kops.quantized_activation(jnp.asarray(x), lut, backend=backend)
            np.testing.assert_array_equal(np.asarray(got), expect)
        # a uint8 table (values above 127) over a shape off the kernel's
        # blocks: padded, looked up exactly, sliced back
        lut_u8 = build_lut(lambda v: 1.0 / (1.0 + np.exp(-v)), 8.0 / 127.0, 1.0 / 255.0, "uint8")
        assert lut_u8.max() > 127
        x = np.random.default_rng(1).integers(-128, 128, (3, 37, 200)).astype(np.int8)
        got = kops.quantized_activation(jnp.asarray(x), lut_u8, backend="interpret")
        np.testing.assert_array_equal(np.asarray(got), lut_u8[x.astype(np.int32) + 128])
