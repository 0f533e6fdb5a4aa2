"""Golden-plan snapshot tests.

``print(cm.plan)`` is the co-design artifact a hardware designer reads —
buffer slots, kernel ids, plan-time specialization params (tile choices,
pre-padded parameter shapes, uint8 folds).  Pinning the rendering for the
quickstart MLP and a per-channel CNN catches plan-level regressions (slot
counts, kernel ids, specialization params) in review, where a numeric
conformance test would stay green.

To update after an *intentional* lowering change:

    REGEN_GOLDEN=1 PYTHONPATH=src python -m pytest tests/test_plan_golden.py

then review the golden diff like any other code change.
"""
import functools
import os

import numpy as np
import pytest

from repro.core.compile import compile_model
from repro.core.toolchain import CNNSpec, ConvLayerSpec, MLPSpec, quantize_cnn, quantize_mlp

GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "golden")


def _check_golden(name: str, text: str) -> None:
    path = os.path.join(GOLDEN_DIR, name)
    if os.environ.get("REGEN_GOLDEN"):
        os.makedirs(GOLDEN_DIR, exist_ok=True)
        with open(path, "w") as f:
            f.write(text)
        pytest.skip(f"regenerated {name}")
    assert os.path.exists(path), f"missing golden file {path} — run with REGEN_GOLDEN=1"
    with open(path) as f:
        want = f.read()
    assert text == want, (
        f"ExecutionPlan rendering for {name} changed.  If intentional, regenerate "
        f"with REGEN_GOLDEN=1 and review the diff.\n--- golden ---\n{want}\n--- got ---\n{text}"
    )


def quickstart_mlp():
    """The examples/quickstart.py model, byte-for-byte (same seed/spec)."""
    rng = np.random.default_rng(0)
    spec = MLPSpec(
        weights=[
            rng.normal(size=(64, 128)).astype(np.float32) * 0.2,
            rng.normal(size=(128, 128)).astype(np.float32) * 0.15,
            rng.normal(size=(128, 10)).astype(np.float32) * 0.2,
        ],
        biases=[
            rng.normal(size=(128,)).astype(np.float32) * 0.1,
            rng.normal(size=(128,)).astype(np.float32) * 0.1,
            rng.normal(size=(10,)).astype(np.float32) * 0.1,
        ],
        activations=["Relu", "Relu", None],
    )
    calib = rng.normal(size=(512, 64)).astype(np.float32)
    return quantize_mlp(spec, calib, observer="percentile", name="quickstart_mlp")


def per_channel_cnn():
    rng = np.random.default_rng(5)
    spec = CNNSpec(
        convs=[
            ConvLayerSpec(
                rng.normal(size=(4, 1, 3, 3)).astype(np.float32) * 0.3,
                rng.normal(size=(4,)).astype(np.float32) * 0.1,
                strides=(1, 1),
                pads=(1, 1, 1, 1),
                activation="Relu",
            )
        ],
        head=MLPSpec(
            weights=[rng.normal(size=(4 * 8 * 8, 10)).astype(np.float32) * 0.1],
            biases=[rng.normal(size=(10,)).astype(np.float32) * 0.1],
            activations=[None],
        ),
    )
    calib = rng.normal(size=(64, 1, 8, 8)).astype(np.float32)
    return quantize_cnn(spec, calib, per_channel=True, two_mul=True, name="per_channel_cnn")


def test_quickstart_mlp_plan_golden():
    cm = compile_model(quickstart_mlp(), backend="interpret")
    assert cm.stats["fused_qlinear"] == 3 and cm.stats["generic"] == 0
    _check_golden("quickstart_mlp.plan.txt", cm.plan.pretty() + "\n")


def test_per_channel_cnn_plan_golden():
    cm = compile_model(per_channel_cnn(), backend="interpret")
    # per-channel chains fuse — no scalar-only fallback to the generic mirror
    assert cm.stats["fused_qconv"] == 1 and cm.stats["fused_qlinear"] == 1
    assert cm.stats["generic"] == 1  # the Flatten between conv stack and head
    _check_golden("per_channel_cnn.plan.txt", cm.plan.pretty() + "\n")


def test_quickstart_mlp_template_plan_golden():
    """The batch-polymorphic *template* rendering: batch-open shape records
    (lead marks the symbolic dim; no m/bm) on every fused step."""
    cm = compile_model(quickstart_mlp(), backend="interpret", batch="dynamic")
    assert cm.stats["fused_qlinear"] == 3 and cm.stats["generic"] == 0
    _check_golden("quickstart_mlp.template.plan.txt", cm.plan.pretty() + "\n")


def test_specialized_plan_binds_bucket_in_rendering():
    """A bucket specialization of the template renders fully concrete —
    same slots/kernels, m/bm bound, batch stamped in the header."""
    cm = compile_model(quickstart_mlp(), backend="interpret", batch="dynamic")
    plan8, _ = cm.specialized(8)
    text = plan8.pretty()
    assert "batch=8" in text.splitlines()[0]
    assert "m=8" in text and "bm=32" in text
    assert "lead=" not in text and "dynamic_batch" not in text


def two_axis_mlp():
    """The tests/test_batch_polymorphic.py two-axis model, byte-for-byte."""
    from repro.core import patterns, pqir, quant

    rng = np.random.default_rng(15)
    p0 = quant.quantize_linear_layer(
        rng.normal(size=(32, 48)).astype(np.float32) * 0.15,
        rng.normal(size=(48,)).astype(np.float32) * 0.1, 0.05, 0.1,
    )
    p1 = quant.quantize_linear_layer(
        rng.normal(size=(48, 24)).astype(np.float32) * 0.2,
        rng.normal(size=(24,)).astype(np.float32) * 0.1, 0.1, 0.12,
    )
    gb = pqir.GraphBuilder("two_axis_mlp")
    x = gb.add_input("x", "int8", ("N", "S", 32))
    h = patterns.fc_layer(gb, x, p0, "fc0", two_mul=True, activation="Relu")
    y = patterns.fc_layer(gb, h, p1, "fc1", two_mul=True)
    gb.add_output(y, "int8", ("N", "S", 24))
    return gb.build()


def test_two_axis_template_plan_golden():
    """The multi-axis template rendering: named axes in the header, named
    lead dims in the axis-open shape records, names in the value typing."""
    cm = compile_model(two_axis_mlp(), backend="interpret", dynamic_axes={"N": None, "S": 32})
    assert cm.stats["fused_qlinear"] == 2 and cm.stats["generic"] == 0
    _check_golden("two_axis_mlp.template.plan.txt", cm.plan.pretty() + "\n")


def test_quickstart_mlp_provenance_golden():
    """``pretty(verbose=True)`` pins the provenance section: which passes
    fired (with counters), which fusion patterns matched which nodes, and
    every scenario-cell specialization with its bindings and chosen tiles.
    Deterministic by construction — provenance carries no wall times, and
    the trace id only appears when a tracer is installed (none here)."""
    cm = compile_model(quickstart_mlp(), backend="interpret", batch="dynamic")
    cm.specialized(1)
    cm.specialized(8)
    text = cm.plan.pretty(verbose=True)
    assert "provenance:" in text and "specializations: 2" in text
    assert "provenance:" not in cm.plan.pretty()  # default rendering unchanged
    _check_golden("quickstart_mlp.provenance.txt", text + "\n")


def test_quickstart_mlp_tuned_provenance_golden(tmp_path):
    """The tuned artifact trail, golden-pinned: a cell whose lattice collapses
    renders untagged (heuristic), a measured cell renders ``[tuned]``, and a
    second session warm-started from the persisted tile cache renders
    ``[cache]`` — all bit-reproducible because the timing oracle is the
    analytic cost model, not a wall clock."""
    from repro.backend import cost
    from repro.backend.autotune import Autotuner

    def cost_measure(step, shape, backend):
        return cost.qmatmul_tile_cost(
            shape["m"], shape["k"], shape["n"], shape["bm"], shape["bk"], shape["bn"]
        )

    cache = str(tmp_path / "tiles.json")
    t1 = Autotuner(budget=4, measure_fn=cost_measure, cache=cache)
    cm = compile_model(quickstart_mlp(), backend="interpret", batch="dynamic", autotune=t1)
    cm.specialized(1)  # mp=32 collapses the lattice: stays heuristic, untagged
    cm.specialized(64)  # bm ∈ {32, 64} per step: measured -> [tuned]
    assert t1.measurements == 6  # 3 fused steps x 2 candidates

    t2 = Autotuner(budget=4, measure_fn=cost_measure, cache=cache)
    cm2 = compile_model(quickstart_mlp(), backend="interpret", batch="dynamic", autotune=t2)
    cm2.specialized(64)  # warm start from the artifact -> [cache]
    assert t2.measurements == 0

    default = cm.plan.pretty()  # default rendering carries no source tags
    assert "[tuned]" not in default and "[cache]" not in default
    text = (
        cm.plan.pretty(verbose=True)
        + "\n--- second session, warm-started from the tile cache ---\n"
        + cm2.plan.pretty(verbose=True)
    )
    _check_golden("quickstart_mlp.tuned.provenance.txt", text + "\n")


def test_two_axis_specialization_renders_bindings():
    cm = compile_model(two_axis_mlp(), backend="interpret", dynamic_axes={"N": None, "S": 32})
    plan, _ = cm.specialized({"N": 4, "S": 32})
    head = plan.pretty().splitlines()[0]
    assert "batch=(N=4,S=32)" in head
    assert "m=128" in plan.pretty()  # flat M = 4 × 32


def quickstart_mlp_int4():
    """The quickstart model re-quantized onto the sub-8-bit weight lane:
    same seeds/spec, ``weight_bits=4`` (weights on [-8, 7], packed two
    nibbles per byte at plan time)."""
    rng = np.random.default_rng(0)
    spec = MLPSpec(
        weights=[
            rng.normal(size=(64, 128)).astype(np.float32) * 0.2,
            rng.normal(size=(128, 128)).astype(np.float32) * 0.15,
            rng.normal(size=(128, 10)).astype(np.float32) * 0.2,
        ],
        biases=[
            rng.normal(size=(128,)).astype(np.float32) * 0.1,
            rng.normal(size=(128,)).astype(np.float32) * 0.1,
            rng.normal(size=(10,)).astype(np.float32) * 0.1,
        ],
        activations=["Relu", "Relu", None],
    )
    calib = rng.normal(size=(512, 64)).astype(np.float32)
    return quantize_mlp(
        spec, calib, observer="percentile", name="quickstart_mlp_int4",
        weight_bits=4,
    )


def test_quickstart_mlp_int4_plan_golden():
    """The w4 plan rendering: every fused step carries ``bits=4`` and its
    packed uint8 weight template (kp/2 rows)."""
    cm = compile_model(quickstart_mlp_int4(), backend="interpret")
    assert cm.stats["fused_qlinear"] == 3 and cm.stats["generic"] == 0
    text = cm.plan.pretty()
    assert text.count("bits=4") == 3
    _check_golden("quickstart_mlp_int4.plan.txt", text + "\n")


def test_quickstart_mlp_int4_provenance_golden():
    """The w4 provenance rendering: every specialized cell's tile record
    carries the ``w4/a8`` precision tag."""
    cm = compile_model(quickstart_mlp_int4(), backend="interpret", batch="dynamic")
    cm.specialized(1)
    cm.specialized(8)
    text = cm.plan.pretty(verbose=True)
    assert "w4/a8" in text and "specializations: 2" in text
    _check_golden("quickstart_mlp_int4.provenance.txt", text + "\n")


@functools.lru_cache(maxsize=None)
def _toy_token_path_renderings():
    """The toy token path's emitted graphs (a hash of each graph's JSON) and
    its prefill/decode plans, template and one bucket each."""
    import hashlib
    import json

    from repro.serving.token_path import CompiledTokenPath, TokenPathConfig

    tp = CompiledTokenPath(TokenPathConfig(), backend="interpret", seed=0)
    lines = []
    for name, model in (("prefill", tp.prefill_model), ("decode", tp.decode_model)):
        doc = json.dumps(model.graph.to_json(), sort_keys=True)
        lines.append(f"{name} graph sha256 {hashlib.sha256(doc.encode()).hexdigest()} nodes {len(model.graph.nodes)}")
    out = {"token_path_toy.graphs.txt": "\n".join(lines) + "\n"}
    for name, cm, bind in (("prefill", tp.prefill_cm, {"N": 1, "S": 32}), ("decode", tp.decode_cm, {"N": 2, "S": 64})):
        out[f"token_path_toy.{name}.plan.txt"] = cm.plan.pretty() + "\n" + cm.specialized(bind)[0].pretty() + "\n"
    return out


@pytest.mark.parametrize("name", [
    "token_path_toy.graphs.txt", "token_path_toy.prefill.plan.txt", "token_path_toy.decode.plan.txt",
])
def test_toy_token_path_is_unchanged_by_the_block_builders(name):
    """The toy block (the benchmark's ouro/MiniCPM cells) emits the same
    graphs and lowers to the same plans as before the sparse-expert block
    was added: the goldens were rendered by the commit before it."""
    _check_golden(name, _toy_token_path_renderings()[name])
