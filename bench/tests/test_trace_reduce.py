"""The reduction from trace events to busy time, kernel time and idle gaps."""
import json
from pathlib import Path

import pytest

import trace_reduce

KERNELS = {"qmatmul": ("qmatmul", "qmatmul_packed"), "qattention": ("qattention",)}
DATA = Path(__file__).resolve().parent / "data"


def synthetic():
    # window 0..1000 ns; one engine step 100..600 with a decode 120..300 and
    # a scatter 320..400; the device runs 130..200, 190..290 (overlapping),
    # 330..350 and 700..800
    host = [
        ["bench.window", 0, 1000], ["bench.step", 100, 600],
        ["bench.decode", 120, 300], ["bench.scatter", 320, 400],
    ]
    device = {"/device:TPU:0": [
        ["qattention", 130, 200, "tpu_custom_call"],
        ["fusion", 190, 290, ""],
        ["qmatmul_packed", 330, 350, "tpu_custom_call"],
        ["copy", 700, 800, ""],
        ["copy", 1500, 1600, ""],  # after the window: left out
    ]}
    return {"host": host, "device": device}


def test_busy_is_the_union_inside_the_window():
    r = trace_reduce.reduce(synthetic(), KERNELS)
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx((160 + 20 + 100) * 1e-9)
    assert r["devices"] == 1


def test_kernel_time_by_family():
    r = trace_reduce.reduce(synthetic(), KERNELS)
    assert r["kernel_s"]["qattention"] == pytest.approx(70e-9)
    assert r["kernel_s"]["qmatmul"] == pytest.approx(20e-9)
    ops = dict(r["device_ops"])
    assert ops["fusion"] == pytest.approx(100e-9) and ops["copy"] == pytest.approx(100e-9)


def test_idle_gaps_go_to_the_innermost_host_span():
    gaps = dict(trace_reduce.reduce(synthetic(), KERNELS)["idle_gaps"])
    # 0..130: midpoint 65, window only; 290..330: midpoint 310, step only;
    # 350..700: midpoint 525, step only; 800..1000: window only
    assert gaps["bench.loadgen"] == pytest.approx((130 + 200) * 1e-9)
    assert gaps["bench.select"] == pytest.approx((40 + 350) * 1e-9)
    assert "bench.decode" not in gaps


def test_op_names_from_hlo_text():
    text = ('%qattention.57 = s8[32,32,128]{2,1,0} custom-call(s8[32,32,128]{2,1,0} %pad.54), '
            'custom_call_target="tpu_custom_call", operand_layout_constraints={}')
    assert trace_reduce.op_name(text) == ("qattention", "tpu_custom_call")
    assert trace_reduce.op_name("%multiply_add_fusion.2 = (s8[32,1024,2048]{2,1,0}) fusion()") == (
        "multiply_add_fusion", "")
    assert trace_reduce.kernel_of("qattention", "", KERNELS) == ""  # not a Pallas call


def test_a_trace_without_a_window_is_refused():
    ev = synthetic()
    ev["host"] = ev["host"][1:]
    with pytest.raises(ValueError):
        trace_reduce.reduce(ev, KERNELS)


def test_a_recorded_chip_trace():
    """A 1 s slice of the ``ouro2.6b-2L.chat`` trace recorded on one v5e:
    one admission (prefill, then the scatter during which the device
    idles) and one decode step."""
    ev = json.loads((DATA / "chat_trace.json").read_text())
    r = trace_reduce.reduce(ev, KERNELS)
    assert r["window_s"] == pytest.approx(1.0)
    assert r["busy_s"] == pytest.approx(0.021865851, rel=1e-9)
    assert r["kernel_s"]["qmatmul"] == pytest.approx(0.006380149, rel=1e-9)
    assert r["kernel_s"]["qattention"] == pytest.approx(0.012774548, rel=1e-9)
    assert [name for name, _ in r["device_ops"][:3]] == ["qattention", "qmatmul_packed", "qmatmul"]
    gaps = dict(r["idle_gaps"])
    assert gaps["bench.scatter"] == pytest.approx(0.93746291, rel=1e-9)
    assert gaps["bench.select"] == pytest.approx(0.034957105, rel=1e-9)
    assert gaps["bench.prefill"] == pytest.approx(0.005713875, rel=1e-6)
    assert sum(gaps.values()) + r["busy_s"] == pytest.approx(r["window_s"], rel=1e-9)
