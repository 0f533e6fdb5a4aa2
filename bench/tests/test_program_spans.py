"""The program's spans in a traced window: idle time by innermost span, the
readings they give, and a traced run of the tiny cell with the program's
tracer installed."""
import json
import math
import time
from pathlib import Path
from types import SimpleNamespace

import pytest

import harness
import program_spans
import trace_reduce
from conftest import tiny_cell

KERNELS = {"qmatmul": ("qmatmul", "qmatmul_packed"), "qattention": ("qattention",)}
DATA = Path(__file__).resolve().parent / "data"
NO_WINDOW = SimpleNamespace(records=[], t0=0.0, t_end=1.0, t_closed=1.0)


def synthetic():
    # window 0..1000 ns; one engine step 100..700: an admission (a prefill,
    # then a scatter), a decode after it that puts the host cache back, and
    # token choice.  The device runs the prefill 115..140, the decode
    # 530..590 and the argmax 620..630.
    program = [
        ["engine.step", 100, 700], ["engine.admit", 100, 450],
        ["engine.prefill", 100, 160], ["tokenpath.prefill.mask", 100, 110], ["run.pad", 110, 115],
        ["run.execute", 115, 120], ["run.slice", 120, 160],
        ["engine.scatter", 160, 440], ["tokenpath.scatter.fetch", 160, 300],
        ["tokenpath.scatter.write", 300, 440],
        ["engine.decode", 450, 610], ["tokenpath.decode.put", 450, 500],
        ["tokenpath.decode.dispatch", 500, 520], ["tokenpath.decode.fetch", 520, 610],
        ["engine.select", 610, 650],
    ]
    host = [["bench.window", 0, 1000], ["bench.step", 100, 700], ["bench.prefill", 100, 160],
            ["bench.scatter", 160, 440], ["bench.decode", 445, 610]]
    device = {"/device:TPU:0": [["fusion", 115, 140, ""], ["qattention", 530, 590, "tpu_custom_call"],
                                ["argmax", 620, 630, ""]]}
    return {"host": host, "device": device, "program": program}


def test_each_idle_instant_goes_to_the_innermost_program_span():
    idle = program_spans.idle_by_span(synthetic())
    expect = {
        program_spans.OUTSIDE: 100 + 300, "tokenpath.prefill.mask": 10, "run.pad": 5, "run.slice": 20,
        "tokenpath.scatter.fetch": 140, "tokenpath.scatter.write": 140, "engine.admit": 10,
        "tokenpath.decode.put": 50, "tokenpath.decode.dispatch": 20, "tokenpath.decode.fetch": 10 + 20,
        "engine.select": 10 + 20, "engine.step": 50,
    }
    assert idle == pytest.approx({k: v * 1e-9 for k, v in expect.items()})
    # with the busy time, the pieces make up the window
    assert sum(idle.values()) == pytest.approx((1000 - 25 - 60 - 10) * 1e-9)


def test_one_gap_through_several_spans_is_split_among_them():
    # the device idles from 140 to 530: the midpoint (335) lies in the
    # scatter's write, but the fetch, the write and the put each get their own
    idle = program_spans.idle_by_span(synthetic())
    assert idle["tokenpath.scatter.fetch"] == pytest.approx(140e-9)
    assert idle["tokenpath.decode.put"] == pytest.approx(50e-9)


def test_innermost_pieces_follow_the_nesting():
    pieces = program_spans.innermost([("step", 0, 100), ("decode", 10, 50), ("put", 10, 20),
                                      ("dispatch", 20, 30), ("late", 40, 120)])
    assert pieces == [(0, 10, "step"), (10, 20, "put"), (20, 30, "dispatch"), (30, 40, "decode"),
                      (40, 50, "late"), (50, 100, "step")]


def test_readings_of_the_synthetic_window():
    r = program_spans.readings(synthetic(), NO_WINDOW)
    m = r["metrics"]
    assert m["scatter_fetch_ms"] == pytest.approx(140e-6)
    assert m["cache_put_ms"] == pytest.approx(50e-6)
    assert m["prefill_fetch_ms"] == pytest.approx(40e-6)
    assert m["host_copy_idle"] == pytest.approx(100.0 * (10 + 5 + 20 + 140 + 140 + 50 + 30) / 1000)
    assert m["queue_wait_p90_ms"] is None  # no request due
    cover = r["cover"]
    assert cover["bench.prefill"] == pytest.approx(1.0) and cover["bench.scatter"] == pytest.approx(1.0)
    assert cover["bench.decode"] == pytest.approx(160 / 165)
    assert cover["idle"] == pytest.approx(1 - 400 / 905)
    assert cover["idle_in_parts"] == pytest.approx((905 - 400 - 10 - 50) / 905)
    assert next(iter(r["idle_s"])) == program_spans.OUTSIDE


def test_the_longest_step_is_followed_down_to_its_longest_part():
    from repro.obs.trace import SpanRecord

    def span(sid, parent, name, dur):
        return SpanRecord(name=name, ts=0.0, dur=dur, tid=0, sid=sid, parent=parent)

    records = [
        span(1, None, "engine.step", 0.020), span(2, 1, "engine.decode", 0.015),
        span(3, 2, "tokenpath.decode.fetch", 0.012), span(4, 2, "tokenpath.decode.put", 0.002),
        span(5, None, "engine.step", 1.900), span(6, 5, "engine.admit", 0.100),
        span(7, 5, "engine.decode", 1.750), span(8, 7, "tokenpath.decode.put", 1.700),
        span(9, 7, "tokenpath.decode.fetch", 0.030),
    ]
    r = program_spans.longest(records)
    assert r["longest_step"] == [["engine.step", 1900.0], ["engine.decode", 1750.0],
                                 ["tokenpath.decode.put", 1700.0]]
    assert r["longest_ms"]["tokenpath.decode.fetch"] == pytest.approx(30.0)
    assert list(r["longest_ms"])[0] == "engine.step"


def test_a_trace_with_no_device_plane_idles_all_window():
    ev = synthetic()
    ev["device"] = {}
    idle = program_spans.idle_by_span(ev)
    assert sum(idle.values()) == pytest.approx(1000e-9)
    assert idle["run.execute"] == pytest.approx(5e-9)


def _window(waits, t_end=10.0, t_closed=10.5):
    records = []
    for i, w in enumerate(waits):
        req = SimpleNamespace(t_submit=float(i) / 2, t_admit=None if w is None else i / 2 + w)
        records.append(harness.Record(item=None, due=i / 2, submitted=i / 2, req=req))
    return harness.Window(0.0, t_end, t_closed, records, [], [])


def test_queue_wait_p90():
    waits = [0.001 * k for k in range(1, 10)] + [None]  # one of ten never admitted
    assert program_spans.queue_wait_p90_ms(_window(waits)) == pytest.approx(9.0)
    # two never admitted: the p90 falls on one, so the least it can be
    waits = [0.001 * k for k in range(1, 9)] + [None, None]
    assert program_spans.queue_wait_p90_ms(_window(waits)) == pytest.approx(1e3 * (10.5 - 4.0))


def test_queue_wait_of_a_program_without_the_stamp():
    win = _window([0.001])
    del win.records[0].req.t_admit
    assert program_spans.queue_wait_p90_ms(win) is None


def test_the_recorded_chip_trace_reduces_as_before_with_program_spans_beside_it():
    ev = json.loads((DATA / "chat_trace.json").read_text())
    before = trace_reduce.reduce(ev, KERNELS)
    w0 = next(s for n, s, e in ev["host"] if n == "bench.window")
    ev["program"] = [["engine.step", w0 + 10, w0 + 20], ["engine.decode", w0 + 11, w0 + 19]]
    assert trace_reduce.reduce(ev, KERNELS) == before


def test_a_traced_closed_loop_run_reports_every_reading(harness_mod, peaks):
    from repro.obs import trace as program_trace

    r = program_spans.run(tiny_cell("closed"), 2**31 + 77, 3.0, tracer=True, profile=True,
                          t_start=time.monotonic(), peaks=peaks, log=lambda *a: None)
    assert program_trace.current() is None  # uninstalled after the window
    assert set(r["end_to_end"]) >= {"ttft_p90_ms", "itl_p99_ms", "setup_s"}
    assert set(r["metrics"]) == {"scatter_fetch_ms", "cache_put_ms", "prefill_fetch_ms",
                                 "queue_wait_p90_ms", "host_copy_idle"}
    for name, value in r["metrics"].items():
        assert value is not None and math.isfinite(value) and value >= 0, name
    assert {"engine.step", "engine.prefill", "tokenpath.decode.put", "run.slice"} <= set(r["longest_ms"])
    assert r["longest_step"][0][0] == "engine.step" and len(r["longest_step"]) >= 2
    assert set(r["cover"]) >= {"bench.prefill", "bench.scatter", "bench.decode", "idle"}
    json.dumps(r)


def test_an_untraced_run_has_no_program_spans(harness_mod, peaks):
    r = program_spans.run(tiny_cell("closed"), 2**31 + 78, 2.0, tracer=False, profile=True,
                          t_start=time.monotonic(), peaks=peaks, log=lambda *a: None)
    assert "longest_ms" not in r
    assert r["metrics"]["cache_put_ms"] is None and r["metrics"]["queue_wait_p90_ms"] is not None
