"""The sparse-expert cell's files, rehearsed on the CPU at a tiny size
(``data/tiny_moe.config.json``: hidden 64, 8 experts of 32, top 2, Pallas in
interpret mode): a run is correct, a traced run reads the routing counter,
and the control and the planted faults fail the comparison."""
import json
import time

from conftest import DATA, tiny_cell

CELL = "qwen1.5moe-a2.7b-4L.reason"


def _cell():
    cell = tiny_cell("closed")
    cell.config = json.loads((DATA / "tiny_moe.config.json").read_text())
    return cell


def _run(harness_mod, peaks, trace=False, seed=2**31 + 41):
    return harness_mod.run(_cell(), seed, 3.0, trace, t_start=time.monotonic(), peaks=peaks,
                           log=lambda *a: None)


def test_the_cell_finds_its_files(harness_mod):
    cell = harness_mod.load_cell(CELL)
    assert {m["name"] for m in cell.end_to_end} == {"setup_s", "ttft_p90_ms", "itl_p99_ms"}
    assert {m["name"] for m in cell.per_layer} == {
        "qmoe_roofline", "moe_experts_hit", "scatter_ms", "prefill_ms", "decode_step_ms", "compiles_in_window",
        "qmatmul_roofline.itl", "qattention_roofline.itl", "mfu.itl", "device_idle.itl"}
    assert cell.chips == 1 and cell.traffic["arrival"] == "closed"
    path = cell.path()
    assert set(path.KERNELS) == {"qmatmul", "qattention", "qmoe"}
    assert callable(cell.reference().checks)
    assert "expert_dropped" in cell.module("faults", cell.config["path"]).FAULTS


def test_a_traced_run_is_correct_and_reads_the_routing(harness_mod, peaks):
    r = _run(harness_mod, peaks, trace=True)
    assert r["correct"] is True and r["checks"]["logit_gap"]["value"] == 0.0
    hit = r["metrics"]["moe_experts_hit"]["value"]
    assert 1 <= hit <= 8


def test_a_dropped_expert_is_not_correct(harness_mod, peaks):
    undo = harness_mod.module("faults", "moe_token_path").plant("expert_dropped")
    try:
        r = _run(harness_mod, peaks)
    finally:
        undo()
    assert r["correct"] is False, r["checks"]


def test_the_control_fails_the_limit(harness_mod):
    cell = _cell()
    seed = 2**31 + 43
    ref, weights, served = harness_mod.build(cell, seed)
    arrivals = harness_mod.arrivals_for(cell, 3.0)
    win = harness_mod.serve(served, arrivals, harness_mod.pool_for(cell, arrivals, seed), 3.0)
    sample = harness_mod.sample_finished(win, int(cell.spec["check"]["requests"]), seed)
    gaps = ref.readings(cell, weights, sample, control=True)
    assert gaps["program"].max() <= cell.spec["check"]["logit_gap_limit"] < gaps["control"].max()
