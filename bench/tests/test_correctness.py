"""The correctness check fails what it must: the control (the reference at
4-bit activations) and the faults a served cell can have
(``bench/faults/token_path.py``), planted under the timed path at a tiny
size on the CPU."""
import time

import pytest

from conftest import tiny_cell


def _run(harness_mod, peaks, seed=2**31 + 3):
    # a closed loop with as many clients as slots keeps every slot live
    return harness_mod.run(tiny_cell("closed"), seed, 3.0, False, t_start=time.monotonic(), peaks=peaks,
                           log=lambda *a: None)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch", "token_altered"])
def test_a_fault_under_the_timed_path_is_not_correct(harness_mod, peaks, fault):
    undo = harness_mod.module("faults", "token_path").plant(fault)
    try:
        r = _run(harness_mod, peaks)
    finally:
        undo()
    assert r["correct"] is False
    assert r["checks"]["logit_gap"]["value"] > r["checks"]["logit_gap"]["limit"]


def test_the_sound_path_is_correct(harness_mod, peaks):
    r = _run(harness_mod, peaks)
    assert r["correct"] is True
    assert r["checks"]["logit_gap"]["value"] <= r["checks"]["logit_gap"]["limit"]


@pytest.mark.parametrize("seed", [11, 2**31 + 12, 13])
def test_the_control_fails_the_limit(harness_mod, seed):
    """The reference computed at 4-bit activations, read at each position
    of the program's own served tokens, lies beyond the limit."""
    cell = tiny_cell()
    ref, weights, served = harness_mod.build(cell, seed)
    arrivals = harness_mod.arrivals_for(cell, 3.0)
    win = harness_mod.serve(served, arrivals, harness_mod.pool_for(cell, arrivals, seed), 3.0)
    sample = harness_mod.sample_finished(win, int(cell.spec["check"]["requests"]), seed)
    gaps = ref.readings(cell, weights, sample, control=True)
    limit = cell.spec["check"]["logit_gap_limit"]
    assert gaps["program"].max() <= limit < gaps["control"].max()
