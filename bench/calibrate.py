"""The readings a cell's correctness limit is set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seconds <s> --seeds 1,2,3 [--control 3]

For each seed, in one process: the weights from the seed, the path built and
warmed up, a window of the cell's own traffic, then the same seeded sample
of finished requests that a run checks.  It prints one JSON line per seed:
the program's widest logit gap below the reference's best (the lower
reading), and, for the first ``--control`` seeds, the gap of the token that
the control puts first, where the control is the reference computed at the
precision below the configuration's (the reference module's ``readings``;
for ``token_block``, 4-bit activations): the upper reading.  The benchmark's own runs never run
the control.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT / "bench"))

import harness  # noqa: E402

def reading(cell: harness.Cell, seed: int, seconds: float, control: bool) -> dict:
    t = time.monotonic()
    ref, weights, served = harness.build(cell, seed)
    setup = time.monotonic() - t
    arrivals = harness.arrivals_for(cell, seconds)
    win = harness.serve(served, arrivals, harness.pool_for(cell, arrivals, seed), seconds)
    sample = harness.sample_finished(win, int(cell.spec["check"]["requests"]), seed)
    served.close()
    del served, win
    gc.collect()
    gaps = ref.readings(cell, weights, sample, control=control)
    out = {
        "seed": seed, "setup_s": setup, "requests": len(sample), "tokens": int(gaps["program"].size),
        "program_gap": float(gaps["program"].max()) if gaps["program"].size else None,
        "program_mismatches": int((gaps["program"] > 0).sum()),
    }
    if control:
        out["control_gap"] = float(gaps["control"].max()) if gaps["control"].size else None
        out["control_mismatches"] = int((gaps["control"] > 0).sum())
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=3, help="seeds that also read the control")
    args = ap.parse_args(argv)
    cell, _ = harness.start(args.workload)
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        print(json.dumps(reading(cell, seed, args.seconds, i < args.control)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
