"""Run one cell once with a fault planted under its timed path, and print
what ``correct`` reads: a check that the comparison catches the fault at
the cell's own size, on the chip.

    python3 bench/plant_fault.py --workload <cell> --fault <name> --seed <n> --seconds <s>

The faults are the cell's path's (``bench/faults/<path>.py``).  The last
line is the run's result; ``correct`` has to read false.  The benchmark's
own runs never plant a fault.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT / "bench"))

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell, peaks = harness.start(args.workload)
    undo = cell.module("faults", cell.config["path"]).plant(args.fault)
    try:
        result = harness.run(cell, args.seed, args.seconds, False, t_start=T_START, peaks=peaks)
    finally:
        undo()
    harness.print_checks(result)
    print(json.dumps({"fault": args.fault, **result}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
