"""Find the highest open-loop rate a cell's path sustains: one set-up, then
one window per offered rate.

    python3 bench/knee.py --workload <cell> --seed <n> --seconds <s> --rates 1,2,3

For each rate it prints one JSON line: requests due in the window, how many
got their first token inside it, the queue of waiting requests over the
first and the last third of the window, TTFT p50/p90 of the answered ones
and output tokens per second.  The backlog grows where the last third's
queue is longer than the first third's, or requests due inside the window
got no first token in it.  The knee is the highest rate whose backlog does
not grow; a cell offers about four fifths of it.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT / "bench"))

import harness  # noqa: E402
import stats  # noqa: E402


def _third_means(samples, t0, t1):
    third = (t1 - t0) / 3.0
    first = [q for t, q in samples if t0 <= t < t0 + third]
    last = [q for t, q in samples if t1 - third <= t <= t1]
    mean = lambda xs: sum(xs) / len(xs) if xs else 0.0  # noqa: E731
    return mean(first), mean(last)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    cell, _ = harness.start(args.workload)
    t = time.monotonic()
    _, _, served = harness.build(cell, args.seed)
    print(f"knee: {cell.name}: set-up {time.monotonic() - t:.1f} s", flush=True)
    for rate in (float(r) for r in args.rates.split(",")):
        load = {"rate_per_s": rate}
        served.reset()
        arrivals = harness.arrivals_for(cell, args.seconds, load)
        pool = harness.pool_for(cell, arrivals, args.seed)
        win = harness.serve(served, arrivals, pool, args.seconds, drain_s=0.0)
        due = [r for r in win.records if win.t0 <= r.due < win.t_end]
        answered = [r.times[0] - r.due for r in due if r.times and r.times[0] <= win.t_end]
        q_first, q_last = _third_means(win.queue_samples, win.t0, win.t_end)
        gaps = stats.token_gaps(win)
        print(json.dumps({
            "rate_per_s": rate, "due": len(due), "first_token_in_window": len(answered),
            "queue_first_third": q_first, "queue_last_third": q_last,
            "ttft_p50_ms": stats.percentile(answered, 50) * 1e3,
            "ttft_p90_ms": stats.percentile(answered, 90) * 1e3,
            "out_tok_s": stats.tokens(win) / args.seconds,
            "itl_p50_ms": stats.percentile(gaps, 50) * 1e3,
            "itl_p99_ms": stats.percentile(gaps, 99) * 1e3,
            "grows": q_last > q_first + 1.0 or len(answered) < len(due) - 2,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
