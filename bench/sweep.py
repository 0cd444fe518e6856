"""Find a cell's knee: the highest offered rate at which its queue does
not grow over the window.  One process, set up once, rates in rising
order; stops at the first rate whose requests do not drain in time.

    python3 -m bench.sweep --workload <name> --rates 0.5,1,1.5 \\
        --seconds <s> --seed <n>

Per rate it prints the backlog (requests due but not yet given their
first token) at the window's start, middle and end, the time to first
token in each half of the window, and the output tokens per second.
The cell's `rate_per_s` is then set by hand: about 4/5 of the knee
for a cell held on its tails, well above it for one held on tokens/s.
Needs the chip, like a run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def backlog(timelines, t):
    due = sum(tl.due <= t for tl in timelines)
    served = sum(bool(tl.token_times) and tl.token_times[0] <= t
                 for tl in timelines)
    return due - served


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--drain", type=float, default=60.0)
    args = ap.parse_args()
    sys.path.insert(0, ROOT)
    from bench import harness, stats
    try:
        sess = harness.set_up(ROOT, args.workload, args.seed)
    except harness.NoChip as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 3
    for rate in [float(r) for r in args.rates.split(",")]:
        win = harness.measure(sess, args.seed, args.seconds, rate=rate,
                              drain=args.drain)
        tl = win.timelines()
        w0, w1 = win.w0, win.w1
        mid = (w0 + w1) / 2
        e2e = stats.end_to_end(tl, w0, w1)
        halves = [stats.ttft_samples(tl, a, b) for a, b in
                  ((w0, mid), (mid, w1))]
        row = {"rate": rate, "drained": win.drained,
               "backlog": [backlog(tl, t) for t in (w0, mid, w1)],
               "ttft_p50_ms_halves": [1e3 * float(np.median(h))
                                      if len(h) else None for h in halves],
               **{k: v for k, v in e2e.items()}}
        print(json.dumps(row), flush=True)
        sess.server.completed.clear()
        if not win.drained:
            break
    return 0


if __name__ == "__main__":
    sys.exit(main())
