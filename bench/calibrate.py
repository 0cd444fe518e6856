"""Readings that set a cell's correctness limit, in one process.

    python3 -m bench.calibrate --workload <name> --seeds 1,2,... \\
        --seconds <s>

For each seed: new weights from the seed in the warm server, the cell's
traffic for a short window at its own load, the window's requests
drained, and the check's sample compared with the float32 reference.
Prints, per seed, the widest gap of the program's served tokens (the
lower reading) and of the tokens the int8 control puts first at the same
positions (the upper reading), then one JSON line with all of them.
Needs the chip, like a run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    sys.path.insert(0, ROOT)
    from bench import harness
    seeds = [int(s) for s in args.seeds.split(",")]
    try:
        sess = harness.set_up(ROOT, args.workload, seeds[0])
    except harness.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 3
    rows = []
    for seed in seeds:
        harness.reseed(sess, seed)
        win = harness.measure(sess, seed, args.seconds)
        sample = harness.check_sample(
            win.reqs, harness.finished(sess.server, win.reqs), seed)
        t = time.perf_counter()
        gaps = harness.served_gaps(sess.cell, sess.server.params, sample,
                                   ("f32", "int8"))
        row = {"seed": seed, "requests": len(sample),
               "tokens": int(len(gaps["f32"])), "drained": win.drained,
               "program": float(gaps["f32"].max()),
               "control": float(gaps["int8"].max()),
               "program_mean": float(gaps["f32"].mean()),
               "control_mean": float(gaps["int8"].mean()),
               "reference_s": time.perf_counter() - t}
        rows.append(row)
        print(json.dumps(row), flush=True)
        sess.server.completed.clear()
        if not win.drained:
            break
    print(json.dumps({"workload": args.workload, "readings": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
