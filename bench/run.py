"""Run one benchmark cell once, on the chips of the machine it starts on.

    python3 -m bench.run --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

Prints facts of the run on earlier lines (set-up and compile time, the
generator's lag, compiles inside the window, the check's sample), the
numbers the correctness check compared with their limits as the last
lines of standard error, and one JSON object as the last line of
standard output.  `--trace 0` reports the cell's end-to-end metrics,
`--trace 1` its per-layer metrics, read from a profiler trace of the end
of the window.  Exits non-zero, printing no result, where JAX finds no
TPU or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from bench import harness
    try:
        out = harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                               bool(args.trace), t_process=T_PROCESS,
                               log=lambda s: print(s, flush=True))
    except harness.NoChip as e:
        print(f"bench: {e}; not running", file=sys.stderr)
        return 3
    print(json.dumps(out), flush=True)
    for name, c in out["compared"].items():
        print(f"compared {name}={c['value']!r} limit={c['limit']!r}",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    rc = main()
    # leave before the runtime's teardown can log after the last lines
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)
