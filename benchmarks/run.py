"""Benchmark aggregator: one module per paper figure/table + the TPU
back-streaming microbench, the serving-loop microbench, and the roofline
table.  Prints ``name,us_per_call,derived`` CSV rows (assignment
deliverable (d)).

``--json PATH`` additionally writes the rows as machine-readable JSON
(name, us_per_call, and the parsed derived key=value fields — runtime,
syncs/token, kernel launches, ...) so the decode fast-path perf
trajectory is tracked across PRs, e.g.::

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        python -m benchmarks.run --only tpu_backstream decode_stream \
        --json BENCH_decode.json

(`decode_stream`'s `stream.sharded` row needs two visible devices.)
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback

from benchmarks import (decode_stream, fig5_motivation, fig10_runtime,
                        fig11_llm_hw, fig12_idle, fig13_stall, fig14_sf,
                        fig15_ooo, fig16_flowctl, roofline_table,
                        tpu_backstream)
from benchmarks.common import print_rows
from repro.launch.compile_cache import use_compile_cache

MODULES = (
    ("fig5_motivation", fig5_motivation),
    ("fig10_runtime", fig10_runtime),
    ("fig11_llm_hw", fig11_llm_hw),
    ("fig12_idle", fig12_idle),
    ("fig13_stall", fig13_stall),
    ("fig14_sf", fig14_sf),
    ("fig15_ooo", fig15_ooo),
    ("fig16_flowctl", fig16_flowctl),
    ("tpu_backstream", tpu_backstream),
    ("decode_stream", decode_stream),
    ("roofline_table", roofline_table),
)


def _parse_derived(derived: str) -> dict:
    """'a=1;b=2.5e-3;c=x' -> {'a': 1, 'b': 0.0025, 'c': 'x'}."""
    out = {}
    for part in derived.split(";"):
        if "=" not in part:
            continue
        k, v = part.split("=", 1)
        try:
            out[k] = int(v)
        except ValueError:
            try:
                out[k] = float(v)
            except ValueError:
                out[k] = v
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
        epilog="Every row name and derived.* field (including the "
               "speculative-decoding accept_rate / tokens_per_sync "
               "metrics) is documented in benchmarks/README.md.")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write rows as machine-readable JSON")
    ap.add_argument("--only", nargs="*", default=None,
                    help="run only these modules (default: all)")
    args = ap.parse_args(argv)
    use_compile_cache()

    modules = MODULES
    if args.only:
        unknown = set(args.only) - {n for n, _ in MODULES}
        if unknown:
            print(f"unknown modules: {sorted(unknown)}", file=sys.stderr)
            return 2
        modules = tuple((n, m) for n, m in MODULES if n in args.only)

    print("name,us_per_call,derived")
    failed = 0
    json_rows = []
    for name, mod in modules:
        t0 = time.time()
        try:
            rows = mod.run()
            print_rows(rows)
            print(f"# {name}: {len(rows)} rows in {time.time() - t0:.1f}s",
                  file=sys.stderr)
            json_rows += [
                {"name": n, "us_per_call": round(t, 3),
                 "derived": _parse_derived(d)} for n, t, d in rows]
        except Exception:
            failed += 1
            traceback.print_exc()
            print(f"{name}.FAILED,0.00,error")
            json_rows.append({"name": f"{name}.FAILED", "us_per_call": 0.0,
                              "derived": {"error": True}})
    if args.json:
        with open(args.json, "w") as f:
            json.dump(json_rows, f, indent=1, sort_keys=True)
        print(f"# wrote {len(json_rows)} rows to {args.json}",
              file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
