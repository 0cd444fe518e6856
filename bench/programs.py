"""Names by which the program's device programs and kernels appear in a
profiler trace, and the pairing of those programs with the host's launch
records.  The names come from the program (its jitted step functions and
Pallas kernels); when one is renamed the metrics that read it go silent
rather than wrong."""
from __future__ import annotations

from typing import List, Optional, Tuple

from bench import trace as trace_lib

# programs: "jit_<function>(<fingerprint>)" on the trace's module line
SEGMENT = "jit_segment("          # launch.steps.make_decode_segment
PREFILL = "jit_prefill("          # launch.steps.make_prefill_into_cache
# kernels: "%<pallas_call name>.<n> = <shape> custom-call(...)" op lines
FLASH_PREFILL = "%flash_attention."               # kernels.flash_attention
DECODE_ATTENTION = "%decode_attention_fused."     # kernels.flash_attention
SSD_SCAN = "%ssd_scan."                           # kernels.ssd


def segments(run) -> List[trace_lib.Ev]:
    return trace_lib.named(run.trace.modules, SEGMENT)


def prefills(run) -> List[trace_lib.Ev]:
    return trace_lib.named(run.trace.modules, PREFILL)


def matched_segments(run) -> Optional[List[Tuple]]:
    """(program, launch) for every decode segment in the trace whose
    rows were read back, or None."""
    if run.trace is None:
        return None
    pairs = run.matched(segments(run), run.segments)
    if not pairs:
        return None
    return [(p, r) for p, r in pairs if r.positions is not None]


def matched_prefills(run) -> Optional[List[Tuple]]:
    if run.trace is None:
        return None
    return run.matched(prefills(run), run.prefills) or None


def kernel_seconds(run, pairs, kernel: str) -> float:
    return sum(e.dur for p, _ in pairs
               for e in trace_lib.inside(
                   trace_lib.named(run.trace.ops, kernel), p)) / 1e9
