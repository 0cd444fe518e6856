"""Host loop (`run_stream` / `_consume_segment`): median device idle time
between consecutive decode-segment programs in the trace, in ms."""
import numpy as np

from bench import programs
from bench import trace as trace_lib


def read(run):
    if run.trace is None:
        return None
    gaps = trace_lib.gaps_between(run.trace, programs.segments(run))
    return float(np.median(gaps)) / 1e6 if gaps else None
