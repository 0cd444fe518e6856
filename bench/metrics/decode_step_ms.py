"""Model steps (`steps.make_decode_segment`): device time of the decode
segments in the trace over the token steps they ran, in ms."""
from bench import programs


def read(run):
    if run.trace is None:
        return None
    segs = programs.segments(run)
    if not segs:
        return None
    steps = len(segs) * run.counters["seg_len"]
    return sum(e.dur for e in segs) / steps / 1e6
