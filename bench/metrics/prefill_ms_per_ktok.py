"""Model steps (`steps.make_prefill_into_cache`): device time of the
prefills in the trace per 1,000 true prompt tokens, in ms."""
from bench import programs


def read(run):
    pairs = programs.matched_prefills(run)
    if not pairs:
        return None
    tokens = sum(r.length for _, r in pairs)
    return sum(p.dur for p, _ in pairs) / 1e6 / (tokens / 1e3)
