"""The whole prefill step: the least chip time for each traced prompt's
prefill at its true length (2 x parameters x tokens, causal attention,
the last position's logits; the weights read once), over the prefill's
device time, in percent."""
from bench import programs
from bench.peaks import least_seconds


def read(run):
    pairs = programs.matched_prefills(run)
    if not pairs:
        return None
    took = sum(p.dur for p, _ in pairs) / 1e9
    least = sum(least_seconds(*run.family.prefill_work(run.config, r.length),
                              run.peaks) for _, r in pairs)
    return 100.0 * least / took if took > 0 else None
