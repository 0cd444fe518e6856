"""The whole decode step: the least chip time for the decode work of the
traced segments (weights once per step, each live row's KV or state),
over their device time plus the device idle time that follows each
before the next segment, in percent."""
from bench import programs
from bench import trace as trace_lib
from bench.peaks import least_seconds


def read(run):
    pairs = programs.matched_segments(run)
    if not pairs:
        return None
    progs = [p for p, _ in pairs]
    gaps = trace_lib.gaps_between(run.trace, progs)
    took = (sum(p.dur for p in progs) + sum(gaps)) / 1e9
    least = sum(least_seconds(*run.family.decode_step_work(run.config, pos),
                              run.peaks)
                for _, r in pairs for pos in r.positions if len(pos))
    return 100.0 * least / took if took > 0 else None
