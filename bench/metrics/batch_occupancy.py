"""Scheduler (`BatchedServer._fill_slots` / `_dispatch_rows`): tokens the
decode segments emitted in the window over the token slots they ran
(segments x seg_len x slots), in percent.  Host counts from the
segments' own emit masks."""


def read(run):
    segs = [r for r in run.in_window(run.segments) if r.positions]
    if not segs:
        return None
    c = run.counters
    emitted = sum(len(p) for r in segs for p in r.positions)
    return 100.0 * emitted / (len(segs) * c["seg_len"] * c["batch"])
