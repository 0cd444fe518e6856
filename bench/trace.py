"""Reduction of a profiler trace to device facts: which operations ran
on the chip and when, on the host's clock.

`read` turns the profiler's `.xplane.pb` into a `Trace` of plain events;
everything after that works on those events alone, so the arithmetic is
tested on a small recorded trace without a chip.  The host marks the
traced window with two `TraceAnnotation`s (`ANCHOR`); their start times
tie the trace's clock to `time.perf_counter`.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

ANCHOR = "bench.anchor"
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Ev:
    name: str
    start: float            # ns, trace clock
    dur: float              # ns

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    """One chip's operations and programs inside the traced window
    [lo, hi] (ns, trace clock), and the clock's tie to the host: trace ns
    = (perf_counter s - perf0) * 1e9 + lo."""
    ops: List[Ev]
    modules: List[Ev]
    lo: float
    hi: float
    perf0: float

    def to_ns(self, t: float) -> float:
        return (t - self.perf0) * 1e9 + self.lo

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    @classmethod
    def from_json(cls, d: Dict) -> "Trace":
        return cls([Ev(*e) for e in d["ops"]],
                   [Ev(*e) for e in d["modules"]],
                   d["lo"], d["hi"], d["perf0"])


def read(path: str, perf0: float) -> List[Trace]:
    """One `Trace` per TPU in the profile at `path`.  `perf0` is the
    host's `perf_counter` when the first anchor was entered."""
    from jax.profiler import ProfileData
    prof = ProfileData.from_file(path)
    anchors = []
    devices = []
    for plane in prof.planes:
        if plane.name.startswith(DEVICE_PLANE):
            lines = {ln.name: ln for ln in plane.lines}
            grab = (lambda name: [Ev(e.name, e.start_ns, e.duration_ns)
                                  for e in lines[name].events]
                    if name in lines else [])
            devices.append((plane.name, grab(OPS_LINE), grab(MODULES_LINE)))
        elif plane.name.startswith("/host"):
            for ln in plane.lines:
                anchors += [e.start_ns for e in ln.events
                            if e.name == ANCHOR]
    if len(anchors) < 2:
        raise ValueError(f"trace {path}: {len(anchors)} window anchors")
    lo, hi = min(anchors), max(anchors)
    out = []
    for _, ops, mods in sorted(devices):
        clip = lambda evs: sorted((e for e in evs
                                   if e.end > lo and e.start < hi),
                                  key=lambda e: e.start)
        out.append(Trace(clip(ops), clip(mods), lo, hi, perf0))
    return out


def union(intervals: Sequence[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Merge intervals into disjoint, sorted ones."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(evs: Sequence[Ev], lo: float, hi: float) -> float:
    """Nanoseconds of [lo, hi] in which at least one of `evs` ran."""
    return sum(min(b, hi) - max(a, lo)
               for a, b in union([(e.start, e.end) for e in evs])
               if b > lo and a < hi)


def idle_share(tr: Trace) -> float:
    return 1.0 - busy_ns(tr.ops, tr.lo, tr.hi) / (tr.hi - tr.lo)


def short(name: str) -> str:
    """An op's name without its HLO text: "%fusion.12 = bf16[..] ..." ->
    "%fusion.12"."""
    return name.split(" = ", 1)[0]


def named(evs: Sequence[Ev], prefix: str) -> List[Ev]:
    """Events whose short name starts with `prefix`."""
    return [e for e in evs if short(e.name).startswith(prefix)]


def leaves(evs: Sequence[Ev]) -> List[Ev]:
    """Events that contain no other event (the op line nests a loop's
    body inside the loop)."""
    evs = sorted(evs, key=lambda e: (e.start, -e.dur))
    return [e for i, e in enumerate(evs)
            if i + 1 == len(evs) or evs[i + 1].start >= e.end]


def inside(evs: Sequence[Ev], outer: Ev) -> List[Ev]:
    return [e for e in evs if e.start >= outer.start and e.end <= outer.end]


def gaps_between(tr: Trace, progs: Sequence[Ev]) -> List[float]:
    """For consecutive programs in `progs`, the device idle time (ns)
    between the end of one and the start of the next."""
    busy = union([(e.start, e.end) for e in tr.ops])
    starts = np.asarray([a for a, _ in busy])
    out = []
    for a, b in zip(progs, progs[1:]):
        lo, hi = a.end, b.start
        if hi <= lo:
            out.append(0.0)
            continue
        i0 = max(0, int(np.searchsorted(starts, lo)) - 1)
        covered = 0.0
        for s, e in busy[i0:]:
            if s >= hi:
                break
            covered += max(0.0, min(e, hi) - max(s, lo))
        out.append(hi - lo - covered)
    return out


def align(progs: Sequence[Ev], spans: Sequence[Tuple[float, float]],
          slack_ns: float = 2e6) -> Optional[int]:
    """Match device programs to the host records that launched them.

    `progs` run in launch order; `spans[j]` is (dispatched, done) of
    record j on the trace clock: program i of the trace belongs to record
    k + i, where it must start after that record's dispatch and end
    before its done time, within `slack_ns` of the two clocks' skew.  Returns the offset k that
    satisfies every program, the smallest one if several do, or None."""
    m, n = len(progs), len(spans)
    if m == 0 or m > n:
        return None
    st = np.asarray([p.start for p in progs])
    en = np.asarray([p.end for p in progs])
    d = np.asarray([s[0] for s in spans])
    f = np.asarray([s[1] for s in spans])
    for k in range(n - m + 1):
        if (np.all(d[k:k + m] <= st + slack_ns)
                and np.all(f[k:k + m] >= en - slack_ns)):
            return k
    return None


def top_ops(tr: Trace, k: int = 10) -> List[List]:
    """The `k` operation names that took most device time, in seconds."""
    tot: Dict[str, float] = {}
    for e in leaves(tr.ops):
        n = short(e.name)
        tot[n] = tot.get(n, 0.0) + e.dur
    return [[n, v / 1e9] for n, v in
            sorted(tot.items(), key=lambda x: -x[1])[:k]]

