"""Where the device idles, by the serving loop's own spans.

    python3 -m bench.spans --workload <name> --seed <n> --seconds <s> \\
        [--whole-window] [--keep <dir>]

The program opens a `serve.<part>` profiler span around each piece of
host work in its serving loop (`repro.launch.serve`), stamps each
request with `arrival`, `admitted_at` and `first_token_at`, and names its
device steps with `jax.named_scope` (`repro.launch.steps`).  This module
reads all three back.  Its command runs one window of a cell as
`bench.run` does, profiles the window's last 5 s (or all of it), and
prints one JSON line: the device idle of the traced slice by the
innermost span the host was in, the share of it inside some span,
the idle inside each admission and its parts, the sampling epilogue's
device time per decode step, queue wait and admission times from the
stamps, and the window's tokens/s and median gap between decode
segments.  `--keep` saves the profile, gzipped.  Needs the chip, like a
run.

Spans are host events, already on the trace's clock.  An op's scope is
its framework op name (the `tf_op` stat of its metadata, which
`jax.profiler.ProfileData` does not expose; it is read from the
`XSpace` message itself).  The command keys the compile cache on the
programs' metadata, so its first run after a program edit compiles
cold.
"""
from __future__ import annotations

import bisect
import dataclasses
import importlib.util
import os
import sys
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from bench import programs
from bench import trace as trace_lib

PREFIX = "serve."
ADMIT = "serve.admit"
OUTSIDE = "outside"          # idle while the host was in no span
EPILOGUE = "sampling_epilogue"


@dataclasses.dataclass
class Span:
    name: str
    start: float            # ns, trace clock
    end: float
    args: Dict[str, Any]


@dataclasses.dataclass
class Profile:
    """A traced slice: the chip's ops and programs (`trace`), the
    program's host spans, and the chip's ops again with each op's scope
    path as its name (`scoped`)."""
    trace: trace_lib.Trace
    spans: List[Span]
    scoped: List[trace_lib.Ev]

    @classmethod
    def from_json(cls, d: Dict) -> "Profile":
        """`d` is a `Trace.from_json` record with two more lists:
        "spans" of [name, start, dur, args] and "scoped" of [scope,
        start, dur]."""
        return cls(trace_lib.Trace.from_json(d),
                   [Span(n, s, s + dur, a) for n, s, dur, a in d["spans"]],
                   [trace_lib.Ev(*e) for e in d["scoped"]])


def _xplane_pb2():
    """The `XSpace` message classes, loaded from the file the installed
    TensorFlow carries, without importing TensorFlow."""
    spec = importlib.util.find_spec("tensorflow")
    path = os.path.join(os.path.dirname(spec.origin), "tsl", "profiler",
                        "protobuf", "xplane_pb2.py")
    mod_spec = importlib.util.spec_from_file_location("bench_xplane_pb2",
                                                      path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def _scoped_ops(path: str) -> List[trace_lib.Ev]:
    """Every op event of the first TPU's op line, named by its scope."""
    xs = _xplane_pb2().XSpace()
    with open(path, "rb") as f:
        xs.ParseFromString(f.read())
    planes = sorted((p for p in xs.planes
                     if p.name.startswith(trace_lib.DEVICE_PLANE)),
                    key=lambda p: p.name)
    if not planes:
        return []
    plane = planes[0]
    tf_op = [k for k, m in plane.stat_metadata.items() if m.name == "tf_op"]
    scope = {}
    for k, md in plane.event_metadata.items():
        scope[k] = next((s.str_value for s in md.stats
                         if s.metadata_id in tf_op), "")
    out = []
    for line in plane.lines:
        if line.name != trace_lib.OPS_LINE:
            continue
        out += [trace_lib.Ev(scope.get(e.metadata_id, ""),
                             line.timestamp_ns + e.offset_ps / 1e3,
                             e.duration_ps / 1e3) for e in line.events]
    return out


def read(path: str, perf0: float) -> Profile:
    """The first TPU's slice of the profile at `path` (see
    `trace_lib.read`), with the program's spans and the ops' scopes."""
    from jax.profiler import ProfileData
    tr = trace_lib.read(path, perf0)[0]
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host"):
            for ln in plane.lines:
                spans += [Span(e.name, e.start_ns,
                               e.start_ns + e.duration_ns, dict(e.stats))
                          for e in ln.events if e.name.startswith(PREFIX)]
    spans = [s for s in spans if s.end > tr.lo and s.start < tr.hi]
    scoped = [e for e in _scoped_ops(path) if e.end > tr.lo
              and e.start < tr.hi]
    return Profile(tr, sorted(spans, key=lambda s: (s.start, -s.end)),
                   sorted(scoped, key=lambda e: e.start))


# ---- device idle, put down to spans ---------------------------------------

def idle_intervals(tr: trace_lib.Trace) -> List[Tuple[float, float]]:
    """The stretches of [lo, hi] in which no op ran."""
    out, t = [], tr.lo
    for a, b in trace_lib.union([(e.start, e.end) for e in tr.ops]):
        if a > t:
            out.append((t, min(a, tr.hi)))
        t = max(t, b)
        if t >= tr.hi:
            break
    if t < tr.hi:
        out.append((t, tr.hi))
    return [(a, b) for a, b in out if b > a]


def idle_by_span(prof: Profile,
                 within: Optional[str] = None) -> Dict[str, float]:
    """Seconds of device idle by the innermost span the host was in
    (`OUTSIDE` where it was in none).  With `within`, only the idle
    inside spans of that name, by the innermost span there."""
    idle = idle_intervals(prof.trace)
    cuts = sorted({x for s in prof.spans for x in (s.start, s.end)}
                  | {x for ab in idle for x in ab})
    by_start = sorted(prof.spans, key=lambda s: s.start)
    by_end = sorted(range(len(by_start)), key=lambda k: by_start[k].end)
    active: Dict[int, Span] = {}
    i = j = 0
    tot: Dict[str, float] = {}
    for a, b in idle:
        lo, hi = bisect.bisect_left(cuts, a), bisect.bisect_left(cuts, b)
        for x, y in zip(cuts[lo:hi], cuts[lo + 1:hi + 1]):
            mid = (x + y) / 2
            while i < len(by_start) and by_start[i].start <= mid:
                active[i] = by_start[i]
                i += 1
            while j < len(by_end) and by_start[by_end[j]].end < mid:
                active.pop(by_end[j], None)
                j += 1
            if within is not None and not any(
                    s.name == within for s in active.values()):
                continue
            inner = min(active.values(), key=lambda s: s.end - s.start,
                        default=None)
            name = inner.name if inner is not None else OUTSIDE
            tot[name] = tot.get(name, 0.0) + (y - x) / 1e9
    return tot


def inside_share(prof: Profile) -> Optional[float]:
    """The share of the slice's device idle during which the host was
    inside some span."""
    by = idle_by_span(prof)
    total = sum(by.values())
    return None if total <= 0 else 1.0 - by.get(OUTSIDE, 0.0) / total


def admit_idle_ms(prof: Profile) -> Optional[float]:
    """Device idle inside admissions, over the admissions the slice
    holds (in part or whole), in ms; None without one."""
    n = sum(s.name == ADMIT for s in prof.spans)
    if not n:
        return None
    return 1e3 * sum(idle_by_span(prof, within=ADMIT).values()) / n


def epilogue_ms_per_step(prof: Profile, seg_len: int) -> Optional[float]:
    """Device time of the ops scoped `sampling_epilogue` in the decode
    segments that lie wholly in the slice and sample, per decode step,
    in ms; None where no op carries the scope."""
    tr = prof.trace
    segs = [m for m in trace_lib.named(tr.modules, programs.SEGMENT)
            if m.start >= tr.lo and m.end <= tr.hi]
    leaves = [e for e in trace_lib.leaves(prof.scoped)
              if EPILOGUE in e.name]
    per = [sum(e.dur for e in trace_lib.inside(leaves, m)) for m in segs]
    per = [p for p in per if p > 0]
    if not per:
        return None
    return sum(per) / (len(per) * seg_len) / 1e6


# ---- request stamps -------------------------------------------------------

def queue_and_admit_ms(reqs, due: Dict[int, float], w0: float, w1: float
                       ) -> Dict[str, Optional[float]]:
    """From the requests' stamps: p50 and p90 of the queue wait over the
    requests due in [w0, w1) (one still queued at w1 counts with its
    wait so far), and p50 of the admission time (admitted_at to
    first_token_at) over the admissions that began in the window; None
    where the requests carry no stamps."""
    if not any(getattr(r, "admitted_at", None) is not None for r in reqs):
        return {"queue_wait_ms_p50": None, "queue_wait_ms_p90": None,
                "admit_ms_p50": None}
    wait = []
    for r in reqs:
        t = due[r.rid]
        if w0 <= t < w1:
            a = r.admitted_at
            wait.append((a if a is not None and a <= w1 else w1) - t)
    admit = [r.first_token_at - r.admitted_at for r in reqs
             if r.admitted_at is not None and r.first_token_at is not None
             and w0 <= r.admitted_at < w1]
    pct = lambda x, q: 1e3 * float(np.percentile(x, q)) if x else None
    return {"queue_wait_ms_p50": pct(wait, 50),
            "queue_wait_ms_p90": pct(wait, 90),
            "admit_ms_p50": pct(admit, 50)}


# ---- one window -----------------------------------------------------------

def run_window(root: str, workload: str, seed: int, seconds: float,
               whole: bool, keep: Optional[str]) -> Dict[str, Any]:
    import glob
    import gzip
    import shutil

    import jax

    from bench import harness, stats, traffic

    # JAX's cache key leaves out the programs' metadata, so an executable
    # cached before a scope was added would serve without it
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    sess = harness.set_up(root, workload, seed)
    cell, server = sess.cell, sess.server
    arrivals = traffic.generate(cell.mix, seed, seconds, server.cfg.vocab)
    got: Dict[str, Any] = {}

    class Tracer(harness.Tracer):
        def read(self):
            pb = glob.glob(os.path.join(self.dir, "plugins", "profile",
                                        "*", "*.xplane.pb"))[0]
            try:
                got["profile"] = read(pb, self.perf0)
                if keep:
                    os.makedirs(keep, exist_ok=True)
                    dst = os.path.join(keep,
                                       f"{workload}.{seed}.xplane.pb.gz")
                    with open(pb, "rb") as f, gzip.open(dst, "wb") as g:
                        shutil.copyfileobj(f, g)
                    got["perf0"] = self.perf0
            finally:
                shutil.rmtree(self.dir, ignore_errors=True)

    lead = cell.mix["lead_in_s"]
    traced = seconds if whole else min(harness.TRACE_SECONDS, seconds)
    reqs, w0, w1, _, tracer, _ = harness.drive(
        server, sess.probe, arrivals, lead, seconds,
        lambda a, b: Tracer(jax, b - traced, b), drain=0.0)
    tracer.read()
    prof = got["profile"]
    t0 = w0 - lead
    due = {a.rid: t0 + a.due for a in arrivals}
    timelines = [stats.Timeline(due[r.rid], list(r.generated.times))
                 for r in reqs]
    e2e = stats.end_to_end(timelines, w0, w1)
    tr = prof.trace
    gaps = trace_lib.gaps_between(
        tr, trace_lib.named(tr.modules, programs.SEGMENT))
    by = idle_by_span(prof)
    idle_s = sum(by.values())
    scopes = {n: sum(f"/{n}/" in e.name for e in prof.scoped)
              for n in ("decode_segment", EPILOGUE, "prefill")}
    out = {
        "workload": workload, "seed": seed, "seconds": seconds,
        "traced_s": tr.window_s, "whole_window": whole,
        "device_kind": jax.devices()[0].device_kind,
        "output_tokens_per_s": e2e["output_tokens_per_s"],
        "ttft_p90_ms": e2e["ttft_p90_ms"],
        "segment_gap_ms": float(np.median(gaps)) / 1e6 if gaps else None,
        "idle_s": idle_s, "idle_share": idle_s / tr.window_s,
        "idle_inside_span_share": inside_share(prof),
        "idle_by_span_s": dict(sorted(by.items(), key=lambda x: -x[1])),
        "admissions": sum(s.name == ADMIT for s in prof.spans),
        "admit_idle_ms": admit_idle_ms(prof),
        "admit_idle_by_part_s": idle_by_span(prof, within=ADMIT),
        "sampling_epilogue_ms": epilogue_ms_per_step(prof, server.seg_len),
        "scoped_ops": scopes,
        **queue_and_admit_ms(reqs, due, w0, w1),
    }
    if "perf0" in got:
        out["perf0"] = got["perf0"]
    return out


def main() -> int:
    import argparse
    import json
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--whole-window", action="store_true")
    ap.add_argument("--keep", default=None)
    args = ap.parse_args()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from bench import harness
    try:
        out = run_window(root, args.workload, args.seed, args.seconds,
                         args.whole_window, args.keep)
    except harness.NoChip as e:
        print(f"spans: {e}", file=sys.stderr)
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
