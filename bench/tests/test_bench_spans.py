"""Device idle put down to the serving loop's own spans (`bench/spans.py`),
on small traces whose answers are known and on a recorded v5e slice; and
the per-layer readers' values on the first recorded slice, pinned."""
import json
import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import harness, programs, spans  # noqa: E402
from bench import trace as T  # noqa: E402

MS = 1e6   # ns
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def small():
    """A 100 ms slice: ops at 10-30 and 50-70 ms; the host admits a
    request at 25-60 ms (first token 25-40, seeding 40-55) and is in no
    span from 80 ms on."""
    ops = [T.Ev("%fusion.1", 10 * MS, 20 * MS),
           T.Ev("%fusion.2", 50 * MS, 20 * MS)]
    tr = T.Trace(ops, [], 0.0, 100 * MS, perf0=0.0)
    sp = [spans.Span("serve.fill_slots", 0, 80 * MS, {}),
          spans.Span("serve.admit", 25 * MS, 60 * MS, {"rid": 3}),
          spans.Span("serve.first_token", 25 * MS, 40 * MS, {}),
          spans.Span("serve.seed_slot", 40 * MS, 55 * MS, {})]
    return spans.Profile(tr, sp, [])


def test_idle_goes_to_the_innermost_span():
    prof = small()
    assert spans.idle_intervals(prof.trace) == [
        (0.0, 10 * MS), (30 * MS, 50 * MS), (70 * MS, 100 * MS)]
    got = spans.idle_by_span(prof)
    want = {"serve.fill_slots": 0.010 + 0.010, "serve.first_token": 0.010,
            "serve.seed_slot": 0.010, spans.OUTSIDE: 0.020}
    assert got.keys() == want.keys()
    assert all(abs(got[k] - v) < 1e-12 for k, v in want.items())
    assert abs(spans.inside_share(prof) - 0.04 / 0.06) < 1e-12
    # inside the admission: 30-50 ms, split between its two parts
    within = spans.idle_by_span(prof, within=spans.ADMIT)
    assert within == pytest.approx({"serve.first_token": 0.010,
                                    "serve.seed_slot": 0.010})
    assert spans.admit_idle_ms(prof) == pytest.approx(20.0)


def test_no_spans_no_admission_idle():
    """A program without spans: every idle stretch is outside, and no
    admission reading is made."""
    prof = small()
    prof.spans = []
    assert spans.idle_by_span(prof) == pytest.approx(
        {spans.OUTSIDE: 0.060})
    assert spans.inside_share(prof) == 0.0
    assert spans.admit_idle_ms(prof) is None
    assert spans.epilogue_ms_per_step(prof, 8) is None


def test_queue_wait_and_admission_from_stamps():
    r = lambda rid, adm, first: SimpleNamespace(
        rid=rid, admitted_at=adm, first_token_at=first)
    due = {0: 1.0, 1: 2.0, 2: 3.0, 3: 9.5}
    reqs = [r(0, 1.5, 1.6), r(1, 2.1, 2.3), r(2, None, None),
            r(3, 9.6, 9.7)]
    got = spans.queue_and_admit_ms(reqs, due, 0.0, 5.0)
    # waits 500, 100 and (still queued at 5 s) 2,000 ms; request 3 is
    # due after the window
    assert got["queue_wait_ms_p50"] == pytest.approx(500.0)
    assert got["queue_wait_ms_p90"] == pytest.approx(
        1e3 * np.percentile([0.5, 0.1, 2.0], 90))
    assert got["admit_ms_p50"] == pytest.approx(150.0)
    # a program that does not stamp its requests gives no reading
    bare = [SimpleNamespace(rid=0)]
    assert set(spans.queue_and_admit_ms(bare, {0: 1.0}, 0.0, 5.0)
               .values()) == {None}


def write_profile(path):
    """A profile as the profiler writes one: the host's two window anchors
    (1,000 and 2,000 ns) and an admission with its seeding, the chip's ops
    (one named by its scope) and a decode-segment program."""
    pb2 = spans._xplane_pb2()
    xs = pb2.XSpace()

    def plane(pid, name, stats, events):
        p = xs.planes.add(id=pid, name=name)
        for k, n in stats.items():
            p.stat_metadata[k].id, p.stat_metadata[k].name = k, n
        for k, n in events.items():
            p.event_metadata[k].id, p.event_metadata[k].name = k, n
        return p

    def ev(line, mid, start, dur, stats=()):
        e = line.events.add(metadata_id=mid, duration_ps=int(dur * 1e3),
                            offset_ps=int((start - line.timestamp_ns) * 1e3))
        for sid, v in stats:
            e.stats.add(metadata_id=sid, int64_value=v)

    host = plane(1, "/host:CPU", {1: "rid"},
                 {1: T.ANCHOR, 2: spans.ADMIT, 3: "serve.seed_slot"})
    ln = host.lines.add(id=1, name="python", timestamp_ns=1000)
    ev(ln, 1, 1000, 1)
    ev(ln, 2, 1100, 500, [(1, 7)])
    ev(ln, 3, 1300, 200)
    ev(ln, 1, 2000, 1)
    dev = plane(2, "/device:TPU:0", {1: "tf_op"},
                {1: "%fusion.1 = f32[8]", 2: "%sort.2 = f32[8]",
                 3: "jit_segment(5)"})
    dev.event_metadata[2].stats.add(
        metadata_id=1,
        str_value="jit(segment)/decode_segment/while/body/"
                  "sampling_epilogue/sort")
    ops = dev.lines.add(id=1, name=T.OPS_LINE, timestamp_ns=1000)
    ev(ops, 1, 1050, 100)
    ev(ops, 2, 1150, 50)
    ev(ops, 1, 1600, 300)
    mods = dev.lines.add(id=2, name=T.MODULES_LINE, timestamp_ns=1000)
    ev(mods, 3, 1050, 150)
    with open(path, "wb") as f:
        f.write(xs.SerializeToString())


def test_read_a_profile(tmp_path):
    path = str(tmp_path / "t.xplane.pb")
    write_profile(path)
    prof = spans.read(path, perf0=5.0)
    tr = prof.trace
    assert (tr.lo, tr.hi) == (1000.0, 2000.0)
    assert [(e.start, e.dur) for e in tr.ops] == [
        (1050.0, 100.0), (1150.0, 50.0), (1600.0, 300.0)]
    assert [(s.name, s.start, s.end, s.args) for s in prof.spans] == [
        (spans.ADMIT, 1100.0, 1600.0, {"rid": 7}),
        ("serve.seed_slot", 1300.0, 1500.0, {})]
    assert [e.name for e in prof.scoped] == [
        "", "jit(segment)/decode_segment/while/body/sampling_epilogue/sort",
        ""]
    # idle 1000-1050 and 1900-2000 outside; 1200-1600 in the admission
    assert spans.idle_by_span(prof) == pytest.approx(
        {spans.OUTSIDE: 150e-9, spans.ADMIT: 200e-9,
         "serve.seed_slot": 200e-9})
    assert spans.epilogue_ms_per_step(prof, 1) == pytest.approx(50e-6)


def recorded():
    """778 ms of a profile of mamba2_370m.chat_burst served on one TPU
    v5e: a sampled decode segment, one admission, and the start of the
    next segment, with the program's host spans and op scopes."""
    with open(os.path.join(DATA, "v5e_trace_spans_excerpt.json")) as f:
        return spans.Profile.from_json(json.load(f))


def test_recorded_spans_nest_and_carry_args():
    prof = recorded()
    names = {s.name for s in prof.spans}
    assert {"serve.fill_slots", "serve.admit", "serve.prefill_dispatch",
            "serve.first_token", "serve.seed_slot", "serve.dispatch_rows",
            "serve.segment_dispatch", "serve.consume",
            "serve.consume.fetch", "serve.assert_ledger"} <= names
    admit, = [s for s in prof.spans if s.name == spans.ADMIT]
    assert admit.args == {"rid": 136, "prompt_len": 1009, "bucket": 1024}
    for part in ("serve.prefill_dispatch", "serve.first_token",
                 "serve.seed_slot"):
        s, = [s for s in prof.spans if s.name == part]
        assert admit.start <= s.start and s.end <= admit.end
    seg = [s for s in prof.spans if s.name == "serve.segment_dispatch"]
    assert seg[0].args == {"live_rows": 39, "plain": 0}
    # every op scope is a path through the program's named scopes
    assert prof.scoped and all(
        e.name.startswith("jit(segment)/decode_segment/")
        and "/sampling_epilogue/" in e.name
        or e.name.startswith("jit(prefill)/prefill/") for e in prof.scoped)


def test_recorded_idle_sits_in_the_admission():
    prof = recorded()
    tr = prof.trace
    by = spans.idle_by_span(prof)
    assert max(by, key=by.get) == "serve.seed_slot"
    assert spans.inside_share(prof) > 0.99
    # a brute-force count of the idle inside the admission, on a 100 ns
    # grid: each op covers the grid points from its start to its end
    step = 100.0
    n = int((tr.hi - tr.lo) / step)
    cover = np.zeros(n + 1, np.int64)
    for e in tr.ops:
        a = int(np.clip(np.ceil((e.start - tr.lo) / step), 0, n))
        b = int(np.clip(np.ceil((e.end - tr.lo) / step), 0, n))
        cover[a] += 1
        cover[b] -= 1
    idle = np.cumsum(cover)[:n] == 0
    admit, = [s for s in prof.spans if s.name == spans.ADMIT]
    a, b = (int(np.ceil((x - tr.lo) / step)) for x in (admit.start,
                                                          admit.end))
    brute = idle[a:b].sum() * step
    assert abs(spans.admit_idle_ms(prof) * 1e6 - brute) < 50 * step
    assert 25.0 < spans.admit_idle_ms(prof) < 40.0


def test_recorded_sampling_epilogue_per_step():
    prof = recorded()
    seg, = [m for m in T.named(prof.trace.modules, programs.SEGMENT)
            if m.start >= prof.trace.lo and m.end <= prof.trace.hi]
    epi = [e for e in T.leaves(prof.scoped) if spans.EPILOGUE in e.name]
    want = sum(e.dur for e in T.inside(epi, seg)) / 8 / 1e6
    assert spans.epilogue_ms_per_step(prof, 8) == pytest.approx(want)
    # most of the sampled step: the full-vocabulary gathers
    assert 0.5 < want / (seg.dur / 8 / 1e6) < 1.0


# the value each per-layer reader gives on the first recorded slice, with
# launch records matched to its programs by hand: pinned, so that a change
# to the trace reduction cannot move them unseen
PINNED = {
    "batch_occupancy": 100.0,
    "kv_reserved_over_used": 1.28,
    "segment_gap_ms": None,
    "segment_gap_ms.tput": None,
    "decode_step_ms": 34.29993125,
    "decode_step_ms.tput": 34.29993125,
    "prefill_ms_per_ktok": 234.62748577777776,
    "prefill_ms_per_ktok.tput": 234.62748577777776,
    "mfu.decode": 32.10206355424613,
    "mfu.decode.tput": 32.10206355424613,
    "mfu.prefill": 18.352290358333722,
    "mfu.prefill.tput": 18.352290358333722,
    "decode_attention_roofline": 4.920070523200209,
    "flash_prefill_roofline": 21.263125829118934,
    "ssd_scan_roofline": None,
    "device_idle_share": 8.595603611111112,
    "device_idle_share.tput": 8.595603611111112,
}


def test_existing_readers_unchanged_on_the_first_recorded_slice():
    with open(os.path.join(DATA, "v5e_trace_excerpt.json")) as f:
        tr = T.Trace.from_json(json.load(f))
    s = lambda ns: ns / 1e9       # the slice's clock: perf0 0, lo 0
    pos = [np.arange(1400, 1416) + t for t in range(8)]
    segs = [harness.Launch(s(31.5e6), s(306.4e6), positions=pos)]
    pres = [harness.Launch(s(-106.5e6), s(1.04e6), length=1500),
            harness.Launch(s(306.0e6), None, length=3000)]
    counters = {"seg_len": 8, "batch": 16, "max_seq": 4096,
                "page_size": 128, "pages_resident_peak": 400}
    cell = harness.load_cell(harness.ROOT, "starcoder2_3b.code_fim")
    run = harness.Run(cell, s(-200e6), s(400e6), pres, segs, counters, tr,
                      "TPU v5 lite")
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        names = {m["name"] for m in json.load(f)["per_layer"]}
    assert set(PINNED) <= names
    for name, want in PINNED.items():
        got = harness.metric_reader(harness.ROOT, name)(run)
        assert (got is None) == (want is None), name
        if want is not None:
            assert got == pytest.approx(want, rel=1e-12), name
