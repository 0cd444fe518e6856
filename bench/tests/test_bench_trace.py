"""The reduction from trace events to device facts, on small traces whose
answers are known."""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import harness, programs  # noqa: E402
from bench import trace as T  # noqa: E402
from bench.peaks import least_seconds  # noqa: E402

MS = 1e6   # ns


def small():
    """A 100 ms slice: two decode segments (10-30, 50-70 ms) with a
    decode-attention kernel inside each, and a prefill (35-45 ms) with a
    flash kernel."""
    mods = [T.Ev("jit_segment(11)", 10 * MS, 20 * MS),
            T.Ev("jit_prefill(22)", 35 * MS, 10 * MS),
            T.Ev("jit_segment(11)", 50 * MS, 20 * MS)]
    ops = [T.Ev("%fusion.1 = f32[8]", 10 * MS, 8 * MS),
           T.Ev("%decode_attention_fused.8 = f32[16]", 18 * MS, 4 * MS),
           T.Ev("%fusion.2 = f32[8]", 22 * MS, 8 * MS),
           T.Ev("%flash_attention.6 = bf16[1]", 35 * MS, 6 * MS),
           T.Ev("%fusion.3 = f32[8]", 41 * MS, 4 * MS),
           # the op line nests a loop's body inside the loop
           T.Ev("%while.3 = (s32[])", 50 * MS, 20 * MS),
           T.Ev("%fusion.1 = f32[8]", 50 * MS, 11 * MS),
           T.Ev("%decode_attention_fused.8 = f32[16]", 61 * MS, 5 * MS),
           T.Ev("%fusion.2 = f32[8]", 66 * MS, 4 * MS)]
    return T.Trace(ops, mods, 0.0, 100 * MS, perf0=1000.0)


def test_busy_idle_and_union():
    tr = small()
    # busy: 10-30, 35-45, 50-70 ms = 50 ms of 100
    assert T.busy_ns(tr.ops, tr.lo, tr.hi) == 50 * MS
    assert abs(T.idle_share(tr) - 0.5) < 1e-12
    assert T.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert T.busy_ns(tr.ops, 15 * MS, 40 * MS) == 20 * MS


def test_kernel_time_and_segment_gaps():
    tr = small()
    segs = T.named(tr.modules, "jit_segment(")
    assert len(segs) == 2
    k = T.named(tr.ops, "%decode_attention_fused.")
    assert sum(e.dur for s in segs for e in T.inside(k, s)) == 9 * MS
    # between the segments the device idles 30-35 and 45-50 ms
    assert T.gaps_between(tr, segs) == [10 * MS]
    assert T.top_ops(tr, 2) == [["%fusion.1", 0.019], ["%fusion.2", 0.012]]
    assert len(T.leaves(tr.ops)) == len(tr.ops) - 1


def test_align_ties_programs_to_launches():
    tr = small()
    segs = T.named(tr.modules, "jit_segment(")
    ns = lambda ms: ms * MS
    # launch 0 ran before the trace; launches 1 and 2 are the two traced
    spans = [(ns(-30), ns(5)), (ns(5), ns(33)), (ns(31), ns(72)),
             (ns(71), ns(120))]
    assert T.align(segs, spans) == 1
    # a launch that came back before its program ended cannot be it
    assert T.align(segs, [(ns(5), ns(20)), (ns(31), ns(72))]) is None
    # the last launch of the window was never read back: no end bound
    assert T.align(segs, spans[:2] + [(ns(31), np.inf)]) == 1
    assert abs(tr.to_ns(1000.001) - 1 * MS) < 1e-3


def run_on(tr):
    """A Run over the small trace whose launch records match it."""
    cell = harness.load_cell(harness.ROOT, "starcoder2_3b.code_fim")
    t = lambda ms: 1000.0 + ms / 1e3
    segs = [harness.Launch(t(5), t(33), positions=[np.array([100, 200])] * 8),
            harness.Launch(t(31), t(72), positions=[np.array([108])] * 8)]
    pres = [harness.Launch(t(34), t(46), length=1000)]
    counters = {"seg_len": 8, "batch": 16, "max_seq": 4096,
                "page_size": 128, "pages_resident_peak": 64}
    return harness.Run(cell, t(0), t(100), pres, segs, counters, tr,
                       "TPU v5 lite")


def test_metric_readers_on_the_small_trace():
    run = run_on(small())
    read = lambda n: harness.metric_reader(harness.ROOT, n)(run)
    assert abs(read("decode_step_ms") - 40 / 16) < 1e-9
    assert abs(read("segment_gap_ms") - 10.0) < 1e-9
    assert abs(read("device_idle_share") - 50.0) < 1e-9
    assert abs(read("prefill_ms_per_ktok") - 10.0) < 1e-9
    assert abs(read("batch_occupancy") - 100 * 24 / (2 * 8 * 16)) < 1e-9
    assert abs(read("kv_reserved_over_used") - 16 * 4096 / (64 * 128)) < 1e-9
    assert len(programs.matched_segments(run)) == 2
    fam, cfg, pk = run.family, run.config, run.peaks
    least = 8 * (least_seconds(*fam.decode_step_work(cfg, [100, 200]), pk)
                 + least_seconds(*fam.decode_step_work(cfg, [108]), pk))
    # two 20 ms segments and the 10 ms of idle between them
    assert abs(read("mfu.decode") - 100 * least / 0.050) < 1e-9
    least = least_seconds(*fam.prefill_work(cfg, 1000), pk)
    assert abs(read("mfu.prefill") - 100 * least / 0.010) < 1e-9
    least = 30 * 8 * (
        least_seconds(*fam.decode_attention_work(cfg, [100, 200]), pk)
        + least_seconds(*fam.decode_attention_work(cfg, [108]), pk))
    assert abs(read("decode_attention_roofline") - 100 * least / 0.009) \
        < 1e-9
    least = 30 * least_seconds(*fam.flash_prefill_work(cfg, 1000), pk)
    assert abs(read("flash_prefill_roofline") - 100 * least / 0.006) < 1e-9
    # the SSD kernel is not in this trace: its reader finds nothing
    assert read("ssd_scan_roofline") is None
    # the same quantity, reported where it moves output_tokens_per_s
    for base in ("segment_gap_ms", "decode_step_ms", "prefill_ms_per_ktok",
                 "mfu.decode", "mfu.prefill", "device_idle_share"):
        assert read(base + ".tput") == read(base)


def test_idle_gaps_go_to_the_host_span_around_them():
    tr = small()
    t = lambda ms: 1000.0 + ms / 1e3
    spans = [("_admit", t(30), t(47)), ("_fill_slots", t(29), t(48)),
             ("wait_for_arrival", t(70), t(100))]
    got = dict(harness.idle_by_host_span(tr, spans))
    assert abs(got["_admit"] - 0.005) < 1e-9           # 30-35 ms
    assert abs(got["_fill_slots"] - 0.005) < 1e-9      # 45-50 ms
    assert abs(got["wait_for_arrival"] - 0.030) < 1e-9
    assert abs(got["serve_loop"] - 0.010) < 1e-9       # 0-10 ms


def recorded():
    """360 ms of a profiler trace of starcoder2_3b.code_fim served on one
    TPU v5e: a decode segment (8 steps x 30 layers) and the admission and
    prefill that follow it."""
    import json
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "v5e_trace_excerpt.json")
    return T.Trace.from_json(json.load(open(path)))


def test_recorded_trace_busy_matches_a_brute_force_count():
    tr = recorded()
    step = 100.0                                   # ns
    mask = np.zeros(int(tr.hi / step) + 1, bool)
    for e in tr.ops:
        a = max(0, int(np.ceil(e.start / step)))
        b = min(len(mask), int(np.ceil(e.end / step)))
        mask[a:b] = True
    brute = mask[:int(tr.hi / step)].sum() * step
    busy = T.busy_ns(tr.ops, tr.lo, tr.hi)
    assert abs(busy - brute) <= step * (len(T.union(
        [(e.start, e.end) for e in tr.ops])) + 2)
    assert 0.05 < T.idle_share(tr) < 0.15


def test_recorded_trace_kernel_time_and_programs():
    tr = recorded()
    segs = T.named(tr.modules, programs.SEGMENT)
    pres = T.named(tr.modules, programs.PREFILL)
    assert len(segs) == 1 and len(pres) == 2
    kern = T.named(tr.ops, programs.DECODE_ATTENTION)
    assert len(kern) == 8 * 30                     # steps x layers
    inside = T.inside(kern, segs[0])
    assert len(inside) == len(kern)
    assert sum(e.dur for e in inside) == 138861180
    flash = T.named(tr.ops, programs.FLASH_PREFILL)
    assert flash and all(T.inside([e], pres[0]) or T.inside([e], pres[1])
                         or e.end > tr.hi for e in flash)
    # the loop over layers contains its body: only leaves are counted
    top = dict(T.top_ops(tr, 50))
    assert abs(top["%decode_attention_fused.8"] - 0.13886118) < 1e-9
    assert not any(n.startswith("%while") for n in top)
