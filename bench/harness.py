"""One run of one cell: set-up, warm-up, the measured window, the
correctness check and the metrics, all found by name.

Everything specific to a configuration, a traffic mix or a per-layer
metric sits in files of its own under the benchmark root:

  BENCHMARK.json                   cells and metrics
  bench/configs/<config>.json      sizes, serving shape, check limit;
                                   `family` names the plain reference
                                   module `bench/models/<family>.py`
  bench/traffic/<mix>.json         parameters of the one generator
  bench/metrics/<metric>.py        `read(run) -> float | None`

The window drives the program's own serving loop,
`BatchedServer(..., stream=True).run_stream`.  Arrivals enter through
the server's `queue` at their due times (`ArrivalQueue`), and every
delivered token is stamped on the host as it lands in
`Request.generated` (`Tokens`).  The benchmark's host spans and device
launch records come from thin wrappers around the server's own methods
and jitted steps (`Probe`): the program has no spans of its own yet.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from bench import stats, traffic
from bench import trace as trace_lib

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
TRACE_SECONDS = 5.0        # traced slice, at the end of the window
DRAIN_SECONDS = 120.0      # longest wait for requests after the window
CHECK_TOKENS = 300         # served tokens the check reads, at least,
CHECK_MIN_REQUESTS = 4     # from at least this many requests
CHECK_REQUESTS = 12        # and at most this many


class NoChip(RuntimeError):
    pass


# ---- the cell, found by name --------------------------------------------

@dataclasses.dataclass
class Cell:
    root: str
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]
    mix_name: str
    mix: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]

    @property
    def family(self):
        return importlib.import_module(
            "bench.models." + self.config["family"])


def load_cell(root: str, workload: str) -> Cell:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    with open(os.path.join(root, conf["file"])) as f:
        config = json.load(f)
    mine = lambda m: workload in m.get("workloads", [workload])
    return Cell(root, workload, int(w["chips"]), w["config"], config,
                w["traffic"], traffic.load_mix(w["traffic"],
                                               os.path.join(root, "bench")),
                [m for m in spec["end_to_end"] if mine(m)],
                [m for m in spec["per_layer"] if mine(m)])


def metric_reader(root: str, name: str) -> Callable:
    """`read` of `bench/metrics/<name>.py`."""
    path = os.path.join(root, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---- host instrumentation -------------------------------------------------

class CompileClock:
    """Sums JAX's backend-compile durations while it is installed."""

    def __init__(self, jax):
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1


class Tokens(list):
    """`Request.generated` that stamps each token as it lands."""

    def __init__(self):
        super().__init__()
        self.times: List[float] = []

    def append(self, tok):
        self.times.append(time.perf_counter())
        super().append(tok)


class Deadline(Exception):
    """Raised from the queue to leave the serving loop when the window's
    requests have not drained in time."""


class ArrivalQueue(list):
    """The server's request queue.  It holds only requests whose due time
    has passed: each look at it first moves in every request now due,
    and `hook(now)` runs there, on the serving loop's own thread."""

    def __init__(self, schedule, hook):
        super().__init__()
        self.schedule = schedule          # [(due, Request)], sorted
        self.next = 0
        self.hook = hook
        self.lag: List[float] = []        # release time - due time

    def release(self):
        now = time.perf_counter()
        self.hook(now)
        while (self.next < len(self.schedule)
               and self.schedule[self.next][0] <= now):
            due, req = self.schedule[self.next]
            self.lag.append(now - due)
            list.append(self, req)
            self.next += 1

    def __len__(self):
        self.release()
        return list.__len__(self)

    def pop(self, i=-1):
        self.release()
        return list.pop(self, i)

    def next_due(self) -> Optional[float]:
        if self.next < len(self.schedule):
            return self.schedule[self.next][0]
        return None


@dataclasses.dataclass
class Launch:
    """One device program launched by the serving loop: when the host
    dispatched it, when its result was back on the host, and what it
    computed (`length`: a prefill's true prompt length; `positions`: a
    decode segment's live rows' positions at each step)."""
    dispatched: float
    done: Optional[float] = None
    length: int = 0
    positions: Optional[List[np.ndarray]] = None


class Probe:
    """Wraps the server's jitted steps and host methods to record launch
    records and host spans.  It changes no argument and no result."""

    SPANS = ("_fill_slots", "_admit", "_pump_prefill", "_dispatch_rows",
             "_consume_segment", "assert_ledger")

    def __init__(self, server):
        self.server = server
        self.prefills: List[Launch] = []
        self.segments: List[Launch] = []
        self.spans: List[tuple] = []        # (name, start, end)
        self.recording = False
        self._consumed = 0
        for name in ("segment_fn", "segment_plain_fn"):
            setattr(server, name, self._segment(getattr(server, name)))
        server.prefill_fn = self._prefill(server.prefill_fn)
        for name in self.SPANS:
            setattr(server, name, self._span(name, getattr(server, name)))
        fin = server._finish_admit

        def finish_admit(*a, **k):
            out = fin(*a, **k)
            if self.prefills and self.prefills[-1].done is None:
                self.prefills[-1].done = time.perf_counter()
            return out
        server._finish_admit = finish_admit
        consume = server._consume_segment

        def consume_segment(seg, emit, state, rows, alens=None):
            out = consume(seg, emit, state, rows, alens=alens)
            if self._consumed < len(self.segments):
                rec = self.segments[self._consumed]
                rec.done = time.perf_counter()
                # both arrays were fetched by the consume above: these
                # reads are host copies, not device syncs
                em = np.asarray(emit).astype(bool)
                end = np.asarray(state.positions).astype(np.int64)
                after = np.cumsum(em[:, ::-1], axis=1)[:, ::-1]
                rec.positions = [(end - after[:, t])[em[:, t]]
                                 for t in range(em.shape[1])]
            self._consumed += 1
            return out
        server._consume_segment = consume_segment

    def reset(self):
        self.prefills.clear()
        self.segments.clear()
        self.spans.clear()
        self._consumed = 0

    def _segment(self, fn):
        def call(*a, **k):
            self.segments.append(Launch(time.perf_counter()))
            return fn(*a, **k)
        return call

    def _prefill(self, fn):
        def call(params, cache, padded, slot, plen, *rest):
            self.prefills.append(Launch(time.perf_counter(),
                                        length=int(plen)))
            return fn(params, cache, padded, slot, plen, *rest)
        return call

    def _span(self, name, fn):
        def call(*a, **k):
            t0 = time.perf_counter()
            try:
                return fn(*a, **k)
            finally:
                if self.recording:
                    self.spans.append((name, t0, time.perf_counter()))
        return call


class Tracer:
    """Starts and stops the profiler at fixed host times, from inside
    the serving loop (`tick`), and marks the traced slice with anchors."""

    def __init__(self, jax, start: float, stop: float):
        self.jax = jax
        self.start, self.stop = start, stop
        self.dir = None
        self.perf0 = None
        self.state = "before"

    def tick(self, now: float) -> None:
        if self.state == "before" and now >= self.start:
            self.dir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = self.jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # it slows every host call
            self.jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.perf0 = time.perf_counter()
            with self.jax.profiler.TraceAnnotation(trace_lib.ANCHOR):
                pass
            self.state = "on"
        elif self.state == "on" and now >= self.stop:
            with self.jax.profiler.TraceAnnotation(trace_lib.ANCHOR):
                pass
            self.jax.profiler.stop_trace()
            self.state = "done"

    def read(self) -> Optional[trace_lib.Trace]:
        if self.state != "done":
            return None
        try:
            import glob
            path = glob.glob(os.path.join(self.dir, "plugins", "profile",
                                          "*", "*.xplane.pb"))[0]
            traces = trace_lib.read(path, self.perf0)
            return traces[0] if traces else None
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


# ---- what a run leaves for the metric readers -------------------------------

@dataclasses.dataclass
class Run:
    cell: Cell
    w0: float                       # window, host perf_counter seconds
    w1: float
    prefills: List[Launch]
    segments: List[Launch]
    counters: Dict[str, Any]
    trace: Optional[trace_lib.Trace]
    device_kind: str

    @property
    def config(self):
        return self.cell.config

    @property
    def family(self):
        return self.cell.family

    @property
    def peaks(self):
        from bench.peaks import peaks_for
        return peaks_for(self.device_kind)

    def in_window(self, launches: List[Launch]) -> List[Launch]:
        return [r for r in launches if self.w0 <= r.dispatched < self.w1]

    def matched(self, progs, launches):
        """Pairs (device program, launch record) for the programs of the
        trace, or None when they cannot be tied to the records.  A launch
        whose result never came back (the window closed first) bounds
        nothing at its end."""
        tr = self.trace
        spans = [(tr.to_ns(r.dispatched),
                  tr.to_ns(r.done) if r.done is not None else np.inf)
                 for r in launches]
        k = trace_lib.align(progs, spans)
        if k is None:
            return None
        return list(zip(progs, launches[k:k + len(progs)]))


# ---- set-up ------------------------------------------------------------------

def _program():
    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    from repro.launch import serve
    return serve


def build_server(cell: Cell, seed: int):
    """The program's server at the configuration's serving shape, with
    weights drawn from `seed` by the benchmark and placed in the
    program's parameter layout."""
    import jax
    serve = _program()
    sv = cell.config["serving"]
    server = serve.BatchedServer(
        cell.config["program_arch"], smoke=bool(sv.get("smoke", False)),
        batch_slots=sv["slots"], max_seq=sv["max_seq"],
        seg_len=sv["seg_len"], stream=True, n_layers=sv.get("n_layers"))
    cell.family.check_program(cell.config, server.cfg)
    want = jax.eval_shape(lambda p: p, server.params)
    server.params = None
    gc.collect()
    params = cell.family.init_params(cell.config, seed)
    got = jax.eval_shape(lambda p: p, params)
    if jax.tree.structure(got) != jax.tree.structure(want) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want))):
        raise ValueError("benchmark weights do not match the program's "
                         "parameter layout")
    server.params = params
    return server


def make_request(serve, a: traffic.Arrival, eos: int):
    sp = serve.SamplingParams(
        temperature=a.temperature, top_p=a.top_p, seed=a.seed,
        stop_tokens=(eos,) if a.stop_at_eos else ())
    req = serve.Request(a.rid, a.prompt, a.max_new, sampling=sp)
    req.generated = Tokens()
    return req


def warm_up(server, cell: Cell, seed: int) -> int:
    """Compile every shape the cell's traffic can reach, and no other:
    one admission per prefill bucket the prompt range covers (with the
    mix's sampling, so the admission path and the segment variant it
    selects compile too), each decoding past one segment."""
    serve = _program()
    pt = cell.mix["prompt_tokens"]
    sv = cell.config["serving"]
    lengths = {}
    for n in range(pt["min"], pt["max"] + 1):
        lengths.setdefault(serve._prefill_bucket(n, sv["max_seq"]), n)
    kinds = traffic.generate(cell.mix, seed, 0.0, server.cfg.vocab)
    kinds = list({a.greedy: a for a in kinds}.values())
    shapes = [(kinds[0], n) for n in sorted(lengths.values())]
    shapes += [(a, min(lengths.values())) for a in kinds[1:]]
    for i, (a, length) in enumerate(shapes):
        a = dataclasses.replace(a, rid=-1 - i,
                                prompt=np.ones((length,), np.int32),
                                max_new=sv["seg_len"] + 2)
        server.queue.append(make_request(serve, a, server.cfg.eos_token))
    server.run_stream()
    server.completed.clear()
    return len(shapes)


# ---- the measured window ------------------------------------------------------

def drive(server, probe: Probe, arrivals: List[traffic.Arrival],
          lead: float, seconds: float, tracer_factory=None,
          drain: float = DRAIN_SECONDS):
    """Offer `arrivals` open loop: load starts now, the window is
    [now + lead, now + lead + seconds].  Returns (requests, w0, w1,
    queue, tracer, drained)."""
    serve = _program()
    eos = server.cfg.eos_token
    t0 = time.perf_counter() + 0.05
    w0, w1 = t0 + lead, t0 + lead + seconds
    reqs = [make_request(serve, a, eos) for a in arrivals]
    tracer = tracer_factory(w0, w1) if tracer_factory else None
    deadline = w1 + drain

    def hook(now):
        if tracer is not None:
            tracer.tick(now)
        if now > deadline:
            raise Deadline()

    q = ArrivalQueue([(t0 + a.due, r) for a, r in zip(arrivals, reqs)],
                     hook)
    server.queue = q
    probe.reset()
    probe.recording = True
    drained = True
    try:
        while True:
            server.run_stream()
            nxt = q.next_due()
            if nxt is None and not list.__len__(q):
                break
            if nxt is not None:
                wait = nxt - time.perf_counter()
                t = time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                probe.spans.append(("wait_for_arrival", t,
                                    time.perf_counter()))
            q.release()
        while tracer is not None and tracer.state != "done":
            time.sleep(0.01)
            tracer.tick(time.perf_counter())
    except Deadline:
        drained = False
    probe.recording = False
    return reqs, w0, w1, q, tracer, drained


# ---- the correctness check ---------------------------------------------------

def finished(server, reqs) -> set:
    """Ids of the requests whose every token has been delivered: retired
    by the server, and holding their whole budget or ending in a stop
    token (a request retired at dispatch has its last tokens still in
    flight until the next segment is consumed)."""
    done = {r.rid for r in server.completed}
    out = set()
    for r in reqs:
        if r.rid not in done or not r.generated:
            continue
        sp = r.sampling
        budget = sp.max_new if sp is not None and sp.max_new else r.max_new
        if len(r.generated) == budget or (
                sp is not None and r.generated[-1] in sp.stop_tokens):
            out.add(r.rid)
    return out


def check_sample(reqs, finished, seed: int):
    """Greedy requests the window finished, drawn from the seed: the one
    with most served tokens first, then others until there are
    CHECK_TOKENS served tokens from CHECK_MIN_REQUESTS requests, or
    CHECK_REQUESTS requests."""
    pool = [r for r in reqs if r.rid in finished
            and (r.sampling is None or r.sampling.temperature <= 0)]
    if not pool:
        return []
    longest = max(pool, key=lambda r: (len(r.generated), r.rid))
    rest = [r for r in pool if r is not longest]
    order = np.random.default_rng([int(seed) & 0xFFFFFFFF,
                                   int(seed) >> 32, 7]).permutation(len(rest))
    out, total = [longest], len(longest.generated)
    for i in order:
        if (total >= CHECK_TOKENS and len(out) >= CHECK_MIN_REQUESTS) \
                or len(out) >= CHECK_REQUESTS:
            break
        out.append(rest[i])
        total += len(rest[i].generated)
    return out


def served_gaps(cell: Cell, params, sample, modes=("f32",)):
    """For each request of the sample: the reference's logits at every
    position that produced a served token, and how far each served
    token's logit lies below the reference's best.  With "int8" among
    `modes`, also the gap of the token the int8 control puts first."""
    out = {m: [] for m in modes}
    for r in sample:
        gen = np.asarray(list(r.generated), np.int32)
        seq = np.concatenate([r.prompt, gen[:-1]]).astype(np.int32)
        rows = np.arange(len(r.prompt) - 1, len(seq))
        ref = cell.family.logits_at(cell.config, params, seq, rows, "f32")
        best = ref.max(axis=1)
        idx = np.arange(len(rows))
        out["f32"].append(best - ref[idx, gen])
        for m in modes:
            if m == "f32":
                continue
            low = cell.family.logits_at(cell.config, params, seq, rows, m)
            out[m].append(best - ref[idx, low.argmax(axis=1)])
    return {m: np.concatenate(v) if v else np.zeros((0,))
            for m, v in out.items()}


# ---- one run -------------------------------------------------------------------

@dataclasses.dataclass
class Session:
    """A cell set up in this process: the program's server, warm, with
    the benchmark's weights and probes in place."""
    cell: Cell
    jax: Any
    devices: List[Any]
    clock: CompileClock
    server: Any
    probe: Probe
    warm_admissions: int


def set_up(root: str, workload: str, seed: int, *,
           require_tpu: bool = True, use_cache: bool = True) -> Session:
    cell = load_cell(root, workload)
    import jax
    devs = jax.devices()
    dev = devs[0]
    if require_tpu and (dev.platform != "tpu" or len(devs) < cell.chips):
        raise NoChip(f"{workload} needs {cell.chips} TPU chip(s); JAX sees "
                     f"{len(devs)} {dev.platform} device(s)")
    _program()
    if use_cache:
        from repro.launch.compile_cache import use_compile_cache
        use_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    clock = CompileClock(jax)
    server = build_server(cell, seed)
    probe = Probe(server)
    n_warm = warm_up(server, cell, seed)
    return Session(cell, jax, devs, clock, server, probe, n_warm)


def reseed(sess: Session, seed: int) -> None:
    """New weights from `seed` in the warm server (same shapes, so nothing
    compiles again)."""
    sess.server.params = None
    gc.collect()
    sess.server.params = sess.cell.family.init_params(sess.cell.config, seed)


@dataclasses.dataclass
class Window:
    arrivals: List[traffic.Arrival]
    reqs: List[Any]
    w0: float
    w1: float
    queue: ArrivalQueue
    tracer: Optional[Tracer]
    drained: bool
    compiles: int
    compile_s: float

    load_start: float = 0.0

    @property
    def due(self) -> Dict[int, float]:
        """Each request's due time on the host's clock."""
        return {a.rid: self.load_start + a.due for a in self.arrivals}

    def timelines(self) -> List[stats.Timeline]:
        due = self.due
        return [stats.Timeline(due[r.rid], list(r.generated.times))
                for r in self.reqs]


def measure(sess: Session, seed: int, seconds: float, trace: bool = False,
            rate: Optional[float] = None,
            drain: float = DRAIN_SECONDS) -> Window:
    """Offer the cell's traffic from `seed` for the lead-in and a window
    of `seconds`, then wait for the window's requests."""
    cell, server = sess.cell, sess.server
    arrivals = traffic.generate(cell.mix, seed, seconds, server.cfg.vocab,
                                rate)
    factory = None
    if trace:
        factory = lambda w0, w1: Tracer(
            sess.jax, w1 - min(TRACE_SECONDS, w1 - w0), w1)
    c0, cs0 = sess.clock.count, sess.clock.seconds
    reqs, w0, w1, queue, tracer, drained = drive(
        server, sess.probe, arrivals, cell.mix["lead_in_s"], seconds,
        factory, drain)
    return Window(arrivals, reqs, w0, w1, queue, tracer, drained,
                  sess.clock.count - c0, sess.clock.seconds - cs0,
                  w0 - cell.mix["lead_in_s"])


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, *, t_process: float, require_tpu: bool = True,
             use_cache: bool = True, log=print) -> Dict[str, Any]:
    """Run one cell once; returns the result line's object."""
    sess = set_up(root, workload, seed, require_tpu=require_tpu,
                  use_cache=use_cache)
    cell, dev = sess.cell, sess.devices[0]
    setup_s = time.perf_counter() - t_process
    log(f"setup: setup_s={setup_s!r} compile_s={sess.clock.seconds!r} "
        f"compiles={sess.clock.count} "
        f"warm_up_admissions={sess.warm_admissions}")
    # the window closes the run: requests still decoding are left there
    # (only finished ones are checked, and a request still waiting counts
    # in the tail with its wait so far)
    win = measure(sess, seed, seconds, trace, drain=0.0)
    mem = dev.memory_stats() or {}
    server, probe = sess.server, sess.probe
    done = finished(server, win.reqs)
    counters = {k: getattr(server, k) for k in (
        "pages_resident_peak", "page_size", "batch", "max_seq", "seg_len",
        "tokens_emitted", "segments_dispatched", "steps", "decode_syncs",
        "prefill_forwards")}
    params = server.params
    # the program's state goes before the reference runs
    server.cache = server.state = server.params = None
    sess.server = probe.server = server = None
    gc.collect()
    tr = win.tracer.read() if win.tracer is not None else None

    due = win.due
    timelines = win.timelines()
    w0, w1 = win.w0, win.w1
    in_window = [r for r in win.reqs if w0 <= due[r.rid] < w1]
    failed = 0          # the program refuses no request and raises none
    lag = np.asarray(win.queue.lag) if win.queue.lag else np.zeros(1)
    log(f"window: seconds={seconds!r} lead_in_s={cell.mix['lead_in_s']!r} "
        f"requests_offered={len(win.arrivals)} "
        f"due_in_window={len(in_window)} finished={len(done)} "
        f"compiles_in_window={win.compiles} "
        f"compile_s_in_window={win.compile_s!r}")
    log(f"generator: release_lag_ms_p50={1e3 * float(np.median(lag))!r} "
        f"p99={1e3 * float(np.percentile(lag, 99))!r} "
        f"max={1e3 * float(lag.max())!r}")

    t_check = time.perf_counter()
    sample = check_sample(win.reqs, done, seed)
    gaps = served_gaps(cell, params, sample)["f32"]
    limit = float(cell.config["check"]["widest_gap_limit"])
    widest = float(gaps.max()) if len(gaps) else None
    correct = bool(widest is not None and widest <= limit)
    log(f"check: requests={len(sample)} served_tokens={len(gaps)} "
        f"longest={max((len(r.generated) for r in sample), default=0)} "
        f"reference_s={time.perf_counter() - t_check!r}")

    run = Run(cell, w0, w1, probe.prefills, probe.segments, counters, tr,
              dev.device_kind)
    metrics: Dict[str, Dict[str, Any]] = {}
    if trace:
        for m in cell.per_layer:
            v = metric_reader(root, m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        e2e = stats.end_to_end(timelines, w0, w1)
        e2e["setup_s"] = setup_s
        log("end_to_end: " + " ".join(f"{k}={v!r}" for k, v in e2e.items()))
        for m in cell.end_to_end:
            if e2e.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": float(e2e[m["name"]]),
                                      "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(sess.devices),
              "memory_peak_bytes": mem.get("peak_bytes_in_use")}
    out: Dict[str, Any] = {
        "correct": correct, "attempted": len(in_window), "failed": failed,
        "metrics": metrics, "device": device}
    if tr is not None:
        device["busy_s"] = trace_lib.busy_ns(tr.ops, tr.lo, tr.hi) / 1e9
        device["window_s"] = tr.window_s
        out["breakdown"] = {"device_ops": trace_lib.top_ops(tr),
                            "idle_gaps": idle_by_host_span(tr, probe.spans)}
    out["compared"] = {"widest_gap": {"value": widest, "limit": limit}}
    return out


def idle_by_host_span(tr: trace_lib.Trace, spans) -> List[List]:
    """Device idle time in the traced slice, by what the host was doing:
    each idle interval goes to the innermost host span around its
    midpoint ("serve_loop" when none is).  At most ten, longest first."""
    busy = trace_lib.union([(e.start, e.end) for e in tr.ops])
    edges = [tr.lo] + [x for ab in busy for x in ab] + [tr.hi]
    ns = [(n, tr.to_ns(a), tr.to_ns(b)) for n, a, b in spans]
    tot: Dict[str, float] = {}
    for a, b in zip(edges[::2], edges[1::2]):
        a, b = max(a, tr.lo), min(b, tr.hi)
        if b <= a:
            continue
        mid = (a + b) / 2
        inner = [(e - s, n) for n, s, e in ns if s <= mid <= e]
        name = min(inner)[1] if inner else "serve_loop"
        tot[name] = tot.get(name, 0.0) + (b - a) / 1e9
    return [[n, v] for n, v in sorted(tot.items(), key=lambda x: -x[1])[:10]]
