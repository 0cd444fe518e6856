"""The serving loop's own profiler spans and request stamps.

`BatchedServer` opens a `jax.profiler.TraceAnnotation` named `serve.<part>`
inside each method of the host loop, so a profile shows what the host
was doing at every moment of the device timeline.  These tests profile
smoke servers on the CPU, read the `.xplane.pb` back, and check that the
spans of a loop iteration are there, nest as the loop calls them, and
carry their arguments; and that every finished request carries ordered
`arrival` / `admitted_at` / `first_token_at` stamps.
"""
import glob
import os
import re

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_config
from repro.kernels import flash_attention as fa
from repro.launch.serve import (BatchedServer, Request, SamplingParams,
                                _flash_live_share)

# the host work of one streamed loop iteration that admits a request
LOOP = ("serve.fill_slots", "serve.admit", "serve.prefill_dispatch",
        "serve.first_token", "serve.seed_slot", "serve.pump_prefill",
        "serve.dispatch_rows", "serve.segment_dispatch", "serve.consume",
        "serve.consume.fetch", "serve.consume.deliver",
        "serve.assert_ledger")


def _profile(server, reqs, path):
    """Serve `reqs` to the end under the profiler; returns the `serve.*`
    host events as (name, start_ns, end_ns, args, thread)."""
    for r in reqs:
        server.submit(r)
    jax.profiler.start_trace(str(path))
    try:
        server.run_until_drained(max_steps=100_000)
    finally:
        jax.profiler.stop_trace()
    pb, = glob.glob(os.path.join(str(path), "plugins", "profile", "*",
                                 "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(pb).planes:
        if not plane.name.startswith("/host"):
            continue
        for ln in plane.lines:
            out += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                     dict(e.stats), ln.name)
                    for e in ln.events if e.name.startswith("serve.")]
    return out


def _named(spans, name):
    return [s for s in spans if s[0] == name]


def _inside(inner, outers):
    """Every span of `inner` lies within a span of `outers` on its
    thread."""
    return all(any(o[4] == i[4] and o[1] <= i[1] and i[2] <= o[2]
                   for o in outers) for i in inner)


def _prompts(vocab, n, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, int(rng.integers(4, 10))).astype(np.int32)
            for _ in range(n)]


def _stamped_in_order(server, n):
    done = server.completed
    assert len(done) == n
    for r in done:
        assert r.arrival <= r.admitted_at <= r.first_token_at, r.rid


@pytest.mark.parametrize("arch", ["starcoder2_3b", "mamba2_370m"])
def test_loop_spans_nest_and_carry_args(arch, tmp_path):
    server = BatchedServer(arch, smoke=True, batch_slots=2, max_seq=64,
                           seg_len=4, stream=True)
    eos = server.cfg.eos_token
    p0, p1 = _prompts(server.cfg.vocab, 2)
    reqs = [Request(0, p0, 6, sampling=SamplingParams(
                temperature=0.8, top_p=0.9, seed=7, stop_tokens=(eos,))),
            Request(1, p1, 6)]
    spans = _profile(server, reqs, tmp_path)

    for name in LOOP:
        assert _named(spans, name), name
    admits = _named(spans, "serve.admit")
    assert _inside(_named(spans, "serve.first_token"), admits)
    assert _inside(_named(spans, "serve.seed_slot"), admits)
    assert _inside(_named(spans, "serve.prefill_dispatch"), admits)
    assert _inside(admits, _named(spans, "serve.fill_slots"))
    consumes = _named(spans, "serve.consume")
    assert _inside(_named(spans, "serve.consume.fetch"), consumes)
    assert _inside(_named(spans, "serve.consume.deliver"), consumes)
    assert {(a[3]["rid"], a[3]["prompt_len"]) for a in admits} \
        == {(0, len(p0)), (1, len(p1))}
    assert all(a[3]["bucket"] >= a[3]["prompt_len"] for a in admits)
    for a in admits:
        share = _flash_live_share(server.cfg, a[3]["prompt_len"],
                                  a[3]["bucket"])
        assert a[3].get("flash_live_share") == share, (arch, a[3])
    seg = sorted(_named(spans, "serve.segment_dispatch"),
                 key=lambda s: s[1])
    assert all(1 <= s[3]["live_rows"] <= 2 for s in seg)
    # both rows decode in the first segment, and the sampled one keeps it
    # off the greedy fast path
    assert (seg[0][3]["live_rows"], seg[0][3]["plain"]) == (2, 0)
    _stamped_in_order(server, 2)


def test_flash_live_share_counts_the_kernel_grid():
    """`serve.admit`'s `flash_live_share`: the kernel's live (q block, kv
    block) pairs over its grid, at the published widths' tiles; absent
    for a model without attention."""
    cfg = get_config("starcoder2_3b")
    tiles = fa.flash_tiles(2048, cfg.n_heads // cfg.n_kv_heads)
    live, total = fa.flash_live_blocks(1500, 2048, *tiles)
    assert _flash_live_share(cfg, 1500, 2048) == live / total
    assert 0 < live < total
    assert _flash_live_share(cfg, 2048, 2048) < 1      # causal: ~half
    assert _flash_live_share(cfg, 8, 8) == 1
    assert _flash_live_share(get_config("mamba2_370m"), 1500, 2048) is None


def test_offload_and_prefix_spans(tmp_path):
    """Oversubscribed per-token serving with the host tier and the prefix
    cache: evictions, restores and prefix lookups have spans of their
    own, inside the slot fill that runs them."""
    server = BatchedServer("starcoder2_3b", smoke=True, batch_slots=2,
                           max_seq=64, seg_len=4, stream=False,
                           host_offload=True, prefix_cache=True,
                           evict_after=1)
    prompts = _prompts(server.cfg.vocab, 3, seed=1)
    prompts += [prompts[0].copy()]             # a full prefix hit
    reqs = [Request(i, p, 8) for i, p in enumerate(prompts)]
    spans = _profile(server, reqs, tmp_path)

    assert server.evictions > 0 and server.restores > 0
    assert server.prefix_hits_full == 1
    fills = _named(spans, "serve.fill_slots")
    evicts, restores = (_named(spans, "serve.evict"),
                        _named(spans, "serve.restore"))
    assert len(evicts) == server.evictions and len(restores) == \
        server.restores + server.restored_dead
    assert _inside(evicts, fills) and _inside(restores, fills)
    lookups = _named(spans, "serve.prefix_lookup")
    assert len(lookups) == len(prompts)
    assert _inside(lookups, _named(spans, "serve.prefill_dispatch"))
    # per-token mode dispatches one segment per step, each in its span
    assert len(_named(spans, "serve.segment_dispatch")) == server.steps
    _stamped_in_order(server, len(prompts))


def test_chunked_admission_is_stamped(tmp_path):
    """A prompt admitted in chunks leaves the queue when its slot is
    reserved; its first token comes from the last chunk, inside
    `serve.pump_prefill`."""
    server = BatchedServer("starcoder2_3b", smoke=True, batch_slots=2,
                           max_seq=64, seg_len=4, stream=True,
                           prefill_chunk=8)
    rng = np.random.default_rng(2)
    long = rng.integers(1, server.cfg.vocab, 30).astype(np.int32)
    short, = _prompts(server.cfg.vocab, 1, seed=3)
    spans = _profile(server, [Request(0, short, 6), Request(1, long, 6)],
                     tmp_path)

    assert server.prefill_chunks == 4
    pumps = _named(spans, "serve.pump_prefill")
    firsts = _named(spans, "serve.first_token")
    assert sum(_inside([f], pumps) for f in firsts) == 1
    assert [a[3]["rid"] for a in _named(spans, "serve.admit")] == [0]
    _stamped_in_order(server, 2)


def test_device_steps_carry_named_scopes():
    """The jitted steps name their parts, so a profile's ops carry the
    scope path; the jitted functions keep their names."""
    server = BatchedServer("starcoder2_3b", smoke=True, batch_slots=2,
                           max_seq=64, seg_len=4, stream=True, spec=True,
                           spec_k=2, draft_arch="self:1", host_offload=True)

    def text(fn, *args):
        return fn.lower(*args).as_text(debug_info=True)

    state, cache = server.state, server.cache
    seg = text(server.segment_fn, server.params, cache, state)
    plain = text(server.segment_plain_fn, server.params, cache, state)
    spec = text(server.spec_segment_fn, server.params, server.draft_params,
                cache, server.draft_cache, state)
    prefill = text(server.prefill_fn, server.params, cache,
                   np.zeros((8,), np.int32), 0, 5)
    extract = text(server.extract_fn, cache, 0, None)
    pages = server.extract_fn(cache, 0, None)
    insert = text(server.insert_fn, cache, pages, 0)
    # a location names its scope path, or its last part after a nested
    # location
    has = lambda txt, scope: re.search(r'["/]' + scope + "/", txt)
    assert '"jit(segment)/decode_segment/' in seg
    assert has(seg, "sampling_epilogue") and not has(plain,
                                                     "sampling_epilogue")
    assert '"jit(segment)/decode_segment/' in spec
    assert has(spec, "spec_draft") and has(spec, "spec_verify")
    assert '"jit(prefill)/prefill/' in prefill
    assert has(extract, "page_extract") and has(insert, "page_insert")
