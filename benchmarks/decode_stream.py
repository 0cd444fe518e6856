"""Serving hot-loop microbench: per-token (bulk-synchronous host loop)
vs streamed (producer-initiated jitted decode segments with overlapped
device_get).  Reports wall time per emitted token, host syncs per token,
and the per-step kernel-launch accounting of the fused decode path —
the three numbers `benchmarks/run.py --json` tracks across PRs.

Two architecture rows: an attention arch (starcoder2) exercising the
fused flash-decode path, and an SSM arch (mamba2) exercising the
recurrent-state prefill — both admitted through the SAME real
prefill-into-cache path (no last-token-seeding fallback exists anymore;
`BatchedServer` asserts every config supports prefill).

Each tracked arch additionally runs a `sampling=top_p` streamed row:
per-slot stochastic sampling through the device-side PRNG chains
(DESIGN.md §6).  Sampling is plain XLA fused into the logits epilogue —
no extra kernel launches — and budget-terminated rows keep dispatch-time
slot accounting, so syncs/token must equal the greedy row EXACTLY (the
row asserts it).

Two `stream.spec` rows per arch track speculative draft-and-verify
segments (DESIGN.md §7): `stream.spec` runs a FULL-depth self-draft
(draft ≡ target — the accept-rate-1 machinery check) and asserts both
that the greedy token streams are bitwise-identical to the plain rows
and that tokens-per-host-sync strictly exceeds the greedy `stream` row
whenever the measured accept rate is >= 0.5; `stream.spec.draft1` runs
the config's truncated self-draft and reports its honest accept rate
(its tokens/sync assert is conditional on the same >= 0.5 bar, which a
randomly initialized 1-of-2-block draft does not usually clear — the
row exists to track the trajectory, not to flatter it).

A `stream.restore` row per arch tracks host-tier cache offload
(DESIGN.md §8): an oversubscribed workload (2x the slots, with repeated
prompts) served under demand-driven eviction/restore + prefix reuse.
The row asserts the offloaded streams are bitwise the non-offload
baseline's AND that decode syncs/token is unchanged — evictions stream
host-ward asynchronously and restores dispatch behind the in-flight
segment, so the token pipeline never stalls on the host tier (the
paper's overlap claim at the PCIe/CXL boundary).  It reports the
restore/evict dispatch latencies, the prefix-cache hit rate and the
prefill tokens skipped.

CPU wall times carry host-loop overheads only (no TPU); the syncs/token
and launch counts are platform-true.  Every derived field is documented
in benchmarks/README.md.
"""
from __future__ import annotations

import time
from typing import List, Optional

import numpy as np

from benchmarks.common import Row, print_rows

ARCHES = ("starcoder2_3b", "mamba2_370m")
SLOTS = 2
MAX_NEW = 16
N_REQ = 4
SEG_LEN = 8
TOP_P = 0.9
TEMPERATURE = 0.8
SPEC_K = 3
# speculative rows run a longer budget: a request must SPAN segments for
# the accept-rate multiple to dominate the one-trailing-segment
# retirement lag of boundary accounting (DESIGN.md §7's tokens/sync
# model); the greedy baseline they are asserted against is re-measured
# at this same budget — never compared across budgets.
SPEC_MAX_NEW = 32
# the restore row oversubscribes 2x: twice the slots' worth of requests,
# each spanning multiple segments so eviction happens mid-decode
RESTORE_N_REQ = 2 * SLOTS


def _restore_workload(cfg):
    """2x-oversubscribed greedy workload with repeated prompts: requests
    SLOTS.. repeat the first SLOTS prompts, so the offloaded server's
    prefix cache takes one full hit per repeat."""
    from repro.launch.serve import Request
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab,
                            int(rng.integers(4, 7))).astype(np.int32)
               for _ in range(SLOTS)]
    return [Request(i, prompts[i % SLOTS].copy(), MAX_NEW)
            for i in range(RESTORE_N_REQ)]


def _run_restore_server(arch: str, offload: bool):
    from repro.launch.serve import BatchedServer
    server = BatchedServer(arch, smoke=True, batch_slots=SLOTS,
                           max_seq=64, protocol="bs", stream=True,
                           seg_len=SEG_LEN, host_offload=offload,
                           prefix_cache=offload, evict_after=1)
    for r in _restore_workload(server.cfg):
        server.submit(r)
    t0 = time.perf_counter()
    server.run_until_drained()
    dt = time.perf_counter() - t0
    return server, dt


def _run_server(arch: str, stream: bool, sampled: bool = False,
                spec: bool = False, draft: Optional[str] = None,
                max_new: int = MAX_NEW):
    from repro.launch.serve import BatchedServer, Request, SamplingParams
    # max_seq stays at the historical 64 so the pre-existing rows keep
    # their exact workload (the BENCH series is only comparable across
    # PRs if the row names keep meaning the same run); the spec rows'
    # worst case — prompt 6 + SPEC_MAX_NEW + SPEC_K = 41 — fits too.
    server = BatchedServer(arch, smoke=True, batch_slots=SLOTS,
                           max_seq=64, protocol="bs", stream=stream,
                           seg_len=SEG_LEN, spec=spec, spec_k=SPEC_K,
                           draft_arch=draft)
    rng = np.random.default_rng(0)
    for i in range(N_REQ):
        plen = int(rng.integers(3, 7))
        sampling: Optional[SamplingParams] = SamplingParams(
            temperature=TEMPERATURE, top_p=TOP_P, seed=i) if sampled \
            else None
        server.submit(Request(i, rng.integers(
            1, server.cfg.vocab, plen).astype(np.int32), max_new,
            sampling=sampling))
    t0 = time.perf_counter()
    server.run_until_drained()
    dt = time.perf_counter() - t0
    return server, dt


PAGE_SIZE = 8            # the paged row's KV page width (DESIGN.md §9)
PREFILL_CHUNK = 8        # the chunked-admission row's chunk width
LONG_PROMPT = 48         # admitted chunk-by-chunk into the busy batch


def _run_paged_server(arch: str, shuffle: bool):
    """The greedy streamed workload on a `PAGE_SIZE`-paged cache; with
    `shuffle`, every row's page table is permuted BEFORE any prefill —
    chunk-as-page equivalence says the streams must not move a bit."""
    import jax.numpy as jnp
    from repro.launch.serve import BatchedServer, Request
    server = BatchedServer(arch, smoke=True, batch_slots=SLOTS,
                           max_seq=64, protocol="bs", stream=True,
                           seg_len=SEG_LEN, page_size=PAGE_SIZE)
    if shuffle and "page_table" in server.cache:
        pt = np.asarray(server.cache["page_table"])
        prng = np.random.default_rng(13)
        server.cache["page_table"] = jnp.asarray(
            np.stack([prng.permutation(pt.shape[1])
                      for _ in range(pt.shape[0])]), np.int32)
    rng = np.random.default_rng(0)
    for i in range(N_REQ):
        plen = int(rng.integers(3, 7))
        server.submit(Request(i, rng.integers(
            1, server.cfg.vocab, plen).astype(np.int32), MAX_NEW))
    t0 = time.perf_counter()
    server.run_until_drained()
    dt = time.perf_counter() - t0
    return server, dt


def _run_chunked_server(arch: str, with_long: bool):
    """Short greedy requests, plus (with_long) one LONG_PROMPT request
    admitted through `prefill_chunk`-token chunks interleaved with the
    decode segments.  Records decode_syncs at each request's retirement
    so the row can assert the in-flight streams never stalled."""
    from repro.launch.serve import BatchedServer, Request

    class Tracking(BatchedServer):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.retire_syncs = {}

        def _consume_segment(self, *a, **kw):
            before = {r.rid for r in self.completed}
            super()._consume_segment(*a, **kw)
            for r in self.completed:
                if r.rid not in before and r.rid not in self.retire_syncs:
                    self.retire_syncs[r.rid] = self.decode_syncs

    server = Tracking(arch, smoke=True, batch_slots=SLOTS + 1,
                      max_seq=64, protocol="bs", stream=True,
                      seg_len=SEG_LEN, prefill_chunk=PREFILL_CHUNK)
    rng = np.random.default_rng(0)
    for i in range(SLOTS):
        plen = int(rng.integers(3, 7))
        server.submit(Request(i, rng.integers(
            1, server.cfg.vocab, plen).astype(np.int32), MAX_NEW))
    if with_long:
        server.submit(Request(SLOTS, rng.integers(
            1, server.cfg.vocab, LONG_PROMPT).astype(np.int32), MAX_NEW))
    t0 = time.perf_counter()
    server.run_until_drained()
    dt = time.perf_counter() - t0
    return server, dt


def _run_quant_server(arch: str, quant_kv: Optional[str]):
    """The greedy streamed paged workload with (or without) the KV cache
    held as int8 pages + per-(head, page) scales (DESIGN.md §10)."""
    from repro.launch import steps as steps_lib
    from repro.launch.serve import BatchedServer, Request
    quant = (steps_lib.QuantConfig(kv=quant_kv) if quant_kv else None)
    server = BatchedServer(arch, smoke=True, batch_slots=SLOTS,
                           max_seq=64, protocol="bs", stream=True,
                           seg_len=SEG_LEN, page_size=PAGE_SIZE,
                           quant=quant)
    rng = np.random.default_rng(0)
    for i in range(N_REQ):
        plen = int(rng.integers(3, 7))
        server.submit(Request(i, rng.integers(
            1, server.cfg.vocab, plen).astype(np.int32), MAX_NEW))
    t0 = time.perf_counter()
    server.run_until_drained()
    dt = time.perf_counter() - t0
    return server, dt


def _kv_cache_bytes(cache) -> int:
    """Bytes held by the self-attention KV pools, scale leaves included —
    the far-tier traffic the paper's byte-economy argument is about."""
    from repro.models import transformer as T
    return sum(int(v.nbytes) for k, v in cache.items()
               if T._is_self_kv(k) or T._is_kv_scale(k))


def run() -> List[Row]:
    rows: List[Row] = []
    for arch in ARCHES:
        outs = {}
        # row names for the attention arch keep their PR-1 form so the
        # BENCH_decode.json series stays continuous; the SSM rows carry
        # an arch suffix.
        suffix = "" if arch == ARCHES[0] else f".{arch}"
        greedy_syncs = {}
        for stream in (False, True):
            server, dt = _run_server(arch, stream)
            toks = sum(len(r.generated) for r in server.completed)
            outs[stream] = {r.rid: tuple(r.generated)
                            for r in server.completed}
            name = "stream" if stream else "per_token"
            syncs_per_tok = server.decode_syncs / max(1, toks)
            greedy_syncs[stream] = syncs_per_tok
            # launch accounting is per layer kind: attention layers decode
            # through ONE fused one-shot flash-decode launch each; mamba
            # layers' ssd_decode_step is plain XLA (no kernel launch).
            kern = ("kernel_launches_per_step=1" if server.cfg.has_attention
                    else "decode_kernel=xla_ssd_step")
            rows.append((
                f"decode_stream.{name}{suffix}", dt / max(1, toks) * 1e6,
                f"tokens={toks};decode_syncs={server.decode_syncs};"
                f"syncs_per_token={syncs_per_tok:.4f};{kern}"))
        assert outs[True] == outs[False], f"streamed tokens diverged: {arch}"
        rows.append((f"decode_stream.equivalence{suffix}", 0.0,
                     f"identical_tokens={int(outs[True] == outs[False])}"))
        # streamed top-p sampling: same budgets, same slot accounting —
        # the sync count per token must not move vs greedy streaming
        server, dt = _run_server(arch, True, sampled=True)
        toks = sum(len(r.generated) for r in server.completed)
        syncs_per_tok = server.decode_syncs / max(1, toks)
        assert syncs_per_tok == greedy_syncs[True], \
            (arch, syncs_per_tok, greedy_syncs[True])
        rows.append((
            f"decode_stream.stream.top_p{suffix}", dt / max(1, toks) * 1e6,
            f"tokens={toks};decode_syncs={server.decode_syncs};"
            f"syncs_per_token={syncs_per_tok:.4f};sampling=top_p;"
            f"top_p={TOP_P};temperature={TEMPERATURE};"
            f"syncs_match_greedy=1;extra_kernel_launches=0"))
        # speculative draft-and-verify streaming (DESIGN.md §7): greedy
        # workload, so the token streams must be bitwise the plain rows'
        # for ANY draft; tokens/sync must beat the greedy stream row
        # whenever accept_rate >= 0.5 (the paper-metric acceptance bar).
        # greedy streamed baseline at the speculative rows' budget — the
        # bitwise-reference streams AND the tokens/sync bar in one run
        base, _ = _run_server(arch, True, max_new=SPEC_MAX_NEW)
        base_streams = {r.rid: tuple(r.generated) for r in base.completed}
        greedy_tps = (sum(len(r.generated) for r in base.completed)
                      / max(1, base.decode_syncs))
        from repro.configs import get_smoke_config
        n_blocks = get_smoke_config(arch).n_blocks
        for row_name, draft in ((f"decode_stream.stream.spec{suffix}",
                                 f"self:{n_blocks}"),
                                (f"decode_stream.stream.spec.draft1{suffix}",
                                 "self:1")):
            server, dt = _run_server(arch, True, spec=True, draft=draft,
                                     max_new=SPEC_MAX_NEW)
            toks = sum(len(r.generated) for r in server.completed)
            got = {r.rid: tuple(r.generated) for r in server.completed}
            assert got == base_streams, f"spec tokens diverged: {arch}"
            syncs_per_tok = server.decode_syncs / max(1, toks)
            tokens_per_sync = toks / max(1, server.decode_syncs)
            rate = server.draft_accepted / max(1, server.draft_proposed)
            if rate >= 0.5:
                assert tokens_per_sync > greedy_tps, \
                    (arch, draft, tokens_per_sync, greedy_tps)
            rows.append((
                row_name, dt / max(1, toks) * 1e6,
                f"tokens={toks};decode_syncs={server.decode_syncs};"
                f"syncs_per_token={syncs_per_tok:.4f};"
                f"tokens_per_sync={tokens_per_sync:.4f};"
                f"greedy_tokens_per_sync={greedy_tps:.4f};"
                f"accept_rate={rate:.4f};spec_k={SPEC_K};"
                f"rounds_per_segment={SEG_LEN};max_new={SPEC_MAX_NEW};"
                f"draft={draft};spec_tokens_bitwise_greedy=1;"
                f"extra_kernel_launches=0"))
        # host-tier offload (DESIGN.md §8): 2x-oversubscribed workload
        # under demand eviction + prefix reuse vs the same workload on a
        # never-evicting server — bitwise streams, unchanged decode
        # syncs (restores hide behind in-flight segments), and a
        # measured prefix-cache hit skipping prefill.
        base, _ = _run_restore_server(arch, offload=False)
        base_streams = {r.rid: tuple(r.generated) for r in base.completed}
        server, dt = _run_restore_server(arch, offload=True)
        got = {r.rid: tuple(r.generated) for r in server.completed}
        assert got == base_streams, f"offloaded tokens diverged: {arch}"
        assert server.decode_syncs == base.decode_syncs, \
            (arch, server.decode_syncs, base.decode_syncs)
        assert server.evictions > 0 and server.restores > 0, arch
        assert server.prefix_hits_full > 0, arch
        toks = sum(len(r.generated) for r in server.completed)
        hits = server.prefix_hits_full + server.prefix_hits_partial
        admissions = hits + server.prefix_misses
        rows.append((
            f"decode_stream.stream.restore{suffix}",
            dt / max(1, toks) * 1e6,
            f"tokens={toks};requests={RESTORE_N_REQ};slots={SLOTS};"
            f"decode_syncs={server.decode_syncs};"
            f"baseline_decode_syncs={base.decode_syncs};"
            f"syncs_match_baseline=1;restore_overlapped=1;"
            f"tokens_bitwise_baseline=1;"
            f"evictions={server.evictions};restores={server.restores};"
            f"restore_dispatch_us="
            f"{server.restore_dispatch_time / max(1, server.restores) * 1e6:.1f};"
            f"evict_dispatch_us="
            f"{server.evict_dispatch_time / max(1, server.evictions) * 1e6:.1f};"
            f"host_tier_mb="
            f"{server.host_tier.bytes_evicted / 2**20:.2f};"
            f"prefix_hit_rate={hits / max(1, admissions):.4f};"
            f"prefill_tokens_skipped={server.prefill_tokens_skipped};"
            f"prefill_forwards={server.prefill_forwards};"
            f"baseline_prefill_forwards={base.prefill_forwards}"))
        # block-sparse KV paging (DESIGN.md §9): the greedy streamed
        # workload on a PAGE_SIZE-paged cache, identity vs shuffled
        # per-row page tables — chunk-as-page equivalence makes the
        # physical placement bitwise-invisible, at unchanged sync cost.
        base, _ = _run_paged_server(arch, shuffle=False)
        base_streams = {r.rid: tuple(r.generated) for r in base.completed}
        server, dt = _run_paged_server(arch, shuffle=True)
        got = {r.rid: tuple(r.generated) for r in server.completed}
        assert got == base_streams, f"paged tokens diverged: {arch}"
        assert server.decode_syncs == base.decode_syncs, arch
        assert server.pages_allocated == server.pages_freed \
            and server.pages_resident == 0, arch
        toks = sum(len(r.generated) for r in server.completed)
        rows.append((
            f"decode_stream.stream.paged{suffix}",
            dt / max(1, toks) * 1e6,
            f"tokens={toks};page_size={PAGE_SIZE};"
            f"paged={int(server.cfg.has_attention)};"
            f"decode_syncs={server.decode_syncs};"
            f"syncs_per_token={server.decode_syncs / max(1, toks):.4f};"
            f"tokens_bitwise_identity_table=1;"
            f"pages_resident={server.pages_resident};"
            f"pages_resident_peak={server.pages_resident_peak};"
            f"pages_allocated={server.pages_allocated};"
            f"pages_freed={server.pages_freed}"))
        # int8 KV quantized serving (DESIGN.md §10): the greedy streamed
        # paged workload with the KV cache as int8 pages + per-(head,
        # page) scales consumed inside the fused decode — the cache's
        # cache-bytes-per-token drop ~4x on attention archs at an
        # UNCHANGED syncs/token (quantization lives inside the jitted
        # segment; the host loop never feels it).  SSM archs carry no
        # KV pool, so their ratio is reported as 1 and not asserted.
        base, _ = _run_quant_server(arch, None)
        base_streams = {r.rid: tuple(r.generated) for r in base.completed}
        server, dt = _run_quant_server(arch, "int8")
        got = {r.rid: tuple(r.generated) for r in server.completed}
        toks = sum(len(r.generated) for r in server.completed)
        assert toks == sum(len(r.generated) for r in base.completed), arch
        assert server.decode_syncs == base.decode_syncs, \
            (arch, server.decode_syncs, base.decode_syncs)
        assert server.pages_allocated == server.pages_freed \
            and server.pages_resident == 0, arch
        fp_bytes = _kv_cache_bytes(base.cache)
        q_bytes = _kv_cache_bytes(server.cache)
        ratio = fp_bytes / q_bytes if q_bytes else 1.0
        if server.cfg.has_attention:
            assert ratio >= 1.9, (arch, fp_bytes, q_bytes, ratio)
        rows_match = sum(int(got[r] == base_streams[r]) for r in got)
        rows.append((
            f"decode_stream.stream.quant{suffix}",
            dt / max(1, toks) * 1e6,
            f"tokens={toks};quant_kv=int8;page_size={PAGE_SIZE};"
            f"decode_syncs={server.decode_syncs};"
            f"syncs_per_token={server.decode_syncs / max(1, toks):.4f};"
            f"syncs_match_fp=1;"
            f"kv_cache_bytes_fp={fp_bytes};"
            f"kv_cache_bytes_int8={q_bytes};"
            f"kv_bytes_reduction={ratio:.2f};"
            f"rows_matching_fp={rows_match}/{len(got)}"))
        # chunked admission prefill (DESIGN.md §9): a LONG_PROMPT request
        # admitted in PREFILL_CHUNK-token chunks between decode segments
        # of a busy batch.  The in-flight stall assertion: every short
        # row retires at the SAME decode_syncs count as in the
        # no-admission run, with bitwise-identical tokens.
        base, _ = _run_chunked_server(arch, with_long=False)
        base_streams = {r.rid: tuple(r.generated) for r in base.completed}
        server, dt = _run_chunked_server(arch, with_long=True)
        got = {r.rid: tuple(r.generated) for r in server.completed}
        for rid, want in base_streams.items():
            assert got[rid] == want, f"in-flight stream moved: {arch}/{rid}"
        assert {r: server.retire_syncs[r] for r in base.retire_syncs} \
            == base.retire_syncs, f"in-flight stream stalled: {arch}"
        n_chunks = -(-LONG_PROMPT // PREFILL_CHUNK)
        assert server.prefill_chunks == n_chunks, arch
        assert server.pages_allocated == server.pages_freed \
            and server.pages_resident == 0, arch
        toks = sum(len(r.generated) for r in server.completed)
        chunk_us = (server.prefill_chunk_time
                    / max(1, server.prefill_chunks) * 1e6)
        rows.append((
            f"decode_stream.stream.chunked_prefill{suffix}",
            dt / max(1, toks) * 1e6,
            f"tokens={toks};long_prompt={LONG_PROMPT};"
            f"prefill_chunk={PREFILL_CHUNK};"
            f"prefill_chunks={server.prefill_chunks};"
            f"prefill_chunk_us={chunk_us:.1f};"
            f"decode_syncs={server.decode_syncs};"
            f"baseline_decode_syncs={base.decode_syncs};"
            f"inflight_syncs_match_baseline=1;"
            f"inflight_tokens_bitwise_baseline=1;"
            f"pages_resident_peak={server.pages_resident_peak}"))
    rows.append(_sharded_row())
    return rows


def _sharded_row() -> Row:
    """`stream.sharded` (DESIGN.md §11): the greedy streamed workload on
    a 2-device mesh (1 data x 2 model head-group shards) over the first
    two of `jax.devices()` vs the single-device baseline, in THIS
    process (one process per chip: a child could not open a device its
    parent holds).  On a CPU host export
    XLA_FLAGS=--xla_force_host_platform_device_count=2 (or more) before
    launch.  Asserts-and-reports the serving TP contract: tokens BITWISE
    the single-device stream's, syncs/token unchanged, and the
    deterministic AXLE wire accounting (`wire_bytes_per_shard`, guarded
    exact-match by tools/check_bench_regression.py)."""
    import jax
    from repro.launch.mesh import make_debug_mesh
    from repro.launch.serve import BatchedServer, Request

    n_dev = jax.device_count()
    if n_dev < 2:
        raise RuntimeError(
            f"stream.sharded needs 2 devices, {n_dev} visible; on a CPU "
            "host set XLA_FLAGS=--xla_force_host_platform_device_count=2 "
            "before launch")

    def run_one(mesh):
        s = BatchedServer("starcoder2_3b", smoke=True, batch_slots=2,
                          max_seq=64, protocol="bs", stream=True,
                          seg_len=8, mesh=mesh)
        rng = np.random.default_rng(0)
        for i in range(4):
            plen = int(rng.integers(3, 7))
            s.submit(Request(i, rng.integers(1, s.cfg.vocab, plen)
                             .astype(np.int32), 16))
        t0 = time.perf_counter()
        s.run_until_drained()
        return s, time.perf_counter() - t0

    base, _ = run_one(None)
    mesh, dt = run_one(make_debug_mesh(1, 2))
    bt = {r.rid: list(map(int, r.generated)) for r in base.completed}
    mt = {r.rid: list(map(int, r.generated)) for r in mesh.completed}
    assert bt == mt, "sharded stream diverged from single-device"
    assert mesh.decode_syncs == base.decode_syncs, \
        (mesh.decode_syncs, base.decode_syncs)
    wire = int(mesh.wire_bytes_per_shard)
    assert int(base.wire_bytes_per_shard) == 0 and wire > 0, wire
    toks = sum(len(v) for v in mt.values())
    return (
        "decode_stream.stream.sharded", dt / max(1, toks) * 1e6,
        f"tokens={toks};mesh=1x2;"
        f"decode_syncs={mesh.decode_syncs};"
        f"syncs_per_token={mesh.decode_syncs / max(1, toks):.4f};"
        f"syncs_match_single_device=1;"
        f"tokens_bitwise_single_device=1;"
        f"wire_bytes_per_shard={wire};"
        f"wire_merges={mesh.wire.merges}")


if __name__ == "__main__":
    print_rows(run())
