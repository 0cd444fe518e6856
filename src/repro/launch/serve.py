"""Batched serving driver with offload-protocol selection and an
asynchronous token-streaming hot loop.

The paper's serving pattern (Table I, LLM row): attention over the
memory-resident KV cache is the producer-side task; the downstream MLP /
sampling is the consumer.  `--protocol {bs,axle,rp}` selects the
partial-attention merge schedule (repro.core.backstream):

  bs   — fused one-shot decode kernel (single-shard) / bulk-synchronous
         all-gather of partial statistics under a mesh (M²NDP flow)
  axle — producer-initiated ring streaming with compute/transfer overlap
  rp   — serialized per-chunk round trips (device-centric baseline)

Requests are continuously batched: a request queue fills free decode
slots, finished sequences retire and their slots are reused.  Every slot
keeps its OWN position clock (a (B,) vector threaded through RoPE, cache
validity and ring-slot writes) — the correctness requirement of
continuous batching that a scalar step counter cannot express.

Two host loops over the same jitted steps:

  per-token (`step`)      — one dispatch + one host sync per token; the
                            bulk-synchronous baseline.
  streamed  (`run_stream`)— producer-initiated: a jitted `seg_len`-token
                            lax.scan segment decodes on-device while the
                            host consumes the PREVIOUS segment's tokens
                            (double buffering via overlapped device_get),
                            so the host syncs once per segment instead of
                            once per token.  Next-segment inputs chain
                            device-side (last tokens / positions / PRNG
                            keys / alive masks never round-trip through
                            the host).

Decoding is per-slot stochastic sampling (DESIGN.md §6): each `Request`
carries a `SamplingParams` (temperature / top_k / top_p / min_p / seed /
stop tokens), realized device-side as a `steps.SlotState` — per-slot PRNG
chains split once per decode step inside the jitted segments, and
in-segment termination masks (stop token hit, token budget exhausted)
that freeze a finished row until the host retires it at a segment
boundary.  The default (no `sampling` on the request) is greedy argmax,
bitwise-identical to the historical loop.

Prompt admission runs a real prefill for EVERY registered architecture —
no degradation path.  Attention layers push the full prompt through the
flash_attention kernel and write per-layer K/V into the slot's cache
rows; mamba layers capture the SSD scan's final recurrent state and the
causal conv's trailing input window (transformer.prefill_into_cache);
encoder-decoder configs additionally run the encoder and write per-slot
cross-attention K/V (encdec.prefill_into_cache).  The old last-token
seeding — which dropped every other prompt token's KV and pinned all
rows to a scalar position clock — is gone.

Host-tier cache offload (`host_offload=True`, DESIGN.md §8) makes the
resident set larger than the slot count: when demand exceeds free slots,
cold slots' cache pages (every leaf kind — KV, conv tail, SSD state,
enc-dec cross-KV + enc_pos) and SlotState row are evicted to host RAM
through chunked async copies (`backstream.stream_offload_to_host`) and
restored on demand through async `device_put` chains that dispatch with
ZERO host syncs — a restore hides behind the in-flight decode segment
exactly as the paper hides back-streamed results behind CCM compute, so
decode syncs/token is unchanged vs a never-evicting server and the
restored stream is bitwise-identical to a never-evicted one (the PRNG
chain head, position clock and budget ride the snapshot).  Layered on
top, `prefix_cache=True` keeps a host-side hash-trie of served prompts:
an admission whose prompt extends a cached prefix restores those pages
instead of recomputing them — a full hit skips the prefill forward
entirely (first token sampled from the stored last-prefix logits), a
partial hit runs only the suffix through `resume_prefill_into_cache`.

Speculative decoding (`spec=True`, DESIGN.md §7) layers draft-and-verify
on top of the streamed segments: a cheap draft model (a truncated-layer
self-draft sliced from the target's own blocks, or any registered arch
sharing the vocabulary) proposes `spec_k` tokens per slot inside the
jitted segment, the target verifies all k+1 positions in ONE batched
multi-position forward, and `ops.verify_tokens` applies the standard
rejection-sampling correction — so each segment emits between `rounds`
and `rounds·(k+1)` tokens per slot at the SAME one-host-sync-per-segment
cost, growing tokens-per-host-sync by the accept rate.  Greedy streams
are bitwise-identical to non-speculative serving for any draft; sampled
streams are distribution-identical.

Quantized serving (`--quant-weights {q8_0,q4_k}` / `--quant-kv int8`,
DESIGN.md §10) composes with all of the above: weight stacks are
block-quantized once at construction (the fused matmul dequantizes in
VMEM), and an int8 KV cache carries per-(layer, row, head, page) scales
that ride the page table, the host-tier evict/restore snapshots (~2x
fewer KV bytes per request) and the prefix trie natively.
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import sharding as sh
from repro.configs import get_config, get_smoke_config
from repro.core import ring as ring_lib
from repro.core.backstream import (HostTier, OffloadConfig, OffloadProtocol,
                                   PrefixCache, stream_offload_to_device,
                                   stream_offload_to_host, use_offload)
from repro.kernels import ops
from repro.launch import steps as steps_lib
from repro.launch.compile_cache import use_compile_cache
from repro.models import transformer
from repro.models.registry import get_model

PROTOCOLS = {"bs": OffloadProtocol.BS, "axle": OffloadProtocol.AXLE,
             "rp": OffloadProtocol.RP}


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request decoding control state (the AXLE point: async device
    segments must carry per-request CONTROL, not just data).

    temperature — 0 (default) decodes greedily (bitwise-identical to the
                  historical argmax loop, no RNG consumed); > 0 samples
                  from the temperature-scaled distribution.
    top_k       — keep only the k highest-probability tokens (0 = off;
                  1 ≡ greedy).
    top_p       — nucleus sampling: keep the smallest top-probability set
                  with mass >= top_p (1.0 = off).
    min_p       — drop tokens below min_p × the max token probability
                  (0.0 = off).
    seed        — per-request PRNG seed.  Token k of a request is always
                  sampled with the k-th split of this seed's key chain:
                  reproducible across seg_len choices, slot assignments,
                  batch-mates, and per-token vs streamed loops.
    stop_tokens — token ids that terminate the request (EOS and friends;
                  at most steps.MAX_STOP_TOKENS of them).  The stop token
                  itself is delivered as the last generated token.
    max_new     — optional per-request budget override of Request.max_new.
    """
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    min_p: float = 0.0
    seed: int = 0
    stop_tokens: Tuple[int, ...] = ()
    max_new: Optional[int] = None


GREEDY = SamplingParams()


@dataclasses.dataclass
class Request:
    """One serving request.

    prompt    — (prompt_len,) int32 token ids; for encoder-decoder archs
                these are the DECODER prompt (task/language tokens).
    max_new   — token budget; the first token is produced by the prefill
                itself (sampled, like every later one, from the request's
                chain — greedy when `sampling` is unset).
    embeds    — encoder-decoder only: (e, d_model) frame embeddings from
                the (stubbed) audio frontend, e <= cfg.enc_len.  Clips
                SHORTER than enc_len are first-class: the slot's cross
                cache rows past e are masked by the per-slot enc_pos
                clock.  None falls back to enc_len of silence (zeros).
    sampling  — per-request SamplingParams; None decodes greedily with no
                stop tokens (the historical contract: exactly `max_new`
                tokens, bitwise-identical across loop modes).
    generated — filled by the server: the generated tokens in order
                (<= max_new of them; ends with a stop token iff one was
                hit).  Independent of which slot or batch the request
                shared (per-row position clocks, per-slot PRNG chains).
    spec_accepted / spec_proposed — filled at retirement under
                speculative serving (DESIGN.md §7): this request's
                lifetime draft-acceptance record, read from the device
                SlotState counters (the per-request numbers the host
                cannot derive from segment outputs once slots are
                reused).  None outside speculative mode (or for
                requests that finished at admission)."""
    rid: int
    prompt: np.ndarray            # (prompt_len,) int32
    max_new: int
    embeds: Optional[np.ndarray] = None
    sampling: Optional[SamplingParams] = None
    generated: Optional[List[int]] = None
    spec_accepted: Optional[int] = None
    spec_proposed: Optional[int] = None
    # host-tier offload (DESIGN.md §8): how many times this request's
    # slot was evicted to host RAM and later restored — the stream stays
    # bitwise-identical regardless (asserted in tests/test_cache_offload)
    suspensions: int = 0


def _prefill_bucket(n: int, cap: int) -> int:
    """Pad prompt lengths to powers of two (>= 8) so the jitted prefill
    retraces once per bucket, not once per length; capped at `cap`
    (= max_seq) so a legal prompt never pads past the cache."""
    p = 8
    while p < n:
        p *= 2
    return min(p, cap)


class BatchedServer:
    """Slot-based continuous batching over a fixed decode batch.

    Each of `batch_slots` rows of the decode cache is a serving slot: a
    queued Request is admitted into a free slot by a real prefill
    (`_prefill`), decodes greedily until its `max_new` budget is spent,
    then retires and frees the slot for the next queued request.

    Per-row position-clock INVARIANT: `positions[s]` is the sequence
    position of the token currently held in `tokens[s]` — i.e. the
    number of tokens (prompt + generated) that precede it.  It starts at
    `len(prompt)` right after prefill (the first generated token sits at
    position P) and advances by one per decode step, per row, never
    globally.  Everything position-dependent — RoPE angles, cache slot
    validity (the cache holds tokens [0, pos), so valid slots are
    strictly `slot < pos`; the current token rides as the merged
    extra partial until its ring-slot write), sliding-window bounds,
    ring-slot writes at `pos % max_seq` — is driven by this (B,) vector,
    which is what makes
    a request's tokens independent of its slot and of whatever the other
    slots are doing.  A scalar step counter cannot express a batch whose
    rows sit at different offsets; the cache's `pos` scalar is kept only
    for the single-sequence `decode_step(positions=None)` path.

    Prompts are padded to power-of-two buckets (`_prefill_bucket`) so the
    jitted prefill traces once per bucket; junk past the true length is
    harmless by construction (see transformer.prefill_into_cache).

    Decoding control state lives DEVICE-side in a `steps.SlotState`: the
    per-slot PRNG chains, sampling parameters, stop sets, budgets and
    alive masks ride the jitted segments, so stochastic per-request
    decoding keeps the ~1-sync-per-segment property.  Termination
    accounting (DESIGN.md §6):

      * rows WITHOUT stop tokens terminate only by budget — a count the
        host knows at dispatch, so they retire at dispatch time exactly
        as in the greedy-only loop (same pipeline depth, same syncs);
      * rows WITH stop tokens terminate stochastically — the device's
        in-segment alive mask is authoritative, the host learns of the
        death one overlapped device_get later and retires the row at
        that segment boundary (the slot refills one segment later than
        a dispatch-time oracle could — the price of not syncing
        mid-segment).

    Two drive modes (`run_until_drained` dispatches on `stream`):
      per-token — `step()`: a seg_len-1 segment + one host sync per
                  token; the bulk-synchronous baseline.
      streamed  — `run_stream()`: jitted `seg_len`-token segments with
                  double-buffered device_get; ~1 host sync per seg_len
                  tokens.  Both modes emit identical tokens (the PRNG
                  chain is per-slot per-step, not per-dispatch).

    Speculative mode (`spec=True`, DESIGN.md §7): the same two drive
    loops run draft-and-verify segments instead — `seg_len` rounds of
    (k-token draft, one multi-position verify) per streamed dispatch
    (one round per `step()`), so a segment delivers a VARIABLE
    `rounds..rounds·(k+1)` tokens per row.  Because the emit count is
    accept-dependent, no row's usage is knowable at dispatch: every row
    takes the segment-boundary accounting regime below (the one stop-
    token rows already use), trading one segment of refill lag for the
    accept-rate multiple on tokens/sync.  Accept accounting is
    two-level: server totals (`draft_accepted`/`draft_proposed`, the
    benchmark's accept-rate source) are derived per segment from the
    emit masks and accept-length outputs, while each request's LIFETIME
    record rides the device SlotState counters and is stamped onto the
    `Request` (`spec_accepted`/`spec_proposed`) at retirement — in a
    drained server the two agree exactly (asserted in
    tests/test_speculative.py).
    """

    def __init__(self, arch_id: str, *, smoke: bool = True,
                 batch_slots: int = 4, max_seq: int = 256,
                 protocol: str = "axle", chunks_per_shard: int = 1,
                 mesh=None, seg_len: int = 8, stream: bool = False,
                 spec: bool = False, spec_k: int = 3,
                 draft_arch: Optional[str] = None,
                 host_offload: bool = False, prefix_cache: bool = False,
                 evict_after: int = 1, offload_chunks: int = 2,
                 page_size: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 quant: Optional[steps_lib.QuantConfig] = None,
                 n_layers: Optional[int] = None):
        self.cfg = (get_smoke_config(arch_id) if smoke
                    else get_config(arch_id))
        if n_layers is not None:
            # a depth cut: the first `n_layers` layers at the
            # configuration's own widths
            self.cfg = dataclasses.replace(self.cfg, n_layers=n_layers)
        self.model = get_model(self.cfg)
        self.batch = batch_slots
        self.max_seq = max_seq
        self.seg_len = seg_len
        self.stream = stream
        self.offload = OffloadConfig(protocol=PROTOCOLS[protocol],
                                     chunks_per_shard=chunks_per_shard)
        # Tensor-parallel serving (DESIGN.md §11): under a mesh the rules
        # are the head-sharded layout whose every collective is a
        # bit-copy, so streamed tokens are BITWISE the single-device
        # server's for any mesh shape (tests/test_sharded_serve.py).
        self.rules = sh.ShardingRules(mesh, head_shard_attn=True) \
            if mesh is not None else None
        self.params = self.model.init_params(self.cfg, jax.random.key(0))
        # serving-time quantization (DESIGN.md §10): block-quantized
        # weight stacks and/or an int8 KV cache.  Weight quant rewrites
        # the params ONCE here — everything downstream (prefill, decode
        # segments, self-draft slicing) dispatches on the QTensor leaves;
        # KV quant is a property of the cache (scale leaves), detected by
        # every consumer from the cache keys, so no step function needs a
        # flag.
        self.quant = quant or steps_lib.QuantConfig()
        if self.quant.weights is not None:
            from repro.models.quantize import quantize_params
            self.params = quantize_params(self.params, self.quant.weights)
        # block-sparse KV paging (DESIGN.md §9): attention caches carry a
        # (B, n_pages) page table; `page_size` overrides the default
        # chunk-as-page size (which reproduces the dense kernel's grid).
        self.cache = self.model.init_cache(self.cfg, batch_slots, max_seq,
                                           page_size=page_size,
                                           kv_quant=self.quant.kv)
        # ---- mesh placement (DESIGN.md §11) --------------------------
        # device_put COMMITS the serving shardings; every donated jit
        # downstream propagates them, so no step function needs explicit
        # in_shardings.  Params: REPLICATED on the model axis — a
        # column-partitioned gemm changes the backend's blocking and
        # perturbs bf16 low bits, so head slicing happens only inside
        # the decode shard_map (serve_param_specs); cache: KV-head axis
        # in the n | KH regime, batch over the data axes — both pure
        # layout choices (serve_cache_specs).
        self.plan = None
        if mesh is not None:
            from repro.launch import partition
            self.plan = partition.PartitionPlan(rules=self.rules,
                                                fsdp=False)
            self.params = jax.device_put(
                self.params, partition.to_shardings(
                    partition.serve_param_specs(self.params, self.cfg,
                                                self.plan), mesh))
            self.cache = jax.device_put(
                self.cache, partition.to_shardings(
                    partition.serve_cache_specs(self.cache, self.cfg,
                                                self.plan), mesh))
        # page ledger: one logical page = `page_size` sequence positions
        # of one slot row, charged AS THE POSITION CLOCK ADVANCES
        # (prompt pages at admission, decode pages at segment dispatch,
        # trimmed to the true clock at consume) and released at every
        # retirement/suspension path — so pages_resident is true
        # occupancy, not the admission-time upper bound (closure
        # invariant: allocated == freed + resident, asserted every tick
        # and by tests/test_serve_churn.py).  Pure-SSM caches have no
        # page table; the ledger still tracks logical KV-footprint spans
        # with the default page size so the accounting is arch-uniform.
        self.page_size = (transformer.cache_page_size(self.cache)
                          if "page_table" in self.cache
                          else transformer.default_page_size(max_seq))
        self.pages_allocated = 0
        self.pages_freed = 0
        self.pages_resident_peak = 0
        self.slot_pages = np.zeros((batch_slots,), np.int64)
        # cache donation: in-place ring-slot updates (§Perf iteration D3)
        # per-token mode is a seg_len-1 segment through the SAME sampling
        # machinery, so both loop modes share one PRNG chain / stop
        # semantics and emit identical tokens.  Each mode has a `plain`
        # greedy fast-path twin (no sort/Gumbel epilogue, no write-mask
        # selects) picked at dispatch when no active row samples or has
        # stops — the pre-sampling hot path at pre-sampling cost; jit is
        # lazy, so a variant never dispatched is never compiled.
        self.step_fn = jax.jit(
            steps_lib.make_decode_segment(self.cfg, 1),
            donate_argnums=(1,))
        self.step_plain_fn = jax.jit(
            steps_lib.make_decode_segment(self.cfg, 1, plain=True),
            donate_argnums=(1,))
        self.segment_fn = jax.jit(
            steps_lib.make_decode_segment(self.cfg, seg_len),
            donate_argnums=(1,))
        self.segment_plain_fn = jax.jit(
            steps_lib.make_decode_segment(self.cfg, seg_len, plain=True),
            donate_argnums=(1,))
        # device-side per-slot decode state (tokens, positions, PRNG
        # chains, budgets, alive masks, sampling params, stop sets,
        # accept counters)
        self.state = steps_lib.init_slot_state(batch_slots)
        # speculative draft-and-verify decoding (DESIGN.md §7): resolve
        # the draft — "self[:N]" slices the target's first N blocks into
        # a truncated-layer self-draft (N defaults to half the depth;
        # N = n_blocks is the bitwise accept-rate-1 configuration), any
        # other value names a registered arch sharing the vocabulary.
        self.spec = spec
        self.spec_k = spec_k
        self.draft_accepted = 0
        self.draft_proposed = 0
        if spec:
            da = draft_arch or self.cfg.draft_arch
            assert da, (f"{arch_id}: speculative serving needs a draft "
                        "(ArchConfig.draft_arch or the draft_arch ctor arg)")
            if da == "self" or da.startswith("self:"):
                n = (int(da.split(":", 1)[1]) if ":" in da
                     else max(1, self.cfg.n_blocks // 2))
                self.draft_cfg = steps_lib.self_draft_config(self.cfg, n)
                self.draft_params = steps_lib.self_draft_params(
                    self.cfg, self.params, n)
            else:
                self.draft_cfg = (get_smoke_config(da) if smoke
                                  else get_config(da))
                assert self.draft_cfg.vocab == self.cfg.vocab, \
                    (self.cfg.vocab, self.draft_cfg.vocab)
                assert self.draft_cfg.enc_dec == self.cfg.enc_dec
                self.draft_params = get_model(self.draft_cfg).init_params(
                    self.draft_cfg, jax.random.key(1))
            self.draft_model = get_model(self.draft_cfg)
            self.draft_cache = self.draft_model.init_cache(
                self.draft_cfg, batch_slots, max_seq)
            if self.plan is not None:
                # the draft rides the same mesh under ITS OWN head
                # regime (a truncated self-draft shares the target's)
                from repro.launch import partition
                self.draft_params = jax.device_put(
                    self.draft_params, partition.to_shardings(
                        partition.serve_param_specs(
                            self.draft_params, self.draft_cfg,
                            self.plan), mesh))
                self.draft_cache = jax.device_put(
                    self.draft_cache, partition.to_shardings(
                        partition.serve_cache_specs(
                            self.draft_cache, self.draft_cfg,
                            self.plan), mesh))
            self.draft_prefill_fn = jax.jit(
                steps_lib.make_prefill_into_cache(self.draft_cfg),
                donate_argnums=(1,))
            # one spec round per step() dispatch, seg_len rounds per
            # streamed dispatch, each with a `plain` greedy fast-path
            # twin (argmax drafts + prefix-match verify, no sampling or
            # Gumbel epilogues) picked at dispatch exactly like the
            # non-speculative plain variants; jit is lazy, so a variant
            # never dispatched is never compiled (donating BOTH caches)
            self.spec_step_fn = jax.jit(
                steps_lib.make_spec_decode_segment(
                    self.cfg, self.draft_cfg, 1, spec_k),
                donate_argnums=(2, 3))
            self.spec_step_plain_fn = jax.jit(
                steps_lib.make_spec_decode_segment(
                    self.cfg, self.draft_cfg, 1, spec_k, plain=True),
                donate_argnums=(2, 3))
            self.spec_segment_fn = jax.jit(
                steps_lib.make_spec_decode_segment(
                    self.cfg, self.draft_cfg, seg_len, spec_k),
                donate_argnums=(2, 3))
            self.spec_segment_plain_fn = jax.jit(
                steps_lib.make_spec_decode_segment(
                    self.cfg, self.draft_cfg, seg_len, spec_k,
                    plain=True),
                donate_argnums=(2, 3))
        # every registered config has a real prefill path (attention,
        # SSM/hybrid state capture, enc-dec) — admission never degrades
        # to last-token seeding.
        assert transformer.supports_prefill_into_cache(self.cfg), \
            self.cfg.arch_id
        # enc-dec admission computes the encoder output ONCE and feeds it
        # to every prefill that needs it (target + speculative draft) —
        # the double-encode fix: a self-draft shares the encoder params
        # by reference, so one `encode` pass is bitwise what each prefill
        # would have recomputed per-admission.
        self.encode_fn = None
        if self.cfg.enc_dec:
            from repro.models import encdec

            def _encode(params, enc_embeds):
                return encdec.encode(self.cfg, params, enc_embeds,
                                     remat=False)

            self.encode_fn = jax.jit(_encode)
            self.prefill_fn = jax.jit(
                steps_lib.make_prefill_into_cache(self.cfg,
                                                  from_enc_out=True),
                donate_argnums=(1,))
        else:
            self.prefill_fn = jax.jit(
                steps_lib.make_prefill_into_cache(self.cfg),
                donate_argnums=(1,))
        self.encoder_passes = 0
        # the draft shares the one encoder pass only when its encoder IS
        # the target's (self-draft params alias); a foreign enc-dec
        # draft keeps its own encoder forward
        self.draft_shares_encoder = False
        if spec and self.cfg.enc_dec:
            da = draft_arch or self.cfg.draft_arch
            self.draft_shares_encoder = (da == "self"
                                         or da.startswith("self:"))
            if self.draft_shares_encoder:
                self.draft_prefill_fn = jax.jit(
                    steps_lib.make_prefill_into_cache(self.draft_cfg,
                                                      from_enc_out=True),
                    donate_argnums=(1,))
        # ---- host-tier cache offload + prefix reuse (DESIGN.md §8) ----
        self.host_offload = host_offload
        self.evict_after = max(1, evict_after)
        self.offload_chunks = offload_chunks
        assert not (prefix_cache and spec), \
            "prefix reuse under speculative serving is a ROADMAP item"
        assert not (prefix_cache and self.cfg.enc_dec), \
            "enc-dec prompts are keyed on audio frames, not token prefixes"
        self.host_tier = HostTier() if host_offload else None
        self.prefix = PrefixCache() if prefix_cache else None
        self.suspended: List[Request] = []
        self.slot_age = np.zeros((batch_slots,), np.int64)
        if host_offload or prefix_cache:
            extract, insert = steps_lib.make_slot_page_fns(self.cfg)
            # `upto` is a shape (KV page width) — static; `row` traces
            self.extract_fn = jax.jit(extract, static_argnums=(2,))
            self.insert_fn = jax.jit(insert, donate_argnums=(0,))
            resume = steps_lib.make_resume_prefill(self.cfg)
            self.resume_fn = (jax.jit(resume, donate_argnums=(1,))
                              if resume is not None else None)
        if host_offload and spec:
            # eviction under speculative serving (DESIGN.md §8.5): the
            # draft's slot pages leave and return WITH the target's, as
            # one paired page set — a restored row resumes draft-and-
            # verify from the exact draft state it was evicted with, so
            # greedy evicted streams stay bitwise non-evicted ones
            dex, dins = steps_lib.make_slot_page_fns(self.draft_cfg)
            self.draft_extract_fn = jax.jit(dex, static_argnums=(2,))
            self.draft_insert_fn = jax.jit(dins, donate_argnums=(0,))
        # ---- chunked admission prefill (DESIGN.md §9) --------------------
        # `prefill_chunk=C` admits prompts longer than C in C-token chunks
        # dispatched at most ONE per loop tick, each slotted BEHIND the
        # decode segment just dispatched — a 10k-token prompt admits
        # without adding a single decode sync for the in-flight streams.
        self.prefill_chunk = prefill_chunk
        self.prefilling: Dict[int, Dict[str, Any]] = {}
        if prefill_chunk is not None:
            assert prefill_chunk >= 1, prefill_chunk
            assert not spec, \
                "chunked prefill under speculative serving is a ROADMAP item"
            assert not prefix_cache, \
                "chunked prefill under prefix reuse is a ROADMAP item"
            assert not self.cfg.enc_dec, \
                "enc-dec prompts admit via the encoder, not chunked prefill"
            cp = steps_lib.make_chunked_prefill(self.cfg)
            assert cp is not None, self.cfg.arch_id
            self.chunk_first_fn = jax.jit(cp.first, donate_argnums=(1,))
            self.chunk_resume_fn = jax.jit(cp.resume, donate_argnums=(1,))
            self.chunk_plan = cp.plan
        self.prefill_chunks = 0        # chunk forwards dispatched
        self.prefill_chunk_time = 0.0  # host-side chunk dispatch seconds
        self.evictions = 0
        self.restores = 0
        self.restored_dead = 0         # evicted rows that died in flight
        self.prefix_hits_full = 0
        self.prefix_hits_partial = 0
        self.prefix_misses = 0
        self.prefill_tokens_skipped = 0
        self.prefill_forwards = 0
        self.evict_dispatch_time = 0.0
        self.restore_dispatch_time = 0.0
        self.queue: List[Request] = []
        self.active: List[Optional[Request]] = [None] * batch_slots
        # host mirrors of the device SlotState, for dispatch-time budget
        # accounting (`remaining`) and the per-row clock asserts
        # (`positions`); the token chain itself lives only on device
        self.positions = np.zeros((batch_slots,), np.int32)
        self.remaining = np.zeros((batch_slots,), np.int32)
        self.completed: List[Request] = []
        self.steps = 0                 # decode token-steps issued
        self.segments_dispatched = 0
        self.host_syncs = 0            # every host<->device sync (incl. prefill)
        self.decode_syncs = 0          # syncs attributable to the decode loop
        self.tokens_emitted = 0
        # ---- AXLE wire accounting (DESIGN.md §11) --------------------
        # Every decode step runs exactly one head-group partial merge
        # per attention sublayer of the TARGET model (a verify forward:
        # one per draft position per sublayer), so the host charges the
        # ledger deterministically at dispatch — no device readback.
        # Zero-wire cases (single shard, replicated fallback, pure-SSM)
        # fall out of the formula: n_shards == 1 or heads_local * 0.
        n_attn = self.cfg.attn_layers_per_block() * self.cfg.n_blocks
        self._merges_per_step = n_attn
        self._merges_per_spec_round = (spec_k + 1) * n_attn
        if mesh is not None:
            from repro.launch import partition
            shard_q, _ = partition.serve_head_regime(self.cfg, self.plan)
            n_eff = self.rules.model_size() if shard_q else 1
            n_data = 1
            for a in self.rules.batch_axes:
                n_data *= mesh.shape[a]
            rows_local = (batch_slots // n_data
                          if n_data > 0 and batch_slots % n_data == 0
                          else batch_slots)
            self.wire = ring_lib.WireLedger(
                n_shards=n_eff, rows_local=rows_local,
                heads_local=self.cfg.n_heads // max(1, n_eff),
                head_dim=self.cfg.head_dim_)
        else:
            self.wire = ring_lib.WireLedger(
                n_shards=1, rows_local=batch_slots,
                heads_local=self.cfg.n_heads,
                head_dim=self.cfg.head_dim_)

    @property
    def wire_bytes_per_shard(self) -> int:
        """Bytes ONE shard sent over the AXLE wire so far (DESIGN.md
        §11) — the mesh-scale analogue of `tpu_backstream.AXLE`'s
        per-merge accounting; 0 off-mesh and in every replicated
        regime."""
        return self.wire.wire_bytes_per_shard

    # -- admission ---------------------------------------------------------

    def submit(self, req: Request) -> None:
        req.generated = []
        self.queue.append(req)

    def _ctx(self):
        return self.rules.mesh if self.rules is not None else _null()

    # -- page ledger (DESIGN.md §9) ----------------------------------------

    def _pages_for(self, footprint: int) -> int:
        """Page span of a `footprint`-position row, clamped to the ring
        capacity (positions past max_seq wrap onto already-charged
        pages)."""
        return -(-min(int(footprint), self.max_seq) // self.page_size)

    def _set_pages(self, slot: int, n: int) -> None:
        """Delta-account slot's resident page count to exactly `n`.

        The ledger charges pages AS THE POSITION CLOCK ADVANCES, not the
        whole prompt+budget span at admission: a dispatch charges the
        segment's worst-case footprint up front (the rows it is about to
        write), consume trims back to the true post-segment clock, and
        retirement/suspension releases everything.  The old
        admission-time span charge counted pages no token had touched —
        `pages_resident` overshot true occupancy by the UNSPENT budget of
        every active row, so the peak statistic (the paper's
        memory-pressure signal) was an upper bound, not a measurement.
        Closure `allocated == freed + resident` holds at every step by
        construction and is asserted per tick (`assert_ledger`)."""
        cur = int(self.slot_pages[slot])
        assert n >= 0, (slot, n)
        if n > cur:
            self.pages_allocated += n - cur
        else:
            self.pages_freed += cur - n
        self.slot_pages[slot] = n
        self.pages_resident_peak = max(self.pages_resident_peak,
                                       self.pages_resident)

    def _free_pages(self, slot: int) -> None:
        self._set_pages(slot, 0)

    @property
    def pages_resident(self) -> int:
        """Pages currently charged to occupied (active or mid-chunked-
        prefill) slots; `allocated == freed + resident` at every point,
        so `allocated == freed` in a drained server (no page leaks)."""
        return int(self.slot_pages.sum())

    def assert_ledger(self) -> None:
        """The per-tick closure invariant: every page ever charged is
        either freed or resident in a currently-occupied slot, and no
        unoccupied slot holds pages."""
        assert self.pages_allocated == self.pages_freed \
            + self.pages_resident, (self.pages_allocated, self.pages_freed,
                                    self.pages_resident)
        for s in range(self.batch):
            if self.active[s] is None and s not in self.prefilling:
                assert self.slot_pages[s] == 0, (s, self.slot_pages[s])

    def _prefill(self, slot: int, req: Request) -> jax.Array:
        """Real prefill: the whole prompt through the jitted prefill step
        — per-layer K/V and/or recurrent (conv, ssm) states written into
        this slot's cache rows; enc-dec archs additionally run the
        encoder on the request's frames (at their TRUE length e <=
        enc_len — shorter clips retrace once per distinct length and set
        the slot's enc_pos clock) and fill the slot's cross-KV.  Returns
        the last prompt position's logits (a device array — no sync)."""
        plen = len(req.prompt)
        assert plen <= self.max_seq, (plen, self.max_seq)
        padded = np.zeros((_prefill_bucket(plen, self.max_seq),), np.int32)
        padded[:plen] = req.prompt
        args = ()
        if self.cfg.enc_dec:
            emb = req.embeds
            if emb is None:       # silence: the stub frontend's zero frames
                emb = np.zeros((self.cfg.enc_len, self.cfg.d_model),
                               np.float32)
            e, d = emb.shape
            assert e <= self.cfg.enc_len and d == self.cfg.d_model, emb.shape
        with self._ctx(), sh.use_rules(self.rules), use_offload(self.offload):
            if self.cfg.enc_dec:
                # ONE encoder pass per admission, shared by every prefill
                # below (the double-encode fix; tests/test_cache_offload
                # asserts encoder_passes == admissions under spec)
                enc_out = self.encode_fn(self.params, jnp.asarray(emb)[None])
                self.encoder_passes += 1
                args = (enc_out,)
            logits, self.cache = self.prefill_fn(
                self.params, self.cache, jnp.asarray(padded), slot, plen,
                *args)
            self.prefill_forwards += 1
            if self.spec:
                # the draft keeps its OWN prompt state per slot — same
                # prefill machinery against the (sliced or separate)
                # draft parameters; its last-token logits are discarded
                # (the first token is always sampled from the TARGET).
                # A self-draft reuses the target's enc_out (it shares the
                # encoder params by reference — bitwise the same pass);
                # only a FOREIGN enc-dec draft runs its own encoder.
                draft_args = args
                if self.cfg.enc_dec and not self.draft_shares_encoder:
                    draft_args = (jnp.asarray(emb)[None],)
                    self.encoder_passes += 1
                _, self.draft_cache = self.draft_prefill_fn(
                    self.draft_params, self.draft_cache,
                    jnp.asarray(padded), slot, plen, *draft_args)
        return logits

    # -- prefix-cache reuse (DESIGN.md §8) ---------------------------------

    def _admit_prefill(self, slot: int, req: Request) -> jax.Array:
        """Prompt admission through the prefix cache: serve the longest
        cached prefix of `req.prompt` from host-resident pages before
        spending any prefill compute.

          full hit    — the whole prompt is cached: restore its pages
                        into the slot row and return the STORED last-
                        token logits; zero forward passes (the skip the
                        prefix cache exists to buy).  Bitwise-identical
                        to a fresh prefill: same prompt means same
                        bucket, and the pages/logits were captured from
                        exactly that jitted prefill.
          partial hit — restore the prefix pages, then run ONLY the
                        suffix through the jitted resume-prefill
                        (token-equal to a full prefill; see
                        transformer.resume_prefill_into_cache).  Falls
                        back to a miss when the bucketed suffix would
                        overflow max_seq (a clamped dynamic_update_slice
                        would silently shift the KV writes).
          miss        — full prefill, then PUT this prompt's pages (+
                        last-token logits, riding the page dict under
                        'logits') so the next sharer hits."""
        if self.prefix is None:
            return self._prefill(slot, req)
        plen = len(req.prompt)
        hit = self.prefix.lookup(req.prompt)
        if hit is not None and hit.length == plen:
            pages = dict(hit.pages.materialize())
            logits = jnp.asarray(pages.pop("logits"))
            dev = stream_offload_to_device(pages, chunks=self.offload_chunks)
            with self._ctx(), sh.use_rules(self.rules), \
                    use_offload(self.offload):
                self.cache = self.insert_fn(self.cache, dev, slot)
            self.prefix_hits_full += 1
            self.prefill_tokens_skipped += plen
            return logits
        if hit is not None:
            start = hit.length
            sbucket = _prefill_bucket(plen - start, self.max_seq)
            if start + sbucket <= self.max_seq:
                pages = dict(hit.pages.materialize())
                pages.pop("logits")
                dev = stream_offload_to_device(pages,
                                               chunks=self.offload_chunks)
                suffix = np.zeros((sbucket,), np.int32)
                suffix[:plen - start] = req.prompt[start:]
                with self._ctx(), sh.use_rules(self.rules), \
                        use_offload(self.offload):
                    self.cache = self.insert_fn(self.cache, dev, slot)
                    logits, self.cache = self.resume_fn(
                        self.params, self.cache, jnp.asarray(suffix),
                        slot, plen, start)
                self.prefix_hits_partial += 1
                self.prefill_tokens_skipped += start
                self.prefill_forwards += 1
                self._prefix_put(slot, req, logits)
                return logits
        self.prefix_misses += 1
        logits = self._prefill(slot, req)
        self._prefix_put(slot, req, logits)
        return logits

    def _prefix_put(self, slot: int, req: Request,
                    logits: jax.Array) -> None:
        """Store this prompt's freshly-written slot pages in the prefix
        trie: KV rows up to the prompt's prefill bucket (`upto` — junk
        between plen and the bucket stays invisible under the validity
        clock on any future restore), the post-prompt recurrent state,
        and the last-token logits — all streamed host-ward through the
        same chunked async copies eviction uses, so the put costs the
        admission path no sync."""
        bucket = _prefill_bucket(len(req.prompt), self.max_seq)
        with self._ctx(), sh.use_rules(self.rules), use_offload(self.offload):
            pages = dict(self.extract_fn(self.cache, slot, bucket))
        pages["logits"] = logits
        self.prefix.put(req.prompt,
                        stream_offload_to_host(pages,
                                               chunks=self.offload_chunks))

    # -- host-tier slot eviction / restore (DESIGN.md §8) ------------------

    def suspend_slot(self, slot: int) -> None:
        """Evict one active slot to the host tier: its cache pages (every
        leaf kind) and its SlotState row leave as chunked async host
        copies — the dispatch itself never blocks, so an eviction rides
        behind whatever decode segment is in flight.  The request joins
        the `suspended` FIFO; `_restore` brings it back when a slot
        frees.  Correct even with an undelivered segment referencing
        this slot: the snapshot is taken from the POST-segment device
        arrays (data dependence), token delivery in `_consume_segment`
        is keyed on the rows dict (not slot occupancy), and the request
        cannot be re-admitted before that segment is consumed (consume
        happens within one loop iteration of dispatch)."""
        req = self.active[slot]
        assert req is not None
        t0 = time.perf_counter()
        with self._ctx(), sh.use_rules(self.rules), use_offload(self.offload):
            pages = dict(self.extract_fn(self.cache, slot, None))
            if self.spec:
                # paired page set (DESIGN.md §8.5): the draft cache's
                # slot row rides the same snapshot under a "draft/" key
                # prefix, so target and draft state stay in lockstep
                # across the evict→restore round trip
                dpages = self.draft_extract_fn(self.draft_cache, slot,
                                               None)
                pages.update({"draft/" + k: v
                              for k, v in dpages.items()})
        snap = stream_offload_to_host(pages, chunks=self.offload_chunks)
        saved = stream_offload_to_host(
            steps_lib.save_slot_state(self.state, slot))
        self.host_tier.put(req.rid, snap, saved)
        self.active[slot] = None
        self._free_pages(slot)
        self.suspended.append(req)
        req.suspensions += 1
        self.evictions += 1
        self.evict_dispatch_time += time.perf_counter() - t0

    def _restore(self, slot: int, req: Request) -> bool:
        """Re-admit a suspended request from the host tier.  The page
        restore is pure async dispatch — per-chunk `device_put` +
        insert, queued behind the in-flight segment with NO decode sync
        (the bench's `stream.restore` rows assert syncs/token is
        unchanged).  Reading the saved SlotState row back for the host
        mirrors is the one blocking step; its async copy was issued at
        eviction, so by restore time it has long drained (accounted like
        an admission sync, outside `decode_syncs`).  Returns False —
        request complete, slot still free — when the row died in its
        final in-flight segment after eviction (its tokens were still
        delivered; stop-regime rows only)."""
        t0 = time.perf_counter()
        snap, saved_snap = self.host_tier.pop(req.rid)
        saved = saved_snap.materialize()
        self.host_syncs += 1        # the saved-state read (was async)
        if not bool(saved["alive"]):
            if self.spec:
                # the row died in its final in-flight segment after
                # eviction: its lifetime accept record rides the saved
                # SlotState row, not the live device counters
                req.spec_accepted = int(saved["accepted"])
                req.spec_proposed = int(saved["proposed"])
            self.restored_dead += 1
            return False
        pages = stream_offload_to_device(snap.materialize(),
                                         chunks=self.offload_chunks)
        dpages = {k[len("draft/"):]: v for k, v in pages.items()
                  if k.startswith("draft/")}
        pages = {k: v for k, v in pages.items()
                 if not k.startswith("draft/")}
        with self._ctx(), sh.use_rules(self.rules), use_offload(self.offload):
            self.cache = self.insert_fn(self.cache, pages, slot)
            if self.spec:
                self.draft_cache = self.draft_insert_fn(
                    self.draft_cache, dpages, slot)
        self.state = steps_lib.restore_slot(self.state, slot, saved)
        self.positions[slot] = int(saved["position"])
        self.remaining[slot] = int(saved["remaining"])
        # re-charge exactly the restored clock's pages (not the unspent
        # budget) — the suspension freed the same count
        self._set_pages(slot, self._pages_for(self.positions[slot]))
        self.slot_age[slot] = 0
        self.restores += 1
        self.restore_dispatch_time += time.perf_counter() - t0
        return True

    def _evict_for_demand(self) -> None:
        """Eviction policy: when waiting requests outnumber free slots,
        spill the coldest active rows (largest `slot_age`, i.e. most
        segments since (re-)admission) to the host tier — but never a
        row younger than `evict_after` segments, the quantum that keeps
        the loop round-robin instead of thrashing."""
        free = sum(self.active[s] is None and s not in self.prefilling
                   for s in range(self.batch))
        need = len(self.queue) + len(self.suspended) - free
        if need <= 0:
            return
        eligible = sorted(
            (s for s in range(self.batch)
             if self.active[s] is not None
             and self.slot_age[s] >= self.evict_after),
            key=lambda s: -self.slot_age[s])
        for s in eligible[:need]:
            self.suspend_slot(s)

    def _admit(self, slot: int, req: Request) -> bool:
        """Prefill + first-token sampling + device state seeding for one
        request.  The first token is sampled with split #0 of the
        request's seed key and every later token with splits #1, #2, …
        inside the jitted segments — one chain, independent of loop mode
        and segmentation.  Returns False if the request finished on its
        first token (budget of 1, or an immediate stop hit)."""
        sp = req.sampling or GREEDY
        assert len(sp.stop_tokens) <= steps_lib.MAX_STOP_TOKENS, sp
        max_new = sp.max_new if sp.max_new is not None else req.max_new
        if self.spec:
            # a verify forward ring-writes up to spec_k junk rows past a
            # row's final position; keep them off the valid prefix
            assert len(req.prompt) + max_new + self.spec_k <= self.max_seq, \
                (len(req.prompt), max_new, self.spec_k, self.max_seq)
        logits = self._admit_prefill(slot, req)
        # the ledger charges what the clock has covered — the prompt's
        # pages, just written; the budget's pages are charged only as
        # decode dispatches actually reach them (see _set_pages)
        self._set_pages(slot, self._pages_for(len(req.prompt)))
        return self._finish_admit(slot, req, logits)

    def _finish_admit(self, slot: int, req: Request,
                      logits: jax.Array) -> bool:
        """The admission tail shared by one-shot (`_admit`) and chunked
        (`_pump_prefill`) prefill: sample the first token from the last
        prompt position's logits (split #0 of the request's chain — the
        one admission host sync) and seed the device SlotState row.
        Returns False if the request finished on its first token."""
        sp = req.sampling or GREEDY
        max_new = sp.max_new if sp.max_new is not None else req.max_new
        key, sub = jax.random.split(jax.random.PRNGKey(sp.seed))
        samp1 = ops.BatchedSampling(
            temperature=jnp.full((1,), sp.temperature, jnp.float32),
            top_k=jnp.full((1,), sp.top_k, jnp.int32),
            top_p=jnp.full((1,), sp.top_p, jnp.float32),
            min_p=jnp.full((1,), sp.min_p, jnp.float32))
        first = int(ops.sample_tokens(logits[None], samp1, sub[None],
                                      vocab=self.cfg.vocab)[0])
        self.host_syncs += 1           # the admission sync (was: argmax)
        req.generated.append(first)
        self.tokens_emitted += 1
        remaining = max_new - 1
        if remaining <= 0 or first in sp.stop_tokens:
            return False
        # the first generated token sits at position len(prompt)
        self.positions[slot] = len(req.prompt)
        self.remaining[slot] = remaining
        stop = np.full((steps_lib.MAX_STOP_TOKENS,), -1, np.int32)
        stop[:len(sp.stop_tokens)] = sp.stop_tokens
        self.state = steps_lib.admit_slot(
            self.state, slot, token=first, position=len(req.prompt),
            key=key, remaining=remaining, temperature=sp.temperature,
            top_k=sp.top_k, top_p=sp.top_p, min_p=sp.min_p,
            stop=jnp.asarray(stop))
        return True

    # -- chunked admission scheduling (DESIGN.md §9) -----------------------

    def _begin_chunked(self, slot: int, req: Request) -> None:
        """Reserve `slot` for a chunked admission: the slot joins the
        `prefilling` map (kept out of decode dispatch, slot filling and
        eviction).  No forward runs here and no pages are charged yet —
        each chunk dispatch in `_pump_prefill` charges exactly the pages
        its rows land in, so mid-admission residency tracks the prefix
        actually written, not the whole prompt+budget span."""
        plen = len(req.prompt)
        assert plen <= self.max_seq, (plen, self.max_seq)
        self.prefilling[slot] = {
            "req": req,
            "plan": self.chunk_plan(plen, self.prefill_chunk),
            "next": 0,
        }

    def _pump_prefill(self) -> None:
        """Dispatch AT MOST ONE prefill chunk — the scheduler's interleave
        invariant: between consecutive decode segments the device sees at
        most one bounded-latency chunk forward, so in-flight streams keep
        their segment cadence (and `decode_syncs`) bit-for-bit unchanged
        while a long prompt admits.  Chunk forwards are pure async
        dispatch; the only host sync is the final chunk's first-token
        sample (inside `_finish_admit`, accounted like any admission)."""
        if not self.prefilling:
            return
        slot = min(self.prefilling)          # deterministic FIFO-by-slot
        st = self.prefilling[slot]
        req = st["req"]
        start, size = st["plan"][st["next"]]
        chunk = np.zeros((self.prefill_chunk,), np.int32)
        chunk[:size] = req.prompt[start:start + size]
        t0 = time.perf_counter()
        with self._ctx(), sh.use_rules(self.rules), use_offload(self.offload):
            if start == 0:
                logits, self.cache = self.chunk_first_fn(
                    self.params, self.cache, jnp.asarray(chunk), slot, size)
            else:
                logits, self.cache = self.chunk_resume_fn(
                    self.params, self.cache, jnp.asarray(chunk), slot,
                    start + size, start)
        self.prefill_chunk_time += time.perf_counter() - t0
        self.prefill_chunks += 1
        # charge the pages this chunk's rows just landed in
        self._set_pages(slot, self._pages_for(start + size))
        st["next"] += 1
        if st["next"] < len(st["plan"]):
            return
        # final chunk: its logits are the whole prompt's last-token
        # logits — regular admission from here on
        del self.prefilling[slot]
        self.prefill_forwards += 1
        if self._finish_admit(slot, req, logits):
            self.active[slot] = req
            self.slot_age[slot] = 0
        else:
            self.completed.append(req)       # finished on its first token
            self._free_pages(slot)

    def _fill_slots(self) -> None:
        """Admit work into free slots: restore suspended requests first
        (FIFO — they were admitted before anything still queued), then
        admit queued requests via real prefill.  Under host offload the
        eviction policy runs first, so a demand surge spills cold slots
        before admission finds them all busy.  All device-state seeding
        happens inside `_admit` / `_restore` (steps.admit_slot /
        steps.restore_slot)."""
        # only requests suspended BEFORE this call are restorable: a row
        # evicted just now may still be referenced by the undelivered
        # in-flight segment — restoring it this early would double-count
        # that segment's position advance in the host mirrors (the next
        # fill runs after that segment is consumed, so one-fill deferral
        # is exactly the safety margin needed)
        restorable = len(self.suspended)
        if self.host_tier is not None:
            self._evict_for_demand()
        for s in range(self.batch):
            if self.active[s] is not None or s in self.prefilling:
                continue
            if restorable > 0 and self.suspended:
                restorable -= 1
                req = self.suspended.pop(0)
                if self._restore(s, req):
                    self.active[s] = req
                else:
                    self.completed.append(req)   # died while evicted
            elif self.queue:
                req = self.queue.pop(0)
                if self.prefill_chunk is not None \
                        and len(req.prompt) > self.prefill_chunk:
                    # long prompt: admit in chunks interleaved with the
                    # decode segments (DESIGN.md §9) — the slot is
                    # reserved but joins decode only after its last chunk
                    self._begin_chunked(s, req)
                    continue
                self.active[s] = req
                self.slot_age[s] = 0
                if not self._admit(s, req):
                    self.completed.append(req)
                    self.active[s] = None
                    self._free_pages(s)

    def _dispatch_rows(self, seg_len: int):
        """Slot accounting at dispatch time, where it is still possible:
        a row with NO stop tokens terminates only by budget, so its token
        usage for the next segment is known now — it retires immediately
        and its slot refills while the segment is still in flight (the
        PR-1 pipeline).  A row WITH stop tokens is `(req, None)`: the
        device's alive mask decides, and `_consume_segment` retires it
        one overlapped device_get later.

        Returns (rows, plain): `plain` is True when every dispatched row
        is greedy with no stop set — the segment can take the fast-path
        variant (no sampling epilogue).  The variants interleave freely
        because greedy rows never READ their keys and sampling params are
        fixed at admission (see make_decode_segment's key-state note).

        Speculative mode (DESIGN.md §7) chooses the spec segment for the
        whole batch instead, and a speculative segment's per-row emit
        count is accept-dependent — unknowable at dispatch — so EVERY
        row becomes `(req, None)`: the device's alive mask and budget
        counters are authoritative and `_consume_segment` retires rows
        one overlapped device_get later (`plain` is returned False; the
        caller dispatches the spec variant)."""
        rows: Dict[int, Any] = {}
        # plain segments write KV ring slots UNMASKED — harmless for a
        # dead slot (its junk never outlives the next full prefill) but
        # fatal for a slot mid-chunked-prefill, whose partial prefix must
        # survive the interleaved segments.  The write-masked variant
        # skips dead rows (write_mask=alive), so force it while any
        # admission is between chunks (greedy bits are unchanged — the
        # variants emit identical tokens, asserted by the churn suite).
        plain = not self.prefilling
        for s in range(self.batch):
            req = self.active[s]
            if req is None:
                continue
            self.slot_age[s] += 1       # segments since (re-)admission
            sp = req.sampling or GREEDY
            if self.spec:
                # the `plain` flag still gates the greedy fast-path
                # (here: the plain spec-segment twin); only the
                # dispatch-time retirement of the budget regime is lost
                if not (sp.temperature <= 0 or sp.top_k == 1) \
                        or sp.stop_tokens:
                    plain = False
                # worst-case footprint of the segment about to run:
                # seg_len rounds of k+1 emits, plus up to spec_k junk
                # ring-writes of a verify forward past the final clock;
                # `_consume_segment` trims back to the true clock
                self._set_pages(s, max(
                    int(self.slot_pages[s]),
                    self._pages_for(self.positions[s]
                                    + seg_len * (self.spec_k + 1)
                                    + self.spec_k)))
                rows[s] = (req, None)
                continue
            if not (sp.temperature <= 0 or sp.top_k == 1):
                plain = False
            if sp.stop_tokens:
                plain = False
                # stop-regime rows: emit count is device-decided — charge
                # the full segment span, trimmed back at consume
                self._set_pages(s, max(
                    int(self.slot_pages[s]),
                    self._pages_for(self.positions[s] + seg_len)))
                rows[s] = (req, None)
                continue
            take = int(min(seg_len, self.remaining[s]))
            self.remaining[s] -= take
            # budget-regime rows advance by exactly `take`: charge the
            # pages this segment's ring writes will touch
            self._set_pages(s, max(int(self.slot_pages[s]),
                                   self._pages_for(self.positions[s]
                                                   + take)))
            rows[s] = (req, take)
            if self.remaining[s] <= 0:
                self.completed.append(req)
                self.active[s] = None
                self._free_pages(s)
        return rows, plain

    # -- per-token loop (bulk-synchronous baseline) ------------------------

    def step(self) -> None:
        """One token for every active slot: a seg_len-1 segment through
        the same sampling machinery as the streamed loop, consumed
        synchronously — one dispatch + one host sync per token.  In
        speculative mode this is one draft-and-verify ROUND per dispatch
        (up to spec_k+1 tokens), still consumed synchronously."""
        self._fill_slots()
        self._pump_prefill()       # <= one admission chunk per token step
        self.assert_ledger()
        if all(r is None for r in self.active):
            return
        rows, plain = self._dispatch_rows(1)
        with self._ctx(), sh.use_rules(self.rules), use_offload(self.offload):
            if self.spec:
                fn = self.spec_step_plain_fn if plain else self.spec_step_fn
                seg, emit, alens, self.state, self.cache, \
                    self.draft_cache = fn(
                        self.params, self.draft_params, self.cache,
                        self.draft_cache, self.state)
                self.steps += self.spec_k + 1
                self.wire.charge_merges(self._merges_per_spec_round)
                self._consume_segment(seg, emit, self.state, rows,
                                      alens=alens)
                self.assert_ledger()
                return
            fn = self.step_plain_fn if plain else self.step_fn
            seg, emit, self.state, self.cache = fn(
                self.params, self.cache, self.state)
        self.steps += 1
        self.wire.charge_merges(self._merges_per_step)
        self._consume_segment(seg, emit, self.state, rows)
        self.assert_ledger()

    # -- streamed loop (producer-initiated token stream) -------------------

    def run_stream(self, max_steps: int = 10_000) -> None:
        """Decode in jitted `seg_len`-token segments with double-buffered
        host consumption: segment i+1 is dispatched BEFORE segment i's
        tokens are copied out, so the device_get overlaps device compute
        and the host syncs once per segment (<= 1 sync / seg_len tokens).

        Tokens are delivered to `Request.generated` one segment later,
        together with the per-row emit masks and alive bits that carry
        the device-side termination verdicts (stop tokens / budgets) back
        to the host — see `_dispatch_rows` for which of the two
        accounting regimes each row is under."""
        pending = None     # (segment, emit masks, state, rows, alens)
        while True:
            self._fill_slots()
            nxt_pending = None
            if self.steps < max_steps \
                    and any(r is not None for r in self.active):
                rows, plain = self._dispatch_rows(self.seg_len)
                with self._ctx(), sh.use_rules(self.rules), \
                        use_offload(self.offload):
                    if self.spec:
                        fn = (self.spec_segment_plain_fn if plain
                              else self.spec_segment_fn)
                        seg, emit, alens, self.state, self.cache, \
                            self.draft_cache = fn(
                                self.params, self.draft_params,
                                self.cache, self.draft_cache, self.state)
                        self.steps += self.seg_len * (self.spec_k + 1)
                        self.wire.charge_merges(
                            self.seg_len * self._merges_per_spec_round)
                    else:
                        fn = (self.segment_plain_fn if plain
                              else self.segment_fn)
                        seg, emit, self.state, self.cache = fn(
                            self.params, self.cache, self.state)
                        alens = None
                        self.steps += self.seg_len
                        self.wire.charge_merges(
                            self.seg_len * self._merges_per_step)
                self.segments_dispatched += 1
                nxt_pending = (seg, emit, self.state, rows, alens)
            # the scheduler's interleave point (DESIGN.md §9): at most one
            # admission-prefill chunk per loop tick, dispatched AFTER the
            # decode segment so it queues behind the in-flight streams —
            # their segment cadence and decode_syncs stay untouched
            self._pump_prefill()
            if pending is not None:
                # ONE host sync per segment; overlaps the segment just
                # dispatched above.
                self._consume_segment(*pending[:4], alens=pending[4])
            self.assert_ledger()
            pending = nxt_pending
            if pending is not None:
                continue
            if self.steps >= max_steps:
                return          # step cap: remaining requests stay active
            if not self.queue and not self.suspended \
                    and not self.prefilling \
                    and all(r is None for r in self.active):
                return

    def _consume_segment(self, seg, emit, state, rows,
                         alens=None) -> None:
        """Deliver one segment's tokens and apply the device's termination
        verdicts.  `state` is the SlotState returned BY that segment (a
        later admission's .at[] writes produce new arrays, so this
        snapshot is stable even with a newer segment already in flight).

        Speculative segments (DESIGN.md §7) additionally hand back the
        per-round accept lengths: with per-row round emit counts m and
        accept lengths a, a round proposed spec_k drafts (if the row was
        alive, i.e. m > 0) and emitted min(m, a) of them — accumulated
        into `draft_accepted`/`draft_proposed` for the accept-rate rows
        of benchmarks/decode_stream.py.  The device SlotState's
        cumulative accepted/proposed counters carry each REQUEST's
        lifetime record across segments; they are stamped onto the
        request at retirement (the snapshot is the one the row died in,
        so a later admission's counter reset cannot race it)."""
        # ONE device_get — the sync the decode_syncs counter stands for;
        # the speculative extras ride the same transfer
        fetch = (seg, emit, state.alive, state.remaining, state.positions)
        if alens is not None:
            fetch += (alens, state.accepted, state.proposed)
        got = jax.device_get(fetch)
        arr, em, alive, rem, pos = got[:5]
        if alens is not None:
            al, acc, prop = got[5:]
        self.host_syncs += 1
        self.decode_syncs += 1
        for s, (req, take) in rows.items():
            toks = arr[s][em[s].astype(bool)]
            for t in toks:
                req.generated.append(int(t))
            self.tokens_emitted += len(toks)
            if alens is not None:
                m_r = em[s].reshape(al.shape[1], -1).sum(axis=1)
                self.draft_proposed += int((m_r > 0).sum()) * self.spec_k
                self.draft_accepted += int(np.minimum(m_r, al[s]).sum())
            if take is not None:
                # device budget accounting must agree with the host's
                # dispatch-time prediction for stop-free rows
                assert len(toks) == take, (s, len(toks), take)
            if self.active[s] is req:
                # per-row position clock: advances by exactly one per
                # emitted token, never for frozen rows
                assert pos[s] == self.positions[s] + len(toks), \
                    (s, pos[s], self.positions[s], len(toks))
                self.positions[s] = int(pos[s])
                # trim the dispatch-time worst-case charge back to the
                # pages the clock actually reached (a no-op for budget
                # rows, a release for early-stopped / frozen rows)
                self._set_pages(s, self._pages_for(self.positions[s]))
                if take is None:
                    self.remaining[s] = int(rem[s])
                    if not alive[s]:
                        if alens is not None:
                            req.spec_accepted = int(acc[s])
                            req.spec_proposed = int(prop[s])
                        self.completed.append(req)
                        self.active[s] = None
                        self._free_pages(s)

    def run_until_drained(self, max_steps: int = 10_000) -> None:
        if self.stream:
            self.run_stream(max_steps)
            return
        while (self.queue or self.suspended or self.prefilling
               or any(r is not None for r in self.active)) \
                and self.steps < max_steps:
            self.step()


class _null:
    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="starcoder2_3b")
    ap.add_argument("--smoke", action="store_true",
                    help="serve the arch's tiny test preset instead of "
                         "its published widths (CPU rehearsal)")
    ap.add_argument("--protocol", default="axle", choices=list(PROTOCOLS))
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--stream", action="store_true",
                    help="producer-initiated segment streaming loop")
    ap.add_argument("--seg-len", type=int, default=8)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy (default); > 0 samples per slot")
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0,
                    help="base sampling seed (request i uses seed + i)")
    ap.add_argument("--stop-eos", action="store_true",
                    help="stop each request at the config's eos_token")
    ap.add_argument("--spec", action="store_true",
                    help="speculative draft-and-verify segments "
                         "(DESIGN.md §7)")
    ap.add_argument("--spec-k", type=int, default=3,
                    help="draft tokens proposed per verify round")
    ap.add_argument("--draft", default=None,
                    help="draft arch: 'self[:N]' (truncated-layer "
                         "self-draft) or a registered arch id; defaults "
                         "to the config's draft_arch")
    ap.add_argument("--offload", action="store_true",
                    help="host-tier cache offload: evict cold slots to "
                         "host RAM and restore on demand (DESIGN.md §8)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="host-side prompt-prefix page reuse "
                         "(decoder-only archs)")
    ap.add_argument("--evict-after", type=int, default=1,
                    help="minimum segments a slot decodes before it is "
                         "eviction-eligible (the round-robin quantum)")
    ap.add_argument("--offload-chunks", type=int, default=2,
                    help="chunks per leaf for host<->device page streams")
    ap.add_argument("--page-size", type=int, default=None,
                    help="KV page size in sequence positions (DESIGN.md "
                         "§9); default = the dense kernel's chunk size")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="admit prompts longer than this in chunked "
                         "prefills interleaved with decode segments "
                         "(DESIGN.md §9)")
    ap.add_argument("--quant-weights", default=None,
                    choices=["q8_0", "q4_k"],
                    help="block-quantize the dense projection stacks; "
                         "the fused matmul dequantizes per block in "
                         "VMEM (DESIGN.md §10)")
    ap.add_argument("--quant-kv", default=None, choices=["int8"],
                    help="int8 KV cache with per-(layer,row,head,page) "
                         "scales applied inside the fused decode kernel "
                         "(DESIGN.md §10)")
    ap.add_argument("--mesh", default=None, metavar="DATAxMODEL",
                    help="serve under a DATAxMODEL device mesh (e.g. "
                         "1x2): tensor-parallel heads over 'model', "
                         "batch over 'data' — tokens stay BITWISE the "
                         "single-device stream (DESIGN.md §11).  On CPU "
                         "set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N "
                         "before launch")
    args = ap.parse_args()
    use_compile_cache()

    mesh = None
    if args.mesh is not None:
        from repro.launch.mesh import make_debug_mesh
        n_data, n_model = (int(p) for p in args.mesh.lower().split("x"))
        assert n_data * n_model <= jax.device_count(), \
            (f"mesh {args.mesh} needs {n_data * n_model} devices, have "
             f"{jax.device_count()} — set XLA_FLAGS="
             f"--xla_force_host_platform_device_count={n_data * n_model}")
        mesh = make_debug_mesh(n_data, n_model)

    rng = np.random.default_rng(0)
    server = BatchedServer(args.arch, smoke=args.smoke,
                           batch_slots=args.slots, mesh=mesh,
                           protocol=args.protocol, stream=args.stream,
                           seg_len=args.seg_len, spec=args.spec,
                           spec_k=args.spec_k, draft_arch=args.draft,
                           host_offload=args.offload,
                           prefix_cache=args.prefix_cache,
                           evict_after=args.evict_after,
                           offload_chunks=args.offload_chunks,
                           page_size=args.page_size,
                           prefill_chunk=args.prefill_chunk,
                           quant=steps_lib.QuantConfig(
                               weights=args.quant_weights,
                               kv=args.quant_kv))
    stops = (server.cfg.eos_token,) if args.stop_eos else ()
    sampled = (args.temperature > 0 or args.top_k > 0 or args.top_p < 1.0
               or args.stop_eos)
    if args.temperature <= 0 and (args.top_k > 1 or args.top_p < 1.0):
        # a filter without a temperature would silently decode greedily
        # (temperature 0 marks the row greedy and ignores top-k/top-p)
        print("[serve] --top-k/--top-p given without --temperature: "
              "defaulting temperature to 1.0", file=sys.stderr)
        args.temperature = 1.0
    t0 = time.time()
    first_prompt = None
    for i in range(args.requests):
        plen = int(rng.integers(4, 12))
        embeds = None
        if server.cfg.enc_dec:    # stub audio frontend: random frames
            embeds = rng.standard_normal(
                (server.cfg.enc_len, server.cfg.d_model)).astype(np.float32)
        sampling = SamplingParams(
            temperature=args.temperature, top_k=args.top_k,
            top_p=args.top_p, seed=args.seed + i,
            stop_tokens=stops) if sampled else None
        prompt = rng.integers(1, server.cfg.vocab, plen).astype(np.int32)
        if args.prefix_cache:
            # demo workload for the prefix cache: every 3rd request repeats
            # the first prompt (full hit), every 3rd+1 extends it (partial)
            if first_prompt is None:
                first_prompt = prompt
            elif i % 3 == 1:
                prompt = first_prompt
            elif i % 3 == 2:
                prompt = np.concatenate([first_prompt, prompt[:4]])
        server.submit(Request(i, prompt, args.max_new,
                              embeds=embeds, sampling=sampling))
    server.run_until_drained()
    dt = time.time() - t0
    toks = sum(len(r.generated) for r in server.completed)
    mode = "stream" if args.stream else "per-token"
    spt = server.decode_syncs / max(1, toks)
    spec = ""
    if args.spec:
        rate = server.draft_accepted / max(1, server.draft_proposed)
        spec = (f" spec_k={args.spec_k} accept_rate={rate:.2f} "
                f"tokens/sync={toks / max(1, server.decode_syncs):.2f}")
    offl = ""
    if args.offload:
        offl = (f" evictions={server.evictions} restores={server.restores}"
                f" host_mb={server.host_tier.bytes_evicted / 2**20:.1f}")
    if args.prefix_cache:
        hits = server.prefix_hits_full + server.prefix_hits_partial
        offl += (f" prefix_hits={hits}/{hits + server.prefix_misses}"
                 f" prefill_skipped={server.prefill_tokens_skipped}tok")
    if args.prefill_chunk is not None:
        offl += (f" prefill_chunks={server.prefill_chunks}"
                 f" pages={server.pages_allocated}alloc/"
                 f"{server.pages_freed}freed")
    if mesh is not None:
        offl += (f" mesh={args.mesh}"
                 f" wire_bytes_per_shard={server.wire_bytes_per_shard}")
    dev = jax.devices()[0]
    print(f"[serve] arch={server.cfg.arch_id} protocol={args.protocol} "
          f"mode={mode} sampling={'on' if sampled else 'greedy'} "
          f"requests={len(server.completed)} tokens={toks} "
          f"steps={server.steps} syncs/token={spt:.3f}{spec}{offl} "
          f"({toks / dt:.1f} tok/s incl. compile; platform={dev.platform} "
          f"device_kind={dev.device_kind} devices={jax.device_count()})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
