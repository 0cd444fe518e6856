"""jit-able train / prefill / serve steps shared by the dry-run, the
training driver, and the serving driver."""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.models.config import ArchConfig
from repro.models.registry import get_model
from repro.optim import adamw
from repro.optim import compression

# stop-token slots per serving request (padded with -1); a static width so
# the SlotState pytree never retraces on admission
MAX_STOP_TOKENS = 4


@dataclasses.dataclass(frozen=True)
class QuantConfig:
    """Serving-time quantization selection (DESIGN.md §10).

    weights — block-quantize every dense projection stack of the TARGET
              params into "q8_0" (int8, per-32 symmetric scale) or
              "q4_k" (packed int4, per-32 scale+min); the fused matmul
              dequantizes blocks in VMEM, so the fp weights never
              materialize in HBM.  A self-draft slices the QUANTIZED
              stacks (QTensor rides the truncation `tree.map`), so
              draft and target read the same bytes.
    kv      — "int8" stores the self-attention KV panels as int8 pools
              with one f32 scale per (layer, row, kv-head, physical
              page); decode/verify/prefill write quantized rows and the
              fused decode kernel applies the per-page scale in-kernel.
              Host-tier eviction, prefix reuse, and chunked prefill all
              transport the quantized pages natively (~2x fewer bytes
              per token of KV traffic).

    Either field may be None (fp weights / fp KV); QuantConfig() is the
    all-fp identity."""
    weights: Optional[str] = None   # None | "q8_0" | "q4_k"
    kv: Optional[str] = None        # None | "int8"

    def __post_init__(self):
        assert self.weights in (None, "q8_0", "q4_k"), self.weights
        assert self.kv in (None, "int8"), self.kv


class SlotState(NamedTuple):
    """Device-resident per-slot decode state of the streamed serve loop —
    everything a `seg_len`-token segment needs to run WITHOUT a host
    round trip, including the stochastic-sampling control state that
    arXiv 2309.04011 argues must ride the async submission path alongside
    the data.

    Field-by-field invariants (DESIGN.md §6 for sampling/termination,
    §7 for the speculative counters):

      tokens    — (B, 1) i32: the CURRENT token of each row — the most
                  recently emitted token, whose K/V (or recurrent
                  update) is NOT yet in the cache.  The cache holds
                  exactly the tokens at positions [0, positions[b]);
                  tokens[b] sits AT positions[b] and rides decode
                  attention as the merged extra partial until its own
                  decode step ring-writes it.
      positions — (B,) i32 per-row position clocks: the sequence
                  position of tokens[b] = the number of prompt +
                  generated tokens strictly before it.  Advances by
                  exactly the number of tokens a row emits (one per
                  alive step in plain segments; the variable accepted
                  count m in speculative segments) and NEVER for frozen
                  rows — the continuous-batching invariant every
                  position-dependent computation (RoPE, cache validity,
                  ring-slot writes, sliding windows) hangs off.
      keys      — (B, 2) uint32 per-slot PRNG chains, seeded from the
                  request's SamplingParams.seed at admission (split #0
                  samples the first token from the prefill logits).
                  Split discipline: plain sampled segments split every
                  row's key once per SCAN STEP (consume-on-emit), so
                  token k of a request is always sampled with the k-th
                  split of its seed — bitwise-reproducible across
                  seg_len segmentations, slots, and per-token vs
                  streamed loops.  Speculative segments split once per
                  ROUND (the split fans out into draft-step and verify
                  draws), so stochastic rows are reproducible for a
                  fixed (seed, k, rounds) but only DISTRIBUTION-equal to
                  the plain chain; greedy rows never read their keys,
                  which is why greedy streams stay bitwise-identical
                  across all loop modes and variants.  Keys never
                  round-trip through the host after admission.
      remaining — (B,) i32 token budget left (max_new accounting, device-
                  authoritative; the host's dispatch-time copy is a
                  prediction for stop-free rows in plain segments and
                  purely informational in speculative mode).
      alive     — (B,) bool: row emits this step/round.  Cleared DEVICE-
                  SIDE when an emitted token hits the row's stop set or
                  the budget runs out; a dead row FREEZES — tokens,
                  positions, keys' consumers, and all cached state
                  (write_mask=alive masks KV ring slots, conv windows,
                  SSM states, draft caches) hold still until the host
                  retires the row at a segment boundary.  `alive` is
                  also the write-mask handed to decode_step /
                  decode_verify — one mask, every state store.
      sampling  — per-slot temperature/top_k/top_p/min_p
                  (ops.BatchedSampling).  Fixed at admission: a request
                  cannot flip greedy↔stochastic mid-stream (the variant-
                  interleaving and key-consumption arguments rely on it).
      stop      — (B, MAX_STOP_TOKENS) i32 stop-token ids, -1-padded
                  (-1 never matches an emitted token, which is >= 0).
      accepted  — (B,) i32: cumulative count of DRAFT tokens this
                  request emitted via speculative acceptance (correction
                  and bonus tokens excluded).  Zeroed at admission;
                  stays 0 in non-speculative serving.
      proposed  — (B,) i32: cumulative count of draft tokens proposed
                  for this row (k per alive speculative round).
                  accepted/proposed is the per-request accept rate the
                  benchmark's tokens-per-sync model is built on
                  (DESIGN.md §7).
    """
    tokens: jax.Array             # (B, 1) i32
    positions: jax.Array          # (B,) i32
    keys: jax.Array               # (B, 2) u32
    remaining: jax.Array          # (B,) i32
    alive: jax.Array              # (B,) bool
    sampling: ops.BatchedSampling
    stop: jax.Array               # (B, MAX_STOP_TOKENS) i32
    accepted: jax.Array           # (B,) i32
    proposed: jax.Array           # (B,) i32


def init_slot_state(batch: int) -> SlotState:
    """All-slots-idle state: nothing alive, greedy parameters, no stops."""
    return SlotState(
        tokens=jnp.zeros((batch, 1), jnp.int32),
        positions=jnp.zeros((batch,), jnp.int32),
        keys=jnp.zeros((batch, 2), jnp.uint32),
        remaining=jnp.zeros((batch,), jnp.int32),
        alive=jnp.zeros((batch,), bool),
        sampling=ops.greedy_sampling(batch),
        stop=jnp.full((batch, MAX_STOP_TOKENS), -1, jnp.int32),
        accepted=jnp.zeros((batch,), jnp.int32),
        proposed=jnp.zeros((batch,), jnp.int32))


def admit_slot(state: SlotState, slot: int, *, token: int, position: int,
               key: jax.Array, remaining: int, temperature: float,
               top_k: int, top_p: float, min_p: float,
               stop: jax.Array) -> SlotState:
    """Seed one slot's device state at admission (a handful of token-sized
    .at[] updates — dispatched asynchronously, sequenced after any
    in-flight segment by data dependence on the state arrays)."""
    s = state
    return SlotState(
        tokens=s.tokens.at[slot, 0].set(token),
        positions=s.positions.at[slot].set(position),
        keys=s.keys.at[slot].set(key),
        remaining=s.remaining.at[slot].set(remaining),
        alive=s.alive.at[slot].set(remaining > 0),
        sampling=ops.BatchedSampling(
            temperature=s.sampling.temperature.at[slot].set(temperature),
            top_k=s.sampling.top_k.at[slot].set(top_k),
            top_p=s.sampling.top_p.at[slot].set(top_p),
            min_p=s.sampling.min_p.at[slot].set(min_p)),
        stop=s.stop.at[slot].set(stop),
        accepted=s.accepted.at[slot].set(0),
        proposed=s.proposed.at[slot].set(0))


def save_slot_state(state: SlotState, slot) -> dict:
    """Gather ONE slot's row of every SlotState field for host-tier
    eviction (DESIGN.md §8) — the mid-stream counterpart of the values
    `admit_slot` seeds.  The returned dict of device scalars/rows is
    what `restore_slot` consumes; the PRNG `key` entry is the slot's
    CURRENT chain head, so a restored slot resumes the exact split
    sequence a never-evicted slot would have continued."""
    return {
        "token": state.tokens[slot, 0],
        "position": state.positions[slot],
        "key": state.keys[slot],
        "remaining": state.remaining[slot],
        "alive": state.alive[slot],
        "temperature": state.sampling.temperature[slot],
        "top_k": state.sampling.top_k[slot],
        "top_p": state.sampling.top_p[slot],
        "min_p": state.sampling.min_p[slot],
        "stop": state.stop[slot],
        "accepted": state.accepted[slot],
        "proposed": state.proposed[slot],
    }


def restore_slot(state: SlotState, slot, saved: dict) -> SlotState:
    """Re-seed one slot from a `save_slot_state` snapshot — `admit_slot`'s
    restore twin.  Unlike admission it does NOT reset the spec counters
    or re-derive alive from remaining: every field (position clock, PRNG
    chain head, accepted/proposed) continues exactly where the evicted
    slot left off, which is what makes an evicted-then-restored stream
    bitwise-equal to a never-evicted one."""
    s = state
    return SlotState(
        tokens=s.tokens.at[slot, 0].set(saved["token"]),
        positions=s.positions.at[slot].set(saved["position"]),
        keys=s.keys.at[slot].set(saved["key"]),
        remaining=s.remaining.at[slot].set(saved["remaining"]),
        alive=s.alive.at[slot].set(saved["alive"]),
        sampling=ops.BatchedSampling(
            temperature=s.sampling.temperature.at[slot].set(
                saved["temperature"]),
            top_k=s.sampling.top_k.at[slot].set(saved["top_k"]),
            top_p=s.sampling.top_p.at[slot].set(saved["top_p"]),
            min_p=s.sampling.min_p.at[slot].set(saved["min_p"])),
        stop=s.stop.at[slot].set(saved["stop"]),
        accepted=s.accepted.at[slot].set(saved["accepted"]),
        proposed=s.proposed.at[slot].set(saved["proposed"]))


def make_train_step(cfg: ArchConfig, opt_cfg: adamw.AdamWConfig, *,
                    compress_grads: bool = False):
    """(params, opt_state, comp_state, batch) ->
       (params, opt_state, comp_state, metrics)."""
    model = get_model(cfg)

    def train_step(params, opt_state, comp_state, batch):
        def loss(p):
            return model.loss_fn(cfg, p, batch)

        (loss_val, metrics), grads = jax.value_and_grad(
            loss, has_aux=True)(params)
        if compress_grads:
            grads, comp_state = compression.compress_grads(grads, comp_state)
        params, opt_state, opt_metrics = adamw.apply(
            opt_cfg, params, grads, opt_state)
        metrics = {**metrics, **opt_metrics, "loss": loss_val}
        return params, opt_state, comp_state, metrics

    return train_step


def make_prefill_step(cfg: ArchConfig):
    """(params, batch) -> logits — full-sequence forward (prefill shape)."""
    model = get_model(cfg)

    def prefill_step(params, batch):
        return model.logits_fn(cfg, params, batch)

    return prefill_step


def make_serve_step(cfg: ArchConfig, *, greedy: bool = True):
    """(params, cache, tokens[, positions]) -> (next_tokens, logits, cache)
    — one decode step with KV/SSM caches; this is what `decode_*`/`long_*`
    shapes lower.  `positions` is an optional (B,) per-row position vector
    (continuous batching); omitted, the scalar cache counter applies."""
    model = get_model(cfg)

    def serve_step(params, cache, tokens, positions=None):
        logits, cache = model.decode_step(cfg, params, cache, tokens,
                                          positions=positions)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        return nxt, logits, cache

    return serve_step


def make_decode_segment(cfg: ArchConfig, seg_len: int, *,
                        plain: bool = False):
    """(params, cache, state: SlotState) ->
       (segment (B, seg_len), emitted (B, seg_len) bool, state, cache).

    A jitted multi-token decode segment: `seg_len` decode+sample steps
    rolled into one lax.scan, so the host dispatches (and syncs on) ONE
    device computation per `seg_len` tokens instead of one per token —
    the producer-initiated token stream of the serving loop.  The cache
    threads through the scan carry (donate it at the jit boundary for
    in-place ring-slot updates).

    Everything that used to require host-side greedy accounting now rides
    the SlotState carry device-side (DESIGN.md §6):

      * per-slot PRNG chains split once per step — token k of a request
        is sampled with the k-th split of its seed key, independent of
        seg_len, slot, and what other slots are doing;
      * in-segment termination: a sampled stop token or an exhausted
        budget clears the row's alive bit; from the next step the row is
        FROZEN — token and position stop advancing, `write_mask=alive`
        keeps its cache slots untouched — until the host retires it at a
        segment boundary;
      * `emitted[b, t]` records whether row b produced a real token at
        step t (its alive bit at entry), which is all the host needs to
        deliver tokens and retire rows one overlapped device_get later.

    Greedy rows (temperature 0 / top_k 1) take the argmax path inside
    `ops.sample_tokens`, bitwise-identical to the pre-sampling loop.

    `plain=True` builds the greedy fast-path variant the server selects
    when EVERY active row is greedy with no stop set (the default
    workload): plain argmax, no key splits, no sort/Gumbel epilogue, no
    write-mask gather+selects (dead rows keep rewriting their slot, as
    the pre-sampling loop did — harmless, re-prefill overwrites it).
    The budget/alive/emit accounting is identical and alive rows' tokens
    are bitwise those of the sampled variant, so the two variants
    interleave freely mid-stream as the workload mix changes.  NOTE the
    key-state caveat: the sampled variant splits EVERY row's key each
    step while plain splits none, so a row's key state depends on which
    variant mix ran — safe only because greedy rows never READ their
    keys, and a row's sampling params are fixed at admission (a request
    cannot flip greedy→stochastic mid-stream)."""
    model = get_model(cfg)

    @jax.named_scope("decode_segment")
    def segment(params, cache, state: SlotState):
        def body(carry, _):
            toks, cache, pos, keys, remaining, alive = carry
            logits, cache = model.decode_step(
                cfg, params, cache, toks, positions=pos,
                write_mask=None if plain else alive)
            if plain:
                nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
                hit_stop = jnp.zeros_like(alive)
            else:
                with jax.named_scope("sampling_epilogue"):
                    both = jax.vmap(lambda k: jax.random.split(k, 2))(keys)
                    keys, sub = both[:, 0], both[:, 1]
                    nxt = ops.sample_tokens(logits[:, -1], state.sampling,
                                            sub, vocab=cfg.vocab)
                    nxt = jnp.where(alive, nxt, toks[:, 0])  # dead rows freeze
                    hit_stop = jnp.any(nxt[:, None] == state.stop, axis=-1)
            emitted = alive
            remaining = remaining - emitted.astype(jnp.int32)
            alive = alive & (remaining > 0) & ~hit_stop
            pos = pos + emitted.astype(jnp.int32)
            return (nxt[:, None], cache, pos, keys, remaining, alive), \
                (nxt, emitted)

        carry = (state.tokens, cache, state.positions, state.keys,
                 state.remaining, state.alive)
        (toks, cache, pos, keys, remaining, alive), (seq, emit) = \
            jax.lax.scan(body, carry, length=seg_len)
        state = state._replace(tokens=toks, positions=pos, keys=keys,
                               remaining=remaining, alive=alive)
        return seq.T, emit.T, state, cache    # seq.T/emit.T: (B, seg_len)

    return segment


def self_draft_config(cfg: ArchConfig, n_blocks: int) -> ArchConfig:
    """The truncated-layer self-draft architecture: the target's first
    `n_blocks` pattern blocks as a standalone model (DESIGN.md §7).  The
    draft shares the target's embedding/unembedding and layer geometry,
    so its caches and decode steps come from the same model functions."""
    import dataclasses
    assert 1 <= n_blocks <= cfg.n_blocks, (n_blocks, cfg.n_blocks)
    return dataclasses.replace(
        cfg, arch_id=f"{cfg.arch_id}_draft{n_blocks}",
        n_layers=n_blocks * len(cfg.block_pattern))


def self_draft_params(cfg: ArchConfig, params, n_blocks: int):
    """Slice the target's stacked block parameters down to the first
    `n_blocks` blocks — a truncated-layer self-draft needs NO parameters
    of its own (embed / final norms / encoder are shared by reference;
    only the per-block stacks are sliced).  The slices are views of the
    same initialization, so a full-depth self-draft (n_blocks ==
    cfg.n_blocks) is bitwise the target — the accept-rate-1 edge case
    the tests and benchmarks pin down."""
    sliced = dict(params)
    for key in ("blocks", "dec_blocks", "cross"):
        if key in params:
            sliced[key] = jax.tree_util.tree_map(
                lambda a: a[:n_blocks], params[key])
    return sliced


def make_spec_decode_segment(cfg: ArchConfig, draft_cfg: ArchConfig,
                             rounds: int, k: int, *, plain: bool = False):
    """(params, draft_params, cache, draft_cache, state: SlotState) ->
       (segment (B, rounds*(k+1)), emitted (B, rounds*(k+1)) bool,
        accept_lens (B, rounds) i32, state, cache, draft_cache).

    The speculative twin of `make_decode_segment` (DESIGN.md §7): each
    of `rounds` scan iterations is one draft-and-verify round —

      1. DRAFT: k sequential draft decode steps propose g_0..g_{k-1},
         plus one sample-free absorb step that folds g_{k-1} into the
         draft's own state (so a fully-accepted round leaves the draft
         cache consistent).  Proposals are sampled through
         `ops.sample_tokens` with the row's OWN sampling parameters, so
         the proposal distribution is exactly the p_j that
         `ops.verify_tokens` corrects against.
      2. VERIFY: ONE multi-position `decode_verify` forward of the
         target over [current, g_0..g_{k-1}] — k+1 positions whose
         logits are each bitwise what sequential decoding would have
         produced (transformer._verify_attn).
      3. ACCEPT: `ops.verify_tokens` returns the accepted prefix length
         and the correction/bonus token; the round emits m = accept+1
         tokens, clipped by the row's budget and truncated at the first
         stop-set hit (both device-side, as in §6).
      4. ADVANCE + ROLLBACK: positions advance by the PER-ROW m
         (variable advance is free under the per-row position clocks);
         attention junk past the new clock is invisible by construction
         (rollback-as-masked-write: rejected rows were written but sit
         at slots >= the clock), and recurrent (conv, ssm) state — which
         has no clock to hide behind — is rolled back by GATHERING
         snapshot m-1 from the per-step states both forwards emitted.

    Tokens-per-host-sync: a plain segment emits seg_len tokens per
    dispatch; a speculative segment emits between `rounds` (all drafts
    rejected) and `rounds·(k+1)` (all accepted) — the accept-rate →
    tokens/sync model DESIGN.md §7 derives and
    benchmarks/decode_stream.py's `stream.spec` rows measure.

    RNG: one key split per round per row (see SlotState.keys); greedy
    rows consume nothing and emit the target argmax stream bitwise, for
    ANY draft.

    `plain=True` builds the greedy fast-path twin (the §6 `plain`
    pattern, speculated): draft proposals are raw argmax, verification
    is prefix-match-vs-argmax with no filtered-distribution math, no
    Gumbel draws and no key splits — picked by the server whenever
    every active row is greedy with no stop set (the default workload),
    bitwise-identical tokens and accept lengths to the sampled variant
    on such batches.  The PR-3 key-state caveat carries over verbatim:
    the sampled variant splits every row's key once per round while
    plain splits none, safe only because greedy rows never READ their
    keys and sampling params are fixed at admission."""
    model = get_model(cfg)
    draft_model = get_model(draft_cfg)
    assert k >= 1, k
    t = k + 1

    @jax.named_scope("decode_segment")
    def segment(params, draft_params, cache, draft_cache,
                state: SlotState):
        b = state.positions.shape[0]
        arange_t = jnp.arange(t, dtype=jnp.int32)
        barange = jnp.arange(b)

        def round_body(carry, _):
            (toks, cache, dcache, pos, keys, remaining, alive,
             accepted, proposed) = carry
            if plain:
                draft_keys = verify_keys = None
            else:
                both = jax.vmap(lambda kk: jax.random.split(kk, 2))(keys)
                keys, round_keys = both[:, 0], both[:, 1]
                sub = jax.vmap(
                    lambda kk: jax.random.split(kk, 2))(round_keys)
                draft_keys, verify_keys = sub[:, 0], sub[:, 1]

            # ---- 1. draft: k proposal steps + one sample-free absorb
            def draft_body(dc, j):
                dcache_j, dtoks = dc
                lg, dcache_j = draft_model.decode_step(
                    draft_cfg, draft_params, dcache_j, dtoks,
                    positions=pos + j, write_mask=alive)
                if plain:
                    nxt = jnp.argmax(lg[:, -1], axis=-1).astype(jnp.int32)
                else:
                    dkj = jax.vmap(
                        lambda kk: jax.random.fold_in(kk, j))(draft_keys)
                    nxt = ops.sample_tokens(lg[:, -1], state.sampling,
                                            dkj, vocab=cfg.vocab)
                nxt = jnp.where(alive, nxt, dtoks[:, 0])
                snap = {key: dcache_j[key] for key in dcache_j
                        if key.startswith(("conv", "ssm"))}
                return (dcache_j, nxt[:, None]), \
                    (dtoks[:, 0], lg[:, -1], snap)

            with jax.named_scope("spec_draft"):
                (dcache, last), (inputs, dlogits, dsnaps) = jax.lax.scan(
                    draft_body, (dcache, toks), jnp.arange(k))
                # inputs (k, B): I_0 = current token, I_j = g_{j-1};
                # dlogits[j] = p_j, the proposal distribution of g_j.
                # The absorb step folds the final proposal g_{k-1} into
                # the draft's own state (so a fully-accepted round leaves
                # the draft cache consistent) — its logits feed nothing,
                # so it skips the sampling epilogue entirely.
                _, dcache = draft_model.decode_step(
                    draft_cfg, draft_params, dcache, last,
                    positions=pos + k, write_mask=alive)
                absorb = {key: dcache[key][None] for key in dcache
                          if key.startswith(("conv", "ssm"))}
                dsnaps = {key: jnp.concatenate([dsnaps[key], absorb[key]])
                          for key in dsnaps}                  # (T,L,B,…)

            # ---- 2. verify: one batched multi-position target forward
            with jax.named_scope("spec_verify"):
                ver_tokens = jnp.concatenate([inputs.T, last],
                                             axis=1)          # (B,T)
                tlogits, cache, tsnaps = model.decode_verify(
                    cfg, params, cache, ver_tokens, pos, write_mask=alive)
                if plain:
                    # prefix-match-vs-argmax: bitwise the greedy rows of
                    # ops.verify_tokens, with none of the filtered-
                    # distribution or Gumbel machinery
                    out = jnp.argmax(tlogits.astype(jnp.float32),
                                     axis=-1).astype(jnp.int32)   # (B,T)
                    match = (ver_tokens[:, 1:]
                             == out[:, :k]).astype(jnp.int32)
                    alen = jnp.sum(jnp.cumprod(match, axis=-1), axis=-1)
                else:
                    out, alen = ops.verify_tokens(
                        tlogits, dlogits.transpose(1, 0, 2),
                        ver_tokens[:, 1:], state.sampling, verify_keys,
                        vocab=cfg.vocab)

            # ---- 3. emit count: budget cap + first stop-set hit
            cand = jnp.minimum(alen + 1, remaining)
            if plain:       # plain requires empty stop sets at dispatch
                fh = jnp.full((b,), t, jnp.int32)
            else:
                hits = jnp.any(out[..., None] == state.stop[:, None, :],
                               axis=-1)
                fh = jnp.where(jnp.any(hits, axis=-1),
                               jnp.argmax(hits, axis=-1), t)
            m = jnp.where(alive, jnp.minimum(cand, fh + 1), 0)
            emitted = arange_t[None, :] < m[:, None]          # (B, T)

            # ---- 4. per-row variable advance
            sel = jnp.maximum(m - 1, 0)
            new_tok = jnp.take_along_axis(out, sel[:, None], axis=1)
            new_toks = jnp.where(alive[:, None], new_tok, toks)
            pos = pos + m
            remaining = remaining - m
            stop_hit = (fh < cand) & alive
            accepted = accepted + jnp.minimum(m, alen)
            proposed = proposed + jnp.where(alive, k, 0)
            alive_out = alive & (remaining > 0) & ~stop_hit
            alens_out = jnp.where(alive, alen, 0)

            # ---- recurrent rollback: gather snapshot m-1 per row.
            # snapshot j = state after absorbing inputs I_0..I_j, and the
            # new clock demands exactly I_0..I_{m-1} absorbed.  Rows dead
            # at round ENTRY keep their old state (freeze).
            cache = dict(cache)
            for key, snap in tsnaps.items():                  # (L,B,T,…)
                rolled = snap[:, barange, sel]                # (L,B,…)
                keep = alive.reshape((1, b) + (1,) * (rolled.ndim - 2))
                cache[key] = jnp.where(
                    keep, rolled.astype(cache[key].dtype), cache[key])
            dcache = dict(dcache)
            for key, snap in dsnaps.items():                  # (T,L,B,…)
                rolled = jnp.moveaxis(snap[sel, :, barange], 0, 1)
                keep = alive.reshape((1, b) + (1,) * (rolled.ndim - 2))
                dcache[key] = jnp.where(
                    keep, rolled.astype(dcache[key].dtype), dcache[key])

            carry = (new_toks, cache, dcache, pos, keys, remaining,
                     alive_out, accepted, proposed)
            return carry, (out, emitted, alens_out)

        carry = (state.tokens, cache, draft_cache, state.positions,
                 state.keys, state.remaining, state.alive,
                 state.accepted, state.proposed)
        (toks, cache, draft_cache, pos, keys, remaining, alive,
         accepted, proposed), (outs, emits, alens) = jax.lax.scan(
            round_body, carry, length=rounds)
        state = state._replace(tokens=toks, positions=pos, keys=keys,
                               remaining=remaining, alive=alive,
                               accepted=accepted, proposed=proposed)
        seq = outs.transpose(1, 0, 2).reshape(b, rounds * t)
        emit = emits.transpose(1, 0, 2).reshape(b, rounds * t)
        return seq, emit, alens.T, state, cache, draft_cache

    return segment


def make_prefill_into_cache(cfg: ArchConfig, *, from_enc_out: bool = False):
    """Real prompt prefill into one continuous-batching slot, for EVERY
    registered architecture (attention, SSM/hybrid, encoder-decoder).

    Decoder-only: (params, cache, prompt (P,), row, length) ->
    (last_logits (V,), cache) — per-layer K/V and/or (conv, ssm) state
    capture; see transformer.prefill_into_cache.

    Encoder-decoder: (params, cache, prompt (P,), row, length,
    enc_embeds (1, enc_len, D)) -> (last_logits (V,), cache) — runs the
    encoder on the request's frames, writes its per-layer cross-KV into
    the slot row, and prefills the decoder self-attention cache; see
    encdec.prefill_into_cache.  With `from_enc_out=True` the returned fn
    takes a precomputed encoder output `enc_out (1, enc_len, D)` in
    place of `enc_embeds`, so target and speculative-draft admission
    share ONE encoder pass (the draft shares encoder params by
    reference — same input, bitwise-same enc_out)."""
    if cfg.enc_dec:
        from repro.models import encdec

        if from_enc_out:
            @jax.named_scope("prefill")
            def prefill_ed_cached(params, cache, prompt, row, length,
                                  enc_out):
                return encdec.prefill_into_cache(cfg, params, cache, prompt,
                                                 row, length, None,
                                                 enc_out=enc_out)

            return prefill_ed_cached

        @jax.named_scope("prefill")
        def prefill_ed(params, cache, prompt, row, length, enc_embeds):
            return encdec.prefill_into_cache(cfg, params, cache, prompt,
                                             row, length, enc_embeds)

        return prefill_ed

    from repro.models import transformer

    @jax.named_scope("prefill")
    def prefill(params, cache, prompt, row, length):
        return transformer.prefill_into_cache(cfg, params, cache, prompt,
                                              row, length)

    return prefill


def make_resume_prefill(cfg: ArchConfig):
    """Suffix prefill from restored prefix-cache pages (DESIGN.md §8):
    (params, cache, suffix (Ps,), row, length, start) ->
    (last_logits (V,), cache).  Row `row` must already hold the restored
    prefix pages (KV rows [0, start) + post-prefix recurrent state) —
    see transformer.resume_prefill_into_cache.  Returns None for enc-dec
    archs, where prompts are keyed on audio frames and prefix reuse is
    undefined."""
    model = get_model(cfg)
    if model.resume_prefill is None:
        return None

    @jax.named_scope("prefill")
    def resume(params, cache, suffix, row, length, start):
        return model.resume_prefill(cfg, params, cache, suffix, row,
                                    length, start)

    return resume


class ChunkedPrefill(NamedTuple):
    """The two jittable halves of chunked admission prefill plus its
    chunk planner (DESIGN.md §9): `first` runs the opening chunk through
    the ordinary one-shot prefill (length = the chunk's true length),
    `resume` continues from the row's own freshly-written state exactly
    as a prefix-cache partial hit would (two-partial attention merge +
    SSD/conv state resume — PR 5 machinery, new caller), and `plan`
    splits a prompt into the (start, size) chunk schedule."""
    first: object      # (params, cache, chunk (C,), row, length)
    resume: object     # (params, cache, chunk (C,), row, length, start)
    plan: object       # (plen, chunk_size) -> [(start, size), ...]


def make_chunked_prefill(cfg: ArchConfig):
    """Chunk-resumable prompt prefill for the interleaved admission
    scheduler (`BatchedServer(prefill_chunk=...)`): each chunk is one
    bounded-latency jitted dispatch, so a 10k-token prompt admits as a
    sequence of small forwards slotted BETWEEN decode segments instead
    of one monolithic prefill that stalls every in-flight stream.

    Chunk c covers prompt tokens [c*C, c*C + size); `first` handles
    c = 0, `resume` every later chunk with start = c*C — by then the
    row's cache already holds KV rows [0, start) and the post-prefix
    recurrent state from the previous chunks, which is precisely the
    restored-prefix precondition of `resume_prefill_into_cache`.  The
    final chunk's logits are the whole prompt's last-token logits (its
    `length` argument is the TRUE total prompt length).  Token-equal to
    one-shot prefill, bitwise for pure-SSM rows (the PR 5 resume
    property, asserted in tests/test_paged_cache.py).

    Returns None for enc-dec archs (prompts keyed on audio frames;
    resume is undefined there — admission stays one-shot)."""
    model = get_model(cfg)
    if model.resume_prefill is None:
        return None
    first = make_prefill_into_cache(cfg)
    resume = make_resume_prefill(cfg)

    def plan(plen: int, chunk_size: int):
        assert chunk_size >= 1
        return [(s, min(chunk_size, plen - s))
                for s in range(0, plen, chunk_size)]

    return ChunkedPrefill(first=first, resume=resume, plan=plan)


def run_chunked_prefill(cp: ChunkedPrefill, params, cache, prompt,
                        row, chunk_size: int):
    """Drive a whole prompt through `cp` chunk-by-chunk (the test/bench
    harness path; the server interleaves the same calls with decode
    segments instead of looping).  prompt: (P,) int array at its TRUE
    length.  Returns (last-token logits (V,), cache)."""
    prompt = jnp.asarray(prompt, jnp.int32)
    plen = int(prompt.shape[0])
    logits = None
    for start, size in cp.plan(plen, chunk_size):
        padded = jnp.zeros((chunk_size,), jnp.int32)
        padded = padded.at[:size].set(
            jax.lax.dynamic_slice(prompt, (start,), (size,)))
        if start == 0:
            logits, cache = cp.first(params, cache, padded, row, size)
        else:
            logits, cache = cp.resume(params, cache, padded, row,
                                      start + size, start)
    return logits, cache


def make_slot_page_fns(cfg: ArchConfig):
    """(extract, insert) for per-slot host-tier cache pages (§8):
    extract(cache, row[, upto]) -> {leaf: page}, insert(cache, pages,
    row) -> cache — thin closures over the registry's per-arch
    extract_slot/insert_slot covering every leaf kind (KV, conv tail,
    SSD state, enc-dec cross-KV + enc_pos)."""
    model = get_model(cfg)

    @jax.named_scope("page_extract")
    def extract(cache, row, upto=None):
        return model.extract_slot(cfg, cache, row, upto)

    @jax.named_scope("page_insert")
    def insert(cache, pages, row):
        return model.insert_slot(cfg, cache, pages, row)

    return extract, insert
