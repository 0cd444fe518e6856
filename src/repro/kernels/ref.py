"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the numerical ground truth the kernels are validated
against (interpret=True on CPU, real lowering on TPU).  They are also the
fallback implementation `ops.py` dispatches to on non-TPU backends.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


# --------------------------------------------------------------------------
# Flash attention (the paper's LLM-inference offload target, Table I)
# --------------------------------------------------------------------------

def mha_reference(q: jax.Array, k: jax.Array, v: jax.Array,
                  length: Optional[jax.Array] = None, *,
                  causal: bool = True, window: int = 0,
                  scale: Optional[float] = None) -> jax.Array:
    """Multi-head attention with GQA.  q: (B,S,H,hd); k,v: (B,S,KH,hd).
    window > 0 => sliding-window causal attention.  `length` (optional
    int32 scalar): keys at or past it are padding, seen by no query
    below it (under causal masking no such row saw them anyway); rows at
    or past it are padding too and keep their unmasked keys, so they
    stay finite.  Returns (B,S,H,hd)."""
    b, s, h, hd = q.shape
    kh = k.shape[2]
    assert h % kh == 0
    group = h // kh
    scale = scale if scale is not None else hd ** -0.5
    qf = q.astype(jnp.float32) * scale
    kf = jnp.repeat(k.astype(jnp.float32), group, axis=2)
    vf = jnp.repeat(v.astype(jnp.float32), group, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", qf, kf)
    qpos = jnp.arange(s)[:, None]
    kpos = jnp.arange(s)[None, :]
    mask = jnp.ones((s, s), bool)
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    if length is not None:
        mask &= (kpos < length) | (qpos >= length)
    logits = jnp.where(mask[None, None], logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, vf)
    return out.astype(q.dtype)


def decode_partial_reference(q: jax.Array, k: jax.Array, v: jax.Array,
                             valid: jax.Array
                             ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Partial-softmax decode attention over one KV chunk.

    q: (B,1,H,hd); k,v: (B,KH,C,hd) — flash-decoding cache layout;
    valid: (B,C) bool.
    Returns (acc (B,H,hd), m (B,H), l (B,H)) — the streamable statistics
    merged across chunks by the back-streaming protocol."""
    b, _, h, hd = q.shape
    kh = k.shape[1]
    group = h // kh
    scale = hd ** -0.5
    qf = q[:, 0].astype(jnp.float32) * scale          # (B,H,hd)
    kf = jnp.repeat(k.astype(jnp.float32), group, axis=1)
    vf = jnp.repeat(v.astype(jnp.float32), group, axis=1)
    kf = kf.transpose(0, 2, 1, 3)                      # (B,C,H,hd)
    vf = vf.transpose(0, 2, 1, 3)
    logits = jnp.einsum("bhd,bchd->bhc", qf, kf)
    logits = jnp.where(valid[:, None, :], logits, -jnp.inf)
    m = jnp.max(logits, axis=-1)                       # (B,H)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(logits - m_safe[..., None])
    p = jnp.where(valid[:, None, :], p, 0.0)
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bhc,bchd->bhd", p, vf)
    m = jnp.where(jnp.isfinite(m), m, -jnp.inf)
    return acc, m, l


def gather_kv_pages(kv: jax.Array, pages: jax.Array,
                    page_size: int) -> jax.Array:
    """Gather a paged KV panel into LOGICAL page order.

    kv: (B, KH, S_phys, hd) physical storage whose seq axis is a pool of
    `S_phys // page_size` pages; pages: (B, n_log) int32 page table
    mapping each row's logical page j to a physical page id.  Returns
    (B, KH, n_log * page_size, hd): the dense logical view.  This is the
    paged oracle's entire trick — once gathered, the dense reference (and
    the dense fused kernel, which reduces chunks in logical j order)
    computes bit-for-bit the same result, so ANY physical placement is
    bitwise-equivalent to the dense path (DESIGN.md §9)."""
    b, kh, s_phys, hd = kv.shape
    assert s_phys % page_size == 0, (s_phys, page_size)
    n_log = pages.shape[1]
    kvr = kv.reshape(b, kh, s_phys // page_size, page_size, hd)
    idx = pages.astype(jnp.int32)[:, None, :, None, None]
    out = jnp.take_along_axis(kvr, jnp.broadcast_to(
        idx, (b, kh, n_log, 1, 1)), axis=2)
    return out.reshape(b, kh, n_log * page_size, hd)


def merge_fused_partial_pair(acc: jax.Array, m: jax.Array, l: jax.Array,
                             acc_e: jax.Array, m_e: jax.Array,
                             l_e: jax.Array
                             ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The fused kernel's two-way partial-softmax merge epilogue.

    acc: (B,H,hd); m, l: (B,H) — merged with a second partial of the same
    shapes.  Every per-head statistic combines independently of every
    other head, which is what makes head-group sharding of the decode
    bitwise-exact: a shard that never saw head h contributes exp(-inf)=0
    there, so merging its partials degenerates to selecting the owning
    shard's values verbatim (DESIGN.md §11)."""
    mm = jnp.maximum(m, m_e)
    mm_safe = jnp.where(jnp.isfinite(mm), mm, 0.0)
    a1 = jnp.where(jnp.isfinite(m), jnp.exp(m - mm_safe), 0.0)
    a2 = jnp.where(jnp.isfinite(m_e), jnp.exp(m_e - mm_safe), 0.0)
    acc = acc * a1[..., None] + acc_e.astype(jnp.float32) * a2[..., None]
    l = l * a1 + l_e * a2
    return acc, jnp.where(jnp.isfinite(mm), mm, -jnp.inf), l


def normalize_fused_partial(acc: jax.Array, l: jax.Array,
                            dtype) -> jax.Array:
    """Final softmax normalization of merged decode partials: acc
    (B,H,hd), l (B,H) -> (B,1,H,hd) in `dtype`.  Split out of
    `decode_fused_reference` so the mesh-sharded decode can run it once
    AFTER all-gathering head-group partials (DESIGN.md §11)."""
    out = acc / jnp.maximum(l, 1e-20)[..., None]
    return out[:, None].astype(dtype)


def decode_fused_partial_reference(
        q: jax.Array, k: jax.Array, v: jax.Array, pos: jax.Array,
        extra: Optional[Tuple[jax.Array, jax.Array, jax.Array]] = None,
        *, window: int = 0, pages: Optional[jax.Array] = None,
        page_size: int = 0,
        kv_scales: Optional[Tuple[jax.Array, jax.Array]] = None
        ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """`decode_fused_reference` minus the final normalization: returns
    the raw merged statistics (acc (B,H,hd), m (B,H), l (B,H)).

    This is the per-shard producer of the mesh-sharded decode: each shard
    computes the fused partial over ITS head group's full cache panel and
    the partials are concatenated (all_gather over the head axis) before
    one global `normalize_fused_partial` (DESIGN.md §11).  Accepts the
    same dequant / paged-gather / sliding-window / extra-merge surface as
    the fused oracle, and IS its implementation — so the single-device
    output and any head-group-sharded recomposition agree bitwise."""
    if kv_scales is not None:
        k = dequantize_kv_pages(k, kv_scales[0])
        v = dequantize_kv_pages(v, kv_scales[1])
    if pages is not None:
        assert page_size > 0, "page_size required with pages"
        k = gather_kv_pages(k, pages, page_size)
        v = gather_kv_pages(v, pages, page_size)
    b = q.shape[0]
    s = k.shape[2]
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    slots = jnp.arange(s)
    valid = slots[None, :] <= pos_b[:, None]
    if window > 0:
        valid &= slots[None, :] > (pos_b - window)[:, None]
    acc, m, l = decode_partial_reference(q, k, v, valid)
    if extra is not None:
        acc, m, l = merge_fused_partial_pair(acc, m, l, *extra)
    return acc, m, l


def decode_fused_reference(q: jax.Array, k: jax.Array, v: jax.Array,
                           pos: jax.Array,
                           extra: Optional[Tuple[jax.Array, jax.Array,
                                                 jax.Array]] = None,
                           *, window: int = 0,
                           pages: Optional[jax.Array] = None,
                           page_size: int = 0,
                           kv_scales: Optional[Tuple[jax.Array, jax.Array]]
                           = None) -> jax.Array:
    """Oracle for the fused one-shot flash-decode kernel.

    q: (B,1,H,hd); k,v: (B,KH,S,hd); pos: (B,) int32 (or scalar,
    broadcast) — per-row last valid cache slot; slots `pos-window < slot
    <= pos` are attended (window=0 => no lower bound).  `extra` is an
    optional (acc (B,H,hd), m (B,H), l (B,H)) partial merged before
    normalization.  `pages`/`page_size`: optional (B, n_log) int32 page
    table — k/v are then PHYSICAL pools gathered to logical order first
    (`gather_kv_pages`), and `pos`/`window` keep their logical meaning.
    `kv_scales`: optional (k_scales, v_scales), each (B, KH, n_phys_pages)
    f32 — k/v are then int8 pools dequantized per PHYSICAL page slab
    (`dequantize_kv_pages`) before anything else, so the paged gather and
    the dense math see exactly the values the fused kernel reconstructs
    in VMEM (DESIGN.md §10).  Returns (B,1,H,hd) in q.dtype."""
    acc, _, l = decode_fused_partial_reference(
        q, k, v, pos, extra, window=window, pages=pages,
        page_size=page_size, kv_scales=kv_scales)
    return normalize_fused_partial(acc, l, q.dtype)


# --------------------------------------------------------------------------
# Per-slot stochastic sampling (the serve loop's consumer-side task)
# --------------------------------------------------------------------------

def sample_tokens_reference(logits: jax.Array, temperature: jax.Array,
                            top_k: jax.Array, top_p: jax.Array,
                            min_p: jax.Array, keys: jax.Array,
                            vocab: int = 0) -> jax.Array:
    """Vectorized-over-slots stochastic token selection — the oracle for
    `ops.sample_tokens` and the single definition of its semantics.

    logits: (B, V); temperature/top_p/min_p: (B,) f32; top_k: (B,) i32;
    keys: (B, 2) uint32 — one independent PRNG key per slot, so one row's
    randomness never depends on another row's key (per-slot independence,
    the continuous-batching requirement).  `vocab`: the TRUE vocabulary
    width when V is the Megatron-padded vocab (0 = no bound) — stochastic
    rows never sample a pad id (ids >= vocab are -inf'd BEFORE the
    softmax, so pad rows carry no probability mass into the top-p
    cumulative either).  Returns (B,) int32.

    Per-row semantics, composing the standard filters:

      * ``temperature <= 0`` or ``top_k == 1`` — greedy: plain
        ``argmax(logits)``, bitwise-identical to the historical greedy
        serve loop (no RNG consumed from the result; the key is unused;
        the vocab bound is NOT applied — greedy compatibility is exact).
      * ``top_k > 0``   — keep only the k highest-scoring tokens.
      * ``top_p < 1``   — nucleus: keep the SMALLEST descending-sorted
        prefix whose probability mass reaches ``top_p`` (a token is kept
        iff the mass strictly before it is < top_p; the top-1 token is
        always kept).
      * ``min_p > 0``   — keep tokens whose probability is at least
        ``min_p`` times the maximum token probability.

    Survivors are sampled via the Gumbel-argmax trick on the
    temperature-scaled logits: argmax(logits/T + G), G ~ Gumbel(0, 1)
    drawn per (row, token) from the row's key.  The draw happens in
    descending-sorted space (one sort total; the winner's RANK maps
    back through the sort permutation) — same distribution, and for a
    fixed key the result is bitwise-deterministic — the property the
    streamed serve loop relies on for seg_len-invariant replay."""
    b, v = logits.shape
    lf = logits.astype(jnp.float32)
    temperature = jnp.asarray(temperature, jnp.float32).reshape(b)
    top_k = jnp.asarray(top_k, jnp.int32).reshape(b)
    top_p = jnp.asarray(top_p, jnp.float32).reshape(b)
    min_p = jnp.asarray(min_p, jnp.float32).reshape(b)

    greedy = (temperature <= 0.0) | (top_k == 1)
    scaled = _scaled_bounded_logits(lf, temperature, vocab)
    order, sorted_logits, keep = _sorted_keep(scaled, top_k, top_p, min_p)
    filtered = jnp.where(keep, sorted_logits, -jnp.inf)

    gumbel = jax.vmap(
        lambda k: jax.random.gumbel(k, (v,), jnp.float32))(keys)
    rank = jnp.argmax(filtered + gumbel, axis=-1)             # winning RANK
    sampled = jnp.take_along_axis(order, rank[:, None], axis=-1)[:, 0]
    return jnp.where(greedy, jnp.argmax(lf, axis=-1),
                     sampled).astype(jnp.int32)


def _scaled_bounded_logits(lf: jax.Array, temperature: jax.Array,
                           vocab: int) -> jax.Array:
    """Temperature scaling + Megatron-pad masking (ids >= vocab -inf'd
    BEFORE any softmax, so pad rows carry no probability mass)."""
    v = lf.shape[-1]
    scaled = lf / jnp.maximum(temperature, 1e-6)[:, None]
    if vocab and vocab < v:
        scaled = jnp.where(jnp.arange(v)[None, :] < vocab, scaled, -jnp.inf)
    return scaled


# Rank width of the partial-sort sampling fast path (`sample_tokens_capped`).
# The reference's head-cumsum below is split at this rank so the fast path's
# keep mask is BITWISE the reference's over ranks [0, SAMPLE_HEAD).
SAMPLE_HEAD = 64
# Conservative margin on the nucleus-closure test: the fast path only
# engages when the head's cumulative mass clears top_p by this much, so
# float divergence between the head cumsum and the full-vocab cumsum can
# never flip a tail rank's keep bit relative to the reference.
_CLOSURE_EPS = 1e-5


def _sorted_keep(scaled: jax.Array, top_k: jax.Array, top_p: jax.Array,
                 min_p: jax.Array
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The top_k/top_p/min_p keep mask, computed in descending-sorted
    space (one stable sort keyed on -scaled — ties broken by token id,
    deterministically, exactly as `argsort(-scaled)` breaks them).
    Shared by sampling (`sample_tokens_reference`, which draws directly
    in sorted space) and verification (`filtered_log_probs`, which
    scatters the mask back to token space).  Returns (order (B,V) rank →
    token id, sorted_logits (B,V), keep (B,V) over ranks).

    Two structural choices exist so the `sample_tokens_capped` partial-
    sort fast path can be bitwise-identical over the head ranks:
    probabilities are softmaxed in TOKEN order and carried into rank
    order by the sort itself, as a value operand beside the logits and
    the token ids (a sort permutes bits and negation is exact, so no
    (B, V) gather is needed; the fast path computes the same token-order
    softmax without sorting), and the cumulative nucleus mass over ranks
    [0, SAMPLE_HEAD) comes from a cumsum of exactly that head slice (a
    full-vocab cumsum may round differently)."""
    b, v = scaled.shape
    probs_tok = jax.nn.softmax(scaled, axis=-1)               # token order
    iota = jax.lax.broadcasted_iota(jnp.int32, (b, v), 1)
    neg_sorted, probs, order = jax.lax.sort(                  # rank order
        (-scaled, probs_tok, iota), dimension=1, num_keys=1, is_stable=True)
    sorted_logits = -neg_sorted
    ranks = jnp.arange(v)[None, :]
    keep = jnp.ones((b, v), bool)
    keep &= jnp.where(top_k[:, None] > 0, ranks < top_k[:, None], True)
    head = min(SAMPLE_HEAD, v)
    cum_head = jnp.cumsum(probs[:, :head], axis=-1)           # head-only bits
    if v > head:
        cum_tail = jnp.cumsum(probs, axis=-1)[:, head:]
        cum = jnp.concatenate([cum_head, cum_tail], axis=-1)
    else:
        cum = cum_head
    cum_before = cum - probs                                  # mass before i
    keep &= (cum_before < top_p[:, None]) | (ranks == 0)
    keep &= probs >= min_p[:, None] * probs[:, :1]
    return order, sorted_logits, keep


def sample_tokens_capped(logits: jax.Array, temperature: jax.Array,
                         top_k: jax.Array, top_p: jax.Array,
                         min_p: jax.Array, keys: jax.Array,
                         vocab: int = 0, head: int = SAMPLE_HEAD
                         ) -> jax.Array:
    """`sample_tokens_reference` with a partial-sort fast path.

    The full reference pays an O(V log V) sort per step; for serving
    params (greedy, modest top_k, nucleus top_p < 1) the winner's rank is
    almost surely within the first `head` ranks.  This entry computes the
    top-`head` ranks with `lax.top_k` (O(V)), checks per row that the
    filters provably close within the head — greedy, `0 < top_k <= head`,
    or head mass ≥ `top_p + _CLOSURE_EPS` — and only when EVERY row is
    closed takes the head-only branch; otherwise it falls back to the
    full reference in-graph (`lax.cond`, so a jitted serve segment pays
    whichever branch the batch needs).

    Bitwise-identical to `sample_tokens_reference` for every input:
      * `lax.top_k` ties break toward the lower index, exactly like the
        stable `argsort(-scaled)`, so head ranks/values match the sort.
      * probabilities come from the same token-order softmax, which the
        reference's sort carries into rank order bit for bit.
      * the head's cumulative mass is the reference's own head cumsum
        (see `_sorted_keep`), so the keep mask matches over head ranks,
        and closure guarantees every tail rank is dropped by BOTH paths
        (the `_CLOSURE_EPS` margin absorbs full-vs-head cumsum rounding).
      * the Gumbel draw is the full (V,) row draw sliced to the head —
        same threefry bits the reference adds at those ranks; tail ranks
        are -inf in both paths, so the argmax winner coincides."""
    b, v = logits.shape
    if v <= head:
        return sample_tokens_reference(logits, temperature, top_k, top_p,
                                       min_p, keys, vocab)
    lf = logits.astype(jnp.float32)
    temperature = jnp.asarray(temperature, jnp.float32).reshape(b)
    top_k = jnp.asarray(top_k, jnp.int32).reshape(b)
    top_p = jnp.asarray(top_p, jnp.float32).reshape(b)
    min_p = jnp.asarray(min_p, jnp.float32).reshape(b)

    greedy = (temperature <= 0.0) | (top_k == 1)
    scaled = _scaled_bounded_logits(lf, temperature, vocab)
    top_vals, top_idx = jax.lax.top_k(scaled, head)           # (B,head)
    probs_tok = jax.nn.softmax(scaled, axis=-1)
    probs_h = jnp.take_along_axis(probs_tok, top_idx, axis=-1)
    cum_head = jnp.cumsum(probs_h, axis=-1)
    closed = (greedy
              | ((top_k > 0) & (top_k <= head))
              | (cum_head[:, -1] >= top_p + _CLOSURE_EPS))

    def fast(_):
        ranks = jnp.arange(head)[None, :]
        keep = jnp.ones((b, head), bool)
        keep &= jnp.where(top_k[:, None] > 0, ranks < top_k[:, None], True)
        cum_before = cum_head - probs_h
        keep &= (cum_before < top_p[:, None]) | (ranks == 0)
        keep &= probs_h >= min_p[:, None] * probs_h[:, :1]
        filtered = jnp.where(keep, top_vals, -jnp.inf)
        gumbel = jax.vmap(
            lambda kk: jax.random.gumbel(kk, (v,), jnp.float32))(keys)
        rank = jnp.argmax(filtered + gumbel[:, :head], axis=-1)
        sampled = jnp.take_along_axis(top_idx, rank[:, None], axis=-1)[:, 0]
        return jnp.where(greedy, jnp.argmax(lf, axis=-1),
                         sampled).astype(jnp.int32)

    def full(_):
        return sample_tokens_reference(logits, temperature, top_k, top_p,
                                       min_p, keys, vocab)

    return jax.lax.cond(jnp.all(closed), fast, full, operand=None)


def filtered_log_probs(logits: jax.Array, temperature: jax.Array,
                       top_k: jax.Array, top_p: jax.Array,
                       min_p: jax.Array, vocab: int = 0) -> jax.Array:
    """(…, V) log-probabilities of the temperature/top_k/top_p/min_p
    filtered distribution — by construction the EXACT distribution a
    stochastic `sample_tokens_reference` row draws from (same scaling,
    same vocab bound, same keep mask; filtered-out tokens are -inf).
    This is the q (target) and p (draft) of the speculative verification
    identity (DESIGN.md §7): rejection-sampling against these
    log-probabilities leaves the per-token output law equal to plain
    sampling from q.

    logits: (B, V) or (B, K, V) — a leading (B,) of per-slot parameters
    broadcasts over the middle K axis."""
    shape = logits.shape
    v = shape[-1]
    lf = logits.astype(jnp.float32).reshape(-1, v)
    rep = lf.shape[0] // temperature.shape[0]
    t = jnp.repeat(jnp.asarray(temperature, jnp.float32), rep)
    tk = jnp.repeat(jnp.asarray(top_k, jnp.int32), rep)
    tp = jnp.repeat(jnp.asarray(top_p, jnp.float32), rep)
    mp = jnp.repeat(jnp.asarray(min_p, jnp.float32), rep)
    scaled = _scaled_bounded_logits(lf, t, vocab)
    order, _, keep = _sorted_keep(scaled, tk, tp, mp)
    inv = jnp.argsort(order, axis=-1)                  # token id -> rank
    keep_tok = jnp.take_along_axis(keep, inv, axis=-1)
    filtered = jnp.where(keep_tok, scaled, -jnp.inf)
    return jax.nn.log_softmax(filtered, axis=-1).reshape(shape)


def verify_tokens_reference(target_logits: jax.Array,
                            draft_logits: jax.Array,
                            draft_tokens: jax.Array,
                            temperature: jax.Array, top_k: jax.Array,
                            top_p: jax.Array, min_p: jax.Array,
                            keys: jax.Array, vocab: int = 0
                            ) -> Tuple[jax.Array, jax.Array]:
    """Speculative draft-and-verify acceptance — the oracle for
    `ops.verify_tokens` and the single definition of its semantics
    (DESIGN.md §7).

    target_logits: (B, K+1, V) — the target model's logits at the K+1
      verified positions (position j conditions on the emitted prefix
      plus draft tokens 0..j-1; position K is the bonus position
      conditioned on all K drafts).
    draft_logits:  (B, K, V) — the draft logits each draft token was
      sampled from (the proposal distribution, after the row's own
      filters — the draft MUST have sampled through `sample_tokens` with
      the same per-row parameters).
    draft_tokens:  (B, K) int32; keys: (B, 2) uint32, one per slot.
    Returns (out_tokens (B, K+1) i32, accept_len (B,) i32): the emitted
    tokens of the round are out_tokens[:accept_len + 1] — accept_len
    accepted draft tokens followed by one correction/bonus token.

    Per-row semantics:

      * greedy rows (``temperature <= 0`` or ``top_k == 1``) — accept
        draft j iff it equals ``argmax(target_logits[j])``; the token
        after the accepted prefix is that position's argmax.  Since every
        accepted draft equals the argmax too, ``out_tokens`` is simply
        the target argmax at all K+1 positions: the emitted stream is
        bitwise the non-speculative greedy stream, for ANY draft (draft
        quality moves the accept rate, never the tokens).  As in
        `sample_tokens_reference`, greedy argmax is deliberately
        unbounded by `vocab` (historical greedy parity).
      * stochastic rows — standard speculative rejection sampling over
        the FILTERED distributions q_j (target) and p_j (draft) from
        `filtered_log_probs`: draft j is accepted with probability
        min(1, q_j(g_j)/p_j(g_j)); the first rejected position emits a
        sample from the residual distribution norm(max(q_j − p_j, 0))
        (falling back to q_j when the residual has no mass, i.e. q = p);
        a fully accepted round emits a bonus sample from q_K.  The
        marginal law of each emitted token is exactly q — sampling-
        distribution-identical to the non-speculative loop, though not
        bitwise (the PRNG chain is consumed per ROUND here, per token
        there).

    All draws derive from the row's key (split into accept-uniforms /
    residual-Gumbels / bonus-Gumbels), so a fixed key gives a bitwise-
    deterministic verdict — the segment-replay property of the streamed
    serve loop."""
    b, kp1, v = target_logits.shape
    k = kp1 - 1
    assert k >= 1, "draft depth must be >= 1"
    temperature = jnp.asarray(temperature, jnp.float32).reshape(b)
    top_k = jnp.asarray(top_k, jnp.int32).reshape(b)
    top_p = jnp.asarray(top_p, jnp.float32).reshape(b)
    min_p = jnp.asarray(min_p, jnp.float32).reshape(b)
    greedy = (temperature <= 0.0) | (top_k == 1)
    draft_tokens = jnp.asarray(draft_tokens, jnp.int32)

    # -- greedy path: accept while the draft matches the target argmax
    tgt_argmax = jnp.argmax(target_logits.astype(jnp.float32),
                            axis=-1).astype(jnp.int32)         # (B,K+1)
    g_match = (draft_tokens == tgt_argmax[:, :k]).astype(jnp.int32)
    g_accept = jnp.sum(jnp.cumprod(g_match, axis=-1), axis=-1)  # (B,)

    # -- stochastic path: rejection sampling over filtered distributions
    lq = filtered_log_probs(target_logits, temperature, top_k, top_p,
                            min_p, vocab)                      # (B,K+1,V)
    lp = filtered_log_probs(draft_logits, temperature, top_k, top_p,
                            min_p, vocab)                      # (B,K,V)
    lq_g = jnp.take_along_axis(lq[:, :k], draft_tokens[..., None],
                               axis=-1)[..., 0]                # (B,K)
    lp_g = jnp.take_along_axis(lp, draft_tokens[..., None],
                               axis=-1)[..., 0]

    def row_draws(key):
        ku, kc, kb = jax.random.split(key, 3)
        return (jax.random.uniform(ku, (k,), jnp.float32),
                jax.random.gumbel(kc, (k, v), jnp.float32),
                jax.random.gumbel(kb, (v,), jnp.float32))

    u, g_res, g_bonus = jax.vmap(row_draws)(keys)
    # accept iff u <= q(g)/p(g), in log space; a draft token the target
    # filtered out entirely (q = 0) is always rejected
    accept = (jnp.log(u) + lp_g <= lq_g) & (lq_g > -jnp.inf)
    s_accept = jnp.sum(jnp.cumprod(accept.astype(jnp.int32), axis=-1),
                       axis=-1)                                # (B,)

    # residual distribution at every candidate rejection position;
    # q == p (no residual mass) falls back to q itself
    q = jnp.exp(lq[:, :k])
    p = jnp.exp(lp)
    res = jnp.maximum(q - p, 0.0)                              # (B,K,V)
    res_ok = jnp.sum(res, axis=-1, keepdims=True) > 0.0
    res_l = jnp.where(res_ok, jnp.log(res), lq[:, :k])
    corr = jnp.argmax(res_l + g_res, axis=-1).astype(jnp.int32)  # (B,K)
    bonus = jnp.argmax(lq[:, k] + g_bonus, axis=-1).astype(jnp.int32)

    out_s = jnp.concatenate([draft_tokens, bonus[:, None]], axis=1)
    at = jnp.minimum(s_accept, k)                              # (B,)
    fix = jnp.where(s_accept < k,
                    jnp.take_along_axis(
                        corr, jnp.minimum(s_accept, k - 1)[:, None],
                        axis=-1)[:, 0],
                    bonus)
    out_s = out_s.at[jnp.arange(b), at].set(fix)

    out = jnp.where(greedy[:, None], tgt_argmax, out_s)
    accept_len = jnp.where(greedy, g_accept, s_accept)
    return out.astype(jnp.int32), accept_len.astype(jnp.int32)


# --------------------------------------------------------------------------
# Block quantization oracles (q8_0 / q4_k weights, int8 KV pages) — §10
# --------------------------------------------------------------------------
#
# These are the numerical ground truth for `kernels.quant` (the Pallas
# dequant-fused matmul) and for the int8 KV consumption inside
# `decode_attention_fused`.  Each format carries a per-block worst-case
# error bound (`quant_error_bound`) that the parity suites assert
# element-wise — the "tolerance tiers" of DESIGN.md §10.

QUANT_BLOCK = 32


def _pad_blocks(w: jax.Array, block: int) -> Tuple[jax.Array, int]:
    """Zero-pad the second-to-last (input) axis of w (..., d, n) up to a
    multiple of `block` and return the blocked view (..., nB, block, n)."""
    d, n = w.shape[-2], w.shape[-1]
    nb = -(-d // block)
    pad = nb * block - d
    wf = w.astype(jnp.float32)
    if pad:
        wf = jnp.concatenate(
            [wf, jnp.zeros(w.shape[:-2] + (pad, n), jnp.float32)], axis=-2)
    return wf.reshape(w.shape[:-2] + (nb, block, n)), pad


def quantize_q8_0(w: jax.Array, block: int = QUANT_BLOCK
                  ) -> Tuple[jax.Array, jax.Array]:
    """Symmetric 8-bit block quantization along the input axis.

    w: (..., d, n) → (scales (..., nB, n) f32, quants (..., nB, block, n)
    int8) with nB = ceil(d/block); scale = absmax/127 per (block, column).
    Ragged final blocks are zero-padded (zeros never raise the absmax).
    Per-element error of dequantize(quantize(w)) is <= scale/2."""
    wb, _ = _pad_blocks(w, block)
    scales = jnp.max(jnp.abs(wb), axis=-2) / 127.0
    safe = jnp.where(scales > 0, scales, 1.0)
    q = jnp.clip(jnp.round(wb / safe[..., None, :]), -127, 127)
    return scales, q.astype(jnp.int8)


def dequantize_q8_0(scales: jax.Array, quants: jax.Array,
                    d: int) -> jax.Array:
    """Inverse of `quantize_q8_0`: (..., nB, n), (..., nB, block, n) →
    (..., d, n) f32 (the true input width `d` slices off block padding)."""
    w = quants.astype(jnp.float32) * scales[..., None, :]
    nb, block, n = w.shape[-3:]
    return w.reshape(w.shape[:-3] + (nb * block, n))[..., :d, :]


def quantize_q4_k(w: jax.Array, block: int = QUANT_BLOCK
                  ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Asymmetric 4-bit block quantization (simplified q4_k: one f32
    scale + one f32 min per block, no super-blocks).

    w: (..., d, n) → (scales (..., nB, n), mins (..., nB, n), packed
    (..., nB, block//2, n) uint8).  q = round((w - min)/scale) in [0, 15],
    two quants per byte (element 2i in the low nibble, 2i+1 in the high).
    Block min/max are taken over VALID lanes only, so a ragged final
    block's range is not widened by padding.  Per-element error is
    <= scale/2 = (max - min)/30."""
    d = w.shape[-2]
    wb, pad = _pad_blocks(w, block)
    if pad:
        lane = jnp.arange(wb.shape[-3] * block).reshape(wb.shape[-3], block)
        vmask = (lane < d)[..., None]                  # (nB, block, 1)
        wmax = jnp.max(jnp.where(vmask, wb, -jnp.inf), axis=-2)
        wmin = jnp.min(jnp.where(vmask, wb, jnp.inf), axis=-2)
    else:
        wmax = jnp.max(wb, axis=-2)
        wmin = jnp.min(wb, axis=-2)
    scales = (wmax - wmin) / 15.0
    safe = jnp.where(scales > 0, scales, 1.0)
    q = jnp.clip(jnp.round((wb - wmin[..., None, :]) / safe[..., None, :]),
                 0, 15).astype(jnp.uint8)
    packed = q[..., 0::2, :] | (q[..., 1::2, :] << 4)
    return scales, wmin, packed


def dequantize_q4_k(scales: jax.Array, mins: jax.Array, packed: jax.Array,
                    d: int) -> jax.Array:
    """Inverse of `quantize_q4_k` → (..., d, n) f32."""
    lo = (packed & 0xF).astype(jnp.float32)
    hi = (packed >> 4).astype(jnp.float32)
    q = jnp.stack([lo, hi], axis=-2)                   # (..., nB, hb, 2, n)
    nb, hb, _, n = q.shape[-4:]
    q = q.reshape(q.shape[:-4] + (nb, hb * 2, n))
    w = q * scales[..., None, :] + mins[..., None, :]
    return w.reshape(w.shape[:-3] + (nb * hb * 2, n))[..., :d, :]


def quant_error_bound(fmt: str, scales: jax.Array) -> jax.Array:
    """Worst-case |dequant(quant(w)) - w| per element, per block: the
    rounding half-step of the format's grid.  Broadcasts against the
    blocked view of w (append a lane axis to compare element-wise)."""
    if fmt == "q8_0":
        return scales * 0.5
    if fmt == "q4_k":
        return scales * 0.5
    raise ValueError(f"unknown quant format: {fmt}")


def quantize_kv_pages(kv: jax.Array, page_size: int
                      ) -> Tuple[jax.Array, jax.Array]:
    """Int8 KV pages with one f32 scale per (head, page).

    kv: (B, KH, S, hd) → (quants int8 same shape, scales (B, KH, S/ps)
    f32); scale = absmax over the page's (ps, hd) slab / 127.  This is
    the whole-cache oracle twin of the models' incremental per-token
    writes (`transformer.quant_kv_update_stacked`)."""
    b, kh, s, hd = kv.shape
    assert s % page_size == 0, (s, page_size)
    n_pages = s // page_size
    kr = kv.astype(jnp.float32).reshape(b, kh, n_pages, page_size, hd)
    scales = jnp.max(jnp.abs(kr), axis=(-2, -1)) / 127.0
    safe = jnp.where(scales > 0, scales, 1.0)
    q = jnp.clip(jnp.round(kr / safe[..., None, None]), -127, 127)
    return q.astype(jnp.int8).reshape(b, kh, s, hd), scales


def dequantize_kv_pages(quants: jax.Array, scales: jax.Array) -> jax.Array:
    """Inverse of `quantize_kv_pages`: scales broadcast per page slab."""
    b, kh, s, hd = quants.shape
    n_pages = scales.shape[-1]
    ps = s // n_pages
    kr = quants.astype(jnp.float32).reshape(b, kh, n_pages, ps, hd)
    return (kr * scales[..., None, None]).reshape(b, kh, s, hd)


# --------------------------------------------------------------------------
# Grouped expert matmul (mixture-of-experts FFN)
# --------------------------------------------------------------------------

def expert_gmm_reference(lhs: jax.Array, w: jax.Array,
                         tile_group: jax.Array, n_live: jax.Array,
                         w2: Optional[jax.Array] = None, *,
                         tm: int) -> jax.Array:
    """Oracle of `gmm.expert_gmm`: each `tm`-row tile of lhs times its
    group's weights (silu(lhs·w) ⊙ lhs·w2 when w2 is given), zero on
    tiles past n_live.  The weights are gathered per tile, so the work is
    the live rows' only, not every group's."""
    m, k = lhs.shape
    tiles = lhs.reshape(m // tm, tm, k)
    out = jnp.einsum("tmk,tkn->tmn", tiles, w[tile_group],
                     preferred_element_type=jnp.float32)
    if w2 is not None:
        out = jax.nn.silu(out) * jnp.einsum(
            "tmk,tkn->tmn", tiles, w2[tile_group],
            preferred_element_type=jnp.float32)
    live = jnp.arange(m // tm) < n_live[0]
    out = jnp.where(live[:, None, None], out, 0.0)
    return out.reshape(m, -1).astype(lhs.dtype)


# --------------------------------------------------------------------------
# KNN distances (VectorDB offload target)
# --------------------------------------------------------------------------

def knn_distances_reference(queries: jax.Array, db: jax.Array) -> jax.Array:
    """Squared L2 distances.  queries: (Q,D), db: (N,D) -> (Q,N) float32."""
    qf = queries.astype(jnp.float32)
    xf = db.astype(jnp.float32)
    q2 = jnp.sum(qf * qf, axis=-1, keepdims=True)      # (Q,1)
    x2 = jnp.sum(xf * xf, axis=-1)                      # (N,)
    return q2 - 2.0 * (qf @ xf.T) + x2[None, :]


def knn_topk_reference(queries: jax.Array, db: jax.Array, k: int
                       ) -> Tuple[jax.Array, jax.Array]:
    """k nearest rows by squared L2: returns (dists (Q,k), idx (Q,k))."""
    d = knn_distances_reference(queries, db)
    neg, idx = jax.lax.top_k(-d, k)
    return -neg, idx


# --------------------------------------------------------------------------
# Sparse Length Sum (DLRM offload target)
# --------------------------------------------------------------------------

def sls_reference(table: jax.Array, indices: jax.Array,
                  weights: Optional[jax.Array] = None) -> jax.Array:
    """Embedding-bag pooled sum.  table: (V,D); indices: (B,L) int32;
    weights: (B,L) or None -> (B,D) in float32."""
    rows = jnp.take(table, indices, axis=0).astype(jnp.float32)  # (B,L,D)
    if weights is not None:
        rows = rows * weights.astype(jnp.float32)[..., None]
    return jnp.sum(rows, axis=1)


# --------------------------------------------------------------------------
# Mamba2 SSD chunked scan (sequence-parallel state handoff target)
# --------------------------------------------------------------------------

def ssd_reference(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
                  C: jax.Array,
                  init_state: Optional[jax.Array] = None
                  ) -> Tuple[jax.Array, jax.Array]:
    """Sequential (non-chunked) SSD recurrence — the exact oracle.

    x: (b,s,h,p); dt: (b,s,h) f32; A: (h,) f32; B,C: (b,s,n).
    Returns (y (b,s,h,p), final_state (b,h,p,n))."""
    b, s, h, p = x.shape
    n = B.shape[-1]
    xf = x.astype(jnp.float32)
    Bf = B.astype(jnp.float32)
    Cf = C.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)

    def step(state, inp):
        xt, dtt, Bt, Ct = inp                          # (b,h,p),(b,h),(b,n),(b,n)
        decay = jnp.exp(dtt * A[None, :])              # (b,h)
        upd = jnp.einsum("bhp,bn->bhpn", xt * dtt[..., None], Bt)
        state = state * decay[..., None, None] + upd
        y = jnp.einsum("bhpn,bn->bhp", state, Ct)
        return state, y

    init = (init_state.astype(jnp.float32) if init_state is not None
            else jnp.zeros((b, h, p, n), jnp.float32))
    final, ys = jax.lax.scan(
        step, init,
        (xf.transpose(1, 0, 2, 3), dtf.transpose(1, 0, 2),
         Bf.transpose(1, 0, 2), Cf.transpose(1, 0, 2)))
    return ys.transpose(1, 0, 2, 3).astype(x.dtype), final
