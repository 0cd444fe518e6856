"""Whisper-style encoder-decoder.  The conv audio frontend is a stub: inputs
arrive as precomputed frame embeddings (B, enc_len, D) per the assignment.

The encoder is a bidirectional transformer; the decoder adds cross-attention
to the encoder output.  Cross-attention is the paper's offload structure for
enc-dec serving: the encoder output lives on the 'CCM side' and partial
cross-attention results stream to the decoder (DESIGN.md SS4).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import layers as L
from repro.models import transformer as T
from repro.models.quantize import matmul
from repro.models.config import ArchConfig
from repro.sharding import constrain

Params = Dict[str, Any]


def _init_cross(cfg: ArchConfig, key) -> Params:
    p = T._init_attn(cfg, key)
    return p


def init_params(cfg: ArchConfig, key) -> Params:
    assert cfg.enc_dec
    k_embed, k_enc, k_dec, k_cross = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.dtype)
    n_enc_blocks = cfg.n_enc_layers // len(cfg.block_pattern)
    enc_keys = jax.random.split(k_enc, n_enc_blocks)
    dec_keys = jax.random.split(k_dec, cfg.n_blocks)
    cross_keys = jax.random.split(k_cross, cfg.n_blocks)
    return {
        "embed": (jax.random.normal(k_embed, (cfg.padded_vocab, cfg.d_model))
                  * cfg.d_model ** -0.5).astype(dt),
        "enc_blocks": jax.vmap(lambda k: T.init_block_params(cfg, k))(enc_keys),
        "dec_blocks": jax.vmap(lambda k: T.init_block_params(cfg, k))(dec_keys),
        "cross": jax.vmap(lambda k: _init_cross(cfg, k))(cross_keys),
        "enc_final_ln": jnp.zeros((cfg.d_model,), dt),
        "final_ln": jnp.zeros((cfg.d_model,), dt),
    }


def abstract_params(cfg: ArchConfig) -> Params:
    return jax.eval_shape(functools.partial(init_params, cfg),
                          jax.random.key(0))


# --------------------------------------------------------------------------
# Encoder
# --------------------------------------------------------------------------

def _enc_attn(cfg: ArchConfig, p: Params, x: jax.Array) -> jax.Array:
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    q, k, v = T._qkv(cfg, p, x, positions)
    o = L.blocked_attention(q, k, v, causal=False)
    return x + matmul(o.reshape(b, s, -1), p["wo"])


def encode(cfg: ArchConfig, params: Params, embeds: jax.Array,
           *, remat: bool = True) -> jax.Array:
    x = embeds.astype(jnp.dtype(cfg.dtype))
    x = constrain(x, "batch")

    def body(x, bp):
        for pos in range(len(cfg.block_pattern)):
            p = bp[pos]
            x = _enc_attn(cfg, p["attn"], x)
            x, _ = T.ffn_layer(cfg, p["ffn"], x, False)
            x = constrain(x, "batch")
        return x

    # W1 (§Perf): without remat the 32 encoder layers' activations are all
    # saved for backward — 538 GB/chip peak at train_4k.
    if remat:
        body = jax.checkpoint(body,
                              policy=jax.checkpoint_policies.nothing_saveable)

    def block(x, bp):
        return body(x, bp), None

    x, _ = lax.scan(block, x, params["enc_blocks"])
    return L.rms_norm(x, params["enc_final_ln"], cfg.norm_eps)


# --------------------------------------------------------------------------
# Decoder (train/prefill)
# --------------------------------------------------------------------------

def _cross_attn(cfg: ArchConfig, p: Params, x: jax.Array,
                enc_out: jax.Array) -> jax.Array:
    b, s, _ = x.shape
    h, kh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    hx = L.rms_norm(x, p["ln"], cfg.norm_eps)
    q = matmul(hx, p["wq"]).reshape(b, s, h, hd)
    k = matmul(enc_out, p["wk"]).reshape(b, enc_out.shape[1], kh, hd)
    v = matmul(enc_out, p["wv"]).reshape(b, enc_out.shape[1], kh, hd)
    o = L.blocked_attention(q, k, v, causal=False, block=500)
    return x + matmul(o.reshape(b, s, -1), p["wo"])


def decoder_forward(cfg: ArchConfig, params: Params, tokens: jax.Array,
                    enc_out: jax.Array, *, remat: bool = True) -> jax.Array:
    x = jnp.take(params["embed"], tokens, axis=0)
    x = constrain(x, "batch")
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))

    def body(x, inp):
        bp, cross_p = inp
        for pos, kind in enumerate(cfg.block_pattern):
            p = bp[pos]
            x = T.attn_layer(cfg, p["attn"], x, kind, positions)
            x = _cross_attn(cfg, cross_p, x, enc_out)
            x, _ = T.ffn_layer(cfg, p["ffn"], x, False)
            x = constrain(x, "batch")
        return x

    if remat:
        body = jax.checkpoint(body,
                              policy=jax.checkpoint_policies.nothing_saveable)

    def block(x, inp):
        return body(x, inp), None

    x, _ = lax.scan(block, x, (params["dec_blocks"], params["cross"]))
    return L.rms_norm(x, params["final_ln"], cfg.norm_eps)


def loss_fn(cfg: ArchConfig, params: Params, batch: Dict[str, jax.Array]
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    enc_out = encode(cfg, params, batch["embeds"])
    x = decoder_forward(cfg, params, batch["tokens"], enc_out)
    ce = L.xent_loss_chunked(x, params["embed"], batch["labels"],
                             vocab=cfg.vocab)
    return ce, {"ce": ce, "aux": jnp.zeros((), jnp.float32)}


def logits_fn(cfg: ArchConfig, params: Params, batch: Dict[str, jax.Array]
              ) -> jax.Array:
    enc_out = encode(cfg, params, batch["embeds"])
    x = decoder_forward(cfg, params, batch["tokens"], enc_out)
    logits = jnp.einsum("bsd,vd->bsv", x, params["embed"])
    return constrain(logits, "logits")


# --------------------------------------------------------------------------
# Decode with caches
# --------------------------------------------------------------------------

def init_cache(cfg: ArchConfig, batch_size: int, max_seq: int,
               enc_len: int = 0, page_size=None,
               kv_quant=None) -> Dict[str, Any]:
    """Self-attention KV cache + precomputed per-layer cross KV.

    `enc_pos` is the per-slot ENCODER length clock: cross-attention at
    decode time attends only to cross-KV rows < enc_pos[b], so a slot
    serving a clip shorter than the cache's enc_len never reads the
    zero-padded (or stale) tail.  It defaults to the full enc_len, which
    keeps the whole-batch `prefill_cross_cache` path and existing decode
    callers at the historical all-rows-valid behavior.

    The decoder self-KV panels inherit the transformer page table
    (DESIGN.md §9, `page_size` passthrough) and the int8 `kv_quant`
    mode (per-page scale leaves, DESIGN.md §10); cross-KV is written
    once per admission and read whole, so it stays dense (unpaged) and
    fp — its bytes are O(enc_len) per request, not O(decoded tokens)."""
    cache = T.init_cache(cfg, batch_size, max_seq, page_size=page_size,
                         kv_quant=kv_quant)
    dt = jnp.dtype(cfg.dtype)
    kh, hd = cfg.n_kv_heads, cfg.head_dim_
    enc_len = enc_len or cfg.enc_len
    cache["cross_k"] = jnp.zeros((cfg.n_blocks, batch_size, kh, enc_len, hd), dt)
    cache["cross_v"] = jnp.zeros((cfg.n_blocks, batch_size, kh, enc_len, hd), dt)
    cache["enc_pos"] = jnp.full((batch_size,), enc_len, jnp.int32)
    return cache


def abstract_cache(cfg: ArchConfig, batch_size: int, max_seq: int,
                   enc_len: int = 0, page_size=None,
                   kv_quant=None) -> Dict[str, Any]:
    return jax.eval_shape(
        functools.partial(init_cache, cfg, batch_size, max_seq, enc_len,
                          page_size=page_size, kv_quant=kv_quant))


def _cross_kv(cfg: ArchConfig, cross_p: Params, enc_out: jax.Array
              ) -> Tuple[jax.Array, jax.Array]:
    """ONE decoder block's cross-attention K/V from the encoder output,
    in the flash-decoding cache layout (B, KH, E, hd) — the single
    definition both the whole-batch precompute and the per-slot prefill
    write through."""
    kh, hd = cfg.n_kv_heads, cfg.head_dim_
    b, e, _ = enc_out.shape
    k = matmul(enc_out, cross_p["wk"]).reshape(b, e, kh, hd).transpose(0, 2, 1, 3)
    v = matmul(enc_out, cross_p["wv"]).reshape(b, e, kh, hd).transpose(0, 2, 1, 3)
    return k, v


def prefill_cross_cache(cfg: ArchConfig, params: Params, enc_out: jax.Array,
                        cache: Dict[str, Any]) -> Dict[str, Any]:
    """Compute cross-attention K/V for every decoder layer from enc_out
    (whole-batch path: every row gets the same encoder output and the
    full encoder length)."""
    ks, vs = jax.vmap(lambda cp: _cross_kv(cfg, cp, enc_out))(params["cross"])
    out = dict(cache)
    out["cross_k"], out["cross_v"] = ks, vs
    out["enc_pos"] = jnp.full_like(cache["enc_pos"], enc_out.shape[1])
    return out


def prefill_into_cache(cfg: ArchConfig, params: Params,
                       cache: Dict[str, Any], tokens: jax.Array,
                       row: jax.Array, length: jax.Array,
                       enc_embeds: jax.Array = None, *,
                       enc_out: jax.Array = None
                       ) -> Tuple[jax.Array, Dict[str, Any]]:
    """Real encoder-decoder prefill of ONE request into batch row `row` —
    what takes whisper-style serving out of `BatchedServer` fallback mode.

    Three phases, mirroring the decoder-only path
    (transformer.prefill_into_cache) plus the encoder side:

      1. encoder pass over the request's frame embeddings
         (enc_embeds: (1, e, D) with e <= the cache's enc_len — the stub
         audio frontend's output at the clip's TRUE frame count; a clip
         shorter than cfg.enc_len no longer needs frontend-side padding);
      2. per-layer cross-attention K/V projected from the encoder output
         and written into this slot's rows of cache['cross_k'/'cross_v']
         (previously a whole-batch precompute, incompatible with
         continuous batching where every slot serves a different request).
         Rows past e are zeroed and `cache['enc_pos'][row]` is set to e,
         so decode cross-attention masks them out (the zeroing is belt
         and braces against the previous occupant's trailing frames; the
         enc_pos clock is what correctness rests on);
      3. decoder self-attention prefill: the whole (padded) decoder
         prompt through the flash_attention kernel, per-layer K/V written
         into the slot's cache rows.  Junk past `length` lands at slots
         >= length, invisible under the per-row position clock.

    The encoder length e is a static shape: a jitted caller retraces once
    per distinct clip length (the serving driver passes clips at their
    true length; bucket upstream if trace churn matters).

    `enc_out` (keyword-only) bypasses phase 1 with a PRECOMPUTED encoder
    output (1, e, D): speculative admission runs target AND draft
    prefill for the same request, and a self-draft shares the encoder
    parameters by reference — encoding twice was pure waste (the
    ROADMAP-carried double-encode).  The serving driver encodes once
    per admission and hands the same enc_out to both prefills; passing
    enc_out is bitwise-identical to passing the enc_embeds it was
    encoded from (asserted in tests/test_cache_offload.py).  Exactly
    one of enc_embeds / enc_out must be given.

    Returns (last-token logits (V,), updated cache)."""
    from repro.kernels import ops
    p_len = tokens.shape[0]
    assert (enc_embeds is None) != (enc_out is None), \
        "pass exactly one of enc_embeds / enc_out"
    if enc_out is None:
        enc_out = encode(cfg, params, enc_embeds, remat=False)  # (1, E, D)
    e = enc_out.shape[1]

    x = jnp.take(params["embed"], tokens[None], axis=0)     # (1, P, D)
    positions = jnp.arange(p_len, dtype=jnp.int32)[None]

    def scan_body(x, inp):
        bp, cross_p = inp
        states = {}
        for pos_i, kind in enumerate(cfg.block_pattern):
            p = bp[pos_i]
            q, k, v = T._qkv(cfg, p["attn"], x, positions)
            window = cfg.sliding_window if kind == "local" else 0
            o = ops.flash_attention(q, k, v, length, causal=True,
                                    window=window)
            x = x + matmul(o.reshape(1, p_len, -1), p["attn"]["wo"])
            states[f"k{pos_i}"] = k.transpose(0, 2, 1, 3)   # (1,KH,P,hd)
            states[f"v{pos_i}"] = v.transpose(0, 2, 1, 3)
            x = _cross_attn(cfg, cross_p, x, enc_out)
            x, _ = T.ffn_layer(cfg, p["ffn"], x, False)
        # this block's cross K/V for the decode loop (static per request)
        states["cross_k"], states["cross_v"] = \
            _cross_kv(cfg, cross_p, enc_out)                # (1,KH,E,hd)
        return x, states

    x, states = lax.scan(
        scan_body, x, (params["dec_blocks"], params["cross"]))
    x = L.rms_norm(x, params["final_ln"], cfg.norm_eps)
    x_last = lax.dynamic_slice_in_dim(x, length - 1, 1, axis=1)
    logits = jnp.einsum("bsd,vd->bsv", x_last, params["embed"])[0, 0]

    row = jnp.asarray(row, jnp.int32)
    out_cache = dict(cache)
    pt = cache.get("page_table")
    for key, val in states.items():                         # (L,1,KH,*,hd)
        c = out_cache[key]
        if key.startswith("cross"):
            # write the FULL cross row: real K/V for the clip's e frames,
            # zeros beyond — decode masks rows >= enc_pos[row] anyway.
            # Cross-KV is unpaged (written once, read whole).
            assert e <= c.shape[3], (e, c.shape)
            val = jnp.pad(val, ((0, 0), (0, 0), (0, 0),
                                (0, c.shape[3] - e), (0, 0)))
        else:
            assert p_len <= c.shape[3], (p_len, c.shape)
            scale_key = key[0] + "scale" + key[1:]
            if scale_key in cache and pt is not None:
                # int8 decoder self-KV: per-page quantize-scatter (fresh
                # scales, previous occupant's junk cleared)
                ps = c.shape[3] // pt.shape[1]
                prow = lax.dynamic_slice(pt, (row, 0), (1, pt.shape[1]))[0]
                vals = val[:, 0].transpose(0, 2, 1, 3)    # (L,P,KH,hd)
                out_cache[key], out_cache[scale_key] = \
                    T.quant_kv_write_rows(c, cache[scale_key], vals, row,
                                          jnp.zeros((), jnp.int32), prow,
                                          ps)
                continue
            if pt is not None:
                # decoder self-KV goes through the row's page table
                # (DESIGN.md §9) — same scatter as the decoder-only
                # prefill: non-adjacent advanced indices put the
                # indexed dims first, so the value is (P, L, KH, hd)
                ps = c.shape[3] // pt.shape[1]
                prow = lax.dynamic_slice(pt, (row, 0), (1, pt.shape[1]))[0]
                lrows = jnp.arange(p_len, dtype=jnp.int32)
                phys = jnp.take(prow, lrows // ps) * ps + lrows % ps
                out_cache[key] = c.at[:, row, :, phys, :].set(
                    val.astype(c.dtype)[:, 0].transpose(2, 0, 1, 3))
                continue
        out_cache[key] = lax.dynamic_update_slice(
            c, val.astype(c.dtype), (0, row, 0, 0, 0))
    out_cache["enc_pos"] = cache["enc_pos"].at[row].set(e)
    return logits, out_cache


# Per-slot cache pages (host-tier offload, DESIGN.md §8): the generic
# shape dispatch of the transformer versions covers every enc-dec leaf —
# 5-dim cross_k/cross_v panels slice like KV panels (but are never
# prefix-truncated: `upto` matches only k{pos}/v{pos} names), and the
# 1-dim enc_pos clock slices on axis 0 — so one definition serves both
# model families (round-trip asserted per leaf kind in
# tests/test_cache_offload.py).
extract_slot_cache = T.extract_slot_cache
insert_slot_cache = T.insert_slot_cache


def decode_verify(cfg: ArchConfig, params: Params, cache: Dict[str, Any],
                  tokens: jax.Array, positions: jax.Array,
                  write_mask=None
                  ) -> Tuple[jax.Array, Dict[str, Any], Dict[str, Any]]:
    """Multi-position verify forward for speculative enc-dec decoding —
    the encoder-decoder twin of `transformer.decode_verify` (DESIGN.md
    §7).  tokens: (B, T) — current token + T-1 draft proposals per row,
    starting at stream position positions[b].  Self-attention runs the
    per-query chunk identity of `transformer._verify_attn`; cross-
    attention is position-independent (every query attends the same
    encoder rows < enc_pos[b]), so it simply repeats the one-token
    cross read per chunk position.  There is no recurrent state, so
    rollback is entirely the position clock's job: all T self-attn K/V
    rows are ring-written (masked by `write_mask`) and the junk tail
    past the accept point stays invisible (snaps is always empty).

    Returns (logits (B, T, V), cache, {})."""
    from repro.core.backstream import decode_attention_combined
    x = jnp.take(params["embed"], tokens, axis=0)             # (B,T,D)
    b, t, _ = x.shape
    pos = jnp.asarray(positions, jnp.int32)
    cross_pos = jnp.asarray(cache["enc_pos"], jnp.int32) - 1
    pages = cache.get("page_table")

    cache_keys = sorted(k for k in cache
                        if k not in ("pos", "enc_pos", "page_table"))
    xs_cache = {k: cache[k] for k in cache_keys}

    def scan_body(x, inp):
        bp, cross_p, blk_cache = inp
        updates = {}
        for pos_i, kind in enumerate(cfg.block_pattern):
            p = bp[pos_i]
            kv_scales = None
            if f"kscale{pos_i}" in blk_cache:
                kv_scales = (blk_cache[f"kscale{pos_i}"],
                             blk_cache[f"vscale{pos_i}"])
            x, knew, vnew = T._verify_attn(
                cfg, p["attn"], x, kind,
                blk_cache[f"k{pos_i}"], blk_cache[f"v{pos_i}"], pos,
                pages, kv_scales)
            updates[f"knew{pos_i}"] = knew                    # (B,T,KH,hd)
            updates[f"vnew{pos_i}"] = vnew
            hx = L.rms_norm(x, cross_p["ln"], cfg.norm_eps)
            q = matmul(hx, cross_p["wq"]).reshape(b, t, cfg.n_heads,
                                                  cfg.head_dim_)
            outs = [decode_attention_combined(
                q[:, j:j + 1], blk_cache["cross_k"], blk_cache["cross_v"],
                cross_pos, n_chunks=1) for j in range(t)]
            o = jnp.concatenate(outs, axis=1)
            x = x + matmul(o.reshape(b, t, -1), cross_p["wo"])
            x, _ = T.ffn_layer(cfg, p["ffn"], x, False)
        return x, updates

    x, ys = lax.scan(
        scan_body, x, (params["dec_blocks"], params["cross"], xs_cache))
    x = L.rms_norm(x, params["final_ln"], cfg.norm_eps)
    logits = jnp.einsum("bsd,vd->bsv", x, params["embed"])

    out_cache: Dict[str, Any] = {"pos": cache["pos"] + t,
                                 "cross_k": cache["cross_k"],
                                 "cross_v": cache["cross_v"],
                                 "enc_pos": cache["enc_pos"]}
    if pages is not None:
        out_cache["page_table"] = pages
    for pos_i in range(len(cfg.block_pattern)):
        if f"kscale{pos_i}" in cache:
            out_cache[f"k{pos_i}"], out_cache[f"kscale{pos_i}"] = \
                T.quant_verify_kv_update(
                    cache[f"k{pos_i}"], cache[f"kscale{pos_i}"],
                    ys[f"knew{pos_i}"], pos, write_mask, pages)
            out_cache[f"v{pos_i}"], out_cache[f"vscale{pos_i}"] = \
                T.quant_verify_kv_update(
                    cache[f"v{pos_i}"], cache[f"vscale{pos_i}"],
                    ys[f"vnew{pos_i}"], pos, write_mask, pages)
            continue
        out_cache[f"k{pos_i}"] = T.verify_kv_update(
            cache[f"k{pos_i}"], ys[f"knew{pos_i}"], pos, write_mask, pages)
        out_cache[f"v{pos_i}"] = T.verify_kv_update(
            cache[f"v{pos_i}"], ys[f"vnew{pos_i}"], pos, write_mask, pages)
    return constrain(logits, "logits"), out_cache, {}


def decode_step(cfg: ArchConfig, params: Params, cache: Dict[str, Any],
                tokens: jax.Array,
                positions=None,
                write_mask=None) -> Tuple[jax.Array, Dict[str, Any]]:
    """One decoder token against self-attn cache + cross KV cache.
    `positions`: optional (B,) per-row token positions (continuous
    batching), defaulting to the scalar cache step counter.  `write_mask`:
    optional (B,) bool in-segment termination mask — masked rows leave
    their self-attn KV slots untouched (see transformer.decode_step).

    Cross attention attends only to rows < `cache['enc_pos'][b]` — the
    per-slot encoder length clock, which is what lets one decode batch
    mix clips of different frame counts (variable encoder lengths).

    As in the decoder-only path (SS Perf iteration D5), the scan reads all
    caches as xs and emits only the tiny new-token self-attn K/V; the
    (static) cross KV never round-trips through scan ys at all."""
    from repro.core.backstream import (cache_update_stacked,
                                       decode_attention_combined,
                                       physical_slots)
    x = jnp.take(params["embed"], tokens, axis=0)
    b = x.shape[0]
    pos = cache["pos"] if positions is None \
        else jnp.asarray(positions, jnp.int32)
    # per-row last valid cross slot; enc_pos is per-SLOT (B,), not
    # per-layer — it rides the scan closure, not the xs
    cross_pos = jnp.asarray(cache["enc_pos"], jnp.int32) - 1
    pages = cache.get("page_table")

    cache_keys = sorted(k for k in cache
                        if k not in ("pos", "enc_pos", "page_table"))
    xs_cache = {k: cache[k] for k in cache_keys}

    def scan_body(x, inp):
        bp, cross_p, blk_cache = inp
        updates = {}
        for pos_i, kind in enumerate(cfg.block_pattern):
            p = bp[pos_i]
            kv_scales = None
            if f"kscale{pos_i}" in blk_cache:
                kv_scales = (blk_cache[f"kscale{pos_i}"],
                             blk_cache[f"vscale{pos_i}"])
            x, knew, vnew = T._decode_attn(
                cfg, p["attn"], x, kind,
                blk_cache[f"k{pos_i}"], blk_cache[f"v{pos_i}"], pos,
                pages, kv_scales)
            updates[f"knew{pos_i}"] = knew
            updates[f"vnew{pos_i}"] = vnew
            # cross attention against the (static) encoder KV
            hx = L.rms_norm(x, cross_p["ln"], cfg.norm_eps)
            q = matmul(hx, cross_p["wq"]).reshape(b, 1, cfg.n_heads,
                                                  cfg.head_dim_)
            o = decode_attention_combined(
                q, blk_cache["cross_k"], blk_cache["cross_v"],
                cross_pos, n_chunks=1)
            x = x + matmul(o.reshape(b, 1, -1), cross_p["wo"])
            x, _ = T.ffn_layer(cfg, p["ffn"], x, False)
        return x, updates

    x, ys = lax.scan(
        scan_body, x, (params["dec_blocks"], params["cross"], xs_cache))
    x = L.rms_norm(x, params["final_ln"], cfg.norm_eps)
    logits = jnp.einsum("bsd,vd->bsv", x, params["embed"])

    out_cache: Dict[str, Any] = {"pos": cache["pos"] + 1,
                                 "cross_k": cache["cross_k"],
                                 "cross_v": cache["cross_v"],
                                 "enc_pos": cache["enc_pos"]}
    if pages is not None:
        out_cache["page_table"] = pages
    for pos_i, kind in enumerate(cfg.block_pattern):
        max_seq = cache[f"k{pos_i}"].shape[3]
        slot = (pos % max_seq).astype(jnp.int32)
        if pages is not None:
            slot = physical_slots(
                pages, jnp.broadcast_to(slot.reshape(-1), (b,)),
                max_seq // pages.shape[1])
        if f"kscale{pos_i}" in cache:
            out_cache[f"k{pos_i}"], out_cache[f"kscale{pos_i}"] = \
                T.quant_kv_update_stacked(
                    cache[f"k{pos_i}"], cache[f"kscale{pos_i}"],
                    ys[f"knew{pos_i}"], slot, write_mask)
            out_cache[f"v{pos_i}"], out_cache[f"vscale{pos_i}"] = \
                T.quant_kv_update_stacked(
                    cache[f"v{pos_i}"], cache[f"vscale{pos_i}"],
                    ys[f"vnew{pos_i}"], slot, write_mask)
            continue
        if write_mask is not None:
            slot = jnp.broadcast_to(slot.reshape(-1), (b,))
            knew = T.masked_kv_update(cache[f"k{pos_i}"],
                                      ys[f"knew{pos_i}"], slot, write_mask)
            vnew = T.masked_kv_update(cache[f"v{pos_i}"],
                                      ys[f"vnew{pos_i}"], slot, write_mask)
        else:
            knew, vnew = ys[f"knew{pos_i}"], ys[f"vnew{pos_i}"]
        out_cache[f"k{pos_i}"] = cache_update_stacked(
            cache[f"k{pos_i}"], knew, slot)
        out_cache[f"v{pos_i}"] = cache_update_stacked(
            cache[f"v{pos_i}"], vnew, slot)
    return constrain(logits, "logits"), out_cache
