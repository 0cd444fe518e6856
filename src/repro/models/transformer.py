"""Decoder-only transformer (+ hybrid/SSM) model: init, train/prefill
forward, and single-step decode with KV / SSM state caches.

The layer stack is expressed as `n_blocks` repetitions of a static
`block_pattern`, scanned with `lax.scan` over stacked parameters so the
lowered HLO is depth-independent (essential for the 512-device dry-run of
72-layer models on one CPU host).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import layers as L
from repro.models.config import ArchConfig
from repro.models.quantize import matmul
from repro.sharding import constrain

Params = Dict[str, Any]
AUX_LOSS_COEF = 0.01


# --------------------------------------------------------------------------
# Initialization
# --------------------------------------------------------------------------

def _init_attn(cfg: ArchConfig, key) -> Params:
    d, h, kh, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s = d ** -0.5
    dt = jnp.dtype(cfg.dtype)
    return {
        "ln": jnp.zeros((d,), dt),
        "wq": (jax.random.normal(k1, (d, h * hd)) * s).astype(dt),
        "wk": (jax.random.normal(k2, (d, kh * hd)) * s).astype(dt),
        "wv": (jax.random.normal(k3, (d, kh * hd)) * s).astype(dt),
        "wo": (jax.random.normal(k4, (h * hd, d)) * (h * hd) ** -0.5).astype(dt),
    }


def _init_ffn(cfg: ArchConfig, key, moe: bool) -> Params:
    d, f = cfg.d_model, cfg.d_ff
    dt = jnp.dtype(cfg.dtype)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    if moe:
        e = cfg.n_experts
        return {
            "ln": jnp.zeros((d,), dt),
            "router": (jax.random.normal(k4, (d, e)) * d ** -0.5).astype(jnp.float32),
            "w_gate": (jax.random.normal(k1, (e, d, f)) * d ** -0.5).astype(dt),
            "w_up": (jax.random.normal(k2, (e, d, f)) * d ** -0.5).astype(dt),
            "w_down": (jax.random.normal(k3, (e, f, d)) * f ** -0.5).astype(dt),
        }
    return {
        "ln": jnp.zeros((d,), dt),
        "w_gate": (jax.random.normal(k1, (d, f)) * d ** -0.5).astype(dt),
        "w_up": (jax.random.normal(k2, (d, f)) * d ** -0.5).astype(dt),
        "w_down": (jax.random.normal(k3, (f, d)) * f ** -0.5).astype(dt),
    }


def _init_mamba(cfg: ArchConfig, key) -> Params:
    d, di, n, nh = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.n_ssm_heads
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 6)
    s = d ** -0.5
    return {
        "ln": jnp.zeros((d,), dt),
        "w_z": (jax.random.normal(ks[0], (d, di)) * s).astype(dt),
        "w_x": (jax.random.normal(ks[1], (d, di)) * s).astype(dt),
        "w_B": (jax.random.normal(ks[2], (d, n)) * s).astype(dt),
        "w_C": (jax.random.normal(ks[3], (d, n)) * s).astype(dt),
        "w_dt": (jax.random.normal(ks[4], (d, nh)) * s).astype(dt),
        "dt_bias": jnp.zeros((nh,), jnp.float32),
        "A_log": jnp.zeros((nh,), jnp.float32),       # A = -exp(A_log) = -1
        "D": jnp.ones((nh,), jnp.float32),
        "conv_w": (jax.random.normal(ks[5], (cfg.conv_width, di))
                   * cfg.conv_width ** -0.5).astype(dt),
        "out_proj": (jax.random.normal(ks[4], (di, d)) * di ** -0.5).astype(dt),
    }


def _is_moe_pos(cfg: ArchConfig, pos: int) -> bool:
    return cfg.is_moe and (pos % cfg.moe_every == 0)


def init_block_params(cfg: ArchConfig, key) -> Tuple[Params, ...]:
    """Parameters for one block (one instance of the pattern)."""
    out = []
    for pos, kind in enumerate(cfg.block_pattern):
        key, k1, k2 = jax.random.split(key, 3)
        layer: Params = {}
        if kind in ("full", "local"):
            layer["attn"] = _init_attn(cfg, k1)
        elif kind == "mamba":
            layer["mamba"] = _init_mamba(cfg, k1)
        if cfg.d_ff > 0:
            layer["ffn"] = _init_ffn(cfg, k2, _is_moe_pos(cfg, pos))
        out.append(layer)
    return tuple(out)


@functools.partial(jax.jit, static_argnums=0)
def init_params(cfg: ArchConfig, key) -> Params:
    """One jitted program, so each weight's f32 draw fuses into its bf16
    cast: the peak is the parameters' own bytes, not a stack of f32
    temporaries (at starcoder2_3b widths those would be ~9 GB)."""
    dt = jnp.dtype(cfg.dtype)
    key, ke, kb = jax.random.split(key, 3)
    # stacked blocks: vmap the per-block init over n_blocks keys
    block_keys = jax.random.split(kb, cfg.n_blocks)
    blocks = jax.vmap(lambda k: init_block_params(cfg, k))(block_keys)
    params: Params = {
        "embed": (jax.random.normal(ke, (cfg.padded_vocab, cfg.d_model))
                  * cfg.d_model ** -0.5).astype(dt),
        "blocks": blocks,
        "final_ln": jnp.zeros((cfg.d_model,), dt),
    }
    return params


def abstract_params(cfg: ArchConfig) -> Params:
    """Shape/dtype-only params (no allocation) for the dry-run."""
    return jax.eval_shape(
        functools.partial(init_params, cfg), jax.random.key(0))


# --------------------------------------------------------------------------
# Layer applications
# --------------------------------------------------------------------------

def _qkv(cfg: ArchConfig, p: Params, x: jax.Array,
         positions: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    b, s, _ = x.shape
    h, kh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    hx = L.rms_norm(x, p["ln"], cfg.norm_eps)
    q = matmul(hx, p["wq"]).reshape(b, s, h, hd)
    k = matmul(hx, p["wk"]).reshape(b, s, kh, hd)
    v = matmul(hx, p["wv"]).reshape(b, s, kh, hd)
    if cfg.mrope:
        pos3 = jnp.broadcast_to(positions[..., None], positions.shape + (3,))
        q = L.apply_mrope(q, pos3, cfg.rope_theta, _mrope_sections(hd))
        k = L.apply_mrope(k, pos3, cfg.rope_theta, _mrope_sections(hd))
    else:
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _mrope_sections(hd: int) -> Tuple[int, int, int]:
    half = hd // 2
    t = half - 2 * (half // 4)
    return (t, half // 4, half // 4)


def attn_layer(cfg: ArchConfig, p: Params, x: jax.Array, kind: str,
               positions: jax.Array) -> jax.Array:
    b, s, _ = x.shape
    q, k, v = _qkv(cfg, p, x, positions)
    q = constrain(q, "attn_in")
    k = constrain(k, "kv")
    v = constrain(v, "kv")
    if kind == "local" and s > cfg.sliding_window:
        o = L.sliding_attention(q, k, v, window=cfg.sliding_window)
    else:
        o = L.blocked_attention(q, k, v, causal=True)
    o = o.reshape(b, s, cfg.n_heads * cfg.head_dim_)
    # all-gather the head-sharded output BEFORE the wo contraction: an
    # all-gather is a bit-copy, whereas letting GSPMD run a partial dot +
    # all-reduce over the sharded H*hd axis would re-associate the float
    # sum and break the bitwise serving contract (DESIGN.md §11)
    o = constrain(o, "batch")
    return x + matmul(o, p["wo"])


def ffn_layer(cfg: ArchConfig, p: Params, x: jax.Array, moe: bool
              ) -> Tuple[jax.Array, jax.Array]:
    b, s, d = x.shape
    hx = L.rms_norm(x, p["ln"], cfg.norm_eps)
    if moe:
        flat = hx.reshape(b * s, d)
        y = L.moe_ffn_dist(flat, p["router"], p["w_gate"], p["w_up"],
                           p["w_down"], cfg.top_k)
        aux = L.moe_aux_loss(flat, p["router"], cfg.top_k)
        return x + y.reshape(b, s, d), aux
    y = L.gated_mlp(hx, p["w_gate"], p["w_up"], p["w_down"])
    return x + y, jnp.zeros((), jnp.float32)


def _mamba_proj(cfg: ArchConfig, p: Params, x: jax.Array):
    """Shared input projections of the mamba sublayer: returns
    (z gate, conv INPUT, B, C, dt (softplus, f32), A) for the train /
    decode / prefill variants, which differ only in how they run the
    conv + SSD recurrence."""
    hx = L.rms_norm(x, p["ln"], cfg.norm_eps)
    z = jax.nn.silu(matmul(hx, p["w_z"]))
    xin = matmul(hx, p["w_x"])
    Bm = hx @ p["w_B"]
    Cm = hx @ p["w_C"]
    dt = jax.nn.softplus((hx @ p["w_dt"]).astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    return z, xin, Bm, Cm, dt, A


def mamba_layer(cfg: ArchConfig, p: Params, x: jax.Array) -> jax.Array:
    b, s, _ = x.shape
    nh, hp = cfg.n_ssm_heads, cfg.ssm_head_dim
    z, xin, Bm, Cm, dt, A = _mamba_proj(cfg, p, x)
    xc, _ = L.causal_conv1d(xin, p["conv_w"])
    y, _ = L.ssd_chunked(xc.reshape(b, s, nh, hp), dt, A, Bm, Cm)
    y = y + (xc.reshape(b, s, nh, hp)
             * p["D"][None, None, :, None].astype(xc.dtype))
    y = (y.reshape(b, s, -1) * z).astype(x.dtype)
    return x + matmul(y, p["out_proj"])


# --------------------------------------------------------------------------
# Forward (train / prefill)
# --------------------------------------------------------------------------

def _embed(cfg: ArchConfig, params: Params, batch: Dict[str, jax.Array]
           ) -> jax.Array:
    if "embeds" in batch:                        # vlm/audio stub frontends
        return batch["embeds"].astype(jnp.dtype(cfg.dtype))
    return jnp.take(params["embed"], batch["tokens"], axis=0)


def _block_fn(cfg: ArchConfig, x: jax.Array, block_params: Tuple[Params, ...],
              positions: jax.Array) -> Tuple[jax.Array, jax.Array]:
    aux_total = jnp.zeros((), jnp.float32)
    for pos, kind in enumerate(cfg.block_pattern):
        p = block_params[pos]
        if kind in ("full", "local"):
            x = attn_layer(cfg, p["attn"], x, kind, positions)
        elif kind == "mamba":
            x = mamba_layer(cfg, p["mamba"], x)
        if cfg.d_ff > 0:
            x, aux = ffn_layer(cfg, p["ffn"], x, _is_moe_pos(cfg, pos))
            aux_total = aux_total + aux
        x = constrain(x, "batch")
    return x, aux_total


def forward(cfg: ArchConfig, params: Params, batch: Dict[str, jax.Array],
            *, remat: bool = True) -> Tuple[jax.Array, jax.Array]:
    """Returns (final hidden states (B,S,D), total aux loss)."""
    x = _embed(cfg, params, batch)
    x = constrain(x, "batch")
    b, s, _ = x.shape
    positions = batch.get(
        "positions",
        jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s)))

    body = functools.partial(_block_fn, cfg, positions=positions)
    if remat:
        body = jax.checkpoint(body,
                              policy=jax.checkpoint_policies.nothing_saveable)

    def scan_body(carry, block_params):
        x = carry
        x, aux = body(x, block_params)
        return x, aux

    x, auxes = lax.scan(scan_body, x, params["blocks"])
    x = L.rms_norm(x, params["final_ln"], cfg.norm_eps)
    return x, jnp.sum(auxes)


def loss_fn(cfg: ArchConfig, params: Params, batch: Dict[str, jax.Array]
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    x, aux = forward(cfg, params, batch)
    ce = L.xent_loss_chunked(x, params["embed"], batch["labels"],
                             vocab=cfg.vocab)
    loss = ce + AUX_LOSS_COEF * aux
    return loss, {"ce": ce, "aux": aux}


def logits_fn(cfg: ArchConfig, params: Params, batch: Dict[str, jax.Array]
              ) -> jax.Array:
    """Full-sequence logits (prefill / evaluation path)."""
    x, _ = forward(cfg, params, batch, remat=False)
    logits = jnp.einsum("bsd,vd->bsv", x, params["embed"])
    return constrain(logits, "logits")


# --------------------------------------------------------------------------
# Decode: caches + single-token step
# --------------------------------------------------------------------------

@dataclasses.dataclass
class CacheSpec:
    """Cache layout for one pattern position across all blocks."""
    kind: str


def default_page_size(max_seq: int) -> int:
    """The KV page size used when none is requested: the largest divisor
    of max_seq not above 128 — the SAME divisor rule the dense fused
    decode kernel uses to pick its chunk size `blk_c`, so the identity
    page table reproduces the dense kernel's grid (and therefore its
    bits) exactly (DESIGN.md §9: chunk-as-page equivalence)."""
    ps = max(1, min(128, max_seq))
    while max_seq % ps:
        ps -= 1
    return ps


def init_cache(cfg: ArchConfig, batch_size: int, max_seq: int,
               dtype: Optional[str] = None,
               page_size: Optional[int] = None,
               kv_quant: Optional[str] = None) -> Dict[str, Any]:
    """Per-pattern-position caches stacked over n_blocks.

    Caches with attention layers also carry a `"page_table"` leaf
    (B, n_pages) int32 — per-row physical-page indices for the
    block-sparse KV pages of DESIGN.md §9.  Logical KV row `r` of batch
    row `b` lives at physical row `table[b, r // page] * page + r % page`
    of the SAME dense (B, KH, S, hd) panels; the identity table (the
    init value here) makes every paged code path bitwise the dense one.
    `page_size` must divide max_seq (default: `default_page_size`).

    `kv_quant="int8"` stores the self-attention K/V panels as int8 pools
    with one symmetric f32 scale per (layer, row, kv-head, PHYSICAL
    page): leaves `kscale{pos}`/`vscale{pos}` of shape
    (L, B, KH, n_pages), riding the layer scan and the host-tier
    extract/insert alongside the panels they scale (DESIGN.md §10).
    Recurrent (conv/ssm) state and cross-KV stay fp — they have no page
    structure to hang a scale on and their bytes are O(1) per request."""
    dt = jnp.dtype(dtype or cfg.dtype)
    nb, b = cfg.n_blocks, batch_size
    kh, hd = cfg.n_kv_heads, cfg.head_dim_
    assert kv_quant in (None, "int8"), kv_quant
    has_attn = any(k in ("full", "local") for k in cfg.block_pattern)
    ps = 0
    if has_attn:
        ps = page_size or default_page_size(max_seq)
        assert max_seq % ps == 0, (max_seq, ps)
    kv_dt = jnp.int8 if kv_quant else dt
    cache: Dict[str, Any] = {"pos": jnp.zeros((), jnp.int32)}
    for pos, kind in enumerate(cfg.block_pattern):
        if kind in ("full", "local"):
            # flash-decoding layout (B, KH, S, hd): contiguous (S, hd)
            # panels per kv head — decode dots read the cache in place
            # (§Perf iteration D2)
            cache[f"k{pos}"] = jnp.zeros((nb, b, kh, max_seq, hd), kv_dt)
            cache[f"v{pos}"] = jnp.zeros((nb, b, kh, max_seq, hd), kv_dt)
            if kv_quant:
                cache[f"kscale{pos}"] = jnp.zeros(
                    (nb, b, kh, max_seq // ps), jnp.float32)
                cache[f"vscale{pos}"] = jnp.zeros(
                    (nb, b, kh, max_seq // ps), jnp.float32)
        elif kind == "mamba":
            cache[f"conv{pos}"] = jnp.zeros(
                (nb, b, cfg.conv_width - 1, cfg.d_inner), dt)
            cache[f"ssm{pos}"] = jnp.zeros(
                (nb, b, cfg.n_ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
                jnp.float32)
    if has_attn:
        cache["page_table"] = jnp.tile(
            jnp.arange(max_seq // ps, dtype=jnp.int32)[None], (b, 1))
    return cache


def abstract_cache(cfg: ArchConfig, batch_size: int, max_seq: int,
                   page_size: Optional[int] = None,
                   kv_quant: Optional[str] = None) -> Dict[str, Any]:
    return jax.eval_shape(
        functools.partial(init_cache, cfg, batch_size, max_seq,
                          page_size=page_size, kv_quant=kv_quant))


def cache_kv_quant(cache: Dict[str, Any]) -> Optional[str]:
    """The cache's KV quantization mode, detected from its scale leaves
    (static: dict keys only)."""
    return "int8" if any(_is_kv_scale(k) for k in cache) else None


def cache_page_size(cache: Dict[str, Any]) -> int:
    """Static page size of a cache with a page table: seq axis of any
    self-KV leaf over the table's page count."""
    pt = cache["page_table"]
    for key, leaf in cache.items():
        if _is_self_kv(key):
            return leaf.shape[3] // pt.shape[1]
    raise ValueError("cache has a page_table but no self-KV leaves")


def _decode_attn(cfg: ArchConfig, p: Params, x: jax.Array, kind: str,
                 k_cache: jax.Array, v_cache: jax.Array, pos: jax.Array,
                 pages: Optional[jax.Array] = None,
                 kv_scales: Optional[Tuple[jax.Array, jax.Array]] = None,
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One-token attention against the cache.  The cache is sharded over the
    sequence axis (flash-decoding): each shard produces a partial-softmax
    result that is merged - the back-streaming integration point (see
    repro.core.backstream.decode_attention_combined).

    `pos` is the current token's position — a scalar, or a (B,) vector of
    per-row positions (continuous batching: every slot sits at its own
    sequence offset; RoPE angles, cache validity and ring-slot writes all
    follow the row's own clock).

    The cache is READ-ONLY here (§Perf iteration D5): the current token's
    contribution is merged as one extra partial (its KV has not been
    written yet), and the returned (k_new, v_new) are ring-slot-written
    for all layers at once OUTSIDE the layer scan — so the scan never
    re-stacks full cache slices.  `pages`: optional (B, n_pages) page
    table — the cache read then goes through per-row page indirection
    (DESIGN.md §9); `pos` keeps its logical meaning.  Returns
    (x, k_new, v_new) with k_new/v_new in cache layout (B, KH, 1, hd)."""
    from repro.core.backstream import decode_attention_combined
    b = x.shape[0]
    if pos.ndim == 0:
        positions = jnp.broadcast_to(pos[None, None], (b, 1)).astype(jnp.int32)
    else:
        positions = pos[:, None].astype(jnp.int32)
    q, k_new, v_new = _qkv(cfg, p, x, positions)
    extra = L.single_kv_partial(q, k_new, v_new)
    window = cfg.sliding_window if kind == "local" else 0
    # cache holds tokens [0, pos); the current token arrives via `extra`
    # (always fp — its KV has not been quantized-written yet)
    o = decode_attention_combined(q, k_cache, v_cache, pos - 1,
                                  window=max(0, window - 1), extra=extra,
                                  pages=pages, kv_scales=kv_scales)
    # bit-copy all-gather before the wo contraction (DESIGN.md §11; see
    # attn_layer) — the shard_map above already returned o replicated,
    # this pins the layout so GSPMD never re-shards into a partial dot
    o = constrain(o.reshape(b, 1, cfg.n_heads * cfg.head_dim_), "batch")
    return (x + matmul(o, p["wo"]), k_new.transpose(0, 2, 1, 3),
            v_new.transpose(0, 2, 1, 3))


def _decode_mamba(cfg: ArchConfig, p: Params, x: jax.Array,
                  conv_state: jax.Array, ssm_state: jax.Array
                  ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    b = x.shape[0]
    nh, hp = cfg.n_ssm_heads, cfg.ssm_head_dim
    z, xin, Bm, Cm, dt, A = _mamba_proj(cfg, p, x)
    xc, conv_state = L.causal_conv1d(xin, p["conv_w"], conv_state)
    y, ssm_state = L.ssd_decode_step(
        ssm_state, xc[:, 0].reshape(b, nh, hp), dt[:, 0], A,
        Bm[:, 0], Cm[:, 0])
    y = y + (xc[:, 0].reshape(b, nh, hp)
             * p["D"][None, :, None].astype(xc.dtype))
    y = (y.reshape(b, 1, -1) * z).astype(x.dtype)
    return x + matmul(y, p["out_proj"]), conv_state, ssm_state


def decode_step(cfg: ArchConfig, params: Params, cache: Dict[str, Any],
                tokens: jax.Array,
                positions: Optional[jax.Array] = None,
                write_mask: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, Dict[str, Any]]:
    """One decoding step.  tokens: (B, 1) int32 (or embeds (B,1,D)).
    `positions`: optional (B,) int32 per-row token positions (continuous
    batching); defaults to the scalar cache step counter, which assumes
    every row sits at the same offset.  `write_mask`: optional (B,) bool
    — rows where it is False compute logits but leave ALL their cached
    state (KV ring slots, conv window, SSM state) untouched; this is the
    in-segment termination mask of the streamed serve loop (DESIGN.md
    §6): a row that hit its stop token or token budget mid-segment stays
    frozen in place until the host retires it at the segment boundary,
    instead of smearing post-EOS junk into the slot it is about to free.
    Returns (logits (B, 1, V), updated cache).

    KV caches pass through the layer scan READ-ONLY (xs); the scan emits
    only the per-layer new-token K/V (tiny), which are ring-slot-written
    into the stacked caches in ONE sharded update per cache after the
    scan (§Perf iteration D5) — the scan never re-stacks cache slices.
    The write mask is applied to those tiny per-layer updates (a gather
    of the old slot values + select), never to the full cache arrays.

    Paged caches (a `"page_table"` leaf, DESIGN.md §9): reads go through
    per-row page indirection inside the attention call, and the ring
    slot of every KV write is translated logical→physical through the
    table first.  Position clocks, validity and the write mask all stay
    logical — the table only relocates bytes."""
    from repro.core.backstream import cache_update_stacked, physical_slots
    if tokens.ndim == 3:
        x = tokens.astype(jnp.dtype(cfg.dtype))
    else:
        x = jnp.take(params["embed"], tokens, axis=0)
    pos = cache["pos"] if positions is None \
        else jnp.asarray(positions, jnp.int32)
    pages = cache.get("page_table")

    # page_table rides the closure, not the layer scan: its leading axis
    # is B, not n_blocks, and it is identical for every layer
    cache_keys = sorted(k for k in cache if k not in ("pos", "page_table"))
    xs = {k: cache[k] for k in cache_keys}

    def scan_body(x, inp):
        block_params, blk_cache = inp
        updates = {}
        for pos_i, kind in enumerate(cfg.block_pattern):
            p = block_params[pos_i]
            if kind in ("full", "local"):
                kv_scales = None
                if f"kscale{pos_i}" in blk_cache:
                    kv_scales = (blk_cache[f"kscale{pos_i}"],
                                 blk_cache[f"vscale{pos_i}"])
                x, knew, vnew = _decode_attn(
                    cfg, p["attn"], x, kind,
                    blk_cache[f"k{pos_i}"], blk_cache[f"v{pos_i}"], pos,
                    pages, kv_scales)
                updates[f"knew{pos_i}"] = knew
                updates[f"vnew{pos_i}"] = vnew
            elif kind == "mamba":
                x, cnew, snew = _decode_mamba(
                    cfg, p["mamba"], x,
                    blk_cache[f"conv{pos_i}"], blk_cache[f"ssm{pos_i}"])
                updates[f"conv{pos_i}"] = cnew
                updates[f"ssm{pos_i}"] = snew
            if cfg.d_ff > 0:
                x, _ = ffn_layer(cfg, p["ffn"], x, _is_moe_pos(cfg, pos_i))
        return x, updates

    x, ys = lax.scan(scan_body, x, (params["blocks"], xs))
    x = L.rms_norm(x, params["final_ln"], cfg.norm_eps)
    logits = jnp.einsum("bsd,vd->bsv", x, params["embed"])

    b = x.shape[0]
    out_cache: Dict[str, Any] = {"pos": cache["pos"] + 1}
    if pages is not None:
        out_cache["page_table"] = pages
    for pos_i, kind in enumerate(cfg.block_pattern):
        if kind in ("full", "local"):
            max_seq = cache[f"k{pos_i}"].shape[3]
            slot = (pos % max_seq).astype(jnp.int32)
            if pages is not None:
                # logical ring slot → physical row through the table;
                # masked-row old-value gathers below must read the SAME
                # physical slot the scatter targets
                slot = physical_slots(
                    pages, jnp.broadcast_to(slot.reshape(-1), (b,)),
                    max_seq // pages.shape[1])
            if f"kscale{pos_i}" in cache:
                # int8 pool: quantize-write the token (page-scale merge +
                # masked-row freeze handled inside)
                out_cache[f"k{pos_i}"], out_cache[f"kscale{pos_i}"] = \
                    quant_kv_update_stacked(
                        cache[f"k{pos_i}"], cache[f"kscale{pos_i}"],
                        ys[f"knew{pos_i}"], slot, write_mask)
                out_cache[f"v{pos_i}"], out_cache[f"vscale{pos_i}"] = \
                    quant_kv_update_stacked(
                        cache[f"v{pos_i}"], cache[f"vscale{pos_i}"],
                        ys[f"vnew{pos_i}"], slot, write_mask)
                continue
            if write_mask is not None:
                # per-row ring write; masked rows re-write their slot's
                # OLD value (token-sized gather+select, not a full-cache
                # select)
                slot_b = jnp.broadcast_to(slot.reshape(-1), (b,))
                knew = masked_kv_update(cache[f"k{pos_i}"],
                                        ys[f"knew{pos_i}"], slot_b,
                                        write_mask)
                vnew = masked_kv_update(cache[f"v{pos_i}"],
                                        ys[f"vnew{pos_i}"], slot_b,
                                        write_mask)
                slot = slot_b
            else:
                knew, vnew = ys[f"knew{pos_i}"], ys[f"vnew{pos_i}"]
            out_cache[f"k{pos_i}"] = cache_update_stacked(
                cache[f"k{pos_i}"], knew, slot)
            out_cache[f"v{pos_i}"] = cache_update_stacked(
                cache[f"v{pos_i}"], vnew, slot)
        elif kind == "mamba":
            for key in (f"conv{pos_i}", f"ssm{pos_i}"):
                new = ys[key]
                if write_mask is not None:
                    keep = write_mask.reshape((1, b) + (1,) * (new.ndim - 2))
                    new = jnp.where(keep, new, cache[key].astype(new.dtype))
                out_cache[key] = new
    return constrain(logits, "logits"), out_cache


def _verify_attn(cfg: ArchConfig, p: Params, x: jax.Array, kind: str,
                 k_cache: jax.Array, v_cache: jax.Array, pos: jax.Array,
                 pages: Optional[jax.Array] = None,
                 kv_scales: Optional[Tuple[jax.Array, jax.Array]] = None,
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """T-position attention for the speculative verify forward
    (DESIGN.md §7): x is (B, T, D) — the current token plus T-1 draft
    tokens, row b's chunk starting at stream position pos[b].

    Per query j this reproduces `_decode_attn` for a sequential decode
    at position pos + j EXACTLY: the chunk's new K/V rows are scattered
    into a local copy of the cache at ring slots pos..pos+T-1 first (the
    same cast-to-cache-dtype the sequential ring write performs), query
    j reads it under the validity clock `slot <= pos + j - 1` — so of
    the freshly scattered rows it sees precisely the j that precede it —
    and its own K/V contribution arrives as the merged extra partial,
    exactly as the sequential path's not-yet-written current token does.
    Masked slots contribute exp(-inf) = 0 to the softmax statistics, so
    the per-query reduction is bit-identical to the one-token step, which
    is what makes greedy speculative streams bitwise-equal to the
    non-speculative loop (asserted in tests/test_speculative.py).

    Returns (x, k_new, v_new) with k_new/v_new (B, T, KH, hd) — the
    caller ring-writes them outside the layer scan (§Perf iteration D5
    discipline, as in decode_step)."""
    from repro.core.backstream import decode_attention_combined, \
        physical_slots
    b, t, _ = x.shape
    s = k_cache.shape[2]
    positions = pos[:, None] + jnp.arange(t, dtype=jnp.int32)[None]
    q, k_new, v_new = _qkv(cfg, p, x, positions)
    slots = positions % s                                     # (B,T)
    if pages is not None:
        # the local scatter must land where the paged READ will look:
        # translate the logical ring slots through the row's table
        slots = physical_slots(pages, slots, s // pages.shape[1])
    bidx = jnp.arange(b)[:, None]
    if kv_scales is not None:
        # int8 local copy: T sequential quantize-writes (with a dummy
        # leading layer axis) so each draft row lands under exactly the
        # page scale its sequential decode would have produced
        kcq, kscq = k_cache[None], kv_scales[0][None]
        vcq, vscq = v_cache[None], kv_scales[1][None]
        for j in range(t):
            kcq, kscq = quant_kv_update_stacked(
                kcq, kscq, k_new[:, j:j + 1].transpose(0, 2, 1, 3)[None],
                slots[:, j])
            vcq, vscq = quant_kv_update_stacked(
                vcq, vscq, v_new[:, j:j + 1].transpose(0, 2, 1, 3)[None],
                slots[:, j])
        kc, vc = kcq[0], vcq[0]
        read_scales = (kscq[0], vscq[0])
    else:
        # advanced-index scatter: (bidx, slots) broadcast to (B,T), so the
        # target slice is (B,T,KH,hd) — k_new/v_new's native layout
        kc = k_cache.at[bidx, :, slots, :].set(k_new.astype(k_cache.dtype))
        vc = v_cache.at[bidx, :, slots, :].set(v_new.astype(v_cache.dtype))
        read_scales = None
    window = cfg.sliding_window if kind == "local" else 0
    outs = []
    for j in range(t):
        extra = L.single_kv_partial(q[:, j:j + 1], k_new[:, j:j + 1],
                                    v_new[:, j:j + 1])
        outs.append(decode_attention_combined(
            q[:, j:j + 1], kc, vc, pos + j - 1,
            window=max(0, window - 1), extra=extra, pages=pages,
            kv_scales=read_scales))
    o = jnp.concatenate(outs, axis=1)                         # (B,T,H,hd)
    # bit-copy all-gather before wo (DESIGN.md §11; see attn_layer)
    o = constrain(o.reshape(b, t, cfg.n_heads * cfg.head_dim_), "batch")
    return x + matmul(o, p["wo"]), k_new, v_new


def _verify_mamba(cfg: ArchConfig, p: Params, x: jax.Array,
                  conv_state: jax.Array, ssm_state: jax.Array
                  ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """T sequential mamba decode micro-steps fused into one sublayer
    application (the recurrence itself cannot be parallelized bitwise,
    so it runs as a T-step scan of the exact `ssd_decode_step` /
    conv-window math of `_decode_mamba`).  Unlike a KV slot, a recurrent
    state has no validity clock to hide junk behind, so EVERY
    intermediate state is returned for the segment's accept-point
    rollback (DESIGN.md §7: rollback-as-gather): snapshot j is the state
    after absorbing chunk inputs 0..j.

    x: (B, T, D).  Returns (x_out, conv_snaps (B, T, W-1, d_inner),
    ssm_snaps (B, T, NH, P, N) f32)."""
    b, t, _ = x.shape
    nh, hp, width = cfg.n_ssm_heads, cfg.ssm_head_dim, cfg.conv_width
    z, xin, Bm, Cm, dt, A = _mamba_proj(cfg, p, x)
    xc, _ = L.causal_conv1d(xin, p["conv_w"], conv_state)
    # conv state after step j = the width-1 input window ending at j
    xp = jnp.concatenate([conv_state.astype(xin.dtype), xin], axis=1)
    conv_snaps = jnp.stack(
        [xp[:, j + 1: j + width] for j in range(t)], axis=1)

    def step(state, inp):
        xct, dtt, Bt, Ct = inp
        y, state = L.ssd_decode_step(state, xct.reshape(b, nh, hp),
                                     dtt, A, Bt, Ct)
        return state, (y, state)

    _, (ys, states) = lax.scan(
        step, ssm_state,
        (xc.transpose(1, 0, 2), dt.transpose(1, 0, 2),
         Bm.transpose(1, 0, 2), Cm.transpose(1, 0, 2)))
    y = ys.transpose(1, 0, 2, 3)                              # (B,T,NH,P)
    ssm_snaps = states.transpose(1, 0, 2, 3, 4)               # (B,T,NH,P,N)
    y = y + (xc.reshape(b, t, nh, hp)
             * p["D"][None, None, :, None].astype(xc.dtype))
    y = (y.reshape(b, t, -1) * z).astype(x.dtype)
    return x + matmul(y, p["out_proj"]), conv_snaps, ssm_snaps


def decode_verify(cfg: ArchConfig, params: Params, cache: Dict[str, Any],
                  tokens: jax.Array, positions: jax.Array,
                  write_mask: Optional[jax.Array] = None
                  ) -> Tuple[jax.Array, Dict[str, Any], Dict[str, Any]]:
    """Multi-position verify forward of speculative decoding (DESIGN.md
    §7): ONE batched forward over tokens (B, T) — row b's current token
    followed by T-1 draft proposals, starting at stream position
    positions[b] — returning logits at ALL T positions, each bitwise
    what a sequential `decode_step` at that position would have produced
    (per-position attention identity: see `_verify_attn`; the recurrent
    sublayers run their exact per-token micro-steps inside the fused
    application: `_verify_mamba`).

    Cache discipline (the rollback-as-masked-write invariant):

      * attention K/V — ALL T rows are ring-written (slots pos..pos+T-1)
        for rows where `write_mask` is True; the segment then advances
        each row's position clock by only the ACCEPTED m <= T tokens, so
        the junk tail rows sit at slots >= the new clock and are
        invisible until genuinely decoded tokens overwrite them (the
        same junk-beyond-clock argument that legitimizes padded-prompt
        prefill under the per-row clocks of the segment protocol,
        DESIGN.md §3).  Masked (dead) rows re-write their old values,
        token-sized gather+select as in `masked_kv_update` (the §6
        termination-freeze discipline, extended to T rows).
      * recurrent (conv, ssm) state — returned UNTOUCHED in the cache;
        every intermediate state is returned in `snaps` (leaf shapes
        (L, B, T, …)) and the segment gathers snapshot m-1 per row —
        rollback is a gather, never a recompute.

    Returns (logits (B, T, V), cache, snaps)."""
    x = jnp.take(params["embed"], tokens, axis=0)             # (B,T,D)
    pos = jnp.asarray(positions, jnp.int32)
    b, t, _ = x.shape
    pages = cache.get("page_table")

    cache_keys = sorted(k for k in cache if k not in ("pos", "page_table"))
    xs = {k: cache[k] for k in cache_keys}

    def scan_body(x, inp):
        block_params, blk_cache = inp
        updates = {}
        for pos_i, kind in enumerate(cfg.block_pattern):
            p = block_params[pos_i]
            if kind in ("full", "local"):
                kv_scales = None
                if f"kscale{pos_i}" in blk_cache:
                    kv_scales = (blk_cache[f"kscale{pos_i}"],
                                 blk_cache[f"vscale{pos_i}"])
                x, knew, vnew = _verify_attn(
                    cfg, p["attn"], x, kind,
                    blk_cache[f"k{pos_i}"], blk_cache[f"v{pos_i}"], pos,
                    pages, kv_scales)
                updates[f"knew{pos_i}"] = knew                # (B,T,KH,hd)
                updates[f"vnew{pos_i}"] = vnew
            elif kind == "mamba":
                x, conv_s, ssm_s = _verify_mamba(
                    cfg, p["mamba"], x,
                    blk_cache[f"conv{pos_i}"], blk_cache[f"ssm{pos_i}"])
                updates[f"conv{pos_i}"] = conv_s              # (B,T,W-1,di)
                updates[f"ssm{pos_i}"] = ssm_s                # (B,T,NH,P,N)
            if cfg.d_ff > 0:
                x, _ = ffn_layer(cfg, p["ffn"], x, _is_moe_pos(cfg, pos_i))
        return x, updates

    x, ys = lax.scan(scan_body, x, (params["blocks"], xs))
    x = L.rms_norm(x, params["final_ln"], cfg.norm_eps)
    logits = jnp.einsum("bsd,vd->bsv", x, params["embed"])

    out_cache: Dict[str, Any] = {"pos": cache["pos"] + t}
    if pages is not None:
        out_cache["page_table"] = pages
    snaps: Dict[str, Any] = {}
    for pos_i, kind in enumerate(cfg.block_pattern):
        if kind in ("full", "local"):
            if f"kscale{pos_i}" in cache:
                out_cache[f"k{pos_i}"], out_cache[f"kscale{pos_i}"] = \
                    quant_verify_kv_update(
                        cache[f"k{pos_i}"], cache[f"kscale{pos_i}"],
                        ys[f"knew{pos_i}"], pos, write_mask, pages)
                out_cache[f"v{pos_i}"], out_cache[f"vscale{pos_i}"] = \
                    quant_verify_kv_update(
                        cache[f"v{pos_i}"], cache[f"vscale{pos_i}"],
                        ys[f"vnew{pos_i}"], pos, write_mask, pages)
                continue
            out_cache[f"k{pos_i}"] = verify_kv_update(
                cache[f"k{pos_i}"], ys[f"knew{pos_i}"], pos, write_mask,
                pages)
            out_cache[f"v{pos_i}"] = verify_kv_update(
                cache[f"v{pos_i}"], ys[f"vnew{pos_i}"], pos, write_mask,
                pages)
        elif kind == "mamba":
            for key in (f"conv{pos_i}", f"ssm{pos_i}"):
                out_cache[key] = cache[key]
                snaps[key] = ys[key]                          # (L,B,T,…)
    return constrain(logits, "logits"), out_cache, snaps


def verify_kv_update(cache: jax.Array, new: jax.Array, pos: jax.Array,
                     write_mask: Optional[jax.Array],
                     pages: Optional[jax.Array] = None) -> jax.Array:
    """Ring-write T consecutive per-row K/V rows into a stacked cache —
    the T-token generalization of `cache_update_stacked` +
    `masked_kv_update`.  cache: (L,B,KH,S,hd); new: (L,B,T,KH,hd)
    (layer-scan ys layout); pos: (B,) slot of row 0; write_mask: (B,)
    bool or None — masked rows re-write their old values (token-sized
    gather+select, never a full-cache where).  `pages`: optional
    (B, n_pages) table — the T logical ring slots are then translated
    to physical rows before the scatter (and the masked-row old-value
    gather, which must read the same physical rows)."""
    from repro.core.backstream import physical_slots
    l, b, kh, s, hd = cache.shape
    t = new.shape[2]
    slots = (pos[:, None] + jnp.arange(t, dtype=jnp.int32)[None]) % s
    if pages is not None:
        slots = physical_slots(pages, slots, s // pages.shape[1])
    bidx = jnp.arange(b)[:, None]
    val = new.astype(cache.dtype).transpose(1, 2, 0, 3, 4)    # (B,T,L,KH,hd)
    if write_mask is not None:
        old = cache[:, bidx, :, slots, :]                     # (B,T,L,KH,hd)
        val = jnp.where(write_mask[:, None, None, None, None], val, old)
    return cache.at[:, bidx, :, slots, :].set(val)


def masked_kv_update(cache: jax.Array, new: jax.Array, slot_b: jax.Array,
                     write_mask: jax.Array) -> jax.Array:
    """Replace masked-out rows of a stacked one-token K/V update with the
    cache's current value at each row's ring slot, so the subsequent
    scatter is a no-op for those rows.  cache: (L,B,KH,S,hd); new:
    (L,B,KH,1,hd); slot_b, write_mask: (B,).  Traffic stays token-sized:
    one (L,B,KH,hd) gather + select, never a full-cache where()."""
    b = cache.shape[1]
    old = cache[:, jnp.arange(b), :, slot_b, :]          # (B,L,KH,hd)
    old = old.transpose(1, 0, 2, 3)[:, :, :, None, :]    # (L,B,KH,1,hd)
    return jnp.where(write_mask[None, :, None, None, None],
                     new, old.astype(new.dtype))


# --------------------------------------------------------------------------
# Int8 KV cache writes (DESIGN.md §10)
# --------------------------------------------------------------------------
#
# Invariant: the fp value of cached row r is quants[r] * scale[page(r)].
# A page's scale only ever grows while the page is live (a new token with
# a larger absmax re-quantizes the page's existing rows to the merged
# scale), and a page whose FIRST row is being written gets a fresh scale
# — which simultaneously clears the previous occupant's junk (rescale
# ratio 0).  When the incoming token fits under the current scale the
# ratio is exactly 1.0 and the re-quantization round-trips bitwise, so
# steady-state decode touches only the token's own row.

_SCALE_EPS = 1e-30


def quant_kv_update_stacked(pool: jax.Array, scales: jax.Array,
                            new: jax.Array, slot_b: jax.Array,
                            write_mask: Optional[jax.Array] = None
                            ) -> Tuple[jax.Array, jax.Array]:
    """One-token ring write into an int8 KV pool — the quantized twin of
    `cache_update_stacked` (+ `masked_kv_update`).  pool: (L,B,KH,S,hd)
    int8; scales: (L,B,KH,nP) f32 per PHYSICAL page; new: (L,B,KH,1,hd)
    fp; slot_b: scalar or (B,) PHYSICAL rows (the caller translates
    logical→physical through the page table first, exactly as for the fp
    scatter); write_mask: (B,) bool or None — masked rows leave pool and
    scale bitwise untouched.  Returns (pool, scales)."""
    l, b, kh, s, hd = pool.shape
    n_p = scales.shape[3]
    ps = s // n_p
    slot_b = jnp.broadcast_to(
        jnp.asarray(slot_b, jnp.int32).reshape(-1), (b,))
    page = slot_b // ps                                   # (B,) physical
    off = slot_b % ps
    bidx = jnp.arange(b)
    newf = new.astype(jnp.float32)[:, :, :, 0]            # (L,B,KH,hd)
    cand = jnp.max(jnp.abs(newf), axis=-1) / 127.0        # (L,B,KH)
    # non-adjacent advanced indices (axes 1, 3) put the broadcast (B,)
    # dim first: (B,L,KH)
    old_s = scales[:, bidx, :, page].transpose(1, 0, 2)   # (L,B,KH)
    new_s = jnp.maximum(old_s, cand)
    if write_mask is not None:
        new_s = jnp.where(write_mask[None, :, None], new_s, old_s)
    # ratio 1.0 exactly when the scale is unchanged (old/old), 0 when the
    # page was empty (old 0) — clearing junk under the fresh scale
    r = old_s / jnp.maximum(new_s, _SCALE_EPS)
    rows = page[:, None] * ps + jnp.arange(ps, dtype=jnp.int32)[None]
    blk = pool[:, bidx[:, None], :, rows]                 # (B,ps,L,KH,hd)
    blk_r = jnp.rint(blk.astype(jnp.float32)
                     * r.transpose(1, 0, 2)[:, None, :, :, None])
    q_tok = jnp.clip(
        jnp.rint(newf / jnp.maximum(new_s, _SCALE_EPS)[..., None]),
        -127, 127)                                        # (L,B,KH,hd)
    tok = q_tok.transpose(1, 0, 2, 3)                     # (B,L,KH,hd)
    if write_mask is not None:
        old_tok = jnp.take_along_axis(
            blk, off[:, None, None, None, None], axis=1)[:, 0]
        tok = jnp.where(write_mask[:, None, None, None],
                        tok, old_tok.astype(tok.dtype))
    sel = jnp.arange(ps)[None, :] == off[:, None]         # (B,ps)
    blk_new = jnp.where(sel[:, :, None, None, None], tok[:, None], blk_r)
    blk_new = jnp.clip(blk_new, -127, 127).astype(pool.dtype)
    pool = pool.at[:, bidx[:, None], :, rows].set(blk_new)
    scales = scales.at[:, bidx, :, page].set(new_s.transpose(1, 0, 2))
    return pool, scales


def quant_verify_kv_update(pool: jax.Array, scales: jax.Array,
                           new: jax.Array, pos: jax.Array,
                           write_mask: Optional[jax.Array],
                           pages: Optional[jax.Array] = None
                           ) -> Tuple[jax.Array, jax.Array]:
    """T-token ring write into an int8 pool — the quantized twin of
    `verify_kv_update`, unrolled as T sequential one-token updates so
    each draft row sees exactly the page scale its sequential decode
    would (T is the spec chunk, <= K+1, so the unroll is tiny).  new:
    (L,B,T,KH,hd); pos: (B,) logical slot of row 0."""
    from repro.core.backstream import physical_slots
    s = pool.shape[3]
    t = new.shape[2]
    for j in range(t):
        slot = (pos + j) % s
        if pages is not None:
            slot = physical_slots(pages, slot, s // pages.shape[1])
        pool, scales = quant_kv_update_stacked(
            pool, scales, new[:, :, j][:, :, :, None, :], slot, write_mask)
    return pool, scales


def quant_kv_write_rows(pool: jax.Array, scales: jax.Array,
                        vals: jax.Array, row: jax.Array, start: jax.Array,
                        prow: jax.Array, ps: int
                        ) -> Tuple[jax.Array, jax.Array]:
    """Scatter T consecutive LOGICAL rows [start, start+T) of batch row
    `row` into an int8 pool + per-page scales — the quantized prefill /
    resume scatter.  pool: (L,B,KH,S,hd) int8; scales: (L,B,KH,nP);
    vals: (L,T,KH,hd) fp; row, start: traced scalars; prow: (nP,) the
    row's logical→physical page map; ps: static page size.

    Page scale rule: a page whose first logical row is at or past
    `start` is wholly (re)written by this call → fresh scale, previous
    junk cleared (ratio 0); the boundary page (start % ps != 0, resume
    only) merges with the restored prefix's scale and re-quantizes the
    prefix rows it keeps.  Junk past the written span stays beyond the
    validity clock as in the fp path."""
    l, b, kh, s, hd = pool.shape
    n_p = scales.shape[3]
    t = vals.shape[1]
    start = jnp.asarray(start, jnp.int32)
    row = jnp.asarray(row, jnp.int32)
    npt = -(-t // ps) + 1                 # candidate pages incl. boundary
    lrows = start + jnp.arange(t, dtype=jnp.int32)        # (T,)
    p0 = start // ps
    pages_t = p0 + jnp.arange(npt, dtype=jnp.int32)       # (npt,) logical
    in_page = pages_t[:, None] == (lrows[None, :] // ps)  # (npt,T)
    live = (pages_t * ps < start + t) & (pages_t < n_p)   # actually touched
    vf = vals.astype(jnp.float32)                         # (L,T,KH,hd)
    amax = jnp.max(jnp.abs(vf), axis=-1)                  # (L,T,KH)
    cand = jnp.max(jnp.where(in_page[None, :, :, None],
                             amax[:, None], 0.0), axis=2) / 127.0  # (L,npt,KH)
    phys_t = jnp.take(prow, jnp.clip(pages_t, 0, n_p - 1))         # (npt,)
    sc_row = lax.dynamic_slice(
        scales, (0, row, 0, 0), (l, 1, kh, n_p))[:, 0]    # (L,KH,nP)
    old = jnp.take(sc_row, phys_t, axis=2).transpose(0, 2, 1)      # (L,npt,KH)
    fresh = pages_t * ps >= start                         # (npt,)
    eff_old = jnp.where(fresh[None, :, None], 0.0, old)
    new_s = jnp.maximum(eff_old, cand)
    r = eff_old / jnp.maximum(new_s, _SCALE_EPS)
    rows_ph = (phys_t[:, None] * ps
               + jnp.arange(ps, dtype=jnp.int32)[None])   # (npt,ps)
    pool_row = lax.dynamic_slice(
        pool, (0, row, 0, 0, 0), (l, 1, kh, s, hd))[:, 0]  # (L,KH,S,hd)
    blk = jnp.take(pool_row, rows_ph.reshape(-1),
                   axis=2).reshape(l, kh, npt, ps, hd)
    blk_r = jnp.rint(blk.astype(jnp.float32)
                     * r.transpose(0, 2, 1)[:, :, :, None, None])
    # quantize each new row under its own page's merged scale
    pi = jnp.clip(lrows // ps - p0, 0, npt - 1)           # (T,)
    scale_t = jnp.take_along_axis(
        new_s, pi[None, :, None], axis=1)                 # (L,T,KH)
    q_rows = jnp.clip(
        jnp.rint(vf / jnp.maximum(scale_t, _SCALE_EPS)[..., None]),
        -127, 127)                                        # (L,T,KH,hd)
    glob = pages_t[:, None] * ps + jnp.arange(ps)[None]   # (npt,ps) logical
    onehot = (glob[:, :, None] == lrows[None, None, :])   # (npt,ps,T)
    contrib = jnp.einsum("abt,ltkd->lkabd",
                         onehot.astype(jnp.float32), q_rows)
    written = onehot.any(axis=2)                          # (npt,ps)
    blk_new = jnp.where(written[None, None, :, :, None], contrib, blk_r)
    blk_new = jnp.clip(blk_new, -127, 127).astype(pool.dtype)
    # untouched candidate pages scatter out of bounds and are dropped
    rows_sc = jnp.where(live[:, None], rows_ph, s).reshape(-1)
    pool_row = pool_row.at[:, :, rows_sc].set(
        blk_new.reshape(l, kh, npt * ps, hd), mode="drop")
    pool = lax.dynamic_update_slice(
        pool, pool_row[:, None], (0, row, 0, 0, 0))
    sc_sc = jnp.where(live, phys_t, n_p)
    sc_row = sc_row.at[:, :, sc_sc].set(
        new_s.transpose(0, 2, 1), mode="drop")
    scales = lax.dynamic_update_slice(
        scales, sc_row[:, None], (0, row, 0, 0))
    return pool, scales


def supports_prefill_into_cache(cfg: ArchConfig) -> bool:
    """Every registered architecture has a real prompt-prefill path into
    the continuous-batching decode cache: attention layers capture per-
    layer K/V, mamba layers capture the (conv_state, ssm_state) pair from
    the chunked SSD scan's final recurrent state, and encoder-decoder
    configs go through `encdec.prefill_into_cache` (encoder pass +
    per-row cross-KV + decoder self-attn prefill).  Kept as a function so
    a future pattern kind degrades loudly instead of silently."""
    if cfg.enc_dec:
        return all(k in ("full", "local") for k in cfg.block_pattern)
    return all(k in ("full", "local", "mamba") for k in cfg.block_pattern)


def _prefill_mamba(cfg: ArchConfig, p: Params, x: jax.Array,
                   length: jax.Array
                   ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Whole-prompt mamba sublayer with recurrent-state capture: the SSD
    scan (kernels.ops.ssd_scan: Pallas on TPU, sequential oracle on CPU)
    returns its final (H, P, N) state and the causal conv exposes its
    trailing width-1 input window, so decode can resume from token
    `length` exactly where `ssd_decode_step` would have landed stepping
    the prompt one token at a time.

    x is the PADDED prompt (B, S, D); `length` masks the junk tail out of
    the recurrence: dt is zeroed past `length`, making the SSD update a
    no-op there (decay exp(0·A) = 1, update dt·x·Bᵀ = 0), and the conv
    state is sliced to the window ending at `length` (zero-padded on the
    left for prompts shorter than the conv width, matching the zero
    initial conv state of the per-token path).

    Returns (x_out (B,S,D), conv_state (B,W-1,d_inner),
    ssm_state (B,NH,P,N) f32)."""
    from repro.kernels import ops
    b, s, _ = x.shape
    nh, hp, width = cfg.n_ssm_heads, cfg.ssm_head_dim, cfg.conv_width
    z, xin, Bm, Cm, dt, A = _mamba_proj(cfg, p, x)
    pad = jnp.concatenate(
        [jnp.zeros((b, width - 1, xin.shape[-1]), xin.dtype), xin], axis=1)
    conv_state = lax.dynamic_slice(
        pad, (0, jnp.asarray(length, jnp.int32), 0),
        (b, width - 1, xin.shape[-1]))
    xc, _ = L.causal_conv1d(xin, p["conv_w"])
    in_prompt = jnp.arange(s) < jnp.asarray(length, jnp.int32)
    dt = jnp.where(in_prompt[None, :, None], dt, 0.0)
    y, ssm_state = ops.ssd_scan(xc.reshape(b, s, nh, hp), dt, A, Bm, Cm)
    y = y + (xc.reshape(b, s, nh, hp)
             * p["D"][None, None, :, None].astype(xc.dtype))
    y = (y.reshape(b, s, -1) * z).astype(x.dtype)
    return x + matmul(y, p["out_proj"]), conv_state, ssm_state


def prefill_into_cache(cfg: ArchConfig, params: Params,
                       cache: Dict[str, Any], tokens: jax.Array,
                       row: jax.Array, length: jax.Array
                       ) -> Tuple[jax.Array, Dict[str, Any]]:
    """Teacher-forced prefill of ONE request's prompt into batch row `row`
    of the decode cache — the real prefill path of the serving loop
    (replacing last-token seeding, which dropped all but one prompt
    token's KV).

    tokens: (P,) int32 padded prompt.  Junk past `length` is fine for
    every layer kind: attention K/V of junk tokens lands at slots >=
    length, which the per-row validity clock keeps invisible until decode
    overwrites them in ring order; mamba layers mask the junk out of the
    recurrence itself (see `_prefill_mamba` — a recurrent state, unlike a
    KV slot, has no validity clock to hide behind).  Attention runs
    through the flash_attention kernel and the SSD scan through ssd_scan
    (ops dispatch: Pallas on TPU, oracle on CPU).  Returns (last-token
    logits (V,), updated cache)."""
    from repro.kernels import ops
    assert not cfg.enc_dec, "enc-dec prefill lives in encdec.prefill_into_cache"
    assert supports_prefill_into_cache(cfg), cfg.arch_id
    p_len = tokens.shape[0]
    x = jnp.take(params["embed"], tokens[None], axis=0)   # (1,P,D)
    positions = jnp.arange(p_len, dtype=jnp.int32)[None]

    def scan_body(x, block_params):
        states = {}
        for pos_i, kind in enumerate(cfg.block_pattern):
            p = block_params[pos_i]
            if kind in ("full", "local"):
                q, k, v = _qkv(cfg, p["attn"], x, positions)
                window = cfg.sliding_window if kind == "local" else 0
                o = ops.flash_attention(q, k, v, causal=True, window=window)
                o = o.reshape(1, p_len, cfg.n_heads * cfg.head_dim_)
                x = x + matmul(o, p["attn"]["wo"])
                states[f"k{pos_i}"] = k.transpose(0, 2, 1, 3)  # (1,KH,P,hd)
                states[f"v{pos_i}"] = v.transpose(0, 2, 1, 3)
            elif kind == "mamba":
                x, conv_s, ssm_s = _prefill_mamba(cfg, p["mamba"], x, length)
                states[f"conv{pos_i}"] = conv_s               # (1,W-1,di)
                states[f"ssm{pos_i}"] = ssm_s                 # (1,NH,P,N)
            if cfg.d_ff > 0:
                x, _ = ffn_layer(cfg, p["ffn"], x, _is_moe_pos(cfg, pos_i))
        return x, states

    x, states = lax.scan(scan_body, x, params["blocks"])  # (L, 1, ...) each
    x = L.rms_norm(x, params["final_ln"], cfg.norm_eps)
    x_last = lax.dynamic_slice_in_dim(x, length - 1, 1, axis=1)  # (1,1,D)
    logits = jnp.einsum("bsd,vd->bsv", x_last, params["embed"])[0, 0]

    row = jnp.asarray(row, jnp.int32)
    out_cache = dict(cache)
    pt = cache.get("page_table")
    for pos_i, kind in enumerate(cfg.block_pattern):
        if kind in ("full", "local"):
            max_seq = cache[f"k{pos_i}"].shape[3]
            assert p_len <= max_seq, (p_len, max_seq)
            keys = (f"k{pos_i}", f"v{pos_i}")
        else:
            keys = (f"conv{pos_i}", f"ssm{pos_i}")
        for key in keys:
            c = cache[key]
            scale_key = key[0] + "scale" + key[1:] if _is_self_kv(key) \
                else None
            if scale_key is not None and scale_key in cache:
                # int8 pool: per-page quantize-scatter of the P prompt
                # rows; every touched page starts fresh (start = 0), so
                # the previous occupant's quants AND scale are cleared
                ps = max_seq // pt.shape[1]
                prow = lax.dynamic_slice(pt, (row, 0), (1, pt.shape[1]))[0]
                vals = states[key][:, 0].transpose(0, 2, 1, 3)  # (L,P,KH,hd)
                out_cache[key], out_cache[scale_key] = quant_kv_write_rows(
                    c, cache[scale_key], vals, row,
                    jnp.zeros((), jnp.int32), prow, ps)
                continue
            upd = states[key].astype(c.dtype)
            if pt is not None and _is_self_kv(key):
                # scatter the P prompt rows through row's page table:
                # logical row r → physical table[row, r//ps]*ps + r%ps
                # (DESIGN.md §9).  Advanced indices (row at axis 1, phys
                # at axis 3) are non-adjacent, so the indexed dims move
                # to the front: the set value is (P, L, KH, hd).
                ps = max_seq // pt.shape[1]
                prow = lax.dynamic_slice(pt, (row, 0), (1, pt.shape[1]))[0]
                lrows = jnp.arange(p_len, dtype=jnp.int32)
                phys = jnp.take(prow, lrows // ps) * ps + lrows % ps
                out_cache[key] = c.at[:, row, :, phys, :].set(
                    upd[:, 0].transpose(2, 0, 1, 3))
            else:
                out_cache[key] = lax.dynamic_update_slice(
                    c, upd, (0, row) + (0,) * (c.ndim - 2))
    return logits, out_cache


# --------------------------------------------------------------------------
# Per-slot cache pages: extract / insert (host-tier offload, DESIGN.md §8)
# --------------------------------------------------------------------------

def _is_self_kv(key: str) -> bool:
    """Self-attention KV leaves are named k{pos}/v{pos}; conv{pos},
    ssm{pos}, cross_k/cross_v and enc_pos are everything else."""
    return key[0] in ("k", "v") and key[1:].isdigit()


def _is_kv_scale(key: str) -> bool:
    """Per-page scale leaves of an int8 KV cache: kscale{pos}/vscale{pos}
    (DESIGN.md §10)."""
    return key[:6] in ("kscale", "vscale") and key[6:].isdigit()


def extract_slot_cache(cfg: ArchConfig, cache: Dict[str, Any],
                       row: jax.Array, upto: Optional[int] = None
                       ) -> Dict[str, Any]:
    """Slice batch row `row` out of every cache leaf — ONE request's
    cache pages, the unit the host tier evicts and the prefix cache
    stores (DESIGN.md §8).  Covers every leaf kind by shape dispatch:
    5-dim KV / cross-KV panels and 4-dim conv windows keep a size-1
    batch axis at position 1; the 1-dim `enc_pos` clock is sliced on
    axis 0; the scalar `pos` counter is per-BATCH bookkeeping of the
    single-sequence path and is excluded (per-slot serving never reads
    it), as is the `page_table` leaf — physical placement is a property
    of the batch the slot sits in, not of the request.

    Paged caches (DESIGN.md §9): self-attention KV leaves come out as
    6-dim PAGE SETS (L, 1, KH, n_pages, page, hd), pages gathered in
    LOGICAL order — the extract is placement-independent, so the host
    tier moves page sets without repacking and `insert_slot_cache` can
    scatter them through ANY destination row's table.  `upto` (static)
    truncates self-attention KV leaves to their first `upto` sequence
    rows — the prefix-page slice; by causality those rows depend only
    on prompt tokens [0, upto), so a stored prefix page is exact for
    ANY continuation.  On a paged cache the cut rounds UP to whole
    pages (ceil(upto / page) pages); the sub-page junk tail is
    invisible under the resume validity `slot < start`, the same
    junk-beyond-clock argument as padded-prompt prefill.  `row` may be
    traced (one jit trace serves every slot)."""
    row = jnp.asarray(row, jnp.int32)
    pt = cache.get("page_table")
    out: Dict[str, Any] = {}
    for key, leaf in cache.items():
        if key in ("pos", "page_table"):
            continue
        if leaf.ndim == 1:                            # enc_pos (B,)
            out[key] = lax.dynamic_slice(leaf, (row,), (1,))
            continue
        sizes = (leaf.shape[0], 1) + leaf.shape[2:]
        sl = lax.dynamic_slice(
            leaf, (0, row) + (0,) * (leaf.ndim - 2), sizes)
        if _is_kv_scale(key) and pt is not None:
            # per-page scales travel with their pages: gather to LOGICAL
            # page order (axis 3 is the physical page axis) and truncate
            # to the same ceil(upto/ps) pages as the KV page set
            prow = lax.dynamic_slice(pt, (row, 0), (1, pt.shape[1]))[0]
            sl = jnp.take(sl, prow, axis=3)
            if upto is not None:
                ps = cache["k" + key[6:]].shape[3] // leaf.shape[3]
                sl = sl[:, :, :, :-(-upto // ps)]
            out[key] = sl
            continue
        if _is_self_kv(key) and pt is not None:
            n_p = pt.shape[1]
            l, _, kh, s, hd = leaf.shape
            ps = s // n_p
            prow = lax.dynamic_slice(pt, (row, 0), (1, n_p))[0]  # (n_p,)
            slr = sl.reshape(l, 1, kh, n_p, ps, hd)
            sl = jnp.take(slr, prow, axis=3)          # logical page order
            if upto is not None:
                sl = sl[:, :, :, :-(-upto // ps)]     # ceil to whole pages
        elif upto is not None and _is_self_kv(key):
            sl = sl[:, :, :, :upto]
        out[key] = sl
    return out


def insert_slot_cache(cfg: ArchConfig, cache: Dict[str, Any],
                      leaves: Dict[str, Any], row: jax.Array
                      ) -> Dict[str, Any]:
    """Write extracted slot pages back into batch row `row` — the
    restore half of the evict→restore round trip.  Leaves may be the
    full-slot extract OR a prefix-truncated KV page set (`upto` rows):
    a short KV leaf writes rows [0, upto) and leaves the tail as the
    previous occupant's junk, invisible under the per-row validity
    clock until ring writes overwrite it (the same junk-beyond-clock
    argument as padded-prompt prefill).  Inverse of
    `extract_slot_cache` leaf-for-leaf (bitwise: pure data movement,
    asserted in tests/test_cache_offload.py).

    Paged caches (DESIGN.md §9): 6-dim self-KV page sets (logical page
    order, see `extract_slot_cache`) are scattered through the
    DESTINATION row's page table — logical page i of the set lands at
    physical page table[row, i] — so a page set extracted under one
    placement restores exactly under any other.  A legacy 5-dim dense
    self-KV leaf is likewise routed row-by-row through the table."""
    row = jnp.asarray(row, jnp.int32)
    pt = cache.get("page_table")
    out = dict(cache)
    for key, val in leaves.items():
        c = cache[key]
        val = jnp.asarray(val).astype(c.dtype)
        if c.ndim == 1:
            out[key] = lax.dynamic_update_slice(c, val, (row,))
        elif _is_kv_scale(key) and pt is not None:
            # logical-order scale set → scatter through the DEST row's
            # page table, mirroring the KV page-set scatter below
            n_p = c.shape[3]
            prow = lax.dynamic_slice(pt, (row, 0), (1, n_p))[0]
            n_sel = val.shape[3]
            out[key] = c.at[:, row, :, prow[:n_sel]].set(
                val[:, 0].transpose(2, 0, 1))
        elif _is_self_kv(key) and pt is not None:
            l, b, kh, s, hd = c.shape
            n_p = pt.shape[1]
            ps = s // n_p
            prow = lax.dynamic_slice(pt, (row, 0), (1, n_p))[0]   # (n_p,)
            if val.ndim == 6:
                # page set: scatter whole pages through the dest table.
                # Advanced indices (row at axis 1, dest pages at axis 3)
                # are non-adjacent → indexed dims lead: value is
                # (n_sel, L, KH, page, hd).
                n_sel = val.shape[3]
                cr = c.reshape(l, b, kh, n_p, ps, hd)
                cr = cr.at[:, row, :, prow[:n_sel], :, :].set(
                    val[:, 0].transpose(2, 0, 1, 3, 4))
                out[key] = cr.reshape(l, b, kh, s, hd)
            else:
                u = val.shape[3]
                lrows = jnp.arange(u, dtype=jnp.int32)
                phys = jnp.take(prow, lrows // ps) * ps + lrows % ps
                out[key] = c.at[:, row, :, phys, :].set(
                    val[:, 0].transpose(2, 0, 1, 3))
        else:
            out[key] = lax.dynamic_update_slice(
                c, val, (0, row) + (0,) * (c.ndim - 2))
    return out


# --------------------------------------------------------------------------
# Resume prefill: continue a prompt from restored prefix pages (§8)
# --------------------------------------------------------------------------

def _resume_attention(cfg: ArchConfig, q: jax.Array, k: jax.Array,
                      v: jax.Array, k_row: jax.Array, v_row: jax.Array,
                      start: jax.Array, window: int) -> jax.Array:
    """Suffix-query attention as a two-partial softmax merge: partial A
    reads the slot's RESTORED prefix KV rows [0, start) straight from
    the cache page (validity `slot < start`, plus the sliding-window
    bound under the global query positions start+t), partial B is
    causal attention within the suffix itself.  Merging the (acc, m, l)
    statistics reproduces full-prompt softmax attention exactly in
    exact arithmetic — the same flash-decoding merge identity the
    decode path rests on; in floats the reduction ORDER differs from
    the one-pass prefill kernel, so resumed prefill is token-equal but
    not bitwise for attention layers (mamba resume IS bitwise — the
    recurrence continues from the exact restored state).

    q: (1,T,H,hd); k/v: (1,T,KH,hd) suffix; k_row/v_row: (1,KH,S,hd)
    restored page; start: traced prefix length.  Returns (1,T,H,hd)."""
    b, t, h, hd = q.shape
    kh = k.shape[2]
    g = h // kh
    s = k_row.shape[2]
    qf = (q.astype(jnp.float32) * hd ** -0.5).reshape(b, t, kh, g, hd)
    gpos = start + jnp.arange(t, dtype=jnp.int32)          # global q positions
    slots = jnp.arange(s, dtype=jnp.int32)
    # partial A: the restored prefix rows
    s1 = jnp.einsum("btkgd,bksd->btkgs", qf, k_row.astype(jnp.float32))
    valid = jnp.broadcast_to(slots[None, :] < start, (t, s))
    if window > 0:
        valid &= slots[None, :] > gpos[:, None] - window
    s1 = jnp.where(valid[None, :, None, None, :], s1, L.NEG_INF)
    m1 = jnp.max(s1, axis=-1)
    p1 = jnp.where(valid[None, :, None, None, :],
                   jnp.exp(s1 - m1[..., None]), 0.0)
    l1 = jnp.sum(p1, axis=-1)
    acc1 = jnp.einsum("btkgs,bksd->btkgd", p1, v_row.astype(jnp.float32))
    # partial B: causal attention within the suffix (query u attends
    # suffix keys <= u; every query attends itself, so l > 0 always)
    s2 = jnp.einsum("btkgd,bukd->btkgu", qf, k.astype(jnp.float32))
    tri = jnp.arange(t)
    cmask = tri[None, :] <= tri[:, None]
    if window > 0:
        cmask &= tri[None, :] > tri[:, None] - window
    s2 = jnp.where(cmask[None, :, None, None, :], s2, L.NEG_INF)
    m2 = jnp.max(s2, axis=-1)
    p2 = jnp.where(cmask[None, :, None, None, :],
                   jnp.exp(s2 - m2[..., None]), 0.0)
    l2 = jnp.sum(p2, axis=-1)
    acc2 = jnp.einsum("btkgu,bukd->btkgd", p2, v.astype(jnp.float32))
    m = jnp.maximum(m1, m2)
    acc = acc1 * jnp.exp(m1 - m)[..., None] \
        + acc2 * jnp.exp(m2 - m)[..., None]
    l = l1 * jnp.exp(m1 - m) + l2 * jnp.exp(m2 - m)
    out = acc / jnp.maximum(l, 1e-20)[..., None]
    return out.reshape(b, t, h, hd).astype(q.dtype)


def _resume_mamba(cfg: ArchConfig, p: Params, x: jax.Array,
                  conv0: jax.Array, ssm0: jax.Array,
                  suffix_len: jax.Array
                  ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """`_prefill_mamba` continued from a restored recurrent state: the
    causal conv runs with the restored width-1 input window as its
    initial state and the SSD scan seeds `init_state` with the restored
    (NH, P, N) state — on the sequential CPU oracle this is bitwise the
    full-prompt prefill (the recurrence visits identical states).  dt is
    zeroed past the TRUE suffix length and the new conv window is
    sliced at it, exactly as `_prefill_mamba` masks its padded tail."""
    from repro.kernels import ops
    b, s, _ = x.shape
    nh, hp, width = cfg.n_ssm_heads, cfg.ssm_head_dim, cfg.conv_width
    z, xin, Bm, Cm, dt, A = _mamba_proj(cfg, p, x)
    conv0 = conv0.astype(xin.dtype)
    pad = jnp.concatenate([conv0, xin], axis=1)
    conv_state = lax.dynamic_slice(
        pad, (0, jnp.asarray(suffix_len, jnp.int32), 0),
        (b, width - 1, xin.shape[-1]))
    xc, _ = L.causal_conv1d(xin, p["conv_w"], conv0)
    in_suffix = jnp.arange(s) < jnp.asarray(suffix_len, jnp.int32)
    dt = jnp.where(in_suffix[None, :, None], dt, 0.0)
    y, ssm_state = ops.ssd_scan(xc.reshape(b, s, nh, hp), dt, A, Bm, Cm,
                                ssm0.astype(jnp.float32))
    y = y + (xc.reshape(b, s, nh, hp)
             * p["D"][None, None, :, None].astype(xc.dtype))
    y = (y.reshape(b, s, -1) * z).astype(x.dtype)
    return x + matmul(y, p["out_proj"]), conv_state, ssm_state


def resume_prefill_into_cache(cfg: ArchConfig, params: Params,
                              cache: Dict[str, Any], tokens: jax.Array,
                              row: jax.Array, length: jax.Array,
                              start: jax.Array
                              ) -> Tuple[jax.Array, Dict[str, Any]]:
    """Prefill ONLY the suffix of a prompt whose first `start` tokens'
    cache pages were just restored from the host tier (prefix-cache
    partial hit, DESIGN.md §8) — the prefill-compute skip the prefix
    cache exists to buy.

    tokens: (Ps,) padded SUFFIX tokens (prompt[start:], bucket-padded);
    length: TRUE total prompt length (start + true suffix length);
    start: prefix length — both traced, so one trace serves every
    (suffix bucket) shape.  Row `row`'s cache must already hold the
    restored pages: KV rows [0, start) and the post-prefix (conv, ssm)
    recurrent state.  The caller guarantees start + Ps <= max_seq (a
    clamped dynamic_update_slice would silently shift the KV writes).

    Attention layers merge a restored-prefix partial with a causal
    suffix partial (`_resume_attention` — token-equal to full prefill,
    not bitwise); mamba layers continue the recurrence from the
    restored state (`_resume_mamba` — bitwise on the sequential
    oracle).  Suffix junk past `length` is handled exactly as in
    `prefill_into_cache`: KV junk lands at slots >= length (invisible
    under the validity clock), recurrent junk is masked out of the
    recurrence itself.  Returns (last-token logits (V,), cache)."""
    assert not cfg.enc_dec, \
        "prefix resume is decoder-only (enc-dec prompts are keyed on audio)"
    assert supports_prefill_into_cache(cfg), cfg.arch_id
    t_len = tokens.shape[0]
    row = jnp.asarray(row, jnp.int32)
    start = jnp.asarray(start, jnp.int32)
    suffix_len = jnp.asarray(length, jnp.int32) - start
    x = jnp.take(params["embed"], tokens[None], axis=0)   # (1,Ps,D)
    positions = (start + jnp.arange(t_len, dtype=jnp.int32))[None]
    # the slot's restored pages ride the layer scan as READ-ONLY xs; on
    # a paged cache the self-KV leaves arrive as 6-dim page sets in
    # LOGICAL order, so collapsing (n_pages, page) → S recovers the
    # logical-dense row the two-partial merge expects (DESIGN.md §9)
    row_cache = extract_slot_cache(cfg, cache, row)

    def scan_body(x, inp):
        block_params, blk_row = inp
        states = {}
        for pos_i, kind in enumerate(cfg.block_pattern):
            p = block_params[pos_i]
            if kind in ("full", "local"):
                q, k, v = _qkv(cfg, p["attn"], x, positions)
                window = cfg.sliding_window if kind == "local" else 0
                k_row, v_row = blk_row[f"k{pos_i}"], blk_row[f"v{pos_i}"]
                if f"kscale{pos_i}" in blk_row:
                    # int8 page set: dequantize under the restored
                    # per-page scales (logical order matches the pages)
                    k_row = (k_row.astype(jnp.float32)
                             * blk_row[f"kscale{pos_i}"][..., None, None])
                    v_row = (v_row.astype(jnp.float32)
                             * blk_row[f"vscale{pos_i}"][..., None, None])
                if k_row.ndim == 5:                   # (1,KH,n_p,ps,hd)
                    k_row = k_row.reshape(k_row.shape[:2] + (-1,)
                                          + k_row.shape[4:])
                    v_row = v_row.reshape(v_row.shape[:2] + (-1,)
                                          + v_row.shape[4:])
                o = _resume_attention(cfg, q, k, v, k_row, v_row,
                                      start, window)
                x = x + matmul(o.reshape(1, t_len, -1), p["attn"]["wo"])
                states[f"k{pos_i}"] = k.transpose(0, 2, 1, 3)
                states[f"v{pos_i}"] = v.transpose(0, 2, 1, 3)
            elif kind == "mamba":
                x, conv_s, ssm_s = _resume_mamba(
                    cfg, p["mamba"], x, blk_row[f"conv{pos_i}"],
                    blk_row[f"ssm{pos_i}"], suffix_len)
                states[f"conv{pos_i}"] = conv_s
                states[f"ssm{pos_i}"] = ssm_s
            if cfg.d_ff > 0:
                x, _ = ffn_layer(cfg, p["ffn"], x, _is_moe_pos(cfg, pos_i))
        return x, states

    x, states = lax.scan(scan_body, x, (params["blocks"], row_cache))
    x = L.rms_norm(x, params["final_ln"], cfg.norm_eps)
    x_last = lax.dynamic_slice_in_dim(x, suffix_len - 1, 1, axis=1)
    logits = jnp.einsum("bsd,vd->bsv", x_last, params["embed"])[0, 0]

    out_cache = dict(cache)
    pt = cache.get("page_table")
    for pos_i, kind in enumerate(cfg.block_pattern):
        if kind in ("full", "local"):
            # suffix KV rows land at logical sequence offset `start`
            for key in (f"k{pos_i}", f"v{pos_i}"):
                c = cache[key]
                scale_key = key[0] + "scale" + key[1:]
                if scale_key in cache:
                    # int8 pool: quantize-scatter the suffix; the
                    # boundary page merges with the restored prefix's
                    # scale, later pages start fresh
                    s = c.shape[3]
                    ps = s // pt.shape[1]
                    prow = lax.dynamic_slice(
                        pt, (row, 0), (1, pt.shape[1]))[0]
                    vals = states[key][:, 0].transpose(0, 2, 1, 3)
                    c, sc = quant_kv_write_rows(
                        c, cache[scale_key], vals, row, start, prow, ps)
                    out_cache[key] = c
                    out_cache[scale_key] = sc
                    continue
                if pt is not None:
                    s = c.shape[3]
                    ps = s // pt.shape[1]
                    prow = lax.dynamic_slice(
                        pt, (row, 0), (1, pt.shape[1]))[0]
                    lrows = start + jnp.arange(t_len, dtype=jnp.int32)
                    phys = jnp.take(prow, lrows // ps) * ps + lrows % ps
                    out_cache[key] = c.at[:, row, :, phys, :].set(
                        states[key].astype(c.dtype)[:, 0]
                        .transpose(2, 0, 1, 3))
                else:
                    out_cache[key] = lax.dynamic_update_slice(
                        c, states[key].astype(c.dtype),
                        (0, row, 0, start, 0))
        else:
            for key in (f"conv{pos_i}", f"ssm{pos_i}"):
                c = cache[key]
                out_cache[key] = lax.dynamic_update_slice(
                    c, states[key].astype(c.dtype),
                    (0, row) + (0,) * (c.ndim - 2))
    return logits, out_cache
