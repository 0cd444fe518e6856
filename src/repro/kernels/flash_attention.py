"""Pallas TPU flash attention (prefill) and partial decode attention.

The paper offloads the *attention block* of LLM inference to the
memory-side compute (Table I).  On TPU the analogue is running attention
where the KV bytes live; these kernels are the compute hot-spot of that
offload:

  * `flash_attention_kernel` — causal / sliding-window GQA flash attention
    with online softmax.  Grid (B, H, n_q, n_k): the KV axis is innermost
    and accumulates partial-softmax statistics in VMEM scratch, exactly
    the (acc, m, l) statistic stream that the back-streaming protocol
    ships between shards.
  * `decode_partial_kernel` — single-token attention over one KV chunk,
    emitting the raw (acc, m, l) partials.  This is the producer-side
    task of `repro.core.backstream.decode_attention_combined`.
  * `decode_fused_kernel` — ONE-SHOT flash decode: grid (B, KH, n_chunks)
    with the chunk axis innermost and accumulating, so the partial-softmax
    (acc, m, l) statistics live in VMEM scratch across the whole KV
    sequence and the merged statistics are written exactly once, for
    one elementwise normalization (the oracle's own epilogue).  No
    per-chunk kernel launches, no per-chunk (acc, m, l) HBM round
    trips, no separate XLA merge.  Supports GQA, sliding windows,
    *per-batch-row* positions (a (B,) pos vector, required for
    continuous batching where slots sit at different sequence offsets),
    paged and int8 KV pools, and an optional extra
    partial (the current token's own (acc, m, l), merged in the epilogue
    so the cache can stay read-only during the layer scan).

Softmax scale: every kernel here multiplies q by head_dim ** -0.5 (the
prefill kernel's `scale` defaults to it).  A model with another scale
(Granite's `attention_multiplier`) folds the ratio into q before the call
(`transformer._qkv`, `ArchConfig.attn_scale`), so the kernels stay as
they are and the product is the model's scale.

VMEM budget per grid cell (bf16 inputs, f32 scratch):
  q (blk_q, hd) + k,v (blk_k, hd) + acc (blk_q, hd) + p (blk_q, blk_k).
With blk_q = blk_k = 128 and hd = 128 that is ~0.3 MB — far below the
~16 MB VMEM of a v5e core, leaving room for XLA's double buffering.
All matmul dims are multiples of 128 => MXU-aligned.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref as _ref

NEG_INF = -1e30


# --------------------------------------------------------------------------
# Prefill flash attention
# --------------------------------------------------------------------------

def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, blk_q: int, blk_k: int, causal: bool,
                  window: int, n_k: int):
    i = pl.program_id(2)          # q block
    j = pl.program_id(3)          # kv block (innermost, accumulating)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale            # (blk_q, hd)
    k = k_ref[0, 0].astype(jnp.float32)                    # (blk_k, hd)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)

    qpos = i * blk_q + lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
    kpos = j * blk_k + lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
    mask = jnp.ones((blk_q, blk_k), jnp.bool_)
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[:, None])
    p = jnp.where(mask, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
    acc_ref[...] = (acc_ref[...] * alpha[:, None]
                    + jax.lax.dot(p, v, preferred_element_type=jnp.float32))
    m_ref[...] = m_new

    @pl.when(j == n_k - 1)
    def _finish():
        l = l_ref[...]
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l, 1e-20)[:, None]).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    scale: Optional[float] = None,
                    blk_q: int = 128, blk_k: int = 128,
                    interpret: bool = False) -> jax.Array:
    """q: (B,S,H,hd); k,v: (B,S,KH,hd) -> (B,S,H,hd).  GQA supported."""
    b, s, h, hd = q.shape
    kh = k.shape[2]
    assert h % kh == 0
    group = h // kh
    blk_q = min(blk_q, s)
    blk_k = min(blk_k, s)
    assert s % blk_q == 0 and s % blk_k == 0, (s, blk_q, blk_k)
    n_q, n_k = s // blk_q, s // blk_k
    scale = scale if scale is not None else hd ** -0.5

    # (B,H,S,hd) layout so the (q block, kv block) tiles are contiguous.
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _flash_kernel, scale=scale, blk_q=blk_q, blk_k=blk_k,
        causal=causal, window=window, n_k=n_k)

    out = pl.pallas_call(
        kernel,
        grid=(b, h, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, blk_q, hd), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, blk_k, hd),
                         lambda b_, h_, i, j: (b_, h_ // group, j, 0)),
            pl.BlockSpec((1, 1, blk_k, hd),
                         lambda b_, h_, i, j: (b_, h_ // group, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, blk_q, hd),
                               lambda b_, h_, i, j: (b_, h_, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q, hd), jnp.float32),
            pltpu.VMEM((blk_q,), jnp.float32),
            pltpu.VMEM((blk_q,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)


# --------------------------------------------------------------------------
# Decode: partial-softmax statistics over one KV chunk
# --------------------------------------------------------------------------

def _decode_partial_kernel(q_ref, k_ref, v_ref, valid_ref,
                           acc_ref, m_ref, l_ref,
                           acc_s, m_s, l_s, *,
                           scale: float, blk_c: int, n_c: int):
    j = pl.program_id(2)          # chunk block (innermost)

    @pl.when(j == 0)
    def _init():
        acc_s[...] = jnp.zeros_like(acc_s)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    q = q_ref[0, 0].astype(jnp.float32) * scale           # (group, hd)
    k = k_ref[0, 0].astype(jnp.float32)                   # (blk_c, hd)
    v = v_ref[0, 0].astype(jnp.float32)
    valid = valid_ref[0, 0] != 0                          # (blk_c,)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = jnp.where(valid[None, :], s, NEG_INF)

    m_prev = m_s[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.where(valid[None, :], jnp.exp(s - m_new[:, None]), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_s[...] = l_s[...] * alpha + jnp.sum(p, axis=-1)
    acc_s[...] = (acc_s[...] * alpha[:, None]
                  + jax.lax.dot(p, v, preferred_element_type=jnp.float32))
    m_s[...] = m_new

    @pl.when(j == n_c - 1)
    def _finish():
        acc_ref[0, 0] = acc_s[...]
        # NEG_INF sentinel -> -inf so the merge ignores empty partials.
        m = m_s[...]
        m_ref[0, 0, 0] = jnp.where(m <= NEG_INF / 2, -jnp.inf, m)
        l_ref[0, 0, 0] = l_s[...]


def decode_attention_partial(q: jax.Array, k: jax.Array, v: jax.Array,
                             valid: jax.Array, *, blk_c: int = 128,
                             interpret: bool = False
                             ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """q: (B,1,H,hd); k,v: (B,KH,C,hd) — flash-decoding cache layout;
    valid: (B,C) bool.
    Returns (acc (B,H,hd) f32, m (B,H) f32, l (B,H) f32).

    TPU block layout: every block's trailing two dims are either
    (8, 128)-tiled or the array's own, so the mask rides as (B, 1, C)
    int32 (a (1, blk_c) lane row per grid cell) and the per-(row, head)
    m/l outputs as (B, KH, 1, group) (one full (1, group) row)."""
    b, _, h, hd = q.shape
    kh, c = k.shape[1], k.shape[2]
    group = h // kh
    blk_c = min(blk_c, c)
    assert c % blk_c == 0
    n_c = c // blk_c
    scale = hd ** -0.5

    qt = q[:, 0].reshape(b, kh, group, hd)                # (B,KH,group,hd)
    valid3 = valid.astype(jnp.int32).reshape(b, 1, c)

    kernel = functools.partial(_decode_partial_kernel, scale=scale,
                               blk_c=blk_c, n_c=n_c)
    acc, m, l = pl.pallas_call(
        kernel,
        grid=(b, kh, n_c),
        in_specs=[
            pl.BlockSpec((1, 1, group, hd), lambda b_, h_, j: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, blk_c, hd), lambda b_, h_, j: (b_, h_, j, 0)),
            pl.BlockSpec((1, 1, blk_c, hd), lambda b_, h_, j: (b_, h_, j, 0)),
            pl.BlockSpec((1, 1, blk_c), lambda b_, h_, j: (b_, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, group, hd), lambda b_, h_, j: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, 1, group), lambda b_, h_, j: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, 1, group), lambda b_, h_, j: (b_, h_, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, kh, group, hd), jnp.float32),
            jax.ShapeDtypeStruct((b, kh, 1, group), jnp.float32),
            jax.ShapeDtypeStruct((b, kh, 1, group), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((group, hd), jnp.float32),
            pltpu.VMEM((group,), jnp.float32),
            pltpu.VMEM((group,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qt, k, v, valid3)
    return (acc.reshape(b, h, hd), m.reshape(b, h), l.reshape(b, h))


# --------------------------------------------------------------------------
# Decode: fused one-shot flash decode (produce + merge + normalize)
# --------------------------------------------------------------------------

def _decode_fused_kernel(pos_ref, *rest, scale: float, blk_c: int,
                         n_c: int, window: int,
                         group: int, paged: bool, has_extra: bool,
                         has_scales: bool):
    # scalar-prefetch operands come first: pos (B,), then the page table
    rest = list(rest)
    tbl_ref = rest.pop(0) if paged else None
    q_ref, k_ref, v_ref = rest[:3]
    rest = rest[3:]
    if has_scales:
        ks_ref, vs_ref = rest[:2]
        rest = rest[2:]
    if has_extra:
        acc_e_ref, m_e_ref, l_e_ref = rest[:3]
        rest = rest[3:]
    acc_ref, m_ref, l_ref, acc_s, m_s, l_s = rest
    b = pl.program_id(0)
    j = pl.program_id(2)          # chunk block (innermost, accumulating)

    @pl.when(j == 0)
    def _init():
        acc_s[...] = jnp.zeros_like(acc_s)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    pos = pos_ref[b]                                      # this row's offset
    q = q_ref[0, 0].astype(jnp.float32) * scale           # (group, hd)
    k = k_ref[0, 0].astype(jnp.float32)                   # (blk_c, hd)
    v = v_ref[0, 0].astype(jnp.float32)
    if has_scales:
        # int8 KV page: the per-(head, page) scale rides the SAME
        # indirection as the page itself, so dequantization happens in
        # VMEM on the tile just DMA'd — fp pages never exist in HBM.
        page = tbl_ref[b, j] if paged else j
        k = k * ks_ref[0, 0, page]
        v = v * vs_ref[0, 0, page]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    kpos = j * blk_c + lax.broadcasted_iota(jnp.int32, (group, blk_c), 1)
    valid = kpos <= pos
    if window > 0:
        valid &= kpos > pos - window
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_s[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.where(valid, jnp.exp(s - m_new[:, None]), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_s[...] = l_s[...] * alpha + jnp.sum(p, axis=-1)
    acc_s[...] = (acc_s[...] * alpha[:, None]
                  + jax.lax.dot(p, v, preferred_element_type=jnp.float32))
    m_s[...] = m_new

    @pl.when(j == n_c - 1)
    def _finish():
        acc = acc_s[...]
        m = m_s[...]
        l = l_s[...]
        if has_extra:
            # merge the current token's own (acc, m, l) partial in VMEM —
            # the epilogue of the back-streaming merge, fused in-kernel.
            m_e = m_e_ref[0, 0, 0]
            mm = jnp.maximum(m, m_e)
            a1 = jnp.exp(m - mm)
            a2 = jnp.exp(m_e - mm)
            acc = acc * a1[:, None] + acc_e_ref[0, 0] * a2[:, None]
            l = l * a1 + l_e_ref[0, 0, 0] * a2
            m = mm
        acc_ref[0, 0] = acc
        # NEG_INF sentinel -> -inf so a later merge ignores empty partials
        m_ref[0, 0, 0] = jnp.where(m <= NEG_INF / 2, -jnp.inf, m)
        l_ref[0, 0, 0] = l


def decode_attention_fused(q: jax.Array, k: jax.Array, v: jax.Array,
                           pos: jax.Array,
                           extra: Optional[Tuple[jax.Array, jax.Array,
                                                 jax.Array]] = None,
                           *, window: int = 0, blk_c: int = 128,
                           pages: Optional[jax.Array] = None,
                           kv_scales: Optional[Tuple[jax.Array, jax.Array]]
                           = None,
                           interpret: bool = False) -> jax.Array:
    """`decode_attention_fused_partial` followed by the shared
    normalization epilogue (`ref.normalize_fused_partial`) — the same
    split as the oracle's, so a head-group-sharded mesh that normalizes
    after its all_gather reproduces this output bit for bit (DESIGN.md
    §11).  Returns (B,1,H,hd) q.dtype."""
    acc, _, l = decode_attention_fused_partial(
        q, k, v, pos, extra, window=window, blk_c=blk_c, pages=pages,
        kv_scales=kv_scales, interpret=interpret)
    return _ref.normalize_fused_partial(acc, l, q.dtype)


def decode_attention_fused_partial(
        q: jax.Array, k: jax.Array, v: jax.Array, pos: jax.Array,
        extra: Optional[Tuple[jax.Array, jax.Array, jax.Array]] = None,
        *, window: int = 0, blk_c: int = 128,
        pages: Optional[jax.Array] = None,
        kv_scales: Optional[Tuple[jax.Array, jax.Array]] = None,
        interpret: bool = False
        ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One-shot flash decode: q (B,1,H,hd) against the whole KV cache
    k/v (B,KH,S,hd), with per-batch-row positions pos (B,) (or a scalar,
    broadcast), masked to slots `pos-window < slot <= pos` (window=0 =>
    no lower bound).  `extra` is an optional (acc (B,H,hd) f32, m (B,H),
    l (B,H)) partial merged in the epilogue.  Returns the merged raw
    statistics (acc (B,H,hd) f32, m (B,H) f32, l (B,H) f32).

    ONE pallas_call for the whole sequence: the chunk axis is the
    innermost grid dimension and (acc, m, l) accumulate in VMEM scratch,
    so there are no per-chunk launches and no partial-statistic HBM
    round trips (vs the lax.map + XLA-merge fallback).

    `pages`: optional (B, n_log) int32 page table (DESIGN.md §9).  A page
    IS a kernel chunk: the grid's chunk axis iterates the n_log LOGICAL
    pages in order and each one is DMA'd from physical chunk
    `pages[b, j]` of the k/v pool via scalar-prefetch-driven BlockSpec
    index maps.  `blk_c` must then be the exact page size (a divisor of
    the pool's seq axis; no divisor search).  `pos`, `window` and the
    masking iota keep their LOGICAL meaning, so the reduction order —
    and therefore the float result, bit for bit — is identical to the
    dense kernel on the logically-gathered cache for ANY physical
    placement.  Table entries past a row's valid length must merely be
    in-bounds page ids; validity masks their lanes out.

    `kv_scales`: optional (k_scales, v_scales), each (B, KH, S/blk_c)
    f32 — k/v are then int8 pools holding quantized pages and each tile
    is dequantized in VMEM right after its DMA, with the scale fetched
    through the SAME page indirection (DESIGN.md §10).  The scale page
    width must equal the kernel chunk (enforced below).

    TPU block layout: per-row scalars (`pos`, the page table) ride
    scalar prefetch into SMEM, the int8 scales reach SMEM one (row,
    head)'s page row at a time, and the per-(row, head) `extra`
    statistics are laid out (B, KH, 1, group) so each block spans the
    array's own trailing dims."""
    b, _, h, hd = q.shape
    kh, s = k.shape[1], k.shape[2]
    assert h % kh == 0
    group = h // kh
    if kv_scales is not None:
        n_sc = kv_scales[0].shape[2]
        assert s % n_sc == 0, (s, n_sc)
        if pages is None:
            blk_c = s // n_sc     # the scale page IS the kernel chunk
        else:
            assert blk_c == s // n_sc, (blk_c, s, n_sc)
    if pages is None:
        blk_c = max(1, min(blk_c, s))
        while s % blk_c:          # largest divisor of s not above blk_c
            blk_c -= 1
        n_c = s // blk_c
    else:
        # paged: blk_c IS the page size, exact; the chunk axis spans the
        # logical page list, not the physical pool
        assert s % blk_c == 0, (s, blk_c)
        n_c = pages.shape[1]
    scale = hd ** -0.5
    paged = pages is not None

    qt = q[:, 0].reshape(b, kh, group, hd)                # (B,KH,group,hd)
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))

    kernel = functools.partial(
        _decode_fused_kernel, scale=scale, blk_c=blk_c, n_c=n_c,
        window=window, group=group, paged=paged,
        has_extra=extra is not None, has_scales=kv_scales is not None)

    # index maps take the scalar-prefetch refs (pos, [table]) trailing;
    # only the k/v chunk map consults the table
    if paged:
        def chunk_map(b_, h_, j, pos_ref, t):
            return (b_, h_, t[b_, j], 0)
    else:
        def chunk_map(b_, h_, j, pos_ref):
            return (b_, h_, j, 0)

    def head_map(b_, h_, j, *_):
        return (b_, h_, 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, group, hd), head_map),
        pl.BlockSpec((1, 1, blk_c, hd), chunk_map),
        pl.BlockSpec((1, 1, blk_c, hd), chunk_map),
    ]
    args = [qt, k, v]
    if kv_scales is not None:
        # one (row, head)'s page scales per grid cell, as a (1, n_pages)
        # SMEM row; the body picks its page's scalar
        n_sc = kv_scales[0].shape[2]
        args += [sc.astype(jnp.float32).reshape(b * kh, 1, n_sc)
                 for sc in kv_scales]
        in_specs += [pl.BlockSpec(
            (1, 1, n_sc), lambda b_, h_, j, *_: (b_ * kh + h_, 0, 0),
            memory_space=pltpu.SMEM)] * 2
    if extra is not None:
        acc_e, m_e, l_e = extra
        args += [acc_e.astype(jnp.float32).reshape(b, kh, group, hd),
                 m_e.astype(jnp.float32).reshape(b, kh, 1, group),
                 l_e.astype(jnp.float32).reshape(b, kh, 1, group)]
        in_specs += [
            pl.BlockSpec((1, 1, group, hd), head_map),
            pl.BlockSpec((1, 1, 1, group), head_map),
            pl.BlockSpec((1, 1, 1, group), head_map),
        ]

    prefetch = [pos_b] + ([pages.astype(jnp.int32)] if paged else [])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch), grid=(b, kh, n_c),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, group, hd), head_map),
            pl.BlockSpec((1, 1, 1, group), head_map),
            pl.BlockSpec((1, 1, 1, group), head_map),
        ],
        scratch_shapes=[
            pltpu.VMEM((group, hd), jnp.float32),
            pltpu.VMEM((group,), jnp.float32),
            pltpu.VMEM((group,), jnp.float32),
        ])
    acc, m, l = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, kh, group, hd), jnp.float32),
            jax.ShapeDtypeStruct((b, kh, 1, group), jnp.float32),
            jax.ShapeDtypeStruct((b, kh, 1, group), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*prefetch, *args)
    return acc.reshape(b, h, hd), m.reshape(b, h), l.reshape(b, h)
