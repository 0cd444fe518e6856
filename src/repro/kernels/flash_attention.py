"""Pallas TPU flash attention (prefill) and partial decode attention.

The paper offloads the *attention block* of LLM inference to the
memory-side compute (Table I).  On TPU the analogue is running attention
where the KV bytes live; these kernels are the compute hot-spot of that
offload:

  * `flash_attention` — causal / sliding-window GQA flash attention
    (prefill) with online softmax.  Grid (B, KH, n_q, n_k): the KV axis
    is innermost and accumulates partial-softmax statistics in VMEM
    scratch, exactly the (acc, m, l) statistic stream that the
    back-streaming protocol ships between shards.  Only (q block, kv
    block) pairs holding a live (query, key) pair run; a grid cell holds
    a whole GQA group's query rows.
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

Softmax scale: every kernel here scales by head_dim ** -0.5 (the
decode kernels multiply q, the prefill kernel its float32 scores; its
`scale` defaults to it).  A model with another scale
(Granite's `attention_multiplier`) folds the ratio into q before the call
(`transformer._qkv`, `ArchConfig.attn_scale`), so the kernels stay as
they are and the product is the model's scale.

Prefill VMEM budget per grid cell (bf16 inputs, f32 scratch), with
rows = group · blk_q <= FLASH_ROWS (2,048) and blk_k <= FLASH_BLK_K (512):
  q (rows, hd) ×2 buffers + k, v (blk_k, hd) ×2 each + acc (rows, hd)
  + m, l (rows, 1) + the score, probability and mask temporaries
  (rows, blk_k) each.
At rows 2,048, blk_k 512 and hd 128 that is ~1.5 MB of blocks and
scratch plus ~4 MB per temporary, inside the 64 MB scoped limit the
call sets (FLASH_VMEM_LIMIT, as `gmm.py` does).  starcoder2_3b's
group of 12 takes blk_q 128 (1,536 rows), granite_4_0_h_small's group
of 4 blk_q 512.  Matmul dims are multiples of 128 => MXU-aligned.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref as _ref

NEG_INF = -1e30


# --------------------------------------------------------------------------
# Prefill flash attention
# --------------------------------------------------------------------------

# Query rows one grid cell holds (a whole GQA group's heads times blk_q
# positions), KV positions one K/V tile holds, and the scoped VMEM the
# kernel may use: a (2048, 512) f32 score tile is 4 MB, and the score,
# probability and mask temporaries of one step stay well inside.
FLASH_ROWS = 2048
FLASH_BLK_K = 512
FLASH_VMEM_LIMIT = 64 * 1024 * 1024


def _tile(s: int, cap: int) -> int:
    """`s` itself when it fits under `cap`, else the largest power of two
    (>= 8) not above `cap` that divides `s`."""
    if s <= cap:
        return s
    t = 8
    while t * 2 <= cap and s % (t * 2) == 0:
        t *= 2
    assert s % t == 0, (s, cap)
    return t


def flash_tiles(s: int, group: int) -> Tuple[int, int]:
    """(blk_q, blk_k) of the prefill kernel for `s` tokens and `group`
    query heads per KV head: a grid cell multiplies a (group · blk_q, hd)
    block of the group's query rows against one (blk_k, hd) K/V tile."""
    return _tile(s, max(FLASH_ROWS // group, 8)), _tile(s, FLASH_BLK_K)


def _block_live(q0, q1, k0, k1, length, causal: bool, window: int):
    """Whether the (query block [q0, q1), key block [k0, k1)) pair holds
    at least one live (query, key) pair: both below `length`, the key not
    after the query (causal) and within `window` of it.  Plain integer
    and comparison arithmetic, so the kernel evaluates it on scalars and
    `flash_live_blocks` on NumPy grids of block origins."""
    live = (q0 < length) & (k0 < length)
    if causal:
        live = live & (k0 < q1)
    if window > 0:
        live = live & (q0 - k1 + 1 < window)
    return live


def _block_full(q0, q1, k0, k1, length, causal: bool, window: int):
    """Whether no (query, key) pair of the block is masked, so the block
    can skip building the mask.  Query rows at or past `length` are junk
    and mask only by key, like every other row."""
    full = k1 <= length
    if causal:
        full = full & (k1 - 1 <= q0)
    if window > 0:
        full = full & (q1 - 1 - k0 < window)
    return full


def flash_live_blocks(length: int, s: int, blk_q: int, blk_k: int,
                      causal: bool = True, window: int = 0
                      ) -> Tuple[int, int]:
    """(live, total) (query block, key block) pairs of one head group's
    prefill grid over `s` tokens of which the first `length` are the
    prompt: the pairs the kernel computes, by the kernel's own
    predicate, against the pairs its grid visits."""
    q0 = np.arange(s // blk_q)[:, None] * blk_q
    k0 = np.arange(s // blk_k)[None, :] * blk_k
    live = _block_live(q0, q0 + blk_q, k0, k0 + blk_k, length, causal,
                       window)
    return int(live.sum()), live.size


def _flash_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
                  l_ref, *, scale: float, blk_q: int, blk_k: int,
                  causal: bool, window: int):
    i = pl.program_id(2)          # q block
    j = pl.program_id(3)          # kv block (innermost, accumulating)
    length = len_ref[0]
    q0, k0 = i * blk_q, j * blk_k
    q1, k1 = q0 + blk_q, k0 + blk_k

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def step(masked: bool):
        # q·kᵀ on the operands as they arrive (bf16 on the chip: the
        # products are exact in the float32 accumulator); p·v in float32
        s = jax.lax.dot_general(q_ref[0, 0, 0], k_ref[0, 0],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if masked:
            # a column of query positions against a row of key positions
            qpos = q0 + lax.rem(
                lax.broadcasted_iota(jnp.int32, (s.shape[0], 1), 0), blk_q)
            kpos = k0 + lax.broadcasted_iota(jnp.int32, (1, blk_k), 1)
            mask = kpos < length
            if causal:
                mask &= kpos <= qpos
            if window > 0:
                mask &= kpos > qpos - window
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]                                   # (rows, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if masked:
            p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p, v_ref[0, 0].astype(jnp.float32),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    live = _block_live(q0, q1, k0, k1, length, causal, window)
    full = _block_full(q0, q1, k0, k1, length, causal, window)

    @pl.when(live & full)
    def _unmasked():
        step(masked=False)

    @pl.when(live & jnp.logical_not(full))
    def _masked():
        step(masked=True)

    @pl.when(j == pl.num_programs(3) - 1)
    def _finish():
        o_ref[0, 0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-20)
                          ).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    length: Optional[jax.Array] = None, *,
                    causal: bool = True, window: int = 0,
                    scale: Optional[float] = None,
                    interpret: bool = False) -> jax.Array:
    """q: (B,S,H,hd); k,v: (B,S,KH,hd) -> (B,S,H,hd).  GQA supported.

    `length` (int32 scalar, may be traced): the first `length` tokens are
    the prompt, the rest padding; None means all S.  Query rows at or
    past it are junk (finite; zero where no block of theirs is live),
    and keys at or past it are seen by no query.  Rows below it are the
    unpadded attention's.

    Grid (B, KH, n_q, n_k), KV axis innermost and accumulating the
    (acc, m, l) softmax statistics in VMEM scratch.  A cell holds one
    KV head's whole GQA group: the group's query rows for blk_q
    positions form one (group · blk_q, hd) block against one (blk_k, hd)
    K/V tile (`flash_tiles`), so each K/V tile is read once per group.
    A (q block, kv block) pair runs only if `_block_live` holds: not
    wholly above the causal diagonal, outside the window, or past
    `length` on either axis.  The live kv blocks of a q block are one
    contiguous run [lo, hi]; the K/V index maps clamp to it (and the q
    map to the last live q block), so a skipped step repeats the block
    index and issues no copy.  Only pairs that `_block_full` does not
    clear build the mask.  The softmax scale multiplies the float32
    scores."""
    b, s, h, hd = q.shape
    kh = k.shape[2]
    assert h % kh == 0
    group = h // kh
    blk_q, blk_k = flash_tiles(s, group)
    n_q, n_k = s // blk_q, s // blk_k
    rows = group * blk_q
    scale = scale if scale is not None else hd ** -0.5
    if length is None:
        length = s
    length = jnp.reshape(jnp.asarray(length, jnp.int32), (1,))

    # q rows of one (KV head, q block) cell are contiguous: head-major
    # within the group, position-minor
    qt = (q.reshape(b, n_q, blk_q, kh, group, hd)
           .transpose(0, 3, 1, 4, 2, 5).reshape(b, kh, n_q, rows, hd))
    kt = k.transpose(0, 2, 1, 3)                          # (B,KH,S,hd)
    vt = v.transpose(0, 2, 1, 3)

    def q_map(b_, h_, i, j, len_ref):
        return b_, h_, jnp.minimum(i, (len_ref[0] - 1) // blk_q), 0, 0

    def kv_map(b_, h_, i, j, len_ref):
        last = jnp.minimum((i + 1) * blk_q, len_ref[0]) if causal \
            else len_ref[0]
        hi = (last - 1) // blk_k
        lo = jnp.maximum((i * blk_q - window + 1) // blk_k, 0) \
            if window > 0 else 0
        return b_, h_, jnp.minimum(jnp.maximum(j, lo), hi), 0

    def o_map(b_, h_, i, j, len_ref):
        return b_, h_, i, 0, 0

    kernel = functools.partial(
        _flash_kernel, scale=scale, blk_q=blk_q, blk_k=blk_k,
        causal=causal, window=window)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, kh, n_q, n_k),
            in_specs=[
                pl.BlockSpec((1, 1, 1, rows, hd), q_map),
                pl.BlockSpec((1, 1, blk_k, hd), kv_map),
                pl.BlockSpec((1, 1, blk_k, hd), kv_map),
            ],
            out_specs=pl.BlockSpec((1, 1, 1, rows, hd), o_map),
            scratch_shapes=[
                pltpu.VMEM((rows, hd), jnp.float32),
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, 1), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((b, kh, n_q, rows, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=FLASH_VMEM_LIMIT),
        interpret=interpret,
    )(length, qt, kt, vt)
    return (out.reshape(b, kh, n_q, group, blk_q, hd)
               .transpose(0, 2, 4, 1, 3, 5).reshape(b, s, h, hd))


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
