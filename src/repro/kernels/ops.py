"""Public jit'd wrappers for the Pallas kernels.

Dispatch policy: on TPU the Pallas lowering runs natively; on CPU the
wrappers fall back to the pure-jnp oracles in `ref.py` unless
`interpret=True` is requested, which executes the kernel body in Pallas
interpret mode (the correctness path the tests sweep).

Mosaic kernels are never partitioned automatically, so under an active
serving mesh (`repro.sharding.use_rules`) the entries the model calls
outside a shard_map run their kernel replicated inside one
(`_replicated_on_mesh`) — the serving graph around them is
model-replicated anyway (DESIGN.md §11).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import flash_attention as _fa
from repro.kernels import gmm as _gmm
from repro.kernels import knn as _knn
from repro.kernels import quant as _quant
from repro.kernels import ref as _ref
from repro.kernels import sls as _sls
from repro.kernels import ssd as _ssd
from repro.sharding import active_rules


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _replicated_on_mesh(fn):
    """Run the jitted kernel entry `fn` inside a fully replicated
    shard_map when a multi-device serving mesh is active and the Pallas
    branch will be taken; otherwise call it as is.  The check runs at
    trace time of the CALLER, outside `fn`'s own jit cache, so a server
    with a mesh and one without never share a lowering."""
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        rules = active_rules()
        mesh = rules.mesh if rules is not None else None
        if mesh is None or mesh.size == 1 \
                or not (_on_tpu() or kwargs.get("interpret", False)):
            return fn(*args, **kwargs)
        return jax.shard_map(functools.partial(fn, **kwargs), mesh=mesh,
                             in_specs=P(), out_specs=P(),
                             check_vma=False)(*args)
    return wrapped


@_replicated_on_mesh
@functools.partial(jax.jit, static_argnames=("causal", "window", "interpret"))
def flash_attention(q, k, v, length=None, *, causal: bool = True,
                    window: int = 0, interpret: bool = False) -> jax.Array:
    """Prefill attention.  `length`: optional int32 scalar, the prompt's
    true length within the padded S (None: all of it); rows below it are
    exact, rows at or past it junk but finite."""
    if _on_tpu() or interpret:
        return _fa.flash_attention(q, k, v, length, causal=causal,
                                   window=window, interpret=interpret)
    return _ref.mha_reference(q, k, v, length, causal=causal, window=window)


@functools.partial(jax.jit, static_argnames=("blk_c", "interpret"))
def decode_attention_partial(q, k, v, valid, *, blk_c: int = 128,
                             interpret: bool = False
                             ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    if _on_tpu() or interpret:
        return _fa.decode_attention_partial(q, k, v, valid, blk_c=blk_c,
                                            interpret=interpret)
    # CPU fallback: the GQA-native einsum formulation (no repeat_kv
    # materialization) — same statistics as the oracle, far less traffic.
    from repro.models import layers as _L
    return _L.decode_attention_partial(q, k, v, valid)


@_replicated_on_mesh
@functools.partial(jax.jit, static_argnames=("window", "blk_c", "interpret"))
def decode_attention_fused(q, k, v, pos, extra=None, pages=None,
                           kv_scales=None, *,
                           window: int = 0, blk_c: int = 128,
                           interpret: bool = False) -> jax.Array:
    """Fused one-shot flash decode (produce + merge + normalize in ONE
    kernel launch).  q: (B,1,H,hd); k,v: (B,KH,S,hd); pos: (B,) or scalar
    per-row positions; extra: optional (acc, m, l) current-token partial.
    `pages`: optional (B, n_log) int32 page table — k/v are then physical
    page POOLS read through per-row page-list indirection, `blk_c` is the
    exact page size, and `pos` keeps its logical meaning (DESIGN.md §9).
    The paged result is bitwise-equal to the dense kernel on the
    logically-gathered cache for any physical placement, because the
    chunk reduction visits pages in logical order either way.
    `kv_scales`: optional (k_scales, v_scales), each (B, KH, S/page) f32
    — k/v are then int8 pools dequantized per page inside the kernel
    (the scale rides the same page indirection; DESIGN.md §10); the
    scale page width overrides `blk_c` in the dense case and must equal
    it in the paged case.  Returns (B,1,H,hd)."""
    if _on_tpu() or interpret:
        return _fa.decode_attention_fused(q, k, v, pos, extra,
                                          window=window, blk_c=blk_c,
                                          pages=pages, kv_scales=kv_scales,
                                          interpret=interpret)
    page_size = blk_c if pages is not None else 0
    if kv_scales is not None and pages is not None:
        assert blk_c == k.shape[2] // kv_scales[0].shape[2]
    return _ref.decode_fused_reference(q, k, v, pos, extra, window=window,
                                       pages=pages, page_size=page_size,
                                       kv_scales=kv_scales)


@functools.partial(jax.jit, static_argnames=("window", "blk_c", "interpret"))
def decode_attention_fused_partial(q, k, v, pos, extra=None, pages=None,
                                   kv_scales=None, *,
                                   window: int = 0, blk_c: int = 128,
                                   interpret: bool = False
                                   ) -> Tuple[jax.Array, jax.Array,
                                              jax.Array]:
    """`decode_attention_fused` minus the final normalization: the
    per-shard producer of the mesh-sharded decode (DESIGN.md §11).

    Same argument surface as the fused entry; returns the raw merged
    statistics (acc (B,H,hd) f32, m (B,H) f32, l (B,H) f32).  Each mesh
    shard runs this over its OWN head group's cache panel, the partials
    are concatenated over the head axis (`all_gather`, tiled — an exact
    bit-copy, no float reduction), and one `ref.normalize_fused_partial`
    epilogue recovers the single-device fused output bitwise, because
    every statistic is per-(row, head) independent.

    On TPU (or interpret=True) the producer is the fused Pallas kernel
    itself, stopped before its normalization (the single-device entry
    above runs the same kernel and the same epilogue, so the two agree
    bitwise on the chip too); on CPU it is the fused oracle's own
    partial path, so the two dispatches share the reference's math
    exactly."""
    if _on_tpu() or interpret:
        return _fa.decode_attention_fused_partial(
            q, k, v, pos, extra, window=window, blk_c=blk_c, pages=pages,
            kv_scales=kv_scales, interpret=interpret)
    page_size = blk_c if pages is not None else 0
    if kv_scales is not None and pages is not None:
        assert blk_c == k.shape[2] // kv_scales[0].shape[2]
    return _ref.decode_fused_partial_reference(
        q, k, v, pos, extra, window=window, pages=pages,
        page_size=page_size, kv_scales=kv_scales)


@_replicated_on_mesh
@functools.partial(jax.jit, static_argnames=("interpret",))
def quant_matmul(x, qt: "_quant.QTensor", *,
                 interpret: bool = False) -> jax.Array:
    """x (..., d_in) @ dequantize(qt) -> (..., n) in x.dtype, reading
    only packed blocks + scales from HBM (DESIGN.md §10).  On TPU (or
    with interpret=True) the dequantization is fused into the Pallas
    matmul tile pipeline; the CPU fallback multiplies against the
    dequantized oracle weight — same f32 grid values, so the two paths
    agree to f32 matmul accumulation order."""
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    if _on_tpu() or interpret:
        out = _quant.quant_matmul(x2, qt, interpret=interpret)
    else:
        w = _quant.dequantize_tensor(qt)
        out = (x2.astype(jnp.float32) @ w).astype(x.dtype)
    return out.reshape(shape[:-1] + (out.shape[-1],))


class BatchedSampling(NamedTuple):
    """Per-slot sampling parameters, vectorized over the decode batch —
    the device-side image of one `SamplingParams` per serving slot.
    All leaves are (B,)-shaped so the pytree rides through jitted decode
    segments (and their lax.scan carries) without retracing per request.

    temperature <= 0 (or top_k == 1) marks a slot greedy; top_k == 0,
    top_p == 1 and min_p == 0 disable the respective filter."""
    temperature: jax.Array        # (B,) f32
    top_k: jax.Array              # (B,) i32
    top_p: jax.Array              # (B,) f32
    min_p: jax.Array              # (B,) f32


def greedy_sampling(batch: int) -> BatchedSampling:
    """All-slots-greedy parameters (the historical serve-loop default)."""
    return BatchedSampling(
        temperature=jnp.zeros((batch,), jnp.float32),
        top_k=jnp.zeros((batch,), jnp.int32),
        top_p=jnp.ones((batch,), jnp.float32),
        min_p=jnp.zeros((batch,), jnp.float32))


@functools.partial(jax.jit, static_argnames=("vocab",))
def sample_tokens(logits, params: BatchedSampling, keys, *,
                  vocab: int = 0) -> jax.Array:
    """Per-slot stochastic token selection.  logits: (B, V); params:
    BatchedSampling of (B,) leaves; keys: (B, 2) uint32 — one PRNG key
    per slot; vocab: true vocabulary width when V is padded (stochastic
    rows never emit a pad id >= vocab; 0 disables the bound).  Returns
    (B,) int32 next tokens.

    Semantics live in `ref.sample_tokens_reference`: greedy rows reduce
    to argmax(logits) bitwise, sampled rows are Gumbel-argmax over the
    temperature/top_k/top_p/min_p filtered distribution.  The serving
    entry is `ref.sample_tokens_capped`: an O(V) `lax.top_k` partial
    sort over the first `ref.SAMPLE_HEAD` ranks, taken whenever every
    row's filters provably close inside the head (greedy, small top_k,
    or nucleus mass reached), with an in-graph `lax.cond` fallback to
    the full reference otherwise: one stable full-vocabulary sort that
    carries the logits and the token-order probabilities into rank order
    beside the token ids, so no (B, V) gather follows it —
    bitwise-identical samples either way (asserted in
    tests/test_sampling.py).  There is still no
    Pallas lowering — plain XLA on every backend, so sampling adds no
    kernel launches to the streamed segment (benchmarks/decode_stream.py
    records this accounting next to its asserted syncs/token figures)."""
    return _ref.sample_tokens_capped(
        logits, params.temperature, params.top_k, params.top_p,
        params.min_p, keys, vocab)


@functools.partial(jax.jit, static_argnames=("vocab",))
def verify_tokens(target_logits, draft_logits, draft_tokens,
                  params: BatchedSampling, keys, *,
                  vocab: int = 0) -> Tuple[jax.Array, jax.Array]:
    """Per-slot speculative draft verification (DESIGN.md §7).
    target_logits: (B, K+1, V) target logits at the K+1 verified
    positions; draft_logits: (B, K, V) proposal logits the draft tokens
    were sampled from; draft_tokens: (B, K); params: BatchedSampling of
    (B,) leaves; keys: (B, 2) uint32 — one PRNG key per slot; vocab:
    true vocabulary width when V is padded.  Returns (out_tokens
    (B, K+1) i32, accept_len (B,) i32): a round emits
    out_tokens[:accept_len + 1] — the accepted draft prefix plus one
    correction/bonus token.

    Semantics live in `ref.verify_tokens_reference` (the jnp oracle IS
    the implementation): greedy rows accept while the draft matches the
    target argmax and always emit the target argmax stream (bitwise the
    non-speculative loop, for ANY draft); stochastic rows run standard
    rejection sampling against the filtered distributions of
    `ref.filtered_log_probs`, which leaves each emitted token's marginal
    law exactly the target's sampling distribution.  As with
    `sample_tokens` there is no Pallas lowering — two O(B·K·V) sorts
    plus elementwise work, plain XLA on every backend, so verification
    adds no kernel launches to the speculative segment."""
    return _ref.verify_tokens_reference(
        target_logits, draft_logits, draft_tokens, params.temperature,
        params.top_k, params.top_p, params.min_p, keys, vocab)


@functools.partial(jax.jit, static_argnames=("blk_q", "blk_n", "interpret"))
def knn_distances(queries, db, *, blk_q: int = 128, blk_n: int = 128,
                  interpret: bool = False) -> jax.Array:
    if _on_tpu() or interpret:
        return _knn.knn_distances(queries, db, blk_q=blk_q, blk_n=blk_n,
                                  interpret=interpret)
    return _ref.knn_distances_reference(queries, db)


@_replicated_on_mesh
@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def expert_gmm(lhs, w, tile_group, n_live, w2=None, *, tm: int,
               interpret: bool = False) -> jax.Array:
    """Grouped matmul of the held experts over group-aligned rows
    (`gmm.expert_gmm`; `w2` fuses the SwiGLU gate).  Nothing but the
    kernel sits in this jit, so the trace's `%expert_gmm` ops are the
    kernel's own time."""
    if _on_tpu() or interpret:
        return _gmm.expert_gmm(lhs, w, tile_group, n_live, w2, tm=tm,
                               interpret=interpret)
    return _ref.expert_gmm_reference(lhs, w, tile_group, n_live, w2, tm=tm)


@functools.partial(jax.jit, static_argnames=("k", "blk_q", "blk_n",
                                             "interpret"))
def knn_topk(queries, db, k: int, *, blk_q: int = 128, blk_n: int = 128,
             interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    if _on_tpu() or interpret:
        return _knn.knn_topk(queries, db, k, blk_q=blk_q, blk_n=blk_n,
                             interpret=interpret)
    return _ref.knn_topk_reference(queries, db, k)


@functools.partial(jax.jit, static_argnames=("blk_b", "interpret"))
def sls(table, indices, weights=None, *, blk_b: int = 8,
        interpret: bool = False) -> jax.Array:
    if _on_tpu() or interpret:
        return _sls.sls(table, indices, weights, blk_b=blk_b,
                        interpret=interpret)
    return _ref.sls_reference(table, indices, weights)


@_replicated_on_mesh
@functools.partial(jax.jit, static_argnames=("blk_s", "interpret"))
def ssd_scan(x, dt, A, B, C, init_state=None, *, blk_s: int = 128,
             interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    if _on_tpu() or interpret:
        return _ssd.ssd_scan(x, dt, A, B, C, init_state, blk_s=blk_s,
                             interpret=interpret)
    return _ref.ssd_reference(x, dt, A, B, C, init_state)
