"""Model layer library: norms, rotary embeddings, attention variants,
gated/MoE FFN, Mamba2 SSD.  Pure JAX; Pallas kernels in repro.kernels are
drop-in replacements for the hot paths on TPU (selected via ops.py).

Conventions:
  activations  x: (B, S, D)        bf16
  attention    q: (B, S, H, hd), k/v: (B, S, KH, hd); every attention
               path scales q by 1/sqrt(hd) — a model with another softmax
               scale arrives with the ratio already folded into q
               (`transformer._qkv`)
  softmax / norm statistics in fp32.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30


# --------------------------------------------------------------------------
# Norms
# --------------------------------------------------------------------------

def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))
    return out.astype(x.dtype)


def rms_norm_gated(y: jax.Array, z: jax.Array, scale: jax.Array,
                   eps: float = 1e-6) -> jax.Array:
    """Mamba-2's gated RMSNorm: RMSNorm(y ⊙ z) over the last axis, z the
    already-activated gate silu(z).  Statistics in fp32."""
    g = y.astype(jnp.float32) * z.astype(jnp.float32)
    return rms_norm(g, scale, eps).astype(y.dtype)


# --------------------------------------------------------------------------
# Rotary embeddings (RoPE and M-RoPE)
# --------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, hd); positions: (B, S) int32."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B,S,hd/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x: jax.Array, positions3: jax.Array, theta: float,
                sections: Tuple[int, int, int]) -> jax.Array:
    """Qwen2-VL multimodal rotary: positions3 (B, S, 3) = (t, h, w) indices.
    The hd/2 frequency bands are partitioned into `sections` (t/h/w)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # (hd/2,)
    assert sum(sections) == hd // 2, (sections, hd)
    sec_ids = jnp.repeat(jnp.arange(3), jnp.array(sections),
                         total_repeat_length=hd // 2)   # (hd/2,) in {0,1,2}
    # select, per frequency band, which of the three position streams applies
    sel = jax.nn.one_hot(sec_ids, 3, dtype=jnp.float32)  # (hd/2, 3)
    pos = jnp.einsum("bst,ht->bsh", positions3.astype(jnp.float32), sel)
    angles = pos * freqs                                 # (B,S,hd/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def default_mrope_positions(batch: int, seq: int) -> jax.Array:
    """Stub 3D positions for the VLM backbone: text tokens use (i, i, i) as
    in Qwen2-VL; the vision frontend (stubbed) would supply real (t,h,w)."""
    i = jnp.arange(seq, dtype=jnp.int32)
    return jnp.broadcast_to(i[None, :, None], (batch, seq, 3))


# --------------------------------------------------------------------------
# Attention (blocked flash-style, pure JAX reference path)
# --------------------------------------------------------------------------

def repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    if n_rep == 1:
        return k
    b, s, kh, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, kh, n_rep, d)) \
              .reshape(b, s, kh * n_rep, d)


def blocked_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      *, causal: bool = True, q_offset: int = 0,
                      block: int = 1024, q_tile: int = 512) -> jax.Array:
    """Flash-style attention, q-tiled (§Perf iteration W2):

      · outer static loop over q tiles of `q_tile` rows — the live score
        tensor is (B, H, q_tile, block) instead of (B, H, Sq, block),
        cutting peak memory ~Sq/q_tile ×;
      · per causal q tile the inner KV scan covers only blocks up to the
        tile's last query — the fully-masked upper-triangle blocks are
        never computed (≈2× attention-FLOP saving at long Sq).

    q: (B,Sq,H,hd), k/v: (B,Sk,KH,hd); q_offset positions queries within
    the KV sequence (prefill chunks)."""
    b, sq, h, hd = q.shape
    sk, kh = k.shape[1], k.shape[2]
    k = repeat_kv(k, h // kh)
    v = repeat_kv(v, h // kh)
    scale = 1.0 / math.sqrt(hd)
    block = min(block, sk)
    while sk % block:          # largest divisor of sk not above `block`
        block -= 1             # (e.g. whisper's 1500 encoder positions)
    n_blocks = sk // block
    qf = (q.astype(jnp.float32) * scale).transpose(0, 2, 1, 3)   # (B,H,Sq,hd)
    kf = k.astype(jnp.float32).transpose(0, 2, 1, 3).reshape(
        b, h, n_blocks, block, hd)
    vf = v.astype(jnp.float32).transpose(0, 2, 1, 3).reshape(
        b, h, n_blocks, block, hd)

    def one_tile(q_t, pos_t, n_kv):
        tq = q_t.shape[2]

        def body(carry, blk):
            m, l, acc = carry
            kb, vb, j = blk
            s = jnp.einsum("bhqd,bhkd->bhqk", q_t, kb)   # (B,H,tq,block)
            if causal:
                kv_pos = j * block + jnp.arange(block)
                mask = pos_t[:, None] >= kv_pos[None, :]
                s = jnp.where(mask[None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + p.sum(axis=-1, keepdims=True)
            acc_new = acc * alpha + jnp.einsum("bhqk,bhkd->bhqd", p, vb)
            return (m_new, l_new, acc_new), None

        carry0 = (jnp.full((b, h, tq, 1), NEG_INF, jnp.float32),
                  jnp.zeros((b, h, tq, 1), jnp.float32),
                  jnp.zeros((b, h, tq, hd), jnp.float32))
        (m, l, acc), _ = lax.scan(
            body, carry0,
            (kf[:, :, :n_kv].transpose(2, 0, 1, 3, 4),
             vf[:, :, :n_kv].transpose(2, 0, 1, 3, 4),
             jnp.arange(n_kv)))
        return acc / jnp.maximum(l, 1e-20)

    outs = []
    for t0 in range(0, sq, q_tile):
        t1 = min(t0 + q_tile, sq)
        pos_t = q_offset + jnp.arange(t0, t1)
        if causal:
            hi = min(sk, q_offset + t1)                  # last query's kv reach
            n_kv = max(1, -(-hi // block))
        else:
            n_kv = n_blocks
        outs.append(one_tile(qf[:, :, t0:t1], pos_t, n_kv))
    out = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=2)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)             # (B,Sq,H,hd)


def sliding_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      *, window: int) -> jax.Array:
    """Banded causal attention: block-local structure with exactly one
    look-back block (block size == window), so FLOPs are O(S * 2W) instead
    of O(S^2).  Requires S % window == 0."""
    b, s, h, hd = q.shape
    kh = k.shape[2]
    k = repeat_kv(k, h // kh)
    v = repeat_kv(v, h // kh)
    if s <= window:
        return blocked_attention(q, k, v, causal=True, block=min(s, 1024))
    assert s % window == 0, (s, window)
    nb = s // window
    scale = 1.0 / math.sqrt(hd)
    qb = (q.astype(jnp.float32) * scale).reshape(b, nb, window, h, hd)
    kb = k.astype(jnp.float32).reshape(b, nb, window, h, hd)
    vb = v.astype(jnp.float32).reshape(b, nb, window, h, hd)
    # previous block (zero-padded for block 0)
    kprev = jnp.concatenate([jnp.zeros_like(kb[:, :1]), kb[:, :-1]], axis=1)
    vprev = jnp.concatenate([jnp.zeros_like(vb[:, :1]), vb[:, :-1]], axis=1)
    kk = jnp.concatenate([kprev, kb], axis=2)            # (B,nb,2W,H,hd)
    vv = jnp.concatenate([vprev, vb], axis=2)
    sco = jnp.einsum("bnqhd,bnkhd->bnhqk", qb, kk)       # (B,nb,H,W,2W)
    qpos = jnp.arange(window)[:, None]
    kpos = jnp.arange(2 * window)[None, :] - window
    band = (kpos <= qpos) & (kpos > qpos - window)       # exact window band
    # block 0 has no valid look-back block (its 'prev' is zero padding)
    has_prev = (jnp.arange(nb) > 0)[None, :, None, None, None]
    full_mask = band[None, None, None, :, :] & \
        (has_prev | (kpos >= 0)[None, None, None, :, :])
    sco = jnp.where(full_mask, sco, NEG_INF)
    p = jax.nn.softmax(sco, axis=-1)
    out = jnp.einsum("bnhqk,bnkhd->bnqhd", p, vv)
    return out.reshape(b, s, h, hd).astype(q.dtype)


def decode_attention_partial(q: jax.Array, k: jax.Array, v: jax.Array,
                             kv_valid: Optional[jax.Array] = None
                             ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Partial attention of a single-step query over one KV chunk, returning
    (acc, m, l) merge-able statistics.  This is the 'CCM-side producer' of
    the back-streaming decode path: each KV shard computes its partial and
    streams (acc, m, l) to the combiner.

    q: (B, 1, H, hd); k/v: (B, KH, C, hd); kv_valid: (B, C) bool mask.
    Returns acc: (B, H, hd) fp32, m/l: (B, H) fp32.
    """
    # GQA-native over the flash-decoding cache layout (B, KH, C, hd): the
    # query reshapes to (B, KH, G, hd) so the cache is read ONCE in its
    # storage dtype with contiguous (C, hd) panels — no repeat_kv
    # materialization, no f32 cache copy, no layout transposes (§Perf
    # iterations D1/D2: these were ~75% of the decode step's HBM
    # traffic).  Dots accumulate in f32 via preferred_element_type; only
    # the tiny (B,KH,G,C) score tensor is ever f32.
    b, _, h, hd = q.shape
    kh = k.shape[1]
    g = h // kh
    scale = 1.0 / math.sqrt(hd)
    qg = (q[:, 0].astype(jnp.float32) * scale).astype(q.dtype)
    qg = qg.reshape(b, kh, g, hd)
    s = jnp.einsum("bkgd,bksd->bkgs", qg, k,
                   preferred_element_type=jnp.float32)   # (B,KH,G,C)
    if kv_valid is not None:
        s = jnp.where(kv_valid[:, None, None, :], s, NEG_INF)
    m = s.max(axis=-1)                                   # (B,KH,G)
    p = jnp.exp(s - m[..., None])
    if kv_valid is not None:
        # fully-masked chunks: exp(NEG_INF - NEG_INF) = 1 would leak a
        # uniform distribution into (l, acc).  The merge's exp(m - m_max)
        # weight already zeroes it, but per-row positions (continuous
        # batching) make empty chunks routine — keep the partial itself
        # exact so any consumer (ring, fused epilogue, tests) can rely
        # on l == 0 for empty chunks.
        p = jnp.where(kv_valid[:, None, None, :], p, 0.0)
    l = p.sum(axis=-1)
    acc = jnp.einsum("bkgs,bksd->bkgd", p.astype(k.dtype), v,
                     preferred_element_type=jnp.float32)
    return (acc.reshape(b, h, hd), m.reshape(b, h), l.reshape(b, h))


def single_kv_partial(q: jax.Array, k_new: jax.Array, v_new: jax.Array
                      ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Partial-softmax statistics of q against ONE new (k, v) token — the
    current decode token's own contribution, merged with the cache
    partials so the cache write can happen outside the layer scan (§Perf
    iteration D5).  q: (B,1,H,hd); k_new/v_new: (B,1,KH,hd)."""
    b, _, h, hd = q.shape
    kh = k_new.shape[2]
    g = h // kh
    scale = 1.0 / math.sqrt(hd)
    qg = (q[:, 0].astype(jnp.float32) * scale).reshape(b, kh, g, hd)
    kf = k_new[:, 0].astype(jnp.float32)                  # (B,KH,hd)
    s = jnp.einsum("bkgd,bkd->bkg", qg, kf)               # (B,KH,G)
    acc = jnp.broadcast_to(v_new[:, 0].astype(jnp.float32)[:, :, None, :],
                           (b, kh, g, hd))
    # with a single key: m = s, p = exp(0) = 1, l = 1, acc = v
    return (acc.reshape(b, h, hd), s.reshape(b, h),
            jnp.ones((b, h), jnp.float32))


def merge_attention_partials(accs: jax.Array, ms: jax.Array, ls: jax.Array
                             ) -> jax.Array:
    """Merge N partial-attention results: accs (N,B,H,hd), ms/ls (N,B,H).
    This is the 'host-side consumer' combine of the decode offload."""
    m = ms.max(axis=0)                                   # (B,H)
    alpha = jnp.exp(ms - m[None])                        # (N,B,H)
    l = (ls * alpha).sum(axis=0)
    acc = (accs * alpha[..., None]).sum(axis=0)
    return acc / jnp.maximum(l, 1e-20)[..., None]        # (B,H,hd)


# --------------------------------------------------------------------------
# FFN: gated MLP and Mixture-of-Experts
# --------------------------------------------------------------------------

def gated_mlp(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
              w_down: jax.Array) -> jax.Array:
    from repro.models.quantize import matmul
    h = jax.nn.silu(matmul(x, w_gate)) * matmul(x, w_up)
    return matmul(h, w_down)


def _tile_rows(t: int, top_k: int, n_experts: int) -> int:
    """Rows of one grouped-matmul tile: the expected rows an expert gets
    under uniform routing, rounded up to a power of two in [16, 128]
    (16: bfloat16's sublane tile; 128: the MXU's edge)."""
    want = -(-t * top_k // n_experts)
    tm = 16
    while tm < min(want, 128):
        tm *= 2
    return tm


def moe_held(x: jax.Array, router: jax.Array, w_gate: jax.Array,
             w_up: jax.Array, w_down: jax.Array, top_k: int,
             shard: int = 0) -> jax.Array:
    """Dropless top-k MoE over the experts this chip holds.

    The router scores all E experts (float32); each token takes its
    top_k and a softmax over those k logits.  The chip holds experts
    [shard·Eh, (shard+1)·Eh) (w_*: (Eh, D, F) / (Eh, F, D)) and computes
    only the (token, expert) pairs that fall on them: no capacity, no
    drop.  The pairs are laid out sorted by expert, each expert's run
    padded to whole tiles, gate/up (fused SiLU·up) and down run as
    grouped matmuls over those tiles (`ops.expert_gmm`), and each token
    sums its held experts' outputs by gate weight.  With Eh = E the sum
    is the whole routed layer; the parts of all shards add up to it.
    x: (T, D) -> (T, D)."""
    from repro.kernels import ops
    t, d = x.shape
    e = router.shape[1]
    eh = w_gate.shape[0]
    with jax.named_scope("moe_route"):
        # float32 as stated: the TPU's default would round the router to
        # bfloat16 for one MXU pass and flip near-tied experts
        logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                         precision=lax.Precision.HIGHEST)
        top_logits, expert_ids = lax.top_k(logits, top_k)      # (T, K)
        gates = jax.nn.softmax(top_logits, axis=-1)
        local = expert_ids.reshape(-1) - shard * eh             # (T*K,)
        held = (local >= 0) & (local < eh)
        group = jnp.where(held, local, eh)                      # eh: not held
        tm = _tile_rows(t, top_k, e)
        cap = t * min(top_k, eh)                  # most pairs a chip can hold
        m_pad = -(-cap // tm) * tm + eh * tm
        onehot = jax.nn.one_hot(group, eh + 1, dtype=jnp.int32)
        rank = ((jnp.cumsum(onehot, axis=0) - 1) * onehot).sum(axis=1)
        sizes = onehot.sum(axis=0)[:eh]
        padded = -(-sizes // tm) * tm
        ends = jnp.cumsum(padded)
        starts = ends - padded
        dest = jnp.where(held, starts[jnp.minimum(group, eh - 1)] + rank,
                         m_pad)                                 # (T*K,)
        row_token = jnp.full((m_pad,), t, jnp.int32).at[dest].set(
            jnp.arange(t * top_k, dtype=jnp.int32) // top_k, mode="drop")
        n_live = ends[-1] // tm
        tile_group = jnp.sum(
            jnp.arange(0, m_pad, tm)[:, None] >= ends[None, :], axis=1)
        tile_group = jnp.minimum(
            tile_group, tile_group[jnp.maximum(n_live - 1, 0)])
        tile_group = jnp.minimum(tile_group, eh - 1)
    with jax.named_scope("expert_gmm"):
        xs = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)])[row_token]
        live = n_live.reshape(1).astype(jnp.int32)
        h = ops.expert_gmm(xs, w_gate, tile_group, live, w_up, tm=tm)
        ys = ops.expert_gmm(h, w_down, tile_group, live, tm=tm)
    ys = jnp.concatenate([ys, jnp.zeros((1, d), ys.dtype)])
    yk = ys[dest.reshape(t, top_k)].astype(jnp.float32)        # (T, K, D)
    return jnp.einsum("tkd,tk->td", yk, gates).astype(x.dtype)


def moe_ffn_dist(x: jax.Array, router: jax.Array, w_gate: jax.Array,
                 w_up: jax.Array, w_down: jax.Array, top_k: int,
                 capacity_factor: float = 1.25) -> jax.Array:
    """Distribution-aware MoE (§Perf iteration G1, beyond-paper).

    The token-global dispatch (`moe_held`) under GSPMD routes the
    (T·K, E) rank cumsum and the slot gathers across the token-sharded
    axis, which lowers to per-layer all-gathers of x and rank tensors
    (measured with the old capacity dispatch: 74 s
    collective / 110 s memory per step for granite-40e).  This variant
    forces *locality* with shard_map:

      • tokens stay on their batch shard — dispatch, rank and combine are
        shard-local (zero collectives for them);
      • experts are padded to a multiple of the model axis and sharded
        over it (EP); every model shard computes only its local experts
        for its batch shard's tokens;
      • one psum over the model axis merges the partial token outputs —
        the only cross-shard traffic: (T_local, D) bf16 per layer.
    """
    from repro.sharding import active_rules
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P

    rules = active_rules()
    t, d = x.shape
    e = router.shape[1]
    if rules is None or rules.model_axis is None:
        # one device: all experts, or the share this chip holds
        return moe_held(x, router, w_gate, w_up, w_down, top_k)
    if w_gate.shape[0] != e:
        raise NotImplementedError(
            f"{w_gate.shape[0]} of {e} experts held: a mesh with a model "
            "axis needs every expert until the expert exchange exists "
            "(ROADMAP D8)")
    if rules.head_shard_attn:
        # bitwise serving (DESIGN.md §11): the capacity cumsum and expert
        # einsums couple ALL tokens, so a data-sharded batch lets GSPMD
        # token-partition them — different gemm blocking, bf16 low-bit
        # drift.  Replicate tokens through the expert compute (an
        # all-gather in, a bit-copy) and hand the replicated result back;
        # the next layer's "batch" constraint re-shards it.
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as _P
        repl = NamedSharding(rules.mesh, _P(None, None))
        x_r = lax.with_sharding_constraint(x, repl)
        y = moe_held(x_r, router, w_gate, w_up, w_down, top_k)
        return lax.with_sharding_constraint(y, repl)
    mesh, maxis, baxes = rules.mesh, rules.model_axis, rules.batch_axes
    bsize = 1
    for a in baxes:
        bsize *= mesh.shape[a]
    msize = mesh.shape[maxis]
    # Only worth it at training/prefill token counts: the shard_map
    # boundary re-gathers (FSDP-sharded) expert weights once per layer,
    # which is amortized over ≥512 local tokens but dominates a decode
    # step's 8-token shards (measured: 12× collective regression on
    # jamba-398B decode; §Perf G1 scope note).
    if bsize == 0 or t % bsize or (t // bsize) < max(512, top_k):
        return moe_held(x, router, w_gate, w_up, w_down, top_k)

    e_pad = ((e + msize - 1) // msize) * msize
    if e_pad != e:
        pad = e_pad - e
        router = jnp.pad(router, ((0, 0), (0, pad)))
        w_gate = jnp.pad(w_gate, ((0, pad), (0, 0), (0, 0)))
        w_up = jnp.pad(w_up, ((0, pad), (0, 0), (0, 0)))
        w_down = jnp.pad(w_down, ((0, pad), (0, 0), (0, 0)))
    e_local = e_pad // msize

    def local(x_l, router_l, wg_l, wu_l, wd_l):
        tl = x_l.shape[0]
        shard = lax.axis_index(maxis)
        logits = x_l.astype(jnp.float32) @ router_l.astype(jnp.float32)
        logits = jnp.where(jnp.arange(e_pad)[None, :] < e, logits, -jnp.inf)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_ids = lax.top_k(probs, top_k)       # (Tl, K)
        gate_vals = gate_vals / gate_vals.sum(-1, keepdims=True)
        cap = max(8, int(math.ceil(tl * top_k / e * capacity_factor)))
        flat_expert = expert_ids.reshape(-1)                  # (Tl*K,)
        local_id = flat_expert - shard * e_local
        mine = (local_id >= 0) & (local_id < e_local)
        local_safe = jnp.where(mine, local_id, 0)
        onehot = (jax.nn.one_hot(local_safe, e_local, dtype=jnp.int32)
                  * mine[:, None].astype(jnp.int32))
        pos = jnp.cumsum(onehot, axis=0) * onehot - 1
        pos_in_expert = pos.max(axis=-1)                      # (Tl*K,)
        keep = mine & (pos_in_expert >= 0) & (pos_in_expert < cap)
        token_ids = jnp.repeat(jnp.arange(tl), top_k)
        slot_token = jnp.full((e_local, cap), tl, jnp.int32)
        slot_token = slot_token.at[
            jnp.where(keep, local_safe, 0),
            jnp.where(keep, pos_in_expert, cap - 1)].set(
            jnp.where(keep, token_ids, tl), mode="drop")
        x_pad = jnp.concatenate([x_l, jnp.zeros((1, d), x_l.dtype)], axis=0)
        xe = x_pad[slot_token]                                # (El, cap, D)
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, wg_l)) \
            * jnp.einsum("ecd,edf->ecf", xe, wu_l)
        ye = jnp.einsum("ecf,efd->ecd", h, wd_l)              # (El, cap, D)
        gates_flat = jnp.where(keep, gate_vals.reshape(-1), 0.0)
        slot_gate = jnp.zeros((e_local, cap), jnp.float32).at[
            jnp.where(keep, local_safe, 0),
            jnp.where(keep, pos_in_expert, cap - 1)].set(
            gates_flat, mode="drop")
        y = jnp.zeros((tl + 1, d), jnp.float32).at[
            slot_token.reshape(-1)].add(
            (ye * slot_gate[..., None]).reshape(-1, d), mode="drop")
        # combine partial expert outputs in bf16 (§Perf G3): halves the
        # per-layer all-reduce and boundary traffic; each token sums at
        # most top_k expert outputs, so bf16 accumulation is safe.
        return lax.psum(y[:tl].astype(x_l.dtype), maxis)

    return shard_map(
        local, mesh=mesh,
        in_specs=(P(baxes, None), P(None, None),
                  P(maxis, None, None), P(maxis, None, None),
                  P(maxis, None, None)),
        out_specs=P(baxes, None),
        check_rep=False,
    )(x, router, w_gate, w_up, w_down)


def moe_aux_loss(x: jax.Array, router: jax.Array, top_k: int) -> jax.Array:
    """Load-balancing auxiliary loss (Switch-style)."""
    logits = x.astype(jnp.float32) @ router.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    e = probs.shape[-1]
    _, idx = lax.top_k(probs, top_k)
    frac_tokens = jnp.mean(
        jax.nn.one_hot(idx, e, dtype=jnp.float32).sum(axis=-2), axis=0)
    frac_probs = probs.mean(axis=0)
    return e * jnp.sum(frac_tokens * frac_probs) / top_k


# --------------------------------------------------------------------------
# Mamba2 SSD (state-space duality, chunked)
# --------------------------------------------------------------------------

def _segsum(x: jax.Array) -> jax.Array:
    """Stable segment-sum: out[..., i, j] = sum_{k in (j, i]} x[..., k]."""
    q = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    out = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((q, q), bool), k=0)
    return jnp.where(mask, out, NEG_INF)


def ssd_chunked(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
                C: jax.Array, *, chunk: int = 256,
                init_state: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, jax.Array]:
    """Mamba2 SSD scan (Dao & Gu 2024, alg. 'chunked').

    x: (b, s, h, p); dt: (b, s, h) (softplus already applied);
    A: (h,) negative; B, C: (b, s, n)  [single group, broadcast over heads].
    Returns (y: (b, s, h, p), final_state: (b, h, p, n)).
    """
    b, s, h, p = x.shape
    n = B.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0
    nc = s // chunk
    xf = x.astype(jnp.float32).reshape(b, nc, chunk, h, p)
    dtf = dt.astype(jnp.float32).reshape(b, nc, chunk, h)
    Bf = B.astype(jnp.float32).reshape(b, nc, chunk, n)
    Cf = C.astype(jnp.float32).reshape(b, nc, chunk, n)
    dA = dtf * A.astype(jnp.float32)                      # (b,nc,q,h) <= 0
    dA_cum = jnp.cumsum(dA, axis=2)                       # within chunk
    # --- intra-chunk (attention-like, causal-decayed) ----------------------
    L = jnp.exp(_segsum(dA.transpose(0, 1, 3, 2)))        # (b,nc,h,q,q)
    scores = jnp.einsum("bcqn,bckn->bcqk", Cf, Bf)        # (b,nc,q,q)
    y_intra = jnp.einsum("bchqk,bcqk,bckh,bckhp->bcqhp",
                         L, scores, dtf, xf)
    # --- chunk states -------------------------------------------------------
    decay_to_end = jnp.exp(dA_cum[:, :, -1:, :] - dA_cum)  # (b,nc,q,h)
    states = jnp.einsum("bcqh,bcqn,bcqhp->bchpn",
                        dtf * decay_to_end, Bf, xf)        # (b,nc,h,p,n)
    # --- inter-chunk recurrence ----------------------------------------------
    chunk_decay = jnp.exp(dA_cum[:, :, -1, :])             # (b,nc,h)
    s0 = (init_state.astype(jnp.float32) if init_state is not None
          else jnp.zeros((b, h, p, n), jnp.float32))

    def scan_fn(prev, inp):
        st, dec = inp                                     # (b,h,p,n), (b,h)
        new = prev * dec[..., None, None] + st
        return new, prev

    final, prev_states = lax.scan(
        scan_fn, s0, (states.transpose(1, 0, 2, 3, 4),
                      chunk_decay.transpose(1, 0, 2)))
    prev_states = prev_states.transpose(1, 0, 2, 3, 4)     # (b,nc,h,p,n)
    decay_from_start = jnp.exp(dA_cum)                     # (b,nc,q,h)
    y_inter = jnp.einsum("bcqn,bcqh,bchpn->bcqhp",
                         Cf, decay_from_start, prev_states)
    y = (y_intra + y_inter).reshape(b, s, h, p)
    return y.astype(x.dtype), final


def ssd_decode_step(state: jax.Array, x: jax.Array, dt: jax.Array,
                    A: jax.Array, B: jax.Array, C: jax.Array
                    ) -> Tuple[jax.Array, jax.Array]:
    """Single-token SSD update.  state: (b,h,p,n); x: (b,h,p); dt: (b,h);
    B, C: (b, n).  Returns (y: (b,h,p), new_state)."""
    dA = jnp.exp(dt.astype(jnp.float32) * A.astype(jnp.float32))  # (b,h)
    xB = jnp.einsum("bhp,bn->bhpn", x.astype(jnp.float32), B.astype(jnp.float32))
    new_state = state * dA[..., None, None] + dt.astype(jnp.float32)[..., None, None] * xB
    y = jnp.einsum("bhpn,bn->bhp", new_state, C.astype(jnp.float32))
    return y.astype(x.dtype), new_state


def causal_conv1d(x: jax.Array, w: jax.Array,
                  state: Optional[jax.Array] = None,
                  bias: Optional[jax.Array] = None
                  ) -> Tuple[jax.Array, jax.Array]:
    """Depthwise causal conv + SiLU: x (b, s, c), w (width, c), bias
    (c,) or None.  Returns (y, new state = last width-1 inputs)."""
    width = w.shape[0]
    if state is None:
        state = jnp.zeros((x.shape[0], width - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([state, x], axis=1)              # (b, s+w-1, c)
    idx = jnp.arange(x.shape[1])[:, None] + jnp.arange(width)[None, :]
    windows = xp[:, idx]                                  # (b, s, w, c)
    y = jnp.einsum("bswc,wc->bsc", windows.astype(jnp.float32),
                   w.astype(jnp.float32))
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return jax.nn.silu(y).astype(x.dtype), xp[:, -(width - 1):]


# --------------------------------------------------------------------------
# Loss (chunked over sequence to bound logits memory)
# --------------------------------------------------------------------------

def xent_loss_chunked(x: jax.Array, emb: jax.Array, labels: jax.Array,
                      *, chunk: int = 512, vocab: int = 0) -> jax.Array:
    """Cross-entropy against a tied embedding, computed in sequence chunks so
    the (B, chunk, V) logits buffer stays bounded.  x: (B, S, D); emb: (V, D);
    labels: (B, S) int32.  `vocab` masks out padded vocab rows."""
    b, s, d = x.shape
    v = emb.shape[0]
    chunk = min(chunk, s)
    assert s % chunk == 0
    nc = s // chunk
    xc = x.reshape(b, nc, chunk, d).transpose(1, 0, 2, 3)
    lc = labels.reshape(b, nc, chunk).transpose(1, 0, 2)

    def body(total, inp):
        xi, li = inp
        logits = jnp.einsum("bqd,vd->bqv", xi, emb).astype(jnp.float32)
        if vocab and vocab < v:
            pad_mask = jnp.arange(v) >= vocab
            logits = jnp.where(pad_mask[None, None, :], NEG_INF, logits)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, li[..., None], axis=-1)[..., 0]
        return total + jnp.sum(lse - gold), None

    total, _ = lax.scan(body, jnp.zeros((), jnp.float32), (xc, lc))
    return total / (b * s)
