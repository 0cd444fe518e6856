"""Fused one-shot flash-decode kernel + streamed serve loop.

Contract 1 (kernel): `decode_attention_fused` — ONE pallas_call whose
innermost grid axis accumulates partial-softmax statistics in VMEM and
writes the normalized output once — must match the pure-jnp oracle across
GQA groups, sliding windows, ragged per-row positions, chunk counts, and
the fused extra-partial epilogue (interpret mode on CPU).

Contract 2 (loop): the producer-initiated streamed serve loop (jitted
multi-token segments, host syncs once per segment) must emit tokens
identical to the per-token loop, and per-row position clocks must make a
request's tokens independent of which slot/batch it shares.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.backstream import (OffloadConfig, OffloadProtocol,
                                   decode_attention_combined, use_offload)
from repro.kernels import flash_attention as fa
from repro.kernels import ref
from repro.models import layers as L


def rand(key, shape, dtype="float32"):
    return jax.random.normal(key, shape).astype(dtype)


# ---------------------------------------------------------- kernel parity

@pytest.mark.parametrize("h,kh", [(4, 4), (8, 2), (4, 1)])   # MHA/GQA/MQA
@pytest.mark.parametrize("blk_c", [32, 64, 128])             # 1..8 chunks
def test_fused_matches_ref_gqa_and_chunks(h, kh, blk_c):
    b, s, hd = 3, 256, 64
    ks = jax.random.split(jax.random.key(0), 3)
    q = rand(ks[0], (b, 1, h, hd))
    k = rand(ks[1], (b, kh, s, hd))
    v = rand(ks[2], (b, kh, s, hd))
    pos = jnp.asarray([s - 1, s // 2, 7], jnp.int32)         # ragged rows
    out = fa.decode_attention_fused(q, k, v, pos, blk_c=blk_c,
                                    interpret=True)
    want = ref.decode_fused_reference(q, k, v, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("window", [16, 64])
def test_fused_sliding_window_per_row(window):
    b, s, h, kh, hd = 2, 128, 4, 2, 32
    ks = jax.random.split(jax.random.key(1), 3)
    q = rand(ks[0], (b, 1, h, hd))
    k = rand(ks[1], (b, kh, s, hd))
    v = rand(ks[2], (b, kh, s, hd))
    pos = jnp.asarray([s - 1, 40], jnp.int32)
    out = fa.decode_attention_fused(q, k, v, pos, window=window,
                                    blk_c=32, interpret=True)
    want = ref.decode_fused_reference(q, k, v, pos, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


def test_fused_scalar_pos_broadcasts():
    b, s, h, kh, hd = 2, 64, 4, 2, 32
    ks = jax.random.split(jax.random.key(2), 3)
    q = rand(ks[0], (b, 1, h, hd))
    k = rand(ks[1], (b, kh, s, hd))
    v = rand(ks[2], (b, kh, s, hd))
    out = fa.decode_attention_fused(q, k, v, jnp.asarray(17, jnp.int32),
                                    blk_c=16, interpret=True)
    want = ref.decode_fused_reference(q, k, v, jnp.full((b,), 17))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


def test_fused_extra_partial_epilogue():
    """The current token's own (acc, m, l) partial merges in-kernel: the
    result must equal plain attention over a cache where the new token's
    KV is physically written at slot pos+1 (per row)."""
    b, s, h, kh, hd = 2, 128, 4, 2, 32
    ks = jax.random.split(jax.random.key(3), 5)
    q = rand(ks[0], (b, 1, h, hd))
    k = rand(ks[1], (b, kh, s, hd))
    v = rand(ks[2], (b, kh, s, hd))
    k_new = rand(ks[3], (b, 1, kh, hd))
    v_new = rand(ks[4], (b, 1, kh, hd))
    extra = L.single_kv_partial(q, k_new, v_new)
    pos = jnp.asarray([s - 2, 3], jnp.int32)
    out = fa.decode_attention_fused(q, k, v, pos, extra, blk_c=32,
                                    interpret=True)
    want = ref.decode_fused_reference(q, k, v, pos, extra)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-4, rtol=1e-4)
    # semantic oracle: write the new KV at slot pos+1 and attend to pos+1
    for row, p in enumerate(np.asarray(pos)):
        kc = k.at[row, :, p + 1].set(k_new[row, 0])
        vc = v.at[row, :, p + 1].set(v_new[row, 0])
        full = ref.decode_fused_reference(
            q[row:row + 1], kc[row:row + 1], vc[row:row + 1],
            jnp.asarray([p + 1]))
        np.testing.assert_allclose(np.asarray(out)[row],
                                   np.asarray(full)[0],
                                   atol=1e-4, rtol=1e-4)


def test_fused_partial_head_groups_recompose_bitwise():
    """The mesh decode's producer (DESIGN.md §11): the fused kernel's raw
    statistics, computed per KV-head group and concatenated over heads,
    normalize to exactly the single-call output — each (row, head) cell
    of the kernel is independent of the others, paged int8 pools and the
    extra partial included (interpret mode)."""
    b, s, h, kh, hd, page = 2, 128, 8, 2, 32, 32
    ks = jax.random.split(jax.random.key(5), 6)
    q = rand(ks[0], (b, 1, h, hd), "bfloat16")
    k, k_sc = ref.quantize_kv_pages(rand(ks[1], (b, kh, s, hd)), page)
    v, v_sc = ref.quantize_kv_pages(rand(ks[2], (b, kh, s, hd)), page)
    pages = jnp.stack([jax.random.permutation(kk, s // page)
                       for kk in jax.random.split(ks[3], b)])
    extra = L.single_kv_partial(q, rand(ks[4], (b, 1, kh, hd), "bfloat16"),
                                rand(ks[5], (b, 1, kh, hd), "bfloat16"))
    pos = jnp.asarray([s - 1, 40], jnp.int32)
    whole = fa.decode_attention_fused(
        q, k, v, pos, extra, blk_c=page, pages=pages,
        kv_scales=(k_sc, v_sc), interpret=True)
    g = h // kh
    parts = [fa.decode_attention_fused_partial(
        q[:, :, i * g:(i + 1) * g], k[:, i:i + 1], v[:, i:i + 1], pos,
        tuple(e[:, i * g:(i + 1) * g] for e in extra), blk_c=page,
        pages=pages, kv_scales=(k_sc[:, i:i + 1], v_sc[:, i:i + 1]),
        interpret=True) for i in range(kh)]
    acc = jnp.concatenate([p[0] for p in parts], axis=1)
    l = jnp.concatenate([p[2] for p in parts], axis=1)
    merged = ref.normalize_fused_partial(acc, l, q.dtype)
    assert np.array_equal(np.asarray(merged, np.float32),
                          np.asarray(whole, np.float32))


def test_fused_empty_rows_are_zero():
    """pos = -1 (nothing valid, no extra) must yield exactly zero, not a
    uniform average — the epilogue's l==0 guard."""
    b, s, h, kh, hd = 2, 64, 2, 2, 16
    ks = jax.random.split(jax.random.key(4), 3)
    q = rand(ks[0], (b, 1, h, hd))
    k = rand(ks[1], (b, kh, s, hd))
    v = rand(ks[2], (b, kh, s, hd))
    pos = jnp.asarray([-1, 10], jnp.int32)
    out = np.asarray(fa.decode_attention_fused(q, k, v, pos, blk_c=16,
                                               interpret=True))
    assert np.all(out[0] == 0.0)
    want = ref.decode_fused_reference(q, k, v, pos)
    np.testing.assert_allclose(out, np.asarray(want), atol=1e-4, rtol=1e-4)


# ------------------------------------------- combined: fused vs fallback

@pytest.mark.parametrize("n_chunks", [1, 4])
def test_combined_fused_matches_chunked_fallback(n_chunks):
    """decode_attention_combined: the fused fast path and the retained
    chunked lax.map fallback must agree for ragged per-row positions."""
    b, s, h, kh, hd = 2, 64, 4, 2, 16
    ks = jax.random.split(jax.random.key(5), 3)
    q = rand(ks[0], (b, 1, h, hd))
    kc = rand(ks[1], (b, kh, s, hd))
    vc = rand(ks[2], (b, kh, s, hd))
    pos = jnp.asarray([s - 1, 11], jnp.int32)
    outs = {}
    for fused in (True, False):
        with use_offload(OffloadConfig(protocol=OffloadProtocol.BS,
                                       fused=fused)):
            outs[fused] = np.asarray(decode_attention_combined(
                q, kc, vc, pos, n_chunks=n_chunks))
    np.testing.assert_allclose(outs[True], outs[False], atol=1e-5,
                               rtol=1e-5)
    want = np.asarray(ref.decode_fused_reference(q, kc, vc, pos))
    np.testing.assert_allclose(outs[True], want, atol=1e-5, rtol=1e-5)


# --------------------------------------------------- serve loop parity

def _mk_server(**kw):
    from repro.launch.serve import BatchedServer
    return BatchedServer("starcoder2_3b", smoke=True, max_seq=64,
                         protocol="bs", **kw)


def _submit_all(server, n_req=4, max_new=9):
    from repro.launch.serve import Request
    rng = np.random.default_rng(7)
    reqs = []
    for i in range(n_req):
        plen = int(rng.integers(3, 7))
        prompt = rng.integers(1, server.cfg.vocab, plen).astype(np.int32)
        reqs.append(Request(i, prompt, max_new))
        server.submit(reqs[-1])
    return reqs


def test_streamed_tokens_match_per_token_loop():
    """Acceptance: streamed segments emit tokens identical to the
    per-token loop, with <= 1 host sync per seg_len tokens."""
    per_tok = _mk_server(batch_slots=2, stream=False)
    _submit_all(per_tok)
    per_tok.run_until_drained()
    want = {r.rid: tuple(r.generated) for r in per_tok.completed}

    seg = _mk_server(batch_slots=2, stream=True, seg_len=8)
    _submit_all(seg)
    seg.run_until_drained()
    got = {r.rid: tuple(r.generated) for r in seg.completed}

    assert set(got) == set(want)
    for rid in want:
        assert got[rid] == want[rid], rid
    toks = sum(len(g) for g in got.values())
    # one device_get per dispatched segment; every segment is seg_len
    # token-steps, so the decode loop syncs at most once per 8 tokens
    # of device work (junk tail tokens of retiring slots included).
    assert seg.decode_syncs == seg.segments_dispatched
    assert seg.decode_syncs * seg.seg_len <= seg.steps + seg.seg_len
    assert toks >= seg.decode_syncs  # >= 1 useful token per sync here


def test_request_tokens_independent_of_batching():
    """Per-row position clocks: a request decoded alone must produce the
    same tokens as the same request continuously batched with others."""
    batched = _mk_server(batch_slots=2, stream=False)
    reqs = _submit_all(batched, n_req=3, max_new=7)
    batched.run_until_drained()
    got = {r.rid: tuple(r.generated) for r in batched.completed}

    for r in reqs:
        solo = _mk_server(batch_slots=1, stream=False)
        from repro.launch.serve import Request
        solo.submit(Request(r.rid, r.prompt, 7))
        solo.run_until_drained()
        (done,) = solo.completed
        assert tuple(done.generated) == got[r.rid], r.rid


def test_prefill_feeds_full_prompt_kv():
    """Real prefill: the first generated token must depend on EARLY prompt
    tokens (last-token seeding cannot see them)."""
    s1 = _mk_server(batch_slots=1)
    s2 = _mk_server(batch_slots=1)
    from repro.launch.serve import Request
    rng = np.random.default_rng(3)
    base = rng.integers(1, s1.cfg.vocab, 6).astype(np.int32)
    variant = base.copy()
    variant[0] = (variant[0] + 1) % s1.cfg.vocab or 1
    s1.submit(Request(0, base, 4))
    s2.submit(Request(0, variant, 4))
    s1.run_until_drained()
    s2.run_until_drained()
    assert s1.completed[0].generated != s2.completed[0].generated \
        or not np.array_equal(base, variant)
