"""Per-kernel validation: Pallas interpret-mode vs pure-jnp oracle, swept
over shapes and dtypes (assignment deliverable (c))."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import flash_attention as fa
from repro.kernels import ops, ref

jax.config.update("jax_enable_x64", False)


def rand(key, shape, dtype):
    return jax.random.normal(key, shape).astype(dtype)


# ------------------------------------------------------------ flash attention

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("b,s,h,kh,hd", [
    (1, 128, 4, 4, 64),      # MHA
    (2, 256, 8, 2, 64),      # GQA 4:1
    (1, 256, 4, 1, 128),     # MQA, wide head
    (2, 128, 2, 2, 32),      # small head dim
])
def test_flash_attention_matches_ref(b, s, h, kh, hd, dtype):
    ks = jax.random.split(jax.random.key(0), 3)
    q = rand(ks[0], (b, s, h, hd), dtype)
    k = rand(ks[1], (b, s, kh, hd), dtype)
    v = rand(ks[2], (b, s, kh, hd), dtype)
    out = ops.flash_attention(q, k, v, causal=True, interpret=True)
    want = ref.mha_reference(q, k, v, causal=True)
    tol = 2e-2 if dtype == "bfloat16" else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


def test_flash_attention_noncausal():
    ks = jax.random.split(jax.random.key(1), 3)
    q = rand(ks[0], (1, 128, 2, 64), "float32")
    k = rand(ks[1], (1, 128, 2, 64), "float32")
    v = rand(ks[2], (1, 128, 2, 64), "float32")
    out = ops.flash_attention(q, k, v, causal=False, interpret=True)
    want = ref.mha_reference(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("window", [32, 64])
def test_flash_attention_sliding_window(window):
    ks = jax.random.split(jax.random.key(2), 3)
    q = rand(ks[0], (1, 256, 2, 64), "float32")
    k = rand(ks[1], (1, 256, 2, 64), "float32")
    v = rand(ks[2], (1, 256, 2, 64), "float32")
    out = ops.flash_attention(q, k, v, causal=True, window=window,
                              interpret=True)
    want = ref.mha_reference(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def _prefill_lengths(s, group):
    """None, the whole sequence, one token, a length inside a KV block
    and one inside a q tile, from the kernel's own tiles at (s, group)."""
    blk_q, blk_k = fa.flash_tiles(s, group)
    return {"none": None, "all": s, "one": 1,
            "mid_block": (s // blk_k - 1) * blk_k + blk_k // 2 + 1,
            "mid_tile": (s // blk_q - 1) * blk_q + blk_q // 2 - 1}


@pytest.mark.parametrize("length", ["none", "all", "one", "mid_block",
                                    "mid_tile"])
@pytest.mark.parametrize("windowed", [False, True])
@pytest.mark.parametrize("group,kh,hd", [(1, 1, 64), (4, 2, 128),
                                         (12, 1, 128)])
@pytest.mark.parametrize("s", [8, 256, 1024])
def test_flash_prefill_parity(s, group, kh, hd, windowed, length):
    """Live rows (below `length`) equal the reference's; junk rows are
    finite.  The tiles, the live-block skipping and the clamped index
    maps all follow from (s, group, length, window)."""
    window = s // 4 + 3 if windowed else 0
    n = _prefill_lengths(s, group)[length]
    ks = jax.random.split(jax.random.key(7), 3)
    q = rand(ks[0], (1, s, group * kh, hd), "float32")
    k = rand(ks[1], (1, s, kh, hd), "float32")
    v = rand(ks[2], (1, s, kh, hd), "float32")
    arg = None if n is None else jnp.int32(n)
    out = np.asarray(ops.flash_attention(q, k, v, arg, causal=True,
                                         window=window, interpret=True))
    want = np.asarray(ref.mha_reference(q, k, v, causal=True, window=window))
    live = s if n is None else n
    np.testing.assert_allclose(out[:, :live], want[:, :live],
                               atol=2e-5, rtol=2e-5)
    assert np.isfinite(out).all()


@pytest.mark.parametrize("s,group,causal,windowed", [
    (8, 1, True, False), (256, 12, True, True), (1024, 12, True, False),
    (1024, 12, True, True), (1024, 4, True, True), (1024, 1, False, True),
    (2048, 12, True, True), (4096, 4, True, False)])
def test_flash_live_blocks_counts_dense_mask(s, group, causal, windowed):
    """`flash_live_blocks` counts exactly the (q block, kv block) pairs
    that hold a pair a dense (causal, window, length) mask marks live,
    with windows and lengths on and beside the tiles' edges."""
    blk_q, blk_k = fa.flash_tiles(s, group)
    qpos = np.arange(s)[:, None]
    kpos = np.arange(s)[None, :]
    edges = {1, 2, blk_q - 1, blk_q, blk_q + 1, blk_q + 2, blk_k - 1, blk_k,
             blk_k + 1, blk_k + 2, s // 3 + 5, s - 1}
    windows = sorted(w for w in edges if 0 < w < s) if windowed else [0]
    lengths = sorted(n for n in edges | {s // 2, s} if 0 < n <= s)
    for window in windows:
        for length in lengths:
            mask = (qpos < length) & (kpos < length)
            if causal:
                mask &= kpos <= qpos
            if window:
                mask &= kpos > qpos - window
            blocks = mask.reshape(s // blk_q, blk_q, s // blk_k, blk_k)
            want = int(blocks.any(axis=(1, 3)).sum())
            got = fa.flash_live_blocks(length, s, blk_q, blk_k, causal,
                                       window)
            assert got == (want, (s // blk_q) * (s // blk_k)), \
                (window, length, got)


@pytest.mark.parametrize("length", [1, 300, 700])
def test_flash_prefill_bidirectional_length(length):
    """Without the causal mask, keys at or past `length` are still seen
    by no live row: the kernel masks them inside a partly live block
    and skips the wholly dead ones."""
    s, group, hd = 1024, 4, 64
    ks = jax.random.split(jax.random.key(9), 3)
    q = rand(ks[0], (1, s, group, hd), "float32")
    k = rand(ks[1], (1, s, 1, hd), "float32")
    v = rand(ks[2], (1, s, 1, hd), "float32")
    n = jnp.int32(length)
    out = ops.flash_attention(q, k, v, n, causal=False, interpret=True)
    want = ref.mha_reference(q, k, v, n, causal=False)
    np.testing.assert_allclose(np.asarray(out[:, :length]),
                               np.asarray(want[:, :length]),
                               atol=2e-5, rtol=2e-5)
    assert np.isfinite(np.asarray(out)).all()


@pytest.mark.parametrize("group,window,length", [(12, 0, 300),
                                                 (4, 200, 512),
                                                 (1, 0, 1)])
def test_flash_prefill_never_reads_dead_kv_blocks(group, window, length):
    """K/V blocks wholly past `length` hold NaN: the live rows do not
    change, so those blocks are neither read nor mixed in."""
    s, kh, hd = 1024, 1, 128
    _, blk_k = fa.flash_tiles(s, group)
    first_dead = -(-length // blk_k) * blk_k
    assert first_dead < s
    ks = jax.random.split(jax.random.key(8), 3)
    q = rand(ks[0], (1, s, group * kh, hd), "float32")
    k = rand(ks[1], (1, s, kh, hd), "float32")
    v = rand(ks[2], (1, s, kh, hd), "float32")
    clean = ops.flash_attention(q, k, v, jnp.int32(length), window=window,
                                interpret=True)
    k_nan = k.at[:, first_dead:].set(jnp.nan)
    v_nan = v.at[:, first_dead:].set(jnp.nan)
    dirty = ops.flash_attention(q, k_nan, v_nan, jnp.int32(length),
                                window=window, interpret=True)
    np.testing.assert_array_equal(np.asarray(dirty[:, :length]),
                                  np.asarray(clean[:, :length]))
    want = ref.mha_reference(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(dirty[:, :length]),
                               np.asarray(want[:, :length]),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("c,valid_up_to", [(128, 128), (256, 100), (256, 1)])
def test_decode_partial_matches_ref(c, valid_up_to):
    b, h, kh, hd = 2, 8, 2, 64
    ks = jax.random.split(jax.random.key(3), 3)
    q = rand(ks[0], (b, 1, h, hd), "float32")
    k = rand(ks[1], (b, kh, c, hd), "float32")
    v = rand(ks[2], (b, kh, c, hd), "float32")
    valid = jnp.broadcast_to(jnp.arange(c) < valid_up_to, (b, c))
    acc, m, l = ops.decode_attention_partial(q, k, v, valid, blk_c=64,
                                             interpret=True)
    acc_r, m_r, l_r = ref.decode_partial_reference(q, k, v, valid)
    np.testing.assert_allclose(np.asarray(acc), np.asarray(acc_r),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(m), np.asarray(m_r),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(l), np.asarray(l_r),
                               atol=1e-4, rtol=1e-4)


def test_decode_partial_merge_equals_full_softmax():
    """Merging per-chunk partials must equal unchunked attention — the
    correctness contract the back-streaming protocol relies on."""
    from repro.models import layers as L
    b, c, h, kh, hd = 1, 256, 4, 2, 64
    ks = jax.random.split(jax.random.key(4), 3)
    q = rand(ks[0], (b, 1, h, hd), "float32")
    k = rand(ks[1], (b, kh, c, hd), "float32")
    v = rand(ks[2], (b, kh, c, hd), "float32")
    valid = jnp.ones((b, c), bool)
    halves = []
    for i in range(2):
        sl = slice(i * c // 2, (i + 1) * c // 2)
        halves.append(ops.decode_attention_partial(
            q, k[:, :, sl], v[:, :, sl], valid[:, sl], blk_c=64,
            interpret=True))
    accs = jnp.stack([x[0] for x in halves])
    ms = jnp.stack([x[1] for x in halves])
    ls = jnp.stack([x[2] for x in halves])
    merged = L.merge_attention_partials(accs, ms, ls)
    want = ref.mha_reference(
        jnp.asarray(q), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
        causal=False)[:, 0]
    np.testing.assert_allclose(np.asarray(merged), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


# --------------------------------------------------------------------- knn

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("q,n,d", [(128, 256, 64), (64, 128, 512),
                                   (128, 128, 32)])
def test_knn_distances(q, n, d, dtype):
    ks = jax.random.split(jax.random.key(5), 2)
    qs = rand(ks[0], (q, d), dtype)
    db = rand(ks[1], (n, d), dtype)
    out = ops.knn_distances(qs, db, blk_q=64, blk_n=64, interpret=True)
    want = ref.knn_distances_reference(qs, db)
    tol = 5e-2 if dtype == "bfloat16" else 1e-4
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=tol * d, rtol=tol)


def test_knn_topk_exact_neighbors():
    ks = jax.random.split(jax.random.key(6), 2)
    qs = rand(ks[0], (64, 128), "float32")
    db = rand(ks[1], (256, 128), "float32")
    dist, idx = ops.knn_topk(qs, db, 8, blk_q=64, blk_n=64, interpret=True)
    _, idx_ref = ref.knn_topk_reference(qs, db, 8)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(idx_ref))
    assert bool(jnp.all(dist[:, 1:] >= dist[:, :-1]))


# --------------------------------------------------------------------- sls

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("v,d,b,l", [(512, 64, 16, 8), (1024, 128, 8, 32)])
def test_sls_matches_ref(v, d, b, l, dtype):
    ks = jax.random.split(jax.random.key(7), 3)
    table = rand(ks[0], (v, d), dtype)
    idx = jax.random.randint(ks[1], (b, l), 0, v).astype(jnp.int32)
    w = jax.random.uniform(ks[2], (b, l), jnp.float32)
    out = ops.sls(table, idx, w, blk_b=8, interpret=True)
    want = ref.sls_reference(table, idx, w)
    tol = 5e-2 if dtype == "bfloat16" else 1e-5
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=tol * l, rtol=tol)


def test_sls_padding_masked():
    table = jnp.ones((32, 16), jnp.float32)
    idx = jnp.array([[0, 1, -1, -1], [2, -1, -1, -1]], jnp.int32)
    out = ops.sls(table, idx, None, blk_b=2, interpret=True)
    np.testing.assert_allclose(np.asarray(out[0]), 2.0)
    np.testing.assert_allclose(np.asarray(out[1]), 1.0)


# --------------------------------------------------------------------- ssd

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("b,s,h,p,n,blk", [
    (1, 128, 2, 32, 32, 64),
    (2, 256, 4, 64, 128, 128),
])
def test_ssd_matches_sequential_ref(b, s, h, p, n, blk, dtype):
    ks = jax.random.split(jax.random.key(8), 4)
    x = rand(ks[0], (b, s, h, p), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h))).astype(jnp.float32)
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    B = rand(ks[3], (b, s, n), dtype)
    C = rand(ks[0], (b, s, n), dtype)
    y, fin = ops.ssd_scan(x, dt, A, B, C, blk_s=blk, interpret=True)
    y_r, fin_r = ref.ssd_reference(x, dt, A, B, C)
    tol = 6e-2 if dtype == "bfloat16" else 1e-3
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_r, np.float32),
                               atol=tol * 10, rtol=tol)
    np.testing.assert_allclose(np.asarray(fin), np.asarray(fin_r),
                               atol=tol * 10, rtol=tol)


def test_ssd_init_state_handoff():
    """Splitting a sequence in half and handing the state across must equal
    the unsplit scan — the sequence-parallel streaming contract."""
    b, s, h, p, n = 1, 256, 2, 32, 64
    ks = jax.random.split(jax.random.key(9), 4)
    x = rand(ks[0], (b, s, h, p), "float32")
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h))).astype(jnp.float32)
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    B = rand(ks[3], (b, s, n), "float32")
    C = rand(ks[0], (b, s, n), "float32")
    y_full, fin_full = ops.ssd_scan(x, dt, A, B, C, blk_s=64, interpret=True)
    half = s // 2
    y1, st = ops.ssd_scan(x[:, :half], dt[:, :half], A, B[:, :half],
                          C[:, :half], blk_s=64, interpret=True)
    y2, fin = ops.ssd_scan(x[:, half:], dt[:, half:], A, B[:, half:],
                           C[:, half:], st, blk_s=64, interpret=True)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_full), atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(fin), np.asarray(fin_full),
                               atol=1e-3, rtol=1e-3)
