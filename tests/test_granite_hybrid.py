"""Granite-4.0-H on the served path, at smoke size on the CPU.

The smoke preset keeps the published block: the 10-layer period (nine
Mamba-2 mixers with the x‖B‖C conv and gated RMSNorm, one NoPE attention
layer), 8 experts over 2 expert shards (4 held), top-3, a shared MLP and
the Granite multipliers.  Three contracts:

  * prefill-then-decode logits of the program equal the plain reference
    (`bench/models/granite_hybrid.py`) on the reference's seeded weights;
  * the held-expert layer drops nothing: the parts that every shard
    computes, plus the shared MLP once, add up to the uncut layer;
  * the grouped-matmul kernel (interpret mode) equals a plain per-row
    einsum, empty groups and dead tiles included.
"""
import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.kernels import gmm, ref
from repro.models import layers as L
from repro.models import transformer as T

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from bench.models import common, granite_hybrid as G  # noqa: E402

ARCH = "granite_4_0_h_small"


def smoke_json():
    """The benchmark configuration's file at the smoke preset's sizes."""
    with open(os.path.join(ROOT, "bench", "configs", ARCH + ".json")) as f:
        base = json.load(f)
    return dict(base, hidden_size=64, num_hidden_layers=10,
                num_attention_heads=4, num_key_value_heads=1,
                intermediate_size=32, shared_intermediate_size=64,
                num_local_experts=4, num_experts_per_tok=3,
                mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
                vocab_size=512, attention_multiplier=1 / 16,
                published=dict(base["published"], num_local_experts=8))


def _served_logits(cfg, params, toks, plen):
    """Prefill toks[:plen] into a fresh cache row, then teacher-force the
    rest one decode step at a time; the logits after each position."""
    cache = T.init_cache(cfg, 2, 32)
    padded = np.zeros((16,), np.int32)
    padded[:plen] = toks[:plen]
    padded[plen:] = 7                                   # junk tail
    lg, cache = T.prefill_into_cache(cfg, params, cache, jnp.asarray(padded),
                                     1, plen)
    out = [np.asarray(lg, np.float32)]
    for t in range(plen, len(toks)):
        tok = jnp.asarray([[1], [toks[t]]], jnp.int32)
        step, cache = T.decode_step(cfg, params, cache, tok,
                                    positions=jnp.asarray([0, t]),
                                    write_mask=jnp.asarray([False, True]))
        out.append(np.asarray(step[1, 0], np.float32))
    return np.stack(out)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_prefill_then_decode_matches_reference(dtype):
    cfg = dataclasses.replace(get_smoke_config(ARCH), dtype=dtype)
    sj = smoke_json()
    G.check_program(sj, get_smoke_config(ARCH))
    params = G.init_params(sj, 2 ** 40 + 3)
    served = jax.tree.map(
        lambda a: a.astype(dtype) if a.dtype == jnp.bfloat16 else a, params)
    rng = np.random.default_rng(4)
    plen, n = 11, 7
    toks = rng.integers(1, 512, plen + n).astype(np.int32)
    got = _served_logits(cfg, served, toks, plen)
    want = G.logits_at(sj, params, toks, np.arange(plen - 1, plen + n))
    spread = float(want.std())
    err = float(np.abs(got - want).max())
    if dtype == "float32":
        # the same arithmetic in float32 on both sides: only summation
        # order differs (chunked SSD scan, grouped matmuls, flash-style
        # attention merges against the token-by-token reference)
        assert err < 1e-4 * spread, (err, spread)
    else:
        # bfloat16 weights, activations and residual stream against the
        # float32 reference: rounding at 2^-8 compounds over 20 sublayers
        # (the worst logit 0.11-0.33 of the logits' spread off at this
        # width); each row still follows the reference's, correlation
        # 0.994-0.999 on six seeds (the int8 control: 0.859-0.992)
        for g, w in zip(got, want):
            assert np.corrcoef(g, w)[0, 1] > 0.95


def test_expert_shares_add_up_to_the_uncut_layer():
    """2 shards of 4 experts: each chip's held part (`moe_held` with its
    shard), plus the shared MLP counted once, is the reference's whole
    layer over all 8 experts — no (token, expert) pair dropped, however
    unevenly the router loads the experts."""
    sj = smoke_json()
    s = common.frozen(dict(G.sizes(sj), Eh=8))         # the uncut layer
    full = dict(sj, num_local_experts=8)
    params = G.init_params(full, 11)
    p = jax.tree.map(lambda w: w[0].astype(jnp.float32),
                     params["blocks"][0]["ffn"])
    # a skewed router: inputs with a common direction that experts 0-2
    # favour, so most tokens pile onto shard 0
    p["router"] = p["router"].at[:, :3].add(0.05)
    t, k = 40, s["k"]
    h = jax.random.normal(jax.random.key(3), (t, s["d"]), jnp.float32) + 0.5
    parts = [L.moe_held(h, p["router"], p["w_gate"][i * 4:(i + 1) * 4],
                        p["w_up"][i * 4:(i + 1) * 4],
                        p["w_down"][i * 4:(i + 1) * 4], k, shard=i)
             for i in range(2)]
    sh = p["shared"]
    got = parts[0] + parts[1] + L.gated_mlp(h, sh["w_gate"], sh["w_up"],
                                            sh["w_down"])
    with jax.default_matmul_precision("highest"):
        want = G._moe(s, common.matmul("f32"), p, h)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # and each shard really is a share: neither part alone is the layer
    for part in parts:
        assert float(jnp.abs(part).max()) > 1e-3


def test_held_share_is_refused_on_a_model_axis():
    """A held share of the experts serves one chip only: under rules with
    a model axis `moe_ffn_dist` refuses it rather than return a part of
    the routed sum, and still runs every expert when all are held."""
    from repro import sharding as sh
    from repro.launch.mesh import make_debug_mesh
    ks = jax.random.split(jax.random.key(5), 5)
    t, d, f, e = 8, 16, 8, 8
    x = jax.random.normal(ks[0], (t, d), jnp.float32)
    router = jax.random.normal(ks[1], (d, e), jnp.float32)
    wg, wu = (jax.random.normal(kk, (e, d, f), jnp.float32) * 0.2
              for kk in ks[2:4])
    wd = jax.random.normal(ks[4], (e, f, d), jnp.float32) * 0.2
    with sh.use_rules(sh.ShardingRules(make_debug_mesh(1, 1))):
        with pytest.raises(NotImplementedError, match="4 of 8 experts"):
            L.moe_ffn_dist(x, router, wg[:4], wu[:4], wd[:4], 2)
        got = L.moe_ffn_dist(x, router, wg, wu, wd, 2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(
        L.moe_held(x, router, wg, wu, wd, 2)), rtol=1e-6, atol=1e-6)


def _aligned_rows(sizes, tm, k, rng):
    """Group-aligned rows: each group's run of rows padded to whole
    tiles; returns (lhs (M, k), row_group (M,), tile_group, n_live)."""
    m = sum(-(-n // tm) * tm for n in sizes) + 2 * tm    # two dead tiles
    lhs = np.zeros((m, k), np.float32)
    row_group = np.full((m,), -1)
    tiles, r = [], 0
    for g, n in enumerate(sizes):
        lhs[r:r + n] = rng.normal(size=(n, k))
        row_group[r:r + n] = g
        span = -(-n // tm) * tm
        tiles += [g] * (span // tm)
        r += span
    n_live = len(tiles)
    tiles += [tiles[-1]] * (m // tm - n_live)
    return lhs, row_group, np.asarray(tiles, np.int32), n_live


@pytest.mark.parametrize("fused", [False, True])
@pytest.mark.parametrize("sizes", [(20, 0, 5, 16), (0, 0, 3, 0)])
def test_expert_gmm_kernel_matches_einsum(fused, sizes):
    tm, k, n = 16, 128, 256
    rng = np.random.default_rng(sum(sizes))
    lhs, row_group, tiles, n_live = _aligned_rows(sizes, tm, k, rng)
    g = len(sizes)
    w = rng.normal(size=(g, k, n)).astype(np.float32) * k ** -0.5
    w2 = rng.normal(size=(g, k, n)).astype(np.float32) * k ** -0.5
    out = gmm.expert_gmm(jnp.asarray(lhs), jnp.asarray(w),
                         jnp.asarray(tiles), jnp.asarray([n_live]),
                         jnp.asarray(w2) if fused else None, tm=tm,
                         interpret=True)
    # plain per-row einsum: each row times its own group's weights
    sel = np.maximum(row_group, 0)
    want = np.einsum("mk,mkn->mn", lhs, w[sel])
    if fused:
        want = np.asarray(jax.nn.silu(want)) * np.einsum(
            "mk,mkn->mn", lhs, w2[sel])
    want[row_group < 0] = 0.0                  # padding rows, dead tiles
    np.testing.assert_allclose(np.asarray(out), want, rtol=2e-5, atol=2e-5)
    oracle = ref.expert_gmm_reference(
        jnp.asarray(lhs), jnp.asarray(w), jnp.asarray(tiles),
        jnp.asarray([n_live]), jnp.asarray(w2) if fused else None, tm=tm)
    np.testing.assert_allclose(np.asarray(oracle), want, rtol=2e-5,
                               atol=2e-5)


def test_tile_rows_follow_the_expected_expert_load():
    # decode at 64 slots: 64 x 10 / 72 = 8.9 rows an expert -> 16-row
    # tiles; a 4,096-token prefill -> the MXU's 128
    assert L._tile_rows(64, 10, 72) == 16
    assert L._tile_rows(4096, 10, 72) == 128
    assert L._tile_rows(2, 2, 16) == 16
