"""Prefill-into-cache for SSM/hybrid and encoder-decoder serving.

Contract 1 (state capture): the whole-prompt prefill must land decode in
EXACTLY the state the per-token path would have reached — the SSD scan's
final recurrent state and the causal conv's trailing input window equal
the states after stepping the prompt one token at a time, junk padding
masked out of the recurrence.

Contract 2 (serving parity): for every architecture family that used to
fall back to last-token seeding (mamba2 = pure SSM, jamba = hybrid,
whisper = encoder-decoder), the streamed continuous-batching server must
emit tokens identical to greedy decoding with the whole-sequence forward
(`logits_fn`) — the reference that recomputes everything from scratch
per token and therefore cannot be wrong about state handoff.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.kernels import ref
from repro.models import layers as L
from repro.models import transformer
from repro.models.registry import get_model


def rand(key, shape, dtype="float32"):
    return jax.random.normal(key, shape).astype(dtype)


# ------------------------------------------------------- state capture

@pytest.mark.parametrize("s,chunk", [(64, 16), (96, 32), (32, 32)])
def test_ssd_chunked_final_state_matches_sequential(s, chunk):
    b, h, p, n = 2, 3, 8, 16
    ks = jax.random.split(jax.random.key(0), 4)
    x = rand(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(rand(ks[1], (b, s, h)))
    A = -jnp.exp(rand(ks[2], (h,)) * 0.1)
    B = rand(ks[3], (b, s, n))
    C = rand(jax.random.key(9), (b, s, n))
    y, fin = L.ssd_chunked(x, dt, A, B, C, chunk=chunk)
    y_r, fin_r = ref.ssd_reference(x, dt, A, B, C)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_r),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(fin), np.asarray(fin_r),
                               atol=1e-4, rtol=1e-4)


def test_causal_conv_state_threading():
    """Whole-sequence conv state == state after stepping token by token,
    and a second segment resumed from the state matches the full run."""
    b, s, c, width = 2, 12, 6, 4
    ks = jax.random.split(jax.random.key(1), 2)
    x = rand(ks[0], (b, s, c))
    w = rand(ks[1], (width, c))
    y_full, st_full = L.causal_conv1d(x, w)
    st = None
    ys = []
    for t in range(s):
        y_t, st = L.causal_conv1d(x[:, t:t + 1], w, st)
        ys.append(y_t)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(ys, axis=1)),
                               np.asarray(y_full), atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(st), np.asarray(st_full),
                               atol=1e-6, rtol=1e-6)


@pytest.mark.parametrize("arch_id", ["mamba2_370m", "jamba_1_5_large"])
def test_prefill_states_match_per_token_decode(arch_id):
    """transformer.prefill_into_cache (padded prompt, one shot) must leave
    the slot's conv/ssm/KV caches where per-token decode_step teacher
    forcing leaves them — including the junk tail past `length`, which
    must NOT leak into the recurrent states."""
    cfg = get_smoke_config(arch_id)
    model = get_model(cfg)
    params = model.init_params(cfg, jax.random.key(0))
    plen, pad_to, max_seq = 5, 8, 16
    rng = np.random.default_rng(11)
    prompt = rng.integers(1, cfg.vocab, plen).astype(np.int32)
    padded = np.zeros((pad_to,), np.int32)
    padded[:plen] = prompt
    padded[plen:] = rng.integers(1, cfg.vocab, pad_to - plen)  # junk tail

    cache_a = model.init_cache(cfg, 1, max_seq)
    logits_a, cache_a = transformer.prefill_into_cache(
        cfg, params, cache_a, jnp.asarray(padded), 0, plen)

    cache_b = model.init_cache(cfg, 1, max_seq)
    for t in range(plen):
        logits_b, cache_b = model.decode_step(
            cfg, params, cache_b, jnp.asarray([[prompt[t]]]),
            positions=jnp.asarray([t]))

    for pos_i, kind in enumerate(cfg.block_pattern):
        if kind == "mamba":
            for key in (f"conv{pos_i}", f"ssm{pos_i}"):
                np.testing.assert_allclose(
                    np.asarray(cache_a[key], np.float32),
                    np.asarray(cache_b[key], np.float32),
                    atol=2e-2, rtol=2e-2, err_msg=key)
        else:
            for key in (f"k{pos_i}", f"v{pos_i}"):
                np.testing.assert_allclose(
                    np.asarray(cache_a[key][:, :, :, :plen], np.float32),
                    np.asarray(cache_b[key][:, :, :, :plen], np.float32),
                    atol=2e-2, rtol=2e-2, err_msg=key)
    # next-token prediction at the last prompt position agrees
    assert int(jnp.argmax(logits_a)) == int(jnp.argmax(logits_b[0, -1]))


def test_supports_prefill_for_every_config():
    """Acceptance: every registered config — attention, SSM, hybrid and
    enc-dec — is a first-class citizen of the prefill path."""
    for arch_id in ARCH_IDS:
        for cfg in (get_config(arch_id), get_smoke_config(arch_id)):
            assert transformer.supports_prefill_into_cache(cfg), cfg.arch_id


# ------------------------------------------------------ serving parity

def _reference_greedy(cfg, model, params, prompt, max_new, embeds=None):
    """Greedy decode via the whole-sequence forward — no caches at all."""
    toks = [int(t) for t in prompt]
    out = []
    for _ in range(max_new):
        batch = {"tokens": jnp.asarray(np.asarray(toks, np.int32))[None]}
        if cfg.enc_dec:
            batch["embeds"] = jnp.asarray(embeds)[None]
        logits = model.logits_fn(cfg, params, batch)
        nxt = int(jnp.argmax(logits[0, -1]))
        out.append(nxt)
        toks.append(nxt)
    return out


@pytest.mark.parametrize("arch_id",
                         ["mamba2_370m", "jamba_1_5_large",
                          "whisper_large_v3"])
def test_streamed_serving_matches_whole_sequence_forward(arch_id):
    """Acceptance: prefill-into-cache + streamed decode emits the same
    tokens as the whole-sequence forward, for the SSM, hybrid and
    enc-dec families (prompts of different lengths sharing a batch)."""
    from repro.launch.serve import BatchedServer, Request
    n_req, max_new = 3, 5
    server = BatchedServer(arch_id, smoke=True, batch_slots=2, max_seq=32,
                           protocol="bs", stream=True, seg_len=4)
    cfg, model, params = server.cfg, server.model, server.params
    rng = np.random.default_rng(5)
    reqs = []
    for i in range(n_req):
        plen = int(rng.integers(3, 7))
        prompt = rng.integers(1, cfg.vocab, plen).astype(np.int32)
        embeds = None
        if cfg.enc_dec:
            embeds = rng.standard_normal(
                (cfg.enc_len, cfg.d_model)).astype(np.float32)
        reqs.append(Request(i, prompt, max_new, embeds=embeds))
        server.submit(reqs[-1])
    server.run_until_drained()
    got = {r.rid: tuple(r.generated) for r in server.completed}
    assert set(got) == set(range(n_req))

    for r in reqs:
        want = _reference_greedy(cfg, model, params, r.prompt, max_new,
                                 embeds=r.embeds)
        assert got[r.rid] == tuple(want), (arch_id, r.rid)


@pytest.mark.parametrize("arch_id", ["mamba2_370m", "starcoder2_3b"])
def test_depth_cut_server_matches_whole_sequence_forward(arch_id):
    """`BatchedServer(n_layers=k)` serves the first k layers of the
    configuration at its own widths (the depth cut the chip smoke holds
    to a re-score): everything but the depth is the configuration's, and
    the streamed tokens equal the cut model's whole-sequence forward."""
    import dataclasses

    from repro.launch.serve import BatchedServer, Request
    server = BatchedServer(arch_id, smoke=True, batch_slots=2, max_seq=32,
                           stream=True, seg_len=4, n_layers=1)
    cfg, model, params = server.cfg, server.model, server.params
    assert cfg == dataclasses.replace(get_smoke_config(arch_id), n_layers=1)
    assert all(leaf.shape[0] == 1
               for leaf in jax.tree.leaves(params["blocks"]))
    rng = np.random.default_rng(6)
    reqs = [Request(i, rng.integers(1, cfg.vocab, int(rng.integers(3, 7))
                                    ).astype(np.int32), 4)
            for i in range(3)]
    for r in reqs:
        server.submit(r)
    server.run_until_drained()
    got = {r.rid: tuple(r.generated) for r in server.completed}
    for r in reqs:
        want = _reference_greedy(cfg, model, params, r.prompt, 4)
        assert got[r.rid] == tuple(want), (arch_id, r.rid)


def test_encdec_short_clip_matches_short_reference():
    """Variable encoder lengths (ROADMAP item): a clip SHORTER than
    cfg.enc_len is served without frontend-side padding — the prefill
    encodes the clip at its true frame count, writes per-slot cross-KV
    rows [0, e) (zeroing the tail) and sets the slot's enc_pos clock, and
    decode cross-attention masks rows >= enc_pos.  Tokens must equal
    greedy decoding with the whole-sequence forward over the SHORT
    embeds, even while a full-length clip shares the batch."""
    from repro.launch.serve import BatchedServer, Request
    server = BatchedServer("whisper_large_v3", smoke=True, batch_slots=2,
                           max_seq=32, protocol="bs", stream=True,
                           seg_len=4)
    cfg, model, params = server.cfg, server.model, server.params
    rng = np.random.default_rng(21)
    max_new = 5
    reqs = []
    for i, e in enumerate((cfg.enc_len - 12, cfg.enc_len)):  # short + full
        prompt = rng.integers(1, cfg.vocab, 4 + i).astype(np.int32)
        embeds = rng.standard_normal((e, cfg.d_model)).astype(np.float32)
        reqs.append(Request(i, prompt, max_new, embeds=embeds))
        server.submit(reqs[-1])
    server.run_until_drained()
    got = {r.rid: tuple(r.generated) for r in server.completed}
    assert int(jnp.max(server.cache["enc_pos"])) <= cfg.enc_len
    for r in reqs:
        want = _reference_greedy(cfg, model, params, r.prompt, max_new,
                                 embeds=r.embeds)
        assert got[r.rid] == tuple(want), (r.rid, got[r.rid], want)
