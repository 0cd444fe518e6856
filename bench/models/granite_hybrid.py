"""Plain reference of Granite-4.0-H (`granitemoehybrid`): a hybrid of
Mamba-2 mixers and NoPE attention, each layer followed by a routed
mixture of SwiGLU experts beside a shared SwiGLU MLP.

From the token ids:
  x = 12 · embedding
Per layer, by `layer_types`:
  h = RMSNorm(x)
  mamba:      z = h W_z;  xBC = silu(causal conv of [h W_x, h W_B, h W_C],
              width 4, with bias);  x_, B, C = split(xBC)
              dt = softplus(h W_dt + dt_bias);  A = -exp(A_log)
              per head: S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T;
                        y_t = S_t C_t + D x_t
              out = RMSNorm(y ⊙ silu(z)) W_out      (the gated RMSNorm)
  attention:  q, k, v = h W_q, h W_k, h W_v (no rotary embedding);
              causal softmax(q k^T · attention_multiplier) v;  out = o W_o
  x = x + 0.22 · out
  h = RMSNorm(x);  r = h W_router (all experts, float32);  the top 10
  logits and a softmax over them;  out = Σ over the chosen experts held
  here of gate · (silu(h W_gate,e) ⊙ h W_up,e) W_down,e
  + (silu(h W_gate,s) ⊙ h W_up,s) W_down,s      (the shared MLP)
  x = x + 0.22 · out
Final RMSNorm, logits against the tied embedding, divided by 16.

The configuration's file states how many experts one chip holds of the
published count (`num_local_experts` of `published`): the router scores
all of them and the reference, like the program, adds only the held
experts' part (shard 0, the first held).  RMSNorm weights are stored as
offsets from 1, as the program stores them.

Everything is float32 at matmul precision "highest", one layer at a
time over the whole sequence, with no cache, batching or kernels; the
Mamba-2 recurrence is stepped one token at a time from a zero state, and
every held expert is computed for every token and weighted by its gate
(zero where not chosen).  `mode="int8"` is the control: every
projection, expert and the logits head multiply int8 operands (see
`common.matmul`); the router stays float32.

This module imports nothing of the program.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench.models import common

Q_BLOCK = 512
# the residual stream's scale at the embedding, after the multiplier: that
# of mamba2_370m's embedding (std 0.02)
EMBED_STD = 0.02
# Granite's `initializer_range`: the router's std at the published init
ROUTER_STD = 0.1
KINDS = {"mamba": "mamba", "attention": "full"}


def sizes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The numbers the reference and the work counts use, from the
    configuration file's keys (the published `config.json`'s names)."""
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    vocab = cfg["vocab_size"]
    L = cfg["num_hidden_layers"]
    nh, P = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    return dict(
        L=L, d=d, h=h, kh=cfg["num_key_value_heads"], hd=d // h,
        ff=cfg["intermediate_size"], sff=cfg["shared_intermediate_size"],
        E=cfg["published"]["num_local_experts"],
        Eh=cfg["num_local_experts"], k=cfg["num_experts_per_tok"],
        nh=nh, P=P, di=nh * P, N=cfg["mamba_d_state"],
        W=cfg["mamba_d_conv"], vocab=vocab, vp=-(-vocab // 256) * 256,
        eps=float(cfg["rms_norm_eps"]),
        emb=float(cfg["embedding_multiplier"]),
        res=float(cfg["residual_multiplier"]),
        att=float(cfg["attention_multiplier"]),
        div=float(cfg["logits_scaling"]),
        kinds=tuple(KINDS[t] for t in cfg["layer_types"][:L]))


def check_program(cfg: Dict[str, Any], prog) -> None:
    """Refuse to run when the program's architecture config has other
    sizes than the configuration file."""
    s = sizes(cfg)
    got = dict(
        L=prog.n_layers, d=prog.d_model, h=prog.n_heads,
        kh=prog.n_kv_heads, hd=prog.head_dim_, ff=prog.d_ff,
        sff=prog.shared_ff, E=prog.n_experts, Eh=prog.held_experts,
        k=prog.top_k, nh=prog.n_ssm_heads, P=prog.ssm_head_dim,
        di=prog.d_inner, N=prog.ssm_state, W=prog.conv_width,
        vocab=prog.vocab, vp=prog.padded_vocab, eps=float(prog.norm_eps),
        emb=float(prog.embed_mult), res=float(prog.residual_mult),
        att=float(prog.attn_scale), div=float(prog.logits_div),
        kinds=tuple(prog.block_pattern) * prog.n_blocks)
    if got != s or prog.rope or not prog.mamba_xbc \
            or prog.dtype != cfg["torch_dtype"]:
        raise ValueError(f"program config {got} != benchmark config {s}")


@functools.partial(jax.jit, static_argnums=0)
def _init(s, key):
    d, h, kh, hd, ff, sff = s["d"], s["h"], s["kh"], s["hd"], s["ff"], \
        s["sff"]
    E, Eh, nh, di, N, W = s["E"], s["Eh"], s["nh"], s["di"], s["N"], s["W"]
    bf = jnp.bfloat16
    keys = iter(jax.random.split(key, 24 * len(s["kinds"]) + 2))

    def normal(shape, std, dtype=bf):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * std).astype(dtype)

    def uniform(shape, bound, dtype=bf):
        return jax.random.uniform(next(keys), shape, jnp.float32, -bound,
                                  bound).astype(dtype)

    def one(shape):            # the program's leading n_blocks axis of 1
        return (1,) + shape

    def mlp(f):
        return {"w_gate": normal(one((d, f)), d ** -0.5),
                "w_up": normal(one((d, f)), d ** -0.5),
                "w_down": normal(one((f, d)), f ** -0.5)}

    embed = normal((s["vp"], d), EMBED_STD / s["emb"])
    embed = embed.at[s["vocab"]:].set(0)      # padding rows are never tokens
    blocks = []
    for kind in s["kinds"]:
        layer = {}
        if kind == "mamba":
            dt = jnp.exp(jax.random.uniform(
                next(keys), one((nh,)), jnp.float32, np.log(1e-3),
                np.log(1e-1)))
            dt = jnp.maximum(dt, 1e-4)
            layer["mamba"] = {
                "ln": jnp.zeros(one((d,)), bf),
                "w_z": uniform(one((d, di)), d ** -0.5),
                "w_x": uniform(one((d, di)), d ** -0.5),
                "w_B": uniform(one((d, N)), d ** -0.5),
                "w_C": uniform(one((d, N)), d ** -0.5),
                "w_dt": uniform(one((d, nh)), d ** -0.5),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "A_log": jnp.log(jax.random.uniform(
                    next(keys), one((nh,)), jnp.float32, 1.0, 16.0)),
                "D": jnp.ones(one((nh,)), jnp.float32),
                "conv_w": uniform(one((W, di + 2 * N)), W ** -0.5),
                "out_proj": uniform(one((di, d)), di ** -0.5),
                "conv_b": uniform(one((di + 2 * N,)), W ** -0.5),
                "norm": jnp.zeros(one((di,)), bf)}
        else:
            layer["attn"] = {
                "ln": jnp.zeros(one((d,)), bf),
                "wq": normal(one((d, h * hd)), d ** -0.5),
                "wk": normal(one((d, kh * hd)), d ** -0.5),
                "wv": normal(one((d, kh * hd)), d ** -0.5),
                "wo": normal(one((h * hd, d)), (h * hd) ** -0.5)}
        layer["ffn"] = {
            "ln": jnp.zeros(one((d,)), bf),
            "router": normal(one((d, E)), ROUTER_STD, jnp.float32),
            "w_gate": normal(one((Eh, d, ff)), d ** -0.5),
            "w_up": normal(one((Eh, d, ff)), d ** -0.5),
            "w_down": normal(one((Eh, ff, d)), ff ** -0.5),
            "shared": mlp(sff)}
        blocks.append(layer)
    return {"embed": embed, "blocks": tuple(blocks),
            "final_ln": jnp.zeros((d,), bf)}


def init_params(cfg: Dict[str, Any], seed: int):
    """Random weights from `seed`, drawn on the device in one jitted call
    in bfloat16 and in the program's layout.  The Mamba-2 mixers take
    Mamba-2's own initialisation (projections and conv uniform in
    +-1/sqrt(fan-in), dt log-uniform in [1e-3, 1e-1] through its bias, A
    uniform in [1, 16], D = 1), as the mamba2_370m configuration does:
    the program's stand-in init makes a stack of SSD layers chaotic under
    bfloat16 rounding.  The router is normal with Granite's
    `initializer_range` 0.1, as the published init draws it: over a
    normed input that spreads the router logits at std 6.4, so the
    gate of an expert ranked 10th is a few millionths of the first's.
    At variance 1/fan-in (logit std 1) it was ~4% of the layer's routed
    sum, and rounding swapped such near-tied 10th and 11th experts now
    and then; each swap moves the output by the same amount whatever
    the precision that caused it, so the check could not tell bfloat16
    from the int8 control.  Every other projection and the experts are
    normal with variance 1/fan-in; every norm weight is 1 (offset 0).
    The embedding is normal with std 0.02 / 12, so that
    12 · embedding starts the residual stream at std 0.02, as
    mamba2_370m's embedding does.  Not Granite's `initializer_range`
    0.1: with the tied head, a stream that starts at 12 · 0.1 per
    component stays dominated by the input token's own embedding
    through 10 random layers (their sum adds ~0.57 a component), so
    every greedy token repeats its input by a wide margin and the
    check, like the int8 control, reads no gap at all."""
    return _init(common.frozen(sizes(cfg)), common.key(seed))


def _conv(u, w, b, W):
    T = u.shape[0]
    up = jnp.concatenate([jnp.zeros((W - 1, u.shape[1])), u], 0)
    w = w.astype(jnp.float32)
    return jax.nn.silu(sum(up[j:j + T] * w[j] for j in range(W))
                       + b.astype(jnp.float32))


def _mixer(s, mm, p, h):
    T = h.shape[0]
    nh, P, N, di = s["nh"], s["P"], s["N"], s["di"]
    z = mm(h, p["w_z"])
    xbc = jnp.concatenate([mm(h, p["w_x"]), mm(h, p["w_B"]),
                           mm(h, p["w_C"])], axis=1)
    xbc = _conv(xbc, p["conv_w"], p["conv_b"], s["W"])
    u, B, C = xbc[:, :di], xbc[:, di:di + N], xbc[:, di + N:]
    dt = jax.nn.softplus(mm(h, p["w_dt"]) + p["dt_bias"])     # (T, nh)
    A = -jnp.exp(p["A_log"])
    uh = u.reshape(T, nh, P)

    def step(S, inp):
        u_t, dt_t, B_t, C_t = inp
        S = (S * jnp.exp(dt_t * A)[:, None, None]
             + (dt_t[:, None] * u_t)[:, :, None] * B_t[None, None, :])
        return S, jnp.einsum("hpn,n->hp", S, C_t,
                             precision=jax.lax.Precision.HIGHEST)

    _, y = jax.lax.scan(step, jnp.zeros((nh, P, N)), (uh, dt, B, C))
    y = (y + uh * p["D"][:, None]).reshape(T, di)
    y = common.rms_norm(y * jax.nn.silu(z), p["norm"], s["eps"])
    return mm(y, p["out_proj"])


def _attention(s, mm, p, h):
    T = h.shape[0]
    nh, kh, hd = s["h"], s["kh"], s["hd"]
    q = mm(h, p["wq"]).reshape(T, nh, hd)
    k = jnp.repeat(mm(h, p["wk"]).reshape(T, kh, hd), nh // kh, axis=1)
    v = jnp.repeat(mm(h, p["wv"]).reshape(T, kh, hd), nh // kh, axis=1)
    pos = jnp.arange(T)
    outs = []
    for q0 in range(0, T, Q_BLOCK):
        qb = q[q0:q0 + Q_BLOCK]
        sc = jnp.einsum("qhd,khd->hqk", qb, k) * s["att"]
        mask = (q0 + jnp.arange(qb.shape[0]))[:, None] >= pos[None, :]
        sc = jnp.where(mask[None], sc, -jnp.inf)
        outs.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v))
    return mm(jnp.concatenate(outs, 0).reshape(T, nh * hd), p["wo"])


def _swiglu(mm, h, wg, wu, wd):
    return mm(jax.nn.silu(mm(h, wg)) * mm(h, wu), wd)


def _moe(s, mm, p, h):
    """The held experts' part of the routed sum, plus the shared MLP."""
    logits = jnp.dot(h, p["router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)     # (T, E)
    top, idx = jax.lax.top_k(logits, s["k"])
    gates = jax.nn.softmax(top, axis=-1)                      # (T, k)
    # gate of held expert e for each token: 0 where it was not chosen
    held = jnp.sum(jnp.where(idx[:, :, None] == jnp.arange(s["Eh"]),
                             gates[:, :, None], 0.0), axis=1)  # (T, Eh)
    sh = p["shared"]

    def expert(out, e):
        wg, wu, wd, g = e
        return out + g[:, None] * _swiglu(mm, h, wg, wu, wd), None

    out, _ = jax.lax.scan(
        expert, _swiglu(mm, h, sh["w_gate"], sh["w_up"], sh["w_down"]),
        (p["w_gate"], p["w_up"], p["w_down"], held.T))
    return out


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _layer(s, mode, kind, x, layer):
    mm = common.matmul(mode)
    p = jax.tree.map(lambda w: w[0], layer)
    if kind == "mamba":
        out = _mixer(s, mm, p["mamba"], common.rms_norm(
            x, p["mamba"]["ln"], s["eps"]))
    else:
        out = _attention(s, mm, p["attn"], common.rms_norm(
            x, p["attn"]["ln"], s["eps"]))
    x = x + s["res"] * out
    f = p["ffn"]
    return x + s["res"] * _moe(s, mm, f, common.rms_norm(x, f["ln"],
                                                          s["eps"]))


def logits_at(cfg: Dict[str, Any], params, tokens: np.ndarray,
              rows: np.ndarray, mode: str = "f32") -> np.ndarray:
    """Logits (len(rows), padded vocab) of the causal forward pass over
    `tokens`, at the positions `rows`."""
    s = common.frozen(sizes(cfg))
    with jax.default_matmul_precision("highest"):
        x = common.embed(params["embed"], tokens) * s["emb"]
        for i in range(s["L"]):
            x = _layer(s, mode, s["kinds"][i], x, params["blocks"][i])
        return common.head(s, mode, x, params, rows) / s["div"]


# ---- work counts: what the algorithm needs, from the sizes alone -------
#
# Expert work is an expectation under uniform routing: each row's k
# choices fall on a held expert with probability Eh / E, so `rows` rows
# bring rows · k · Eh / E (row, expert) pairs, and a held expert is
# reached by at least one of them with probability 1 - (1 - k / E)^rows;
# only the experts reached have their weights read.

def _mixer_params(s) -> int:
    return (s["d"] * (2 * s["di"] + 2 * s["N"] + s["nh"])
            + s["di"] * s["d"])


def _attn_params(s) -> int:
    return s["d"] * s["hd"] * (2 * s["h"] + 2 * s["kh"])


def _expert_params(s) -> int:
    return 3 * s["d"] * s["ff"]


def _n_mamba(s) -> int:
    return sum(k == "mamba" for k in s["kinds"])


def _n_attn(s) -> int:
    return s["L"] - _n_mamba(s)


def layer_count(cfg, kind: str) -> int:
    """Served layers of one mixer kind: "mamba" (the SSD scan and decode
    step run there) or "full" (the attention kernels run there)."""
    return sizes(cfg)["kinds"].count(kind)


def reached_experts(cfg, rows: int) -> float:
    s = sizes(cfg)
    return s["Eh"] * (1.0 - (1.0 - s["k"] / s["E"]) ** rows)


def held_pairs(cfg, rows: int) -> float:
    s = sizes(cfg)
    return rows * s["k"] * s["Eh"] / s["E"]


def _dense_params(s) -> int:
    """Weights every token multiplies: mixers or attention, the router
    and the shared MLP (the embedding lookup and the head excluded)."""
    return (_n_mamba(s) * _mixer_params(s) + _n_attn(s) * _attn_params(s)
            + s["L"] * (s["d"] * s["E"] + 3 * s["d"] * s["sff"]))


def weight_bytes(cfg, rows: int = 1) -> float:
    """Bytes of the weights a forward step over `rows` rows reads once:
    bfloat16 projections, convs and norms, the float32 router and SSM
    vectors, the held experts those rows reach, the tied embedding once
    as the logits head."""
    s = sizes(cfg)
    mixer = (2 * (_mixer_params(s) + s["W"] * (s["di"] + 2 * s["N"])
                  + (s["di"] + 2 * s["N"]) + s["di"] + s["d"])
             + 4 * 3 * s["nh"])
    attn = 2 * (_attn_params(s) + s["d"])
    ffn = 2 * (3 * s["d"] * s["sff"] + s["d"]) + 4 * s["d"] * s["E"]
    return (_n_mamba(s) * mixer + _n_attn(s) * attn + s["L"] * ffn
            + s["L"] * reached_experts(cfg, rows) * 2 * _expert_params(s)
            + 2 * (s["vp"] * s["d"] + s["d"]))


def kv_bytes_per_token(cfg) -> int:
    s = sizes(cfg)
    return _n_attn(s) * 2 * s["kh"] * s["hd"] * 2


def state_bytes_per_row(cfg) -> int:
    """One sequence's recurrent state: the float32 SSM state and the
    bfloat16 conv window (over x‖B‖C) of every Mamba-2 layer."""
    s = sizes(cfg)
    return _n_mamba(s) * (4 * s["nh"] * s["P"] * s["N"]
                          + 2 * (s["W"] - 1) * (s["di"] + 2 * s["N"]))


def scan_flops_per_token(cfg) -> float:
    """One layer's recurrence for one token: decay, outer-product update
    and read-out, 5 operations per state element."""
    s = sizes(cfg)
    return 5.0 * s["nh"] * s["P"] * s["N"]


def _token_flops(s, cfg, rows: int) -> float:
    """Matmul FLOPs of `rows` tokens through every layer (no head)."""
    return (2.0 * _dense_params(s) * rows
            + 2.0 * s["L"] * held_pairs(cfg, rows) * _expert_params(s)
            + _n_mamba(s) * scan_flops_per_token(cfg) * rows)


def prefill_work(cfg, length: int):
    """(FLOPs, bytes) of one prompt's prefill at its true length: every
    token through every layer, causal attention, the last position's
    logits; the weights (and the experts the prompt reaches) read once,
    the prompt's KV and the recurrent state written."""
    s = sizes(cfg)
    flops = (_token_flops(s, cfg, length)
             + 4.0 * _n_attn(s) * s["h"] * s["hd"] * length * (length + 1)
             / 2 + 2.0 * s["d"] * s["vocab"])
    nbytes = (weight_bytes(cfg, length) + kv_bytes_per_token(cfg) * length
              + state_bytes_per_row(cfg))
    return flops, float(nbytes)


def decode_step_work(cfg, positions):
    """(FLOPs, bytes) of one decode step for the live rows at `positions`:
    the weights once (the experts those rows reach), each row's valid KV
    read and its new KV written, each row's state read and written."""
    s = sizes(cfg)
    pos = np.asarray(positions, np.float64)
    n = len(pos)
    flops = (_token_flops(s, cfg, n) + n * 2.0 * s["d"] * s["vocab"]
             + 4.0 * _n_attn(s) * s["h"] * s["hd"] * float((pos + 1).sum()))
    nbytes = (weight_bytes(cfg, n)
              + kv_bytes_per_token(cfg) * float((pos + 1).sum())
              + 2.0 * n * state_bytes_per_row(cfg))
    return flops, float(nbytes)


def decode_attention_work(cfg, positions):
    """(FLOPs, bytes) of one attention layer's decode attention call for
    the live rows at `positions`: read q and the valid K/V, write the
    output."""
    s = sizes(cfg)
    pos = np.asarray(positions, np.float64)
    keys = float(pos.sum())     # the cache holds tokens [0, pos)
    flops = 4.0 * s["h"] * s["hd"] * (keys + len(pos))
    nbytes = (2.0 * 2 * s["kh"] * s["hd"] * keys
              + 2.0 * 2 * s["h"] * s["hd"] * len(pos))
    return flops, nbytes


def flash_prefill_work(cfg, length: int):
    """(FLOPs, bytes) of one attention layer's causal attention over a
    prompt's true length: read q, k, v, write the output."""
    s = sizes(cfg)
    flops = 4.0 * s["h"] * s["hd"] * length * (length + 1) / 2
    nbytes = 2.0 * s["hd"] * length * (2 * s["h"] + 2 * s["kh"])
    return flops, nbytes


def ssd_scan_work(cfg, length: int):
    """(FLOPs, bytes) of one layer's scan over a prompt's true length:
    read x, B, C (bfloat16) and dt (float32), write y (bfloat16) and the
    final float32 state."""
    s = sizes(cfg)
    flops = scan_flops_per_token(cfg) * length
    nbytes = (length * (2 * 2 * s["di"] + 4 * s["nh"] + 2 * 2 * s["N"])
              + 4 * s["nh"] * s["P"] * s["N"])
    return flops, float(nbytes)


def expert_gmm_work(cfg, rows: int):
    """(FLOPs, bytes) of one layer's grouped expert matmuls for `rows`
    live rows, expected under uniform routing (see above): gate, up and
    down for the held pairs; the reached held experts' weights read,
    each pair's input row read and its SwiGLU activation written
    (gate/up), that activation read and the expert's output written
    (down), all bfloat16."""
    s = sizes(cfg)
    pairs = held_pairs(cfg, rows)
    flops = 2.0 * pairs * _expert_params(s)
    nbytes = (reached_experts(cfg, rows) * 2 * _expert_params(s)
              + pairs * 2 * 2 * (s["d"] + s["ff"]))
    return flops, float(nbytes)
