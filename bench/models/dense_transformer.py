"""Plain reference of the dense decoder as the served program builds it
(StarCoder2-3B's widths; the departures from the published model are
listed in the configuration's file).

Per layer: RMSNorm (weight stored as an offset from 1) -> grouped-query
attention with rotary positions (half-split rotation) -> residual;
RMSNorm -> SwiGLU feed-forward -> residual.  Final RMSNorm, logits
against the tied embedding.  No biases.

Everything here is float32 at matmul precision "highest", one layer at a
time, with no cache, no batching and no kernels; attention is the
textbook masked softmax over query blocks.  `mode="int8"` is the
control: every projection and the logits head multiply int8 operands
(weights per output column, activations per token, symmetric) and
accumulate in int32, the step below the bfloat16 the configuration
states.

Weights are made here from a key, on the device, in the layout and
dtype the program serves (`init_params`); this module imports nothing of
the program.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench.models import common

Q_BLOCK = 512


def sizes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The numbers the reference and the work counts use, from the
    configuration file's keys."""
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or d // h
    vocab = cfg["vocab_size"]
    return dict(L=cfg["num_hidden_layers"], d=d, h=h,
                kh=cfg["num_key_value_heads"], hd=hd,
                ff=cfg["intermediate_size"], vocab=vocab,
                vp=-(-vocab // 256) * 256, theta=float(cfg["rope_theta"]),
                eps=float(cfg["norm_epsilon"]))


def check_program(cfg: Dict[str, Any], prog) -> None:
    """Refuse to run when the program's architecture config has other
    sizes than the configuration file."""
    s = sizes(cfg)
    got = dict(L=prog.n_layers, d=prog.d_model, h=prog.n_heads,
               kh=prog.n_kv_heads, hd=prog.head_dim_, ff=prog.d_ff,
               vocab=prog.vocab, vp=prog.padded_vocab,
               theta=float(prog.rope_theta), eps=float(prog.norm_eps))
    if got != s or tuple(prog.block_pattern) != ("full",) \
            or prog.dtype != cfg["torch_dtype"]:
        raise ValueError(f"program config {got} != benchmark config {s}")


@functools.partial(jax.jit, static_argnums=0)
def _init(s, key):
    L, d, h, kh, hd, ff, vp = (s["L"], s["d"], s["h"], s["kh"], s["hd"],
                               s["ff"], s["vp"])
    bf = jnp.bfloat16
    k = jax.random.split(key, 8)

    def normal(kk, shape, std):
        return (jax.random.normal(kk, shape, jnp.float32) * std).astype(bf)

    embed = normal(k[0], (vp, d), d ** -0.5)
    embed = embed.at[s["vocab"]:].set(0)      # padding rows are never tokens
    attn = {"ln": jnp.zeros((L, d), bf),
            "wq": normal(k[1], (L, d, h * hd), d ** -0.5),
            "wk": normal(k[2], (L, d, kh * hd), d ** -0.5),
            "wv": normal(k[3], (L, d, kh * hd), d ** -0.5),
            "wo": normal(k[4], (L, h * hd, d), (h * hd) ** -0.5)}
    ffn = {"ln": jnp.zeros((L, d), bf),
           "w_gate": normal(k[5], (L, d, ff), d ** -0.5),
           "w_up": normal(k[6], (L, d, ff), d ** -0.5),
           "w_down": normal(k[7], (L, ff, d), ff ** -0.5)}
    return {"embed": embed, "blocks": ({"attn": attn, "ffn": ffn},),
            "final_ln": jnp.zeros((d,), bf)}


def init_params(cfg: Dict[str, Any], seed: int):
    """Random weights from `seed`, drawn on the device in one jitted call,
    in bfloat16 and in the program's parameter layout.  Scales: each
    projection's entries have variance 1 / fan-in, the embedding
    1 / d_model, the norm offsets 0."""
    s = sizes(cfg)
    return _init(common.frozen(s), common.key(seed))


def _rope(x, pos, theta):
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * freqs        # (T, hd/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer(s, mode, x, blocks, i):
    """Decoder layer `i` over a whole (T, d) sequence."""
    mm = common.matmul(mode)
    p = jax.tree.map(lambda w: w[i], blocks)
    T = x.shape[0]
    h, kh, hd = s["h"], s["kh"], s["hd"]
    a = p["attn"]
    hx = common.rms_norm(x, a["ln"], s["eps"])
    pos = jnp.arange(T)
    q = _rope(mm(hx, a["wq"]).reshape(T, h, hd), pos, s["theta"])
    k = _rope(mm(hx, a["wk"]).reshape(T, kh, hd), pos, s["theta"])
    v = mm(hx, a["wv"]).reshape(T, kh, hd)
    g = h // kh
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    outs = []
    for q0 in range(0, T, Q_BLOCK):
        qb = q[q0:q0 + Q_BLOCK]
        sc = jnp.einsum("qhd,khd->hqk", qb, k) / math.sqrt(hd)
        mask = (q0 + jnp.arange(qb.shape[0]))[:, None] >= pos[None, :]
        sc = jnp.where(mask[None], sc, -jnp.inf)
        outs.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v))
    o = jnp.concatenate(outs, 0).reshape(T, h * hd)
    x = x + mm(o, a["wo"])
    f = p["ffn"]
    hx = common.rms_norm(x, f["ln"], s["eps"])
    y = jax.nn.silu(mm(hx, f["w_gate"])) * mm(hx, f["w_up"])
    return x + mm(y, f["w_down"])


def logits_at(cfg: Dict[str, Any], params, tokens: np.ndarray,
              rows: np.ndarray, mode: str = "f32") -> np.ndarray:
    """Logits (len(rows), padded vocab) of the causal forward pass over
    `tokens`, at the positions `rows`."""
    s = common.frozen(sizes(cfg))
    with jax.default_matmul_precision("highest"):
        x = common.embed(params["embed"], tokens)
        for layer in range(s["L"]):
            x = _layer(s, mode, x, params["blocks"][0], layer)
        return common.head(s, mode, x, params, rows)


# ---- work counts: what the algorithm needs, from the sizes alone -------

def weight_bytes(cfg) -> int:
    """Bytes of every weight the served model reads once per forward
    step (bfloat16), the tied embedding counted once as the logits head."""
    s = sizes(cfg)
    per_layer = (s["d"] * s["hd"] * (2 * s["h"] + 2 * s["kh"])
                 + 3 * s["d"] * s["ff"] + 2 * s["d"])
    return 2 * (s["L"] * per_layer + s["vp"] * s["d"] + s["d"])


def matmul_params(cfg) -> int:
    """Weights that multiply every token (the embedding lookup excluded,
    the logits head too: it multiplies only the positions whose logits
    are needed)."""
    s = sizes(cfg)
    return s["L"] * (s["d"] * s["hd"] * (2 * s["h"] + 2 * s["kh"])
                     + 3 * s["d"] * s["ff"])


def kv_bytes_per_token(cfg) -> int:
    s = sizes(cfg)
    return s["L"] * 2 * s["kh"] * s["hd"] * 2


def attention_flops(cfg, n_keys) -> float:
    """QK^T and PV for one query against `n_keys` keys, all layers."""
    s = sizes(cfg)
    return 4.0 * s["L"] * s["h"] * s["hd"] * n_keys


def prefill_work(cfg, length: int):
    """(FLOPs, bytes) of one prompt's prefill at its true length: every
    token through every projection, causal attention, the last
    position's logits; the weights read once, the prompt's KV written."""
    s = sizes(cfg)
    flops = (2.0 * matmul_params(cfg) * length
             + attention_flops(cfg, 1) * length * (length + 1) / 2
             + 2.0 * s["d"] * s["vocab"])
    nbytes = weight_bytes(cfg) + kv_bytes_per_token(cfg) * length
    return flops, float(nbytes)


def decode_step_work(cfg, positions):
    """(FLOPs, bytes) of one decode step for the live rows whose current
    tokens sit at `positions` (so each reads that many cached tokens):
    the weights once, each row's valid KV, its new KV written."""
    s = sizes(cfg)
    pos = np.asarray(positions, np.float64)
    n = len(pos)
    flops = (n * 2.0 * (matmul_params(cfg) + s["d"] * s["vocab"])
             + attention_flops(cfg, 1) * float((pos + 1).sum()))
    nbytes = (weight_bytes(cfg)
              + kv_bytes_per_token(cfg) * float((pos + 1).sum()))
    return flops, nbytes


def decode_attention_work(cfg, positions):
    """(FLOPs, bytes) of one layer's decode attention call for the live
    rows at `positions`: read q and the valid K/V, write the output."""
    s = sizes(cfg)
    pos = np.asarray(positions, np.float64)
    keys = float(pos.sum())     # the cache holds tokens [0, pos)
    flops = 4.0 * s["h"] * s["hd"] * (keys + len(pos))
    nbytes = (2.0 * 2 * s["kh"] * s["hd"] * keys
              + 2.0 * 2 * s["h"] * s["hd"] * len(pos))
    return flops, nbytes


def flash_prefill_work(cfg, length: int):
    """(FLOPs, bytes) of one layer's causal attention over a prompt's
    true length: read q, k, v, write the output."""
    s = sizes(cfg)
    flops = 4.0 * s["h"] * s["hd"] * length * (length + 1) / 2
    nbytes = 2.0 * s["hd"] * length * (2 * s["h"] + 2 * s["kh"])
    return flops, nbytes
