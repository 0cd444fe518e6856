"""Plain reference of the attention-free SSD stack as the served program
builds it (Mamba-2 370M's widths; the departures from the published
model are listed in the configuration's file).

Per layer, from the residual x:
  h = RMSNorm(x);  z = silu(h W_z);  u = h W_x;  B = h W_B;  C = h W_C
  dt = softplus(h W_dt + dt_bias);  A = -exp(A_log)
  u = silu(depthwise causal conv of u, width 4, no bias)
  per head: S_t = exp(dt_t A) S_{t-1} + dt_t u_t B_t^T;  y_t = S_t C_t
  y = (y + D u) * z;  x = x + y W_out
Final RMSNorm, logits against the tied embedding.

The recurrence is stepped one token at a time from a zero state, in
float32, with matmuls at precision "highest": no chunking, no cache, no
kernels.  `mode="int8"` is the control: the projections and the logits
head multiply int8 operands (see `common.matmul`).

This module imports nothing of the program.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench.models import common


def sizes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    d = cfg["d_model"]
    di = cfg["expand"] * d
    vocab = cfg["vocab_size"]
    return dict(L=cfg["n_layer"], d=d, di=di, P=cfg["headdim"],
                nh=di // cfg["headdim"], N=cfg["d_state"], W=cfg["d_conv"],
                vocab=vocab, vp=-(-vocab // 256) * 256,
                eps=float(cfg["norm_epsilon"]))


def check_program(cfg: Dict[str, Any], prog) -> None:
    """Refuse to run when the program's architecture config has other
    sizes than the configuration file."""
    s = sizes(cfg)
    got = dict(L=prog.n_layers, d=prog.d_model, di=prog.d_inner,
               P=prog.ssm_head_dim, nh=prog.n_ssm_heads, N=prog.ssm_state,
               W=prog.conv_width, vocab=prog.vocab, vp=prog.padded_vocab,
               eps=float(prog.norm_eps))
    if got != s or tuple(prog.block_pattern) != ("mamba",) \
            or prog.d_ff != 0 or prog.dtype != cfg["torch_dtype"]:
        raise ValueError(f"program config {got} != benchmark config {s}")


@functools.partial(jax.jit, static_argnums=0)
def _init(s, key):
    L, d, di, nh, N, W, vp = (s["L"], s["d"], s["di"], s["nh"], s["N"],
                              s["W"], s["vp"])
    bf = jnp.bfloat16
    k = jax.random.split(key, 10)

    def uniform(kk, shape, bound, dtype=bf):
        return jax.random.uniform(kk, shape, jnp.float32, -bound,
                                  bound).astype(dtype)

    embed = (jax.random.normal(k[0], (vp, d), jnp.float32) * 0.02).astype(bf)
    embed = embed.at[s["vocab"]:].set(0)      # padding rows are never tokens
    dt = jnp.exp(jax.random.uniform(k[6], (L, nh), jnp.float32,
                                    np.log(1e-3), np.log(1e-1)))
    dt = jnp.maximum(dt, 1e-4)
    m = {"ln": jnp.zeros((L, d), bf),
         "w_z": uniform(k[1], (L, d, di), d ** -0.5),
         "w_x": uniform(k[2], (L, d, di), d ** -0.5),
         "w_B": uniform(k[3], (L, d, N), d ** -0.5),
         "w_C": uniform(k[4], (L, d, N), d ** -0.5),
         "w_dt": uniform(k[5], (L, d, nh), d ** -0.5),
         "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),   # softplus^-1(dt)
         "A_log": jnp.log(jax.random.uniform(k[7], (L, nh), jnp.float32,
                                             1.0, 16.0)),
         "D": jnp.ones((L, nh), jnp.float32),
         "conv_w": uniform(k[8], (L, W, di), W ** -0.5),
         "out_proj": uniform(k[9], (L, di, d), di ** -0.5)}
    return {"embed": embed, "blocks": ({"mamba": m},),
            "final_ln": jnp.zeros((d,), bf)}


def init_params(cfg: Dict[str, Any], seed: int):
    """Random weights from `seed`, drawn on the device in one jitted call
    in the program's layout, by Mamba-2's own initialisation (the
    reference implementation's defaults): projections uniform in
    +-1/sqrt(fan-in), conv taps in +-1/sqrt(width), dt log-uniform in
    [1e-3, 1e-1] through the bias, A uniform in [1, 16], D = 1, the
    embedding normal with std 0.02.  The output projection keeps its
    +-1/sqrt(d_inner) default without the published 1/sqrt(n_layer)
    rescale: that rescale assumes the gated RMSNorm before it, which
    brings y to unit scale and which the program leaves out."""
    return _init(common.frozen(sizes(cfg)), common.key(seed))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer(s, mode, x, blocks, i):
    mm = common.matmul(mode)
    p = jax.tree.map(lambda w: w[i], blocks)["mamba"]
    T = x.shape[0]
    nh, P, N, W = s["nh"], s["P"], s["N"], s["W"]
    h = common.rms_norm(x, p["ln"], s["eps"])
    z = jax.nn.silu(mm(h, p["w_z"]))
    u = mm(h, p["w_x"])
    B = mm(h, p["w_B"])
    C = mm(h, p["w_C"])
    dt = jax.nn.softplus(mm(h, p["w_dt"]) + p["dt_bias"])     # (T, nh)
    A = -jnp.exp(p["A_log"])
    up = jnp.concatenate([jnp.zeros((W - 1, u.shape[1])), u], 0)
    cw = p["conv_w"].astype(jnp.float32)
    u = jax.nn.silu(sum(up[j:j + T] * cw[j] for j in range(W)))
    uh = u.reshape(T, nh, P)

    def step(S, inp):
        u_t, dt_t, B_t, C_t = inp
        S = (S * jnp.exp(dt_t * A)[:, None, None]
             + (dt_t[:, None] * u_t)[:, :, None] * B_t[None, None, :])
        return S, jnp.einsum("hpn,n->hp", S, C_t,
                             precision=jax.lax.Precision.HIGHEST)

    _, y = jax.lax.scan(step, jnp.zeros((nh, P, N)), (uh, dt, B, C))
    y = (y + uh * p["D"][:, None]).reshape(T, nh * P) * z
    return x + mm(y, p["out_proj"])


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

def _proj_params(s) -> int:
    return s["d"] * (2 * s["di"] + 2 * s["N"] + s["nh"]) + s["di"] * s["d"]


def weight_bytes(cfg) -> int:
    """Bytes of every weight read once per forward step, the tied
    embedding counted once as the logits head."""
    s = sizes(cfg)
    per_layer = (2 * (_proj_params(s) + s["W"] * s["di"] + s["d"])
                 + 4 * 3 * s["nh"])
    return s["L"] * per_layer + 2 * (s["vp"] * s["d"] + s["d"])


def matmul_params(cfg) -> int:
    s = sizes(cfg)
    return s["L"] * _proj_params(s)


def state_bytes_per_row(cfg) -> int:
    """One sequence's recurrent state: the float32 SSM state and the
    bfloat16 conv window of every layer."""
    s = sizes(cfg)
    return s["L"] * (4 * s["nh"] * s["P"] * s["N"]
                     + 2 * (s["W"] - 1) * s["di"])


def scan_flops_per_token(cfg) -> float:
    """One layer's recurrence for one token: decay, outer-product update
    and read-out, 5 operations per state element (vector-unit work,
    counted against the matrix unit's peak, so the bound it gives is
    loose)."""
    s = sizes(cfg)
    return 5.0 * s["nh"] * s["P"] * s["N"]


def prefill_work(cfg, length: int):
    s = sizes(cfg)
    flops = (2.0 * matmul_params(cfg) * length
             + s["L"] * scan_flops_per_token(cfg) * length
             + 2.0 * s["d"] * s["vocab"])
    nbytes = weight_bytes(cfg) + state_bytes_per_row(cfg)
    return flops, float(nbytes)


def decode_step_work(cfg, positions):
    """(FLOPs, bytes) of one decode step for the live rows (`positions`
    only counts them: the state does not grow): the weights once, each
    row's state read and written."""
    s = sizes(cfg)
    n = len(positions)
    flops = n * (2.0 * (matmul_params(cfg) + s["d"] * s["vocab"])
                 + s["L"] * scan_flops_per_token(cfg))
    nbytes = weight_bytes(cfg) + 2.0 * n * state_bytes_per_row(cfg)
    return flops, float(nbytes)


def ssd_scan_work(cfg, length: int):
    """(FLOPs, bytes) of one layer's scan over a prompt's true length:
    read x, B, C (bfloat16) and dt (float32), write y (bfloat16) and the
    final float32 state."""
    s = sizes(cfg)
    flops = scan_flops_per_token(cfg) * length
    nbytes = (length * (2 * 2 * s["di"] + 4 * s["nh"] + 2 * 2 * s["N"])
              + 4 * s["nh"] * s["P"] * s["N"])
    return flops, float(nbytes)
