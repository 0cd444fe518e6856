"""Arithmetic shared by the plain references: keys from a seed,
RMSNorm, the float32 and int8 matmuls, embedding and logits head."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


class frozen(dict):
    """A dict of sizes usable as a static jit argument."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def key(seed: int) -> jax.Array:
    """A PRNG key that depends on all 64 bits of `seed` (`jax.random.key`
    keeps only the low 32)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def rms_norm(x, scale, eps):
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))


def _dot_f32(x, w):
    return jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)


def _quant(a, axis):
    a = a.astype(jnp.float32)
    scale = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.round(a / scale).astype(jnp.int8), scale


def _dot_int8(x, w):
    qx, sx = _quant(x, -1)           # per token
    qw, sw = _quant(w, 0)            # per output column
    acc = jnp.dot(qx, qw, preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * sx * sw


def matmul(mode: str):
    if mode == "f32":
        return _dot_f32
    if mode == "int8":
        return _dot_int8
    raise ValueError(f"unknown reference mode {mode!r}")


def embed(table, tokens: np.ndarray) -> jax.Array:
    """Rows of the embedding for `tokens`, padded with token 0 to a
    multiple of 512 positions so that a few shapes serve every length
    (the model is causal: positions after the sequence change nothing
    before it)."""
    t = np.asarray(tokens, np.int32)
    padded = np.zeros((-(-len(t) // 512) * 512,), np.int32)
    padded[:len(t)] = t
    return _take(table, jnp.asarray(padded))


@jax.jit
def _take(table, tokens):
    return jnp.take(table, tokens, axis=0).astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _head(s, mode, x, final_ln, table, rows):
    xr = rms_norm(jnp.take(x, rows, axis=0), final_ln, s["eps"])
    return matmul(mode)(xr, table.T)


def head(s, mode, x, params, rows: np.ndarray) -> np.ndarray:
    """Logits over the whole padded vocabulary at positions `rows`."""
    rows = np.asarray(rows, np.int32)
    out = []
    for r0 in range(0, len(rows), 256):
        chunk = rows[r0:r0 + 256]
        pad = np.zeros((256,), np.int32)
        pad[:len(chunk)] = chunk
        lg = _head(s, mode, x, params["final_ln"], params["embed"],
                   jnp.asarray(pad))
        out.append(np.asarray(lg)[:len(chunk)])
    return np.concatenate(out, 0)
