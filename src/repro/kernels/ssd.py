"""Pallas kernel for the Mamba2 SSD (state-space duality) chunked scan.

Mamba2's SSD form turns the linear recurrence
    state_t = exp(dt_t·A)·state_{t−1} + dt_t·x_t·B_tᵀ ;  y_t = state_t·C_t
into chunk-local *matmuls* plus a tiny cross-chunk state handoff — the
TPU-native (MXU) formulation.  The cross-chunk state is exactly the
producer→consumer partial result that the back-streaming protocol ships
between sequence shards (DESIGN.md §4, mamba2 row).

Grid (B, H, n_chunks): the chunk axis is innermost/sequential, carrying
the (P, N) running state in VMEM scratch.  Per-cell VMEM: x (blk_s, P),
B/C (blk_s, N), the (blk_s, blk_s) intra-chunk decay matrix, and the
(P, N) state — with blk_s = 128, P = 64, N = 128 about 0.3 MB.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, init_ref,
                y_ref, final_ref, state_s, *, blk_s: int, n_c: int):
    hi = pl.program_id(1)
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_s[...] = init_ref[0, 0].astype(jnp.float32)

    x = x_ref[0, 0].astype(jnp.float32)          # (blk_s, P)
    dt = dt_ref[0, 0].astype(jnp.float32)        # (1, blk_s) lane row
    a = a_ref[hi]                                # scalar (negative)
    bm = b_ref[0].astype(jnp.float32)            # (blk_s, N)
    cm = c_ref[0].astype(jnp.float32)            # (blk_s, N)
    state = state_s[...]                         # (P, N)

    ii = lax.broadcasted_iota(jnp.int32, (blk_s, blk_s), 0)
    jj = lax.broadcasted_iota(jnp.int32, (blk_s, blk_s), 1)
    tri = jj <= ii
    # Mosaic lowers neither cumsum nor a vector transpose, so both come
    # from masked reductions over the (blk_s, blk_s) grid: exact f32
    # adds (the masked-out lanes add zeros), no matmul rounding.
    dt_col = jnp.sum(jnp.where(ii == jj, dt, 0.0), axis=1,
                     keepdims=True)              # (blk_s, 1)
    loga = dt * a                                # (1, blk_s), all <= 0
    loga_col = dt_col * a                        # (blk_s, 1)
    # inclusive prefix sums, as a column and as a row
    cum_col = jnp.sum(jnp.where(tri, loga, 0.0), axis=1, keepdims=True)
    cum_row = jnp.sum(jnp.where(ii <= jj, loga_col, 0.0), axis=0,
                      keepdims=True)
    cum_last = cum_col[blk_s - 1:, :]            # (1, 1)

    # Intra-chunk: y_i += sum_{j<=i} (C_i·B_j) · exp(cum_i − cum_j) · dt_j · x_j
    g = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    decay = jnp.where(tri, jnp.exp(cum_col - cum_row), 0.0)
    s_mat = g * decay * dt
    y = jax.lax.dot(s_mat, x, preferred_element_type=jnp.float32)

    # Inter-chunk: carried-state contribution, decayed to each position.
    y += jax.lax.dot_general(cm, state, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32) \
        * jnp.exp(cum_col)
    y_ref[0, 0] = y.astype(y_ref.dtype)

    # State handoff: decay to chunk end, absorb this chunk's updates.
    w = jnp.exp(cum_last - cum_col) * dt_col     # (blk_s, 1)
    upd = jax.lax.dot_general(x, bm * w, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    state_s[...] = state * jnp.exp(cum_last) + upd

    @pl.when(ci == n_c - 1)
    def _finish():
        final_ref[0, 0] = state_s[...]


def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
             C: jax.Array, init_state: Optional[jax.Array] = None, *,
             blk_s: int = 128, interpret: bool = False
             ) -> Tuple[jax.Array, jax.Array]:
    """x: (b,s,h,p); dt: (b,s,h); A: (h,); B,C: (b,s,n).
    Returns (y (b,s,h,p), final_state (b,h,p,n) f32).

    TPU block layout: dt rides as (b, h, 1, s) so each cell's block is a
    (1, blk_s) lane row spanning the array's own sublane dim, and A (one
    f32 scalar per head) sits whole in SMEM."""
    b, s, h, p = x.shape
    n = B.shape[-1]
    blk_s = min(blk_s, s)
    assert s % blk_s == 0, (s, blk_s)
    n_c = s // blk_s
    if init_state is None:
        init_state = jnp.zeros((b, h, p, n), jnp.float32)

    xt = x.transpose(0, 2, 1, 3)                 # (b,h,s,p)
    dtt = dt.transpose(0, 2, 1)[:, :, None]      # (b,h,1,s)

    kernel = functools.partial(_ssd_kernel, blk_s=blk_s, n_c=n_c)
    y, final = pl.pallas_call(
        kernel,
        grid=(b, h, n_c),
        in_specs=[
            pl.BlockSpec((1, 1, blk_s, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, 1, blk_s),
                         lambda bi, hi, ci: (bi, hi, 0, ci)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, blk_s, n), lambda bi, hi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, blk_s, n), lambda bi, hi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, 1, p, n), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, blk_s, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, p, n), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, p), x.dtype),
            jax.ShapeDtypeStruct((b, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xt, dtt, A.astype(jnp.float32), B, C, init_state)
    return y.transpose(0, 2, 1, 3), final
