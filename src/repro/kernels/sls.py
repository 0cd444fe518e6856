"""Pallas kernel for Sparse Length Sum / embedding-bag pooling (DLRM).

The paper's DLRM workload offloads {embedding table lookup → SLS} to the
memory-side compute (Table I): the huge table stays in (CXL/HBM) memory,
and only the pooled (B, D) bags stream back to the host MLP.

TPU adaptation: the table Ref lives in ANY/HBM memory space (it does not
fit VMEM — Criteo-scale tables are GBs); each grid cell owns a tile of
`blk_b` bags, walks its (blk_b, L) index list, and accumulates gathered
rows into an f32 VMEM accumulator.  On real hardware the row loads become
HBM→VMEM DMAs issued from the kernel — the same "compute where the bytes
live" structure as the CCM-side SLS, with only the pooled result leaving
the device.  Bags are fixed-length with -1 padding (masked out).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _sls_kernel(table_ref, idx_ref, w_ref, o_ref, *, blk_b: int, bag_len: int):
    d = o_ref.shape[-1]

    def bag_body(b, _):
        def elem_body(l, acc):
            i = idx_ref[b, l]
            valid = i >= 0
            i_safe = jnp.maximum(i, 0)
            row = table_ref[pl.dslice(i_safe, 1), :]
            row = row.astype(jnp.float32)[0] * w_ref[b, l].astype(jnp.float32)
            return acc + jnp.where(valid, row, 0.0)

        acc = lax.fori_loop(0, bag_len, elem_body, jnp.zeros((d,), jnp.float32))
        o_ref[b, :] = acc
        return 0

    lax.fori_loop(0, blk_b, bag_body, 0)


def sls(table: jax.Array, indices: jax.Array,
        weights: Optional[jax.Array] = None, *,
        blk_b: int = 8, interpret: bool = False) -> jax.Array:
    """table: (V,D); indices: (B,L) int32 (−1 = pad); weights: (B,L) or None.
    Returns pooled bags (B, D) float32."""
    v, d = table.shape
    b, l = indices.shape
    blk_b = min(blk_b, b)
    assert b % blk_b == 0, (b, blk_b)
    if weights is None:
        weights = jnp.ones((b, l), jnp.float32)

    kernel = functools.partial(_sls_kernel, blk_b=blk_b, bag_len=l)
    return pl.pallas_call(
        kernel,
        grid=(b // blk_b,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),                 # table in HBM
            pl.BlockSpec((blk_b, l), lambda i: (i, 0)),
            pl.BlockSpec((blk_b, l), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((blk_b, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(table, indices, weights)
