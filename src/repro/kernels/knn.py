"""Pallas kernel for blocked KNN squared-L2 distances (VectorDB offload).

The paper's KNN workloads offload vector-distance calculation to the
memory-side compute and stream one 4-byte distance per database row back
to the host, which performs the top-K select (§III-B).  This kernel is
that producer-side task: a (queries × db-block) tile of squared L2
distances computed in the matmul form  ||q||² − 2·q·xᵀ + ||x||²  so the
inner product runs on the MXU.

Tiling: grid (n_q_blocks, n_db_blocks); each cell loads a (blk_q, D)
query tile and a (blk_n, D) db tile into VMEM and emits a (blk_q, blk_n)
f32 distance tile.  With blk_q = blk_n = 128 and D = 2048 (the paper's
largest dim) that is 2·128·2048·4 B ≈ 2.1 MB of VMEM — comfortably
resident, MXU-aligned on every axis.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _knn_kernel(q_ref, x_ref, o_ref):
    q = q_ref[...].astype(jnp.float32)         # (blk_q, D)
    x = x_ref[...].astype(jnp.float32)         # (blk_n, D)
    q2 = jnp.sum(q * q, axis=-1, keepdims=True)
    x2 = jnp.sum(x * x, axis=-1)
    qx = jax.lax.dot_general(q, x, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    o_ref[...] = q2 - 2.0 * qx + x2[None, :]


def knn_distances(queries: jax.Array, db: jax.Array, *,
                  blk_q: int = 128, blk_n: int = 128,
                  interpret: bool = False) -> jax.Array:
    """queries: (Q,D); db: (N,D) -> squared L2 distances (Q,N) f32."""
    q, d = queries.shape
    n = db.shape[0]
    blk_q = min(blk_q, q)
    blk_n = min(blk_n, n)
    assert q % blk_q == 0 and n % blk_n == 0, (q, n, blk_q, blk_n)

    return pl.pallas_call(
        _knn_kernel,
        grid=(q // blk_q, n // blk_n),
        in_specs=[
            pl.BlockSpec((blk_q, d), lambda i, j: (i, 0)),
            pl.BlockSpec((blk_n, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((blk_q, blk_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((q, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(queries, db)


def knn_topk(queries: jax.Array, db: jax.Array, k: int, *,
             blk_q: int = 128, blk_n: int = 128,
             interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Full KNN: kernel-computed distances + host-side top-k merge — the
    exact producer/consumer split of the paper's KNN offload."""
    d = knn_distances(queries, db, blk_q=blk_q, blk_n=blk_n,
                      interpret=interpret)
    neg, idx = jax.lax.top_k(-d, k)
    return -neg, idx
