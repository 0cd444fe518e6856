"""Pallas kernel for the grouped matmul of a mixture-of-experts layer.

The (token, expert) rows that fall on the experts a chip holds are laid
out sorted by expert, each expert's run of rows padded to a whole number
of `tm`-row tiles, so every tile belongs to exactly one expert (group).
The kernel multiplies each live tile by its group's weight panel; an
expert no row reached has no tile, so its weights are never read.

Grid (N / tn, tiles): the tile axis is innermost, so a group's (K, tn)
weight block stays in VMEM across its consecutive tiles and is fetched
once per column block.  Tiles past the live count (`n_live`, scalar
prefetch) point their input blocks at the last live tile's — the
pipeline sees an unchanged block index and issues no copy — and write
zeros.  The whole contraction (K) sits in one block: at the widths
served (K 4,096 or 768) a tile's blocks are a few MB of VMEM.

`w2` fuses the SwiGLU gate: out = silu(lhs·w) ⊙ (lhs·w2), the gate and
up projections of one tile computed from a single load of its rows.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

VMEM_LIMIT = 64 * 1024 * 1024


def _gmm_kernel(group_ref, live_ref, lhs_ref, *rest, fused: bool):
    if fused:
        w_ref, w2_ref, out_ref = rest
    else:
        (w_ref, out_ref), w2_ref = rest, None
    ti = pl.program_id(1)

    @pl.when(ti < live_ref[0])
    def _compute():
        x = lhs_ref[...]
        acc = jnp.dot(x, w_ref[0], preferred_element_type=jnp.float32)
        if fused:
            up = jnp.dot(x, w2_ref[0], preferred_element_type=jnp.float32)
            acc = jax.nn.silu(acc) * up
        out_ref[...] = acc.astype(out_ref.dtype)

    @pl.when(ti >= live_ref[0])
    def _dead():
        out_ref[...] = jnp.zeros_like(out_ref)


def _col_block(n: int) -> int:
    for tn in (512, 384, 256, 128):
        if n % tn == 0:
            return tn
    return n


def expert_gmm(lhs: jax.Array, w: jax.Array, tile_group: jax.Array,
               n_live: jax.Array, w2: Optional[jax.Array] = None, *,
               tm: int, interpret: bool = False) -> jax.Array:
    """lhs: (M, K) group-aligned rows, M a multiple of `tm`; w, w2:
    (G, K, N); tile_group: (M // tm,) int32 group of each tile (dead
    tiles repeat the last live tile's group); n_live: (1,) int32 live
    tiles.  Returns (M, N) in lhs.dtype, zero on dead tiles."""
    m, k = lhs.shape
    n = w.shape[2]
    assert m % tm == 0, (m, tm)
    tn = _col_block(n)
    fused = w2 is not None

    def last_live(ti, live):
        return jnp.minimum(ti, jnp.maximum(live[0] - 1, 0))

    def lhs_map(j, ti, group, live):
        return last_live(ti, live), 0

    def w_map(j, ti, group, live):
        return group[last_live(ti, live)], 0, j

    def out_map(j, ti, group, live):
        return ti, j

    w_spec = pl.BlockSpec((1, k, tn), w_map)
    in_specs = [pl.BlockSpec((tm, k), lhs_map), w_spec]
    operands = [lhs, w]
    if fused:
        in_specs.append(w_spec)
        operands.append(w2)
    return pl.pallas_call(
        functools.partial(_gmm_kernel, fused=fused),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n // tn, m // tm),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((tm, tn), out_map)),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="expert_gmm",
    )(tile_group.astype(jnp.int32), n_live.astype(jnp.int32), *operands)
