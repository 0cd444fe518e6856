"""Block-quantized weight tensors + the Pallas dequant-fused matmul.

The paper's byte-economy argument (DESIGN.md §10): what decode is bound
on is the bytes streamed out of the far tier, so weights live in HBM as
packed per-block quants + scales (q8_0: 32 int8 + one f32 scale per
block-column; q4_k: 32 nibbles + f32 scale/min) and are dequantized in
VMEM *inside* the matmul kernel, one tile at a time — the fp weight
matrix never exists in HBM.

`QTensor` is a registered pytree: the scales/quants/mins leaves ride
`lax.scan` xs, `jax.tree.map` slicing (the truncated self-draft's
`a[:n_blocks]`), and donation exactly like the dense arrays they
replace; the format and true input width are static aux data, so jitted
callers specialize per format without retracing per call.

Numerics ground truth: `ref.quantize_q8_0/q4_k` + dequantize twins —
the CPU dispatch path in `ops.quant_matmul` multiplies against the
dequantized oracle weights, and the Pallas path (interpret on CPU) is
parity-tested against it.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.tree_util import GetAttrKey

from repro.kernels import ref as _ref

QUANT_BLOCK = _ref.QUANT_BLOCK
WEIGHT_FORMATS = ("q8_0", "q4_k")


@jax.tree_util.register_pytree_with_keys_class
@dataclasses.dataclass
class QTensor:
    """A block-quantized 2-D weight (plus optional leading stack axes).

    scales: (..., nB, n) f32        — per (block, out-column) scale
    quants: (..., nB, 32, n) int8   (q8_0)
            (..., nB, 16, n) uint8  (q4_k; two nibbles per byte)
    mins:   (..., nB, n) f32        (q4_k only; None for q8_0)
    fmt:    "q8_0" | "q4_k"         (static)
    d_in:   true input width before block padding (static)
    """
    scales: jax.Array
    quants: jax.Array
    mins: Optional[jax.Array]
    fmt: str
    d_in: int

    @property
    def shape(self) -> Tuple[int, ...]:
        return self.scales.shape[:-2] + (self.d_in, self.scales.shape[-1])

    @property
    def ndim(self) -> int:
        return len(self.shape)

    @property
    def dtype(self):
        return jnp.float32

    @property
    def nbytes(self) -> int:
        n = self.scales.nbytes + self.quants.nbytes
        return n + (self.mins.nbytes if self.mins is not None else 0)

    def tree_flatten_with_keys(self):
        children = ((GetAttrKey("scales"), self.scales),
                    (GetAttrKey("quants"), self.quants),
                    (GetAttrKey("mins"), self.mins))
        return children, (self.fmt, self.d_in)

    @classmethod
    def tree_unflatten(cls, aux, children):
        scales, quants, mins = children
        return cls(scales=scales, quants=quants, mins=mins,
                   fmt=aux[0], d_in=aux[1])


def quantize_tensor(w: jax.Array, fmt: str,
                    block: int = QUANT_BLOCK) -> QTensor:
    """Quantize a (..., d, n) weight into the given block format."""
    if fmt == "q8_0":
        scales, quants = _ref.quantize_q8_0(w, block)
        return QTensor(scales, quants, None, fmt, w.shape[-2])
    if fmt == "q4_k":
        scales, mins, quants = _ref.quantize_q4_k(w, block)
        return QTensor(scales, quants, mins, fmt, w.shape[-2])
    raise ValueError(f"unknown quant format: {fmt}")


def dequantize_tensor(qt: QTensor) -> jax.Array:
    """Materialize the f32 (..., d, n) weight (the oracle path)."""
    if qt.fmt == "q8_0":
        return _ref.dequantize_q8_0(qt.scales, qt.quants, qt.d_in)
    if qt.fmt == "q4_k":
        return _ref.dequantize_q4_k(qt.scales, qt.mins, qt.quants, qt.d_in)
    raise ValueError(f"unknown quant format: {qt.fmt}")


# --------------------------------------------------------------------------
# Pallas dequant-fused matmul
# --------------------------------------------------------------------------
#
# Grid (n_m, n_n, n_k) with the reduction axis innermost and accumulating
# in VMEM scratch: each step DMAs KB_STEP packed (block, bn) weight tiles
# plus their scale (and min) rows, expands them to f32 IN VMEM, and feeds
# the MXU.  Packed bytes are the only weight traffic out of HBM.
#
# TPU block layout: one step covers KB_STEP = 8 quant blocks, i.e. a
# 256-wide x tile (a lane multiple) and an (8, bn) scale tile (a sublane
# multiple); a single 32-wide quant block is not a legal x block.

KB_STEP = 8


def _q8_matmul_kernel(x_ref, s_ref, q_ref, o_ref, acc_ref, *, n_k: int):
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]                                        # (bm, KB*block)
    w = q_ref[...].astype(jnp.float32) * s_ref[...][:, None, :]
    kbs, block, bn = w.shape                              # (KB, block, bn)
    w = w.reshape(kbs * block, bn)
    acc_ref[...] += jax.lax.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(kb == n_k - 1)
    def _finish():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _q4k_matmul_kernel(x_ref, s_ref, m_ref, q_ref, o_ref, acc_ref, *,
                       n_k: int):
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # x arrives nibble-split (see quant_matmul): the step's low-nibble
    # (even) elements in the first half of the tile, the high-nibble
    # (odd) ones in the second, so no in-kernel interleave is needed
    x = x_ref[...]                                        # (bm, KB*block)
    packed = q_ref[...].astype(jnp.int32)                 # (KB, block//2, bn)
    sc = s_ref[...][:, None, :]
    mn = m_ref[...][:, None, :]
    kbs, hb, bn = packed.shape
    lo = ((packed & 0xF).astype(jnp.float32) * sc + mn).reshape(kbs * hb, bn)
    hi = ((packed >> 4).astype(jnp.float32) * sc + mn).reshape(kbs * hb, bn)
    half = kbs * hb
    acc_ref[...] += (
        jax.lax.dot(x[:, :half], lo, preferred_element_type=jnp.float32)
        + jax.lax.dot(x[:, half:], hi, preferred_element_type=jnp.float32))

    @pl.when(kb == n_k - 1)
    def _finish():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def quant_matmul(x: jax.Array, qt: QTensor, *, blk_m: int = 128,
                 blk_n: int = 128, interpret: bool = False) -> jax.Array:
    """x (m, d_in) @ dequantize(qt) (d_in, n) -> (m, n) in x.dtype, with
    the dequantization fused into the matmul's VMEM pipeline.  `qt` must
    be unstacked (2-D logical shape) — stacked weights are sliced per
    layer by the caller's `lax.scan` before reaching a matmul."""
    assert qt.scales.ndim == 2, "quant_matmul wants an unstacked QTensor"
    m, d = x.shape
    n_b, n = qt.scales.shape
    block = QUANT_BLOCK
    assert qt.d_in == d, (qt.d_in, d)

    # pad x's input axis with zeros up to a whole number of KB_STEP-block
    # steps (padded weight lanes multiply zero activations, so they
    # contribute nothing even where q4_k's asymmetric grid dequantizes
    # padding to a nonzero value; padded blocks get zero scales too)
    n_bp = -(-n_b // KB_STEP) * KB_STEP
    xf = x.astype(jnp.float32)
    bm = min(blk_m, m)
    bn = min(blk_n, n)
    pm, pn = -(-m // bm) * bm, -(-n // bn) * bn
    xf = jnp.pad(xf, ((0, pm - m), (0, n_bp * block - d)))
    scales = jnp.pad(qt.scales, ((0, n_bp - n_b), (0, pn - n)))
    quants = jnp.pad(qt.quants, ((0, n_bp - n_b), (0, 0), (0, pn - n)))
    mins = (jnp.pad(qt.mins, ((0, n_bp - n_b), (0, pn - n)))
            if qt.mins is not None else None)
    n_k = n_bp // KB_STEP
    grid = (pm // bm, pn // bn, n_k)

    x_spec = pl.BlockSpec((bm, KB_STEP * block), lambda i, j, kb: (i, kb))
    s_spec = pl.BlockSpec((KB_STEP, bn), lambda i, j, kb: (kb, j))
    q_spec = pl.BlockSpec((KB_STEP, quants.shape[1], bn),
                          lambda i, j, kb: (kb, 0, j))
    out_spec = pl.BlockSpec((bm, bn), lambda i, j, kb: (i, j))
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))
    scratch = [pltpu.VMEM((bm, bn), jnp.float32)]

    if qt.fmt == "q8_0":
        kernel = functools.partial(_q8_matmul_kernel, n_k=n_k)
        out = pl.pallas_call(
            kernel, grid=grid, in_specs=[x_spec, s_spec, q_spec],
            out_specs=out_spec,
            out_shape=jax.ShapeDtypeStruct((pm, pn), x.dtype),
            scratch_shapes=scratch, compiler_params=params,
            interpret=interpret,
        )(xf, scales, quants)
    elif qt.fmt == "q4_k":
        # nibble-split x: within each step, element 2i of every block
        # moves to the first half and element 2i+1 to the second
        xs = xf.reshape(pm, n_k, KB_STEP, block // 2, 2)
        xs = xs.transpose(0, 1, 4, 2, 3).reshape(pm, n_bp * block)
        kernel = functools.partial(_q4k_matmul_kernel, n_k=n_k)
        out = pl.pallas_call(
            kernel, grid=grid, in_specs=[x_spec, s_spec, s_spec, q_spec],
            out_specs=out_spec,
            out_shape=jax.ShapeDtypeStruct((pm, pn), x.dtype),
            scratch_shapes=scratch, compiler_params=params,
            interpret=interpret,
        )(xs, scales, mins, quants)
    else:
        raise ValueError(f"unknown quant format: {qt.fmt}")
    return out[:m, :n]
