"""Compile the served Pallas kernels for a described TPU v5e.

Interpret mode runs a kernel's body but never its TPU lowering, so a
block shape the chip's compiler refuses (trailing dims neither (8, 128)-
tiled nor the array's own), a primitive Mosaic cannot lower, or a VMEM
overrun passes every interpret-mode test.  These tests compile each
kernel the serving path calls, at the published widths of the
benchmarked models, for one chip of a v5e:2x2 topology that is described,
not attached, and check that the program really holds the kernel
(`tpu_custom_call`).

Widths: starcoder2_3b decode (8 slots, 24 q heads over 2 KV heads,
head_dim 128, its 16k context, 128-token pages), its 2048-token flash
prefill, its 4,096- and 8-token prefills with a traced true length
(granite's 32-over-8 heads too) and (3072 x 12288) FFN weight;
mamba2_370m's SSD scan (32 heads x 64, state 128); granite_4_0_h_small's grouped expert matmuls (18 held
experts of 4096 x 768, decode at 64 slots x top-10 and a 4,096-token
prefill), its 128-head SSD scan and its 32-over-8-head decode attention.
The kernel modules are called directly: `ops` would see the CPU backend
and take the oracle branch.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attention as fa
from repro.kernels import gmm, quant, ssd

B, H, KH, HD, S, PAGE = 8, 24, 2, 128, 16384, 128
N_PAGES = S // PAGE
PREFILL = 2048
SSM_H, SSM_P, SSM_N = 32, 64, 128
D_MODEL, D_FF = 3072, 12288
# granite_4_0_h_small
G_D, G_FF, G_HELD, G_TOPK = 4096, 768, 18, 10
G_H, G_KH, G_SSM_H = 32, 8, 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables cannot be read back from the
    # persistent cache; keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _decode(extra=False, paged=False, int8=False):
    def fn(q, k, v, pos, *rest):
        rest = list(rest)
        pages = rest.pop(0) if paged else None
        scales = (rest.pop(0), rest.pop(0)) if int8 else None
        ex = tuple(rest) if extra else None
        return fa.decode_attention_fused(q, k, v, pos, ex, blk_c=PAGE,
                                         pages=pages, kv_scales=scales)

    kv_dt = jnp.int8 if int8 else jnp.bfloat16
    shapes = [((B, 1, H, HD), jnp.bfloat16), ((B, KH, S, HD), kv_dt),
              ((B, KH, S, HD), kv_dt), ((B,), jnp.int32)]
    if paged:
        shapes.append(((B, N_PAGES), jnp.int32))
    if int8:
        shapes += [((B, KH, N_PAGES), jnp.float32)] * 2
    if extra:
        shapes += [((B, H, HD), jnp.float32), ((B, H), jnp.float32),
                   ((B, H), jnp.float32)]
    return fn, shapes


def _flash(s, h=H, kh=KH, length=False):
    """Prefill at `s` tokens; with `length`, a traced true length rides
    as the kernel's scalar operand (the served call)."""
    shapes = [((1, s, h, HD), jnp.bfloat16), ((1, s, kh, HD), jnp.bfloat16),
              ((1, s, kh, HD), jnp.bfloat16)]
    if length:
        shapes.append(((), jnp.int32))
    return fa.flash_attention, shapes


def _ssd(s):
    return (ssd.ssd_scan,
            [((1, s, SSM_H, SSM_P), jnp.bfloat16),
             ((1, s, SSM_H), jnp.float32), ((SSM_H,), jnp.float32),
             ((1, s, SSM_N), jnp.bfloat16), ((1, s, SSM_N), jnp.bfloat16)])


def _quant(fmt, rows):
    nb = D_MODEL // quant.QUANT_BLOCK
    if fmt == "q8_0":
        parts = [((nb, D_FF), jnp.float32),
                 ((nb, quant.QUANT_BLOCK, D_FF), jnp.int8)]
    else:
        parts = [((nb, D_FF), jnp.float32),
                 ((nb, quant.QUANT_BLOCK // 2, D_FF), jnp.uint8),
                 ((nb, D_FF), jnp.float32)]

    def fn(x, scales, quants, mins=None):
        qt = quant.QTensor(scales, quants, mins, fmt, D_MODEL)
        return quant.quant_matmul(x, qt)

    return fn, [((rows, D_MODEL), jnp.bfloat16)] + parts


def _gmm(rows, tm, fused):
    """Group-aligned rows of `rows` tokens' held pairs at worst case (all
    top-10 choices held) plus one padded tile per held expert."""
    m = -(-rows * G_TOPK // tm) * tm + G_HELD * tm
    k, n = (G_D, G_FF) if fused else (G_FF, G_D)

    def fn(lhs, w, tiles, live, *w2):
        return gmm.expert_gmm(lhs, w, tiles, live, *w2, tm=tm)

    shapes = [((m, k), jnp.bfloat16), ((G_HELD, k, n), jnp.bfloat16),
              ((m // tm,), jnp.int32), ((1,), jnp.int32)]
    if fused:
        shapes.append(((G_HELD, k, n), jnp.bfloat16))
    return fn, shapes


def _granite_decode():
    def fn(q, k, v, pos, pages, *extra):
        return fa.decode_attention_fused(q, k, v, pos, tuple(extra),
                                         blk_c=PAGE, pages=pages)
    s = 4096
    return fn, [((B, 1, G_H, HD), jnp.bfloat16),
                ((B, G_KH, s, HD), jnp.bfloat16),
                ((B, G_KH, s, HD), jnp.bfloat16), ((B,), jnp.int32),
                ((B, s // PAGE), jnp.int32), ((B, G_H, HD), jnp.float32),
                ((B, G_H), jnp.float32), ((B, G_H), jnp.float32)]


CASES = {
    "decode_fused.dense": lambda: _decode(),
    "decode_fused.dense_extra": lambda: _decode(extra=True),
    "decode_fused.paged_extra": lambda: _decode(extra=True, paged=True),
    "decode_fused.int8_extra": lambda: _decode(extra=True, int8=True),
    "decode_fused.paged_int8_extra": lambda: _decode(extra=True, paged=True,
                                                     int8=True),
    "decode_partial": lambda: (
        fa.decode_attention_partial,
        [((B, 1, H, HD), jnp.bfloat16), ((B, KH, S, HD), jnp.bfloat16),
         ((B, KH, S, HD), jnp.bfloat16), ((B, S), jnp.bool_)]),
    "flash_attention.prefill": lambda: _flash(PREFILL),
    "flash_attention.short_prompt": lambda: _flash(8),
    "flash_attention.starcoder_4096_length": lambda: _flash(4096,
                                                            length=True),
    "flash_attention.starcoder_short_length": lambda: _flash(8, length=True),
    "flash_attention.granite_4096_length": lambda: _flash(
        4096, G_H, G_KH, length=True),
    "flash_attention.granite_short_length": lambda: _flash(
        8, G_H, G_KH, length=True),
    "ssd_scan.prefill": lambda: _ssd(PREFILL),
    "ssd_scan.short_prompt": lambda: _ssd(8),
    "quant_matmul.q8_0.decode": lambda: _quant("q8_0", B),
    "quant_matmul.q4_k.decode": lambda: _quant("q4_k", B),
    "quant_matmul.q8_0.prefill": lambda: _quant("q8_0", PREFILL),
    "quant_matmul.q4_k.prefill": lambda: _quant("q4_k", PREFILL),
    "expert_gmm.decode_gate_up": lambda: _gmm(64, 16, True),
    "expert_gmm.decode_down": lambda: _gmm(64, 16, False),
    "expert_gmm.prefill_gate_up": lambda: _gmm(4096, 128, True),
    "expert_gmm.prefill_down": lambda: _gmm(4096, 128, False),
    "ssd_scan.granite_prefill": lambda: (
        ssd.ssd_scan,
        [((1, PREFILL, G_SSM_H, SSM_P), jnp.bfloat16),
         ((1, PREFILL, G_SSM_H), jnp.float32), ((G_SSM_H,), jnp.float32),
         ((1, PREFILL, SSM_N), jnp.bfloat16),
         ((1, PREFILL, SSM_N), jnp.bfloat16)]),
    "decode_fused.granite_paged_extra": _granite_decode,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip):
    fn, shapes = CASES[case]()
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), case
