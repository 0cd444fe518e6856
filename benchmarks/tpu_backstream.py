"""Beyond-paper: the back-streaming protocol as a TPU collective schedule.

Times the three decode-attention merge schedules (BS bulk all-gather,
AXLE ring streaming, RP serialized chunks) on the host platform and
verifies numerical equivalence.  On CPU the wall times only show
schedule overheads — the dry-run HLO (§Roofline) carries the real signal
— but the equivalence + bytes-on-wire derivation is platform-true.

Fused vs chunked bytes/launch accounting: see DESIGN.md §3 — in short,
the chunked schedule is n_chunks launches with
(2·n_chunks − 1)·B·H·(hd+2)·4 bytes of (acc, m, l) statistic round trips
through HBM; the fused one-shot kernel is ONE launch whose statistics
never leave VMEM.  The `fused_launches=...` / `stat_roundtrip_bytes=...`
fields in the rows below derive exactly that.
"""
from __future__ import annotations

import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import Row, print_rows
from repro import sharding as sh
from repro.core.backstream import (OffloadConfig, OffloadProtocol,
                                   decode_attention_combined, use_offload)
from repro.launch.mesh import make_debug_mesh

B, S, H, KH, HD = 4, 2048, 8, 8, 64


def _mk_inputs():
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (B, 1, H, HD), jnp.float32)
    k = jax.random.normal(ks[1], (B, KH, S, HD), jnp.float32)
    v = jax.random.normal(ks[2], (B, KH, S, HD), jnp.float32)
    pos = jnp.asarray(S - 1, jnp.int32)
    return q, k, v, pos


def run() -> List[Row]:
    rows: List[Row] = []
    q, k, v, pos = _mk_inputs()
    n_dev = jax.device_count()
    mesh = make_debug_mesh(1, n_dev) if n_dev > 1 else None
    outs = {}
    n_chunks = 4
    for name, proto, fused in (
            ("BS", OffloadProtocol.BS, True),
            ("BS_chunked", OffloadProtocol.BS, False),
            ("RP", OffloadProtocol.RP, False),
            ("AXLE", OffloadProtocol.AXLE, True)):
        cfg = OffloadConfig(protocol=proto, chunks_per_shard=n_chunks,
                            fused=fused)
        rules = sh.ShardingRules(mesh, seq_shard_attn=True) if mesh else None

        def f(q, k, v):
            return decode_attention_combined(q, k, v, pos)

        ctx = mesh if mesh is not None else _null()
        with ctx, sh.use_rules(rules), use_offload(cfg):
            jf = jax.jit(f)
            out = jf(q, k, v)
            out.block_until_ready()
            t0 = time.perf_counter()
            n = 20
            for _ in range(n):
                out = jf(q, k, v)
            out.block_until_ready()
            dt = (time.perf_counter() - t0) / n
        outs[name] = np.asarray(out)
        # bytes on the wire per merge under each schedule (n shards):
        # BS all-gather: (n-1)·B·H·(hd+2)·4 per shard; AXLE ring: same total
        # but chunked into n-1 hops that overlap compute.  Launch/traffic
        # accounting per the DESIGN note above: the fused path is ONE
        # kernel launch with zero (acc, m, l) HBM round trips; the chunked
        # path is n_chunks launches with (2·n_chunks − 1)·B·H·(hd+2)·4
        # bytes of statistic round-trip traffic.
        n_sh = mesh.shape["model"] if mesh else 1
        wire = (n_sh - 1) * B * H * (HD + 2) * 4
        # mirror decode_attention_combined's routing so the rows report
        # the schedule that actually ran, not the one requested: the
        # fused one-shot launch applies only to the unsharded non-RP
        # case; the sharded AXLE ring runs one fused-partial launch per
        # shard with the statistics riding the ring (wire bytes above),
        # never round-tripping HBM.
        if proto == OffloadProtocol.AXLE and n_sh > 1:
            launches, stat_rt = n_sh, 0
        elif fused and n_sh <= 1 and proto != OffloadProtocol.RP:
            launches, stat_rt = 1, 0
        else:
            launches = n_chunks * max(1, n_sh)
            stat_rt = (2 * launches - 1) * B * H * (HD + 2) * 4
        rows.append((f"tpu_backstream.{name}", dt * 1e6,
                     f"wire_bytes_per_shard={wire};"
                     f"fused_launches={launches};"
                     f"stat_roundtrip_bytes={stat_rt}"))
    err_rp = float(np.max(np.abs(outs["RP"] - outs["BS"])))
    err_ax = float(np.max(np.abs(outs["AXLE"] - outs["BS"])))
    err_ch = float(np.max(np.abs(outs["BS_chunked"] - outs["BS"])))
    rows.append(("tpu_backstream.equivalence", 0.0,
                 f"max_err_rp={err_rp:.2e};max_err_axle={err_ax:.2e};"
                 f"max_err_chunked={err_ch:.2e}"))
    assert err_rp < 1e-4 and err_ax < 1e-4 and err_ch < 1e-4
    return rows


class _null:
    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False


if __name__ == "__main__":
    print_rows(run())
