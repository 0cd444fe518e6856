#!/usr/bin/env python3
"""Bring-up smoke of the serving path on TPU, at published widths.

    python chip_smoke.py            # one chip: kernels vs their oracles,
                                    # then starcoder2_3b and mamba2_370m
                                    # (full depth, then its first two
                                    # layers) served through BatchedServer
    python chip_smoke.py --chips 4  # the mesh phase only: starcoder2_3b
                                    # on device 0, then on a 2x2 mesh

Weights are random, drawn from fixed seeds; prompts come from a seeded
numpy generator.  Every phase prints plain facts (compile seconds,
drained wall seconds, tokens, peak device bytes) on earlier lines.  These
are smoke facts, not benchmark numbers.  The last line of stdout is one
JSON object, {"ok": true, "device": {"platform", "kind", "count"}}.

It exits non-zero and prints no result when JAX sees no TPU, when the
repository's `src/` is not beside this file, or when any phase fails.
One process drives every chip it uses.  `LIBTPU_INIT_ARGS` is left as
the machine sets it.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

# ---- kernel-vs-oracle tolerances ---------------------------------------
# Error metric: max |kernel - oracle| / max |oracle| over the whole output.
# The oracles (kernels/ref.py) run at matmul precision "highest"; the
# kernels run as served.  Inside a kernel an f32 x f32 MXU dot may be
# carried out in bf16 passes, which rounds each operand that is not
# already a bf16 value (the scaled q, the softmax weights, a dequantized
# weight) to 8 mantissa bits, i.e. a relative 2^-9 = 0.002 per product.
# Outputs stored in bf16 (flash prefill, the SSD scan's y) add up to
# 2^-8 of rounding on each side; decode and the quant matmul are compared
# in f32.  Each bound leaves a factor of two to five over that budget; a
# wrong mask, page index or scale shows up as an O(1) error.
TOL_ATTN = 1e-2      # decode (fused, paged, int8) and flash prefill
TOL_SSD = 2e-2       # chunked scan vs the sequential recurrence: the
#                      decay products are re-associated per 128-token
#                      chunk, and the carried state goes through f32 dots
TOL_QUANT = 1e-2     # dequant-fused matmul vs dequantize-then-matmul
# ---- served-token re-scoring --------------------------------------------
# The served tokens come from bf16 weights and activations through the
# Pallas prefill/decode kernels and a bf16 KV cache; the re-score is one
# no-cache bf16 forward through the jnp attention path.  The two differ by
# bf16 rounding accumulated over every layer, and their logits are bf16
# values (one ulp is 2^-7 ~ 0.008 near 1, 0.03 near 4).  A served token
# that is not the re-score's argmax is accepted only where the re-score's
# top two logits are within TOL_TOP2 of each other and the served token's
# own logit is within TOL_TOP2 of the top: a few ulps at the logits' scale.
TOL_TOP2 = 0.125
# mamba2_370m with random weights is numerically chaotic in depth: a
# relative perturbation of 1e-4 at the input of its 48-layer stack grows
# ~870x by the last layer (f32, width 256, on a CPU), and two scan orders
# that are both exact in f32 (chunked vs sequential) agree on a third of
# the greedy tokens after 48 layers at width 512.  No re-score can hold
# the full stack to its tokens, so the full depth is served and its
# re-score checked for finite logits only, and the tokens are held to
# the re-score on the first MAMBA_HELD_LAYERS layers at published widths.
# There, 2^-8 relative noise on every layer's scan output moves a
# greedy token's logit by at most 0.047 (width 1024, same CPU check).
MAMBA_HELD_LAYERS = 2


class CompileClock:
    """Sums JAX's backend-compile durations while it is installed."""

    def __init__(self, jax):
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    assert got.shape == want.shape, (got.shape, want.shape)
    assert np.isfinite(got).all(), "non-finite kernel output"
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


def check(name: str, err: float, tol: float) -> None:
    print(f"kernel {name}: max_rel_err={err!r} tol={tol}", flush=True)
    assert err <= tol, f"{name}: {err} > {tol}"


# ------------------------------------------------------------------------
# Phase 1: every served kernel against its oracle, at published widths
# ------------------------------------------------------------------------

def kernel_phase() -> None:
    import jax
    import jax.numpy as jnp
    from repro.kernels import flash_attention as fa
    from repro.kernels import quant, ref, ssd

    bf = jnp.bfloat16
    keys = iter(jax.random.split(jax.random.key(1234), 64))

    def normal(shape, dtype=jnp.float32, scale=1.0):
        return (jax.random.normal(next(keys), shape) * scale).astype(dtype)

    def oracle(fn, *args, **kw):
        with jax.default_matmul_precision("highest"):
            return jax.jit(fn, static_argnames=tuple(kw))(*args, **kw)

    # starcoder2_3b decode: B=8 slots, 24 q heads over 2 KV heads,
    # head_dim 128, its 16k context, 128-token pages
    b, h, kh, hd, s, page = 8, 24, 2, 128, 16384, 128
    n_pages = s // page
    q = normal((b, 1, h, hd), bf)
    k = normal((b, kh, s, hd), bf)
    v = normal((b, kh, s, hd), bf)
    pos = jax.random.randint(next(keys), (b,), 0, s, jnp.int32)
    extra = (normal((b, h, hd)), normal((b, h)),
             1.0 + jnp.abs(normal((b, h))))
    pages = jnp.stack([jax.random.permutation(next(keys), n_pages)
                       for _ in range(b)]).astype(jnp.int32)
    kq, ks = ref.quantize_kv_pages(k, page)
    vq, vs = ref.quantize_kv_pages(v, page)

    cases = {
        "decode_fused.dense": dict(k=k, v=v),
        "decode_fused.dense_extra": dict(k=k, v=v, extra=extra),
        "decode_fused.paged_extra": dict(k=k, v=v, extra=extra,
                                         pages=pages),
        "decode_fused.paged_int8_extra": dict(k=kq, v=vq, extra=extra,
                                              pages=pages,
                                              kv_scales=(ks, vs)),
    }
    # compared before the bf16 cast: the merged statistics, normalized
    # in f32 (the served entry casts that very quotient to bf16)
    for name, c in cases.items():
        acc, _, l = jax.jit(lambda q, c: fa.decode_attention_fused_partial(
            q, c["k"], c["v"], pos, c.get("extra"), blk_c=page,
            pages=c.get("pages"), kv_scales=c.get("kv_scales")))(q, c)
        acc_r, _, l_r = oracle(
            lambda q, c: ref.decode_fused_partial_reference(
                q, c["k"], c["v"], pos, c.get("extra"),
                pages=c.get("pages"), page_size=page if "pages" in c else 0,
                kv_scales=c.get("kv_scales")), q, c)
        check(name, rel_err(acc / l[..., None], acc_r / l_r[..., None]),
              TOL_ATTN)

    # the AXLE ring's producer: raw partial statistics under a mask
    valid = jnp.arange(s)[None, :] <= pos[:, None]
    acc, m, l = jax.jit(fa.decode_attention_partial)(q, k, v, valid)
    acc_r, m_r, l_r = oracle(ref.decode_partial_reference, q, k, v, valid)
    check("decode_partial.acc_over_l",
          rel_err(acc / l[..., None], acc_r / l_r[..., None]), TOL_ATTN)
    check("decode_partial.m", rel_err(m, m_r), TOL_ATTN)

    # flash prefill: one 2048-token prompt, starcoder2_3b heads
    sp = 2048
    qp = normal((1, sp, h, hd), bf)
    kp = normal((1, sp, kh, hd), bf)
    vp = normal((1, sp, kh, hd), bf)
    got = jax.jit(fa.flash_attention)(qp, kp, vp)
    want = oracle(ref.mha_reference, qp, kp, vp)
    check("flash_attention.causal_2048", rel_err(got, want), TOL_ATTN)

    # mamba2_370m prefill scan: 32 heads x 64, state 128, 2048 tokens
    nh, hp, n = 32, 64, 128
    x = normal((1, sp, nh, hp), bf)
    dt = jax.nn.softplus(normal((1, sp, nh)))
    a = -jnp.exp(normal((nh,), scale=0.5))
    bm = normal((1, sp, n), bf, scale=n ** -0.5)
    cm = normal((1, sp, n), bf, scale=n ** -0.5)
    y, fin = jax.jit(ssd.ssd_scan)(x, dt, a, bm, cm)
    y_r, fin_r = oracle(ref.ssd_reference, x, dt, a, bm, cm)
    check("ssd_scan.y", rel_err(y, y_r), TOL_SSD)
    check("ssd_scan.final_state", rel_err(fin, fin_r), TOL_SSD)

    # dequant-fused matmul at starcoder2_3b's w_gate (3072 x 12288), for a
    # decode batch and a ragged prefill block; x in f32 keeps the output
    # free of bf16 rounding (the kernel upcasts x to f32 either way)
    d, f = 3072, 12288
    w = normal((d, f), scale=d ** -0.5)
    for fmt in quant.WEIGHT_FORMATS:
        qt = quant.quantize_tensor(w, fmt)
        for rows in (8, 200):
            xq = normal((rows, d))
            got = jax.jit(quant.quant_matmul)(xq, qt)
            want = oracle(lambda x, qt: x.astype(jnp.float32)
                          @ quant.dequantize_tensor(qt), xq, qt)
            check(f"quant_matmul.{fmt}.m{rows}", rel_err(got, want),
                  TOL_QUANT)


# ------------------------------------------------------------------------
# Phases 2/3: a model served through BatchedServer at published widths
# ------------------------------------------------------------------------

def make_requests(vocab: int, n: int, plen: tuple, max_new: int, seed: int):
    from repro.launch.serve import Request
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        p = int(rng.integers(plen[0], plen[1] + 1))
        out.append(Request(i, rng.integers(1, vocab, p).astype(np.int32),
                           max_new))
    return out


def serve(arch: str, *, n_req: int, plen: tuple, max_new: int,
          max_seq: int, clock, mesh=None, n_layers=None,
          rescore: str = "hold") -> dict:
    """Serve `n_req` seeded greedy requests and drain.  `rescore`:
    "hold" re-scores two of them with one no-cache forward and holds
    every served token to it, "finite" runs that forward and checks only
    that its logits are finite, "none" skips it.  `n_layers` cuts the
    depth at the published widths.  Returns the generated tokens by
    request id (host data only, so the server and its device arrays are
    gone when this returns)."""
    from repro.configs import get_config
    from repro.launch.serve import BatchedServer

    c0, t0 = clock.seconds, time.perf_counter()
    server = BatchedServer(arch, smoke=False, stream=True, batch_slots=8,
                           max_seq=max_seq, mesh=mesh, n_layers=n_layers)
    want = get_config(arch)
    assert server.cfg.n_layers == (n_layers or want.n_layers)
    assert server.cfg == dataclasses.replace(
        want, n_layers=server.cfg.n_layers)
    build_s = time.perf_counter() - t0
    reqs = make_requests(server.cfg.vocab, n_req, plen, max_new, seed=7)
    for r in reqs:
        server.submit(r)
    t1 = time.perf_counter()
    server.run_until_drained()
    drained_s = time.perf_counter() - t1
    done = {r.rid: r for r in server.completed}
    assert sorted(done) == list(range(n_req)), sorted(done)
    for r in done.values():
        assert len(r.generated) == max_new, (r.rid, len(r.generated))
    tokens = {rid: [int(t) for t in r.generated] for rid, r in done.items()}
    where = ("device=0" if mesh is None
             else "mesh=" + "x".join(map(str, mesh.devices.shape)))
    print(f"serve {arch} {where} layers={server.cfg.n_layers}: "
          f"requests={n_req} "
          f"tokens={sum(map(len, tokens.values()))} "
          f"build_s={build_s!r} drained_wall_s={drained_s!r} "
          f"compile_s={clock.seconds - c0!r}", flush=True)
    if rescore != "none":
        rescore_two(server, reqs, tokens, hold=rescore == "hold")
    del server, done, reqs
    gc.collect()
    return tokens


def rescore_two(server, reqs, tokens, hold: bool) -> None:
    """Teacher-force prompt + served tokens of the shortest and the
    longest request through ONE `transformer.logits_fn` forward (no cache,
    no Pallas kernels); its logits must be finite and, with `hold`, every
    served token must be its argmax or a near-tie."""
    import jax
    import jax.numpy as jnp
    from repro.models import transformer

    cfg = server.cfg
    picks = [min(reqs, key=lambda r: len(r.prompt)),
             max(reqs, key=lambda r: len(r.prompt))]
    seqs = [np.concatenate([r.prompt, np.asarray(tokens[r.rid][:-1],
                                                 np.int32)])
            for r in picks]
    # causal model: padding after the sequence leaves its logits alone;
    # 256 is the reference SSD scan's chunk
    width = -(-max(map(len, seqs)) // 256) * 256
    batch = np.zeros((len(seqs), width), np.int32)
    for i, sq in enumerate(seqs):
        batch[i, :len(sq)] = sq
    # only the positions that produced served tokens go to the host
    starts = jnp.asarray([len(r.prompt) - 1 for r in picks])
    n_new = len(tokens[picks[0].rid])

    @jax.jit
    def score(params, toks):
        lg = transformer.logits_fn(cfg, params, {"tokens": toks})
        return jax.vmap(lambda row, s0: jax.lax.dynamic_slice_in_dim(
            row, s0, n_new, axis=0))(lg, starts)

    logits = np.asarray(score(server.params, jnp.asarray(batch)),
                        np.float32)
    assert np.isfinite(logits).all(), "non-finite re-score logits"
    agree, near_ties, far, worst, first_far = 0, 0, 0, 0.0, None
    for i, r in enumerate(picks):
        for t, tok in enumerate(tokens[r.rid]):
            row = logits[i, t]
            top2 = np.sort(row)[-2:]
            gap = float(top2[1] - top2[0])
            if tok == int(row.argmax()):
                agree += 1
                continue
            short = float(top2[1] - row[tok])
            if gap < TOL_TOP2 and short < TOL_TOP2:
                near_ties += 1
            else:
                far += 1
                first_far = first_far or (r.rid, t, tok, int(row.argmax()),
                                          gap, short)
            worst = max(worst, short)
    total = sum(len(tokens[r.rid]) for r in picks)
    print(f"rescore {cfg.arch_id} layers={cfg.n_layers} held={hold}: "
          f"rids={[r.rid for r in picks]} "
          f"prompt_lens={[len(r.prompt) for r in picks]} "
          f"positions={total} argmax_agree={agree} near_ties={near_ties} "
          f"beyond_tol={far} worst_shortfall={worst!r} tol={TOL_TOP2}",
          flush=True)
    if hold:
        assert far == 0, (cfg.arch_id, "first beyond tol (rid, t, served, "
                          "argmax, gap, shortfall)", first_far)


def report_memory(jax, label: str) -> None:
    stats = jax.devices()[0].memory_stats() or {}
    print(f"memory {label}: peak_bytes_in_use="
          f"{stats.get('peak_bytes_in_use')!r}", flush=True)


# ------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the 2x2 mesh phase")
    args = ap.parse_args()

    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    from repro.launch.compile_cache import use_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX sees {dev.platform}); not running",
              file=sys.stderr)
        return 1
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(jax.devices())} devices", file=sys.stderr)
        return 1
    print(f"cache_dir={use_compile_cache()} device_kind={dev.device_kind} "
          f"devices={len(jax.devices())}", flush=True)
    clock = CompileClock(jax)

    if args.chips == 4:
        # DESIGN.md §11: the same requests on one device and on a 2x2
        # mesh must stream the same tokens
        from repro.launch.mesh import make_debug_mesh
        spec = dict(n_req=16, plen=(128, 2048), max_new=64, max_seq=4096,
                    clock=clock, rescore="none")
        single = serve("starcoder2_3b", **spec)
        meshed = serve("starcoder2_3b", mesh=make_debug_mesh(2, 2), **spec)
        for rid in sorted(single):
            a, b = single[rid], meshed[rid]
            if a != b:
                at = next(i for i, (x, y) in enumerate(zip(a, b)) if x != y)
                print(f"mesh diverges: request {rid} position {at}: "
                      f"single={a[at]} mesh={b[at]}", flush=True)
                return 1
        print(f"mesh 2x2 tokens equal single-device: "
              f"{sum(map(len, single.values()))} tokens", flush=True)
        report_memory(jax, "mesh phase")
    else:
        t0 = time.perf_counter()
        kernel_phase()
        print(f"phase kernels: wall_s={time.perf_counter() - t0!r}",
              flush=True)
        serve("starcoder2_3b", n_req=16, plen=(128, 2048), max_new=64,
              max_seq=4096, clock=clock)
        report_memory(jax, "after starcoder2_3b")
        jax.clear_caches()
        mamba = dict(n_req=8, plen=(128, 1024), max_new=32, max_seq=2048,
                     clock=clock)
        serve("mamba2_370m", rescore="finite", **mamba)
        report_memory(jax, "after mamba2_370m")
        serve("mamba2_370m", n_layers=MAMBA_HELD_LAYERS, **mamba)
    print(f"compile_s_total={clock.seconds!r} compiles={clock.count}",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
