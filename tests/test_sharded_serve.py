"""Mesh-sharded tensor-parallel serving: shard-count invariance
(DESIGN.md §11).

The serving contract under a `jax.sharding.Mesh` is BITWISE: for any
mesh shape, the streamed tokens, the syncs/token, and the page-ledger
closure must be identical to the single-device server's — the only
quantity allowed to move is the AXLE wire traffic
(`wire_bytes_per_shard`), which scales with the mesh by construction.

Multi-device CPU runs need `--xla_force_host_platform_device_count` set
BEFORE jax initializes, so every mesh-touching check runs in a
subprocess "cell" (the test_dryrun.py pattern).  One cell runs a whole
arch's matrix — mixed greedy / fixed-seed stochastic / stop-token
workload through slot recycling — and the parametrized tests here
assert against the memoized JSON.  Kernel-level and ledger-level
properties (the head-split concatenation identity, ring flow control)
run in-process, hypothesis-drawn where the dependency is available.
"""
import functools
import json
import os
import subprocess
import sys

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

FAST_ARCHES = ["starcoder2_3b", "granite_moe_3b", "mamba2_370m"]
SLOW_ARCHES = FAST_ARCHES + ["mistral_nemo_12b"]

# ---------------------------------------------------------------------------
# The subprocess cell: one forced-4-device child per (mode, arch)
# ---------------------------------------------------------------------------

_CHILD = r'''
import json, sys
import numpy as np
from repro.configs import get_smoke_config
from repro.launch.mesh import make_debug_mesh
from repro.launch.serve import BatchedServer, Request, SamplingParams

MODE, ARCH = sys.argv[1], sys.argv[2]
cfg = get_smoke_config(ARCH)


def workload(n=6, seed=7):
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        prompt = rng.integers(1, min(cfg.vocab, 512),
                              rng.integers(3, 9)).astype(np.int32)
        max_new = int(rng.integers(2, 9))
        kind = i % 3
        if kind == 0:        # greedy (the bitwise-across-modes baseline)
            sampling = None
        elif kind == 1:      # fixed-seed stochastic
            sampling = SamplingParams(temperature=0.9, top_p=0.85,
                                      seed=100 + i)
        else:                # stochastic + stop set (boundary retires)
            sampling = SamplingParams(temperature=1.1, top_k=16,
                                      seed=200 + i,
                                      stop_tokens=(int(rng.integers(cfg.vocab)),))
        reqs.append((kind, dict(rid=i, prompt=prompt, max_new=max_new,
                                sampling=sampling)))
    return reqs


def run(dm, spec=False, host_offload=False):
    mesh = make_debug_mesh(*dm) if dm else None
    kw = dict(smoke=True, batch_slots=2, max_seq=64, protocol="bs",
              stream=True, seg_len=4, mesh=mesh)
    if spec:
        kw.update(spec=True, spec_k=2)
    if host_offload:
        kw.update(host_offload=True, evict_after=1)
    server = BatchedServer(ARCH, **kw)
    kinds = {}
    for kind, w in workload():
        kinds[w["rid"]] = kind
        server.submit(Request(**w))
    server.run_until_drained(max_steps=100_000)
    assert not server.queue and all(r is None for r in server.active)
    return dict(
        tokens={r.rid: list(map(int, r.generated))
                for r in server.completed},
        kinds=kinds,
        syncs=server.decode_syncs,
        wire=int(server.wire_bytes_per_shard),
        wire_model=dict(n_shards=server.wire.n_shards,
                        rows_local=server.wire.rows_local,
                        heads_local=server.wire.heads_local,
                        head_dim=server.wire.head_dim,
                        merges=server.wire.merges),
        pages_allocated=int(server.pages_allocated),
        pages_freed=int(server.pages_freed),
        evictions=int(getattr(server, "evictions", 0)),
        restores=int(getattr(server, "restores", 0)),
    )


out = {}
if MODE == "matrix":
    out["base"] = run(None)
    out["m12"] = run((1, 2))
    out["m14"] = run((1, 4))
elif MODE == "slow2x2":
    out["base"] = run(None)
    out["m12"] = run((1, 2))
    out["m22"] = run((2, 2))
    out["m14"] = run((1, 4))
elif MODE == "spec":
    out["base"] = run(None, spec=True)
    out["m12"] = run((1, 2), spec=True)
elif MODE == "churn":
    out["base"] = run(None, host_offload=True)
    out["m12"] = run((1, 2), host_offload=True)
elif MODE == "misc":
    import jax
    from repro import sharding as sh
    from repro.launch import partition
    from repro.models.registry import get_model

    mesh = make_debug_mesh(1, 4)
    rules = sh.ShardingRules(mesh, head_shard_attn=True)
    plan = partition.PartitionPlan(rules=rules, fsdp=False)

    # page-split guard: S=64 over 4 model shards is 16 per shard; a
    # page_size of 32 (n_pages=2) straddles the boundary -> ValueError
    S = lambda *s: jax.ShapeDtypeStruct(s, np.float32)
    seq_rules = sh.ShardingRules(mesh, seq_shard_attn=True)
    seq_plan = partition.PartitionPlan(rules=seq_rules, fsdp=False)
    bad = {"k0": S(2, 2, 2, 64, 8), "v0": S(2, 2, 2, 64, 8),
           "page_table": jax.ShapeDtypeStruct((2, 2), np.int32)}
    try:
        partition.cache_specs(bad, cfg, seq_plan)
        out["page_split_raised"] = False
    except ValueError as e:
        out["page_split_raised"] = "split a page" in str(e)
    ok = {"k0": S(2, 2, 2, 64, 8), "v0": S(2, 2, 2, 64, 8),
          "page_table": jax.ShapeDtypeStruct((2, 4), np.int32)}
    specs_ok = partition.cache_specs(ok, cfg, seq_plan)
    out["page_split_ok_divisible"] = "model" in (specs_ok["k0"][3] or "")

    # serving specs: params fully replicated, cache model-replicated
    model = get_model(cfg)
    ab = model.abstract_params(cfg)
    pspecs = jax.tree.leaves(
        partition.serve_param_specs(ab, cfg, plan),
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
    out["params_all_replicated"] = all(
        all(a is None for a in s) for s in pspecs)
    abc = model.init_cache(cfg, 2, 64)
    cspecs = partition.serve_cache_specs(abc, cfg, plan)
    out["cache_no_model_axis"] = all(
        "model" not in [a for a in spec if isinstance(a, str)]
        for spec in cspecs.values())

    # head regimes across the smoke families at n=2 and n=4
    regimes = {}
    for arch in ["starcoder2_3b", "mistral_nemo_12b", "granite_moe_3b",
                 "mamba2_370m"]:
        acfg = get_smoke_config(arch)
        for n in (2, 4):
            m = make_debug_mesh(1, n)
            p = partition.PartitionPlan(
                rules=sh.ShardingRules(m, head_shard_attn=True), fsdp=False)
            regimes[f"{arch}@{n}"] = list(
                partition.serve_head_regime(acfg, p))
    out["regimes"] = regimes

    # Mosaic kernels are never auto-partitioned: under a serving mesh the
    # ops entries run their kernel replicated inside a shard_map — same
    # values as the unsharded call (interpret mode stands in for the chip)
    import jax.numpy as jnp
    from repro.kernels import ops
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (1, 16, 4, 8))
    kv = [jax.random.normal(kk, (1, 16, 2, 8)) for kk in ks[1:]]
    fa = jax.jit(lambda *a: ops.flash_attention(*a, interpret=True))
    plain = fa(q, *kv)
    with mesh, sh.use_rules(rules):
        meshed = jax.jit(
            lambda *a: ops.flash_attention(*a, interpret=True))(q, *kv)
        jaxpr = str(jax.make_jaxpr(
            lambda *a: ops.flash_attention(*a, interpret=True))(q, *kv))
    out["kernel_replicated_bitwise"] = bool(
        jnp.array_equal(plain, meshed))
    out["kernel_in_shard_map"] = "shard_map" in jaxpr
print("JSON:" + json.dumps(out))
'''


@functools.lru_cache(maxsize=None)
def _cell(mode, arch="starcoder2_3b"):
    env = dict(os.environ, PYTHONPATH=SRC,
               XLA_FLAGS="--xla_force_host_platform_device_count=4 "
               + os.environ.get("XLA_FLAGS", ""))
    out = subprocess.run([sys.executable, "-c", _CHILD, mode, arch],
                         env=env, capture_output=True, text=True,
                         timeout=1500)
    assert out.returncode == 0, (out.stdout[-1500:], out.stderr[-3000:])
    line = [ln for ln in out.stdout.splitlines()
            if ln.startswith("JSON:")][-1]
    return json.loads(line[len("JSON:"):])


def _assert_invariant(base, mesh_run):
    """tokens, syncs/token and ledger closure identical; wire may move."""
    assert mesh_run["tokens"] == base["tokens"]
    assert mesh_run["syncs"] == base["syncs"]
    assert mesh_run["pages_allocated"] == base["pages_allocated"]
    assert mesh_run["pages_freed"] == base["pages_freed"]


# ---------------------------------------------------------------------------
# fast tier: {1x1, 1x2, 1x4} across three arch families
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", FAST_ARCHES)
def test_tokens_bitwise_1x2(arch):
    """Streamed tokens at mesh 1x2 are BITWISE the single-device run's,
    through slot recycling, for greedy and stochastic rows alike."""
    cell = _cell("matrix", arch)
    _assert_invariant(cell["base"], cell["m12"])


@pytest.mark.parametrize("arch", FAST_ARCHES)
def test_tokens_bitwise_1x4(arch):
    cell = _cell("matrix", arch)
    _assert_invariant(cell["base"], cell["m14"])


@pytest.mark.parametrize("arch", FAST_ARCHES)
def test_stochastic_rows_present_and_bitwise(arch):
    """The workload genuinely exercises sampling: stochastic rows exist,
    emit vocab-bounded ids, and match bitwise across mesh shapes (greedy
    argmax can mask low-bit logits drift; sampled rows cannot)."""
    cell = _cell("matrix", arch)
    cfg_vocab_rows = [rid for rid, kind in cell["base"]["kinds"].items()
                      if kind != 0]
    assert len(cfg_vocab_rows) >= 3
    for rid in cfg_vocab_rows:
        assert cell["m12"]["tokens"][rid] == cell["base"]["tokens"][rid]
        assert cell["m14"]["tokens"][rid] == cell["base"]["tokens"][rid]


@pytest.mark.parametrize("arch", FAST_ARCHES)
def test_syncs_and_ledger_closed(arch):
    """Page-ledger CLOSURE on every shape: a drained server freed every
    page it allocated, and the counts match single-device exactly."""
    cell = _cell("matrix", arch)
    for key in ("base", "m12", "m14"):
        run = cell[key]
        assert run["pages_allocated"] == run["pages_freed"]
        assert run["pages_allocated"] > 0
    assert cell["m12"]["syncs"] == cell["base"]["syncs"]
    assert cell["m14"]["syncs"] == cell["base"]["syncs"]


def test_wire_bytes_formula_and_scaling():
    """wire_bytes_per_shard follows the AXLE accounting exactly:
    merges * (n-1) * rows_local * heads_local * (hd + 2) * 4 — and the
    single-device wire is identically zero."""
    from repro.core import ring
    cell = _cell("matrix", "starcoder2_3b")
    assert cell["base"]["wire"] == 0
    for key in ("m12", "m14"):
        wm = cell[key]["wire_model"]
        expect = wm["merges"] * ring.merge_wire_bytes_per_shard(
            wm["n_shards"], wm["rows_local"], wm["heads_local"],
            wm["head_dim"])
        assert cell[key]["wire"] == expect > 0
    # more shards, smaller head groups, more hops: 1x4 moves more than 1x2
    assert cell["m14"]["wire"] > cell["m12"]["wire"]


def test_replicated_fallback_has_zero_wire():
    """When neither n | KH nor (KH==1 and n | H) holds the server falls
    back to fully replicated attention — still bitwise, zero wire."""
    cell = _cell("matrix", "granite_moe_3b")     # KH=2, H=6: no 4-split
    assert cell["m14"]["wire"] == 0
    assert cell["m14"]["tokens"] == cell["base"]["tokens"]
    cell = _cell("matrix", "mamba2_370m")        # pure SSM: no attention
    assert cell["m12"]["wire"] == cell["m14"]["wire"] == 0


def test_spec_decode_bitwise_on_mesh():
    """Speculative serving (draft + multi-position verify) under 1x2:
    same tokens, same syncs, and the wire charges (k+1) merge rounds per
    accepted segment."""
    cell = _cell("spec", "starcoder2_3b")
    _assert_invariant(cell["base"], cell["m12"])
    assert cell["m12"]["wire"] > 0


def test_misc_page_split_guard_and_serve_specs():
    """Satellite guards: (a) sequence-axis sharding that would split a
    page fails loudly in `cache_specs`; (b) the serving specs keep
    params fully replicated and the cache off the model axis (the
    bitwise contract's jit-graph half); (c) head regimes match the
    divisibility table."""
    cell = _cell("misc")
    assert cell["page_split_raised"] is True
    assert cell["page_split_ok_divisible"] is True
    assert cell["params_all_replicated"] is True
    assert cell["cache_no_model_axis"] is True
    # (shard_q, shard_kv): n|KH -> both; KH==1 and n|H -> q only
    assert cell["regimes"]["starcoder2_3b@2"] == [True, False]
    assert cell["regimes"]["starcoder2_3b@4"] == [True, False]
    assert cell["regimes"]["mistral_nemo_12b@2"] == [True, True]
    assert cell["regimes"]["mistral_nemo_12b@4"] == [False, False]
    assert cell["regimes"]["granite_moe_3b@2"] == [True, True]
    assert cell["regimes"]["granite_moe_3b@4"] == [False, False]
    assert cell["regimes"]["mamba2_370m@2"] == [False, False]


def test_kernels_run_replicated_in_shard_map_under_mesh():
    """An ops kernel entry called under a multi-device serving mesh runs
    inside a fully replicated shard_map (Mosaic kernels are never
    auto-partitioned) and returns the unsharded call's values."""
    r = _cell("misc")
    assert r["kernel_in_shard_map"]
    assert r["kernel_replicated_bitwise"]


# ---------------------------------------------------------------------------
# churn tier: host-tier offload/evict/restore under a 2-device mesh
# ---------------------------------------------------------------------------

def test_churn_offload_bitwise_under_mesh():
    """Host-tier eviction/restoration churn (suspend to host RAM, stream
    back on readmission) composes with the mesh: identical tokens and
    ledger, and the churn really happened on both sides."""
    cell = _cell("churn", "starcoder2_3b")
    _assert_invariant(cell["base"], cell["m12"])
    assert cell["m12"]["evictions"] == cell["base"]["evictions"] > 0
    assert cell["m12"]["restores"] == cell["base"]["restores"]


# ---------------------------------------------------------------------------
# slow tier: the 2x2 mesh (data x model), all four families
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("arch", SLOW_ARCHES)
def test_tokens_bitwise_2x2(arch):
    """Data-parallel batch sharding composes with model-axis head groups:
    2x2 (rows split over data, heads over model) stays bitwise with the
    same syncs — and wire bytes HALVE vs 1x2 (half the local rows, same
    hop count) whenever the head-group path engages."""
    cell = _cell("slow2x2", arch)
    _assert_invariant(cell["base"], cell["m22"])
    _assert_invariant(cell["base"], cell["m12"])
    _assert_invariant(cell["base"], cell["m14"])
    if cell["m12"]["wire"]:
        assert cell["m22"]["wire"] * 2 == cell["m12"]["wire"]


# ---------------------------------------------------------------------------
# in-process property suite (hypothesis-drawn where available)
# ---------------------------------------------------------------------------

def test_headsplit_concat_identity_drawn():
    """THE invariance property, at kernel level: for any drawn decode
    problem and any whole-head split, concatenating per-group fused
    partials and normalizing once reproduces `decode_fused_reference`
    BITWISE.  This is why the mesh serve path is shard-count invariant:
    the all_gather in `_headgroup_gather_decode` is this concatenation."""
    hypothesis = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")
    import jax.numpy as jnp
    from repro.kernels import ref

    @hypothesis.settings(max_examples=25, deadline=None)
    @hypothesis.given(data=st.data())
    def prop(data):
        rng = np.random.default_rng(data.draw(st.integers(0, 2**32 - 1)))
        b = data.draw(st.integers(1, 3))
        hd = data.draw(st.sampled_from([4, 8]))
        kh = data.draw(st.sampled_from([1, 2, 4]))
        group = data.draw(st.integers(1, 2))     # q heads per kv head
        h = kh * group
        n_split = data.draw(st.sampled_from(
            [n for n in (1, 2, 4) if kh % n == 0 or (kh == 1 and h % n == 0)]))
        s = 16
        q = jnp.asarray(rng.standard_normal((b, 1, h, hd)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((b, kh, s, hd)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((b, kh, s, hd)), jnp.float32)
        pos = jnp.asarray(rng.integers(1, s, b), jnp.int32)
        window = data.draw(st.sampled_from([0, 8]))

        full = ref.decode_fused_reference(q, k, v, pos, window=window)
        accs, ls = [], []
        hl, khl = h // n_split, max(1, kh // n_split)
        for i in range(n_split):
            qg = q[:, :, i * hl:(i + 1) * hl]
            if kh >= n_split:
                kg = k[:, i * khl:(i + 1) * khl]
                vg = v[:, i * khl:(i + 1) * khl]
            else:                                # KH==1: replicated KV
                kg, vg = k, v
            acc, m, l = ref.decode_fused_partial_reference(
                qg, kg, vg, pos, window=window)
            accs.append(acc)
            ls.append(l)
        merged = ref.normalize_fused_partial(
            jnp.concatenate(accs, axis=1), jnp.concatenate(ls, axis=1),
            q.dtype)
        assert (np.asarray(full) == np.asarray(merged)).all(), \
            (n_split, h, kh)

    prop()


def test_wire_ledger_model_drawn():
    """WireLedger arithmetic under drawn charge sequences: linearity in
    merges, zero at n=1, and the per-merge payload formula."""
    hypothesis = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")
    from repro.core import ring

    @hypothesis.settings(max_examples=50, deadline=None)
    @hypothesis.given(n=st.integers(1, 8), rows=st.integers(1, 16),
                      heads=st.integers(1, 8), hd=st.integers(1, 128),
                      charges=st.lists(st.integers(0, 64), max_size=20))
    def prop(n, rows, heads, hd, charges):
        led = ring.WireLedger(n_shards=n, rows_local=rows,
                              heads_local=heads, head_dim=hd)
        for c in charges:
            led.charge_merges(c)
        per = ring.merge_wire_bytes_per_shard(n, rows, heads, hd)
        assert per == (0 if n == 1 else (n - 1) * rows * heads * (hd + 2) * 4)
        assert led.wire_bytes_per_shard == sum(charges) * per
        assert led.wire_bytes_total == led.wire_bytes_per_shard * n
        assert led.segments == len(charges)

    prop()


def test_ring_flow_control_stateful():
    """Hypothesis-stateful check of the gap-aware ring (SS IV-C): under
    arbitrary allocate / out-of-order consume / flow-control-update
    interleavings, the paper's invariants hold and the producer's stale
    credits never over-promise."""
    hypothesis = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")
    stateful = pytest.importorskip("hypothesis.stateful")
    from repro.core import ring

    CAP = 8

    class RingMachine(stateful.RuleBasedStateMachine):
        def __init__(self):
            super().__init__()
            self.ring = ring.make_ring(CAP)
            self.outstanding = []        # allocated, not yet consumed

        @stateful.rule(n=st.integers(1, 4))
        def allocate(self, n):
            if bool(ring.can_allocate(self.ring, n)):
                self.ring, start = ring.allocate(self.ring, n)
                self.outstanding.extend(
                    range(int(start), int(start) + n))

        @stateful.rule(data=st.data())
        def consume_one(self, data):
            if self.outstanding:
                i = data.draw(st.integers(0, len(self.outstanding) - 1))
                idx = self.outstanding.pop(i)    # out-of-order by draw
                self.ring = ring.consume(self.ring, idx)

        @stateful.rule()
        def deliver_head(self):
            self.ring = ring.flow_control_update(self.ring)

        @stateful.invariant()
        def paper_invariants(self):
            assert bool(ring.invariants_ok(self.ring))

        @stateful.invariant()
        def credits_conservative(self):
            # stale credits never exceed TRUE free slots
            true_free = CAP - (int(self.ring.tail) - int(self.ring.head))
            assert int(ring.free_slots_producer(self.ring)) <= true_free

        @stateful.invariant()
        def head_is_contiguous_prefix(self):
            # every index below head has been consumed (gap-aware head
            # never skips an unconsumed slot)
            assert all(i >= int(self.ring.head) for i in self.outstanding)

    RingMachine.TestCase.settings = hypothesis.settings(
        max_examples=30, stateful_step_count=30, deadline=None)
    run = stateful.run_state_machine_as_test
    run(RingMachine, settings=RingMachine.TestCase.settings)


def test_merge_pair_owner_selection():
    """Merging a partial with an 'absent' partial (m=-inf, l=0) selects
    the owner verbatim — the degenerate case head-group sharding relies
    on (DESIGN.md §11)."""
    import jax.numpy as jnp
    from repro.kernels import ref
    rng = np.random.default_rng(3)
    acc = jnp.asarray(rng.standard_normal((2, 4, 8)), jnp.float32)
    m = jnp.asarray(rng.standard_normal((2, 4)), jnp.float32)
    l = jnp.asarray(rng.uniform(0.5, 2.0, (2, 4)), jnp.float32)
    neg = jnp.full_like(m, -jnp.inf)
    zero = jnp.zeros_like(l)
    a2, m2, l2 = ref.merge_fused_partial_pair(
        acc, m, l, jnp.zeros_like(acc), neg, zero)
    assert (np.asarray(a2) == np.asarray(acc)).all()
    assert (np.asarray(m2) == np.asarray(m)).all()
    assert (np.asarray(l2) == np.asarray(l)).all()
