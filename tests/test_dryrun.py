"""Dry-run machinery tests: HLO cost model correctness, partition specs,
and one real (subprocess) production-mesh lower+compile cell."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.roofline import hlo_cost

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_hlo_cost_counts_scan_trip_counts():
    """`compiled.cost_analysis()` counts while bodies once; our HLO cost
    model must multiply by the known trip count (the roofline depends
    on it — see EXPERIMENTS.md §Roofline-method)."""
    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=10)
        return y

    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    compiled = jax.jit(f).lower(x, w).compile()
    want = 2 * 128 ** 3 * 10
    ca = compiled.cost_analysis()
    ca = ca[0] if isinstance(ca, (list, tuple)) else ca  # jax<0.5 wraps it
    assert ca["flops"] < want / 5                        # XLA undercounts
    got = hlo_cost.analyze_text(compiled.as_text()).flops
    assert got == pytest.approx(want, rel=0.01)


def test_hlo_cost_collectives_and_memory_model():
    """Collective result bytes and the ideal-fusion memory model."""
    def f(x, w):
        y = jnp.tanh(x.astype(jnp.float32)) * 2.0 + 1.0   # fusible chain
        return y @ w                                       # materializes

    x = jax.ShapeDtypeStruct((256, 256), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    compiled = jax.jit(f).lower(x, w).compile()
    cost = hlo_cost.analyze_text(compiled.as_text())
    assert cost.flops == pytest.approx(2 * 256 ** 3, rel=0.01)
    # dot reads x (through the fused elementwise chain: bf16 source) + w,
    # writes f32 out: 256*256*(2 + 4 + 4), within fusion-shape tolerance
    want = 256 * 256 * (2 + 4 + 4)
    assert cost.bytes == pytest.approx(want, rel=0.6)
    assert cost.coll_bytes == 0


def test_partition_specs_cover_every_leaf():
    from repro.configs import get_config
    from repro.launch import partition
    from repro.launch.mesh import make_debug_mesh
    from repro.models.registry import get_model
    from repro import sharding as sh

    for arch in ("phi3_5_moe_42b", "jamba_1_5_large", "whisper_large_v3",
                 "granite_4_0_h_small"):
        cfg = get_config(arch)
        model = get_model(cfg)
        ab = model.abstract_params(cfg)
        mesh = make_debug_mesh(1, 1)
        rules = sh.ShardingRules(mesh)
        plan = partition.PartitionPlan(rules=rules, fsdp=True)
        specs = partition.param_specs(ab, cfg, plan)
        flat_p = jax.tree.leaves(ab)
        flat_s = jax.tree.leaves(
            specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
        assert len(flat_p) == len(flat_s)
        for p, s in zip(flat_p, flat_s):
            assert isinstance(s, jax.sharding.PartitionSpec)
            assert len(s) == len(p.shape), (arch, p.shape, s)


@pytest.mark.slow
def test_production_mesh_cell_compiles():
    """One real 512-device multi-pod lower+compile in a subprocess (the
    XLA device-count flag must precede jax init)."""
    code = (
        "import os;"
        "os.environ['XLA_FLAGS']='--xla_force_host_platform_device_count=512';"
        "from repro.launch.dryrun import run_cell;"
        "import json;"
        "row = run_cell('mamba2_370m', 'decode_32k', multi_pod=True);"
        "print(json.dumps({'status': row['status'],"
        " 'dominant': row['roofline']['dominant']}))"
    )
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    row = json.loads(out.stdout.strip().splitlines()[-1])
    assert row["status"] == "ok"


@pytest.mark.slow
def test_moe_dist_matches_reference_on_mesh():
    """`moe_ffn_dist` (shard_map-local dispatch + padded EP, §Perf G1)
    must match the single-device dropless `moe_held` on a real mesh,
    for both EP-divisible and padded expert counts (its capacity factor
    of 8 drops nothing here)."""
    code = """
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=4'
import jax, jax.numpy as jnp, numpy as np
from repro.models import layers as L
from repro import sharding as sh
mesh = jax.make_mesh((2, 2), ('data', 'model'))
t, d, f, k = 64, 32, 48, 2
ks = jax.random.split(jax.random.key(0), 5)
x = jax.random.normal(ks[0], (t, d), jnp.float32)
for e in (6, 5):                     # divisible / padded
    router = jax.random.normal(ks[1], (d, e)) * 0.3
    wg = jax.random.normal(ks[2], (e, d, f)) * 0.1
    wu = jax.random.normal(ks[3], (e, d, f)) * 0.1
    wd = jax.random.normal(ks[4], (e, f, d)) * 0.1
    ref = L.moe_held(x, router, wg, wu, wd, k)
    with mesh, sh.use_rules(sh.ShardingRules(mesh)):
        got = jax.jit(lambda *a: L.moe_ffn_dist(
            *a, top_k=k, capacity_factor=8.0))(x, router, wg, wu, wd)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
print('OK')
"""
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout
