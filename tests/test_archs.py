"""Per-architecture smoke tests (deliverable (f)): every assigned arch,
reduced config, one forward/train step + one decode step on CPU, asserting
output shapes and no NaNs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, get_smoke_config, SHAPES, \
    input_specs, shape_supported
from repro.data.pipeline import DataConfig, synth_batch
from repro.launch import steps as steps_lib
from repro.models.registry import get_model
from repro.optim import adamw

B, S = 2, 32


def _batch(cfg):
    dcfg = DataConfig(vocab=cfg.vocab, batch=B, seq_len=S,
                      frontend=cfg.frontend, d_model=cfg.d_model,
                      enc_dec=cfg.enc_dec,
                      enc_len=S if cfg.enc_dec else 0)
    return {k: jnp.asarray(v) for k, v in synth_batch(dcfg, 0).items()}


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_smoke_train_step(arch_id):
    cfg = get_smoke_config(arch_id)
    model = get_model(cfg)
    params = model.init_params(cfg, jax.random.key(0))
    opt_cfg = adamw.AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=10)
    step = jax.jit(steps_lib.make_train_step(cfg, opt_cfg))
    batch = _batch(cfg)
    p2, opt2, _, metrics = step(params, adamw.init(params), None, batch)
    loss = float(metrics["loss"])
    assert np.isfinite(loss) and loss > 0, (arch_id, loss)
    assert np.isfinite(float(metrics["grad_norm"]))
    # parameters actually moved
    moved = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(
        a.astype(jnp.float32) - b.astype(jnp.float32)))), params, p2)
    assert max(jax.tree.leaves(moved)) > 0


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_smoke_decode_step(arch_id):
    cfg = get_smoke_config(arch_id)
    model = get_model(cfg)
    params = model.init_params(cfg, jax.random.key(0))
    cache = model.init_cache(cfg, B, S)
    step = jax.jit(steps_lib.make_serve_step(cfg))
    tokens = jnp.ones((B, 1), jnp.int32)
    for _ in range(3):
        tokens, logits, cache = step(params, cache, tokens)
    assert logits.shape == (B, 1, cfg.padded_vocab)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))
    assert int(cache["pos"]) == 3
    assert bool(jnp.all((tokens >= 0) & (tokens < cfg.padded_vocab)))


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_full_config_matches_assignment(arch_id):
    """The full configs carry the exact assigned hyperparameters."""
    cfg = get_config(arch_id)
    expected = {
        "phi3_5_moe_42b": (32, 4096, 32, 8, 6400, 32064, 16, 2),
        "granite_moe_3b": (32, 1536, 24, 8, 512, 49155, 40, 8),
        "mistral_nemo_12b": (40, 5120, 32, 8, 14336, 131072, 0, 0),
        "starcoder2_3b": (30, 3072, 24, 2, 12288, 49152, 0, 0),
        "gemma3_12b": (48, 3840, 16, 8, 15360, 262144, 0, 0),
        "minitron_4b": (32, 3072, 24, 8, 9216, 256000, 0, 0),
        "qwen2_vl_2b": (28, 1536, 12, 2, 8960, 151936, 0, 0),
        "jamba_1_5_large": (72, 8192, 64, 8, 24576, 65536, 16, 2),
        "mamba2_370m": (48, 1024, 16, 16, 0, 50280, 0, 0),
        "whisper_large_v3": (32, 1280, 20, 20, 5120, 51866, 0, 0),
        "opt_2_7b": None,
        "granite_4_0_h_small": (40, 4096, 32, 8, 768, 100352, 72, 10),
    }[arch_id]
    if expected is None:
        return
    got = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
           cfg.d_ff, cfg.vocab, cfg.n_experts, cfg.top_k)
    assert got == expected, (arch_id, got, expected)


def test_long_500k_skips_are_correct():
    """long_500k runs only for sub-quadratic archs (DESIGN.md §4)."""
    runs = {a for a in ARCH_IDS
            if shape_supported(get_config(a), "long_500k") is None}
    assert runs == {"mamba2_370m", "jamba_1_5_large"}


@pytest.mark.parametrize("arch_id", ARCH_IDS)
@pytest.mark.parametrize("shape", list(SHAPES))
def test_input_specs_are_abstract(arch_id, shape):
    cfg = get_config(arch_id)
    if shape_supported(cfg, shape):
        pytest.skip("cell skipped by design")
    specs = input_specs(cfg, shape)
    assert specs, (arch_id, shape)
    for k, v in specs.items():
        assert isinstance(v, jax.ShapeDtypeStruct), (k, type(v))
        seq, batch, kind = SHAPES[shape]
        assert v.shape[0] == batch
