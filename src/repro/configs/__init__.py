"""Architecture configs: one module per assigned architecture (+ the paper's
own OPT-2.7B workload).  `get_config(arch_id)` returns the full ArchConfig;
`get_smoke_config(arch_id)` returns a CPU-sized reduction of the same family
for smoke tests.  `input_specs(cfg, shape_name)` builds ShapeDtypeStruct
stand-ins for every model input of the given benchmark shape.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.models.config import ArchConfig

ARCH_IDS = (
    "phi3_5_moe_42b",
    "granite_moe_3b",
    "mistral_nemo_12b",
    "starcoder2_3b",
    "gemma3_12b",
    "minitron_4b",
    "qwen2_vl_2b",
    "jamba_1_5_large",
    "mamba2_370m",
    "whisper_large_v3",
    "opt_2_7b",          # the paper's own LLM inference workload
    "granite_4_0_h_small",
)

# Benchmark shapes (assignment): name -> (seq_len, global_batch, kind)
SHAPES: Dict[str, tuple] = {
    "train_4k": (4_096, 256, "train"),
    "prefill_32k": (32_768, 32, "prefill"),
    "decode_32k": (32_768, 128, "decode"),
    "long_500k": (524_288, 1, "decode"),
}


def get_config(arch_id: str) -> ArchConfig:
    mod = importlib.import_module(f"repro.configs.{arch_id}")
    return mod.CONFIG


def get_smoke_config(arch_id: str) -> ArchConfig:
    mod = importlib.import_module(f"repro.configs.{arch_id}")
    return mod.SMOKE


def shape_supported(cfg: ArchConfig, shape_name: str) -> Optional[str]:
    """Returns None if the (arch, shape) cell runs, else the skip reason."""
    seq, batch, kind = SHAPES[shape_name]
    if shape_name == "long_500k" and not cfg.subquadratic:
        return ("skip: 500k-token decode requires sub-quadratic attention; "
                f"{cfg.arch_id} has full-attention layers (DESIGN.md SS4)")
    return None


def input_specs(cfg: ArchConfig, shape_name: str) -> Dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStruct stand-ins for the model inputs of one benchmark
    shape.  No device allocation - dry-run only."""
    seq, batch, kind = SHAPES[shape_name]
    f = jax.ShapeDtypeStruct
    dt = jnp.dtype(cfg.dtype)
    if cfg.enc_dec:
        # encoder frames are stub embeddings, capped at the model's encoder
        # length; the decoder consumes `seq` text tokens.
        enc = {"embeds": f((batch, min(seq, cfg.enc_len), cfg.d_model), dt)}
        if kind == "train":
            return {**enc, "tokens": f((batch, seq), jnp.int32),
                    "labels": f((batch, seq), jnp.int32)}
        if kind == "prefill":
            return {**enc, "tokens": f((batch, seq), jnp.int32)}
        return {"tokens": f((batch, 1), jnp.int32)}   # decode vs cached cross-KV
    if kind == "train":
        if cfg.frontend != "none":
            # modality stub: precomputed frame/patch embeddings
            return {"embeds": f((batch, seq, cfg.d_model), dt),
                    "labels": f((batch, seq), jnp.int32)}
        return {"tokens": f((batch, seq), jnp.int32),
                "labels": f((batch, seq), jnp.int32)}
    if kind == "prefill":
        if cfg.frontend != "none":
            return {"embeds": f((batch, seq, cfg.d_model), dt)}
        return {"tokens": f((batch, seq), jnp.int32)}
    # decode: one new token against a seq-length cache (the VLM backbone
    # decodes text tokens; only the prefill carries patch embeddings)
    return {"tokens": f((batch, 1), jnp.int32)}
