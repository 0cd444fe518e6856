"""IBM Granite-4.0-H-Small (32B total / 9B active, `granitemoehybrid`):
40 layers, a 10-layer period of nine Mamba-2 mixers and one attention
layer (attention at layers 5, 15, 25, 35); every layer also runs a
72-expert top-10 SwiGLU MoE (softmax over the 10 chosen logits) beside a
shared SwiGLU MLP of width 1,536.  [hf:ibm-granite/granite-4.0-h-small]

Attention is NoPE: no rotary embedding.  Its softmax scale is the
published `attention_multiplier` 1/128, which `attn_scale` folds into q
(q · 0.0078125 · √128), so the attention kernels' own head_dim ** -0.5
yields it.  The Mamba-2 mixer is the published one (`mamba_xbc`): the
causal conv with bias runs over x‖B‖C and a gated RMSNorm precedes
out_proj.  Multipliers: embedding ×12, residual ×0.22, logits ÷16.

The stated deployment is 4-way expert parallelism (`expert_shards`):
each layer's 72 experts split over 4 chips, the router still scoring all
72, and one chip holding and computing shard 0's 18.  So CONFIG is one
chip's shard and not the whole model: its layers add only the held 18
experts' part of the routed sum, and `layers.moe_ffn_dist` refuses it
on a mesh with a model axis (the exchange that would bring the other
shards' parts does not exist yet, ROADMAP D8)."""
from repro.models.config import ArchConfig

_PATTERN = ("mamba", "mamba", "mamba", "mamba", "mamba", "full", "mamba",
            "mamba", "mamba", "mamba")

CONFIG = ArchConfig(
    arch_id="granite_4_0_h_small", family="hybrid",
    n_layers=40, d_model=4096, n_heads=32, n_kv_heads=8, d_ff=768,
    vocab=100352, head_dim=128,
    eos_token=100257,          # <|end_of_text|>
    n_experts=72, top_k=10, moe_every=1, expert_shards=4, shared_ff=1536,
    block_pattern=_PATTERN,
    rope=False, attn_scale=0.0078125,
    ssm_state=128, ssm_head_dim=64, ssm_expand=2, conv_width=4,
    mamba_xbc=True,
    norm_eps=1e-5, embed_mult=12.0, residual_mult=0.22, logits_div=16.0,
)

SMOKE = ArchConfig(
    arch_id="granite_4_0_h_small_smoke", family="hybrid",
    n_layers=10, d_model=64, n_heads=4, n_kv_heads=1, d_ff=32,
    vocab=512, head_dim=16,
    eos_token=2,
    n_experts=8, top_k=3, moe_every=1, expert_shards=2, shared_ff=64,
    block_pattern=_PATTERN,
    rope=False, attn_scale=1.0 / 16,    # 1 / head_dim, as published
    ssm_state=16, ssm_head_dim=16, ssm_expand=2, conv_width=4,
    mamba_xbc=True,
    norm_eps=1e-5, embed_mult=12.0, residual_mult=0.22, logits_div=16.0,
)
