"""Mellum2-12B-A2.5B — sparse-MoE code model with 3:1 sliding/full
attention.

28 layers, d_model=2304, GQA 32 query / 4 KV heads of 128, every MLP a
64-expert top-8 SwiGLU MoE (expert width 896, softmax router,
renormalised top-k, no shared expert), vocab 98304, untied head.
Layers repeat (sliding, sliding, sliding, full): window 1024 with
default RoPE, full layers with YaRN (factor 16 over 8192 positions);
theta 500000 on both. The published MTP head is not modelled.
[hf:JetBrains/Mellum2-12B-A2.5B-Instruct]
"""
from repro.configs.base import ArchConfig, Yarn

CONFIG = ArchConfig(
    name="mellum2-12b",
    arch_type="moe",
    source="hf:JetBrains/Mellum2-12B-A2.5B-Instruct",
    n_layers=28,
    d_model=2304,
    n_heads=32,
    n_kv_heads=4,
    d_ff=896,
    vocab_size=98304,
    head_dim=128,
    layer_pattern=("local", "local", "local", "attn"),
    window=1024,
    n_experts=64,
    top_k=8,
    mlp_kind="swiglu",
    norm="rmsnorm",
    rope_theta=500000.0,
    global_yarn=Yarn(factor=16.0, original_max_positions=8192,
                     beta_fast=32.0, beta_slow=1.0,
                     attention_factor=1.2772588722239782),
)
