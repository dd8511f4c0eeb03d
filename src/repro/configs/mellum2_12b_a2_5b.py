"""mellum2-12b-a2.5b [moe]: 28L d_model=2304 32H (GQA kv=4) vocab=98304.

MoE in every layer: 64 experts of width 896, top-8, softmax router with
the top-k renormalised, no shared expert.  Attention repeats (sliding,
sliding, sliding, full) seven times, 1024-token window; RoPE theta 500000,
YaRN (factor 16 over 8192 positions) on the full layers only
(hf:JetBrains/Mellum2-12B-A2.5B-Instruct).  The MTP head is not served.
"""
from repro.configs.base import ModelConfig, MoEConfig, YarnConfig

CONFIG = ModelConfig(
    name="mellum2-12b-a2.5b",
    family="moe",
    n_layers=28,
    d_model=2304,
    n_heads=32,
    n_kv_heads=4,
    head_dim=128,
    d_ff=7168,
    vocab_size=98304,
    hidden_act="silu",
    layer_pattern=("swa", "swa", "swa", "attn"),
    sliding_window=1024,
    rmsnorm_eps=1e-6,
    rope_theta=500000.0,
    rope_scaling=YarnConfig(factor=16.0, original_max_position=8192,
                            beta_fast=32.0, beta_slow=1.0,
                            attention_factor=1.2772588722239782,
                            layer_kinds=("attn",)),
    moe=MoEConfig(
        n_experts=64,
        top_k=8,
        n_shared=0,
        d_expert=896,
        every_n_layers=1,
    ),
    max_seq_len=131072,
)
