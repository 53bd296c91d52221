"""mellum2-12b [moe] — Mellum2-12B-A2.5B: window and full attention
mixed, routed experts in every layer, YaRN on the full layers.

28L d_model=2304 32H (GQA kv=4, head_dim 128) vocab=98304 untied;
every MLP sparse: 64 experts of width 896, top-8, probabilities
renormalised over the 8, no shared expert.  Three sliding-window layers
(window 1024) to each full layer; rotary theta 500000, plain on the
window layers and YaRN (factor 16 over 8192 original positions,
beta_fast 32, beta_slow 1, attention factor 1.2772588722239782) on the
full ones.  `d_ff` is the dense width the config states
(`intermediate_size` 7168); no layer is dense.

This configuration holds experts 0-15 of each layer: one chip's share
of a 4-chip host that splits every layer's 64 experts 16 per chip
(expert parallelism 4), with attention, embedding and head replicated.
[hf:JetBrains/Mellum2-12B-A2.5B-Instruct config.json]
"""

from repro.configs.base import ArchConfig, YaRN

CONFIG = ArchConfig(
    name="mellum2-12b",
    family="moe",
    n_layers=28,
    d_model=2304,
    n_heads=32,
    n_kv_heads=4,
    head_dim=128,
    d_ff=7168,
    vocab_size=98304,
    n_experts=64,
    top_k=8,
    moe_d_ff=896,
    expert_start=0,
    experts_held=16,
    window_pattern=(1024, 1024, 1024, 0),
    rope_pattern=("plain", "plain", "plain", "yarn"),
    yarn=YaRN(
        factor=16.0,
        original_max_position_embeddings=8192,
        beta_fast=32.0,
        beta_slow=1.0,
        attention_factor=1.2772588722239782,
    ),
    rope_theta=500000.0,
    norm_eps=1e-6,
    tie_embeddings=False,
    subquadratic=False,
    source="hf:JetBrains/Mellum2-12B-A2.5B-Instruct",
)
