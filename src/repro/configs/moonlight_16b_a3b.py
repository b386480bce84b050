"""Moonlight-16B-A3B — latent attention (DeepSeek-V3 MLA), one leading
dense layer, then 26 expert layers of 64 routed experts (top-6, sigmoid
router) and 2 shared experts.

[hf:moonshotai/Moonlight-16B-A3B config.json; hf]  27L d_model=2048 16H
kv_lora_rank=512 (no query compression), qk_nope/rope 128/64, v 128,
dense d_ff=11264, expert d_ff=1408, vocab=163840, rope_theta=50000.
``CONFIG`` is one chip's share of a four-chip host that splits the
experts four ways: it holds experts 0-15 of every expert layer.
"""
from .base import ModelConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b", family="mla_moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16, d_ff=1408,
    vocab=163840, kv_lora_rank=512, qk_nope_head_dim=128,
    qk_rope_head_dim=64, v_head_dim=128, rope_theta=50000.0,
    n_experts=64, top_k=6, routed_scale=2.446,
    n_shared_experts=2, first_k_dense=1, dense_d_ff=11264,
    experts_held=16, expert_first=0,
    source="[hf:moonshotai/Moonlight-16B-A3B; hf]",
)

SMOKE = ModelConfig(
    name="moonlight-16b-a3b-smoke", family="mla_moe",
    n_layers=4, d_model=64, n_heads=4, n_kv_heads=4, d_ff=32, vocab=4096,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, rope_theta=50000.0,
    n_experts=16, top_k=6, routed_scale=1.0,
    n_shared_experts=2, first_k_dense=1, dense_d_ff=96,
    experts_held=4, expert_first=0,
    source="reduced",
)
