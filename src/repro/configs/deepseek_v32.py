"""deepseek-v3.2-exp [moe] — 61L d_model=7168, MLA (128 heads, q_lora 1536,
kv_lora 512, qk 128+64, v 128), YaRN x40, DSA lightning indexer (64 heads
of 128, top-2048 tokens), 3 dense layers (d_ff 18432) then 58 MoE layers
of 256 routed experts (width 2048, 8 a token, noaux_tc over 8 groups,
4 kept, scale 2.5) + 1 shared expert; vocab 129280.

Serving path only (paged prefill and decode, ``models/mla.py``): it is not
in ``ARCHS``, whose entries also train and lower through ``forward``.

[hf:deepseek-ai/DeepSeek-V3.2-Exp config.json]
"""
from repro.configs.base import ArchConfig, MemoryConfig

CONFIG = ArchConfig(
    name="deepseek-v3.2-exp",
    family="moe",
    n_layers=61,
    d_model=7168,
    n_heads=128,
    n_kv_heads=128,
    d_ff=18432,
    vocab_size=129280,
    head_dim=192,
    norm_eps=1e-6,
    rope_theta=10000.0,
    n_experts=256,
    experts_per_token=8,
    n_expert_groups=8,
    topk_expert_groups=4,
    routed_scaling=2.5,
    moe_d_ff=2048,
    n_shared_experts=1,
    first_k_dense=3,
    q_lora_rank=1536,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    rope_factor=40.0,
    rope_original_max_len=4096,
    rope_beta_fast=32.0,
    rope_beta_slow=1.0,
    rope_mscale_all_dim=1.0,
    memory=MemoryConfig(index_heads=64, index_dim=128, top_k=2048,
                        min_context=2048),
)
