"""kimi-k2-1t-a32b [moe]: Kimi-K2-Instruct, the DeepSeek-V3 block.

Source: https://huggingface.co/moonshotai/Kimi-K2-Instruct/blob/main/config.json
(the block of arXiv:2412.19437 sec. 2.1). 61 layers, d_model 7168; the first
layer dense (first_k_dense_replace 1, intermediate_size 18432), the other 60
routed: 384 experts of 2048, top-8 by sigmoid score plus a selection bias
(noaux_tc, n_group = topk_group = 1), weights renormalized and scaled by
2.827, plus one shared expert of 2048. Latent attention (MLA): 64 heads,
q_lora_rank 1536, kv_lora_rank 512, qk_nope 128 + qk_rope 64, v 128. Rope
theta 50000 with YaRN (factor 32 over 4096 positions, beta_fast = beta_slow
= 1, mscale = mscale_all_dim = 1). RMSNorm eps 1e-6, vocab 163840, untied,
no MTP layers.

bf16 params: 1T fp32 masters cannot fit the single-pod mesh. The uncut
config holds all 384 experts and keeps the capacity-based dispatch;
``reduced()`` holds a share of the experts (the dropless layer).
"""
import dataclasses

import jax.numpy as jnp

from repro.models.config import ModelConfig

SOURCE_URL = (
    "https://huggingface.co/moonshotai/Kimi-K2-Instruct/blob/main/config.json"
)

CONFIG = ModelConfig(
    name="kimi-k2-1t-a32b",
    family="moe",
    n_layers=61,
    d_model=7168,
    n_heads=64,
    n_kv_heads=64,  # MLA: every head has its own key and value
    head_dim=128,  # v_head_dim
    d_ff=18_432,  # the leading dense layer
    vocab_size=163_840,
    rope_theta=50_000.0,
    q_lora_rank=1536,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    yarn_factor=32.0,
    yarn_original_len=4096,
    yarn_beta_fast=1.0,
    yarn_beta_slow=1.0,
    yarn_mscale=1.0,
    n_dense_layers=1,
    n_experts=384,
    experts_per_token=8,
    moe_d_ff=2048,
    shared_expert_d_ff=2048,
    router_scoring="sigmoid",
    routed_scaling=2.827,
    norm_eps=1e-6,
    param_dtype=jnp.bfloat16,
)


def reduced() -> ModelConfig:
    return dataclasses.replace(
        CONFIG, n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, head_dim=16,
        d_ff=128, vocab_size=256, q_lora_rank=32, kv_lora_rank=16,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        yarn_original_len=16,  # the ramp falls inside a test's positions
        n_experts=8, experts_per_token=2, moe_d_ff=32, shared_expert_d_ff=32,
        n_experts_held=4, expert_offset=2, remat="none",
        param_dtype=jnp.float32,
    )
