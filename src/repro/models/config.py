"""Model configuration covering all assigned architecture families.

One frozen dataclass drives dense / MoE / SSM / hybrid / enc-dec / VLM
backbones. Family semantics:

  dense    decoder-only transformer (minitron, qwen2, llama3, chameleon,
           gemma2 via local/global options)
  moe      dense attention + routed-expert FFN (kimi-k2, qwen2-moe)
  ssm      pure Mamba2/SSD stack, attention-free (mamba2)
  hybrid   Mamba2 backbone + shared attention block every `hybrid_period`
           layers (zamba2)
  encdec   encoder-decoder with stubbed modality frontend (whisper)

Modality frontends ([audio]/[vlm]) are STUBS per the assignment: input_specs
provide precomputed frame embeddings (whisper) or fused token ids over the
unified vocab (chameleon).
"""
from __future__ import annotations

import dataclasses
import os
from typing import Any

import jax.numpy as jnp


def kernel_mode() -> str:
    """Default use_pallas mode for the kernel-routing knobs.

    'auto' (Pallas on TPU, jnp oracle on CPU) unless the REPRO_KERNEL_MODE
    env var overrides it — the escape hatch back to 'jnp' (the inline
    einsum paths) or to a forced mode, without touching configs.
    """
    return os.environ.get("REPRO_KERNEL_MODE", "auto")


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # dense | moe | ssm | hybrid | encdec
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int

    # --- attention options ---------------------------------------------
    qkv_bias: bool = False  # qwen2
    rope_theta: float = 10_000.0
    sliding_window: int | None = None  # gemma2 local layers
    local_global: bool = False  # gemma2: alternate local/global layers
    attn_softcap: float | None = None  # gemma2: 50.0
    final_softcap: float | None = None  # gemma2: 30.0

    # --- latent attention (MLA, DeepSeek-V3 sec. 2.1) -------------------------
    # kv_lora_rank > 0 turns every layer's attention into MLA: queries from
    # a q_lora_rank-wide normed latent, keys and values from a
    # kv_lora_rank-wide normed latent plus one rope key of qk_rope_head_dim
    # shared by all heads. The serving cache holds that latent row
    # (kv_lora_rank + qk_rope_head_dim values a token a layer).
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # YaRN rope scaling (factor 1 = plain rope): frequencies ramp from the
    # base ones to base / factor between the correction range of
    # beta_fast / beta_slow rotations over the original context; attention
    # logits scale by (0.1 * mscale * ln(factor) + 1)^2 (mscale =
    # mscale_all_dim, so cos and sin are unscaled)
    yarn_factor: float = 1.0
    yarn_original_len: int = 4096
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0

    # --- MoE options ------------------------------------------------------
    n_dense_layers: int = 0  # leading dense (d_ff) layers of an MoE stack
    # 'softmax': top-k of softmax scores, renormalized. 'sigmoid': top-k of
    # sigmoid scores plus a per-expert selection bias (noaux_tc); the
    # weights are the chosen unbiased scores, renormalized
    router_scoring: str = "softmax"
    routed_scaling: float = 1.0  # routed experts' weights scale by this
    # expert share: n_experts_held > 0 means this chip holds experts
    # [expert_offset, expert_offset + n_experts_held) of n_experts; the
    # router spans all of them and the layer computes, without dropping a
    # token, the part of the result its own experts give
    n_experts_held: int = 0
    expert_offset: int = 0
    n_experts: int = 0
    experts_per_token: int = 0
    moe_d_ff: int = 0
    shared_expert_d_ff: int = 0  # 0 = no shared expert
    capacity_factor: float = 1.25
    # 0 = scatter/gather dispatch (simple; XLA reshards it badly at scale).
    # >0 = GShard-style grouped one-hot EINSUM dispatch with this many token
    # groups (set = data-shard count): dispatch becomes (G,Tg,E,C) one-hot
    # contractions that are data/model-local by construction — trades
    # ~2x MoE flops for eliminating the dispatch collectives (§Perf).
    moe_groups: int = 0

    # --- SSM (Mamba2/SSD) options ------------------------------------------
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_conv_width: int = 4
    ssm_chunk: int = 256
    # >0: scan SSD within-chunk compute over head blocks of this size,
    # keeping the (Q x Q) decay tile per-block instead of materializing the
    # full (B, nc, Q, Q, nh) tensor — the jnp twin of the Pallas kernel's
    # grid blocking (§Perf lever for SSM training memory).
    ssm_head_block: int = 0

    # --- hybrid (zamba2) ----------------------------------------------------
    hybrid_period: int = 6  # shared attention block every k ssm layers

    # --- enc-dec (whisper) ----------------------------------------------------
    n_encoder_layers: int = 0
    encoder_len: int = 1500  # post-conv audio frames (frontend stubbed)

    # --- numerics / misc -----------------------------------------------------
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16
    # remat policy for the scanned blocks: 'none'|'full'|'dots_saveable'
    remat: str = "full"
    # perf options (EXPERIMENTS.md §Perf; defaults = naive baseline)
    blockwise_attention: bool = False  # online-softmax, no S x S buffer
    attention_block_k: int = 1024
    # route full-sequence self-attention through the kernels/ops.py backend
    # registry: 'jnp' = the sharded einsum path in models/layers.py,
    # otherwise a use_pallas mode ('auto'|'on'|'interpret'|'off') handed to
    # ops.flash_attention (custom_vjp Pallas kernel on TPU, jnp oracle on
    # CPU under 'auto' — the default; REPRO_KERNEL_MODE env var overrides).
    # Decode/cross paths stay on 'jnp'. The kernel is a custom_vjp, so
    # training gradients route through the blocked Pallas backward under
    # the same mode.
    attention_kernel: str = dataclasses.field(default_factory=kernel_mode)
    # route the SSD within-chunk compute (train/prefill) through the
    # registry's ssd_chunk custom_vjp kernel: 'jnp' = the inline einsum
    # path in models/ssm.py, otherwise a use_pallas mode (default 'auto';
    # REPRO_KERNEL_MODE overrides). The O(1) recurrent decode step stays
    # on 'jnp' (no chunk structure).
    ssm_kernel: str = dataclasses.field(default_factory=kernel_mode)
    # route paged-cache serving decode (src/repro/serve/) through the
    # registry's decode_attention kernel: a use_pallas mode (default
    # 'auto'; REPRO_KERNEL_MODE overrides). 'jnp' degrades to 'off' (the
    # jnp-gather oracle) — unlike train/prefill there is no separate
    # inline path, the oracle IS the reference implementation.
    decode_kernel: str = dataclasses.field(default_factory=kernel_mode)
    # shard attention compute by Q heads (n_heads) instead of KV heads:
    # GQA models with kv_heads < mesh 'model' size otherwise replicate the
    # whole attention computation across the model axis. Expands K/V per
    # group (the expansion is itself sharded, so per-device KV bytes are
    # unchanged) and removes the n_heads/kv_heads-fold compute redundancy.
    shard_q_heads: bool = False
    # shard the residual stream's d_model axis over 'model' (sequence-
    # parallel style): divides the per-layer saved activations (the remat
    # boundary carries) by the model-axis size, at the cost of per-layer
    # all-gathers. The lever for 100B+ training memory.
    shard_residual_embed: bool = False

    # --- shape-grid participation -------------------------------------------
    supports_long_context: bool = False  # run long_500k only if sub-quadratic
    has_decoder: bool = True  # decode shapes apply

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def latent_dim(self) -> int:
        """Width of one cached MLA row: the kv latent and the rope key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    @property
    def experts_here(self) -> int:
        """Experts whose weights this chip holds."""
        return self.n_experts_held or self.n_experts

    def attn_param_count(self) -> int:
        d = self.d_model
        if self.is_mla:
            h = self.n_heads
            return (d * self.q_lora_rank + self.q_lora_rank * h * self.qk_head_dim
                    + d * self.latent_dim
                    + self.kv_lora_rank * h * (self.qk_nope_head_dim
                                               + self.v_head_dim)
                    + h * self.v_head_dim * d)
        return d * self.q_dim + 2 * d * self.kv_dim + self.q_dim * d

    @property
    def ssm_d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.ssm_d_inner // self.ssm_head_dim

    @property
    def is_attention_free(self) -> bool:
        return self.family == "ssm"

    def param_count(self) -> int:
        """Analytic parameter count (for 6ND model-flops accounting)."""
        d, ff, v = self.d_model, self.d_ff, self.vocab_size
        emb = v * d * (1 if self.tie_embeddings else 2)
        attn = self.attn_param_count()
        dense_mlp = 3 * d * ff
        moe_mlp = self.experts_here * 3 * d * self.moe_d_ff + (
            3 * d * self.shared_expert_d_ff if self.shared_expert_d_ff else 0
        ) + d * self.n_experts  # router
        di, st, hd = self.ssm_d_inner, self.ssm_state, self.ssm_head_dim
        nh = self.ssm_heads if self.ssm_d_inner else 0
        ssm_blk = (
            d * (2 * di + 2 * st + nh)  # in_proj -> z, x, B, C, dt
            + (di + 2 * st) * self.ssm_conv_width  # conv
            + nh * 2  # A_log, D
            + di * d  # out_proj
        )
        per = {
            "dense": attn + dense_mlp,
            "moe": attn + moe_mlp,
            "ssm": ssm_blk,
            "hybrid": ssm_blk,  # + shared attn block counted once below
            "encdec": attn + dense_mlp,
        }[self.family]
        total = emb + self.n_layers * per
        if self.family == "moe":
            total += self.n_dense_layers * (dense_mlp - moe_mlp)
        if self.family == "hybrid":
            total += attn + dense_mlp  # one shared attention+mlp block
        if self.family == "encdec":
            # encoder layers + decoder cross-attention
            total += self.n_encoder_layers * (attn + dense_mlp)
            total += self.n_layers * attn  # cross-attn per decoder layer
        return int(total)

    def active_param_count(self) -> int:
        """Active parameters per token (MoE: routed top-k + shared only)."""
        if self.family != "moe":
            return self.param_count()
        d = self.d_model
        attn = self.attn_param_count()
        act_mlp = self.experts_per_token * 3 * d * self.moe_d_ff + (
            3 * d * self.shared_expert_d_ff if self.shared_expert_d_ff else 0
        ) + d * self.n_experts
        emb = self.vocab_size * d * (1 if self.tie_embeddings else 2)
        dense = self.n_dense_layers * (3 * d * self.d_ff - act_mlp)
        return int(emb + self.n_layers * (attn + act_mlp) + dense)
