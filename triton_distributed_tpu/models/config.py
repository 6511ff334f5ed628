"""Model configs (parity: reference ``models/config.py:31`` ModelConfig).

The reference keys everything off an HF model name and reads the
architecture from HF configs at load time; here the architecture fields
are explicit (JAX builds the program from static shapes) with presets for
the model families the reference ships (Qwen3 dense + MoE,
``models/__init__.py:32-48``).
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    model_name: str = "Qwen/Qwen3-8B"
    vocab_size: int = 151936
    hidden_size: int = 4096
    intermediate_size: int = 12288
    num_layers: int = 36
    num_q_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 128
    rope_theta: float = 1e6
    rms_eps: float = 1e-6
    tie_word_embeddings: bool = False
    # MoE (0 experts = dense)
    num_experts: int = 0
    num_experts_per_tok: int = 0
    moe_intermediate_size: int = 0
    # Renormalize top-k router weights to sum to 1 (HF Qwen3MoeConfig
    # field; the official Qwen3-MoE checkpoints set it true, but the HF
    # DEFAULT is false — checkpoint loading must follow the config, not
    # assume).
    norm_topk_prob: bool = True
    # Latent attention (DeepSeek-V3 block; 0 = the GQA layer). Queries go
    # through a rank-``q_lora_rank`` bottleneck; keys and values are
    # rebuilt from ONE cached row of ``kv_lora_rank`` normed latents
    # plus ``qk_rope_head_dim`` rotary dims shared by every head.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # YaRN rope scaling (factor 0 = plain RoPE at ``rope_theta``).
    yarn_factor: float = 0.0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_original_max: int = 4096
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 0.0
    # Leading dense layers before the expert layers (0 = uniform).
    first_k_dense: int = 0
    # Expert layers of the DeepSeek-V3 kind: shared experts beside the
    # routed ones, ``sigmoid`` scores with a choice-only bias, the best
    # ``topk_group`` of ``n_group`` groups kept, weights times
    # ``routed_scaling_factor``.
    n_shared_experts: int = 0
    scoring_func: str = "softmax"
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: float = 1.0
    # One expert-parallel rank's share: experts ``[expert_offset,
    # expert_offset + experts_held)`` of ``num_experts`` live here (0 =
    # all of them). The router keeps all its outputs.
    experts_held: int = 0
    expert_offset: int = 0
    # runtime
    max_length: int = 4096
    dtype: jnp.dtype = jnp.bfloat16
    # Paged-KV storage width: None stores the pool in ``dtype``
    # (bit-identical legacy layout); "int8" stores int8 codes plus
    # symmetric per-page-per-head scales, dequantized inside the
    # attention kernels (docs/serving.md "Quantized KV cache").
    kv_dtype: str | None = None


# Architecture presets (numbers from the public HF configs the reference
# loads via AutoLLM; reference models/__init__.py:32-48).
_PRESETS: dict[str, dict] = {
    "Qwen/Qwen3-0.6B": dict(
        hidden_size=1024, intermediate_size=3072, num_layers=28,
        num_q_heads=16, num_kv_heads=8, head_dim=128,
        tie_word_embeddings=True,
    ),
    "Qwen/Qwen3-1.7B": dict(
        hidden_size=2048, intermediate_size=6144, num_layers=28,
        num_q_heads=16, num_kv_heads=8, head_dim=128,
        tie_word_embeddings=True,
    ),
    "Qwen/Qwen3-4B": dict(
        hidden_size=2560, intermediate_size=9728, num_layers=36,
        num_q_heads=32, num_kv_heads=8, head_dim=128,
        tie_word_embeddings=True,
    ),
    "Qwen/Qwen3-8B": dict(
        hidden_size=4096, intermediate_size=12288, num_layers=36,
        num_q_heads=32, num_kv_heads=8, head_dim=128,
    ),
    "Qwen/Qwen3-32B": dict(
        hidden_size=5120, intermediate_size=25600, num_layers=64,
        num_q_heads=64, num_kv_heads=8, head_dim=128,
    ),
    "Qwen/Qwen3-30B-A3B": dict(
        hidden_size=2048, intermediate_size=6144, num_layers=48,
        num_q_heads=32, num_kv_heads=4, head_dim=128,
        num_experts=128, num_experts_per_tok=8, moe_intermediate_size=768,
    ),
    # rednote-hilab/dots.vlm1.inst's language model (the DeepSeek-V3
    # block), as published: 61 layers, of which three leading dense.
    # One chip serves a share of it (docs/serving.md "One rank's share").
    "rednote-hilab/dots.vlm1.inst": dict(
        vocab_size=129280, hidden_size=7168, intermediate_size=18432,
        num_layers=61, num_q_heads=128, num_kv_heads=128, head_dim=192,
        rope_theta=1e4, q_lora_rank=1536, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        yarn_factor=40.0, yarn_beta_fast=32.0, yarn_beta_slow=1.0,
        yarn_original_max=4096, yarn_mscale=1.0, yarn_mscale_all_dim=1.0,
        first_k_dense=3, num_experts=256, num_experts_per_tok=8,
        moe_intermediate_size=2048, n_shared_experts=1,
        scoring_func="sigmoid", n_group=8, topk_group=4,
        routed_scaling_factor=2.5,
    ),
    # Tiny configs for tests / CPU-simulator runs.
    "tiny": dict(
        vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=2,
        num_q_heads=8, num_kv_heads=4, head_dim=32, max_length=128,
        dtype=jnp.float32,
    ),
    "tiny-moe": dict(
        vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=2,
        num_q_heads=8, num_kv_heads=4, head_dim=32, max_length=128,
        num_experts=8, num_experts_per_tok=2, moe_intermediate_size=64,
        dtype=jnp.float32,
    ),
    "tiny-mla-moe": dict(
        vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=3,
        num_q_heads=4, num_kv_heads=4, head_dim=48, rope_theta=1e4,
        max_length=256, q_lora_rank=48, kv_lora_rank=32,
        qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
        yarn_factor=40.0, yarn_original_max=64, yarn_mscale_all_dim=1.0,
        first_k_dense=1, num_experts=16, num_experts_per_tok=4,
        moe_intermediate_size=32, n_shared_experts=1,
        scoring_func="sigmoid", n_group=4, topk_group=2,
        routed_scaling_factor=2.5, dtype=jnp.float32,
    ),
}


def get_config(model_name: str, **overrides) -> ModelConfig:
    if model_name not in _PRESETS:
        raise ValueError(
            f"unknown model {model_name!r}; presets: {sorted(_PRESETS)}"
        )
    fields = dict(_PRESETS[model_name])
    fields.update(overrides)
    return ModelConfig(model_name=model_name, **fields)
