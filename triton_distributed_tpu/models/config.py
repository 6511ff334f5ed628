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
    # The layer list, DECLARED: one kind a layer, ``"attention"`` or
    # ``"mamba"`` (a Mamba-2 mixer over a per-slot recurrent state);
    # empty = every layer is an attention layer. The scan groups of the
    # serving programs follow from it (``models/hybrid_ssm.py``).
    layer_types: tuple = ()
    # The Mamba-2 mixer: ``mamba_n_heads`` heads of ``mamba_d_head``
    # (together ``mamba_expand x hidden_size``), a state of
    # ``mamba_d_state`` a head and channel, ``mamba_n_groups`` groups of
    # B / C shared by their heads, a causal depthwise convolution of
    # ``mamba_d_conv`` taps, prefill in blocks of ``mamba_chunk_size``.
    mamba_n_heads: int = 0
    mamba_d_head: int = 0
    mamba_d_state: int = 0
    mamba_d_conv: int = 4
    mamba_n_groups: int = 1
    mamba_chunk_size: int = 256
    # What an attention layer does beside its dot products, and the
    # stream's scalar multipliers (Qwen3: rotary, ``head_dim ** -0.5``,
    # all ones; its per-head q/k norm is on where the params carry the
    # scales). ``attention_multiplier`` 0 = the default scale.
    rope: bool = True
    attention_multiplier: float = 0.0
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    # runtime
    max_length: int = 4096
    dtype: jnp.dtype = jnp.bfloat16
    # Paged-KV storage width: None stores the pool in ``dtype``
    # (bit-identical legacy layout); "int8" stores int8 codes plus
    # symmetric per-page-per-head scales, dequantized inside the
    # attention kernels (docs/serving.md "Quantized KV cache").
    kv_dtype: str | None = None

    @property
    def mamba_layers(self) -> int:
        return sum(t == "mamba" for t in self.layer_types)

    @property
    def attention_layers(self) -> int:
        """Layers that cache rows in the paged pool."""
        return self.num_layers - self.mamba_layers

    @property
    def pool_row_dim(self) -> int:
        """Width of a K/V row in the paged pool: ``head_dim``, but a
        64-wide head is held padded to the TPU's 128 lanes with zero
        columns. Left at 64, XLA stores the pool's rows transposed at
        every program's entry and exit and copies the whole pool to and
        from the layout the kernels read (a described compile: four
        pool-sized copies a program); the padding costs the pool's
        bytes twice and changes no score (``tp_attn._to_pool``)."""
        return 128 if self.head_dim == 64 else self.head_dim

    @property
    def slot_keeps(self) -> tuple:
        """What a decode slot holds between steps, the ONE statement
        the engine's refusals and the cache manager read:
        ``"kv_pages"`` (per-head K/V rows in the paged pool),
        ``"latent_rows"`` (one latent row a token in it) and
        ``"recurrent_state"`` (a fixed-size state a slot that is no
        pages: it cannot be appended to, truncated, shared by page,
        rolled back or, yet, exported)."""
        rows = "latent_rows" if self.kv_lora_rank else "kv_pages"
        return (rows, "recurrent_state") if self.mamba_layers else (rows,)


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
    # ibm-granite/granite-4.0-h-micro as published (model_type
    # granitemoehybrid, no routed experts): 36 Mamba-2 layers and GQA
    # attention at layers 5, 15, 25, 35, every layer with the same
    # SwiGLU; no positions (the recurrence orders the tokens), no q/k
    # norm, a stated softmax scale and four scalar multipliers. The
    # head is drawn apart from the embedding (random tied weights would
    # make every token predict itself).
    "ibm-granite/granite-4.0-h-micro": dict(
        vocab_size=100352, hidden_size=2048, intermediate_size=8192,
        num_layers=40, num_q_heads=32, num_kv_heads=8, head_dim=64,
        rope_theta=1e4, rms_eps=1e-5,
        layer_types=tuple(
            "attention" if i % 10 == 5 else "mamba" for i in range(40)),
        mamba_n_heads=64, mamba_d_head=64, mamba_d_state=128,
        mamba_d_conv=4, mamba_n_groups=1, mamba_chunk_size=256,
        rope=False, attention_multiplier=0.015625,
        embedding_multiplier=12.0, residual_multiplier=0.22,
        logits_scaling=8.0,
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
    "tiny-hybrid": dict(
        vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=7,
        num_q_heads=8, num_kv_heads=4, head_dim=16, rms_eps=1e-5,
        max_length=256,
        layer_types=("mamba", "mamba", "attention", "mamba", "mamba",
                     "attention", "mamba"),
        mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
        mamba_chunk_size=8, rope=False,
        attention_multiplier=0.0625, embedding_multiplier=12.0,
        residual_multiplier=0.22, logits_scaling=8.0, dtype=jnp.float32,
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
