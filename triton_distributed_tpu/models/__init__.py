"""Models + serving (parity: reference ``python/triton_dist/models/``).

``AutoLLM`` mirrors ``models/__init__.py:32-48`` — dispatch by model
name/config to Qwen3 dense or MoE, to the latent-attention expert
model (``LatentMoE``, presets with a ``kv_lora_rank``) or to the hybrid
of Mamba-2 and attention layers (``HybridSSM``, presets whose
``layer_types`` declares recurrent layers), loading HF weights when a checkpoint
directory is given and random-initializing otherwise (the reference's
perf scripts also run on random weights).
"""

from __future__ import annotations

import json
import os

import jax

from triton_distributed_tpu.models.config import ModelConfig, get_config  # noqa: F401
from triton_distributed_tpu.models.continuous import (  # noqa: F401
    ContinuousEngine,
    Request,
    RequestError,
    RequestFailedError,
    RequestResult,
)
from triton_distributed_tpu.models.engine import Engine  # noqa: F401
from triton_distributed_tpu.models.paged_kv_cache import (  # noqa: F401
    PoolAuditError,
    audit_pool,
)
from triton_distributed_tpu.models.kv_cache import KVCache, init_cache  # noqa: F401
from triton_distributed_tpu.models.prefix_cache import (  # noqa: F401
    PrefixCache,
)
from triton_distributed_tpu.models.speculative import (  # noqa: F401
    NGramDraft,
    SpecState,
)
from triton_distributed_tpu.models.qwen import (  # noqa: F401
    Qwen3,
    Qwen3Params,
    load_hf_state_dict,
)
from triton_distributed_tpu.runtime.mesh import DistContext, current_context


class AutoLLM:
    """Parity: ``AutoLLM.from_pretrained`` (reference
    ``models/__init__.py:32-48``)."""

    @staticmethod
    def from_pretrained(
        name_or_path: str,
        *,
        ctx: DistContext | None = None,
        axis: str = "tp",
        seed: int = 0,
        **overrides,
    ) -> Qwen3:
        ctx = ctx or current_context()
        if os.path.isdir(name_or_path):
            cfg, state = _load_hf_checkpoint(name_or_path, **overrides)
            n = ctx.axis_size(axis)
            if cfg.num_experts:
                from triton_distributed_tpu.models.qwen_moe import (
                    Qwen3MoE,
                    load_hf_moe_state_dict,
                )

                model = Qwen3MoE(cfg, axis=axis, ctx=ctx)
                model.set_params(load_hf_moe_state_dict(cfg, state, n))
                return model
            model = Qwen3(cfg, axis=axis, ctx=ctx)
            model.set_params(load_hf_state_dict(cfg, state, n))
            return model
        cfg = get_config(name_or_path, **overrides)
        if cfg.mamba_layers:
            from triton_distributed_tpu.models.hybrid_ssm import HybridSSM

            model = HybridSSM(cfg, axis=axis, ctx=ctx)
        elif cfg.kv_lora_rank:
            from triton_distributed_tpu.models.latent_moe import LatentMoE

            model = LatentMoE(cfg, axis=axis, ctx=ctx)
        elif cfg.num_experts:
            from triton_distributed_tpu.models.qwen_moe import Qwen3MoE

            model = Qwen3MoE(cfg, axis=axis, ctx=ctx)
        else:
            model = Qwen3(cfg, axis=axis, ctx=ctx)
        model.init_params(jax.random.key(seed))
        return model


def _load_hf_checkpoint(path: str, **overrides):
    """Read config.json + *.safetensors from a local HF checkpoint dir."""
    with open(os.path.join(path, "config.json")) as f:
        hf = json.load(f)
    cfg = ModelConfig(
        model_name=hf.get("_name_or_path", path),
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        num_layers=hf["num_hidden_layers"],
        num_q_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"],
        head_dim=hf.get(
            "head_dim", hf["hidden_size"] // hf["num_attention_heads"]
        ),
        rope_theta=hf.get("rope_theta", 1e6),
        rms_eps=hf.get("rms_norm_eps", 1e-6),
        tie_word_embeddings=hf.get("tie_word_embeddings", False),
        # MoE checkpoints (Qwen3MoeForCausalLM): presence of experts in
        # the config routes to the MoE model + state-dict mapper.
        num_experts=hf.get("num_experts", 0),
        num_experts_per_tok=hf.get("num_experts_per_tok", 0),
        moe_intermediate_size=hf.get("moe_intermediate_size", 0),
        # Follow the checkpoint (HF default is FALSE; official Qwen3-MoE
        # releases set it true) — assuming true silently renormalizes
        # router weights upstream leaves unnormalized.
        norm_topk_prob=hf.get("norm_topk_prob", False),
        **overrides,
    )
    if hf.get("num_experts", 0):
        # Interleaved dense/sparse layers (decoder_sparse_step > 1 or
        # mlp_only_layers) store real dense MLP weights for some layers;
        # the uniform-sparse mapper would clobber them with placeholders
        # and then fail on a cryptic missing-router KeyError. Refuse
        # loudly instead. (The shipped Qwen3-MoE checkpoints are
        # uniformly sparse: step=1, mlp_only_layers=[].)
        step = hf.get("decoder_sparse_step", 1)
        dense_layers = hf.get("mlp_only_layers") or []
        if step != 1 or dense_layers:
            raise NotImplementedError(
                "interleaved dense/sparse MoE checkpoints are not "
                f"supported (decoder_sparse_step={step}, "
                f"mlp_only_layers={dense_layers}); only uniformly "
                "sparse Qwen3-MoE layouts load"
            )
    from safetensors import safe_open

    state = {}
    for fname in sorted(os.listdir(path)):
        if fname.endswith(".safetensors"):
            with safe_open(os.path.join(path, fname), framework="np") as f:
                for key in f.keys():
                    state[key] = f.get_tensor(key)
    if not state:
        raise FileNotFoundError(f"no .safetensors files under {path}")
    return cfg, state
