"""The plain reference of the program's `tiny-moe` preset: the dense
reference's attention block, and in the ffn's place a softmax top-k
router over ``num_experts`` SwiGLU experts (Hugging Face
``Qwen3MoeForCausalLM``: softmax over all experts, the best
``num_experts_per_tok`` taken and renormalised). Every expert is
computed for every token and weighted by the router's gate, nought for
the experts not picked: plain, and exact.

Weights are the seed's, by the dense recipe over twelve splits of the
seed's key: q, k, v, the experts' gate and up ``[L, E, d, f]``, the
embedding, o, the router ``[L, d, E]``, the experts' down ``[L, E, f,
d]`` and the head. It keeps the contract `benchmark/reference.py`
states, and is named by `tiny-moe.config.json` alone.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from benchmark import reference as dense


@dataclasses.dataclass(frozen=True)
class Dims:
    vocab: int
    d: int
    ffn: int      # one expert's width
    experts: int
    top_k: int
    renorm: bool
    layers: int
    hq: int
    hkv: int
    hd: int
    theta: float
    eps: float
    dtype: str

    @classmethod
    def of(cls, config: dict) -> "Dims":
        return cls(
            vocab=config["vocab_size"], d=config["hidden_size"],
            ffn=config["moe_intermediate_size"],
            experts=config["num_experts"],
            top_k=config["num_experts_per_tok"],
            renorm=bool(config.get("norm_topk_prob", True)),
            layers=config["num_hidden_layers"],
            hq=config["num_attention_heads"],
            hkv=config["num_key_value_heads"], hd=config["head_dim"],
            theta=float(config.get("rope_theta", 1e6)),
            eps=float(config.get("rms_norm_eps", 1e-6)),
            dtype=config.get("torch_dtype", "bfloat16"))


def _layout(m: Dims) -> dict:
    L, d, F, E = m.layers, m.d, m.ffn, m.experts
    return {
        "wq": (0, (L, d, m.hq * m.hd), None, 2),
        "wk": (1, (L, d, m.hkv * m.hd), None, 2),
        "wv": (2, (L, d, m.hkv * m.hd), None, 2),
        "gate": (3, (L, E, d, F), None, 3),
        "up": (4, (L, E, d, F), None, 3),
        "embed": (5, (m.vocab, d), 0.02, None),
        "wo": (6, (L, m.hq * m.hd, d), None, 1),
        "router": (7, (L, d, E), None, None),
        "w2": (8, (L, E, F, d), None, 2),
        "lm_head": (9, (d, m.vocab), None, 1),
    }


def make_weights(config, seed: int, devices) -> dict:
    m = Dims.of(config)
    return dense.draw(_layout(m), 12, m.dtype, seed, devices)


def _experts(m: Dims, mode: str, x, lw):
    """The routed ffn with its residual: x [B, S, d] float32."""
    h = dense._rms(x, m.eps)
    probs = jax.nn.softmax(dense._mm(h, lw["router"], "f32"), axis=-1)
    top, ids = jax.lax.top_k(probs, m.top_k)
    if m.renorm:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    gates = jnp.sum(jax.nn.one_hot(ids, m.experts) * top[..., None], axis=-2)
    for e in range(m.experts):
        act = (jax.nn.silu(dense._mm(h, lw["gate"][e], mode))
               * dense._mm(h, lw["up"][e], mode))
        x = x + gates[..., e, None] * dense._mm(act, lw["w2"][e], mode)
    return x


_LAYER_KEYS = ("wq", "wk", "wv", "wo", "router", "gate", "up", "w2")


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer_at(m, mode, x, stacked, l):
    lw = {k: jax.lax.dynamic_index_in_dim(stacked[k], l, 0, keepdims=False)
          for k in _LAYER_KEYS}
    return _experts(m, mode, dense.attention(m, mode, x, lw), lw)


def forward_logits(m: Dims, weights: dict, tokens, rows, cols, *,
                   mode: str = "f32") -> jax.Array:
    x = dense._embed(weights["embed"], jnp.asarray(tokens, jnp.int32))
    stacked = {k: weights[k] for k in _LAYER_KEYS}
    for l in range(m.layers):
        x = _layer_at(m, mode, x, stacked, jnp.int32(l))
    return dense._head(m, mode, x, jnp.asarray(rows, jnp.int32),
                       jnp.asarray(cols, jnp.int32), weights["lm_head"])


def judge(config, weights: dict, samples: list, pad_to: int, rows_pad: int,
          **kw) -> dict:
    m = Dims.of(config)
    return dense.judge_with(
        lambda tokens, rows, cols, mode: forward_logits(
            m, weights, tokens, rows, cols, mode=mode),
        samples, pad_to, rows_pad, **kw)
