"""Operations and bytes the arithmetic of one rank's share of the
DeepSeek-V3 language block requires, from its shapes alone: the contract
``benchmark/work.py`` states, for a configuration with latent attention
(``q_lora_rank``, ``kv_lora_rank``, ``qk_nope_head_dim`` +
``qk_rope_head_dim``, ``v_head_dim``), ``first_k_dense_replace`` leading
dense layers and expert layers of which this rank holds
``n_routed_experts`` of the router's ``published.n_routed_experts``.

FLOPs count the ABSORBED form of attention for decode (a query meets a
context token's latent row: ``2 H (kv_rank + rope)`` for the score and
``2 H kv_rank`` for the value) and the EXPANDED form for prefill (``2 H
(nope + rope + v)`` a pair under the causal mask), and a token's routed
experts held here at their expectation, ``num_experts_per_tok x held /
experts`` (0.5 at the served cut).

The least time of a decode step counts the bytes NO routing can avoid:
the attention weights, the dense ffn, the routers, the shared experts,
the head's columns, and the latent rows of the decoded tokens' contexts;
and NO routed expert: a step's tokens may choose none that is held
here, so a share of this roofline cannot read over 100% by the luck of
a routing. (A deployment's rank is sent rows for all its experts; what
share of them this cell's rows touch is ``model.experts_touched_share``.)
"""

from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float32": 4}


def _bytes(c: dict) -> int:
    return DTYPE_BYTES[c.get("torch_dtype", "bfloat16")]


def _layers(c: dict) -> tuple:
    dense = c["first_k_dense_replace"]
    return dense, c["num_hidden_layers"] - dense


def router_width(c: dict) -> int:
    return (c.get("published") or {}).get("n_routed_experts",
                                          c["n_routed_experts"])


def attn_params(c: dict) -> int:
    """q_a, q_b, kv_a, kv_b (its key and value columns), o of a layer."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    return (d * c["q_lora_rank"] + c["q_lora_rank"] * h * qk
            + d * (c["kv_lora_rank"] + c["qk_rope_head_dim"])
            + c["kv_lora_rank"] * h * (c["qk_nope_head_dim"]
                                       + c["v_head_dim"])
            + h * c["v_head_dim"] * d)


def expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def dense_ffn_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def router_params(c: dict) -> int:
    return c["hidden_size"] * router_width(c)


def held_params(c: dict) -> int:
    """Every matmul weight this rank holds: the layers with the held
    experts and the shared one, the embedding rows and the head."""
    dense, sparse = _layers(c)
    per_expert_layer = (attn_params(c) + router_params(c)
                        + (c["n_routed_experts"] + c["n_shared_experts"])
                        * expert_params(c))
    return (dense * (attn_params(c) + dense_ffn_params(c))
            + sparse * per_expert_layer
            + 2 * c["hidden_size"] * c["vocab_size"])


def unavoidable_params(c: dict) -> int:
    """What a step reads whatever the routing: everything of
    :func:`held_params` but the routed experts and the embedding table
    (a gather of one row a token)."""
    dense, sparse = _layers(c)
    return (dense * (attn_params(c) + dense_ffn_params(c))
            + sparse * (attn_params(c) + router_params(c)
                        + c["n_shared_experts"] * expert_params(c))
            + c["hidden_size"] * c["vocab_size"])


def token_matmul_params(c: dict) -> float:
    """Weights one token is multiplied by, its routed experts held here
    at their expectation."""
    _, sparse = _layers(c)
    routed = (c["num_experts_per_tok"] * c["n_routed_experts"]
              / router_width(c))
    return unavoidable_params(c) + sparse * routed * expert_params(c)


def kv_bytes_per_token(c: dict) -> int:
    """One latent row a layer: ``kv_lora_rank + qk_rope_head_dim``."""
    return (c["num_hidden_layers"]
            * (c["kv_lora_rank"] + c["qk_rope_head_dim"]) * _bytes(c))


def _absorbed_flops(c: dict, context: int) -> int:
    h = c["num_attention_heads"]
    return (2 * c["num_hidden_layers"] * h * context
            * (2 * c["kv_lora_rank"] + c["qk_rope_head_dim"]))


def _expanded_flops(c: dict, pairs: int) -> int:
    h = c["num_attention_heads"]
    return (2 * c["num_hidden_layers"] * h * pairs
            * (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
               + c["v_head_dim"]))


def decode_flops(c: dict, tokens: int, context_sum: int) -> float:
    return (2 * token_matmul_params(c) * tokens
            + _absorbed_flops(c, context_sum))


def prefill_flops(c: dict, prompt_lens) -> float:
    head = c["hidden_size"] * c["vocab_size"]
    per_tok = 2 * (token_matmul_params(c) - head)
    return sum(per_tok * n + _expanded_flops(c, n * (n + 1) // 2) + 2 * head
               for n in prompt_lens)


def _least(c, programs, kv_tokens, flops, peak, chips) -> tuple:
    nbytes = (programs * unavoidable_params(c) * _bytes(c)
              + kv_tokens * kv_bytes_per_token(c))
    t_mem = nbytes / (peak.hbm_bytes_per_s * chips)
    t_cmp = flops / (peak.flops_bf16 * chips)
    return (t_mem, "memory") if t_mem >= t_cmp else (t_cmp, "compute")


def decode_least_seconds(c: dict, steps: int, tokens: int, context_sum: int,
                         peak, chips: int = 1) -> tuple:
    return _least(c, steps, context_sum, decode_flops(c, tokens, context_sum),
                  peak, chips)


def prefill_least_seconds(c: dict, prompt_lens, chunks: int, peak,
                          chips: int = 1) -> tuple:
    return _least(c, chunks, sum(prompt_lens), prefill_flops(c, prompt_lens),
                  peak, chips)


def mla_decode_least_seconds(c: dict, context_sum: int, peak,
                             chips: int = 1) -> tuple:
    """The absorbed decode kernel alone over decoded tokens whose
    contexts add up to ``context_sum``: their latent rows over the
    memory bandwidth, the absorbed FLOPs over the peak, the larger."""
    t_mem = context_sum * kv_bytes_per_token(c) / (peak.hbm_bytes_per_s
                                                   * chips)
    t_cmp = _absorbed_flops(c, context_sum) / (peak.flops_bf16 * chips)
    return (t_mem, "memory") if t_mem >= t_cmp else (t_cmp, "compute")
