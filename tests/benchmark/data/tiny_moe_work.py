"""Operations and bytes the sparse-expert decoder's arithmetic requires,
from its shapes alone: the contract `benchmark/work.py` states, for a
configuration with ``num_experts`` experts of width
``moe_intermediate_size``, ``num_experts_per_tok`` of them a token. A
token is multiplied by the attention weights, the router and its own
experts; a step has to READ at least one token's experts (all its rows
may agree), which is what the least time counts."""

from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float32": 4}


def _dims(c: dict) -> tuple:
    return (c["hidden_size"], c["moe_intermediate_size"],
            c["num_attention_heads"], c["num_key_value_heads"],
            c["head_dim"], c["num_hidden_layers"], c["vocab_size"],
            c["num_experts"], c["num_experts_per_tok"])


def attn_params(c: dict) -> int:
    d, _, hq, hkv, hd, *_ = _dims(c)
    return d * (hq + 2 * hkv) * hd + hq * hd * d


def token_matmul_params(c: dict) -> int:
    """Weights one token is multiplied by: attention, router, its own
    experts, in every layer, and the head."""
    d, f, _, _, _, layers, vocab, experts, k = _dims(c)
    return layers * (attn_params(c) + d * experts + k * 3 * d * f) + d * vocab


def _bytes(c: dict) -> int:
    return DTYPE_BYTES[c.get("torch_dtype", "bfloat16")]


def kv_bytes_per_token(c: dict) -> int:
    _, _, _, hkv, hd, layers, *_ = _dims(c)
    return 2 * layers * hkv * hd * _bytes(c)


def _attn_flops(c: dict, context: int) -> int:
    _, _, hq, _, hd, layers, *_ = _dims(c)
    return 4 * layers * hq * hd * context


def decode_flops(c: dict, tokens: int, context_sum: int) -> int:
    return 2 * token_matmul_params(c) * tokens + _attn_flops(c, context_sum)


def prefill_flops(c: dict, prompt_lens) -> int:
    d, *_, vocab, _, _ = _dims(c)
    per_tok = 2 * (token_matmul_params(c) - d * vocab)
    return sum(per_tok * n + _attn_flops(c, n * (n + 1) // 2) + 2 * d * vocab
               for n in prompt_lens)


def _least(c, programs, kv_tokens, flops, peak, chips) -> tuple:
    nbytes = (programs * token_matmul_params(c) * _bytes(c)
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
