"""Operations and bytes the model's arithmetic requires, from its
shapes alone. Kept with the benchmark: no program code is asked how
much work it did, only how many steps and tokens it ran.

These are the counts of the dense GQA decoder. The contract a
configuration's work module keeps (its file names the module under
``"work"``; ``layerwork.flops`` and the roofline readers call these four
and nothing else, ``config`` being the configuration file as a dict and
``peak`` a ``peaks.Peak``):

``decode_flops(config, tokens, context_sum)``
``prefill_flops(config, prompt_lens)``
``decode_least_seconds(config, steps, tokens, context_sum, peak, chips=1)``
``prefill_least_seconds(config, prompt_lens, chunks, peak, chips=1)``
    the last two return ``(seconds, "memory" | "compute")``.
"""

from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float32": 4}


def _dims(config: dict) -> tuple:
    d, f = config["hidden_size"], config["intermediate_size"]
    hq, hkv = config["num_attention_heads"], config["num_key_value_heads"]
    hd = config.get("head_dim") or d // hq
    return (d, f, hq, hkv, hd, config["num_hidden_layers"],
            config["vocab_size"])


def layer_matmul_params(config: dict) -> int:
    """Matmul weights of one decoder layer: q, k, v, o, gate, up, down."""
    d, f, hq, hkv, hd, _, _ = _dims(config)
    return d * (hq + 2 * hkv) * hd + hq * hd * d + 3 * d * f


def matmul_params(config: dict) -> int:
    """Every weight a token is multiplied by: the layers and the output
    head. The embedding table is a gather of one row, not a matmul."""
    d, _, _, _, _, layers, vocab = _dims(config)
    return layers * layer_matmul_params(config) + d * vocab


def total_params(config: dict) -> int:
    """As published: the embedding counted once more unless tied."""
    d, _, _, _, hd, layers, vocab = _dims(config)
    norms = layers * (2 * d + 2 * hd) + d
    emb = 0 if config.get("tie_word_embeddings") else d * vocab
    # matmul_params counts one [d, V] table (the head, or the tied one).
    return matmul_params(config) + emb + norms


def weight_bytes(config: dict) -> int:
    """Bytes a decode step has to read once: every matmul weight."""
    return matmul_params(config) * DTYPE_BYTES[config.get("torch_dtype",
                                                          "bfloat16")]


def kv_bytes_per_token(config: dict) -> int:
    _, _, _, hkv, hd, layers, _ = _dims(config)
    return 2 * layers * hkv * hd * DTYPE_BYTES[config.get("torch_dtype",
                                                          "bfloat16")]


def attn_flops(config: dict, context: int) -> int:
    """QK^T and PV for ONE query token over ``context`` keys."""
    _, _, hq, _, hd, layers, _ = _dims(config)
    return 4 * layers * hq * hd * context


def decode_flops(config: dict, tokens: int, context_sum: int) -> int:
    """``tokens`` decoded tokens whose contexts add up to ``context_sum``."""
    return 2 * matmul_params(config) * tokens + attn_flops(config, context_sum)


def prefill_flops(config: dict, prompt_lens) -> int:
    """Whole prompts under a causal mask: token i attends to i + 1 keys.
    The output head runs for the last position only."""
    d, _, _, _, _, layers, vocab = _dims(config)
    per_tok = 2 * layers * layer_matmul_params(config)
    total = 0
    for n in prompt_lens:
        total += per_tok * n + attn_flops(config, n * (n + 1) // 2)
        total += 2 * d * vocab
    return total


def decode_least_seconds(config: dict, steps: int, tokens: int,
                         context_sum: int, peak, chips: int = 1) -> tuple:
    """The least time ``steps`` decode steps can take on ``chips`` chips
    that share every layer: each step reads every weight once and the
    live cache of the tokens it decodes. Returns (seconds, bound)."""
    nbytes = steps * weight_bytes(config) + context_sum * kv_bytes_per_token(
        config)
    t_mem = nbytes / (peak.hbm_bytes_per_s * chips)
    t_cmp = decode_flops(config, tokens, context_sum) / (
        peak.flops_bf16 * chips)
    return (t_mem, "memory") if t_mem >= t_cmp else (t_cmp, "compute")


def prefill_least_seconds(config: dict, prompt_lens, chunks: int, peak,
                          chips: int = 1) -> tuple:
    """The same for prefill: every chunk program reads the weights once."""
    nbytes = chunks * weight_bytes(config) + sum(prompt_lens) * (
        kv_bytes_per_token(config))
    t_mem = nbytes / (peak.hbm_bytes_per_s * chips)
    t_cmp = prefill_flops(config, prompt_lens) / (peak.flops_bf16 * chips)
    return (t_mem, "memory") if t_mem >= t_cmp else (t_cmp, "compute")
