"""Operations and bytes the arithmetic of a hybrid of Mamba-2 and GQA
attention layers requires, from its shapes alone: the contract
``benchmark/work.py`` states, for a configuration with ``layer_types``
(``mamba`` / ``attention`` a layer), the ``mamba_*`` widths and one
SwiGLU of ``shared_intermediate_size`` in every layer.

A decode step's least bytes: every matmul weight once a step (the
embedding table is a gather of one row), the recurrent state of every
ADVANCED row read and written once a recurrent layer (``S [H, P, N]``
float32 and the convolution's ``taps - 1`` inputs: 76.4 MB a row at the
published widths, 153 MB moved), and the K/V rows of the decoded
tokens' contexts in the attention layers at their logical width (8 KB
a token; the program holds them padded to 16). The recurrence counts
``4 di N`` FLOPs a token a layer (the outer product into the state and
the read-out, a multiply and an add each); a prefill token, in the
chunked form at blocks of ``Q``, ``2 Q N + 2 Q di + 4 di N``.
"""

from __future__ import annotations

DTYPE_BYTES = {"bfloat16": 2, "float32": 4}


def _bytes(c: dict) -> int:
    return DTYPE_BYTES[c.get("torch_dtype", "bfloat16")]


def _kinds(c: dict) -> tuple:
    mamba = sum(k == "mamba" for k in c["layer_types"])
    return mamba, len(c["layer_types"]) - mamba


def _inner(c: dict) -> int:
    return c["mamba_n_heads"] * c["mamba_d_head"]


def _conv_dim(c: dict) -> int:
    return _inner(c) + 2 * c["mamba_n_groups"] * c["mamba_d_state"]


def _head_dim(c: dict) -> int:
    return c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]


def mamba_params(c: dict) -> int:
    """``W_in`` and ``W_out`` of a Mamba-2 layer."""
    d = c["hidden_size"]
    return (d * (_inner(c) + _conv_dim(c) + c["mamba_n_heads"])
            + _inner(c) * d)


def attn_params(c: dict) -> int:
    d, hd = c["hidden_size"], _head_dim(c)
    hq, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    return d * (hq + 2 * hkv) * hd + hq * hd * d


def mlp_params(c: dict) -> int:
    ffn = c.get("shared_intermediate_size") or c["intermediate_size"]
    return 3 * c["hidden_size"] * ffn


def matmul_params(c: dict) -> int:
    """Every weight a token is multiplied by: the layers and the head."""
    mamba, attn = _kinds(c)
    return (mamba * mamba_params(c) + attn * attn_params(c)
            + (mamba + attn) * mlp_params(c)
            + c["hidden_size"] * c["vocab_size"])


def ssm_state_bytes(c: dict) -> int:
    """``S`` of one row over all recurrent layers, float32."""
    return (_kinds(c)[0] * _inner(c) * c["mamba_d_state"] * 4)


def state_bytes_per_row(c: dict) -> int:
    """What a slot keeps whatever its context: ``S`` and the
    convolution's last ``taps - 1`` inputs, over all recurrent layers."""
    tail = (c["mamba_d_conv"] - 1) * _conv_dim(c) * _bytes(c)
    return ssm_state_bytes(c) + _kinds(c)[0] * tail


def kv_bytes_per_token(c: dict) -> int:
    """K and V rows of one token over the attention layers."""
    return (_kinds(c)[1] * 2 * c["num_key_value_heads"] * _head_dim(c)
            * _bytes(c))


def _recurrence_flops(c: dict, tokens: int) -> int:
    return _kinds(c)[0] * tokens * 4 * _inner(c) * c["mamba_d_state"]


def _attention_flops(c: dict, pairs: int) -> int:
    """QK^T and PV over ``pairs`` (query, key) pairs."""
    return (_kinds(c)[1] * pairs * 4 * c["num_attention_heads"]
            * _head_dim(c))


def decode_flops(c: dict, tokens: int, context_sum: int) -> float:
    return (2 * matmul_params(c) * tokens + _recurrence_flops(c, tokens)
            + _attention_flops(c, context_sum))


def prefill_flops(c: dict, prompt_lens) -> float:
    head = c["hidden_size"] * c["vocab_size"]
    q, n = c["mamba_chunk_size"], c["mamba_d_state"]
    chunked = _kinds(c)[0] * (2 * q * n + 2 * q * _inner(c)
                              + 4 * _inner(c) * n)
    per_tok = 2 * (matmul_params(c) - head) + chunked
    return sum(per_tok * s + _attention_flops(c, s * (s + 1) // 2) + 2 * head
               for s in prompt_lens)


def _least(nbytes, flops, peak, chips) -> tuple:
    t_mem = nbytes / (peak.hbm_bytes_per_s * chips)
    t_cmp = flops / (peak.flops_bf16 * chips)
    return (t_mem, "memory") if t_mem >= t_cmp else (t_cmp, "compute")


def decode_least_seconds(c: dict, steps: int, tokens: int, context_sum: int,
                         peak, chips: int = 1) -> tuple:
    nbytes = (steps * matmul_params(c) * _bytes(c)
              + tokens * 2 * state_bytes_per_row(c)
              + context_sum * kv_bytes_per_token(c))
    return _least(nbytes, decode_flops(c, tokens, context_sum), peak, chips)


def prefill_least_seconds(c: dict, prompt_lens, chunks: int, peak,
                          chips: int = 1) -> tuple:
    nbytes = (chunks * (matmul_params(c) * _bytes(c)
                        + 2 * state_bytes_per_row(c))
              + sum(prompt_lens) * kv_bytes_per_token(c))
    return _least(nbytes, prefill_flops(c, prompt_lens), peak, chips)


def ssm_decode_least_seconds(c: dict, rows: int, peak,
                             chips: int = 1) -> tuple:
    """The state kernel alone over ``rows`` advanced rows (each through
    every recurrent layer): ``S`` read and written once."""
    return _least(rows * 2 * ssm_state_bytes(c), _recurrence_flops(c, rows),
                  peak, chips)
