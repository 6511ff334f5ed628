"""MoE routing: top-k gating, expert-sort, weighted combine.

Parity: the reference's token sorting lives in CUDA
(``csrc/lib/moe_utils.cu:61-356`` ``moe_ag_scatter_align_block_size`` —
sorts topk token→expert assignments into block-aligned expert batches)
with a Triton reimpl (``threadblock_swizzle_ag_moe_triton.py``).

TPU design: XLA's sort is a first-class TPU op, so the sort/align is a
``jnp.argsort`` + ``bincount`` composition; grouped GEMM consumes the
``group_sizes`` vector directly (``jax.lax.ragged_dot``), no block
alignment pass needed — the alignment the CUDA kernel creates by hand is
what ragged_dot's tiling does internally.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class RouterOut(NamedTuple):
    expert_ids: jax.Array   # [T, k] int32
    weights: jax.Array      # [T, k] f32 — normalized gate weights


class SortedTokens(NamedTuple):
    order: jax.Array        # [T*k] — argsort of flattened expert ids
    token_ids: jax.Array    # [T*k] — source token per sorted slot
    expert_ids: jax.Array   # [T*k] — expert per sorted slot (ascending)
    weights: jax.Array      # [T*k] f32 — gate weight per sorted slot
    group_sizes: jax.Array  # [E] int32 — tokens per expert


def router_topk(
    x: jax.Array,         # [T, d]
    w_router: jax.Array,  # [d, E]
    k: int,
    *,
    norm_topk_prob: bool = True,
) -> RouterOut:
    """Qwen3-MoE gate: softmax over all experts, take top-k, renormalize
    (HF ``norm_topk_prob``)."""
    logits = jnp.dot(
        x.astype(jnp.float32), w_router.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    probs = jax.nn.softmax(logits, axis=-1)
    weights, ids = jax.lax.top_k(probs, k)
    if norm_topk_prob:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return RouterOut(ids.astype(jnp.int32), weights)


def router_group_limited(
    x: jax.Array,         # [T, d]
    w_router: jax.Array,  # [d, E]
    bias: jax.Array,      # [E] f32: moves the choice, never the weight
    k: int,
    *,
    n_group: int,
    topk_group: int,
    route_scale: float = 1.0,
) -> RouterOut:
    """DeepSeek-V3 gate (``noaux_tc``): sigmoid scores over all experts
    in float32; the choice is made on ``score + bias``: a group's score
    is the sum of its two best, the best ``topk_group`` of ``n_group``
    groups are kept and the ``k`` best inside them chosen. Weights are
    the chosen experts' scores WITHOUT the bias, divided by their sum,
    times ``route_scale``."""
    logits = jnp.dot(
        x.astype(jnp.float32), w_router.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    scores = jax.nn.sigmoid(logits)
    t, e = scores.shape
    choice = (scores + bias.astype(jnp.float32)).reshape(t, n_group, -1)
    group_score = jnp.sum(jax.lax.top_k(choice, 2)[0], axis=-1)
    kept = jax.lax.top_k(group_score, topk_group)[1]        # [T, topk_group]
    keep = jnp.any(
        kept[:, :, None] == jnp.arange(n_group)[None, None, :], axis=1
    )
    choice = jnp.where(keep[:, :, None], choice, -jnp.inf).reshape(t, e)
    ids = jax.lax.top_k(choice, k)[1]
    weights = jnp.take_along_axis(scores, ids, axis=-1)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True) * route_scale
    return RouterOut(ids.astype(jnp.int32), weights)


def held_sort(route: RouterOut, offset: int, held: int) -> SortedTokens:
    """:func:`moe_sort` for a rank that holds experts ``[offset, offset
    + held)`` only: assignments to an expert held elsewhere are dropped
    before the sort (they sort past every held expert and no group
    counts them). ``expert_ids`` and ``group_sizes`` are local, ``[0,
    held)``; ``group_sizes`` sums to the kept rows, which come first."""
    local = route.expert_ids.reshape(-1) - offset
    local = jnp.where((local >= 0) & (local < held), local, held)
    k = route.expert_ids.shape[1]
    order = jnp.argsort(local, stable=True)
    return SortedTokens(
        order=order,
        token_ids=(order // k).astype(jnp.int32),
        expert_ids=local[order],
        weights=route.weights.reshape(-1)[order],
        group_sizes=jnp.bincount(local, length=held + 1)[:held].astype(
            jnp.int32),
    )


def moe_sort(route: RouterOut, num_experts: int) -> SortedTokens:
    """Sort (token, expert) assignments into expert-contiguous order
    (parity: the CUDA align kernel's output contract)."""
    flat_e = route.expert_ids.reshape(-1)
    flat_w = route.weights.reshape(-1)
    k = route.expert_ids.shape[1]
    order = jnp.argsort(flat_e, stable=True)
    return SortedTokens(
        order=order,
        token_ids=(order // k).astype(jnp.int32),
        expert_ids=flat_e[order],
        weights=flat_w[order],
        group_sizes=jnp.bincount(flat_e, length=num_experts).astype(jnp.int32),
    )


class AlignedBlocks(NamedTuple):
    """Block-aligned grouped-GEMM schedule (the CUDA align kernel's
    output contract, ``moe_utils.cu:61-193``)."""

    sorted_ids: jax.Array    # [cap] — slot → flattened source index; pad = N
    block_expert: jax.Array  # [bcap] — tile → expert id; past-end = -1
    num_blocks: jax.Array    # [] int32
    num_padded: jax.Array    # [] int32


def align_capacities(n: int, num_experts: int, block_size: int) -> tuple[int, int]:
    """Static worst-case output sizes: every expert padded by up to
    ``block_size - 1`` slots."""
    cap = n + num_experts * (block_size - 1)
    cap = (cap + block_size - 1) // block_size * block_size
    return cap, cap // block_size


def moe_align_block_size(
    expert_ids: jax.Array,  # [T, k] or [N] int32
    num_experts: int,
    block_size: int,
) -> AlignedBlocks:
    """Pure-JAX block-aligned expert sort (jit-safe, static shapes).

    Parity: ``moe_ag_scatter_align_block_size`` (``moe_utils.cu:61-356``).
    The native XLA-FFI/C++ variant with identical semantics lives in
    ``csrc/moe_utils.cc`` (host planning path); this composition is the
    on-device default — XLA sorts/scans are first-class TPU ops.
    """
    flat = expert_ids.reshape(-1).astype(jnp.int32)
    n = flat.shape[0]
    cap, bcap = align_capacities(n, num_experts, block_size)
    counts = jnp.bincount(flat, length=num_experts)
    padded = (counts + block_size - 1) // block_size * block_size
    start = jnp.cumsum(padded) - padded  # exclusive prefix
    order = jnp.argsort(flat, stable=True)
    es = flat[order]
    # Within-expert rank of each sorted slot = position - first slot of
    # that expert in plain sorted order.
    first_sorted = jnp.cumsum(counts) - counts
    within = jnp.arange(n) - first_sorted[es]
    dest = start[es] + within
    sorted_ids = jnp.full((cap,), n, jnp.int32).at[dest].set(
        order.astype(jnp.int32)
    )
    bounds = jnp.cumsum(padded) // block_size  # block-end per expert
    blk = jnp.arange(bcap)
    block_expert = jnp.searchsorted(bounds, blk, side="right").astype(jnp.int32)
    num_blocks = (jnp.sum(padded) // block_size).astype(jnp.int32)
    block_expert = jnp.where(blk < num_blocks, block_expert, -1)
    return AlignedBlocks(
        sorted_ids=sorted_ids,
        block_expert=block_expert,
        num_blocks=num_blocks,
        num_padded=jnp.sum(padded).astype(jnp.int32),
    )


def moe_combine(
    expert_out: jax.Array,  # [T*k, d] — per sorted slot
    sorted_tokens: SortedTokens,
    num_tokens: int,
) -> jax.Array:
    """Weighted scatter-add back to token order → [T, d] (parity: the
    gather-topk-reduce stage of ``moe_reduce_rs.py:293``)."""
    weighted = expert_out.astype(jnp.float32) * sorted_tokens.weights[:, None]
    out = jnp.zeros((num_tokens, expert_out.shape[1]), jnp.float32)
    out = out.at[sorted_tokens.token_ids].add(weighted)
    return out.astype(expert_out.dtype)
