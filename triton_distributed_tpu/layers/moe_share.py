"""One expert-parallel rank's share of a DeepSeek-V3 expert layer.

The layer is ``y = sum_chosen w_e FFN_e(x) + FFN_shared(x)``: a
group-limited sigmoid router (:func:`router_group_limited`) picks ``k``
of ALL the experts a token, and one shared expert runs on every token.
A rank is told which experts it holds, ``[offset, offset + held)``: the
router keeps its full width and its weights are normalised over all
``k`` chosen, held or not, and the layer returns the sum over the
experts that are chosen AND held, plus the shared expert. A prefill
chunk drops the assignments to experts held elsewhere before the sort
and runs ``grouped_ffn`` over the held experts' ``group_sizes``; a decode
step (``MoEShareDims.dense_rows``) reads, in place from the stacked
weights, only the held experts that one of its LIVE rows chose
(:func:`moe_decode_experts`). The shares of all ranks' routed parts plus
the shared expert once add up to the uncut layer
(``tests/test_moe_share.py``); nothing here stands in for the ranks
that are not there.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from triton_distributed_tpu.layers.tp_mlp import TPMLPParams, _silu_mul
from triton_distributed_tpu.ops.moe.decode_experts import (
    moe_decode_experts,
    touched_experts,
)
from triton_distributed_tpu.ops.moe.grouped_gemm import grouped_ffn
from triton_distributed_tpu.ops.moe.routing import (
    held_sort,
    moe_combine,
    router_group_limited,
)
from triton_distributed_tpu.runtime.pytree import register_param_dataclass


@dataclasses.dataclass
class MoEShareParams:
    w_router: jax.Array   # [d, E]: all experts
    bias: jax.Array       # [E] f32: e_score_correction_bias
    w1: jax.Array         # [held, d, 2 * f]: gate|up of the held experts
    w2: jax.Array         # [held, f, d]; both [layers, held, ...] with layer=
    shared: TPMLPParams   # the shared expert: w1 [d, 2 * fs], w2 [fs, d]


register_param_dataclass(
    MoEShareParams, ["w_router", "bias", "w1", "w2", "shared"])


@dataclasses.dataclass(frozen=True)
class MoEShareDims:
    top_k: int
    n_group: int
    topk_group: int
    route_scale: float
    offset: int
    held: int
    # Up to this many rows (a decode step's, a short chunk's) the held
    # experts are not sorted for: a kernel walks the experts that a live
    # row chose and reads each one's weights once, in place from the
    # stacked weights (88 MB an expert a layer at the served cut, 3 of
    # 16 touched a step in the cell). ``ragged_dot`` needs a layer's
    # experts copied out whole first (0.94 + 0.47 GB a layer: 17 of a 33
    # ms step on the v5e, PERF.md "PR 35"), and a wide chunk, which
    # touches them all and amortises that, keeps it.
    dense_rows: int = 64

    @classmethod
    def of(cls, cfg) -> "MoEShareDims":
        return cls(
            top_k=cfg.num_experts_per_tok, n_group=cfg.n_group,
            topk_group=cfg.topk_group,
            route_scale=cfg.routed_scaling_factor,
            offset=cfg.expert_offset,
            held=cfg.experts_held or cfg.num_experts,
        )


def swiglu(params: TPMLPParams, x: jax.Array) -> jax.Array:
    """A plain SwiGLU on every row, gate|up fused in ``w1``."""
    h = _silu_mul(
        jnp.dot(x, params.w1, preferred_element_type=jnp.float32).astype(
            x.dtype))
    return jnp.dot(h, params.w2, preferred_element_type=jnp.float32).astype(
        x.dtype)


def moe_share_fwd(params: MoEShareParams, x: jax.Array, dims: MoEShareDims,
                  live: jax.Array | None = None,
                  layer: jax.Array | int | None = None):
    """``x [T, d]`` to ``(y [T, d], counts [2] int32)``: this rank's
    share of the layer, and of the rows ``live [T]`` marks (all where
    None) how many were routed to a held expert and how many held
    experts got at least one of them. A decode step computes the routed
    part for its live rows alone (a dead row gets the shared expert
    only; nobody reads it), so ``counts[1]`` is also the number of
    experts whose weights the step read. With ``layer`` the routed
    experts' ``w1`` / ``w2`` are a group's stacked ``[layers, held,
    ...]``, whole, and ``layer`` the one meant: a layer scan closes over
    them, because as its ``xs`` they are sliced out for a kernel, a copy
    of the layer's experts a step."""
    t = x.shape[0]
    with jax.named_scope("moe_share"):
        route = router_group_limited(
            x, params.w_router, params.bias, dims.top_k,
            n_group=dims.n_group, topk_group=dims.topk_group,
            route_scale=dims.route_scale,
        )
        local = route.expert_ids - dims.offset
        held = (local >= 0) & (local < dims.held)
        if live is not None:
            held &= live[:, None]
        hits = jnp.zeros((dims.held + 1,), jnp.int32).at[
            jnp.where(held, local, dims.held)].add(1)[: dims.held]
        if t <= dims.dense_rows:
            gate = jnp.sum(
                jnp.where(
                    held[:, :, None]
                    & (local[:, :, None] == jnp.arange(dims.held)),
                    route.weights[:, :, None], 0.0),
                axis=1)  # [T, held]
            y = moe_decode_experts(
                x, gate, *touched_experts(hits > 0), params.w1, params.w2,
                layer=layer).astype(x.dtype)
        else:
            w1, w2 = params.w1, params.w2
            if layer is not None:
                w1, w2 = (jax.lax.dynamic_index_in_dim(w, layer, keepdims=False)
                          for w in (w1, w2))
            st = held_sort(route, dims.offset, dims.held)
            kept = st.expert_ids < dims.held
            h = grouped_ffn(x[st.token_ids], w1, w2, st.group_sizes)
            # Rows past the held experts' groups belong to no group:
            # their output is not the layer's, their weight is nought.
            st = st._replace(weights=jnp.where(kept, st.weights, 0.0))
            y = moe_combine(jnp.where(kept[:, None], h, 0), st, t)
        y = y + swiglu(params.shared, x)
        counts = jnp.stack([jnp.sum(hits), jnp.sum(hits > 0)]).astype(
            jnp.int32)
    return y, counts
