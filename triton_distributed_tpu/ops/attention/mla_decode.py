"""Absorbed latent-attention decode straight over a paged latent pool.

Latent attention (DeepSeek-V3's MLA) caches ONE row a token a layer:
``kv_rank`` normed latents ``c_kv`` and ``rope`` rotary dims ``k_rope``
shared by every head. In the absorbed form a head's query is carried
into the latent space (``q_lat_h = q_nope_h (W_kvb^K_h)^T``), so that

    score_h = s (q_lat_h . c_kv + q_rope_h . k_rope)
    o_lat_h = softmax(score_h) c_kv

and the caller maps ``o_lat_h`` back through ``W_kvb^V_h``: every head
reads the SAME row, whose first ``kv_rank`` columns are also the values.

The kernel walks PR 33's grid (:func:`paged_decode_walk`: one dynamic
axis of live (slot, page) pairs, the softmax online across a slot's
pages in VMEM): a step fetches one latent page, ``[page, kv_rank]`` and
its rotary keys ``[rope, page]`` (that pool is kept TRANSPOSED, the page
axis on the lanes: a 64-wide row would be padded to 128 lanes in HBM),
through the page table and runs all heads against it,
the values read from the page already in VMEM. At 128 heads x (512 +
64) a step is 35.7 MFLOP over 144 KB: 242 FLOP a byte, the v5e's ridge.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_distributed_tpu.ops.attention.flash_decode import (
    paged_decode_walk,
    pages_to_dense,
)
from triton_distributed_tpu.ops.common import exporting_portable, interpret_mode

_NEG_INF = -1e30


def _mla_decode_kernel(
    kv_len_ref,  # [B] int32 SMEM (scalar prefetch)
    table_ref,   # [B, pps] int32 SMEM: consumed by the index maps
    slot_ref,    # [B * pps] int32 SMEM: the walk: sequence of step i
    page_ref,    # [B * pps] int32 SMEM: ... and its table entry
    ql_ref,      # [1, H, kv_rank]: queries in the latent space
    qr_ref,      # [1, H, rope]: their rotary part
    c_ref,       # [1, 1, page, kv_rank]: the page's latents (keys AND values)
    r_ref,       # [1, 1, rope, page]: the page's shared rotary keys
    o_ref,       # [1, H, kv_rank]
    m_ref, l_ref, acc_ref,  # VMEM f32 [H, 1], [H, 1], [H, kv_rank]
    *,
    sm_scale: float,
):
    i = pl.program_id(0)
    b, ci = slot_ref[i], page_ref[i]
    pps = table_ref.shape[1]
    page = c_ref.shape[2]
    valid = kv_len_ref[b] - ci * page  # keys of this page that count

    @pl.when(ci == 0)
    def _first_page():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(valid > 0)
    def _accumulate():
        dt = jnp.promote_types(ql_ref.dtype, c_ref.dtype)
        c = c_ref[0, 0].astype(dt)  # [page, kv_rank]
        contract_last = (((1,), (1,)), ((), ()))
        s = jax.lax.dot_general(
            ql_ref[0].astype(dt), c, contract_last,
            preferred_element_type=jnp.float32,
        ) + jnp.dot(
            qr_ref[0].astype(dt), r_ref[0, 0].astype(dt),
            preferred_element_type=jnp.float32,
        )
        cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(cols < valid, s * sm_scale, _NEG_INF)  # [H, page]
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        m_ref[...] = m_new
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            p.astype(dt), c, preferred_element_type=jnp.float32
        )

    # The sequence's last live page (or its table's last entry).
    @pl.when((valid <= page) | (ci == pps - 1))
    def _last_page():
        l = jnp.maximum(l_ref[...], 1e-30)  # an empty row reads 0
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def rope_pages_to_dense(r_pages, page_table, layer=None):
    """The transposed rotary pool ``[(L,) P, 1, rope, page]`` gathered
    through ``page_table [B, pps]`` into rows ``[B, pps * page, rope]``."""
    g = (r_pages[page_table] if layer is None
         else r_pages[layer, page_table])[:, :, 0]  # [B, pps, rope, page]
    b, pps, rope, page = g.shape
    return g.transpose(0, 1, 3, 2).reshape(b, pps * page, rope)


def mla_decode_reference(q_lat, q_rope, c_pages, r_pages, page_table, kv_len,
                         *, sm_scale: float):
    """The plain absorbed formula over a one-layer pool: the golden of
    :func:`mla_paged_decode` and its portable-export path."""
    c = pages_to_dense(c_pages, page_table)[:, 0].astype(jnp.float32)
    r = rope_pages_to_dense(r_pages, page_table).astype(jnp.float32)
    s = (jnp.einsum("bhc,bsc->bhs", q_lat.astype(jnp.float32), c)
         + jnp.einsum("bhr,bsr->bhs", q_rope.astype(jnp.float32), r))
    live = jnp.arange(c.shape[1])[None, None, :] < kv_len[:, None, None]
    p = jax.nn.softmax(jnp.where(live, s * sm_scale, _NEG_INF), axis=-1)
    return jnp.einsum("bhs,bsc->bhc", p, c).astype(q_lat.dtype)


def mla_paged_decode(
    q_lat: jax.Array,    # [B, H, kv_rank]
    q_rope: jax.Array,   # [B, H, rope]
    c_pages: jax.Array,  # [L, P, 1, page, kv_rank] whole pool, or 4-D
    r_pages: jax.Array,  # [L, P, 1, rope, page]: transposed
    page_table: jax.Array,  # [B, pages_per_seq] int32
    kv_len: jax.Array,      # [B] int32: valid context length
    *,
    sm_scale: float,
    layer: jax.Array | int | None = None,  # which layer of a 5-D pool
    walk=None,  # paged_decode_walk(kv_len, page, pages_per_seq), hoisted
    interpret=None,
) -> jax.Array:
    """Single-token absorbed latent attention over the paged latent
    pool: ``o_lat [B, H, kv_rank]``, the softmax-weighted latents of
    each sequence. The pool is addressed in place by (layer, page) as
    :func:`paged_flash_decode` does it: the layer rides in the table,
    ``table + layer * P``, over the pool seen as ``[L * P, ...]``."""
    b, h, rank = q_lat.shape
    rope = q_rope.shape[-1]
    if (c_pages.ndim == 5) != (layer is not None):
        raise ValueError(
            "a 5-D pool needs layer=, a 4-D one-layer pool takes none "
            f"(pool rank {c_pages.ndim}, layer {layer!r})"
        )
    if layer is not None:
        n_layers, p = c_pages.shape[:2]
        page_table = page_table + jnp.asarray(layer, jnp.int32) * p
        c_pages, r_pages = (
            a.reshape(n_layers * p, *a.shape[2:]) for a in (c_pages, r_pages)
        )
    page = c_pages.shape[2]
    pps = page_table.shape[1]
    kv_len = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32), (b,))
    resolved = interpret_mode() if interpret is None else interpret
    if resolved and exporting_portable():
        return mla_decode_reference(
            q_lat, q_rope, c_pages, r_pages, page_table, kv_len,
            sm_scale=sm_scale,
        )
    slot, page_of, steps = (
        paged_decode_walk(kv_len, page, pps) if walk is None else walk
    )

    def of_slot(i, _, tab, slot, page_of):
        return slot[i], 0, 0

    def of_page(i, _, tab, slot, page_of):
        return tab[slot[i], page_of[i]], 0, 0, 0

    return pl.pallas_call(
        functools.partial(_mla_decode_kernel, sm_scale=sm_scale),
        name="tdt_mla_decode_paged",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(steps,),
            in_specs=[
                pl.BlockSpec((1, h, rank), of_slot),
                pl.BlockSpec((1, h, rope), of_slot),
                pl.BlockSpec((1, 1, page, rank), of_page),
                pl.BlockSpec((1, 1, rope, page), of_page),
            ],
            out_specs=pl.BlockSpec((1, h, rank), of_slot),
            scratch_shapes=[
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, rank), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, rank), q_lat.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=resolved,
    )(kv_len, page_table, slot, page_of, q_lat, q_rope, c_pages, r_pages)
