"""Tensor-parallel attention (GQA + RoPE + optional QK-norm).

Parity: reference ``layers/nvidia/tp_attn.py`` — ``TP_Attn`` with fused
qkv ag_gemm, rotary, flash attention, o-proj gemm_rs
(``dist_triton_fwd``:203-271) and the AR decode path (local GEMMs +
flash-decode + all_reduce). Heads are sharded over the ``tp`` axis; each
device owns ``hq/n`` query heads and ``hkv/n`` KV heads with the full
sequence — the KV cache is therefore head-sharded, and decode needs no
cross-device attention (that is the SP decode layer's job).

Prefill activations are sequence-sharded between layers; the qkv
projection is the overlapped ag_gemm and the output projection the
overlapped gemm_rs, mirroring the reference's zero-exposed-comm prefill.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Literal

import jax
import jax.numpy as jnp

from triton_distributed_tpu.ops.attention.flash_attention import flash_attention
from triton_distributed_tpu.ops.attention.flash_decode import flash_decode
from triton_distributed_tpu.ops.attention.rope import apply_rope
from triton_distributed_tpu.ops.overlap.gemm_ar import gemm_ar
from triton_distributed_tpu.ops.overlap.ag_gemm import ag_gemm
from triton_distributed_tpu.ops.overlap.gemm_rs import gemm_rs
from triton_distributed_tpu.runtime.mesh import DistContext, current_context
from triton_distributed_tpu.runtime.pytree import register_param_dataclass

Mode = Literal["xla", "pallas", "pallas_ar", "xla_ar"]


@dataclasses.dataclass
class TPAttnParams:
    """Per-shard weights: ``wqkv [d, (hq_loc + 2*hkv_loc) * hd]``
    (q | k | v blocks), ``wo [hq_loc * hd, d]``, optional per-head RMS
    scales ``q_norm``/``k_norm`` ``[hd]`` (Qwen3)."""

    wqkv: jax.Array
    wo: jax.Array
    q_norm: jax.Array | None
    k_norm: jax.Array | None


register_param_dataclass(TPAttnParams, ["wqkv", "wo", "q_norm", "k_norm"])


def _rms_head(x: jax.Array, scale: jax.Array | None, eps: float = 1e-6):
    if scale is None:
        return x
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * scale.astype(jnp.float32)).astype(x.dtype)


@dataclasses.dataclass(frozen=True)
class TPAttnDims:
    """Static head geometry for the local shard."""
    hq_loc: int
    hkv_loc: int
    head_dim: int
    rope_theta: float | None = 1e6  # None: no positions at all
    sm_scale: float | None = None  # None: head_dim ** -0.5
    qk_norm_eps: float = 1e-6  # the per-head q/k RMS norm's

    @property
    def qkv_loc(self) -> int:
        return (self.hq_loc + 2 * self.hkv_loc) * self.head_dim

    def split_qkv(self, qkv: jax.Array):
        """``[..., qkv_loc] → q [..., hq_loc, hd], k/v [..., hkv_loc, hd]``."""
        hd = self.head_dim
        q, k, v = jnp.split(
            qkv, [self.hq_loc * hd, (self.hq_loc + self.hkv_loc) * hd], axis=-1
        )
        lead = qkv.shape[:-1]
        return (
            q.reshape(*lead, self.hq_loc, hd),
            k.reshape(*lead, self.hkv_loc, hd),
            v.reshape(*lead, self.hkv_loc, hd),
        )


def tp_attn_prefill(
    params: TPAttnParams,
    x: jax.Array,  # [s_loc, d] — sequence shard (batch folded upstream)
    dims: TPAttnDims,
    *,
    axis: str = "tp",
    mode: Mode = "pallas",
    ctx: DistContext | None = None,
):
    """Per-shard prefill forward (inside ``shard_map``).

    Returns ``(out [s_loc, d], k [hkv_loc, S, hd], v [hkv_loc, S, hd])``
    — k/v are the full-sequence local-head cache entries (parity:
    ``TP_Attn.dist_triton_fwd`` writing the KV cache, ``tp_attn.py:203``).
    """
    if mode == "pallas":
        qkv = ag_gemm(x, params.wqkv, axis=axis, ctx=ctx)  # [S, qkv_loc]
    else:
        full = jax.lax.all_gather(x, axis, axis=0, tiled=True)
        qkv = jnp.dot(
            full, params.wqkv, preferred_element_type=jnp.float32
        ).astype(x.dtype)
    s_full = qkv.shape[0]
    q, k, v = dims.split_qkv(qkv)  # [S, h, hd]
    q = _rms_head(q, params.q_norm, dims.qk_norm_eps)
    k = _rms_head(k, params.k_norm, dims.qk_norm_eps)
    pos = jnp.arange(s_full)
    q = apply_rope(q.swapaxes(0, 1), pos, dims.rope_theta)  # [h, S, hd]
    k = apply_rope(k.swapaxes(0, 1), pos, dims.rope_theta)
    v = v.swapaxes(0, 1)
    o = flash_attention(q[None], k[None], v[None], causal=True)[0]  # [h, S, hd]
    o_flat = o.swapaxes(0, 1).reshape(s_full, dims.hq_loc * dims.head_dim)
    o_flat = o_flat.astype(x.dtype)
    if mode == "pallas":
        out = gemm_rs(o_flat, params.wo, axis=axis, ctx=ctx)
    else:
        part = jnp.dot(o_flat, params.wo, preferred_element_type=jnp.float32)
        out = jax.lax.psum_scatter(
            part, axis, scatter_dimension=0, tiled=True
        ).astype(x.dtype)
    return out, k, v


# -- in-place writers of the paged pool -----------------------------------
#
# The pool ``[L, P, h, page, hd]`` rides a layer scan's carry whole
# (``Qwen3._scan_layers_paged``) and is only ever addressed by (layer,
# page): a scan that takes it as ``xs`` slices each layer's pool out,
# stacks it back and copies the stack onto the donated buffer, every
# step. Both writers are ``dynamic_update_slice``s, which XLA performs in
# place on a loop-carried buffer in its own layout. A ``scatter`` of rows
# ``.at[layer, pids, :, offs, :]`` into the carried pool is NOT: on the
# TPU it asks for a pool laid out ``[L, P, page, h, hd]`` and XLA
# re-lays the whole pool out before the loop, after it, and again for
# the kernel in every layer.


def _append_rows(pages, scales, rows, layer, pids, offs):
    """Decode append: ``rows [B, h, hd]``, one per sequence, land at
    ``(layer, pids[i], :, offs[i], :)`` — ``B`` single-row updates (an
    int8 pool's rows first go through the one scale protocol). Inactive
    slots all point at the trash page, where the last write wins.
    Returns ``(pages, scales)``."""
    if scales is not None:
        from triton_distributed_tpu.models.paged_kv_cache import quantize_rows

        pages, scales, rows = quantize_rows(
            pages, scales, rows, pids, offs, layer
        )
    for i in range(rows.shape[0]):
        pages = jax.lax.dynamic_update_slice(
            pages, rows[i][None, None, :, None, :].astype(pages.dtype),
            (layer, pids[i], 0, offs[i], 0),
        )
    return pages, scales


def _write_chunk(pages, scales, rows, layer, table_row, start, n_real=None):
    """Chunk write: ``rows [C, h, hd]`` are the CONTIGUOUS sequence
    positions ``start + i`` of the sequence whose pages ``table_row``
    lists, so they fill at most ``(C - 1) // page + 2`` consecutive
    table entries: each of those pages is read, merged with its rows
    and written back whole. Rows that fall off the table (final-chunk
    right-padding past capacity) go to the trash page (id 0) instead of
    letting a clamped index corrupt the last real page. On an int8 pool
    the rows past ``n_real`` (the chunk's right-padding) are kept out of
    the sequence's pages and their scales altogether: on a full-width
    pool pad KV is inert (overwritten/masked), but a quantized pad row
    would grow — or, at page offset 0, seed — its page's scale with
    garbage amax, permanently requantizing accepted history against rows
    that are not part of the sequence. Returns ``(pages, scales)``."""
    c, h, hd = rows.shape
    page = pages.shape[3]
    pps = table_row.shape[0]
    idx = jnp.arange(c, dtype=jnp.int32)
    n_write = c
    if scales is not None:
        from triton_distributed_tpu.models.paged_kv_cache import quantize_rows

        pos = start + idx
        real = (pos >= 0) & (pos < pps * page)
        if n_real is not None:
            n_write = n_real
            real &= idx < n_real
        pids = jnp.where(
            real, jnp.take(table_row, jnp.clip(pos // page, 0, pps - 1)), 0
        )
        pages, scales, rows = quantize_rows(
            pages, scales, rows, pids, jnp.where(real, pos % page, 0), layer
        )
    # Row r of table entry ``first + j`` is chunk row ``j*page + r -
    # start % page``: a page-long window of the chunk padded by a page
    # either side, masked to the rows the chunk really holds there.
    padded = jnp.pad(
        rows.astype(pages.dtype).swapaxes(0, 1),
        ((0, 0), (page, page), (0, 0)),
    )  # [h, page + C + page, hd]
    first = start // page
    shift = page - start % page
    r = jnp.arange(page, dtype=jnp.int32)
    for j in range((c + page - 2) // page + 1):
        entry = first + j
        src = j * page + r - (start % page)  # chunk row held by page row r
        mine = (src >= 0) & (src < n_write)
        on_table = (entry >= 0) & (entry < pps)
        pid = jnp.where(
            on_table, jnp.take(table_row, jnp.clip(entry, 0, pps - 1)), 0
        )
        at = (layer, pid, 0, 0, 0)
        old = jax.lax.dynamic_slice(pages, at, (1, 1, h, page, hd))
        new = jax.lax.dynamic_slice_in_dim(padded, j * page + shift, page, 1)
        merged = jnp.where(mine[:, None], new, old[0, 0])  # [h, page, hd]
        pages = jax.lax.dynamic_update_slice(pages, merged[None, None], at)
    return pages, scales


def _rotate(dims: TPAttnDims, x: jax.Array, pos: jax.Array) -> jax.Array:
    """Rotate-half RoPE at ``pos``, where the configuration has
    positions at all (``rope_theta``)."""
    if dims.rope_theta is None:
        return x
    return apply_rope(x, pos, dims.rope_theta)


def _to_pool(dims: TPAttnDims, pages: jax.Array, *heads: jax.Array):
    """``heads`` (each ``[..., head_dim]``) widened with zero columns to
    the pool's row width, and the softmax scale to hand the kernels
    with them. A pool row is ``head_dim`` wide except where the cache
    manager pads it to the TPU's 128 lanes
    (``ModelConfig.pool_row_dim``: XLA stores a 64-wide row transposed
    and re-lays the pool out around every program); zero columns add
    nothing to a score and come back as zero columns of the output, so
    the arithmetic is the same under the head's OWN scale."""
    pad = pages.shape[-1] - dims.head_dim
    if not pad:
        return (*heads, dims.sm_scale)
    scale = (dims.head_dim ** -0.5 if dims.sm_scale is None
             else dims.sm_scale)
    return (*(jnp.pad(h, [(0, 0)] * (h.ndim - 1) + [(0, pad)])
              for h in heads), scale)


def tp_attn_prefill_paged_chunk(
    params: TPAttnParams,
    x: jax.Array,           # [C, d] replicated — one chunk of ONE sequence
    k_pages: jax.Array,     # [L, P, hkv_loc, page, hd] — the WHOLE pool shard
    v_pages: jax.Array,
    layer: jax.Array,       # scalar int32 — the layer addressed in the pool
    table_row: jax.Array,   # [pages_per_seq] int32 — the sequence's pages
    q_offset: jax.Array,    # scalar int32 — tokens already cached
    dims: TPAttnDims,
    *,
    kv_pages: int | None = None,
    axis: str = "tp",
    mode: Mode = "xla_ar",
    ctx: DistContext | None = None,
    k_scale: jax.Array | None = None,  # [L, P, hkv_loc] f32 — int8 scales
    v_scale: jax.Array | None = None,
    q_end: jax.Array | None = None,    # scalar int32 — end of REAL rows
    rope_pos: jax.Array | None = None,  # [C] int32 — rope positions (tree)
    attn_bias: jax.Array | None = None,  # [C, S_kv] f32 additive mask
):
    """Per-shard chunked-prefill step over the paged pool (inside
    ``shard_map``): QKV for ``C`` suffix tokens, rope at absolute
    positions ``q_offset + i``, KV scattered through the page table, and
    flash attention of the chunk's queries against the WHOLE cached
    context (prefix pages + the chunk itself) via the dynamic
    ``kv_offset``. This is the prefix-cache suffix prefill: matched
    prefix pages are read, never recomputed. The pool is the whole
    ``[L, ...]`` array off the layer scan's carry, written and gathered
    in place at ``(layer, page)`` (:func:`_write_chunk`).

    With ``k_scale``/``v_scale`` (int8 pool) the scatter quantizes the
    chunk's rows (growing/resetting the touched pages' scales) and the
    attention reads int8 codes with per-page scales dequantized inside
    the kernel (``block_k = page_size`` so pool pages ARE kv blocks).
    Quantized chunks route PAD rows (positions ≥ ``q_end``, the
    round_chunk right-padding) to the trash page: on the full-width
    path pad KV is inert (overwritten/masked), but a quantized pad row
    would grow — or, at page offset 0, seed — the touched page's scale
    with garbage amax, permanently requantizing accepted history
    against rows that are not part of the sequence.

    ``rope_pos``/``attn_bias`` serve the tree-speculation verify chunk:
    rows are tree NODES in DFS storage order, roped at their tree DEPTH
    (``rope_pos[i] = q_offset + depth_i``, which differs from the
    storage position for branched nodes) while the KV scatter keeps
    storage positions ``q_offset + i`` — accepted rows later row-move to
    their linear positions bit-identically, because K/V content depends
    only on token and rope position. ``attn_bias`` masks sibling
    branches out of each other's softmax (0 visible / -1e30 masked over
    the gathered dense view).

    Activations stay replicated (decode's AR layout, not prefill's
    sequence-sharded one): chunks are short, so the ag/rs overlap machinery
    would buy nothing, and replication keeps one compiled program valid for
    every chunk offset. Returns
    ``(out [C, d], k_pages, v_pages, k_scale, v_scale)``.
    """
    c = x.shape[0]
    page = k_pages.shape[3]
    quant = k_scale is not None
    qkv = jnp.dot(x, params.wqkv, preferred_element_type=jnp.float32).astype(
        x.dtype
    )
    q, k, v = dims.split_qkv(qkv)  # [C, h, hd]
    q = _rms_head(q, params.q_norm, dims.qk_norm_eps)
    k = _rms_head(k, params.k_norm, dims.qk_norm_eps)
    pos = q_offset + jnp.arange(c, dtype=jnp.int32)  # [C] absolute storage
    rpos = pos if rope_pos is None else rope_pos
    q = _rotate(dims, q.swapaxes(0, 1), rpos)  # [h, C, hd]
    k = _rotate(dims, k.swapaxes(0, 1), rpos)
    q, k, v, sm_scale = _to_pool(dims, k_pages, q, k, v.swapaxes(0, 1))

    # Write the chunk's KV through the table, in place.
    n_real = None if q_end is None else q_end - q_offset
    k_pages, k_scale = _write_chunk(
        k_pages, k_scale, k.swapaxes(0, 1), layer, table_row, q_offset, n_real
    )
    v_pages, v_scale = _write_chunk(
        v_pages, v_scale, v.swapaxes(0, 1), layer, table_row, q_offset, n_real
    )

    # Attend over the sequence's dense view (prefix + chunk). The
    # gather is bounded to ``kv_pages`` table entries — the caller's
    # static bucket covering q_offset + C — so a short suffix never
    # materializes the full max_length view (the causal skip saves the
    # COMPUTE past q_end, but gather traffic is paid for what's
    # gathered). Positions beyond q_offset + C inside the bucket are
    # masked by causality (rows live at q_offset..q_offset+C-1), so
    # stale/trash content there is inert.
    from triton_distributed_tpu.ops.attention.flash_decode import (
        pages_to_dense,
    )

    gather_row = table_row if kv_pages is None else table_row[:kv_pages]
    k_dense = pages_to_dense(k_pages, gather_row[None], layer)  # [1,h,S_kv,hd]
    v_dense = pages_to_dense(v_pages, gather_row[None], layer)
    s_max = gather_row.shape[0] * page
    if quant:
        # The gathered view keeps int8 codes; per-page scales gather
        # through the same bucket and dequantize inside the kernel
        # (block_k = page so pages and kv blocks coincide).
        ks_dense = k_scale[layer, gather_row].T[None]  # [1, h, pps]
        vs_dense = v_scale[layer, gather_row].T[None]
        o = flash_attention(
            q[None], k_dense, v_dense, causal=True, kv_offset=q_offset,
            sm_scale=sm_scale,
            block_k=page, k_scale=ks_dense, v_scale=vs_dense,
            bias=None if attn_bias is None else attn_bias[:, :s_max],
        )[0]  # [h, C, hd]
    else:
        o = flash_attention(
            q[None], k_dense, v_dense, causal=True, kv_offset=q_offset,
            sm_scale=sm_scale,
            block_k=128 if s_max % 128 == 0 else page,
            bias=None if attn_bias is None else attn_bias[:, :s_max],
        )[0]  # [h, C, hd]
    if o.shape[-1] != dims.head_dim:  # a padded pool row's zero columns
        o = o[..., : dims.head_dim]
    o_flat = o.swapaxes(0, 1).reshape(c, dims.hq_loc * dims.head_dim)
    o_flat = o_flat.astype(x.dtype)
    if mode in ("xla", "xla_ar"):
        part = jnp.dot(o_flat, params.wo, preferred_element_type=jnp.float32)
        out = jax.lax.psum(part.astype(x.dtype), axis)
    else:
        out = gemm_ar(o_flat, params.wo, axis=axis, ctx=ctx)
    return out, k_pages, v_pages, k_scale, v_scale


def tp_attn_decode(
    params: TPAttnParams,
    x: jax.Array,        # [B, d] replicated — one new token per sequence
    k_cache: jax.Array,  # [B, hkv_loc, S_max, hd]
    v_cache: jax.Array,
    kv_len: jax.Array,   # [B] int32 — tokens already in cache
    dims: TPAttnDims,
    *,
    axis: str = "tp",
    mode: Mode = "pallas_ar",
    ctx: DistContext | None = None,
):
    """Per-shard decode step (inside ``shard_map``).

    Local qkv GEMM → rope at position ``kv_len`` → cache append →
    GQA flash-decode over local heads → o-proj partial → all-reduce.
    Returns ``(out [B, d] replicated, k_cache, v_cache)``.
    """
    b = x.shape[0]
    qkv = jnp.dot(x, params.wqkv, preferred_element_type=jnp.float32).astype(
        x.dtype
    )
    q, k, v = dims.split_qkv(qkv)  # [B, h, hd]
    q = _rms_head(q, params.q_norm, dims.qk_norm_eps)
    k = _rms_head(k, params.k_norm, dims.qk_norm_eps)
    q = apply_rope(q, kv_len[:, None], dims.rope_theta)
    k = apply_rope(k, kv_len[:, None], dims.rope_theta)

    # Append at position kv_len[b] (per-sequence scatter).
    def upd(cache, new):  # cache [h, S, hd], new [h, hd], pos scalar
        def one(c, n, p):
            return jax.lax.dynamic_update_slice(c, n[:, None, :], (0, p, 0))
        return jax.vmap(one)(cache, new, kv_len)

    k_cache = upd(k_cache, k)
    v_cache = upd(v_cache, v)

    o = flash_decode(q, k_cache, v_cache, kv_len + 1)  # [B, hq_loc, hd]
    o_flat = o.reshape(b, dims.hq_loc * dims.head_dim).astype(x.dtype)
    if mode in ("xla", "xla_ar"):
        part = jnp.dot(o_flat, params.wo, preferred_element_type=jnp.float32)
        out = jax.lax.psum(part.astype(x.dtype), axis)
    elif mode in ("pallas", "pallas_ar"):
        # o-proj fused with its cross-rank sum (parity: the reference AR
        # decode o-proj + allreduce, tp_attn.py:261-271).
        out = gemm_ar(o_flat, params.wo, axis=axis, ctx=ctx)
    else:
        raise ValueError(f"unknown mode {mode!r}")
    return out, k_cache, v_cache


def tp_attn_decode_paged(
    params: TPAttnParams,
    x: jax.Array,          # [B, d] replicated — one new token per sequence
    k_pages: jax.Array,    # [L, P, hkv_loc, page, hd] — the WHOLE pool shard
    v_pages: jax.Array,
    layer: jax.Array,      # scalar int32 — the layer addressed in the pool
    page_table: jax.Array,  # [B, pages_per_seq] int32
    kv_len: jax.Array,      # [B] int32
    dims: TPAttnDims,
    *,
    walk,  # dense_decode_walk(kv_len + 1, page, pages_per_seq)
    axis: str = "tp",
    mode: Mode = "pallas_ar",
    ctx: DistContext | None = None,
    k_scale: jax.Array | None = None,  # [L, P, hkv_loc] f32 — int8 scales
    v_scale: jax.Array | None = None,
):
    """Per-shard decode step over a paged KV pool (inside ``shard_map``).

    Same dataflow as :func:`tp_attn_decode`, but the cache is the page
    pool: the append scatters through the page table and the attention
    is :func:`paged_flash_decode` (table-indexed BlockSpecs — no dense
    gather). Parity: the reference megakernel's paged decode
    (``mega_triton_kernel/models/paged_kv_cache.py``).

    The pool is the WHOLE ``[L, ...]`` array off the layer scan's carry:
    the step's ``B`` rows land in place at ``(layer, page_table[i, pos //
    page], :, pos % page, :)`` and the kernel reads pages at ``(layer,
    page)``, so a step moves the rows it writes and the pages it
    attends, never a layer's pool. ``walk`` is the kernel's grid for
    this step's lengths (the appended row counted): the caller derives
    it once a step, outside its layer scan, and it is required here so
    that no layer derives it again.

    With ``k_scale``/``v_scale`` (int8 pool) the append quantizes each
    new row into its page (growing the page scale, requantizing when it
    moves) and the attention streams int8 codes, dequantized inside the
    kernel — the decode step's KV read is half the bf16 bytes. Returns
    ``(out [B, d], k_pages, v_pages, k_scale, v_scale)``.
    """
    from triton_distributed_tpu.ops.attention import paged_flash_decode

    b = x.shape[0]
    page = k_pages.shape[3]
    qkv = jnp.dot(x, params.wqkv, preferred_element_type=jnp.float32).astype(
        x.dtype
    )
    q, k, v = dims.split_qkv(qkv)  # [B, h, hd]
    q = _rms_head(q, params.q_norm, dims.qk_norm_eps)
    k = _rms_head(k, params.k_norm, dims.qk_norm_eps)
    q = _rotate(dims, q, kv_len[:, None])
    k = _rotate(dims, k, kv_len[:, None])
    q, k, v, sm_scale = _to_pool(dims, k_pages, q, k, v)

    # Active rows never share a page; inactive rows fan into the trash
    # page, where the scale protocol's duplicate-pid contract holds.
    pids = page_table[jnp.arange(b), kv_len // page]
    k_pages, k_scale = _append_rows(
        k_pages, k_scale, k, layer, pids, kv_len % page
    )
    v_pages, v_scale = _append_rows(
        v_pages, v_scale, v, layer, pids, kv_len % page
    )

    o = paged_flash_decode(
        q, k_pages, v_pages, page_table, kv_len + 1, layer=layer,
        walk=walk, sm_scale=sm_scale, k_scale=k_scale, v_scale=v_scale,
    )
    if o.shape[-1] != dims.head_dim:  # a padded pool row's zero columns
        o = o[..., : dims.head_dim]
    o_flat = o.reshape(b, dims.hq_loc * dims.head_dim).astype(x.dtype)
    if mode in ("xla", "xla_ar"):
        part = jnp.dot(o_flat, params.wo, preferred_element_type=jnp.float32)
        out = jax.lax.psum(part.astype(x.dtype), axis)
    elif mode in ("pallas", "pallas_ar"):
        out = gemm_ar(o_flat, params.wo, axis=axis, ctx=ctx)
    else:
        raise ValueError(f"unknown mode {mode!r}")
    return out, k_pages, v_pages, k_scale, v_scale


class TPAttn:
    """Host-level layer (parity: ``TP_Attn``, ``layers/nvidia/tp_attn.py:78``)."""

    def __init__(
        self,
        d_model: int,
        num_q_heads: int,
        num_kv_heads: int,
        head_dim: int,
        *,
        qk_norm: bool = True,
        rope_theta: float = 1e6,
        dtype=jnp.bfloat16,
        axis: str = "tp",
        ctx: DistContext | None = None,
    ):
        self.ctx = ctx or current_context()
        self.axis = axis
        n = self.ctx.axis_size(axis)
        if num_q_heads % n or num_kv_heads % n:
            raise ValueError(
                f"heads ({num_q_heads}, {num_kv_heads}) not divisible by tp={n}"
            )
        self.d_model = d_model
        self.num_q_heads = num_q_heads
        self.num_kv_heads = num_kv_heads
        self.dims = TPAttnDims(
            hq_loc=num_q_heads // n,
            hkv_loc=num_kv_heads // n,
            head_dim=head_dim,
            rope_theta=rope_theta,
        )
        self.qk_norm = qk_norm
        self.dtype = dtype
        self.params: TPAttnParams | None = None

    def load(
        self,
        wq: jax.Array,  # [d, hq * hd]
        wk: jax.Array,  # [d, hkv * hd]
        wv: jax.Array,  # [d, hkv * hd]
        wo: jax.Array,  # [hq * hd, d]
        q_norm: jax.Array | None = None,
        k_norm: jax.Array | None = None,
    ) -> TPAttnParams:
        """Shard full weights: per-device wqkv = [q_loc | k_loc | v_loc]."""
        n = self.ctx.axis_size(self.axis)
        hd = self.dims.head_dim
        d = self.d_model

        def by_shard(w, h):  # [d, h*hd] → [n, d, (h/n)*hd]
            return w.reshape(d, n, (h // n) * hd).swapaxes(0, 1)

        wqkv = jnp.concatenate(
            [
                by_shard(wq, self.num_q_heads),
                by_shard(wk, self.num_kv_heads),
                by_shard(wv, self.num_kv_heads),
            ],
            axis=2,
        )  # [n, d, qkv_loc]
        wqkv = wqkv.swapaxes(0, 1).reshape(d, n * self.dims.qkv_loc)
        self.params = TPAttnParams(
            wqkv=self.ctx.shard(wqkv.astype(self.dtype), None, self.axis),
            wo=self.ctx.shard(wo.astype(self.dtype), self.axis, None),
            q_norm=None if q_norm is None else self.ctx.replicate(q_norm),
            k_norm=None if k_norm is None else self.ctx.replicate(k_norm),
        )
        return self.params

    def init(self, key: jax.Array) -> TPAttnParams:
        hd = self.dims.head_dim
        ks = jax.random.split(key, 4)
        scale = self.d_model**-0.5
        wq = jax.random.normal(ks[0], (self.d_model, self.num_q_heads * hd)) * scale
        wk = jax.random.normal(ks[1], (self.d_model, self.num_kv_heads * hd)) * scale
        wv = jax.random.normal(ks[2], (self.d_model, self.num_kv_heads * hd)) * scale
        wo = jax.random.normal(ks[3], (self.num_q_heads * hd, self.d_model)) * scale
        qn = kn = jnp.ones((hd,)) if self.qk_norm else None
        return self.load(
            wq.astype(self.dtype), wk.astype(self.dtype), wv.astype(self.dtype),
            wo.astype(self.dtype), qn, kn,
        )

    @property
    def param_specs(self):
        from jax.sharding import PartitionSpec as P

        return TPAttnParams(
            wqkv=P(None, self.axis), wo=P(self.axis, None),
            q_norm=None if not self.qk_norm else P(),
            k_norm=None if not self.qk_norm else P(),
        )

    def prefill(self, x: jax.Array, mode: Mode = "pallas") -> jax.Array:
        """``x [S, d]`` host-global; returns ``[S, d]`` (seq-sharded)."""
        from jax.sharding import PartitionSpec as P

        assert self.params is not None
        f = self.ctx.shard_map(
            functools.partial(
                tp_attn_prefill, dims=self.dims, axis=self.axis, mode=mode,
                ctx=self.ctx,
            ),
            in_specs=(self.param_specs, P(self.axis, None)),
            out_specs=(P(self.axis, None), P(self.axis), P(self.axis)),
        )
        out, _, _ = f(self.params, x)
        return out
