"""Latent attention (DeepSeek-V3's MLA) over a paged latent cache.

Per token, ``x`` the normed residual stream:

    c_q = norm(x W_qa);  q = c_q W_qb: heads of [q_nope; q_rope]
    [c_kv; k_r] = x W_kva;  c_kv = norm(c_kv);  k_rope = RoPE(k_r)

and the cache row is ``[c_kv; k_rope]``, nothing else: ``c_kv`` lives in
the pool's ``k_pages`` ``[L, P, 1, page, kv_rank]`` and ``k_rope`` in
its ``v_pages`` ``[L, P, 1, rope, page]`` (one "head": the row is shared
by all of them), so every whole-page utility of ``paged_kv_cache.py``
and the radix cache serve it as they serve per-head K and V. The rotary
pool is kept TRANSPOSED, the page axis last: the TPU tiles the last axis
onto 128 lanes, a 64-wide row would be padded to 128 in HBM (and XLA,
left to choose, stores it this way round and copies the whole pool into
row-major at every step's entry); a token's row is 1,152 bytes a layer
in bf16, none of it padding.

Two paths give the same numbers. **Expanded** (prefill chunks): per-head
keys ``[c_kv W_kb_h; k_rope]`` and values ``c_kv W_vb_h`` are rebuilt
from the slot's gathered latent pages and go through
``flash_attention`` (``D_qk`` nope + rope, ``D_v``). **Absorbed**
(decode): ``q_lat_h = q_nope_h W_kb_h^T`` meets the latent rows
directly (:func:`mla_paged_decode`) and ``W_vb_h`` maps the result
back. The rotary dims rotate in halves (``apply_rope``); the published
code de-interleaves them first, which for seeded weights is a
permutation of columns of ``W_qb`` / ``W_kva``.

One chip holds the whole layer (data-parallel attention): there is no
tensor-parallel split here.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from triton_distributed_tpu.layers.tp_attn import _append_rows, _write_chunk
from triton_distributed_tpu.ops.attention.flash_attention import flash_attention
from triton_distributed_tpu.ops.attention.flash_decode import pages_to_dense
from triton_distributed_tpu.ops.attention.mla_decode import (
    mla_paged_decode,
    rope_pages_to_dense,
)
from triton_distributed_tpu.ops.attention.rope import (
    apply_rope,
    rope_freqs,
    yarn_freqs,
    yarn_mscale,
)
from triton_distributed_tpu.runtime.pytree import register_param_dataclass


@dataclasses.dataclass
class MLAParams:
    wq_a: jax.Array     # [d, q_rank]
    q_norm: jax.Array   # [q_rank]
    wq_b: jax.Array     # [q_rank, H * (nope + rope)], a head [nope; rope]
    wkv_a: jax.Array    # [d, kv_rank + rope]: [c_kv; k_r]
    kv_norm: jax.Array  # [kv_rank]
    wk_b: jax.Array     # [kv_rank, H * nope]: kv_b's key columns
    wv_b: jax.Array     # [kv_rank, H * v]: kv_b's value columns
    wo: jax.Array       # [H * v, d]


MLA_FIELDS = ["wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wk_b", "wv_b",
              "wo"]
register_param_dataclass(MLAParams, MLA_FIELDS)


@dataclasses.dataclass(frozen=True)
class MLADims:
    heads: int
    nope: int
    rope: int
    v: int
    kv_rank: int
    theta: float = 1e4
    eps: float = 1e-6
    # YaRN (factor 0 = plain RoPE).
    yarn_factor: float = 0.0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_original_max: int = 4096
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 0.0

    @classmethod
    def of(cls, cfg) -> "MLADims":
        return cls(
            heads=cfg.num_q_heads, nope=cfg.qk_nope_head_dim,
            rope=cfg.qk_rope_head_dim, v=cfg.v_head_dim,
            kv_rank=cfg.kv_lora_rank, theta=cfg.rope_theta, eps=cfg.rms_eps,
            yarn_factor=cfg.yarn_factor, yarn_beta_fast=cfg.yarn_beta_fast,
            yarn_beta_slow=cfg.yarn_beta_slow,
            yarn_original_max=cfg.yarn_original_max,
            yarn_mscale=cfg.yarn_mscale,
            yarn_mscale_all_dim=cfg.yarn_mscale_all_dim,
        )

    @property
    def inv_freq(self) -> jax.Array:
        if not self.yarn_factor:
            return rope_freqs(self.rope, self.theta)
        return yarn_freqs(self.rope, self.theta, self.yarn_factor,
                          self.yarn_beta_fast, self.yarn_beta_slow,
                          self.yarn_original_max)

    @property
    def rope_mscale(self) -> float:
        """What cos and sin are multiplied by (1 where ``mscale`` and
        ``mscale_all_dim`` agree, as published)."""
        if not self.yarn_factor:
            return 1.0
        return (yarn_mscale(self.yarn_factor, self.yarn_mscale)
                / yarn_mscale(self.yarn_factor, self.yarn_mscale_all_dim))

    @property
    def sm_scale(self) -> float:
        s = (self.nope + self.rope) ** -0.5
        if self.yarn_factor and self.yarn_mscale_all_dim:
            s *= yarn_mscale(self.yarn_factor, self.yarn_mscale_all_dim) ** 2
        return s


def _norm(x, w, eps):
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * w.astype(jnp.float32)).astype(x.dtype)


def _mm(x, w):
    return jnp.dot(x, w, preferred_element_type=jnp.float32).astype(x.dtype)


def mla_project(params: MLAParams, x: jax.Array, pos: jax.Array,
                dims: MLADims):
    """``x [T, d]`` at absolute positions ``pos [T]`` to ``q_nope [T, H,
    nope]``, ``q_rope [T, H, rope]`` (rotated), and the cache row's two
    parts ``c_kv [T, kv_rank]`` (normed) and ``k_rope [T, rope]``
    (rotated)."""
    t = x.shape[0]
    q = _mm(_norm(_mm(x, params.wq_a), params.q_norm, dims.eps), params.wq_b)
    q = q.reshape(t, dims.heads, dims.nope + dims.rope)
    q_nope, q_rope = q[..., : dims.nope], q[..., dims.nope:]
    kv = _mm(x, params.wkv_a)
    c_kv = _norm(kv[:, : dims.kv_rank], params.kv_norm, dims.eps)
    inv = dims.inv_freq
    q_rope = apply_rope(q_rope, pos[:, None], inv_freq=inv)
    k_rope = apply_rope(kv[:, dims.kv_rank:], pos, inv_freq=inv)
    if dims.rope_mscale != 1.0:
        q_rope = (q_rope * dims.rope_mscale).astype(x.dtype)
        k_rope = (k_rope * dims.rope_mscale).astype(x.dtype)
    return q_nope, q_rope, c_kv, k_rope


def _per_head(w: jax.Array, heads: int) -> jax.Array:
    """``[kv_rank, H * n]`` seen as ``[kv_rank, H, n]`` (a bitcast)."""
    return w.reshape(w.shape[0], heads, -1)


def mla_absorbed(params, q_nope, q_rope, c_kv, k_rope, mask, dims: MLADims):
    """The absorbed formula in plain ``jax.numpy`` over explicit rows:
    ``q_* [T, H, .]``, rows ``c_kv [S, kv_rank]`` / ``k_rope [S, rope]``,
    ``mask [T, S]`` (True = attends). The tests' and the chip smoke's
    golden for both served paths."""
    f32 = jnp.float32
    q_lat = jnp.einsum("thn,chn->thc", q_nope.astype(f32),
                       _per_head(params.wk_b, dims.heads).astype(f32))
    s = (jnp.einsum("thc,sc->ths", q_lat, c_kv.astype(f32))
         + jnp.einsum("thr,sr->ths", q_rope.astype(f32), k_rope.astype(f32)))
    p = jax.nn.softmax(
        jnp.where(mask[:, None, :], s * dims.sm_scale, -1e30), axis=-1)
    o_lat = jnp.einsum("ths,sc->thc", p, c_kv.astype(f32))
    return jnp.einsum("thc,chv->thv", o_lat,
                      _per_head(params.wv_b, dims.heads).astype(f32))


def _append_cols(pages, cols, layer, pids, offs):
    """:func:`_append_rows` for the transposed rotary pool ``[L, P, 1,
    rope, page]``: ``cols [B, rope]``, one per sequence, land in place
    at ``(layer, pids[i], 0, :, offs[i])``."""
    for i in range(cols.shape[0]):
        pages = jax.lax.dynamic_update_slice(
            pages, cols[i][None, None, None, :, None].astype(pages.dtype),
            (layer, pids[i], 0, 0, offs[i]),
        )
    return pages


def _write_chunk_cols(pages, rows, layer, table_row, start):
    """:func:`_write_chunk` for the transposed rotary pool: ``rows [C,
    rope]`` are the contiguous positions ``start + i`` of the sequence
    whose pages ``table_row`` lists; each page they touch is read,
    merged with its columns and written back whole, and what falls off
    the table goes to the trash page."""
    c, rope = rows.shape
    page = pages.shape[4]
    pps = table_row.shape[0]
    padded = jnp.pad(rows.astype(pages.dtype).T, ((0, 0), (page, page)))
    first = start // page
    shift = page - start % page
    r = jnp.arange(page, dtype=jnp.int32)
    for j in range((c + page - 2) // page + 1):
        entry = first + j
        src = j * page + r - (start % page)  # chunk row held by column r
        mine = (src >= 0) & (src < c)
        on_table = (entry >= 0) & (entry < pps)
        pid = jnp.where(
            on_table, jnp.take(table_row, jnp.clip(entry, 0, pps - 1)), 0
        )
        at = (layer, pid, 0, 0, 0)
        old = jax.lax.dynamic_slice(pages, at, (1, 1, 1, rope, page))
        new = jax.lax.dynamic_slice_in_dim(padded, j * page + shift, page, 1)
        merged = jnp.where(mine[None, :], new, old[0, 0, 0])
        pages = jax.lax.dynamic_update_slice(
            pages, merged[None, None, None], at)
    return pages


def mla_decode_paged(
    params: MLAParams,
    x: jax.Array,          # [B, d]: one new token per sequence
    k_pages: jax.Array,    # [L, P, 1, page, kv_rank]: the WHOLE pool
    v_pages: jax.Array,    # [L, P, 1, rope, page]: transposed
    layer: jax.Array,      # scalar int32
    page_table: jax.Array,  # [B, pages_per_seq] int32
    kv_len: jax.Array,      # [B] int32
    dims: MLADims,
    *,
    walk,  # paged_decode_walk(kv_len + 1, page, pages_per_seq)
):
    """One decode step of the absorbed path: the new token's row lands
    in place at ``(layer, page_table[i, pos // page], 0, pos % page)``
    and ``tdt_mla_decode_paged`` reads the sequence's latent pages
    through the table. Returns ``(out [B, d], k_pages, v_pages)``."""
    b = x.shape[0]
    page = k_pages.shape[3]
    with jax.named_scope("mla"):
        q_nope, q_rope, c_kv, k_rope = mla_project(params, x, kv_len, dims)
        q_lat = jnp.einsum(
            "bhn,chn->bhc", q_nope, _per_head(params.wk_b, dims.heads),
            preferred_element_type=jnp.float32,
        ).astype(x.dtype)
        pids = page_table[jnp.arange(b), kv_len // page]
        k_pages, _ = _append_rows(
            k_pages, None, c_kv[:, None, :], layer, pids, kv_len % page)
        v_pages = _append_cols(v_pages, k_rope, layer, pids, kv_len % page)
        o_lat = mla_paged_decode(
            q_lat, q_rope, k_pages, v_pages, page_table, kv_len + 1,
            sm_scale=dims.sm_scale, layer=layer, walk=walk,
        )
        o = jnp.einsum(
            "bhc,chv->bhv", o_lat, _per_head(params.wv_b, dims.heads),
            preferred_element_type=jnp.float32,
        ).astype(x.dtype)
        out = _mm(o.reshape(b, dims.heads * dims.v), params.wo)
    return out, k_pages, v_pages


def mla_prefill_paged_chunk(
    params: MLAParams,
    x: jax.Array,          # [C, d]: one (padded) chunk of ONE sequence
    k_pages: jax.Array,
    v_pages: jax.Array,
    layer: jax.Array,
    table_row: jax.Array,  # [pages_per_seq] int32
    q_offset: jax.Array,   # scalar int32: rows already cached
    dims: MLADims,
    *,
    kv_pages: int | None = None,
):
    """One prefill chunk of the expanded path: the chunk's rows are
    written through the table, then per-head keys and values are rebuilt
    from the slot's gathered latent pages (``kv_pages`` table entries:
    the caller's bucket covering ``q_offset + C``; a radix hit or a
    second chunk starts past 0) and attended causally from
    ``kv_offset = q_offset``. Rows past the chunk inside the bucket are
    masked by causality. Returns ``(out [C, d], k_pages, v_pages)``."""
    c = x.shape[0]
    page = k_pages.shape[3]
    h = dims.heads
    with jax.named_scope("mla"):
        pos = q_offset + jnp.arange(c, dtype=jnp.int32)
        q_nope, q_rope, c_kv, k_rope = mla_project(params, x, pos, dims)
        k_pages, _ = _write_chunk(
            k_pages, None, c_kv[:, None, :], layer, table_row, q_offset)
        v_pages = _write_chunk_cols(
            v_pages, k_rope, layer, table_row, q_offset)
        gather_row = table_row if kv_pages is None else table_row[:kv_pages]
        lat = pages_to_dense(k_pages, gather_row[None], layer)[0, 0]
        rot = rope_pages_to_dense(v_pages, gather_row[None], layer)[0]
        s_kv = lat.shape[0]
        k_nope = _mm(lat, params.wk_b).reshape(s_kv, h, dims.nope)
        v = _mm(lat, params.wv_b).reshape(s_kv, h, dims.v)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(rot[:, None, :], (s_kv, h, dims.rope))],
            axis=-1,
        )
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        # The widest blocks that divide: at 128 x 128 the kernel's grid
        # is 115,000 steps for a 3,584-token chunk of 128 heads and the
        # chunk spends 58% of its time here (PERF.md "PR 35").
        o = flash_attention(
            q.swapaxes(0, 1)[None], k.swapaxes(0, 1)[None],
            v.swapaxes(0, 1)[None], causal=True, kv_offset=q_offset,
            sm_scale=dims.sm_scale,
            block_q=next((b for b in (512, 256, 128) if c % b == 0), 128),
            block_k=next((b for b in (512, 256, 128) if s_kv % b == 0), page),
        )[0]  # [H, C, v]
        out = _mm(o.swapaxes(0, 1).reshape(c, h * dims.v), params.wo)
    return out, k_pages, v_pages
