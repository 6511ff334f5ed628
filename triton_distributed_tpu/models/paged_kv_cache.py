"""Paged KV cache: fixed-size pages + per-sequence page tables.

Parity: reference ``mega_triton_kernel/models/paged_kv_cache.py`` — a
page-pool cache for the megakernel decode path (pages allocated from a
free list, indirection through a page table).

TPU design: the pool is one array ``[L, num_pages, Hkv_loc, page, hd]``
(pages are just the S axis tiled), the page table is host-side state
(allocation is control-plane work, per sequence not per token), and
appends are jit-safe dynamic-slice writes at ``(page_id, offset)``.
Attention either materializes a dense view (``as_dense`` — gather by
page table, cheap at decode sizes) or consumes pages directly via the
table as a scalar-prefetch operand (future paged flash-decode).
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from triton_distributed_tpu.models.config import ModelConfig
from triton_distributed_tpu.runtime.faults import fault_point
from triton_distributed_tpu.runtime.mesh import DistContext
from triton_distributed_tpu.runtime.pytree import register_param_dataclass


@dataclasses.dataclass
class PagedKVCache:
    k_pages: jax.Array     # [L, P, Hkv_loc, page_size, hd]
    v_pages: jax.Array
    page_table: jax.Array  # [B, pages_per_seq] int32 — page ids
    kv_len: jax.Array      # [B] int32
    # Symmetric per-page-per-head dequantization scales ``[L, P,
    # Hkv_loc]`` f32 — present iff the pool stores int8
    # (``kv_dtype="int8"``). ``None`` keeps the full-width layout (and
    # every code path over it) bit-identical to the unquantized build.
    k_scale: jax.Array | None = None
    v_scale: jax.Array | None = None
    # The OTHER kind of state, for a model whose layer list declares
    # recurrent layers (``cfg.slot_keeps`` holds "recurrent_state";
    # ``None`` everywhere else keeps the tree leaf for leaf): a state
    # that is not pages. ``ssm_state [Lm, B, N, di]`` float32 is one
    # Mamba-2 layer's state a slot, transposed (``N`` on sublanes, the
    # ``di = H x P`` inner channels on lanes: layers/mamba2.py), whatever
    # the slot's context (``None``
    # for a model of short convolutions, which keeps none), and
    # ``conv_state [Lr, taps - 1, B, C]`` the last inputs of a recurrent
    # layer's causal convolution (slots before channels: the layout XLA
    # gives the array anyway, so pinning it copies nothing); both are
    # indexed by (recurrent layer, slot), written
    # absolutely by a chunk and advanced by a decode step ONLY for the
    # rows ``live [B]`` marks: the slots the engine has IN FLIGHT,
    # uploaded with the tables. The page table cannot say that: a slot
    # whose admission is between two chunks is mapped, and a state
    # advanced there is wrong for good.
    ssm_state: jax.Array | None = None
    conv_state: jax.Array | None = None
    live: jax.Array | None = None

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None


register_param_dataclass(
    PagedKVCache,
    ["k_pages", "v_pages", "page_table", "kv_len", "k_scale", "v_scale",
     "ssm_state", "conv_state", "live"],
)


# -- int8 quantization ----------------------------------------------------
#
# Storage mode ``kv_dtype="int8"``: the pool keeps int8 codes plus ONE
# symmetric scale per (layer, page, kv head) — ``x ≈ code * scale`` with
# ``scale = amax / 127`` over the page's (page_size, head_dim) block.
# Scales are *monotone within a page's lifetime*: a fresh write at page
# offset 0 sets the scale absolutely (the page has no valid prior rows),
# later appends grow it via max and re-quantize the already-stored codes
# under the grown scale (ratio 1 → value-exact no-op when the scale did
# not move). Decode/prefill kernels dequantize in-register
# (``ops/attention/flash_decode.py`` / ``flash_attention.py``), so
# full-width KV never materializes in HBM.

_Q_MAX = 127.0
# Safe-division floor: an all-zero page has amax 0 → scale 0; dividing
# by the floor instead maps 0/eps → 0 rather than NaN.
_SCALE_EPS = 1e-30


def page_scales(x: jax.Array) -> jax.Array:
    """Symmetric per-page-per-head scale of ``x [..., page, hd]``:
    amax over the trailing (page, hd) block / 127."""
    return jnp.max(jnp.abs(x.astype(jnp.float32)), axis=(-2, -1)) / _Q_MAX


def quantize_page(x: jax.Array, scale: jax.Array) -> jax.Array:
    """Quantize ``x [..., page, hd]`` under ``scale [...]`` (int8,
    round-to-nearest, clipped symmetric at ±127)."""
    s = jnp.maximum(scale, _SCALE_EPS)[..., None, None]
    q = jnp.round(x.astype(jnp.float32) / s)
    return jnp.clip(q, -_Q_MAX, _Q_MAX).astype(jnp.int8)


def dequantize_page(q: jax.Array, scale: jax.Array) -> jax.Array:
    """Inverse of :func:`quantize_page` (f32)."""
    return q.astype(jnp.float32) * scale[..., None, None]


def quantize_pages(pages: jax.Array) -> tuple[jax.Array, jax.Array]:
    """One-shot pool quantization (tests/benches): ``[..., page, hd]``
    → ``(int8 codes, per-page-per-head scales [...])``."""
    scale = page_scales(pages)
    return quantize_page(pages, scale), scale


def quantize_rows(pages, scales, rows, pids, offs, layer=None):
    """The int8 pool's scale protocol for ``rows [C, H, hd]`` bound for
    ``(pids[c], offs[c])``: grow each touched page's scale to cover its
    new rows (reset, not grown, when a row lands at page offset 0 — a
    fresh page has no valid prior rows, and a stale tenant's scale must
    not survive page recycling), re-quantize the touched pages' existing
    codes under the grown scale (value-exact when it did not move), and
    quantize the rows under it. Returns ``(pages, scales, int8 rows)``;
    writing the rows is the caller's (:func:`quantized_row_scatter`, or
    ``layers/tp_attn.py``'s in-place writers).

    ``pages [P, H, page, hd]`` / ``scales [P, H]`` are ONE layer's, or
    with ``layer`` (traced: a layer scan's index) the WHOLE pool's
    ``[L, P, H, page, hd]`` / ``[L, P, H]``, addressed in place at
    (layer, page) so no layer is sliced out of the pool or stacked back.

    THE one implementation of the reset/grow/requant rule: every write
    path goes through it, so a change lands in all of them at once.
    Duplicate ``pids`` (several rows in one page, trash-page fan-in)
    are safe: scatter-min/max are associative and duplicate requant
    writes are identical."""
    at = (lambda ix: ix) if layer is None else (lambda ix: (layer, ix))
    rows = rows.astype(jnp.float32)
    row_sc = jnp.max(jnp.abs(rows), axis=-1) / _Q_MAX  # [C, H]
    clear = jnp.broadcast_to(
        jnp.where(offs == 0, 0.0, jnp.inf)[:, None], row_sc.shape
    )
    new_scales = scales.at[at(pids)].min(clear)
    new_scales = new_scales.at[at(pids)].max(row_sc)
    old_sc = scales[at(pids)]      # [C, H]
    new_sc = new_scales[at(pids)]

    def requant(pages):
        ratio = old_sc / jnp.maximum(new_sc, _SCALE_EPS)
        got = pages[at(pids)].astype(jnp.float32)
        req = jnp.clip(
            jnp.round(got * ratio[..., None, None]), -_Q_MAX, _Q_MAX
        ).astype(jnp.int8)
        return pages.at[at(pids)].set(req)

    # Steady-state decode rarely moves a scale (a page's amax settles
    # after its first rows): skip the page-sized read+rewrite entirely
    # when every touched scale is unchanged — the requant would be a
    # value-exact no-op, but its HBM traffic is real. (Under append_n's
    # layer vmap the cond lowers to a select and both branches run;
    # that path is the mega/test batch append, not the decode loop.)
    pages = jax.lax.cond(
        jnp.any(new_sc != old_sc), requant, lambda p: p, pages
    )
    q_rows = jnp.clip(
        jnp.round(rows / jnp.maximum(new_sc[..., None], _SCALE_EPS)),
        -_Q_MAX, _Q_MAX,
    ).astype(jnp.int8)
    return pages, new_scales, q_rows


def quantized_row_scatter(pages, scales, rows, pids, offs):
    """Scatter ``rows [C, H, hd]`` into a ONE-LAYER int8 pool
    ``pages [P, H, page, hd]`` at ``(pids[c], offs[c])`` under
    :func:`quantize_rows`' scale protocol. :func:`append_n` vmaps it
    over the layer axis; the serving programs, whose pool rides a layer
    scan's carry whole, call :func:`quantize_rows` with their layer and
    write the rows in place (``layers/tp_attn.py``)."""
    pages, scales, q_rows = quantize_rows(pages, scales, rows, pids, offs)
    return pages.at[pids, :, offs, :].set(q_rows), scales


# vmap of the row scatter over a leading layer axis — append_n's
# quantized write ([L, P, H, page, hd] pools, rows [L, C, H, hd],
# shared (pids, offs)).
_row_scatter_layers = jax.vmap(
    quantized_row_scatter, in_axes=(0, 0, 0, None, None)
)


class PagePool:
    """Host-side free-list allocator (parity: the reference's page pool).

    Page assignment is control-plane state: sequences allocate/free whole
    page lists on admission/eviction, so this stays in Python while the
    data plane (pool arrays + appends) is jitted.
    """

    def __init__(self, num_pages: int):
        self.num_pages = num_pages
        self.free = list(range(num_pages - 1, -1, -1))

    def allocate(self, n: int) -> list[int]:
        fault_point("pool.allocate", n=n, free=len(self.free))
        if n > len(self.free):
            raise RuntimeError(f"page pool exhausted ({n} > {len(self.free)})")
        return [self.free.pop() for _ in range(n)]

    def release(self, pages: list[int]) -> None:
        self.free.extend(pages)


def init_paged_cache(
    cfg: ModelConfig,
    batch_size: int,
    ctx: DistContext,
    axis: str = "tp",
    *,
    max_length: int | None = None,
    page_size: int = 128,
    num_pages: int | None = None,
    assign_pages: bool = True,
    kv_dtype: str | None = None,
) -> tuple[PagedKVCache, PagePool]:
    """Allocate the pool + page tables for ``batch_size`` sequences.

    ``assign_pages=False`` leaves the pool full and the table zeroed —
    for callers that manage page assignment themselves (continuous
    batching admits/evicts per request, possibly with ``num_pages``
    oversubscribed below ``batch_size * pages_per_seq``).

    A latent-attention model (``cfg.kv_lora_rank``) caches ONE row a
    token a layer, shared by all heads: ``k_pages [L, P, 1, page,
    kv_lora_rank]`` holds the normed latents and ``v_pages [L, P, 1,
    qk_rope_head_dim, page]`` the shared rotary keys, TRANSPOSED (the
    page axis on the TPU's 128 lanes: a 64-wide row would be padded to
    128 in HBM). Both are pools of one "head", so the whole-page
    utilities below, the radix cache and :func:`audit_pool` serve them
    unchanged.

    ``kv_dtype="int8"`` (or ``cfg.kv_dtype``; the explicit argument
    wins) allocates the pool as int8 plus per-page-per-head
    ``k_scale``/``v_scale`` arrays — roughly half the bf16 pool's HBM
    bytes, dequantized inside the attention kernels. Unset keeps the
    full-width ``cfg.dtype`` pool bit-identical to the unquantized
    build.
    """
    resolved_kv = kv_dtype if kv_dtype is not None else cfg.kv_dtype
    if resolved_kv not in (None, "int8"):
        raise ValueError(
            f"kv_dtype={resolved_kv!r} unsupported; expected None or 'int8'"
        )
    s_max = max_length or cfg.max_length
    if s_max % page_size:
        raise ValueError(f"max_length {s_max} not a page multiple")
    pages_per_seq = s_max // page_size
    num_pages = num_pages or batch_size * pages_per_seq
    pool = PagePool(num_pages)
    if assign_pages:
        table = np.asarray(
            [pool.allocate(pages_per_seq) for _ in range(batch_size)],
            np.int32,
        )
    else:
        table = np.zeros((batch_size, pages_per_seq), np.int32)
    # The pool holds the layers that cache rows: all of them, but for a
    # model whose layer list declares recurrent layers.
    shape = (
        cfg.attention_layers, num_pages, cfg.num_kv_heads, page_size,
        cfg.pool_row_dim,
    )
    v_shape = shape
    if cfg.kv_lora_rank:
        if resolved_kv == "int8":
            raise ValueError(
                "--kv-dtype int8 has no latent-pool path: "
                f"{cfg.model_name} caches latent rows, and the int8 scale "
                "protocol is per page and KV head"
            )
        shape = (cfg.num_layers, num_pages, 1, page_size, cfg.kv_lora_rank)
        v_shape = (*shape[:3], cfg.qk_rope_head_dim, page_size)
    spec = (None, None, axis, None, None)
    pool_dtype = jnp.int8 if resolved_kv == "int8" else cfg.dtype
    if resolved_kv == "int8":
        scale_shape = (cfg.num_layers, num_pages, cfg.num_kv_heads)
        k_scale = ctx.shard(jnp.zeros(scale_shape, jnp.float32),
                            None, None, axis)
        v_scale = ctx.shard(jnp.zeros(scale_shape, jnp.float32),
                            None, None, axis)
    else:
        k_scale = v_scale = None
    state = {}
    if "recurrent_state" in cfg.slot_keeps:
        if resolved_kv == "int8":
            raise ValueError(
                f"--kv-dtype int8: {cfg.model_name} keeps a "
                "recurrent state beside a full-width pool of "
                f"{cfg.attention_layers} layers, and has no int8 path")
        ssm, conv = recurrent_state_shapes(cfg, batch_size)
        state = dict(
            ssm_state=None if ssm is None else ctx.replicate(
                jnp.zeros(ssm, jnp.float32)),
            conv_state=ctx.replicate(jnp.zeros(conv, cfg.dtype)),
            live=ctx.replicate(jnp.zeros((batch_size,), bool)),
        )
    cache = PagedKVCache(
        k_pages=ctx.shard(jnp.zeros(shape, pool_dtype), *spec),
        v_pages=ctx.shard(jnp.zeros(v_shape, pool_dtype), *spec),
        page_table=ctx.replicate(jnp.asarray(table)),
        kv_len=ctx.replicate(jnp.zeros((batch_size,), jnp.int32)),
        k_scale=k_scale,
        v_scale=v_scale,
        **state,
    )
    return cache, pool


def recurrent_state_shapes(cfg: ModelConfig, slots: int) -> tuple:
    """``(ssm_state, conv_state)`` shapes for ``slots`` decode slots: of
    a Mamba-2 layer a state ``[N, di]`` (transposed: layers/mamba2.py)
    and its convolution's last
    ``taps - 1`` inputs (``x | B | C`` channels) a slot; of a short
    convolution (LFM2) no state (``None``) and its last ``taps - 1``
    gated inputs (``hidden_size`` channels)."""
    if cfg.conv_layers:
        return None, (cfg.conv_layers, cfg.conv_L_cache - 1, slots,
                      cfg.hidden_size)
    from triton_distributed_tpu.layers.mamba2 import Mamba2Dims

    m = Mamba2Dims.of(cfg)
    return (
        (cfg.mamba_layers, slots, m.state, m.inner),
        (cfg.mamba_layers, m.taps - 1, slots, m.conv_dim),
    )


def state_bytes_per_slot(cache: PagedKVCache) -> int:
    """HBM bytes of one slot's recurrent state over all its layers,
    whatever the slot's context (0 for a model that keeps none): what a
    decode step reads AND writes for every row in flight. The cache
    keeps one where it marks the rows in flight (``live``)."""
    if cache.live is None:
        return 0
    slots = cache.live.shape[0]
    return sum(
        a.dtype.itemsize * math.prod(a.shape) // slots
        for a in (cache.ssm_state, cache.conv_state) if a is not None
    )


def kv_bytes_per_token(cache: PagedKVCache) -> float:
    """HBM bytes one cached token costs across K+V pools (+ scale
    overhead when quantized) — the quantity steady-state decode streams
    per token per step. Computed from the GLOBAL array shapes (the pool
    is head-sharded; shapes here are pre-shard). A pool's bytes a token
    are its page's over the page size, whatever the page's own layout
    (a latent model's rotary pool is ``[.., rope, page]``)."""
    page = cache.k_pages.shape[3]
    L = cache.k_pages.shape[0]
    per = L * sum(
        a.dtype.itemsize * math.prod(a.shape[2:]) // page
        for a in (cache.k_pages, cache.v_pages)
    )
    if cache.quantized:
        H = cache.k_pages.shape[2]
        per += (
            cache.k_scale.dtype.itemsize + cache.v_scale.dtype.itemsize
        ) * L * H / page
    return float(per)


class PoolAuditError(RuntimeError):
    """The pool/radix invariant audit found leaked, double-owned, or
    phantom pages — the serving loop's bookkeeping is corrupt."""


def audit_pool(
    pool: PagePool,
    num_pages: int | None = None,
    owners: dict[str, list[int]] | None = None,
    *,
    shared: dict[str, list[int]] | None = None,
    reserved: tuple[int, ...] = (0,),
) -> list[str]:
    """Cross-check the pool's ownership partition; returns violation
    strings (empty == clean).

    ``owners`` maps an owner name (``"slot3"``, ``"tree"``) to the
    pages it holds EXCLUSIVELY. ``shared`` maps an owner to pages it
    maps *by reference* (a slot's refcounted prefix pages) — those must
    belong to exactly one exclusive owner and never be free. The audit
    proves:

    - free list ∪ exclusive owners ∪ ``reserved`` == all pages
      (nothing leaked, nothing phantom),
    - no page has two exclusive owners, is both owned and free, or is
      a reserved page (the trash page is nobody's),
    - the free list holds no duplicates,
    - every shared mapping targets a live exclusively-owned page.

    Host-side and allocation-free: cheap enough to run after every
    ``run()`` (the continuous engine does) and from every test.
    """
    problems: list[str] = []
    total = pool.num_pages if num_pages is None else int(num_pages)
    free = list(pool.free)
    free_set = set(free)
    if len(free_set) != len(free):
        dup = sorted(p for p in free_set if free.count(p) > 1)
        problems.append(f"free list holds duplicate pages {dup}")
    claimed: dict[int, str] = {}
    for name, pages in (owners or {}).items():
        seen_local: set[int] = set()
        for p in pages:
            p = int(p)
            if p in seen_local:
                problems.append(f"{name} lists page {p} twice")
                continue
            seen_local.add(p)
            if p in claimed:
                problems.append(
                    f"page {p} owned by both {claimed[p]} and {name}"
                )
                continue
            claimed[p] = name
            if p in free_set:
                problems.append(
                    f"page {p} owned by {name} but also on the free list"
                )
            if p in reserved:
                problems.append(f"{name} owns reserved page {p}")
    all_pages = set(range(total))
    accounted = free_set | set(claimed) | set(reserved)
    leaked = all_pages - accounted
    if leaked:
        problems.append(f"leaked pages (no owner, not free): {sorted(leaked)}")
    phantom = accounted - all_pages
    if phantom:
        problems.append(f"unknown page ids: {sorted(phantom)}")
    for name, pages in (shared or {}).items():
        for p in pages:
            p = int(p)
            if p in free_set:
                problems.append(
                    f"{name} maps shared page {p} that is on the free list"
                )
            elif p not in claimed:
                problems.append(
                    f"{name} maps shared page {p} that no owner holds"
                )
    return problems


def gather_bucket(end_pos: int, page_size: int, pages_per_seq: int) -> int:
    """Page-table gather width for a chunk program whose queries/writes
    end at position ``end_pos``: enough table entries to cover it,
    rounded up to a power of two so at most log2(pages_per_seq)
    programs compile per chunk width. Shared by the prefix-cache suffix
    prefill and the speculative verify chunks — one definition, one
    program-key convention."""
    need = -(-int(end_pos) // page_size)
    if need <= 1:
        return 1
    return min(1 << max(need - 1, 0).bit_length(), pages_per_seq)


def rollback_kv(cache: PagedKVCache, slot, new_len) -> PagedKVCache:
    """Truncate ``slot``'s cached length to ``new_len`` (speculative
    decoding's KV rollback: a verify chunk wrote K+1 rows, acceptance
    kept a prefix, and every row past the accepted length becomes
    ordinary garbage-beyond-kv_len — masked by causality, overwritten
    by the next append). The page table is untouched: rejected rows
    live in pages the sequence still owns, so truncation is a length
    write, never an allocator round trip. ``slot``/``new_len`` are
    traced — one compiled program serves every rollback.

    Quantized pools roll back in lockstep for free: scales are
    per-page, not per-row, and monotone within a page's lifetime, so
    the scale that covered the rejected rows still upper-bounds every
    retained row — truncating ``kv_len`` leaves the codes/scale pair
    exact for the live prefix (the next append may grow it again;
    a later write at page offset 0 resets it)."""
    return dataclasses.replace(
        cache,
        kv_len=tdt_kv_set_len(
            cache.kv_len,
            jnp.asarray(slot, jnp.int32),
            jnp.asarray(new_len, jnp.int32),
        ),
    )


# Donated: rollback runs once per rejected verify chunk — an eager
# .at[].set would copy the (small) kv_len array but break the cache
# threading discipline every other cache op follows.
@functools.partial(jax.jit, donate_argnums=(0,))
def tdt_kv_set_len(kv_len, slot, n):
    return kv_len.at[slot].set(n)


def move_kv_rows(
    cache: PagedKVCache,
    slot: int,
    src: list[int],
    dst: list[int],
) -> PagedKVCache:
    """Move token rows of ``slot`` from absolute positions ``src`` to
    ``dst`` (both K and V, all layers) — the tree-speculation COMMIT:
    a verify chunk wrote the draft tree's nodes at DFS storage
    positions ``kv + i``, acceptance picked one root path, and its
    nodes compact to the contiguous positions ``kv+1 .. kv+a`` a linear
    decode would have written (the subsequent ``kv_len`` rollback then
    makes every losing branch ordinary garbage-beyond-kv_len). Rows are
    gathered BEFORE any scatter, so overlapping src/dst are safe; DFS
    order guarantees a node's storage index is ≥ its depth, so every
    move is leftward (``dst[i] <= src[i]``) and distinct dst never
    collide. Moved rows are bit-identical to linearly-written rows:
    K/V content depends only on the token and its rope position, and
    tree nodes rope at their DEPTH, not their storage slot.

    Full-width pools only: a quantized pool's per-page scales make a
    row hop between pages a requantization event whose rounding depends
    on move order — the engine keeps quantized pools on width-1 chains,
    which never need moves. Positions are traced (one compiled program
    per move-count bucket); the move list is right-padded to a power of
    two with position-0 self-moves (position 0 is a prompt row, never a
    real src or dst, and duplicate identical writes are benign).
    """
    if cache.quantized:
        raise ValueError(
            "move_kv_rows is full-width-pool only; quantized pools run "
            "width-1 speculation chains (no row moves)"
        )
    if len(src) != len(dst):
        raise ValueError(f"src/dst length mismatch ({len(src)} vs {len(dst)})")
    pairs = [(int(s), int(d)) for s, d in zip(src, dst) if int(s) != int(d)]
    if not pairs:
        return cache
    m = 1 << (len(pairs) - 1).bit_length()
    pairs += [(0, 0)] * (m - len(pairs))
    src_a = jnp.asarray([p[0] for p in pairs], jnp.int32)
    dst_a = jnp.asarray([p[1] for p in pairs], jnp.int32)
    page = int(cache.k_pages.shape[3])
    k_pages, v_pages = tdt_kv_move_rows(
        cache.k_pages, cache.v_pages, cache.page_table[slot],
        src_a, dst_a, page,
    )
    return dataclasses.replace(cache, k_pages=k_pages, v_pages=v_pages)


# Donated like the other pool writers (an eager scatter would copy the
# pool to move a handful of rows); one program per move-count bucket.
@functools.partial(jax.jit, static_argnums=(5,), donate_argnums=(0, 1))
def tdt_kv_move_rows(kp, vp, table_row, src, dst, page: int):
    ps, so = jnp.take(table_row, src // page), src % page
    pd, do = jnp.take(table_row, dst // page), dst % page
    # Two advanced indices split by slices → advanced axes lead: the
    # gathered rows are [m, L, H, hd]. Gather both pools before either
    # scatter so overlapping positions read pre-move content.
    rows_k = kp[:, ps, :, so, :]
    rows_v = vp[:, ps, :, so, :]
    kp = kp.at[:, pd, :, do, :].set(rows_k)
    vp = vp.at[:, pd, :, do, :].set(rows_v)
    return kp, vp


def truncate_pages(
    pool: PagePool,
    pages: list[int],
    keep_tokens: int,
    page_size: int,
    *,
    shared: int = 0,
) -> list[int]:
    """Release every page of ``pages`` lying wholly past ``keep_tokens``
    cached tokens back to ``pool``; returns the retained prefix.

    The first ``shared`` entries are prefix-cache-shared (mapped by
    refcount, owned by the radix tree) and are NEVER freed here
    regardless of ``keep_tokens`` — releasing them would double-free a
    page another sequence still attends. No-ops safely at page
    boundaries: ``keep_tokens`` landing exactly on a boundary keeps
    ``keep_tokens / page_size`` pages, and ``keep_tokens`` beyond the
    page list keeps everything.
    """
    if shared < 0 or shared > len(pages):
        raise ValueError(
            f"shared={shared} out of range for {len(pages)} pages"
        )
    keep = max(-(-max(int(keep_tokens), 0) // page_size), shared)
    if keep >= len(pages):
        return pages
    pool.release(pages[keep:])
    return pages[:keep]


def paged_cache_specs(axis: str = "tp", quantized: bool = False,
                      recurrent: bool = False):
    """shard_map PartitionSpecs matching :func:`init_paged_cache`.
    ``quantized`` adds the per-page-per-head scale specs (head-sharded
    like the pool) and ``recurrent`` the replicated recurrent state's;
    unset matches the pytree without them exactly."""
    from jax.sharding import PartitionSpec as P

    state = dict(ssm_state=P(), conv_state=P(), live=P()) if recurrent else {}
    return PagedKVCache(
        **state,
        k_pages=P(None, None, axis, None, None),
        v_pages=P(None, None, axis, None, None),
        page_table=P(),
        kv_len=P(),
        k_scale=P(None, None, axis) if quantized else None,
        v_scale=P(None, None, axis) if quantized else None,
    )


def write_prefill(
    cache: PagedKVCache,
    b_idx: int,
    k_dense: jax.Array,  # [L, 1, Hkv_loc, S, hd] — one filled sequence
    v_dense: jax.Array,
    true_len: int,
) -> PagedKVCache:
    """Scatter a dense-prefilled sequence into its pages (host-level;
    pages are contiguous S tiles so each page is one slice copy).
    ``true_len`` is a static prompt length; ceil(true_len/page) pages
    are written (the dense source must cover that many positions)."""
    page = cache.k_pages.shape[3]
    npages = -(-int(true_len) // page)
    if k_dense.shape[3] < npages * page:
        raise ValueError(
            f"dense prefill holds {k_dense.shape[3]} positions; "
            f"{npages * page} needed for true_len={true_len}"
        )

    row = cache.page_table[b_idx]
    kv_len = cache.kv_len.at[b_idx].set(jnp.asarray(true_len, jnp.int32))
    if cache.quantized:
        tl = jnp.asarray(true_len, jnp.int32)
        k_pages, k_scale = tdt_kv_scatter_q(
            cache.k_pages, cache.k_scale, k_dense, row, tl, npages, page
        )
        v_pages, v_scale = tdt_kv_scatter_q(
            cache.v_pages, cache.v_scale, v_dense, row, tl, npages, page
        )
        return PagedKVCache(
            k_pages=k_pages, v_pages=v_pages, page_table=cache.page_table,
            kv_len=kv_len, k_scale=k_scale, v_scale=v_scale,
        )
    return PagedKVCache(
        k_pages=tdt_kv_scatter(cache.k_pages, k_dense, row, npages, page),
        v_pages=tdt_kv_scatter(cache.v_pages, v_dense, row, npages, page),
        page_table=cache.page_table,
        kv_len=kv_len,
    )


# Donated + jitted (as is the quantized scatter below): the
# page-by-page scatter updates the pool in place; eager
# dynamic_update_slices would copy the whole (GB-scale) pool once per
# page.
@functools.partial(jax.jit, static_argnums=(3, 4), donate_argnums=(0,))
def tdt_kv_scatter(pages, dense, table_row, npages: int, page: int):
    for j in range(npages):
        pid = table_row[j]
        chunk = jax.lax.dynamic_slice_in_dim(
            dense, j * page, page, axis=3
        )[:, 0][:, None]
        pages = jax.lax.dynamic_update_slice(
            pages, chunk.astype(pages.dtype), (0, pid, 0, 0, 0)
        )
    return pages


@functools.partial(jax.jit, static_argnums=(5, 6), donate_argnums=(0, 1))
def tdt_kv_scatter_q(pages, scales, dense, table_row, true_len,
                     npages: int, page: int):
    """Quantized :func:`tdt_kv_scatter`: every written page is a FRESH full
    write, so its scale is set absolutely from the page's amax (never
    grown from a previous tenant's stale scale). Dense rows at
    positions ≥ ``true_len`` are ZEROED before quantization: the dense
    scratch is reused across prefills, so the last partial page would
    otherwise fold a PREVIOUS request's stale KV into this page's amax
    — inflating the scale and making the codes depend on admission
    order."""
    for j in range(npages):
        pid = table_row[j]
        chunk = jax.lax.dynamic_slice_in_dim(
            dense, j * page, page, axis=3
        )[:, 0]  # [L, H, page, hd]
        pos = j * page + jnp.arange(page, dtype=jnp.int32)
        chunk = jnp.where(
            (pos < true_len)[None, None, :, None],
            chunk.astype(jnp.float32), 0.0,
        )
        sc = page_scales(chunk)  # [L, H]
        pages = jax.lax.dynamic_update_slice(
            pages, quantize_page(chunk, sc)[:, None], (0, pid, 0, 0, 0)
        )
        scales = jax.lax.dynamic_update_slice(
            scales, sc[:, None], (0, pid, 0)
        )
    return pages, scales


def copy_page(cache: PagedKVCache, src: int, dst: int) -> PagedKVCache:
    """Copy one pool page (both K and V, all layers) — the prefix
    cache's copy-on-write: a partially matched shared page is cloned
    into the new sequence's private page before its suffix writes into
    it. ``src``/``dst`` are traced, so one compiled program serves every
    COW."""
    s = jnp.asarray(src, jnp.int32)
    d = jnp.asarray(dst, jnp.int32)
    return dataclasses.replace(
        cache,
        k_pages=tdt_kv_copy_page(cache.k_pages, s, d),
        v_pages=tdt_kv_copy_page(cache.v_pages, s, d),
        # COW on a quantized pool clones the scale WITH the codes — the
        # pair is the page's content; cloning one without the other
        # would dequantize the copy under the wrong amax.
        k_scale=(
            None if cache.k_scale is None
            else tdt_kv_copy_page(cache.k_scale, s, d)
        ),
        v_scale=(
            None if cache.v_scale is None
            else tdt_kv_copy_page(cache.v_scale, s, d)
        ),
    )


# Donated for the same reason as tdt_kv_scatter: an eager update would
# copy the whole pool to move one page. Shape-polymorphic over the
# trailing dims, so the same program body serves pools AND their
# [L, P, H] scale arrays (jit re-specializes per shape).
@functools.partial(jax.jit, donate_argnums=(0,))
def tdt_kv_copy_page(pages, s, d):
    return jax.lax.dynamic_update_slice_in_dim(
        pages, jax.lax.dynamic_slice_in_dim(pages, s, 1, axis=1), d, axis=1
    )


def append(
    cache: PagedKVCache,
    k_new: jax.Array,  # [L, B, Hkv_loc, hd] — one token per sequence
    v_new: jax.Array,
) -> PagedKVCache:
    """Append one token per sequence at ``kv_len`` (jit-safe); the
    NS=1 case of :func:`append_n` (one scatter per pool)."""
    return append_n(cache, k_new[:, :, :, None, :], v_new[:, :, :, None, :])


def append_n(
    cache: PagedKVCache,
    k_new: jax.Array,  # [L, B, Hkv_loc, NS, hd] — NS tokens per sequence
    v_new: jax.Array,
    n_valid: jax.Array | None = None,  # [B] i32 — rows kept per sequence
) -> PagedKVCache:
    """Append ``NS`` tokens per sequence at ``kv_len`` in ONE scatter.

    The multi-step megakernel emits NS rows per launch; appending them
    row-by-row would pay the per-op dispatch tax NS times (the very
    cost multi-step exists to amortize). One advanced-index scatter
    per pool handles all (b, step) rows — page-boundary crossings fall
    out of the per-row (page_id, offset) computation.

    ``n_valid[b]`` (None → NS) routes row's ``>= n_valid[b]`` to the
    trash page instead of the sequence's own pages: a serving launch
    whose row finishes mid-launch emits guaranteed-overshoot rows
    (``gen_len`` bound, known at launch time), and on an int8 pool
    those rows would otherwise GROW the final page's scale before the
    page retires into the radix tree — quantization noise paid by
    every later request reusing that prefix. Trash-routed rows are
    discarded either way; this keeps retired pages' scales covering
    real rows only. (eos can still finish earlier than ``gen_len``;
    those rare rows land in owned pages as ordinary
    garbage-beyond-kv_len, same as the single-step path.)

    Caller contract: ``kv_len[b] + NS`` stays within the page table's
    capacity for every row.
    """
    page = cache.k_pages.shape[3]
    L, B, H, NS, hd = k_new.shape
    pos = cache.kv_len[:, None] + jnp.arange(NS, dtype=jnp.int32)[None]
    pids = jnp.take_along_axis(cache.page_table, pos // page, axis=1)
    if n_valid is not None:
        step = jnp.arange(NS, dtype=jnp.int32)[None]
        pids = jnp.where(step < n_valid[:, None], pids, 0)
    flat_p = pids.reshape(-1)        # [B*NS]
    flat_o = (pos % page).reshape(-1)

    def write(pages, new):
        # Two advanced indices split by slices → advanced axes move to
        # the front: the indexed view is [B*NS, L, H, hd]. NO
        # unique_indices: inactive/finished rows all route to the trash
        # page (identical indices), where duplicate writes are fine
        # under scatter's last-write-wins but UB if claimed unique.
        upd = new.transpose(1, 3, 0, 2, 4).reshape(B * NS, L, H, hd)
        return pages.at[:, flat_p, :, flat_o, :].set(upd.astype(pages.dtype))

    if cache.quantized:
        # Quantized append: ONE scale-protocol implementation
        # (:func:`quantized_row_scatter` — reset at offset 0, grow +
        # requant otherwise), vmapped over the layer axis and applied
        # STEP BY STEP (lax.fori over NS, one C=B scatter per step)
        # rather than as one B·NS-row batch: a batch scatter would grow
        # each touched page's scale ONCE to cover all NS rows, while
        # the single-step serving path grows it row-by-row with a
        # requant at each growth — a different rounding-event order
        # that leaves different codes behind. Serving shares retired
        # pages across requests through the radix tree, so the fused
        # NS-launch must leave the pool BIT-IDENTICAL to NS unfused
        # steps over the same tokens; sequencing the scatters inside
        # the one program keeps that while still dispatching once.
        pids_q = pids  # [B, NS] (trash-routed where n_valid caps)
        offs_q = pos % page

        def step_scatter(s, carry):
            kp, ks, vp, vs = carry
            rows_k = jax.lax.dynamic_index_in_dim(k_new, s, axis=3)[
                :, :, :, 0, :]  # [L, B, H, hd]
            rows_v = jax.lax.dynamic_index_in_dim(v_new, s, axis=3)[
                :, :, :, 0, :]
            p_s = jax.lax.dynamic_index_in_dim(pids_q, s, axis=1)[:, 0]
            o_s = jax.lax.dynamic_index_in_dim(offs_q, s, axis=1)[:, 0]
            kp, ks = _row_scatter_layers(kp, ks, rows_k, p_s, o_s)
            vp, vs = _row_scatter_layers(vp, vs, rows_v, p_s, o_s)
            return kp, ks, vp, vs

        k_pages, k_scale, v_pages, v_scale = jax.lax.fori_loop(
            0, NS, step_scatter,
            (cache.k_pages, cache.k_scale, cache.v_pages, cache.v_scale),
        )
        return PagedKVCache(
            k_pages=k_pages, v_pages=v_pages,
            page_table=cache.page_table, kv_len=cache.kv_len + NS,
            k_scale=k_scale, v_scale=v_scale,
        )
    return PagedKVCache(
        k_pages=write(cache.k_pages, k_new),
        v_pages=write(cache.v_pages, v_new),
        page_table=cache.page_table,
        kv_len=cache.kv_len + NS,
    )


def gather_pages(cache: PagedKVCache, page_ids: list[int]):
    """Gather the listed pool pages to HOST arrays — the slot-migration
    export path (``models/slot_state.py``): one ``take`` per pool on the
    page axis, fetched as-is (int8 codes stay codes, scales ride along),
    so the snapshot is byte-exact with respect to the pool it came from.
    Returns ``(k_pages, v_pages, k_scale, v_scale)`` numpy arrays —
    ``[L, n, Hkv, page, hd]`` pools and ``[L, n, Hkv]`` scales (scales
    are None on an unquantized pool)."""
    ids = jnp.asarray([int(p) for p in page_ids], jnp.int32)
    k = np.asarray(tdt_kv_gather_pages(cache.k_pages, ids))
    v = np.asarray(tdt_kv_gather_pages(cache.v_pages, ids))
    if cache.quantized:
        ks = np.asarray(tdt_kv_gather_pages(cache.k_scale, ids))
        vs = np.asarray(tdt_kv_gather_pages(cache.v_scale, ids))
    else:
        ks = vs = None
    return k, v, ks, vs


# NOT donated (unlike every writer above): the gather is a pure read —
# the pool stays live for the decode loop that owns it. jit
# re-specializes per (pool shape, id count); migration exports reuse a
# handful of shapes per engine.
@jax.jit
def tdt_kv_gather_pages(pages, ids):
    return jnp.take(pages, ids, axis=1)


def write_page(cache: PagedKVCache, pid: int, k_page, v_page,
               k_scale=None, v_scale=None) -> PagedKVCache:
    """Write one page's full content (both pools, all layers) into pool
    page ``pid`` — the slot-migration import path: the payload arrays
    come from :func:`gather_pages` on another engine and are written
    VERBATIM (int8 codes + their scale as a pair), so a migrated slot's
    dequantized KV is bit-identical to the source's. ``k_page``/
    ``v_page`` are ``[L, Hkv, page, hd]``; scales ``[L, Hkv]`` (required
    iff the pool is quantized)."""
    if cache.quantized != (k_scale is not None):
        raise ValueError(
            "page payload and pool disagree on quantization "
            f"(pool quantized={cache.quantized}, scales "
            f"{'present' if k_scale is not None else 'absent'})"
        )
    p = jnp.asarray(pid, jnp.int32)
    kp = tdt_kv_write_page(cache.k_pages, p,
                         jnp.asarray(k_page, cache.k_pages.dtype))
    vp = tdt_kv_write_page(cache.v_pages, p,
                         jnp.asarray(v_page, cache.v_pages.dtype))
    ks, vs = cache.k_scale, cache.v_scale
    if cache.quantized:
        ks = tdt_kv_write_page(ks, p, jnp.asarray(k_scale, jnp.float32))
        vs = tdt_kv_write_page(vs, p, jnp.asarray(v_scale, jnp.float32))
    return dataclasses.replace(
        cache, k_pages=kp, v_pages=vp, k_scale=ks, v_scale=vs
    )


# Donated like tdt_kv_scatter (an eager update would copy the pool to
# move one page); shape-polymorphic over trailing dims so the same body
# serves pools and their [L, P, H] scale arrays.
@functools.partial(jax.jit, donate_argnums=(0,))
def tdt_kv_write_page(pages, pid, data):
    return jax.lax.dynamic_update_slice_in_dim(
        pages, data[:, None], pid, axis=1
    )


def as_dense(cache: PagedKVCache, layer=None):
    """Materialize contiguous ``[L?, B, Hkv_loc, S_max, hd]`` views by
    gathering pages through the table (decode feeds this to
    ``flash_decode``; the page gather is a take on the page axis).
    Quantized pools dequantize the gathered view (f32) — this is the
    tests/fallback path, never the serving hot path, which reads int8
    codes straight through the kernels."""
    from triton_distributed_tpu.ops.attention.flash_decode import (
        pages_to_dense,
        scales_to_dense,
    )

    kp = cache.k_pages if layer is None else cache.k_pages[layer]
    vp = cache.v_pages if layer is None else cache.v_pages[layer]
    k = pages_to_dense(kp, cache.page_table)
    v = pages_to_dense(vp, cache.page_table)
    if cache.quantized:
        page = cache.k_pages.shape[3]
        ks = cache.k_scale if layer is None else cache.k_scale[layer]
        vs = cache.v_scale if layer is None else cache.v_scale[layer]
        k = k.astype(jnp.float32) * scales_to_dense(
            ks, cache.page_table, page
        )[..., None]
        v = v.astype(jnp.float32) * scales_to_dense(
            vs, cache.page_table, page
        )[..., None]
    return k, v
