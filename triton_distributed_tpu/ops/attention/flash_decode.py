"""GQA flash-decode: split-KV kernel + cross-rank combine.

Parity: reference ``kernels/nvidia/flash_decode.py`` — split-KV kernel
:130 (each program attends q over one KV chunk, emitting a partial
output + log-sum-exp), intra-rank combine :393, and the **inter-rank**
combine :482 where ranks exchange (partial O, LSE) via ``putmem_signal``
and merge with a log-sum-exp weighting — scaling decode 1→32 GPUs
(README "Scaling of Distributed Flash-Decode").

TPU design: the split-KV pass is one Pallas kernel, grid =
(batch, kv_heads, kv_chunks) with the GQA head group riding the sublane
dimension (q block ``[group, d]``), context length masked per chunk from
a scalar-prefetch ``kv_len``. The combine is a log-sum-exp merge —
intra-chip over the chunk axis, and for the distributed form across the
``sp`` mesh axis after an all-gather of the (O, LSE) partials (XLA
collective or our Pallas ring — the device-initiated putmem analog).

The PAGED form (:func:`paged_flash_decode`, the served decode step's
kernel) is a kernel of its own: one grid step per live (sequence, page)
pair with every KV head of the page in one block, and the merge over a
sequence's pages done online inside the kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_distributed_tpu.ops.collectives.all_gather import all_gather
from triton_distributed_tpu.ops.common import exporting_portable, interpret_mode

_NEG_INF = -1e30


def _decode_body(
    kv_len_ref,  # [B] int32 SMEM (scalar prefetch)
    q_ref,       # [1, 1, group, d] VMEM
    k_ref,       # [1, 1, chunk, d] VMEM — full-width, or int8 codes
    v_ref,       # [1, 1, chunk, d] VMEM
    ks_ref,      # [B*Hkv*C] f32 SMEM (scalar prefetch) or None — K scales
    vs_ref,      # [B*Hkv*C] f32 SMEM (scalar prefetch) or None — V scales
    o_ref,       # [1, 1, 1, group, d] VMEM f32 — partial output, chunk ci
    lse_ref,     # [1, 1, C, group] VMEM f32 — full chunk column, row ci
                 # written per step (Mosaic needs the block's trailing two
                 # dims to match the array, so the block spans all chunks)
    *,
    sm_scale: float,
    chunk_k: int,
):
    b = pl.program_id(0)
    ci = pl.program_id(2)
    start = ci * chunk_k
    # This (sequence, kv head, chunk)'s slot in the flattened scales.
    si = (b * pl.num_programs(1) + pl.program_id(1)) * pl.num_programs(2) + ci
    valid = kv_len_ref[b] - start  # may be <=0 (fully masked chunk)

    @pl.when(valid > 0)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        group = q.shape[0]
        # In-register dequant: the symmetric per-chunk scale is a
        # scalar, so it folds into the softmax multiplier AFTER QK^T —
        # the MXU sees the raw int8-widened codes and full-width K
        # never exists anywhere (not even in VMEM).
        mult = sm_scale if ks_ref is None else sm_scale * ks_ref[si]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * mult  # [group, chunk]
        cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(cols < valid, s, _NEG_INF)
        m = jnp.max(s, axis=1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=1, keepdims=True)
        if vs_ref is None:
            o = jnp.dot(
                p.astype(v_ref.dtype), v_ref[0, 0],
                preferred_element_type=jnp.float32,
            )
        else:
            # P·V over the codes, scale folded after the matmul.
            o = jnp.dot(
                p, v_ref[0, 0].astype(jnp.float32),
                preferred_element_type=jnp.float32,
            ) * vs_ref[si]
        o_ref[0, 0, 0] = o / l
        lse_ref[0, 0, ci] = (m + jnp.log(l))[:, 0]

    @pl.when(valid <= 0)
    def _skip():
        o_ref[:] = jnp.zeros_like(o_ref)
        lse_ref[0, 0, ci] = jnp.full(lse_ref.shape[-1:], _NEG_INF, jnp.float32)


def _decode_kernel(kv_len_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, **kw):
    _decode_body(
        kv_len_ref, q_ref, k_ref, v_ref, None, None, o_ref, lse_ref, **kw
    )


def _decode_kernel_q(
    kv_len_ref, ks_ref, vs_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, **kw
):
    _decode_body(
        kv_len_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, lse_ref, **kw
    )


def lse_combine(o_parts: jax.Array, lse_parts: jax.Array, part_axis: int = 0):
    """Merge partial attention outputs by log-sum-exp weighting.

    Parity: reference combine kernels (``flash_decode.py:393,482``).
    ``o_parts [..., P, ..., d]`` f32 with partials on ``part_axis``;
    ``lse_parts`` matching without d. Returns (o, lse) reduced over P.
    """
    m = jnp.max(lse_parts, axis=part_axis, keepdims=True)
    m = jnp.maximum(m, _NEG_INF)  # all-masked guard
    w = jnp.exp(lse_parts - m)
    den = jnp.sum(w, axis=part_axis)
    o = jnp.sum(o_parts * w[..., None], axis=part_axis) / jnp.maximum(
        den[..., None], 1e-30
    )
    lse = jnp.squeeze(m, part_axis) + jnp.log(jnp.maximum(den, 1e-30))
    return o, lse


def flash_decode(
    q: jax.Array,        # [B, Hq, D]
    k_cache: jax.Array,  # [B, Hkv, S, D]
    v_cache: jax.Array,  # [B, Hkv, S, D]
    kv_len: jax.Array,   # [B] int32 — valid context length per sequence
    *,
    sm_scale: float | None = None,
    chunk_k: int = 256,
    return_lse: bool = False,
    k_scale: jax.Array | None = None,  # [B, Hkv, S/chunk_k] f32
    v_scale: jax.Array | None = None,
    interpret=None,
):
    """Single-token GQA decode attention over a (possibly padded) KV cache.

    Parity: ``gqa_fwd_batch_decode`` (``flash_decode.py:763``). Returns
    ``o [B, Hq, D]`` (q.dtype) and optionally ``lse [B, Hq]`` f32 for the
    cross-rank combine.

    ``k_scale``/``v_scale`` enable the int8 storage mode: ``k_cache``/
    ``v_cache`` hold int8 codes and the per-chunk-per-head symmetric
    scales (one f32 per ``chunk_k`` block) dequantize IN-REGISTER inside
    the kernel — full-width KV never materializes.
    """
    b, hq, d = q.shape
    _, hkv, s, _ = k_cache.shape
    if hq % hkv:
        raise ValueError(f"q heads {hq} not a multiple of kv heads {hkv}")
    group = hq // hkv
    if sm_scale is None:
        sm_scale = d**-0.5
    chunk_k = min(chunk_k, s)
    if s % chunk_k:
        raise ValueError(f"cache len {s} not divisible by chunk_k {chunk_k}")
    num_chunks = s // chunk_k
    kv_len = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32), (b,))
    quant = k_scale is not None
    if quant != (v_scale is not None):
        raise ValueError("k_scale and v_scale must be passed together")
    for name, sc in (("k_scale", k_scale), ("v_scale", v_scale)):
        if quant and sc.shape != (b, hkv, num_chunks):
            raise ValueError(
                f"{name} shape {sc.shape} != per-chunk layout "
                f"{(b, hkv, num_chunks)} (chunk_k={chunk_k})"
            )

    # jax.export can't serialize the host callbacks interpret-mode Pallas
    # lowers to; exports traced off-TPU take the pure-XLA reference path.
    resolved = interpret_mode() if interpret is None else interpret
    if resolved and exporting_portable():
        if quant:
            k_cache = k_cache.astype(jnp.float32) * jnp.repeat(
                k_scale, chunk_k, axis=-1
            )[..., None]
            v_cache = v_cache.astype(jnp.float32) * jnp.repeat(
                v_scale, chunk_k, axis=-1
            )[..., None]
        return gqa_decode_reference(
            q, k_cache, v_cache, kv_len,
            sm_scale=sm_scale, return_lse=return_lse,
        )

    qg = q.reshape(b, hkv, group, d)
    grid = (b, hkv, num_chunks)
    # Index maps receive the scalar-prefetch refs as trailing args.
    in_specs = [
        pl.BlockSpec((1, 1, group, d), lambda b, h, ci, *_: (b, h, 0, 0)),
        pl.BlockSpec((1, 1, chunk_k, d), lambda b, h, ci, *_: (b, h, ci, 0)),
        pl.BlockSpec((1, 1, chunk_k, d), lambda b, h, ci, *_: (b, h, ci, 0)),
    ]
    scalars = [kv_len]
    if quant:
        # One f32 per (sequence, head, chunk): read as scalars from
        # SMEM. A (1, 1, 1) VMEM block of the scale array is below
        # Mosaic's (8, 128) tile and is refused by the TPU compiler.
        scalars += [k_scale.reshape(-1), v_scale.reshape(-1)]
    kernel = functools.partial(
        _decode_kernel_q if quant else _decode_kernel,
        sm_scale=sm_scale, chunk_k=chunk_k,
    )
    o_parts, lse_parts = pl.pallas_call(
        kernel,
        name="tdt_flash_decode",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=grid,
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec(
                    (1, 1, 1, group, d), lambda b, h, ci, *_: (b, h, ci, 0, 0)
                ),
                pl.BlockSpec(
                    (1, 1, num_chunks, group), lambda b, h, ci, *_: (b, h, 0, 0)
                ),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, num_chunks, group, d), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, num_chunks, group), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=resolved,
    )(*scalars, qg, k_cache, v_cache)

    o, lse = lse_combine(o_parts, lse_parts, part_axis=2)  # [B, Hkv, group, d]
    o = o.reshape(b, hq, d).astype(q.dtype)
    if return_lse:
        return o, lse.reshape(b, hq)
    return o


def paged_decode_walk(kv_len: jax.Array, page: int, pps: int):
    """The grid of :func:`paged_flash_decode`: one step per LIVE (slot,
    page) pair, ordered by slot.

    Returns ``(slot [B * pps], page_of [B * pps], steps)``: grid step
    ``i < steps`` attends sequence ``slot[i]``'s table entry
    ``page_of[i]``; ``steps = sum_b clip(ceil(kv_len[b] / page), 1,
    pps)`` (an empty row still gets the one step that writes its
    zeros). Entries past ``steps`` are in range and never read. The
    lists depend on ``kv_len`` alone, so a caller that runs the kernel
    once a layer derives them ONCE a step, outside its layer scan, and
    hands them in as ``walk=``: as XLA ops inside the call they would
    run again in every layer.
    """
    kv_len = jnp.asarray(kv_len, jnp.int32)
    b = kv_len.shape[0]
    pages = jnp.clip(pl.cdiv(kv_len, page), 1, pps)
    ends = jnp.cumsum(pages)
    step = jnp.arange(b * pps, dtype=jnp.int32)
    slot = jnp.minimum(
        jnp.searchsorted(ends, step, side="right").astype(jnp.int32), b - 1
    )
    page_of = jnp.minimum(step - (ends - pages)[slot], pps - 1)
    return slot, page_of, ends[-1]


def _paged_decode_kernel(
    kv_len_ref,  # [B] int32 SMEM (scalar prefetch)
    table_ref,   # [B, pps] int32 SMEM — consumed by the index maps
    slot_ref,    # [B * pps] int32 SMEM — the walk: sequence of step i
    page_ref,    # [B * pps] int32 SMEM — ... and its table entry
    *refs,
    sm_scale: float,
    quant: bool,
    return_lse: bool,
):
    """One live (slot, page) pair: every KV head of the page against
    that sequence's q heads, folded into the sequence's running softmax
    (the pairs of a sequence are consecutive grid steps, so its
    accumulators and output block stay resident in VMEM)."""
    if quant:
        # [B * pps * Hkv] f32 SMEM — the table's pages' scales.
        ks_ref, vs_ref, *refs = refs
    q_ref, k_ref, v_ref, o_ref, *refs = refs  # q/o [1, Hkv, group, d]
    if return_lse:                            # k/v [1, Hkv, page, d]
        lse_ref, *refs = refs                 # [1, Hkv, group, 1]
    m_ref, l_ref, acc_ref = refs  # VMEM f32 [Hkv, group, 1 | 1 | d]
    i = pl.program_id(0)
    b, ci = slot_ref[i], page_ref[i]
    pps = table_ref.shape[1]
    hkv, page = k_ref.shape[1:3]
    valid = kv_len_ref[b] - ci * page  # keys of this page that count

    @pl.when(ci == 0)
    def _first_page():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(valid > 0)
    def _accumulate():
        # The MXU takes the pool's own dtype (bf16 x bf16 products are
        # exact in the f32 accumulator; an f32 upcast buys nothing and
        # costs a multi-pass matmul). int8 codes widen exactly for
        # QK^T; their P.V keeps f32 operands (below).
        dt = jnp.promote_types(q_ref.dtype, k_ref.dtype)
        cols = jax.lax.broadcasted_iota(jnp.int32, (q_ref.shape[2], page), 1)
        for h in range(hkv):
            # In-register dequant: the symmetric per-page-per-head scale
            # is a scalar, so it folds into the softmax multiplier AFTER
            # QK^T and into the accumulator AFTER P·V — full-width KV
            # never exists anywhere (not even in VMEM).
            si = (b * pps + ci) * hkv + h
            s = jax.lax.dot_general(
                q_ref[0, h].astype(dt), k_ref[0, h].astype(dt),
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
            ) * (sm_scale * ks_ref[si] if quant else sm_scale)
            s = jnp.where(cols < valid, s, _NEG_INF)  # [group, page]
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            if quant:
                # P stays f32 against the widened codes, as in the dense
                # body: with bf16 q, ``dt`` would round P to 8 bits.
                pv = jnp.dot(
                    p, v_ref[0, h].astype(jnp.float32),
                    preferred_element_type=jnp.float32,
                ) * vs_ref[si]
            else:
                pv = jnp.dot(
                    p.astype(dt), v_ref[0, h].astype(dt),
                    preferred_element_type=jnp.float32,
                )
            m_ref[h] = m_new
            l_ref[h] = alpha * l_ref[h] + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[h] = alpha * acc_ref[h] + pv

    # The sequence's last live page (or its table's last entry).
    @pl.when((valid <= page) | (ci == pps - 1))
    def _last_page():
        l = jnp.maximum(l_ref[...], 1e-30)  # an empty row reads 0
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
        if return_lse:
            lse_ref[0] = m_ref[...] + jnp.log(l)


def paged_flash_decode(
    q: jax.Array,        # [B, Hq, D]
    k_pages: jax.Array,  # [L, P, Hkv, page, D] whole pool, or [P, Hkv, page, D]
    v_pages: jax.Array,
    page_table: jax.Array,  # [B, pages_per_seq] int32
    kv_len: jax.Array,      # [B] int32 — valid context length
    *,
    layer: jax.Array | int | None = None,  # which layer of a 5-D pool
    walk=None,  # paged_decode_walk(kv_len, page, pages_per_seq), hoisted
    sm_scale: float | None = None,
    return_lse: bool = False,
    k_scale: jax.Array | None = None,  # [(L,) P, Hkv] f32 — per-page-per-head
    v_scale: jax.Array | None = None,
    interpret=None,
):
    """Single-token GQA decode attention straight over a paged KV pool.

    Parity: the reference megakernel's paged decode
    (``mega_triton_kernel/models/paged_kv_cache.py:58`` + its attention
    task reading through the page table). TPU design: the grid is ONE
    axis with a step per live (sequence, page) pair —
    ``sum_b ceil(kv_len[b] / page)`` steps (:func:`paged_decode_walk`),
    a dynamic bound — and each step carries every KV head of its page.
    The walk and the page table ride as scalar-prefetch operands and the
    BlockSpec index maps dereference them: step ``i`` fetches pool page
    ``table[slot[i], page_of[i]]`` as one ``(1, Hkv, page, D)`` block (in
    the pool's layout all heads of a page are one contiguous run: 256 KB
    at Qwen3-4B), so no gather materializes, a short sequence costs its
    own pages and not the longest one's, and nothing is fetched for a
    table entry past a sequence's length. The body is a static loop over
    the KV heads (the GQA group rides the sublanes, q block
    ``[Hkv, group, D]``) feeding the MXU the pool's own dtype with f32
    accumulation, the softmax in f32 and ONLINE across a sequence's
    pages: its pairs are consecutive steps, so the running max, sum and
    accumulator stay in VMEM and ``o [B, Hq, D]`` (and the LSE) leave
    the kernel finished — no partials, no merge in XLA.

    With ``k_scale``/``v_scale`` (the pool's per-page-per-head int8
    scales), the K/V blocks are int8 codes and each step reads its
    page's scales (gathered through the SAME table indirection, SMEM
    scalars), dequantizing in-register after QK^T / P·V — the decode
    step streams HALF the bf16 pool's HBM bytes and full-width KV never
    exists.

    The pool is addressed in place by (layer, page): given the WHOLE
    ``[L, P, Hkv, page, D]`` pool (scales ``[L, P, Hkv]``) and ``layer``
    (traced — a layer scan's index), page ``i`` of that layer is row
    ``layer * P + i`` of the pool seen as ``[L * P, Hkv, page, D]`` (a
    bitcast), so the layer rides into the kernel inside the page table,
    ``table + layer * P``, and only the pages a sequence holds are ever
    read. A caller that slices ``pool[layer]`` for this call makes XLA
    materialize that layer's whole pool first, every layer of every
    step. (Carrying the layer as a third scalar-prefetch operand with
    blocks ``(None, 1, 1, page, D)`` reads the same pages; on the v5e
    the served step measured 0.29 ms longer that way, PERF.md "PR 27".)
    """
    b, hq, d = q.shape
    if (k_pages.ndim == 5) != (layer is not None):
        raise ValueError(
            "a 5-D pool needs layer=, a 4-D one-layer pool takes none "
            f"(pool rank {k_pages.ndim}, layer {layer!r})"
        )
    for name, sc in (("k_scale", k_scale), ("v_scale", v_scale)):
        if sc is not None and sc.shape != k_pages.shape[:-2]:
            raise ValueError(
                f"{name} shape {sc.shape} != the pool's per-page layout "
                f"{k_pages.shape[:-2]}"
            )
    if layer is not None:
        n_layers, p = k_pages.shape[:2]
        page_table = page_table + jnp.asarray(layer, jnp.int32) * p
        k_pages, v_pages, k_scale, v_scale = (
            None if a is None else a.reshape(n_layers * p, *a.shape[2:])
            for a in (k_pages, v_pages, k_scale, v_scale)
        )
    p, hkv, page, _ = k_pages.shape
    if hq % hkv:
        raise ValueError(f"q heads {hq} not a multiple of kv heads {hkv}")
    group = hq // hkv
    if sm_scale is None:
        sm_scale = d**-0.5
    pps = page_table.shape[1]
    kv_len = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32), (b,))
    quant = k_scale is not None
    if quant != (v_scale is not None):
        raise ValueError("k_scale and v_scale must be passed together")

    resolved = interpret_mode() if interpret is None else interpret
    if resolved and exporting_portable():
        k_d, v_d = _pages_to_dense(k_pages, v_pages, page_table)
        if quant:
            k_d = k_d.astype(jnp.float32) * scales_to_dense(
                k_scale, page_table, page
            )[..., None]
            v_d = v_d.astype(jnp.float32) * scales_to_dense(
                v_scale, page_table, page
            )[..., None]
        return gqa_decode_reference(
            q, k_d, v_d, kv_len, sm_scale=sm_scale, return_lse=return_lse
        )

    slot, page_of, steps = (
        paged_decode_walk(kv_len, page, pps) if walk is None else walk
    )
    if slot.shape != (b * pps,) or page_of.shape != (b * pps,):
        raise ValueError(
            f"walk of {slot.shape} / {page_of.shape} entries is not "
            f"paged_decode_walk's for {b} sequences of {pps} pages"
        )
    scalars = [kv_len, page_table, slot, page_of]
    if quant:
        # Scales follow their pages through the table HERE, in XLA (a
        # [B, pps, Hkv] gather of f32), and reach the kernel as
        # flattened SMEM scalars: a (1, 1, 1) VMEM block of the scale
        # array is below Mosaic's (8, 128) tile and is refused.
        scalars += [
            jnp.take(sc, page_table, axis=0).reshape(-1)
            for sc in (k_scale, v_scale)
        ]

    def of_slot(i, _, tab, slot, page_of, *__):
        return slot[i], 0, 0, 0

    def of_page(i, _, tab, slot, page_of, *__):
        # The paged part: the pair's block is pool page
        # table[slot, page_of], all of its heads.
        return tab[slot[i], page_of[i]], 0, 0, 0

    out_specs = [pl.BlockSpec((1, hkv, group, d), of_slot)]
    out_shape = [jax.ShapeDtypeStruct((b, hkv, group, d), q.dtype)]
    if return_lse:
        out_specs.append(pl.BlockSpec((1, hkv, group, 1), of_slot))
        out_shape.append(jax.ShapeDtypeStruct((b, hkv, group, 1), jnp.float32))
    out = pl.pallas_call(
        functools.partial(
            _paged_decode_kernel,
            sm_scale=sm_scale, quant=quant, return_lse=return_lse,
        ),
        name="tdt_flash_decode_paged",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(steps,),
            in_specs=[
                pl.BlockSpec((1, hkv, group, d), of_slot),
                pl.BlockSpec((1, hkv, page, d), of_page),
                pl.BlockSpec((1, hkv, page, d), of_page),
            ],
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((hkv, group, 1), jnp.float32),
                pltpu.VMEM((hkv, group, 1), jnp.float32),
                pltpu.VMEM((hkv, group, d), jnp.float32),
            ],
        ),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=resolved,
    )(*scalars, q.reshape(b, hkv, group, d), k_pages, v_pages)
    o = out[0].reshape(b, hq, d)
    if return_lse:
        return o, out[1].reshape(b, hq)
    return o


def pages_to_dense(
    pages: jax.Array, page_table: jax.Array, layer=None
) -> jax.Array:
    """Gather a page pool ``[..., P, H, page, d]`` into a dense
    ``[..., B, H, S, d]`` view through the table. Single source of the
    gather layout — ``models.paged_kv_cache.as_dense`` delegates here.
    With ``layer`` the pool is the whole ``[L, P, H, page, d]`` and the
    view is that layer's: ONE gather at (layer, page) that reads the
    table's pages only, never a slice of the layer's pool."""
    if layer is None:
        g = jnp.take(pages, page_table, axis=-4)  # [..., B, pps, H, page, d]
    else:
        g = pages[layer, page_table]              # [B, pps, H, page, d]
    g = jnp.swapaxes(g, -4, -3)               # [..., B, H, pps, page, d]
    s = g.shape
    return g.reshape(*s[:-3], s[-3] * s[-2], s[-1])


def scales_to_dense(scales: jax.Array, page_table: jax.Array, page: int):
    """Per-position dequant scales matching a :func:`pages_to_dense`
    view: ``[..., P, H] → [..., B, H, S]`` through the table (every
    position of a page shares its page's scale)."""
    g = jnp.take(scales, page_table, axis=-2)  # [..., B, pps, H]
    g = jnp.swapaxes(g, -2, -1)                # [..., B, H, pps]
    return jnp.repeat(g, page, axis=-1)        # [..., B, H, S]


def _pages_to_dense(k_pages, v_pages, page_table):
    return pages_to_dense(k_pages, page_table), pages_to_dense(
        v_pages, page_table
    )


def _gather_merge(o, lse, axis: str, method: str, ctx=None):
    """Gather per-rank partial (O, LSE) over ``axis`` and LSE-merge.

    ``method='pallas'`` packs the partials into one [b·hq, d+1] payload
    and rides the device-initiated ring all-gather; ``'xla'`` uses the
    XLA collective. Shared by the one- and two-level decode merges.
    """
    b, hq, d = o.shape
    if method == "pallas":
        flat = jnp.concatenate([o.reshape(b * hq, d), lse.reshape(b * hq, 1)], 1)
        gathered = all_gather(flat, axis=axis, ctx=ctx)  # [n*b*hq, d+1]
        gathered = gathered.reshape(-1, b * hq, d + 1)
        o_all = gathered[..., :d].reshape(-1, b, hq, d)
        lse_all = gathered[..., d].reshape(-1, b, hq)
    else:
        o_all = jax.lax.all_gather(o, axis)      # [n, B, Hq, D]
        lse_all = jax.lax.all_gather(lse, axis)  # [n, B, Hq]
    return lse_combine(o_all, lse_all, part_axis=0)


def distributed_flash_decode(
    q: jax.Array,        # [B, Hq, D] replicated
    k_shard: jax.Array,  # [B, Hkv, S_loc, D] — this rank's KV slice
    v_shard: jax.Array,
    kv_len: jax.Array,   # [B] int32 GLOBAL context length
    *,
    axis: str = "sp",
    sm_scale: float | None = None,
    chunk_k: int = 256,
    method: str = "xla",
    k_scale: jax.Array | None = None,  # [B, Hkv, S_loc/chunk_k] f32
    v_scale: jax.Array | None = None,
    ctx=None,
):
    """Decode attention with the KV cache sequence-sharded over ``axis``.

    Runs inside ``shard_map``. Each rank attends q over its local KV slice
    (split-KV kernel), then partial (O, LSE) are exchanged across ranks
    and merged — parity with the reference's inter-rank combine
    (``flash_decode.py:482``) which putmem_signals partials between GPUs.
    ``method='pallas'`` uses the device-initiated ring all-gather;
    ``'xla'`` the XLA collective.

    ``k_scale``/``v_scale`` (this rank's per-chunk-per-head int8 scales)
    switch the local split-KV pass to in-kernel dequant over int8
    shards — exactly the regime the paper's low-latency decode kernels
    target: the ICI exchange already ships only (O, LSE) partials, so
    quantization halves the HBM stream on every rank without touching
    the combine.
    """
    me = jax.lax.axis_index(axis)
    s_loc = k_shard.shape[2]
    # Positions covered locally: [me*s_loc, me*s_loc + s_loc).
    local_len = jnp.clip(kv_len - me * s_loc, 0, s_loc)
    o, lse = flash_decode(
        q, k_shard, v_shard, local_len,
        sm_scale=sm_scale, chunk_k=chunk_k, return_lse=True,
        k_scale=k_scale, v_scale=v_scale,
    )
    merged, _ = _gather_merge(o.astype(jnp.float32), lse, axis, method, ctx)
    return merged.astype(q.dtype)


def distributed_flash_decode_2level(
    q: jax.Array,        # [B, Hq, D] replicated
    k_shard: jax.Array,  # [B, Hkv, S_loc, D] — this rank's KV slice
    v_shard: jax.Array,
    kv_len: jax.Array,   # [B] int32 GLOBAL context length
    *,
    inner_axis: str = "sp",
    outer_axis: str = "dcn",
    sm_scale: float | None = None,
    chunk_k: int = 256,
    method: str = "xla",
    k_scale: jax.Array | None = None,  # [B, Hkv, S_loc/chunk_k] f32
    v_scale: jax.Array | None = None,
    ctx=None,
):
    """Decode attention with the KV cache sequence-sharded over
    ``(outer_axis, inner_axis)`` in rank order — slices over DCN, ranks
    within a slice over ICI.

    Parity: the reference's multi-node flash-decode scaling
    (``README.md:202-209``, 32 GPUs = 4 nodes × 8) with its two-level
    combine: each rank reduces its local split-KV partials, partial
    (O, LSE) merge first across the fast intra-slice fabric (optionally
    the device-initiated Pallas ring when ``method='pallas'``), then the
    per-slice results merge once over DCN with XLA collectives.
    ``k_scale``/``v_scale`` switch the local pass to int8 shards with
    in-kernel dequant (see :func:`distributed_flash_decode`).
    """
    n_in = jax.lax.axis_size(inner_axis)
    me = jax.lax.axis_index(outer_axis) * n_in + jax.lax.axis_index(inner_axis)
    s_loc = k_shard.shape[2]
    local_len = jnp.clip(kv_len - me * s_loc, 0, s_loc)
    o, lse = flash_decode(
        q, k_shard, v_shard, local_len,
        sm_scale=sm_scale, chunk_k=chunk_k, return_lse=True,
        k_scale=k_scale, v_scale=v_scale,
    )
    # Level 1: intra-slice merge over ICI; level 2: one inter-slice
    # merge over DCN (always XLA — DCN traffic is XLA's domain).
    o_sl, lse_sl = _gather_merge(
        o.astype(jnp.float32), lse, inner_axis, method, ctx
    )
    merged, _ = _gather_merge(o_sl, lse_sl, outer_axis, "xla", ctx)
    return merged.astype(q.dtype)


def gqa_decode_reference(
    q, k_cache, v_cache, kv_len, *, sm_scale=None, return_lse=False
):
    """Golden decode (parity: the reference's torch goldens); also the
    portable-export path of :func:`flash_decode`."""
    b, hq, d = q.shape
    _, hkv, s, _ = k_cache.shape
    if sm_scale is None:
        sm_scale = d**-0.5
    k = jnp.repeat(k_cache, hq // hkv, axis=1).astype(jnp.float32)
    v = jnp.repeat(v_cache, hq // hkv, axis=1).astype(jnp.float32)
    s_ = jnp.einsum("bhd,bhkd->bhk", q.astype(jnp.float32), k) * sm_scale
    kv_len = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32), (b,))
    mask = jnp.arange(s)[None, None, :] < kv_len[:, None, None]
    s_ = jnp.where(mask, s_, _NEG_INF)
    p = jax.nn.softmax(s_, axis=-1)
    o = jnp.einsum("bhk,bhkd->bhd", p, v).astype(q.dtype)
    if return_lse:
        return o, jax.nn.logsumexp(s_, axis=-1)
    return o
