"""Blockwise flash attention (prefill) — Pallas TPU kernel.

Parity role: the reference consumes flash attention from its own Triton
kernels inside SP-AG attention (``sp_ag_attention_intra_node.py:256`` —
causal consumer) and from torch SDPA in layers (``tp_attn.py:203-271``).
Here the kernel is first-class: causal/GQA flash attention with an
optional log-sum-exp output, which the distributed decode and SP paths
reuse for cross-shard softmax merging (``flash_decode.py:482`` analog).

TPU design: grid = (batch·q_heads, q_blocks, kv_blocks), kv innermost so
the f32 accumulator + running (m, l) live in VMEM scratch across the kv
sweep; the MXU sees [block_q, d] @ [d, block_k] and [block_q, block_k] @
[block_k, d] shapes; causal blocks above the diagonal are skipped via
``pl.when`` (zero-work predication, the analog of the reference's early
``continue`` on masked tiles).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_distributed_tpu.ops.common import exporting_portable, interpret_mode

_NEG_INF = -1e30


def _attn_kernel(
    off_ref,  # [1] int32 SMEM (scalar prefetch) or None — kv offset
    q_ref,    # [1, block_q, d] VMEM
    k_ref,    # [1, block_k, d] VMEM — full-width, or int8 codes
    v_ref,    # [1, block_k, d] VMEM
    ks_ref,   # [B*Hkv*num_k] f32 SMEM (scalar prefetch) or None — K scales
    vs_ref,   # [B*Hkv*num_k] f32 SMEM (scalar prefetch) or None — V scales
    b_ref,    # [block_q, block_k] VMEM f32 or None — additive score bias
    o_ref,    # [1, block_q, d] VMEM
    lse_ref,  # [1, 1, sq] VMEM or None — full row; slice qi written at
              # finalize (Mosaic requires the block's trailing dims to
              # match the array, so the block spans the whole q length)
    acc,      # [block_q, d] f32 scratch
    m_i,      # [block_q, 1] f32 scratch — running max
    l_i,      # [block_q, 1] f32 scratch — running sum-exp
    *,
    sm_scale: float,
    causal: bool,
    kv_offset: int,
    block_q: int,
    block_k: int,
    group: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    num_k = pl.num_programs(2)
    # This (batch·kv head, kv block)'s slot in the flattened scales.
    si = (pl.program_id(0) // group) * num_k + ki
    kv_offset = kv_offset if off_ref is None else off_ref[0]

    @pl.when(ki == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_i[:] = jnp.full_like(m_i, _NEG_INF)
        l_i[:] = jnp.zeros_like(l_i)

    # Causal skip: the kv block starts after the last q row can see.
    q_end = kv_offset + (qi + 1) * block_q - 1  # last absolute q position
    run = (ki * block_k <= q_end) if causal else True

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        # In-register dequant (int8 KV): the per-block symmetric scale
        # is a scalar, so it folds into the softmax multiplier after
        # QK^T — full-width K never materializes.
        mult = sm_scale if ks_ref is None else sm_scale * ks_ref[si]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * mult  # [block_q, block_k]
        if b_ref is not None:
            # Additive score bias (0 / -inf): the tree-attention mask of
            # the speculative verify chunk. Applied before the causal
            # mask — the bias only ever masks MORE than causality, so
            # the causal block-skip above stays sound.
            s = s + b_ref[...]
        if causal:
            rows = kv_offset + qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            cols = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(cols <= rows, s, _NEG_INF)
        m_new = jnp.maximum(m_i[:], jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_i[:] - m_new)
        l_i[:] = l_i[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        if vs_ref is None:
            pv = jnp.dot(
                p.astype(v_ref.dtype), v_ref[0],
                preferred_element_type=jnp.float32,
            )
        else:
            pv = jnp.dot(
                p, v_ref[0].astype(jnp.float32),
                preferred_element_type=jnp.float32,
            ) * vs_ref[si]
        acc[:] = acc[:] * alpha + pv
        m_i[:] = m_new

    @pl.when(ki == num_k - 1)
    def _finalize():
        l = jnp.maximum(l_i[:], 1e-30)
        o_ref[0] = (acc[:] / l).astype(o_ref.dtype)
        if lse_ref is not None:
            lse_ref[0, 0, pl.ds(qi * block_q, block_q)] = (m_i[:] + jnp.log(l))[
                :, 0
            ]


def flash_attention(
    q: jax.Array,  # [B, Hq, Sq, D]
    k: jax.Array,  # [B, Hkv, Sk, D]
    v: jax.Array,  # [B, Hkv, Sk, W] (W may differ from D: latent attention)
    *,
    causal: bool = True,
    sm_scale: float | None = None,
    kv_offset: int | jax.Array = 0,
    block_q: int = 128,
    block_k: int = 128,
    return_lse: bool = False,
    k_scale: jax.Array | None = None,  # [B, Hkv, Sk/block_k] f32
    v_scale: jax.Array | None = None,
    bias: jax.Array | None = None,     # [Sq, Sk] f32 additive score bias
    interpret=None,
):
    """Causal/GQA flash attention. ``kv_offset``: absolute position of
    ``q[..., 0, :]`` within the kv sequence (non-zero for chunked prefill
    against a KV cache — parity with the reference's offset handling in
    ``flash_decode.py`` host wrappers). A traced/array ``kv_offset``
    rides as a scalar-prefetch operand, so one compiled kernel serves
    every chunk offset of a chunked prefill (a static int keeps the
    constant-folded path).

    ``k_scale``/``v_scale`` enable the int8 KV mode (the paged-prefill
    chunk path over a quantized pool): ``k``/``v`` hold int8 codes and
    one symmetric f32 scale per ``block_k`` block per head dequantizes
    in-register after QK^T / P·V. Callers align ``block_k`` with the
    quantization granularity (the chunk path sets ``block_k =
    page_size`` so per-page pool scales ARE per-block scales).

    ``bias`` is an optional ``[Sq, Sk]`` f32 additive score bias shared
    across batch and heads (0 = visible, ``-1e30`` = masked) — the
    tree-attention mask of speculative verify chunks, where sibling
    draft branches must not attend to each other. It composes with
    ``causal=True``: tree masks only ever REMOVE visibility relative to
    storage-order causality (ancestors precede descendants in storage),
    so the causal block skip stays valid.

    Returns ``o [B, Hq, Sq, W]`` (and ``lse [B, Hq, Sq]`` f32 when
    ``return_lse`` — base-e log-sum-exp of scaled scores, the quantity the
    distributed combine merges).
    """
    b, hq, sq, d = q.shape
    (_, hkv, sk, _), w = k.shape, v.shape[-1]  # w: the values' width
    if hq % hkv:
        raise ValueError(f"q heads {hq} not a multiple of kv heads {hkv}")
    group = hq // hkv
    if sm_scale is None:
        sm_scale = d**-0.5
    quant = k_scale is not None
    if quant != (v_scale is not None):
        raise ValueError("k_scale and v_scale must be passed together")
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    # Validate BOTH scale layouts BEFORE the portable early-return: the
    # reference path below would otherwise dequantize a mis-shaped
    # scale at the wrong granularity, and the Pallas path's clamped
    # block indices would silently read the wrong page's scale.
    for name, sc in (("k_scale", k_scale), ("v_scale", v_scale)):
        if quant and sc.shape != (b, hkv, sk // block_k):
            raise ValueError(
                f"{name} shape {sc.shape} != per-block layout "
                f"{(b, hkv, sk // block_k)} (block_k={block_k})"
            )
    if bias is not None and bias.shape != (sq, sk):
        raise ValueError(f"bias shape {bias.shape} != {(sq, sk)}")
    # jax.export can't serialize the host callbacks interpret-mode
    # Pallas lowers to; portable exports take the XLA-reference path
    # (same contract as flash_decode's portable fallback).
    interpret = interpret_mode() if interpret is None else interpret
    if interpret and exporting_portable():
        if quant:
            k = k.astype(jnp.float32) * jnp.repeat(
                k_scale, block_k, axis=-1
            )[..., None]
            v = v.astype(jnp.float32) * jnp.repeat(
                v_scale, block_k, axis=-1
            )[..., None]
        return mha_reference(
            q, k, v, causal=causal, sm_scale=sm_scale,
            kv_offset=kv_offset, return_lse=return_lse, bias=bias,
        )
    if sq % block_q or sk % block_k:
        raise ValueError(f"seq ({sq},{sk}) not divisible by blocks "
                         f"({block_q},{block_k}); pad upstream")

    qf = q.reshape(b * hq, sq, d)
    kf = k.reshape(b * hkv, sk, d)
    vf = v.reshape(b * hkv, sk, w)
    grid = (b * hq, sq // block_q, sk // block_k)
    dynamic_off = not isinstance(kv_offset, int)

    out_shape = [jax.ShapeDtypeStruct((b * hq, sq, w), q.dtype)]
    out_specs = [
        pl.BlockSpec((1, block_q, w), lambda bh, qi, ki, *_: (bh, qi, 0)),
    ]
    if return_lse:
        out_shape.append(jax.ShapeDtypeStruct((b * hq, 1, sq), jnp.float32))
        out_specs.append(
            pl.BlockSpec((1, 1, sq), lambda bh, qi, ki, *_: (bh, 0, 0))
        )

    kernel = functools.partial(
        _attn_kernel,
        sm_scale=sm_scale,
        causal=causal,
        kv_offset=0 if dynamic_off else kv_offset,
        block_q=block_q,
        block_k=block_k,
        group=group,
    )
    kernel = functools.partial(
        _adapt_refs, kernel, dynamic_off, quant, bias is not None,
        return_lse,
    )
    # Index maps take the scalar-prefetch refs as trailing args.
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda bh, qi, ki, *_: (bh, qi, 0)),
        pl.BlockSpec(
            (1, block_k, d), lambda bh, qi, ki, *_: (bh // group, ki, 0)
        ),
        pl.BlockSpec(
            (1, block_k, w), lambda bh, qi, ki, *_: (bh // group, ki, 0)
        ),
    ]
    operands = [qf, kf, vf]
    if bias is not None:
        in_specs.append(
            pl.BlockSpec((block_q, block_k), lambda bh, qi, ki, *_: (qi, ki))
        )
        operands.append(bias.astype(jnp.float32))
    # Scalar-prefetch (SMEM) operands: a traced kv offset, so one
    # compiled kernel serves every chunk offset, and the int8 scales —
    # one f32 per (kv head, kv block), read as scalars. A (1, 1) VMEM
    # block of the scale array is below Mosaic's (8, 128) tile and is
    # refused by the TPU compiler.
    scalars = []
    if dynamic_off:
        scalars.append(jnp.asarray(kv_offset, jnp.int32).reshape(1))
    if quant:
        scalars += [k_scale.reshape(-1), v_scale.reshape(-1)]
    res = pl.pallas_call(
        kernel,
        name="tdt_flash_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((block_q, w), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
            ],
        ),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(*scalars, *operands)

    o = res[0].reshape(b, hq, sq, w)
    if return_lse:
        return o, res[1].reshape(b, hq, sq)
    return o


def _adapt_refs(kernel, has_off: bool, has_scales: bool, has_bias: bool,
                has_lse: bool, *refs):
    """Route pallas_call's positional refs into ``_attn_kernel``'s
    keyword-stable signature: the scalar-prefetch refs first (optional
    kv offset, optional int8 dequant scales), then q/k/v, optional
    score bias, optional lse output, then the three scratch refs."""
    refs = list(refs)
    off_ref = refs.pop(0) if has_off else None
    ks_ref = vs_ref = None
    if has_scales:
        ks_ref, vs_ref = refs.pop(0), refs.pop(0)
    q_ref, k_ref, v_ref = refs[:3]
    nxt = 3
    b_ref = None
    if has_bias:
        b_ref = refs[nxt]
        nxt += 1
    o_ref = refs[nxt]
    lse_ref = refs[nxt + 1] if has_lse else None
    acc, m_i, l_i = refs[-3:]
    kernel(off_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, b_ref, o_ref,
           lse_ref, acc, m_i, l_i)


def mha_reference(
    q, k, v, *, causal=True, sm_scale=None, kv_offset: int = 0,
    return_lse: bool = False, bias=None,
):
    """Golden attention (parity: the reference's torch-SDPA goldens)."""
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    if sm_scale is None:
        sm_scale = d**-0.5
    k = jnp.repeat(k, hq // hkv, axis=1)
    v = jnp.repeat(v, hq // hkv, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32))
    s *= sm_scale
    if bias is not None:
        s = s + bias.astype(jnp.float32)[None, None]
    if causal:
        rows = kv_offset + jnp.arange(sq)[:, None]
        cols = jnp.arange(sk)[None, :]
        s = jnp.where(cols <= rows, s, _NEG_INF)
    lse = jax.scipy.special.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None])
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)
    if return_lse:
        return o, lse
    return o
