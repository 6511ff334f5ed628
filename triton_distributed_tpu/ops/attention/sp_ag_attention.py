"""Sequence-parallel AllGather attention (long-context prefill) — the
KV-gather and the causal flash-attention consumer fused in ONE kernel.

Parity: reference ``kernels/nvidia/sp_ag_attention_intra_node.py`` /
``_inter_node.py`` — KV shards are allgathered chunk-by-chunk on a comm
stream (CE push :105 / NVSHMEM push kernel :115) while a causal
flash-attn consumer ``dl.wait``s per-chunk signals (:256/:328); entry
points ``fused_sp_ag_attn_*`` (:432/:504).

TPU design (no streams — SURVEY.md §7): each device pushes its local KV
shard over ICI to every later-ranked peer at kernel start (causal
attention only looks backward), then sweeps its q blocks against KV
chunks 0..me, waiting on each chunk's arrival semaphore at first touch.
The DMA engines carry the gather while the MXU runs flash attention on
already-arrived chunks — the reference's producer/consumer overlap with
the semaphore replacing the tile-barrier spin.

Grid = (hq, q_blocks, n_chunks), chunk innermost so the running-softmax
accumulators live across the chunk sweep; chunks beyond ``me`` are
predicated off (those rows attend only to earlier ranks).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_distributed_tpu import language as dl
from triton_distributed_tpu.ops.common import comm_pallas_call, next_collective_id

_SP_AG_COLLECTIVE_ID = next_collective_id()
_NEG_INF = -1e30


def _sp_ag_attn_kernel(
    q_ref,     # [1, bq, hd] VMEM — q block (head h, block qb)
    kv_ref,    # [2, hkv, s_loc, hd] ANY — local KV shard (k=0, v=1)
    o_ref,     # [1, bq, hd] VMEM — output block (written at r == me)
    lse_ref,   # [1, bq, 1] VMEM — log-sum-exp per q row (same schedule)
    ws,        # [n, 2, hkv, s_loc, hd] ANY out — arrived KV chunks
    k_vmem,    # [s_loc, hd] VMEM scratch
    v_vmem,    # [s_loc, hd] VMEM scratch
    acc,       # [bq, hd] f32
    m_i,       # [bq, 1] f32
    l_i,       # [bq, 1] f32
    stage_sems,  # DMA (2,)
    copy_sem,    # DMA ()
    send_sems,   # DMA (n,) — slot i for the push to peer i
    recv_sems,   # DMA (n,) — slot r signaled when chunk r lands
    *,
    axis: str,
    group: int,
    sm_scale: float,
    bq: int,
):
    me = dl.rank(axis)
    n = dl.num_ranks(axis)
    h = pl.program_id(0)
    qb = pl.program_id(1)
    r = pl.program_id(2)
    num_h = pl.num_programs(0)
    num_qb = pl.num_programs(1)
    s_loc = kv_ref.shape[2]
    g = h // group  # kv head for this q head

    @pl.when(jnp.logical_and(h == 0, jnp.logical_and(qb == 0, r == 0)))
    def _produce():
        # Entry barrier: peers' ws must be allocated before pushes land.
        dl.barrier_all(axis)
        # Own chunk into the local workspace slot...
        dma = pltpu.make_async_copy(kv_ref, ws.at[me], copy_sem)
        dma.start()
        # ...and pushed to every later-ranked peer (they look back at us).
        def push(i, _):
            dl.put_signal(
                kv_ref, ws.at[me], i, send_sems.at[i], recv_sems.at[me],
                axis=axis,
            )
            return _
        jax.lax.fori_loop(me + 1, n, push, None)
        dma.wait()

    # First touch of a remote chunk: wait for its arrival signal.
    @pl.when(jnp.logical_and(h == 0, jnp.logical_and(qb == 0, r < me)))
    def _await_chunk():
        dl.wait_recv(recv_sems.at[r], ws.at[r])

    @pl.when(r == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_i[:] = jnp.full_like(m_i, _NEG_INF)
        l_i[:] = jnp.zeros_like(l_i)

    @pl.when(r <= me)
    def _consume():
        # Stage chunk r's K/V for this kv head into VMEM.
        kdma = pltpu.make_async_copy(ws.at[r, 0, g], k_vmem, stage_sems.at[0])
        vdma = pltpu.make_async_copy(ws.at[r, 1, g], v_vmem, stage_sems.at[1])
        kdma.start()
        vdma.start()
        kdma.wait()
        vdma.wait()

        q = q_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_vmem[:].astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale  # [bq, s_loc]

        # Causal mask only applies within the own chunk (earlier ranks'
        # chunks are fully visible); folded into one jnp.where so the
        # softmax update traces once.
        rows = qb * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        visible = jnp.logical_or(r < me, cols <= rows)
        scores = jnp.where(visible, s, _NEG_INF)

        m_new = jnp.maximum(m_i[:], jnp.max(scores, axis=1, keepdims=True))
        p = jnp.exp(scores - m_new)
        alpha = jnp.exp(m_i[:] - m_new)
        l_i[:] = l_i[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc[:] = acc[:] * alpha + jnp.dot(
            p.astype(v_vmem.dtype), v_vmem[:],
            preferred_element_type=jnp.float32,
        )
        m_i[:] = m_new

    @pl.when(r == me)
    def _finalize():
        l = jnp.maximum(l_i[:], 1e-30)
        o_ref[0] = (acc[:] / l).astype(o_ref.dtype)
        lse_ref[0] = m_i[:] + jnp.log(l)

    @pl.when(
        jnp.logical_and(
            h == num_h - 1, jnp.logical_and(qb == num_qb - 1, r == n - 1)
        )
    )
    def _drain():
        def drain_one(i, _):
            pltpu.make_async_copy(kv_ref, kv_ref, send_sems.at[i]).wait()
            return _
        jax.lax.fori_loop(me + 1, n, drain_one, None)


def sp_ag_attention(
    q: jax.Array,  # [hq, s_loc, hd] — this device's q shard
    k: jax.Array,  # [hkv, s_loc, hd] — this device's KV shard
    v: jax.Array,
    *,
    axis: str = "sp",
    sm_scale: float | None = None,
    block_q: int = 256,
    return_lse: bool = False,
    ctx=None,
) -> jax.Array:
    """Causal SP attention inside ``shard_map``; sequence sharded over
    ``axis`` in rank order. Returns ``o [hq, s_loc, hd]`` (q layout),
    plus the per-row log-sum-exp ``[hq, s_loc]`` when ``return_lse``
    (for hierarchical/DCN-level merges).

    Parity: ``fused_sp_ag_attn_intra_node``
    (``sp_ag_attention_intra_node.py:432``).
    """
    n = jax.lax.axis_size(axis)
    hq, s_loc, hd = q.shape
    hkv = k.shape[0]
    if hq % hkv:
        raise ValueError(f"q heads {hq} not a multiple of kv heads {hkv}")
    if sm_scale is None:
        sm_scale = hd**-0.5
    bq = min(block_q, s_loc)
    if s_loc % bq:
        raise ValueError(f"s_loc={s_loc} not divisible by block_q={bq}")
    kv = jnp.stack([k, v])  # [2, hkv, s_loc, hd]

    out, lse, _ws = comm_pallas_call(
        "tdt_sp_ag_attention",
        functools.partial(
            _sp_ag_attn_kernel,
            axis=axis, group=hq // hkv, sm_scale=sm_scale, bq=bq,
        ),
        (
            jax.ShapeDtypeStruct((hq, s_loc, hd), q.dtype),
            jax.ShapeDtypeStruct((hq, s_loc, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, 2, hkv, s_loc, hd), k.dtype),
        ),
        grid=(hq, s_loc // bq, n),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda h, qb, r: (h, qb, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=(
            pl.BlockSpec((1, bq, hd), lambda h, qb, r: (h, qb, 0)),
            pl.BlockSpec((1, bq, 1), lambda h, qb, r: (h, qb, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ),
        scratch_shapes=[
            pltpu.VMEM((s_loc, hd), k.dtype),
            pltpu.VMEM((s_loc, hd), v.dtype),
            pltpu.VMEM((bq, hd), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA((n,)),
            pltpu.SemaphoreType.DMA((n,)),
        ],
        collective_id=_SP_AG_COLLECTIVE_ID,
        dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        ctx=ctx,
    )(q, kv)
    return (out, lse[..., 0]) if return_lse else out


def sp_ag_attention_2level(
    q: jax.Array,  # [hq, s_loc, hd] — this device's q shard
    k: jax.Array,  # [hkv, s_loc, hd]
    v: jax.Array,
    *,
    inner_axis: str = "sp",
    outer_axis: str = "dcn",
    sm_scale: float | None = None,
    block_q: int = 256,
    ctx=None,
) -> jax.Array:
    """Two-level causal SP attention: sequence sharded over
    ``(outer_axis, inner_axis)`` in rank order — slices over DCN, ranks
    within a slice over ICI.

    Parity: ``fused_sp_ag_attn_inter_node``
    (``sp_ag_attention_inter_node.py:115,504``) — there the intra-node
    gather rides NVSHMEM while inter-node chunks arrive over IB. TPU
    redesign: the intra-slice half runs the fused one-kernel Pallas
    gather+attention (ICI); the inter-slice half attends the q shard
    over earlier slices' KV gathered with XLA collectives (DCN), and the
    two partial softmaxes merge by log-sum-exp — the reference's
    combine step (``flash_decode.py:482`` pattern) at slice granularity.
    """
    n_out = jax.lax.axis_size(outer_axis)
    me_out = jax.lax.axis_index(outer_axis)
    hq, s_loc, hd = q.shape
    hkv = k.shape[0]
    g = hq // hkv
    if sm_scale is None:
        sm_scale = hd**-0.5

    # Intra-slice: fused Pallas kernel over the ICI axis.
    o_intra, lse_intra = sp_ag_attention(
        q, k, v, axis=inner_axis, sm_scale=sm_scale, block_q=block_q,
        return_lse=True, ctx=ctx,
    )
    o_intra = o_intra.astype(jnp.float32)
    if n_out == 1:
        return o_intra.astype(q.dtype)

    # Inter-slice: earlier slices are fully visible (causal order). KV
    # is gathered slice-major over both axes with XLA collectives (the
    # DCN leg — the reference's inter-node buffer likewise holds the
    # gathered sequence, sp_ag_attention_inter_node.py:115), then the
    # online softmax streams slice by slice: score memory stays
    # O(g·s_loc × s_slice) instead of one dense matrix over the global
    # sequence, and the fori upper bound is me_out, so slice 0 does no
    # masked busywork.
    k_slice = jax.lax.all_gather(k, inner_axis, axis=1, tiled=True)
    v_slice = jax.lax.all_gather(v, inner_axis, axis=1, tiled=True)
    k_all = jax.lax.all_gather(k_slice, outer_axis)  # [n_out, hkv, s_sl, hd]
    v_all = jax.lax.all_gather(v_slice, outer_axis)
    s_slice = k_slice.shape[1]

    qg = q.reshape(hkv, g * s_loc, hd).astype(jnp.float32)
    rows = g * s_loc

    def slice_step(r, carry):
        m, l, acc = carry
        kr = k_all[r].astype(jnp.float32)  # [hkv, s_slice, hd]
        vr = v_all[r].astype(jnp.float32)
        s = jax.lax.dot_general(
            qg, kr, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * sm_scale  # [hkv, g*s_loc, s_slice]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p, vr, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        return m_new, l, acc

    m0 = jnp.full((hkv, rows, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((hkv, rows, 1), jnp.float32)
    a0 = jnp.zeros((hkv, rows, hd), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, me_out, slice_step, (m0, l0, a0))
    o_prev = (acc / jnp.maximum(l, 1e-30)).reshape(hq, s_loc, hd)
    lse_prev = (m + jnp.log(jnp.maximum(l, 1e-30))).reshape(hq, s_loc)

    from triton_distributed_tpu.ops.attention.flash_decode import lse_combine

    o, _ = lse_combine(
        jnp.stack([o_intra, o_prev]),
        jnp.stack([lse_intra, lse_prev]),
        part_axis=0,
    )
    return o.astype(q.dtype)
