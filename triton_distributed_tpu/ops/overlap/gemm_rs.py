"""GEMM + ReduceScatter overlap — the TP output-projection archetype.

Parity: reference ``kernels/nvidia/gemm_reduce_scatter.py`` —
``GEMMReduceScatterTensorParallelContext``:42, producer GEMM with
per-tile notify :122-413, ``gemm_rs_op``:508, ``gemm_rs``:569 — plus the
ring-reduce consumer from ``reduce_scatter.py:674-744``.

TPU design: one kernel fuses producer and consumer. Row-parallel GEMM
(``a [M, k_loc] @ b [k_loc, N]`` giving partial C) is computed chunk by
chunk in *ring-reduce order*: at step s the device computes its partial
for destination chunk ``(me-1-s) mod n``, adds the accumulated partial
arriving from its left neighbor, and forwards the sum right — so each
row chunk circulates once around the ring, gathering every device's
contribution, while the MXU stays busy producing the next chunk. The
final step's chunk is the device's own output. Per-step receive slots in
HBM make the protocol flow-control-free (slot s is written exactly once,
by the left neighbor's step s-1).

Scale: the accumulated partial lives in HBM (``accbuf``), streamed
through VMEM in (tile_m × tile_n) tiles (parity: the reference's
persistent M tiling, ``gemm_reduce_scatter.py:122``) — baseline shapes
(m_per × N ≫ VMEM) never resident-stage.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from triton_distributed_tpu import language as dl
from triton_distributed_tpu.ops.common import (
    comm_cost,
    comm_pallas_call,
    next_collective_id,
    overlap_vmem_limit,
    pick_tile,
)
from triton_distributed_tpu.runtime.mesh import DistContext, current_context

_GEMM_RS_COLLECTIVE_ID = next_collective_id()


@dataclasses.dataclass(frozen=True)
class GemmRSConfig:
    """Parity: tile fields of ``GEMMReduceScatterTensorParallelContext``.

    ``bidir``: split each circulating chunk's rows in half and run TWO
    counter-rotating rings (top half clockwise, bottom half counter-
    clockwise) — both directions of the ICI torus axis carry payload,
    2x wire bandwidth in the comm-bound regime (the same lever as the
    bidirectional all-gather; the reference's analog is its NUMA-split
    dual rings, ``reduce_scatter.py:285``). Requires an even number of
    row tiles; auto-falls back to the single ring otherwise.

    ``wire_dtype``: dtype of the RING HOP payload only (local
    accumulation stays ``acc_dtype``; the final output stays the input
    dtype). Default None = input dtype — for bf16 inputs that is
    already the reference's reduce-in-output-dtype scheme
    (``kernel_ring_reduce_tma``, ``reduce_scatter.py:674-744``): one
    bf16 rounding per hop. ``jnp.float8_e4m3fn`` halves wire bytes
    again. Error model (documented, tested): each hop rounds the
    accumulated partial to e4m3 (~2^-4 relative half-ulp), so a chunk
    crossing h hops carries ~sqrt(h)·2^-4 RMS relative error on the
    PARTIAL-SUM magnitude — safe when partials don't catastrophically
    cancel (inference activations); not for gradients. e4m3's ±448
    dynamic range is the caller's responsibility (pre-scaled
    activations); overflow saturates to ±448 rather than inf.
    """

    tile_n: int = 512
    tile_m: int | None = None  # None → whole m_per (small shapes)
    acc_dtype: jnp.dtype = jnp.float32
    bidir: bool = True
    wire_dtype: jnp.dtype | None = None
    # n=1 normally short-circuits to a plain XLA dot; the tile sweep
    # (perf/sweep_overlap_tiles.py) needs the KERNEL's staging pipeline
    # measured on one chip — without this flag its gemm_rs numbers
    # would silently time XLA at every tile config.
    force_kernel: bool = False


# 8 MB (tile_m=1024 at K=4096 bf16) measured best on v5e — see
# perf/sweep_overlap_tiles.py and the ag_gemm budget note.
_RS_STAGE_BUDGET = 8 * 1024 * 1024


def create_gemm_rs_context(
    m: int, n_out: int, k_loc: int, dtype=jnp.bfloat16, tile_n: int | None = None,
    n_ranks: int = 8, bidir: bool = True,
) -> GemmRSConfig:
    itemsize = jnp.dtype(dtype).itemsize
    m_per = max(m // max(n_ranks, 1), 1)
    tile_m = m_per
    while tile_m > 128 and tile_m * k_loc * itemsize > _RS_STAGE_BUDGET:
        tile_m //= 2
    while m_per % tile_m:
        tile_m //= 2
    # The dual-ring (bidir) kernel needs an even row-tile count to split
    # each chunk between the two directions; a whole-chunk tile would
    # silently fall back to the single ring (half the wire bandwidth).
    if bidir and tile_m == m_per and m_per % 2 == 0 and m_per >= 16:
        tile_m //= 2
    return GemmRSConfig(
        tile_n=pick_tile(n_out, 1024) if tile_n is None else tile_n,
        tile_m=max(tile_m, 1),
        bidir=bidir,
    )


def _gemm_rs_kernel(
    a_ref,      # [M, k_loc] ANY/HBM — this device's column shard of A
    b_ref,      # [k_loc, tile_n] VMEM — B tile j
    o_ref,      # [m_per, N] ANY/HBM — final reduced chunk (written once)
    ws,         # [n-1, m_per, N] ANY/HBM output (wire dtype) — per-step
                # inbound slots (workspace-as-output; no HBM scratch)
    accbuf,     # [2, m_per, N] ANY/HBM output (wire dtype) — outbound
    a_vmem,     # [2, tile_m, k_loc] VMEM — A tile double buffer
    inb_vmem,   # [2, tile_m, tile_n] VMEM (wire dtype) — inbound tile
    out_vmem,   # [2, tile_m, tile_n] VMEM (wire dtype) — outbound tile
    fin_vmem,   # [2, tile_m, tile_n] VMEM (input dtype) — final-step
                # tile, or None when wire dtype == input dtype
    load_sems,  # DMA (2,)
    inb_sems,   # DMA (2,)
    out_sems,   # DMA (2,)
    send_sems,  # DMA (ndir, n-1)
    recv_sems,  # DMA (ndir, n-1)
    *,
    axis: str,
    acc_dtype,
    bidir: bool,
):
    me = dl.rank(axis)
    n = dl.num_ranks(axis)
    s = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)
    num_i = pl.num_programs(1)
    num_j = pl.num_programs(2)
    tile_m = a_vmem.shape[1]
    tile_n = b_ref.shape[1]
    right = jax.lax.rem(me + 1, n)
    left = jax.lax.rem(me - 1 + n, n)
    t = i * num_j + j          # tile linear index within the step
    num_t = num_i * num_j
    p = jax.lax.rem(t, 2)      # inbound/outbound buffer parity
    # Bidir: row tiles [0, ni2) ride the clockwise ring (dir 0, to the
    # right neighbor), [ni2, num_i) the counter-clockwise ring (dir 1).
    ndir = 2 if bidir else 1
    ni2 = num_i // 2 if bidir else num_i
    half_m = ni2 * tile_m
    m_per = num_i * tile_m

    def rows(ti):
        return pl.ds(ti * tile_m, tile_m)

    def cols(tj):
        return pl.ds(tj * tile_n, tile_n)

    def dir_rows(d):
        # Direction d's row span of a chunk-sized [m_per, N] buffer.
        if d == 0:
            return pl.ds(0, half_m)
        return pl.ds(half_m, m_per - half_m)

    def a_chunk(step, ti):
        # Destination chunk this step's row-tile belongs to: clockwise
        # rows serve chunk me-1-step (flowing right), counter-clockwise
        # rows chunk me+1+step (flowing left); both reach the own chunk
        # at step n-1.
        cw = jax.lax.rem(me - 1 - step + 2 * n, n)
        if not bidir:
            return cw
        ccw = jax.lax.rem(me + 1 + step, n)
        return jnp.where(ti < ni2, cw, ccw)

    def a_buf(step, ti):
        return jax.lax.rem(step * num_i + ti, 2)

    def stage_a(step, ti):
        b = a_buf(step, ti)
        return pltpu.make_async_copy(
            a_ref.at[pl.ds(a_chunk(step, ti) * m_per + ti * tile_m, tile_m)],
            a_vmem.at[b],
            load_sems.at[b],
        )

    def stage_inb(step, ti, tj, par):
        return pltpu.make_async_copy(
            ws.at[step - 1, rows(ti), cols(tj)],
            inb_vmem.at[par],
            inb_sems.at[par],
        )

    @pl.when(jnp.logical_and(s == 0, t == 0))
    def _start():
        # Entry barrier: the first remote put (end of step 0) targets the
        # right neighbor's ws output, which must already be allocated.
        dl.barrier_all(axis)
        dma = stage_a(0, 0)
        dma.start()
        dma.wait()

    @pl.when(jnp.logical_and(s > 0, t == 0))
    def _step_begin():
        # A tile 0 staged at the end of the previous step.
        b = a_buf(s, 0)
        pltpu.make_async_copy(
            a_vmem.at[b], a_vmem.at[b], load_sems.at[b]
        ).wait()
        # Inbound accumulated partials (per direction) must have landed.
        for d in range(ndir):
            dl.wait_recv(recv_sems.at[d, s - 1], ws.at[s - 1, dir_rows(d)])
        dma = stage_inb(s, 0, 0, 0)
        dma.start()
        dma.wait()
        # accbuf slot s%2 was last pushed at step s-2; drain before reuse.
        @pl.when(s >= 2)
        def _():
            for d in range(ndir):
                pltpu.make_async_copy(
                    accbuf.at[s % 2, dir_rows(d)],
                    accbuf.at[s % 2, dir_rows(d)],
                    send_sems.at[d, s - 2],
                ).wait()

    @pl.when(jnp.logical_and(jnp.logical_and(s > 0, t > 0), t < num_t))
    def _land_inb():
        # Inbound tile t staged at tile t-1.
        pltpu.make_async_copy(
            inb_vmem.at[p], inb_vmem.at[p], inb_sems.at[p]
        ).wait()

    @pl.when(jnp.logical_and(t > 0, j == 0))
    def _land_a():
        b = a_buf(s, i)
        pltpu.make_async_copy(
            a_vmem.at[b], a_vmem.at[b], load_sems.at[b]
        ).wait()

    # Prefetches for tile t+1 (inbound) and row-tile i+1 (A), issued
    # before the matmul so the DMA engines run under MXU work.
    @pl.when(jnp.logical_and(s > 0, t + 1 < num_t))
    def _prefetch_inb():
        ni = (t + 1) // num_j
        nj = jax.lax.rem(t + 1, num_j)
        stage_inb(s, ni, nj, 1 - p).start()

    @pl.when(jnp.logical_and(i + 1 < num_i, j == num_j - 1))
    def _prefetch_a():
        stage_a(s, i + 1).start()

    @pl.when(jnp.logical_and(s + 1 < n, t == num_t - 1))
    def _prefetch_a_next_step():
        stage_a(s + 1, 0).start()

    partial = jnp.dot(
        a_vmem[a_buf(s, i)], b_ref[:], preferred_element_type=acc_dtype
    )

    def drain_tile(buf, par):
        pltpu.make_async_copy(
            buf.at[par], buf.at[par], out_sems.at[par]
        ).wait()

    # Reuse of the outbound tile buffer: its previous DMA-out (tile t-2,
    # same step, same buffer kind) must be done.
    @pl.when(jnp.logical_and(t >= 2, s < n - 1))
    def _drain_out():
        drain_tile(out_vmem, p)

    @pl.when(jnp.logical_and(t >= 2, s == n - 1))
    def _drain_fin():
        drain_tile(fin_vmem if fin_vmem is not None else out_vmem, p)

    @pl.when(jnp.logical_and(s == 0, s < n - 1))
    def _first_step():
        out_vmem[p] = partial.astype(out_vmem.dtype)

    @pl.when(jnp.logical_and(s > 0, s < n - 1))
    def _accumulate():
        out_vmem[p] = (
            partial + inb_vmem[p].astype(acc_dtype)
        ).astype(out_vmem.dtype)

    fbuf = fin_vmem if fin_vmem is not None else out_vmem

    @pl.when(s == n - 1)
    def _final_accumulate():
        if n == 1:
            # Degenerate ring (force_kernel at tp=1): no inbound partial
            # exists — the tile is the full reduction.
            fbuf[p] = partial.astype(fbuf.dtype)
        else:
            fbuf[p] = (
                partial + inb_vmem[p].astype(acc_dtype)
            ).astype(fbuf.dtype)

    @pl.when(s < n - 1)
    def _to_accbuf():
        pltpu.make_async_copy(
            out_vmem.at[p], accbuf.at[s % 2, rows(i), cols(j)],
            out_sems.at[p],
        ).start()

    @pl.when(s == n - 1)
    def _to_out():
        pltpu.make_async_copy(
            fbuf.at[p], o_ref.at[rows(i), cols(j)], out_sems.at[p]
        ).start()

    @pl.when(t == num_t - 1)
    def _step_end():
        # All outbound tile DMAs of this step must have landed in HBM
        # before the chunk is forwarded (or the kernel exits).
        def _drain_step_bufs(buf):
            pltpu.make_async_copy(
                buf.at[p], buf.at[p], out_sems.at[p]
            ).wait()

            @pl.when(num_t > 1)
            def _():
                pltpu.make_async_copy(
                    buf.at[1 - p], buf.at[1 - p], out_sems.at[1 - p]
                ).wait()

        @pl.when(s < n - 1)
        def _drain_hop():
            _drain_step_bufs(out_vmem)

        @pl.when(s == n - 1)
        def _drain_final():
            _drain_step_bufs(fbuf)

        @pl.when(s < n - 1)
        def _forward():
            # Receiver consumes this at its step s+1 from slot s: dir 0
            # rows go right, dir 1 rows go left.
            dl.put_signal(
                accbuf.at[s % 2, dir_rows(0)], ws.at[s, dir_rows(0)],
                right, send_sems.at[0, s], recv_sems.at[0, s], axis=axis,
            )
            if bidir:
                dl.put_signal(
                    accbuf.at[s % 2, dir_rows(1)], ws.at[s, dir_rows(1)],
                    left, send_sems.at[1, s], recv_sems.at[1, s], axis=axis,
                )

        @pl.when(s == n - 1)
        def _finish():
            # Steps 0..n-3 drained on accbuf reuse; only n-2 remains.
            if n > 1:
                step = n - 2
                for d in range(ndir):
                    pltpu.make_async_copy(
                        accbuf.at[step % 2, dir_rows(d)],
                        accbuf.at[step % 2, dir_rows(d)],
                        send_sems.at[d, step],
                    ).wait()


def gemm_rs(
    a: jax.Array,
    b: jax.Array,
    axis: str = "tp",
    config: GemmRSConfig | None = None,
    ctx: DistContext | None = None,
) -> jax.Array:
    """Overlapped ``reduce_scatter(a @ b)`` inside ``shard_map``.

    ``a``: ``[M, k_loc]`` column shard; ``b``: ``[k_loc, N]`` row shard.
    Returns this device's reduced row chunk ``[M/n, N]`` — same contract
    as reference ``gemm_rs`` (``gemm_reduce_scatter.py:569``).
    """
    n = jax.lax.axis_size(axis)
    m, k_loc = a.shape
    _, n_out = b.shape
    if m % n:
        raise ValueError(f"M={m} not divisible by axis size {n}")
    m_per = m // n
    config = config or create_gemm_rs_context(
        m, n_out, k_loc, a.dtype, n_ranks=n
    )
    tile_n = min(config.tile_n, n_out)
    if n_out % tile_n:
        raise ValueError(f"n_out={n_out} not divisible by tile_n={tile_n}")
    num_j = n_out // tile_n
    tile_m = min(config.tile_m or m_per, m_per)
    if m_per % tile_m:
        raise ValueError(f"m_per={m_per} not divisible by tile_m={tile_m}")
    num_i = m_per // tile_m

    if n == 1 and not config.force_kernel:
        return jnp.dot(a, b, preferred_element_type=config.acc_dtype).astype(a.dtype)

    wire = jnp.dtype(config.wire_dtype or a.dtype)
    # Bidir needs an even row-tile split of each chunk; degenerate
    # configs fall back to the single ring.
    bidir = bool(config.bidir) and num_i % 2 == 0 and num_i >= 2
    ndir = 2 if bidir else 1
    separate_final = wire != jnp.dtype(a.dtype)

    def kernel(a_ref, b_ref, o_ref, ws, accbuf, a_vmem, inb_vmem, out_vmem,
               *rest):
        if separate_final:
            fin_vmem, *sems = rest
        else:
            fin_vmem, sems = None, list(rest)
        _gemm_rs_kernel(
            a_ref, b_ref, o_ref, ws, accbuf, a_vmem, inb_vmem, out_vmem,
            fin_vmem, *sems, axis=axis, acc_dtype=config.acc_dtype,
            bidir=bidir,
        )

    scratch = [
        pltpu.VMEM((2, tile_m, k_loc), a.dtype),
        pltpu.VMEM((2, tile_m, tile_n), wire),
        pltpu.VMEM((2, tile_m, tile_n), wire),
    ]
    if separate_final:
        scratch.append(pltpu.VMEM((2, tile_m, tile_n), a.dtype))
    scratch += [
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.DMA((ndir, max(n - 1, 1))),
        pltpu.SemaphoreType.DMA((ndir, max(n - 1, 1))),
    ]

    out, _ws, _acc = comm_pallas_call(
        "tdt_gemm_rs",
        kernel,
        (
            jax.ShapeDtypeStruct((m_per, n_out), a.dtype),
            # n=1 (force_kernel): every ws/accbuf access is RUNTIME-
            # guarded (s>0 / s<n-1 / n>1) but still TRACED, so the dummy
            # shapes must fit each static slice size (≤ m_per rows,
            # ≤ tile_n cols) while dropping the n_out/tile_n-fold dead
            # HBM the full workspaces would allocate.
            jax.ShapeDtypeStruct(
                (n - 1, m_per, n_out) if n > 1 else (1, m_per, tile_n),
                wire,
            ),
            jax.ShapeDtypeStruct(
                (2, m_per, n_out) if n > 1 else (2, m_per, tile_n), wire
            ),
        ),
        grid=(n, num_i, num_j),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(
                (k_loc, tile_n), lambda s, i, j: (0, j), memory_space=pltpu.VMEM
            ),
        ],
        out_specs=(
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ),
        scratch_shapes=scratch,
        collective_id=_GEMM_RS_COLLECTIVE_ID,
        # Mosaic double-buffers the BlockSpec-pipelined operands; at
        # north-star shapes that exceeds the 16 MB default scoped-VMEM
        # limit (v5e/v5p have 128 MB physical). Large-tile configs (the
        # sweep-tuned defaults) need headroom above 64 MB.
        vmem_limit_bytes=overlap_vmem_limit(
            tile_m, k_loc, tile_n, a.dtype.itemsize, out_tile_bufs=3
        ),
        dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        cost_estimate=comm_cost(
            flops=2 * m * k_loc * n_out,
            # A + B read once, partials pushed around the ring(s) in the
            # wire dtype and re-read for the local adds, chunk written.
            bytes_accessed=(a.size + b.size + m_per * n_out)
            * a.dtype.itemsize
            + 3 * (n - 1) * m_per * n_out * wire.itemsize,
        ),
        ctx=ctx,
    )(a, b)
    return out


def gemm_rs_op(
    a: jax.Array,
    b: jax.Array,
    axis: str = "tp",
    config: GemmRSConfig | None = None,
    ctx: DistContext | None = None,
) -> jax.Array:
    """Host-level wrapper: ``a [M, K]`` column-sharded over ``axis``,
    ``b [K, N]`` row-sharded; returns ``[M, N]`` row-sharded (the summed
    GEMM, scattered)."""
    ctx = ctx or current_context()
    f = ctx.shard_map(
        functools.partial(gemm_rs, axis=axis, config=config, ctx=ctx),
        in_specs=(P(None, axis), P(axis, None)),
        out_specs=P(axis, None),
    )
    return f(a, b)
