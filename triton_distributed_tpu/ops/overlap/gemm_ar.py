"""GEMM + AllReduce overlap — the TP decode-latency archetype.

Parity: reference ``kernels/nvidia/gemm_allreduce.py`` —
``GemmARContext``/``LLGemmARContext``:48/74, persistent GEMM-with-notify
:329/389, ``consumer_all_reduce_kernel``:124, fused one-kernel variant
:233, ops :509/546 — whose role is the row-parallel o-proj/fc2 GEMM of a
TP decode step where the partial products must be summed across ranks
and *every* rank needs the full result.

TPU design, two methods (mirroring the reference's LL one-shot vs
two-shot split):

- ``ONE_SHOT``: one fused Pallas kernel. The GEMM is tiled over N; as
  each output tile comes off the MXU it is broadcast to every peer's
  arrival slot with ``put_signal`` while the MXU moves on to the next
  tile (comm of tile j hides under compute of tile j+1 — the same
  per-tile notify pipelining as the reference's persistent GEMM
  producer). A second grid phase waits per-(peer, tile) arrival
  semaphores and reduces the n partials locally. Latency-optimal for
  decode shapes (small M·N): every payload crosses the ICI once.
- ``TWO_SHOT``: composition of the overlapped ring ``gemm_rs`` kernel
  (GEMM hidden under ring reduce-scatter) with a bidirectional-ring
  all-gather — bandwidth-optimal for prefill shapes, the same
  RS-then-AG structure XLA uses for large psums.
"""

from __future__ import annotations

import dataclasses
import enum
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from triton_distributed_tpu import language as dl
from triton_distributed_tpu.ops.common import (
    device_initiable,
    VMEM_COMM_MAX_BYTES,
    comm_cost,
    comm_pallas_call,
    next_collective_id,
    pick_tile,
)
from triton_distributed_tpu.ops.collectives.all_gather import (
    AllGatherMethod,
    all_gather,
)
from triton_distributed_tpu.ops.overlap.gemm_rs import GemmRSConfig, gemm_rs
from triton_distributed_tpu.runtime.mesh import DistContext, current_context

_GEMM_AR_COLLECTIVE_ID = next_collective_id()

# Above this full-output size the one-shot kernel's n-copy arrival
# buffer stops paying for its single-hop latency win (parity: the
# size-based LL/two-shot dispatch in ``gemm_allreduce.py:509-546``).
_ONE_SHOT_MAX_BYTES = 512 * 1024


class GemmARMethod(enum.Enum):
    AUTO = "auto"
    XLA = "xla"  # psum(a @ b) — XLA's own overlap scheduling
    ONE_SHOT = "one_shot"  # fused per-tile broadcast + local reduce
    TWO_SHOT = "two_shot"  # overlapped gemm_rs ring + ring all-gather


@dataclasses.dataclass(frozen=True)
class GemmARConfig:
    """Parity: tile fields of ``GemmARContext`` (``gemm_allreduce.py:48``)."""

    tile_n: int = 512
    acc_dtype: jnp.dtype = jnp.float32


def create_gemm_ar_context(
    m: int, n_out: int, k_loc: int, dtype=jnp.bfloat16, tile_n: int | None = None
) -> GemmARConfig:
    return GemmARConfig(tile_n=pick_tile(n_out) if tile_n is None else tile_n)


def _gemm_ar_one_shot_kernel(
    a_ref,      # [M, k_loc] VMEM — this device's K shard of A (resident)
    b_ref,      # [k_loc, tile_n] VMEM — B tile min(s, num_j-1)
    o_ref,      # [M, tile_n] VMEM — reduced output tile max(s-1, 0)
    ws,         # [n, M, N] ANY/HBM output — slot p holds peer p's partial
    *rest,      # [tr (SMEM ring, trace only)], sbuf, vbuf, sems, [clk]
    axis: str,
    acc_dtype,
    trace: bool = False,
):
    if trace:
        tr, sbuf, vbuf, stage_sem, send_sems, recv_sems, clk = rest
    else:
        tr = clk = None
        sbuf, vbuf, stage_sem, send_sems, recv_sems = rest
    me = dl.rank(axis)
    n = dl.num_ranks(axis)
    s = pl.program_id(0)
    num_j = pl.num_programs(0) - 1

    # Device task-tracer seam (docs/observability.md "Device task
    # tracer"): the standalone overlap kernel records the SAME ring
    # format as the megakernel — produce phases as AR_SEND rows (mid =
    # puts in flight), reduce phases as AR_WAIT rows (mid = partials
    # landed), the drain as a BARRIER row — decoded by the one
    # obs/kernel_trace.py decoder (strict=False: iterations only run
    # the phases their grid position owns). Phase rows sit in
    # EXECUTION order (0 produce, 1 reduce, 2 drain — the order the
    # pl.when blocks run within an iteration), so the decoder's
    # per-step clock-monotonicity check holds on real rings.
    def tick():
        c = clk[0] + 1
        clk[0] = c
        return c

    def record(phase, opcode, slot, begin, end, mid):
        tr[s, phase, 0] = s          # task_id = grid iteration
        tr[s, phase, 1] = opcode
        tr[s, phase, 2] = 0          # layer
        tr[s, phase, 3] = slot       # tile index
        tr[s, phase, 4] = begin
        tr[s, phase, 5] = end
        tr[s, phase, 6] = mid
        tr[s, phase, 7] = 1

    @pl.when(s == 0)
    def _entry():
        # Peers' ws slots must exist before the first remote put lands.
        if trace:
            clk[0] = 0
        dl.barrier_all(axis)

    @pl.when(s < num_j)
    def _produce():
        # Partial tile s off the MXU → local slot (HBM) → broadcast. The
        # remote puts are non-blocking: tile s's n-1 sends drain while
        # tile s-1 is being reduced and tile s+1 is on the MXU (per-tile
        # notify pipelining, as the reference's producer GEMM does with
        # its tile barriers).
        begin = tick() if trace else None
        tile_n = b_ref.shape[1]
        jsl = pl.ds(s * tile_n, tile_n)
        sbuf[:] = jnp.dot(
            a_ref[:], b_ref[:], preferred_element_type=acc_dtype
        ).astype(sbuf.dtype)
        dma = dl.local_copy(sbuf, ws.at[me].at[:, jsl], stage_sem)
        dma.start()
        dma.wait()
        for i in range(1, n):
            peer = jax.lax.rem(me + i, n)
            dl.put_signal(
                ws.at[me].at[:, jsl], ws.at[me].at[:, jsl], peer,
                send_sems.at[i - 1], recv_sems.at[me, s], axis=axis,
            )
        if trace:
            mid = tick()  # puts in flight
            record(0, 12, s, begin, tick(), mid)  # TaskType.AR_SEND

    @pl.when(s > 0)
    def _reduce():
        # Reduce tile s-1: wait its n-1 inbound partials (per-(src, tile)
        # semaphores — the analog of the reference consumer's per-tile
        # ``dl.wait`` + ``consume_token``), stage, sum locally.
        begin = tick() if trace else None
        tile_n = o_ref.shape[1]
        j = s - 1
        jsl = pl.ds(j * tile_n, tile_n)
        for i in range(1, n):
            src = jax.lax.rem(me + i, n)
            dl.wait_recv(recv_sems.at[src, j], ws.at[src].at[:, jsl])
        if trace:
            mid = tick()  # partials landed; the rest is the local fold
        dma = dl.local_copy(ws.at[:, :, jsl], vbuf, stage_sem)
        dma.start()
        dma.wait()
        acc = vbuf[0].astype(acc_dtype)
        for i in range(1, n):
            acc = acc + vbuf[i].astype(acc_dtype)
        o_ref[:] = acc.astype(o_ref.dtype)
        if trace:
            record(1, 13, j, begin, tick(), mid)  # TaskType.AR_WAIT

    @pl.when(s == num_j)
    def _drain():
        # All num_j tiles were sent to each peer: [M, N] bytes per peer.
        begin = tick() if trace else None
        for i in range(1, n):
            pltpu.make_async_copy(
                ws.at[me], ws.at[me], send_sems.at[i - 1]
            ).wait()
        if trace:
            # Phase row 2: the drain runs AFTER this iteration's
            # reduce — its row index must follow reduce's or the
            # decoder's monotonicity check would misfire.
            record(2, 9, 0, begin, tick(), 0)  # TaskType.BARRIER


def gemm_ar(
    a: jax.Array,
    b: jax.Array,
    axis: str = "tp",
    method: GemmARMethod = GemmARMethod.AUTO,
    config: GemmARConfig | None = None,
    ctx: DistContext | None = None,
    trace: bool = False,
) -> jax.Array:
    """Overlapped ``psum(a @ b)`` inside ``shard_map``.

    ``a``: ``[M, k_loc]`` column shard; ``b``: ``[k_loc, N]`` row shard.
    Every device returns the full reduced ``[M, N]`` — same contract as
    reference ``gemm_allreduce_op`` (``gemm_allreduce.py:509``).

    ``trace=True`` (ONE_SHOT only) additionally returns this shard's
    device task ring ``[num_j+1, 3, 8]`` int32 — produce/reduce/drain
    phase rows IN EXECUTION ORDER per grid iteration (produce < reduce
    < drain, so ``validate_ring``'s per-step monotonicity holds), in
    the megakernel tracer's format, decoded by
    ``obs.kernel_trace.decode_trace(..., strict=False)`` — iterations
    only write the phases their grid position owns
    (docs/observability.md "Device task tracer"). Note the decoder's
    ``overlap_report`` windows pair AR_SEND/AR_WAIT within one step:
    this kernel's send (tile j, iteration j) and its wait (iteration
    j+1) land in different steps — reshape the ring to one step
    (``ring.reshape(ranks, 1, -1, 8)``) to pair them.
    """
    n = jax.lax.axis_size(axis)
    m, k_loc = a.shape
    _, n_out = b.shape
    config = config or create_gemm_ar_context(m, n_out, k_loc, a.dtype)
    if trace and method is not GemmARMethod.ONE_SHOT:
        raise ValueError(
            "trace=True requires method=ONE_SHOT (the ring rides the "
            "fused kernel; XLA/TWO_SHOT paths have no device ring)"
        )

    if n == 1:
        out = jnp.dot(
            a, b, preferred_element_type=config.acc_dtype
        ).astype(a.dtype)
        if trace:
            # No fused kernel ran (single rank: nothing to overlap) —
            # keep the documented (out, ring) arity with an all-zero
            # (= all-unwritten) ring so strict=False decodes to [].
            tile_n = min(config.tile_n, n_out)
            num_j = n_out // max(tile_n, 1)
            return out, jnp.zeros((num_j + 1, 3, 8), jnp.int32)
        return out

    out_bytes = m * n_out * a.dtype.itemsize
    if method == GemmARMethod.AUTO:
        # Both kernels slice the [M, N] workspace by rows, and Mosaic
        # refuses a slice that does not cover whole sublane tiles
        # ("Slice shape ... must be aligned to tiling"): the one-shot
        # kernel takes all M rows — whole 8-row tiles, or one
        # power-of-two tile no smaller than a packed 32-bit row — and
        # the two-shot ring halves each rank's M/n-row chunk. Any other
        # M (an odd --max-batch, a 96-token chunk at tp=4) goes to XLA.
        rows_ok = m % 8 == 0 or (
            m in (1, 2, 4) and m * a.dtype.itemsize >= 4
        )
        if not device_initiable(axis, ctx):
            method = GemmARMethod.XLA
        elif out_bytes <= _ONE_SHOT_MAX_BYTES and rows_ok:
            method = GemmARMethod.ONE_SHOT
        elif m % (16 * n) == 0 and out_bytes <= VMEM_COMM_MAX_BYTES:
            # The trailing ring all-gather holds the full [M, N] in VMEM.
            method = GemmARMethod.TWO_SHOT
        else:
            method = GemmARMethod.XLA

    if method == GemmARMethod.XLA:
        return jax.lax.psum(
            jnp.dot(a, b, preferred_element_type=config.acc_dtype).astype(a.dtype),
            axis,
        )

    if method == GemmARMethod.TWO_SHOT:
        reduced = gemm_rs(
            a, b, axis=axis,
            config=GemmRSConfig(
                tile_n=config.tile_n, acc_dtype=config.acc_dtype
            ),
            ctx=ctx,
        )
        # AUTO applies the VMEM-size / on-TPU guards inside all_gather.
        return all_gather(reduced, axis, AllGatherMethod.AUTO, ctx)

    # ONE_SHOT
    tile_n = min(config.tile_n, n_out)
    if n_out % tile_n:
        raise ValueError(f"n_out={n_out} not divisible by tile_n={tile_n}")
    num_j = n_out // tile_n

    outs = comm_pallas_call(
        "tdt_gemm_ar",
        functools.partial(
            _gemm_ar_one_shot_kernel, axis=axis,
            acc_dtype=config.acc_dtype, trace=trace,
        ),
        (
            jax.ShapeDtypeStruct((m, n_out), a.dtype),
            jax.ShapeDtypeStruct((n, m, n_out), a.dtype),
        ) + ((
            # Device task ring: [grid, phase, TRACE_INTS] — phases in
            # execution order (0 produce, 1 reduce, 2 drain); not every
            # iteration runs every phase, so the decoder skips
            # unwritten rows with strict=False.
            jax.ShapeDtypeStruct((num_j + 1, 3, 8), jnp.int32),
        ) if trace else ()),
        grid=(num_j + 1,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(
                (k_loc, tile_n),
                lambda s: (0, jnp.minimum(s, num_j - 1)),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=(
            pl.BlockSpec(
                (m, tile_n),
                lambda s: (0, jnp.maximum(s - 1, 0)),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(memory_space=pl.ANY),
        ) + ((pl.BlockSpec(memory_space=pltpu.SMEM),) if trace else ()),
        scratch_shapes=[
            pltpu.VMEM((m, tile_n), a.dtype),
            pltpu.VMEM((n, m, tile_n), a.dtype),
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA((n - 1,)),
            pltpu.SemaphoreType.DMA((n, num_j)),
        ] + ([pltpu.SMEM((1,), jnp.int32)] if trace else []),
        collective_id=_GEMM_AR_COLLECTIVE_ID,
        # Mosaic double-buffers the BlockSpec-pipelined operands; at
        # north-star shapes that exceeds the 16 MB default scoped-VMEM
        # limit (v5e/v5p have 128 MB physical).
        vmem_limit_bytes=64 * 1024 * 1024,
        dimension_semantics=("arbitrary",),
        cost_estimate=comm_cost(
            flops=2 * m * k_loc * n_out,
            # A + B read, partials broadcast to n peers, n landed
            # partials re-read for the reduction, output written.
            bytes_accessed=(a.size + b.size
                            + 2 * n * m * n_out + m * n_out)
            * a.dtype.itemsize,
        ),
        ctx=ctx,
    )(a, b)
    if trace:
        return outs[0], outs[2]
    return outs[0]


def gemm_ar_op(
    a: jax.Array,
    b: jax.Array,
    axis: str = "tp",
    method: GemmARMethod = GemmARMethod.AUTO,
    config: GemmARConfig | None = None,
    ctx: DistContext | None = None,
    trace: bool = False,
) -> jax.Array:
    """Host-level wrapper: ``a [M, K]`` column-sharded over ``axis``,
    ``b [K, N]`` row-sharded; returns the full ``[M, N]`` (replicated) —
    the summed GEMM on every device. ``trace=True`` (ONE_SHOT only)
    returns ``(out, ring [n_ranks, num_j+1, 3, 8])`` — the per-rank
    device task rings (docs/observability.md "Device task tracer")."""
    ctx = ctx or current_context()
    if trace:
        def shard(a_, b_):
            out, ring = gemm_ar(
                a_, b_, axis=axis, method=method, config=config,
                ctx=ctx, trace=True,
            )
            return out, ring[None]

        f = ctx.shard_map(
            shard,
            in_specs=(P(None, axis), P(axis, None)),
            out_specs=(P(None, None), P(axis)),
        )
        return f(a, b)
    f = ctx.shard_map(
        functools.partial(gemm_ar, axis=axis, method=method, config=config, ctx=ctx),
        in_specs=(P(None, axis), P(axis, None)),
        out_specs=P(None, None),
    )
    return f(a, b)
