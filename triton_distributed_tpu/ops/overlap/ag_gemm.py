"""AllGather + GEMM overlap — the TP prefill archetype, in ONE Pallas kernel.

Parity: reference ``kernels/nvidia/allgather_gemm.py`` —
``AllGatherGEMMTensorParallelContext``:417 (symmetric workspace + barrier
alloc), ``create_ag_gemm_context``:489, ``ag_gemm``:534, consumer GEMM
``kernel_consumer_gemm_persistent``:158 (per-tile ``dl.wait`` then
``dl.consume_token`` then ``tl.dot``).

TPU design (SURVEY.md §7 hard part "overlap without streams"): the
reference splits producer (copy-engine/NVSHMEM pushes on a comm stream)
from consumer (GEMM kernel spinning on tile barriers). TPU has no user
streams — instead ONE kernel drives both: the ICI DMA engines carry the
all-gather in the background while the MXU computes, and semaphores
sequence chunk arrival → compute, exactly replacing the reference's
tile-barrier spin loops.

Protocol per device (tp axis, n ranks, A row-sharded [m_per, K], B
column-sharded [K, n_loc]):

1. grid = (n, num_n_tiles); step s computes A-chunk ``(me + s) mod n``
   against B tiles. Starting with the own chunk means compute begins
   with zero comm latency (the reference's rank-swizzled tile order,
   ``threadblock_swizzle``, exists for the same reason).
2. At (0, 0): push own chunk to every peer's workspace slot ``me``
   (single-hop; DMA engines route + progress it concurrently with MXU
   work — the "copy-engine producer" analog).
3. At (s, 0): wait for chunk ``(me+s+1)``'s arrival semaphore and start
   its HBM→VMEM stage into the idle half of a double buffer — the wait
   only stalls if comm is slower than the previous chunk's compute.
4. Compute c[s, j] = a_vmem[s%2] @ b[j] on the MXU.

Output rows come back permuted (step-major); ``ag_gemm`` un-permutes with
a cheap gather, keeping the kernel free of data-dependent output maps.
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

_AG_GEMM_COLLECTIVE_ID = next_collective_id()


@dataclasses.dataclass(frozen=True)
class AGGemmConfig:
    """Tile configuration (parity: the tile fields of
    ``AllGatherGEMMTensorParallelContext``, ``allgather_gemm.py:417``).

    The reference context also owns symmetric workspace tensors; here the
    workspace is kernel-scratch HBM, allocated by Mosaic per call site, so
    the config is pure numbers. ``tile_m`` chunks the per-rank A shard's
    HBM→VMEM staging (parity: the reference's persistent M tiling,
    ``allgather_gemm.py:158``) so baseline shapes — m_per×K far beyond
    VMEM — stream instead of resident-staging.
    """

    tile_n: int = 512
    tile_m: int | None = None  # None → whole m_per (small shapes)
    acc_dtype: jnp.dtype = jnp.float32
    # Arrival-adaptive chunk scheduling (parity: the reference's
    # rank-aware tile-order swizzles, ``threadblock_swizzle_ag_moe.py``
    # / ``ag_gemm_threadblock_swizzle.py`` — compute lands on
    # already-arrived data). At each step boundary the kernel probes
    # every unprocessed chunk's arrival semaphore (non-blocking
    # ``semaphore_read``) and computes the first one that has fully
    # landed, falling back to ring order when none has. In the overlap
    # regime (per-chunk compute ≥ chunk wire time — the regime these
    # kernels are tuned for) every non-laggard chunk has landed by the
    # first boundary, so a straggler is deferred to the END of the
    # schedule and (n-2) other chunks' compute covers most of the lag.
    # Outside that regime the probe can be inconclusive and the
    # schedule degrades toward ring order (the fallback blocks on the
    # ring-next chunk, laggard or not). The realized order is emitted
    # so callers/benchmarks can observe the schedule. TPU-only: ``semaphore_read`` has no
    # interpret-mode lowering, so off-TPU the kernel keeps the static
    # ring order (same split as the LL all-gather's barrier-free mode).
    # None = auto (on real TPU), True/False = forced.
    adaptive: bool | None = None
    # Race-provocation fixtures (parity: ``for_correctness`` producer
    # sleeps, ``allgather_gemm.py:507-508``, and ``straggler_option``,
    # :534). Static: production traces carry zero overhead.
    for_correctness: bool = False
    straggler_rank: int | None = None
    straggler_nanos: int = 500_000


# Per-buffer VMEM staging budget for the A double buffer. Tiles are
# shrunk until tile_m * K * itemsize fits (each of the two buffers gets
# this much). 8 MB (tile_m=1024 at K=4096 bf16) measured best on v5e at
# north-star shapes (perf/sweep_overlap_tiles.py): larger M tiles cut
# the per-(step, tile) B re-streaming, and 1024-wide B tiles keep the
# MXU pipeline full.
_AG_STAGE_BUDGET = 8 * 1024 * 1024


def create_ag_gemm_context(
    m_per: int, n_loc: int, k: int, dtype=jnp.bfloat16, tile_n: int | None = None
) -> AGGemmConfig:
    """Pick tiles for the shapes (parity: ``create_ag_gemm_context``:489)."""
    itemsize = jnp.dtype(dtype).itemsize
    tile_m = m_per
    while tile_m > 128 and tile_m * k * itemsize > _AG_STAGE_BUDGET:
        tile_m //= 2
    while m_per % tile_m:
        tile_m //= 2
    return AGGemmConfig(
        tile_n=pick_tile(n_loc, 1024) if tile_n is None else tile_n,
        tile_m=max(tile_m, 1),
    )


def adaptive_pick(done_smem, recv_sems, chunk_bytes, me, n):
    """Arrival-adaptive chunk pick: first unprocessed chunk whose
    arrival semaphore already counts a full chunk; ring order (first
    unprocessed) when none has landed yet. The probe is non-consuming —
    the caller's blocking wait still drains the chosen chunk's
    semaphore.

    Shared by the overlap kernel and ``perf/adaptive_order_probe.py``
    (the single-chip straggler-reaction observation) so the probe
    exercises EXACTLY the production scheduler logic. Parity: the
    reference's rank-aware tile-order swizzles
    (``threadblock_swizzle_ag_moe.py``)."""
    def scan(off, carry):
        ready_pick, any_pick = carry
        c = jax.lax.rem(me + off, n)
        unproc = done_smem[c] == 0
        ready = dl.read(recv_sems.at[c]) >= chunk_bytes
        any_pick = jnp.where(
            jnp.logical_and(any_pick < 0, unproc), c, any_pick
        )
        ready_pick = jnp.where(
            jnp.logical_and(
                ready_pick < 0, jnp.logical_and(unproc, ready)
            ),
            c,
            ready_pick,
        )
        return ready_pick, any_pick

    ready_pick, any_pick = jax.lax.fori_loop(
        1, n, scan, (jnp.int32(-1), jnp.int32(-1))
    )
    return jnp.where(ready_pick >= 0, ready_pick, any_pick)


def _ag_gemm_kernel(
    a_ref,      # [m_per, K] ANY/HBM — this device's A shard
    b_ref,      # [K, tile_n] VMEM — B tile j (pipelined by BlockSpec)
    c_ref,      # [1, tile_m, tile_n] VMEM — output tile (s, i, j)
    ws,         # [n, m_per, K] ANY/HBM output — gathered A chunks
                # (a workspace; Mosaic only allows VMEM/SMEM/semaphore
                # scratch, so HBM workspaces are extra outputs)
    order_ref,  # [n] SMEM int32 output — chunk processed at each step
    a_vmem,     # [2, tile_m, K] VMEM — double-buffered compute M-tile
    load_sems,  # DMA (2,) — HBM→VMEM stage
    send_sems,  # DMA (n-1,)
    recv_sems,  # DMA (n,) — slot r signaled when chunk r lands
    done_smem,  # [n] SMEM int32 scratch — processed bitmask
    *,
    axis: str,
    acc_dtype,
    adaptive: bool = False,
    for_correctness: bool = False,
    straggler_rank: int | None = None,
    straggler_nanos: int = 0,
):
    me = dl.rank(axis)
    n = dl.num_ranks(axis)
    s = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)
    num_i = pl.num_programs(1)
    num_j = pl.num_programs(2)
    tile_m = a_vmem.shape[1]
    chunk_bytes = ws.shape[1] * ws.shape[2] * jnp.dtype(ws.dtype).itemsize

    def rows(ti):
        return pl.ds(ti * tile_m, tile_m)

    def buf(step, ti):
        return jax.lax.rem(step * num_i + ti, 2)

    def stage(step, ti, chunk=None):
        """HBM→VMEM stage of chunk's M-tile ``ti`` (own shard at step 0)."""
        b = buf(step, ti)
        if chunk is None:  # step 0: own chunk, straight from a_ref
            src = a_ref.at[rows(ti)]
        else:
            src = ws.at[chunk, rows(ti)]
        return pltpu.make_async_copy(src, a_vmem.at[b], load_sems.at[b])

    @pl.when(jnp.logical_and(s == 0, jnp.logical_and(i == 0, j == 0)))
    def _start():
        # Stage own first tile for immediate compute (overlaps barrier).
        stage(0, 0).start()
        # Schedule state: own chunk is step 0 (zero-latency start — the
        # same reason as the reference's rank-swizzled tile order).
        def init(c, carry):
            done_smem[c] = jnp.where(c == me, 1, 0)
            return carry

        jax.lax.fori_loop(0, n, init, None)
        order_ref[0] = me
        # Entry barrier: peers' ws outputs must be allocated before any
        # remote write lands.
        dl.barrier_all(axis)
        # Race fixtures: lag this rank's pushes so any consumer missing a
        # wait reads stale workspace (reference for_correctness sleep /
        # straggler injection).
        dl.straggle_if_rank(straggler_rank, axis, straggler_nanos)
        if for_correctness:
            dl.maybe_delay(200_000)
        # Push own chunk (whole shard, HBM→HBM over ICI) to every peer
        # (slot index = source rank, so consumers wait per-chunk).
        for p in range(1, n):
            peer = jax.lax.rem(me + p, n)
            dl.put_signal(
                a_ref, ws.at[me], peer,
                send_sems.at[p - 1], recv_sems.at[me], axis=axis,
            )
        stage(0, 0).wait()

    @pl.when(jnp.logical_and(s + i > 0, j == 0))
    def _land_current():
        # VMEM stage for (s, i) was started at the previous tile's last j.
        b = buf(s, i)
        pltpu.make_async_copy(
            a_vmem.at[b], a_vmem.at[b], load_sems.at[b]
        ).wait()

    c_ref[0] = jnp.dot(
        a_vmem[buf(s, i)], b_ref[:], preferred_element_type=acc_dtype
    ).astype(c_ref.dtype)

    @pl.when(jnp.logical_and(i + 1 < num_i, j == num_j - 1))
    def _prefetch_same_chunk():
        # Next M-tile of the current chunk — already resident in HBM.
        @pl.when(s == 0)
        def _():
            stage(s, i + 1).start()

        @pl.when(s > 0)
        def _():
            stage(s, i + 1, chunk=order_ref[s]).start()

    # n == 1: the next-chunk block is unreachable (s+1 < n never holds),
    # but Mosaic still compiles the body — where the arrival scan
    # constant-folds to a -1 semaphore index and trips a lowering check
    # (`d >> 32 == 0` seen on-chip). Don't emit it at all.
    @pl.when(
        jnp.logical_and(
            i == num_i - 1, jnp.logical_and(s + 1 < n, j == num_j - 1)
        )
    )
    def _prefetch_next_chunk():
        if n == 1:
            return
        # Arrival fence + first-tile stage for the next chunk, placed
        # after this step's last tile is issued so the blocking wait sits
        # at the end of the step's compute, not ahead of it (keeps the
        # MXU busy while the ICI push is in flight).
        if adaptive:
            nxt = adaptive_pick(done_smem, recv_sems, chunk_bytes, me, n)
        else:
            nxt = jax.lax.rem(me + s + 1, n)
        done_smem[nxt] = 1
        order_ref[s + 1] = nxt
        dl.wait_recv(recv_sems.at[nxt], ws.at[nxt])
        stage(s + 1, 0, chunk=nxt).start()

    @pl.when(
        jnp.logical_and(
            s == n - 1, jnp.logical_and(i == num_i - 1, j == num_j - 1)
        )
    )
    def _drain():
        for p in range(1, n):
            pltpu.make_async_copy(a_ref, a_ref, send_sems.at[p - 1]).wait()


def ag_gemm(
    a: jax.Array,
    b: jax.Array,
    axis: str = "tp",
    config: AGGemmConfig | None = None,
    ctx: DistContext | None = None,
) -> jax.Array:
    """Overlapped ``all_gather(a) @ b`` inside ``shard_map``.

    ``a``: ``[m_per, K]`` row shard; ``b``: ``[K, n_loc]`` column shard.
    Returns ``[n * m_per, n_loc]`` (full rows, local columns) — same
    contract as reference ``ag_gemm`` (``allgather_gemm.py:534``).
    """
    n = jax.lax.axis_size(axis)
    m_per, k = a.shape
    k2, n_loc = b.shape
    if k != k2:
        raise ValueError(f"K mismatch {a.shape} @ {b.shape}")
    config = config or create_ag_gemm_context(m_per, n_loc, k, a.dtype)
    tile_n = min(config.tile_n, n_loc)
    if n_loc % tile_n:
        raise ValueError(f"n_loc={n_loc} not divisible by tile_n={tile_n}")
    num_j = n_loc // tile_n
    tile_m = min(config.tile_m or m_per, m_per)
    if m_per % tile_m:
        raise ValueError(f"m_per={m_per} not divisible by tile_m={tile_m}")
    num_i = m_per // tile_m

    adaptive = config.adaptive
    if adaptive is None:
        from triton_distributed_tpu.ops.common import _on_tpu

        # semaphore_read (the non-blocking arrival probe) has no
        # interpret-mode lowering; off-TPU the kernel keeps ring order.
        adaptive = _on_tpu(ctx)

    grid = (n, num_i, num_j)
    out, _ws, order = comm_pallas_call(
        "tdt_ag_gemm",
        functools.partial(
            _ag_gemm_kernel, axis=axis, acc_dtype=config.acc_dtype,
            adaptive=adaptive,
            for_correctness=config.for_correctness,
            straggler_rank=config.straggler_rank,
            straggler_nanos=config.straggler_nanos,
        ),
        (
            jax.ShapeDtypeStruct((n, m_per, n_loc), a.dtype),
            jax.ShapeDtypeStruct((n, m_per, k), a.dtype),
            jax.ShapeDtypeStruct((n,), jnp.int32),
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # a: manual DMA
            pl.BlockSpec(
                (k, tile_n), lambda s, i, j: (0, j), memory_space=pltpu.VMEM
            ),
        ],
        out_specs=(
            pl.BlockSpec(
                (1, tile_m, tile_n),
                lambda s, i, j: (s, i, j),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ),
        scratch_shapes=[
            pltpu.VMEM((2, tile_m, k), a.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((max(n - 1, 1),)),
            pltpu.SemaphoreType.DMA((n,)),
            pltpu.SMEM((max(n, 1),), jnp.int32),
        ],
        collective_id=_AG_GEMM_COLLECTIVE_ID,
        # Mosaic double-buffers the BlockSpec-pipelined operands; at
        # north-star shapes that exceeds the 16 MB default scoped-VMEM
        # limit (v5e/v5p have 128 MB physical). Large-tile configs (the
        # sweep-tuned defaults) need headroom above 64 MB.
        vmem_limit_bytes=overlap_vmem_limit(
            tile_m, k, tile_n, a.dtype.itemsize, out_tile_bufs=1
        ),
        dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
        cost_estimate=comm_cost(
            flops=2 * n * m_per * k * n_loc,
            # A streamed in + pushed around the ring, B read per step,
            # gathered A and the output written once.
            bytes_accessed=(2 * n * a.size + n * b.size + n * a.size
                            + n * m_per * n_loc) * a.dtype.itemsize,
        ),
        ctx=ctx,
    )(a, b)

    # The kernel emits the realized schedule (order[s] = chunk computed
    # at step s — ring order, or arrival order when adaptive). Global
    # row-chunk r sits at the step where order[step] == r; argsort of a
    # permutation inverts it. One gather puts rows in global order.
    return out[jnp.argsort(order)].reshape(n * m_per, n_loc)


def ag_gemm_op(
    a: jax.Array,
    b: jax.Array,
    axis: str = "tp",
    config: AGGemmConfig | None = None,
    ctx: DistContext | None = None,
) -> jax.Array:
    """Host-level wrapper: ``a`` row-sharded over ``axis``, ``b``
    column-sharded; returns C with columns sharded (host shape [M, N])."""
    ctx = ctx or current_context()
    f = ctx.shard_map(
        functools.partial(ag_gemm, axis=axis, config=config, ctx=ctx),
        in_specs=(P(axis, None), P(None, axis)),
        out_specs=P(None, axis),
    )
    return f(a, b)
