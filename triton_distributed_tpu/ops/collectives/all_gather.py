"""AllGather: XLA path + device-initiated Pallas ring protocols over ICI.

Parity: reference ``kernels/nvidia/allgather.py`` — ``AllGatherMethod``
enum (:46, FullMesh/Ring1D/Ring2D push/pull) and the copy-engine /
NVSHMEM producers (:81-471).

TPU design: ICI is a torus of point-to-point links, so the native
protocols are rings; a "full mesh" push (every peer DMAs to every peer
simultaneously) is also expressible and wins at small sizes (one hop
latency instead of n-1). The XLA method is the NCCL-analog golden path.
Ring step count and peer index arithmetic are static at trace time
(axis sizes are Python ints), so protocols unroll fully — no scalar
loops on the core.
"""

from __future__ import annotations

import enum
import functools

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from triton_distributed_tpu import language as dl
from triton_distributed_tpu.ops.common import (
    device_initiable,
    comm_pallas_call,
    next_collective_id,
)
from triton_distributed_tpu.runtime.mesh import DistContext, current_context


class AllGatherMethod(enum.Enum):
    """Parity: ``allgather.py:46`` (auto/full-mesh/ring variants)."""

    AUTO = "auto"
    XLA = "xla"
    PALLAS_RING = "pallas_ring"
    PALLAS_BIDIR_RING = "pallas_bidir_ring"
    PALLAS_FULL_MESH = "pallas_full_mesh"
    PALLAS_PULL = "pallas_pull"


_AG_COLLECTIVE_ID = next_collective_id()


def _ring_kernel(x_ref, o_ref, copy_sem, send_sems, recv_sems, *, axis: str):
    """Unidirectional ring: at step s forward the chunk received at step
    s-1 to the right neighbor; chunks land at their global row offset.

    Equivalent role: the reference's copy-engine 1-D ring-push
    all-gather producer (``allgather.py:140``), with the copy engine
    replaced by the ICI DMA engine and the tile barrier by per-step
    recv semaphores.

    All refs live in ANY/HBM and every byte moves by DMA — the kernel is
    pure orchestration, so payload size is bounded by HBM, not VMEM.
    """
    me = dl.rank(axis)
    n = dl.num_ranks(axis)
    m_per = x_ref.shape[0]
    right = jax.lax.rem(me + 1, n)

    # Own shard lands at its global offset (local HBM→HBM DMA), started
    # under the barrier.
    cp = pltpu.make_async_copy(
        x_ref, o_ref.at[pl.ds(me * m_per, m_per)], copy_sem
    )
    cp.start()
    # Entry barrier: peers must have entered (their o_ref allocated and
    # no longer owned by preceding XLA ops) before any remote write.
    dl.barrier_all(axis)
    cp.wait()

    dmas = []
    for s in range(n - 1):
        # Chunk to send this step originated at (me - s) mod n.
        src_rank = jax.lax.rem(me - s + n, n)
        sl = pl.ds(src_rank * m_per, m_per)
        dmas.append(
            dl.put_signal(
                o_ref.at[sl], o_ref.at[sl], right,
                send_sems.at[s], recv_sems.at[s], axis=axis,
            )
        )
        # This step's incoming chunk originated at (me - s - 1) mod n.
        in_rank = jax.lax.rem(me - s - 1 + n, n)
        dl.wait_recv(recv_sems.at[s], o_ref.at[pl.ds(in_rank * m_per, m_per)])
    dl.quiet(*dmas)


def _bidir_ring_kernel(
    x_ref, o_ref, copy_sem, send_sems, recv_sems, *, axis: str
):
    """Bidirectional ring: each shard's top half travels clockwise and
    bottom half counter-clockwise, using both directions of the torus
    axis — 2x effective ICI bandwidth, (n-1) steps of half-chunks.

    Equivalent role: the reference's NUMA-aware 2D rings
    (``allgather.py:196``) — different topology, same idea: use every
    link concurrently. ANY/HBM refs, DMA-only (no VMEM ceiling).
    """
    me = dl.rank(axis)
    n = dl.num_ranks(axis)
    m_per = x_ref.shape[0]
    half = m_per // 2
    right = jax.lax.rem(me + 1, n)
    left = jax.lax.rem(me - 1 + n, n)

    cp = pltpu.make_async_copy(
        x_ref, o_ref.at[pl.ds(me * m_per, m_per)], copy_sem
    )
    cp.start()
    dl.barrier_all(axis)
    cp.wait()

    dmas = []
    for s in range(n - 1):
        cw_src = jax.lax.rem(me - s + n, n)
        cw_sl = pl.ds(cw_src * m_per, half)
        dmas.append(
            dl.put_signal(
                o_ref.at[cw_sl], o_ref.at[cw_sl], right,
                send_sems.at[0, s], recv_sems.at[0, s], axis=axis,
            )
        )
        ccw_src = jax.lax.rem(me + s, n)
        ccw_sl = pl.ds(ccw_src * m_per + half, m_per - half)
        dmas.append(
            dl.put_signal(
                o_ref.at[ccw_sl], o_ref.at[ccw_sl], left,
                send_sems.at[1, s], recv_sems.at[1, s], axis=axis,
            )
        )
        cw_in = jax.lax.rem(me - s - 1 + n, n)
        ccw_in = jax.lax.rem(me + s + 1, n)
        dl.wait_recv(recv_sems.at[0, s], o_ref.at[pl.ds(cw_in * m_per, half)])
        dl.wait_recv(
            recv_sems.at[1, s],
            o_ref.at[pl.ds(ccw_in * m_per + half, m_per - half)],
        )
    dl.quiet(*dmas)


def _full_mesh_kernel(
    x_ref, o_ref, copy_sem, send_sems, recv_sems, *, axis: str
):
    """Every device pushes its shard directly to every peer (1 hop).

    Equivalent role: ``cp_engine_producer_all_gather_full_mesh_push``
    (reference ``allgather.py:81``). Best at small sizes where per-hop
    latency dominates; the fabric routes concurrent DMAs.

    All arrivals share one recv semaphore: shards are equal-sized, so
    waiting (n-1) shard-sizes is order-independent. ANY/HBM, DMA-only.
    """
    me = dl.rank(axis)
    n = dl.num_ranks(axis)
    m_per = x_ref.shape[0]
    own = pl.ds(me * m_per, m_per)

    cp = pltpu.make_async_copy(x_ref, o_ref.at[own], copy_sem)
    cp.start()
    dl.barrier_all(axis)
    cp.wait()

    dmas = []
    for i in range(1, n):
        peer = jax.lax.rem(me + i, n)
        dmas.append(
            dl.put_signal(
                o_ref.at[own], o_ref.at[own], peer,
                send_sems.at[i - 1], recv_sems, axis=axis,
            )
        )
    for _ in range(1, n):
        dl.wait_recv(recv_sems, o_ref.at[own])
    dl.quiet(*dmas)


def _pull_kernel(
    x_ref, o_ref, copy_sem, send_sems, recv_sems, req_sems,
    *, axis: str, window: int
):
    """Receiver-driven (pull) full-mesh gather.

    Equivalent role: the reference's pull producers —
    ``cp_engine_producer_all_gather_full_mesh_pull`` (``allgather.py:106``)
    and the LL ``_forward_pull`` (``low_latency_allgather.py:48``). The
    ICI DMA engine is push-only, so "pull" is the :func:`dl.request` /
    :func:`dl.serve_get` rendezvous: shard ``s`` only moves after the
    receiver asks for it, paced ``window`` requests at a time, so a rank
    never suffers n-1 simultaneous inbound DMAs (the incast the push
    full-mesh creates and a straggler amplifies).

    NO entry barrier — a serve is gated on the requester's own request,
    which proves its ``o_ref`` is live (see :func:`dl.request`). At
    ``window >= n-1`` this is latency-equivalent to full-mesh push minus
    the barrier hop, plus one request signal.

    Deadlock-freedom (serve order is ascending step ``s``): serve step
    ``s`` consumes request #``s``, which rank ``me-s`` issues either up
    front (``s <= window``) or after its arrival ``s-window`` — produced
    by serve step ``s-window`` of another rank. Every wait therefore
    depends only on strictly smaller serve steps; induction on ``s``
    closes the cycle-free argument.
    """
    me = dl.rank(axis)
    n = dl.num_ranks(axis)
    m_per = x_ref.shape[0]
    own = pl.ds(me * m_per, m_per)
    # n=1: both loops must be empty (w=0) — a self-request would leave
    # req_sems[0] signaled but never served at kernel exit.
    w = min(max(window, 1), n - 1)

    cp = pltpu.make_async_copy(x_ref, o_ref.at[own], copy_sem)
    cp.start()

    # Window of outstanding pull requests: ask peers me+1 .. me+w first.
    for i in range(1, w + 1):
        dl.request(req_sems.at[i - 1], jax.lax.rem(me + i, n), axis)

    dmas = []
    for s in range(1, n):
        # Serve: requester me-s asked for my shard with its request #s.
        requester = jax.lax.rem(me - s + n, n)
        dmas.append(
            dl.serve_get(
                req_sems.at[s - 1], x_ref, o_ref.at[own], requester,
                send_sems.at[s - 1], recv_sems.at[s - 1], axis,
            )
        )
        # My own request #s has now been served by peer me+s.
        src = jax.lax.rem(me + s, n)
        dl.wait_recv(recv_sems.at[s - 1], o_ref.at[pl.ds(src * m_per, m_per)])
        if s + w <= n - 1:
            dl.request(
                req_sems.at[s + w - 1], jax.lax.rem(me + s + w, n), axis
            )
    cp.wait()
    dl.quiet(*dmas)


def all_gather(
    x: jax.Array,
    axis: str = "tp",
    method: AllGatherMethod = AllGatherMethod.AUTO,
    ctx: DistContext | None = None,
    pull_window: int = 2,
) -> jax.Array:
    """Gather shards along ``axis`` into the leading dim. Call inside
    ``shard_map``; ``x`` is this device's shard ``[m_per, ...]`` and the
    result is ``[n * m_per, ...]``.
    """
    n = jax.lax.axis_size(axis)
    if method == AllGatherMethod.AUTO:
        if not device_initiable(axis, ctx) or x.ndim < 2:
            # CPU-simulator meshes run Pallas in interpret mode, which is
            # for explicit kernel tests only; 1-D payloads (biases etc.)
            # also take the XLA path the Pallas kernels don't cover.
            method = AllGatherMethod.XLA
        else:
            # DMA-only kernels: no VMEM ceiling (payload stays in HBM).
            nbytes = x.size * x.dtype.itemsize
            if n <= 2 or nbytes <= 64 * 1024:
                method = AllGatherMethod.PALLAS_FULL_MESH
            else:
                method = AllGatherMethod.PALLAS_BIDIR_RING

    if method == AllGatherMethod.XLA:
        return jax.lax.all_gather(x, axis, tiled=True)

    if x.ndim < 2:
        raise ValueError("pallas all_gather needs >=2D input (rows, lanes)")
    m_per = x.shape[0]
    out_shape = jax.ShapeDtypeStruct((n * m_per, *x.shape[1:]), x.dtype)

    if method == AllGatherMethod.PALLAS_BIDIR_RING and (m_per < 2 or n <= 2):
        method = AllGatherMethod.PALLAS_RING  # halves degenerate

    if method == AllGatherMethod.PALLAS_RING:
        kernel = functools.partial(_ring_kernel, axis=axis)
        scratch = [
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA((max(n - 1, 1),)),
            pltpu.SemaphoreType.DMA((max(n - 1, 1),)),
        ]
    elif method == AllGatherMethod.PALLAS_BIDIR_RING:
        kernel = functools.partial(_bidir_ring_kernel, axis=axis)
        scratch = [
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA((2, max(n - 1, 1))),
            pltpu.SemaphoreType.DMA((2, max(n - 1, 1))),
        ]
    elif method == AllGatherMethod.PALLAS_FULL_MESH:
        kernel = functools.partial(_full_mesh_kernel, axis=axis)
        scratch = [
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA((max(n - 1, 1),)),
            pltpu.SemaphoreType.DMA(()),
        ]
    elif method == AllGatherMethod.PALLAS_PULL:
        kernel = functools.partial(_pull_kernel, axis=axis, window=pull_window)
        scratch = [
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA((max(n - 1, 1),)),
            pltpu.SemaphoreType.DMA((max(n - 1, 1),)),
            pltpu.SemaphoreType.REGULAR((max(n - 1, 1),)),
        ]
    else:
        raise ValueError(f"unknown method {method}")

    return comm_pallas_call(
        "tdt_all_gather_" + method.value.removeprefix("pallas_"),
        kernel,
        out_shape,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=scratch,
        collective_id=_AG_COLLECTIVE_ID,
        ctx=ctx,
    )(x)


_AG_2D_COLLECTIVE_ID = next_collective_id()


def _torus_2d_kernel(
    x_ref,       # [m_per, L] ANY — own shard
    o_ref,       # [nx*ny*m_per, L] ANY — gathered, rank-major slots
    copy_sem,    # DMA ()
    send_y_sems,  # DMA (ny-1,)
    send_x_sems,  # DMA (nx-1, ny)
    recv_y_sems,  # DMA (ny,) — slot j' for column chunk (me_x, j')
    recv_x_sem,   # DMA () — byte counter for all row arrivals
    *,
    ax: str,
    ay: str,
):
    """Fused 2D-torus all-gather (equivalent role: the reference's
    NUMA-aware 2D producers, ``allgather.py:196`` ``ring_push_numa_2d``
    — use BOTH torus axes' links concurrently).

    Phase y: own chunk full-mesh along the column (``ay``). Phase x:
    every column chunk — own immediately, peers' AS EACH ARRIVES — is
    forwarded full-mesh along the row (``ax``), so row links carry
    traffic while column pushes are still in flight; no phase barrier.
    All transfers are row-or-column, so ONE combined row+column entry
    barrier (``dl.barrier_cross`` — NOT two sequential per-axis
    barriers, whose anonymous signals would alias on the kernel's
    single barrier semaphore) gives peer-buffer liveness without a
    diagonal handshake.
    """
    mx = dl.rank(ax)
    my = dl.rank(ay)
    nx = dl.num_ranks(ax)
    ny = dl.num_ranks(ay)
    m_per = x_ref.shape[0]

    def slot(gx, gy):
        return pl.ds((gx * ny + gy) * m_per, m_per)

    own = slot(mx, my)
    cp = pltpu.make_async_copy(x_ref, o_ref.at[own], copy_sem)
    cp.start()
    dl.barrier_cross(ax, ay)
    cp.wait()

    dmas = []
    # Column broadcast of the own chunk (y links busy first).
    for q in range(1, ny):
        peer = jax.lax.rem(my + q, ny)
        dmas.append(
            dl.put_signal(
                o_ref.at[own], o_ref.at[own], peer,
                send_y_sems.at[q - 1], recv_y_sems.at[my], axis=ay,
            )
        )
    # Row broadcast of the own chunk — x links busy concurrently.
    for p in range(1, nx):
        peer = jax.lax.rem(mx + p, nx)
        dmas.append(
            dl.put_signal(
                o_ref.at[own], o_ref.at[own], peer,
                send_x_sems.at[p - 1, my], recv_x_sem, axis=ax,
            )
        )
    # Forward each column chunk along the row as it arrives.
    for q in range(1, ny):
        src_y = jax.lax.rem(my + q, ny)
        sl = slot(mx, src_y)
        dl.wait_recv(recv_y_sems.at[src_y], o_ref.at[sl])
        for p in range(1, nx):
            peer = jax.lax.rem(mx + p, nx)
            dmas.append(
                dl.put_signal(
                    o_ref.at[sl], o_ref.at[sl], peer,
                    send_x_sems.at[p - 1, src_y], recv_x_sem, axis=ax,
                )
            )
    # Row arrivals: (nx-1) stripes of ny chunks, all chunk-sized, on one
    # byte-counting semaphore.
    for _ in range((nx - 1) * ny):
        dl.wait_recv(recv_x_sem, o_ref.at[own])
    dl.quiet(*dmas)


def all_gather_torus_2d(
    x: jax.Array,
    axes: tuple[str, str] = ("dp", "tp"),
    ctx: DistContext | None = None,
) -> jax.Array:
    """Fused all-gather over a 2D torus mesh (distinct from the 2-LEVEL
    ``hierarchical.all_gather_2d_op``, which splits ICI/DCN — here BOTH
    axes are ICI and one kernel drives all four link directions): shards gathered across
    BOTH axes in one kernel, rank-major ((ax, ay) row-major) row order.
    Call inside ``shard_map``; ``x`` is ``[m_per, ...]``, result
    ``[nx*ny*m_per, ...]``."""
    ax, ay = axes
    nx = jax.lax.axis_size(ax)
    ny = jax.lax.axis_size(ay)
    if x.ndim < 2:
        raise ValueError("pallas all_gather_torus_2d needs >=2D input")
    m_per = x.shape[0]
    out_shape = jax.ShapeDtypeStruct((nx * ny * m_per, *x.shape[1:]), x.dtype)
    return comm_pallas_call(
        "tdt_all_gather_torus_2d",
        functools.partial(_torus_2d_kernel, ax=ax, ay=ay),
        out_shape,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA((max(ny - 1, 1),)),
            pltpu.SemaphoreType.DMA((max(nx - 1, 1), ny)),
            pltpu.SemaphoreType.DMA((ny,)),
            pltpu.SemaphoreType.DMA(()),
        ],
        collective_id=_AG_2D_COLLECTIVE_ID,
        ctx=ctx,
    )(x)


def all_gather_op(
    x: jax.Array,
    axis: str = "tp",
    method: AllGatherMethod = AllGatherMethod.AUTO,
    ctx: DistContext | None = None,
    pull_window: int = 2,
) -> jax.Array:
    """Host-level wrapper: ``x`` is sharded along its leading dim over
    ``axis``; result is the gathered (replicated) array. Mainly for
    tests/benchmarks — layers call :func:`all_gather` inside their own
    ``shard_map``.
    """
    ctx = ctx or current_context()
    rest = [None] * (x.ndim - 1)
    f = ctx.shard_map(
        functools.partial(
            all_gather, axis=axis, method=method, ctx=ctx,
            pull_window=pull_window,
        ),
        in_specs=P(axis, *rest),
        out_specs=P(None, *rest),
    )
    return f(x)
