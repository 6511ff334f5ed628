"""AllToAll: XLA path + single-hop Pallas push over ICI.

Parity: reference ``kernels/nvidia/low_latency_all_to_all.py`` —
``all_to_all_kernel``:36 (putmem_signal per destination, double-buffered
by call count) and ``AllToAllContext``:125. The EP-specific variant with
token splits + fp8 scales lives in ``ops/moe/ep_a2a.py``; this is the
dense equal-split primitive.

Protocol: chunk i of the local array goes to device i's slot ``me``;
every pair exchanges directly (one ICI hop on a full axis, routed on a
torus). Arrivals share one recv semaphore since chunks are equal-sized.
"""

from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from triton_distributed_tpu import language as dl
from triton_distributed_tpu.ops.common import (
    device_initiable,
    VMEM_COMM_MAX_BYTES,
    comm_pallas_call,
    next_collective_id,
)
from triton_distributed_tpu.runtime.mesh import DistContext, current_context

_A2A_COLLECTIVE_ID = next_collective_id()


def _a2a_kernel(x_ref, o_ref, send_sems, recv_sems, *, axis: str):
    me = dl.rank(axis)
    n = dl.num_ranks(axis)
    m_per = x_ref.shape[0] // n

    def chunk(idx):
        return pl.ds(idx * m_per, m_per)

    dl.barrier_all(axis)  # peers' o_ref must exist before any put
    # Own chunk stays local.
    o_ref[chunk(me)] = x_ref[chunk(me)]

    dmas = []
    for i in range(1, n):
        peer = jax.lax.rem(me + i, n)
        dmas.append(
            dl.put_signal(
                x_ref.at[chunk(peer)],
                o_ref.at[chunk(me)],
                peer,
                send_sems.at[i - 1],
                recv_sems,
                axis=axis,
            )
        )
    for _ in range(1, n):
        dl.wait_recv(recv_sems, o_ref.at[chunk(me)])
    dl.quiet(*dmas)


def all_to_all(
    x: jax.Array,
    axis: str = "tp",
    method: str = "auto",
    ctx: DistContext | None = None,
) -> jax.Array:
    """Exchange equal chunks: row-chunk i of ``x`` lands at device i's
    row-chunk ``me``. Call inside ``shard_map``; ``x`` is
    ``[n*m_per, ...]``, result the same shape.
    """
    n = jax.lax.axis_size(axis)
    if method == "auto":
        on_chip = x.size * x.dtype.itemsize <= VMEM_COMM_MAX_BYTES
        method = "pallas" if device_initiable(axis, ctx) and on_chip else "xla"
    if method == "xla":
        return jax.lax.all_to_all(
            x.reshape(n, x.shape[0] // n, *x.shape[1:]),
            axis, split_axis=0, concat_axis=0, tiled=False,
        ).reshape(x.shape)
    if x.ndim < 2:
        raise ValueError("pallas all_to_all needs >=2D input")
    if x.shape[0] % n:
        raise ValueError(f"rows {x.shape[0]} not divisible by axis size {n}")
    return comm_pallas_call(
        "tdt_all_to_all",
        functools.partial(_a2a_kernel, axis=axis),
        jax.ShapeDtypeStruct(x.shape, x.dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.SemaphoreType.DMA((max(n - 1, 1),)),
            pltpu.SemaphoreType.DMA(()),
        ],
        collective_id=_A2A_COLLECTIVE_ID,
        ctx=ctx,
    )(x)


def all_to_all_op(
    x: jax.Array,
    axis: str = "tp",
    method: str = "auto",
    ctx: DistContext | None = None,
) -> jax.Array:
    """Host-level wrapper: ``x`` host shape ``[n, n*m_per, ...]`` (row i =
    device i's sends); result ``[n, n*m_per, ...]`` (row i = device i's
    receives)."""
    ctx = ctx or current_context()
    rest = [None] * (x.ndim - 2)

    def body(xi):
        return all_to_all(xi[0], axis=axis, method=method, ctx=ctx)[None]

    f = ctx.shard_map(
        body,
        in_specs=P(axis, None, *rest),
        out_specs=P(axis, None, *rest),
    )
    return f(x)
