"""Broadcast: root's buffer to every rank on the axis.

Parity: reference device-API broadcast family
(``libnvshmem_device.py:806-948`` ``broadcast*``/``broadcastmem``,
host-side ``nvshmem.core.broadcast``). On TPU the latency method is a
one-shot root push (root DMAs its buffer into every peer's output slot
over ICI — single hop, all sends in flight); larger payloads ride XLA's
collective machinery (a masked psum lowers to an ICI broadcast tree).
"""

from __future__ import annotations

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
    comm_pallas_call,
    next_collective_id,
)
from triton_distributed_tpu.runtime.mesh import DistContext, current_context


class BroadcastMethod(enum.Enum):
    AUTO = "auto"
    XLA = "xla"
    ONE_SHOT = "one_shot"  # root pushes to every peer (small msgs)


_BCAST_COLLECTIVE_ID = next_collective_id()


def _one_shot_bcast_kernel(
    x_ref, o_ref, send_sems, recv_sem, *, axis: str, root: int
):
    me = dl.rank(axis)
    n = dl.num_ranks(axis)

    dl.barrier_all(axis)  # peers' o_ref must exist before any put

    @pl.when(me == root)
    def _send():
        o_ref[...] = x_ref[...]
        dmas = []
        for i in range(1, n):
            peer = jax.lax.rem(root + i, n)
            dmas.append(
                dl.put_signal(
                    x_ref, o_ref, peer,
                    send_sems.at[i - 1], recv_sem, axis=axis,
                )
            )
        dl.quiet(*dmas)

    @pl.when(me != root)
    def _recv():
        dl.wait_recv(recv_sem, o_ref)


def broadcast(
    x: jax.Array,
    axis: str = "tp",
    root: int = 0,
    method: BroadcastMethod = BroadcastMethod.AUTO,
    ctx: DistContext | None = None,
) -> jax.Array:
    """Every rank returns rank ``root``'s ``x``. Call inside shard_map."""
    n = jax.lax.axis_size(axis)
    if not 0 <= root < n:
        raise ValueError(f"root={root} out of range for axis size {n}")
    nbytes = x.size * x.dtype.itemsize
    if method == BroadcastMethod.AUTO:
        method = (
            BroadcastMethod.ONE_SHOT
            if device_initiable(axis, ctx) and x.ndim >= 2 and nbytes <= VMEM_COMM_MAX_BYTES
            else BroadcastMethod.XLA
        )

    if method == BroadcastMethod.XLA:
        me = jax.lax.axis_index(axis)
        masked = jnp.where(me == root, x, jnp.zeros_like(x))
        return jax.lax.psum(masked, axis)

    if x.ndim < 2:
        raise ValueError("pallas broadcast needs >=2D input")
    return comm_pallas_call(
        "tdt_broadcast",
        functools.partial(_one_shot_bcast_kernel, axis=axis, root=root),
        jax.ShapeDtypeStruct(x.shape, x.dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.SemaphoreType.DMA((max(n - 1, 1),)),
            pltpu.SemaphoreType.DMA(()),
        ],
        collective_id=_BCAST_COLLECTIVE_ID,
        ctx=ctx,
    )(x)


def broadcast_op(
    x: jax.Array,
    axis: str = "tp",
    root: int = 0,
    method: BroadcastMethod = BroadcastMethod.AUTO,
    ctx: DistContext | None = None,
) -> jax.Array:
    """Host-level wrapper: ``x`` sharded over ``axis`` (host shape
    ``[n, ...]``, row i = rank i's buffer); returns root's buffer
    replicated (host shape ``[...]``)."""
    ctx = ctx or current_context()
    rest = [None] * (x.ndim - 1)

    def body(xi):
        return broadcast(xi[0], axis=axis, root=root, method=method, ctx=ctx)

    f = ctx.shard_map(
        body, in_specs=P(axis, *rest), out_specs=P(*rest)
    )
    return f(x)
