"""Low-latency (barrier-free) collectives for small messages.

Parity: reference ``kernels/nvidia/low_latency_allgather.py`` — the
pull/push LL protocols (:48-448) and the flag-in-data codecs (:549) that
let a rank push without a preceding barrier, plus the double-buffer
phase discipline of ``low_latency_all_to_all.py``.

TPU translation of the codec: the reference packs a monotonically
increasing flag next to the payload so a receiver can spin until the
CURRENT call's data (not a stale buffer) has arrived. On TPU the DMA
engine's arrival semaphore IS the flag — data visibility before signal
is the hardware contract — so what remains of the protocol is the
buffer-reuse discipline:

- symmetric slots are double-buffered on the call counter (``phase``),
  carried by the caller like the reference's ``buffer_id``;
- a producer may overwrite slot ``p`` only after every consumer of its
  previous use has ACKed (a 1-increment remote semaphore signal — the
  reference's flag-value comparison folded into semaphore counting).

No entry barrier, no trailing barrier: steady-state latency is one ICI
hop (put) + one hop (ack, off the critical path) — the same structure
that makes the reference's LL allgather win at small sizes.

Usage (the workspace threads through calls like the reference's
symmetric buffer):

    ws = ll_all_gather_workspace(ctx, m_per, lanes, dtype)
    phase = jnp.int32(0)
    for step in ...:
        out, ws = ll_all_gather(x, ws, phase, axis="tp", ctx=ctx)
        phase = phase + 1
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from triton_distributed_tpu import language as dl
from triton_distributed_tpu.ops.common import (
    comm_pallas_call,
    next_collective_id,
)
from triton_distributed_tpu.runtime.mesh import DistContext, current_context

_LL_AG_COLLECTIVE_ID = next_collective_id()


def ll_all_gather_workspace(
    n: int, m_per: int, lanes: int, dtype=jnp.float32
) -> jax.Array:
    """Per-device symmetric slots: ``[2 phases, n sources, m_per, lanes]``."""
    return jnp.zeros((2, n, m_per, lanes), dtype)


def _ll_ag_kernel(
    x_ref,       # [m_per, L] ANY — this device's shard
    ws_in,       # [2, n, m_per, L] ANY — symmetric slots (aliased to ws_out)
    phase_ref,   # [1] SMEM int32 — call counter
    o_ref,       # [n*m_per, L] ANY
    ws_out,      # aliased ws_in
    copy_sems,   # DMA (2,) — assemble copies (own + peers)
    send_sems,   # DMA (n-1,)
    recv_sems,   # DMA (2,) — arrivals per phase slot
    ack_sems,    # REGULAR (2,) — consumer acks per phase slot
    *,
    axis: str,
    barrier_free: bool,
):
    me = dl.rank(axis)
    n = dl.num_ranks(axis)
    m_per = x_ref.shape[0]
    phase = phase_ref[0]
    p = jax.lax.rem(phase, 2)

    if barrier_free:
        # Reuse discipline: slot p's previous use (call phase-2) must
        # have been consumed by every peer before we overwrite their
        # copy. Ack counts accumulate across launches — valid on real
        # TPU where sync-flag semaphores are persistent hardware
        # counters (Mosaic's drained-at-exit convention exists exactly
        # because leftovers would leak into the next kernel).
        @pl.when(phase >= 2)
        def _wait_acks():
            dl.wait(ack_sems.at[p], n - 1)

    else:
        # Interpret-mode shim: the simulator zeroes semaphores at kernel
        # exit, so cross-launch ack counting cannot work; an entry
        # barrier provides the same reuse guarantee (at +1 hop latency,
        # the cost the barrier-free path exists to shed).
        dl.barrier_all(axis)

    # Push: data lands in the peer's PERSISTENT slot, so no allocation
    # race exists; the arrival semaphore is the codec flag.
    dmas = []
    for i in range(1, n):
        peer = jax.lax.rem(me + i, n)
        dmas.append(
            dl.put_signal(
                x_ref, ws_in.at[p, me], peer,
                send_sems.at[i - 1], recv_sems.at[p], axis=axis,
            )
        )

    # Own shard → output straight away (overlaps the waits).
    own = pltpu.make_async_copy(
        x_ref, o_ref.at[pl.ds(me * m_per, m_per)], copy_sems.at[0]
    )
    own.start()

    # Wait all n-1 arrivals for THIS phase slot, then assemble.
    for _ in range(1, n):
        dl.wait_recv(recv_sems.at[p], ws_in.at[p, 0])
    for i in range(1, n):
        src = jax.lax.rem(me + i, n)
        cp = pltpu.make_async_copy(
            ws_in.at[p, src], o_ref.at[pl.ds(src * m_per, m_per)],
            copy_sems.at[1],
        )
        cp.start()
        cp.wait()
    own.wait()

    if barrier_free:
        # ACK every producer: their slot-p copy here is consumed.
        for i in range(1, n):
            src = jax.lax.rem(me + i, n)
            dl.signal(ack_sems.at[p], 1, dst=src, axis=axis)
    dl.quiet(*dmas)


def ll_all_gather(
    x: jax.Array,
    ws: jax.Array,
    phase: jax.Array | int,
    axis: str = "tp",
    ctx: DistContext | None = None,
    barrier_free: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Barrier-free small-message all-gather inside ``shard_map``.

    ``x``: ``[m_per, L]``; ``ws``: persistent workspace from
    :func:`ll_all_gather_workspace` (returned updated — thread it);
    ``phase``: monotonically increasing call counter the caller carries.
    ``barrier_free`` defaults to on-TPU detection — the ack discipline
    needs hardware-persistent semaphores, which the interpret simulator
    does not model (see kernel docstring). Returns ``([n*m_per, L], ws)``.
    """
    from triton_distributed_tpu.ops.common import _on_tpu

    n = jax.lax.axis_size(axis)
    m_per, lanes = x.shape
    out_shape = jax.ShapeDtypeStruct((n * m_per, lanes), x.dtype)
    phase = jnp.asarray(phase, jnp.int32).reshape(1)
    if barrier_free is None:
        barrier_free = _on_tpu(ctx)

    out, ws_new = comm_pallas_call(
        "tdt_ll_all_gather",
        functools.partial(_ll_ag_kernel, axis=axis, barrier_free=barrier_free),
        (out_shape, jax.ShapeDtypeStruct(ws.shape, ws.dtype)),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=(
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ),
        scratch_shapes=[
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((max(n - 1, 1),)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.REGULAR((2,)),
        ],
        collective_id=_LL_AG_COLLECTIVE_ID,
        ctx=ctx,
        input_output_aliases={1: 1},
    )(x, ws, phase)
    return out, ws_new


def ll_all_gather_op(
    x: jax.Array,
    steps: int = 1,
    axis: str = "tp",
    ctx: DistContext | None = None,
) -> jax.Array:
    """Host-level wrapper for tests/benchmarks: runs ``steps``
    back-to-back LL all-gathers (exercising the phase/ack discipline)
    and returns the final gathered array."""
    ctx = ctx or current_context()
    n = ctx.axis_size(axis)

    def body(xi):
        ws = ll_all_gather_workspace(n, xi.shape[0], xi.shape[1], xi.dtype)
        out = None
        for s in range(steps):
            out, ws = ll_all_gather(xi, ws, jnp.int32(s), axis=axis, ctx=ctx)
        return out

    f = ctx.shard_map(body, in_specs=P(axis, None), out_specs=P(None, None))
    return f(x)
