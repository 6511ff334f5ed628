"""Point-to-point transport for pipeline parallelism.

Parity: reference ``kernels/nvidia/p2p.py`` (85 LoC) +
``layers/nvidia/p2p.py:43`` ``CommOp`` — N symmetric buffers with signal
set/wait/read used by ``test/nvidia/test_pp.py`` send (:77) / recv (:96)
to move activations between pipeline stages.

TPU design: a pipeline hop is a neighbor shift along the ``pp`` mesh
axis. Two methods:

- ``xla``: ``jax.lax.ppermute`` — XLA schedules the collective-permute
  asynchronously (the copy-engine-stream analog) and overlaps it with
  unrelated compute automatically.
- ``pallas``: one kernel where every stage ``put_signal``s its payload to
  the next stage's landing buffer and waits its own arrival — the
  device-initiated ``putmem_signal`` path, fusable into larger kernels.

The reference's ``CommOp`` double-buffers N slots to pipeline multiple
in-flight micro-batches; in JAX that buffering falls out of SPMD
dataflow (each microbatch's shift is its own value), so no buffer pool
object is needed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_distributed_tpu import language as dl
from triton_distributed_tpu.ops.common import (
    comm_pallas_call,
    next_collective_id,
    device_initiable,
)

_P2P_COLLECTIVE_ID = next_collective_id()


def _shift_kernel(x_ref, o_ref, send_sem, recv_sem, *, axis: str, wrap: bool):
    """Every stage pushes to ``me+1`` (ring if ``wrap``); stage 0's
    landing buffer is zeroed when not wrapping (nothing arrives)."""
    me = dl.rank(axis)
    n = dl.num_ranks(axis)
    nxt = jax.lax.rem(me + 1, n)

    dl.barrier_all(axis)
    send = jnp.logical_or(wrap, me < n - 1)
    recv = jnp.logical_or(wrap, me > 0)

    @pl.when(send)
    def _send():
        dl.put_signal(x_ref, o_ref, nxt, send_sem, recv_sem, axis=axis)

    @pl.when(jnp.logical_not(recv))
    def _zero():
        o_ref[:] = jnp.zeros_like(o_ref)

    @pl.when(recv)
    def _recv():
        dl.wait_recv(recv_sem, o_ref)

    @pl.when(send)
    def _drain():
        pltpu.make_async_copy(x_ref, x_ref, send_sem).wait()


def pp_shift(
    x: jax.Array,
    axis: str = "pp",
    *,
    wrap: bool = False,
    method: str = "auto",
    ctx=None,
) -> jax.Array:
    """Shift ``x`` one stage forward along ``axis`` (inside ``shard_map``):
    stage i's output becomes stage i+1's input; stage 0 receives zeros
    (or stage n-1's payload when ``wrap``)."""
    n = jax.lax.axis_size(axis)
    if method == "auto":
        method = "pallas" if device_initiable(axis, ctx) and x.ndim >= 2 else "xla"
    if n == 1:
        return x if wrap else jnp.zeros_like(x)
    if method == "xla":
        if wrap:
            perm = [(i, (i + 1) % n) for i in range(n)]
        else:
            perm = [(i, i + 1) for i in range(n - 1)]
        return jax.lax.ppermute(x, axis, perm)
    return comm_pallas_call(
        "tdt_p2p_shift",
        functools.partial(_shift_kernel, axis=axis, wrap=wrap),
        jax.ShapeDtypeStruct(x.shape, x.dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA(()),
        ],
        collective_id=_P2P_COLLECTIVE_ID,
        ctx=ctx,
    )(x)


def pp_send_recv(
    x: jax.Array,
    src: int,
    dst: int,
    axis: str = "pp",
) -> jax.Array:
    """Single directed hop: ``src``'s payload lands on ``dst``; everyone
    else receives zeros (parity: ``CommOp.send``/``recv`` pairs in
    ``test_pp.py:77-96``)."""
    out = jax.lax.ppermute(x, axis, [(src, dst)])
    return out


def pp_recv_from_prev(x: jax.Array, axis: str = "pp", **kw) -> jax.Array:
    """Alias with the receiving-stage viewpoint (reference ``CommOp.read``)."""
    return pp_shift(x, axis, **kw)
