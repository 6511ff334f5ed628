"""AllReduce: method enum + size-based auto dispatch, Pallas + XLA paths.

Parity: reference ``kernels/nvidia/allreduce.py`` (1,208 LoC: double-tree
:215, one-shot :333-443, two-shot :447-717) and the method registry
``kernels/allreduce.py:28-61`` with ``get_auto_allreduce_method``
(:1101) picking by message size.

TPU translation: the reference's multimem/NVLS switch reductions have no
ICI analog (SURVEY.md §7 hard parts) — the latency-optimal small-message
method here is ONE_SHOT (single-hop full-mesh exchange + local reduce)
and the bandwidth method is TWO_SHOT (ring reduce-scatter + ring
all-gather), which is also how XLA lowers large psums over ICI.
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
from triton_distributed_tpu.ops.collectives.all_gather import (
    AllGatherMethod,
    all_gather,
)
from triton_distributed_tpu.ops.collectives.reduce_scatter import (
    ReduceScatterMethod,
    reduce_scatter,
)
from triton_distributed_tpu.runtime.mesh import DistContext, current_context


class AllReduceMethod(enum.Enum):
    """Parity: ``kernels/allreduce.py:28-41``."""

    AUTO = "auto"
    XLA = "xla"  # jax.lax.psum — XLA's own ICI collective
    ONE_SHOT = "one_shot"  # full-mesh exchange + local reduce (small msgs)
    TWO_SHOT = "two_shot"  # ring RS + ring AG (large msgs)
    DOUBLING = "doubling"  # recursive doubling — log-depth (mid msgs)


_ONESHOT_COLLECTIVE_ID = next_collective_id()
_DOUBLING_COLLECTIVE_ID = next_collective_id()

# Below this payload size the single-hop exchange beats the ring's
# 2(n-1) hops (parity: get_auto_allreduce_method, allreduce.py:1101).
_ONE_SHOT_MAX_BYTES = 256 * 1024

# Band where log-depth beats both: above the one-shot sweet spot (n
# simultaneous incoming puts congest a small mesh) but below where the
# ring's 2·(n-1)/n bytes-per-rank bandwidth optimality dominates the
# log₂(n) hop saving.
_DOUBLING_MAX_BYTES = 1024 * 1024


def get_auto_allreduce_method(nbytes: int, n: int) -> AllReduceMethod:
    if nbytes <= _ONE_SHOT_MAX_BYTES:
        return AllReduceMethod.ONE_SHOT
    if nbytes <= _DOUBLING_MAX_BYTES and n & (n - 1) == 0:
        return AllReduceMethod.DOUBLING
    # TWO_SHOT composes ring RS + ring AG; above the VMEM ceiling the RS
    # leg switches to its HBM-slot variant, so no payload cap remains.
    return AllReduceMethod.TWO_SHOT


def _one_shot_kernel(
    x_ref, o_ref, gather, send_sems, recv_sems, *,
    axis: str, straggler_rank: int | None = None, straggler_nanos: int = 0,
):
    """Push local data to every peer's slot, then reduce locally.

    Parity: one-shot push ``allreduce.py:333`` (every rank broadcasts,
    every rank reduces all n copies); straggler fixture parity:
    ``_run_straggler`` (``allreduce.py:137``).
    """
    me = dl.rank(axis)
    n = dl.num_ranks(axis)

    dl.barrier_all(axis)  # peers' gather slots must exist before any put
    dl.straggle_if_rank(straggler_rank, axis, straggler_nanos)
    gather[me] = x_ref[:]
    dmas = []
    for i in range(1, n):
        peer = jax.lax.rem(me + i, n)
        dmas.append(
            dl.put_signal(
                gather.at[me], gather.at[me], peer,
                send_sems.at[i - 1], recv_sems, axis=axis,
            )
        )
    for _ in range(1, n):
        dl.wait_recv(recv_sems, gather.at[me])
    dl.quiet(*dmas)

    acc = gather[0].astype(jnp.float32)
    for i in range(1, n):
        acc = acc + gather[i].astype(jnp.float32)
    o_ref[:] = acc.astype(o_ref.dtype)


def _doubling_kernel(
    x_ref, o_ref, src, recv, send_sems, recv_sems, *,
    axis: str, straggler_rank: int | None = None, straggler_nanos: int = 0,
):
    """Recursive halving-doubling (butterfly) allreduce: log₂(n) rounds,
    round k exchanges the running sum with partner ``me XOR 2^k``.

    This is the TPU redesign of the reference's double-binary-tree method
    (``allreduce.py:145-215``): same log-depth latency class, but the
    butterfly keeps every rank's program identical (partner is computed
    from the rank id, no parent/child tables) — a better fit for SPMD
    Pallas where all ranks trace one kernel. Power-of-two axis sizes
    only; AUTO falls back to ring methods otherwise.
    """
    me = dl.rank(axis)
    n = dl.num_ranks(axis)
    lg = n.bit_length() - 1  # n is a power of two

    dl.barrier_all(axis)  # peers' recv slots must exist before any put
    dl.straggle_if_rank(straggler_rank, axis, straggler_nanos)

    acc = x_ref[:].astype(jnp.float32)
    dmas = []
    for k in range(lg):
        partner = jax.lax.bitwise_xor(me, 1 << k)
        src[k] = acc.astype(src.dtype)
        dmas.append(
            dl.put_signal(
                src.at[k], recv.at[k], partner,
                send_sems.at[k], recv_sems.at[k], axis=axis,
            )
        )
        dl.wait_recv(recv_sems.at[k], recv.at[k])
        acc = acc + recv[k].astype(jnp.float32)
    dl.quiet(*dmas)
    o_ref[:] = acc.astype(o_ref.dtype)


def _straggle_entry(x, axis, straggler_rank, straggler_nanos, ctx):
    """Identity op that lags one rank (race fixture for composed paths
    whose leg kernels carry no injection params). Static no-op when no
    straggler is configured — production traces are untouched."""
    if straggler_rank is None or not straggler_nanos:
        return x

    def kern(x_ref, o_ref, sem):
        dl.straggle_if_rank(straggler_rank, axis, straggler_nanos)
        # HBM->HBM DMA identity: no VMEM residency, so the fixture also
        # works on the >VMEM-ceiling band the HBM-staged RS leg serves.
        cp = pltpu.make_async_copy(x_ref, o_ref, sem)
        cp.start()
        cp.wait()

    return comm_pallas_call(
        "tdt_straggle_entry",
        kern,
        jax.ShapeDtypeStruct(x.shape, x.dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA(())],
        ctx=ctx,
    )(x)


def all_reduce(
    x: jax.Array,
    axis: str = "tp",
    method: AllReduceMethod = AllReduceMethod.AUTO,
    ctx: DistContext | None = None,
    *,
    straggler_rank: int | None = None,
    straggler_nanos: int = 500_000,
) -> jax.Array:
    """Sum ``x`` across ``axis``; every device gets the full result.

    Call inside ``shard_map``; ``x`` is this device's partial sum.
    ``straggler_rank`` lags one rank's pushes (stress fixture; parity:
    ``_run_straggler``).
    """
    n = jax.lax.axis_size(axis)
    nbytes = x.size * x.dtype.itemsize
    if method == AllReduceMethod.AUTO:
        method = (
            get_auto_allreduce_method(nbytes, n)
            if device_initiable(axis, ctx) and x.ndim >= 2
            else AllReduceMethod.XLA
        )

    if method == AllReduceMethod.XLA:
        return jax.lax.psum(x, axis)

    if method == AllReduceMethod.ONE_SHOT:
        if x.ndim < 2:
            raise ValueError("pallas all_reduce needs >=2D input")
        return comm_pallas_call(
            "tdt_all_reduce_one_shot",
            functools.partial(
                _one_shot_kernel, axis=axis,
                straggler_rank=straggler_rank,
                straggler_nanos=straggler_nanos,
            ),
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=[
                pltpu.VMEM((n, *x.shape), x.dtype),
                pltpu.SemaphoreType.DMA((max(n - 1, 1),)),
                pltpu.SemaphoreType.DMA(()),
            ],
            collective_id=_ONESHOT_COLLECTIVE_ID,
            ctx=ctx,
        )(x)

    if method == AllReduceMethod.DOUBLING:
        if x.ndim < 2:
            raise ValueError("pallas all_reduce needs >=2D input")
        if n & (n - 1):
            raise ValueError(f"DOUBLING needs power-of-two axis, got {n}")
        lg = max(n.bit_length() - 1, 1)
        return comm_pallas_call(
            "tdt_all_reduce_doubling",
            functools.partial(
                _doubling_kernel, axis=axis,
                straggler_rank=straggler_rank,
                straggler_nanos=straggler_nanos,
            ),
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=[
                pltpu.VMEM((lg, *x.shape), x.dtype),  # per-round send
                pltpu.VMEM((lg, *x.shape), x.dtype),  # per-round recv
                pltpu.SemaphoreType.DMA((lg,)),
                pltpu.SemaphoreType.DMA((lg,)),
            ],
            collective_id=_DOUBLING_COLLECTIVE_ID,
            ctx=ctx,
        )(x)

    if method == AllReduceMethod.TWO_SHOT:
        # Ring reduce-scatter then ring all-gather; rows must split n-ways.
        if x.shape[0] % n:
            # ONE_SHOT gathers n copies into VMEM — only sane when small;
            # large indivisible payloads go to XLA.
            if nbytes <= _ONE_SHOT_MAX_BYTES:
                return all_reduce(
                    x, axis, AllReduceMethod.ONE_SHOT, ctx,
                    straggler_rank=straggler_rank,
                    straggler_nanos=straggler_nanos,
                )
            return jax.lax.psum(x, axis)
        # Straggler fixture on a COMPOSED path: the legs' kernels carry
        # no injection params, so the lag is applied as a delay-only
        # kernel that skews this rank's ENTRY into the RS leg — the
        # same late-producer class the monolithic kernels provoke
        # in-kernel.
        x = _straggle_entry(x, axis, straggler_rank, straggler_nanos, ctx)
        rs_method = (
            # Both ICI directions on the RS leg too (demotes itself on
            # degenerate shapes) — the AG leg is already bidirectional.
            ReduceScatterMethod.PALLAS_BIDIR_RING
            if nbytes <= VMEM_COMM_MAX_BYTES
            else ReduceScatterMethod.PALLAS_RING_HBM  # no VMEM ceiling
        )
        reduced = reduce_scatter(x, axis, rs_method, ctx)
        return all_gather(reduced, axis, AllGatherMethod.PALLAS_BIDIR_RING, ctx)

    raise ValueError(f"unknown method {method}")


def all_reduce_op(
    x: jax.Array,
    axis: str = "tp",
    method: AllReduceMethod = AllReduceMethod.AUTO,
    ctx: DistContext | None = None,
) -> jax.Array:
    """Host-level wrapper: ``x[i]`` is device i's partial array (host
    shape ``[n, ...]``); returns the summed array (replicated)."""
    ctx = ctx or current_context()
    rest = [None] * (x.ndim - 1)

    def body(xi):
        return all_reduce(xi[0], axis=axis, method=method, ctx=ctx)

    f = ctx.shard_map(body, in_specs=P(axis, *rest), out_specs=P(*rest))
    return f(x)
