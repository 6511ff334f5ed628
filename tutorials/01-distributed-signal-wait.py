"""Tutorial 01: device-side signal / wait / remote DMA.

Parity: reference ``tutorials/01-distributed-notify-wait.py`` — the
producer rank notifies a consumer's barrier and the consumer spin-waits
before loading. The TPU translation of notify/wait (SURVEY.md §2.4):

- ``notify(rank, sem)``      → ``dl.signal(sem, dst=rank, axis=...)`` or,
  fused with data, ``dl.put_signal`` (the DMA's recv semaphore IS the
  arrival signal — data visibility before signal is hardware-guaranteed).
- ``wait(sem, n)`` + token   → ``dl.wait(sem, n)`` / ``dl.wait_recv``
  (no consume_token: Mosaic orders subsequent loads after the wait).

Here every rank passes a value around a ring: put to the right neighbor,
wait on the left arrival, repeat n-1 times — after n-1 hops each rank
holds its left neighbor's ... neighbor's value, i.e. the value from
rank+1 (mod n).
"""

from _common import setup

jax = setup()

import functools

import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from triton_distributed_tpu import language as dl
from triton_distributed_tpu.ops.common import comm_pallas_call, next_collective_id
from triton_distributed_tpu.runtime.mesh import initialize_distributed

AXIS = "tp"


def ring_pass_kernel(x_ref, o_ref, buf, send_sem, recv_sem, *, hops: int):
    me = dl.rank(AXIS)
    n = dl.num_ranks(AXIS)
    right = jax.lax.rem(me + 1, n)

    dl.barrier_all(AXIS)  # peers' buffers must exist before any put
    o_ref[...] = x_ref[...]

    def hop(i, _):
        # put my current value into the right neighbor's landing buffer;
        # the neighbor's recv_sem fires when the bytes are visible.
        dma = dl.put_signal(o_ref, buf, right, send_sem, recv_sem, axis=AXIS)
        dl.wait_recv(recv_sem, buf)  # left neighbor's put has landed
        dma.wait_send()              # my source is reusable
        o_ref[...] = buf[...]
        # Round fence: without it, a fast left neighbor's NEXT put could
        # overwrite buf before this rank consumed it (the classic missing
        # credit/ack race the reference provokes with for_correctness
        # sleeps). Production kernels use double buffers instead — see
        # the slot-per-source scheme in ops/overlap/ag_gemm.py.
        dl.barrier_all(AXIS)
        return _

    jax.lax.fori_loop(0, hops, hop, None)


def main():
    ctx = initialize_distributed(tp=min(8, len(jax.devices())))
    n = ctx.axis_size(AXIS)
    x = jnp.arange(n * 8 * 128, dtype=jnp.float32).reshape(n * 8, 128)

    kernel = functools.partial(ring_pass_kernel, hops=n - 1)

    def shard_fn(xi):
        return comm_pallas_call(
            "tutorial_signal_wait",
            kernel,
            jax.ShapeDtypeStruct(xi.shape, xi.dtype),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=[
                pltpu.VMEM(xi.shape, xi.dtype),
                pltpu.SemaphoreType.DMA,
                pltpu.SemaphoreType.DMA,
            ],
            collective_id=next_collective_id(),
            ctx=ctx,
        )(xi)

    f = ctx.shard_map(shard_fn, in_specs=P(AXIS, None), out_specs=P(AXIS, None))
    out = np.asarray(f(x))
    # After n-1 hops, rank r holds rank (r+1) mod n's shard.
    gold = np.asarray(x).reshape(n, 8, 128)[(np.arange(n) + 1) % n]
    np.testing.assert_allclose(out.reshape(n, 8, 128), gold)
    print(f"ring signal/wait over {n} devices: OK")


if __name__ == "__main__":
    main()
