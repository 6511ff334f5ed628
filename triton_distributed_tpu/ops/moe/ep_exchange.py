"""Device-initiated EP all-to-all transport (Pallas, per-destination puts).

Parity: reference ``kernels/nvidia/low_latency_all_to_all.py`` —
``all_to_all_kernel``:36-125 pushes each destination's token rows with
``putmem_signal`` and the receiver spins on per-source signals — and the
device dispatch/combine pair ``kernels/nvidia/ep_a2a.py:37,152``. This
module is the TPU translation: ONE Pallas kernel per direction whose
DMAs push only the FILLED prefix of each per-destination segment, block
by block, with the DMA arrival semaphore as the signal.

Design notes (vs the XLA ``all_to_all`` transport in ``ep_a2a.py``):

- **Wire bytes scale with the real splits**, not the worst-case padding:
  peer ``p`` receives ``ceil(splits[p]/block)*block`` rows instead of the
  full ``capacity``-row segment. At the reference's headline config
  (128 tok/rank, topk=8, 8 ranks, lossless capacity = t*k = 1024) the
  uniform-routing fill is ~128 rows/segment — ~8x fewer wire bytes.
- **Splits stay on the XLA control plane.** The reference exchanges
  splits with a device kernel (``kernel_get_ag_splits_and_recv_offset``,
  ``ep_a2a.py:244``) because a CUDA launch is the only way to touch the
  NIC; under ``jit`` the [n]-int splits exchange compiles into the SAME
  program as the payload kernel and rides ICI as an async collective, so
  device-initiating it would only re-implement XLA's scalar path. The
  payload — where the bytes are — is what the kernel owns: the counts
  are scalar-prefetched into SMEM and every bulk byte moves by
  device-issued ``put_signal``.
- **Payload rows are packed** (fp8/bf16 payload + f32 scale + int32
  expert id in one uint8 row, lane-padded) so ONE exchange moves
  everything — the reference's flag-in-data LL codec shape, with the
  byte-counting DMA semaphore standing in for the flag word.

The receiver's segment rows past ``recv_counts[src]`` are NOT written
(that's the point); callers must mask by count, as ``ep_moe_ffn`` does.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_distributed_tpu import language as dl
from triton_distributed_tpu.ops.common import (
    comm_cost,
    comm_pallas_call,
    next_collective_id,
)
from triton_distributed_tpu.runtime.mesh import DistContext

_EP_EXCHANGE_COLLECTIVE_ID = next_collective_id()

# Rows per DMA block. 32 sublanes is the int8 native tile height, and a
# multiple of every coarser dtype's tile height, so block DMAs stay
# aligned for any packed row width.
EP_BLOCK_ROWS = 32


def _for_each_run(count_blocks, nbits: int, fn):
    """Invoke ``fn(off_blocks, size_blocks)`` once per power-of-two run
    of ``count_blocks``'s binary decomposition (``off`` traced, ``size``
    static). Exactly ``popcount(count_blocks)`` <= ``nbits`` DMA-sized
    runs cover the filled prefix — the descriptor-count lever that
    replaced the old block-by-block loops (VERDICT r3 task 5: the n=1
    floor was ~5 ms because the kernel issued O(capacity/block)
    predicated DMAs; runs make it O(log))."""
    off = jnp.int32(0)
    for b in reversed(range(nbits)):
        sz = 1 << b
        bit = (count_blocks >> b) & 1

        @pl.when(bit == 1)
        def _(off=off, sz=sz):
            fn(off, sz)

        off = off + bit * sz


def _ep_exchange_kernel(
    splits_ref,   # [n] SMEM int32 — rows this rank sends to each dest
    expect_ref,   # [n] SMEM int32 — rows each source sends this rank
    x_ref,        # [n, NB, block, R] ANY uint8 — send segments, blocked
    o_ref,        # [n, NB, block, R] ANY uint8 — recv segments, blocked
    send_sems,    # DMA (n-1,)
    recv_sem,     # DMA ()
    local_sem,    # DMA ()
    *,
    axis: str,
    block: int,
    straggler_rank: int | None = None,
    straggle_nanos: int = 0,
):
    me = dl.rank(axis)
    n = dl.num_ranks(axis)
    nb_cap = x_ref.shape[1]
    # Blocks are a LEADING (untiled) dim so power-of-two runs can slice
    # at traced offsets with no sublane-alignment proof (the tiled dims
    # are the static [block, R] tail).
    nbits = max(nb_cap.bit_length(), 1)

    def seg_run(ref, seg, off, sz):
        return ref.at[seg, pl.ds(off, sz)]

    # Peers' o_ref must exist before any put (same contract as the dense
    # a2a); also fences reuse of THIS call's buffers across calls.
    dl.barrier_all(axis)
    dl.straggle_if_rank(straggler_rank, axis, straggle_nanos)

    # Own segment never crosses the wire: local DMA of the filled
    # prefix, one descriptor per binary run.
    own_nb = pl.cdiv(splits_ref[me], block)
    _for_each_run(own_nb, nbits, lambda off, sz: pltpu.make_async_copy(
        seg_run(x_ref, me, off, sz), seg_run(o_ref, me, off, sz), local_sem
    ).start())

    # Push the filled prefix of every peer segment, run by run. Data
    # from rank ``me`` lands in the peer's segment ``me`` (the dense-a2a
    # slot convention), so receivers never contend for a slot.
    for i in range(1, n):
        peer = jax.lax.rem(me + i, n)
        nb = pl.cdiv(splits_ref[peer], block)
        _for_each_run(nb, nbits, lambda off, sz, peer=peer, i=i:
                      dl.put_signal(
                          seg_run(x_ref, peer, off, sz),
                          seg_run(o_ref, me, off, sz),
                          peer,
                          send_sems.at[i - 1],
                          recv_sem,
                          axis=axis,
                      ))

    # DMA semaphores only accept descriptor-expressed waits (Pallas
    # rejects a raw semaphore_wait on a dma_sem), so waits mirror the
    # senders' run structure: one descriptor per binary run. A count
    # can exceed one segment's capacity (arrivals sum over sources), so
    # full-segment descriptors cover the quotient — <= n-1 of them —
    # and binary runs the remainder: O(n + log) waits total, vs the old
    # O(n * capacity/block) wait loop.
    def wait_runs(count_blocks, sem):
        full = count_blocks // nb_cap

        def one_full(_, carry):
            dl.wait_recv(sem, o_ref.at[0])
            return carry

        jax.lax.fori_loop(0, full, one_full, None)
        _for_each_run(count_blocks - full * nb_cap, nbits, lambda off, sz:
                      dl.wait_recv(sem, seg_run(o_ref, 0, 0, sz)))

    # Arrivals: the shared recv semaphore counts bytes, so WHICH sized
    # descriptors express the wait doesn't matter — only their total.
    total_in = jnp.int32(0)
    for i in range(1, n):
        src = jax.lax.rem(me + i, n)
        total_in = total_in + pl.cdiv(expect_ref[src], block)

    wait_runs(total_in, recv_sem)

    # Drain own-segment local copies.
    wait_runs(own_nb, local_sem)

    # Quiet: drain sends so x_ref is reusable after the call returns.
    # Send semaphores also count bytes — runs per peer cover every
    # byte pushed to it.
    for i in range(1, n):
        peer = jax.lax.rem(me + i, n)
        nb = pl.cdiv(splits_ref[peer], block)
        _for_each_run(nb, nbits, lambda off, sz, peer=peer, i=i:
                      dl.remote_copy(
                          seg_run(x_ref, peer, off, sz),
                          seg_run(o_ref, me, off, sz),
                          peer,
                          send_sems.at[i - 1],
                          recv_sem,
                          axis=axis,
                      ).wait_send())


def ep_exchange(
    rows: jax.Array,         # [n, C, R] uint8 — per-destination segments
    splits: jax.Array,       # [n] int32 — rows really sent per dest (<= C)
    recv_counts: jax.Array,  # [n] int32 — rows each source sends here
    axis: str = "ep",
    ctx: DistContext | None = None,
    block: int = EP_BLOCK_ROWS,
    straggler_rank: int | None = None,
    straggle_nanos: int = 0,
) -> jax.Array:
    """Block-granular device-push all-to-all of packed uint8 rows.

    Call inside ``shard_map``. Segment ``p`` of ``rows`` goes to device
    ``p``'s segment ``me``; only ``ceil(splits[p]/block)`` blocks cross
    the wire. Returns ``[n, C, R]`` whose segment ``s`` holds
    ``recv_counts[s]`` valid rows — rows past the count (and past the
    last sent block) are unwritten garbage the caller must mask.
    """
    n, c, r = rows.shape
    if rows.dtype != jnp.uint8:
        raise ValueError(f"ep_exchange moves packed uint8 rows, got {rows.dtype}")
    if r % 128:
        raise ValueError(f"packed row width {r} must be lane-aligned (128)")
    pad_c = (-c) % block
    if pad_c:
        rows = jnp.pad(rows, ((0, 0), (0, pad_c), (0, 0)))
    cp = c + pad_c
    # Blocked layout [n, NB, block, R]: the block index becomes a
    # LEADING (untiled) dim, so the kernel's power-of-two runs can DMA
    # from traced block offsets (dynamic sublane slices of [C, R] would
    # need an alignment proof Mosaic can't make on a run sum).
    rows = rows.reshape(n, cp // block, block, r)

    out = comm_pallas_call(
        "tdt_ep_exchange",
        functools.partial(
            _ep_exchange_kernel,
            axis=axis,
            block=block,
            straggler_rank=straggler_rank,
            straggle_nanos=straggle_nanos,
        ),
        jax.ShapeDtypeStruct((n, cp // block, block, r), jnp.uint8),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.SemaphoreType.DMA((max(n - 1, 1),)),
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA(()),
        ],
        collective_id=_EP_EXCHANGE_COLLECTIVE_ID,
        ctx=ctx,
        cost_estimate=comm_cost(bytes_accessed=2 * n * cp * r),
    )(splits.astype(jnp.int32), recv_counts.astype(jnp.int32), rows)
    out = out.reshape(n, cp, r)
    return out[:, :c] if pad_c else out


# -- row packing (the LL codec: payload + scale + metadata in one row) ------

def _to_u8(x: jax.Array) -> jax.Array:
    """Bitcast any-dtype [..., d] to uint8 [..., d*itemsize]."""
    if x.dtype == jnp.uint8:
        return x
    u8 = jax.lax.bitcast_convert_type(x, jnp.uint8)
    return u8.reshape(*x.shape[:-1], x.shape[-1] * x.dtype.itemsize)


def _from_u8(u8: jax.Array, dtype, d: int) -> jax.Array:
    """Inverse of :func:`_to_u8` for the leading ``d*itemsize`` bytes."""
    it = jnp.dtype(dtype).itemsize
    if it == 1:
        return jax.lax.bitcast_convert_type(u8[..., :d], dtype)
    return jax.lax.bitcast_convert_type(
        u8[..., : d * it].reshape(*u8.shape[:-1], d, it), dtype
    )


def pack_rows(parts: list[jax.Array]) -> tuple[jax.Array, list[int]]:
    """Pack per-row arrays (same leading shape) into lane-padded uint8
    rows. Returns ``(rows_u8, byte_offsets)`` — offsets index the start
    of each part for :func:`unpack_rows`."""
    chunks = [_to_u8(p) for p in parts]
    offsets, off = [], 0
    for ch in chunks:
        offsets.append(off)
        off += ch.shape[-1]
    pad = (-off) % 128
    if pad:
        chunks.append(jnp.zeros((*chunks[0].shape[:-1], pad), jnp.uint8))
    return jnp.concatenate(chunks, axis=-1), offsets


def unpack_row(rows_u8: jax.Array, offset: int, dtype, d: int) -> jax.Array:
    """Slice one packed part back out (see :func:`pack_rows`)."""
    it = jnp.dtype(dtype).itemsize
    return _from_u8(rows_u8[..., offset : offset + d * it], dtype, d)
