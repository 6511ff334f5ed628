"""Shared helpers for comm kernels: pallas_call builder, collective ids.

Parity role: reference ``kernels/nvidia/common_ops.py`` (grid barriers,
stream signal ops) — on TPU the equivalents are mostly folded into Mosaic,
so what remains shared is boilerplate: interpret-mode selection, collective
id allocation, VMEM budgeting.
"""

from __future__ import annotations

import contextlib
import itertools
from typing import Any, Sequence

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_distributed_tpu.runtime.mesh import DistContext, current_context

# Distinct collective_id per kernel *site* so barrier semaphores of
# different collectives in one program never alias. Stable across traces
# of the same site because allocation happens at import/def time.
_collective_ids = itertools.count(1)


def next_collective_id() -> int:
    return next(_collective_ids)


# Crossover between VMEM-resident comm kernels (payload + peer slots all
# on-chip — lowest latency) and the HBM-chunked / DMA-only variants that
# have no payload ceiling (all_gather ANY-kernels, reduce_scatter
# PALLAS_RING_HBM, tiled overlap staging). AUTO dispatch switches
# variant here, never to XLA on size grounds.
VMEM_COMM_MAX_BYTES = 4 * 1024 * 1024


def pick_stage_tile(
    m: int, row_bytes: int, budget: int, floor: int = 128
) -> int:
    """Largest divisor tile of ``m`` (by halving) whose staging buffer
    ``tile * row_bytes`` fits ``budget``; never below ``floor`` unless
    divisibility demands it. Shared by the HBM-chunked kernels
    (ag_gemm / gemm_rs staging, reduce_scatter tiled adds)."""
    tile = m
    while tile > floor and tile * row_bytes > budget:
        tile //= 2
    while m % tile:
        tile //= 2
    return max(tile, 1)


# Hard ceiling for the overlap kernels' scoped VMEM (below v5e's 128 MB
# physical VMEM); configs whose estimated need exceeds it can't compile.
OVERLAP_VMEM_CAP = 110 * 1024 * 1024


def overlap_vmem_bytes(
    tile_m: int, k: int, tile_n: int, itemsize: int, out_tile_bufs: int = 3
) -> int:
    """Estimated scoped-VMEM need of a fused overlap GEMM config.

    Mosaic's own accounting runs ~1.5x the raw buffer bytes (pipelined
    operand copies, stack), hence the 3x-per-double-buffer coefficients
    plus a fixed margin. ``out_tile_bufs`` scales the (tile_m, tile_n)
    term — gemm_rs keeps three double-buffered output-sized tiles where
    ag_gemm keeps one.
    """
    return (
        (3 * tile_m * k + 3 * k * tile_n
         + 3 * out_tile_bufs * tile_m * tile_n) * itemsize
        + 16 * 1024 * 1024
    )


def overlap_vmem_limit(
    tile_m: int, k: int, tile_n: int, itemsize: int, out_tile_bufs: int = 3
) -> int:
    """Scoped-VMEM limit for the fused overlap GEMM kernels."""
    return min(
        OVERLAP_VMEM_CAP,
        max(
            64 * 1024 * 1024,
            overlap_vmem_bytes(tile_m, k, tile_n, itemsize, out_tile_bufs),
        ),
    )


def pick_tile(n: int, preferred: int = 512) -> int:
    """Largest power-of-two-ish tile dividing ``n`` (shared by the
    overlap-GEMM context builders; parity: the reference's per-shape tile
    heuristics in its ``create_*_context`` helpers)."""
    tile = min(preferred, n)
    while n % tile:
        tile //= 2
    return max(tile, 128 if n % 128 == 0 else 1)


# jax.export cannot serialize host callbacks, which is what interpret-mode
# Pallas lowers to off-TPU. Ops with a pure-XLA equivalent consult
# exporting_portable() and take it while an export is being traced.
_EXPORT_PORTABLE = False


@contextlib.contextmanager
def portable_export():
    """Trace-for-export mode: ops avoid interpret-mode Pallas."""
    global _EXPORT_PORTABLE
    prev = _EXPORT_PORTABLE
    _EXPORT_PORTABLE = True
    try:
        yield
    finally:
        _EXPORT_PORTABLE = prev


def exporting_portable() -> bool:
    return _EXPORT_PORTABLE


def interpret_mode(ctx: DistContext | None = None):
    """Interpret params when not on real TPU (CPU simulator mesh)."""
    if ctx is None:
        try:
            ctx = current_context()
        except RuntimeError:
            ctx = None
    if ctx is not None:
        return ctx.pallas_interpret()
    return False if jax.default_backend() == "tpu" else pltpu.InterpretParams()


def comm_pallas_call(
    name: str,
    kernel,
    out_shape: Any,
    *,
    in_specs: Sequence[pl.BlockSpec] | None = None,
    out_specs: Any = None,
    scratch_shapes: Sequence[Any] = (),
    grid: tuple[int, ...] | None = None,
    collective_id: int | None = None,
    ctx: DistContext | None = None,
    vmem_limit_bytes: int | None = None,
    cost_estimate: pl.CostEstimate | None = None,
    dimension_semantics: Sequence[str] | None = None,
    input_output_aliases: dict[int, int] | None = None,
):
    """Build a pallas_call configured for communication kernels.

    ``name`` is the kernel's name in a profiler trace (``tdt_<op>``):
    required, so that no comm kernel reads ``closed_call.N`` there.
    Applies: side-effect marking (DMA-only kernels must not be DCE'd),
    collective id (barrier semaphore scoping), and interpret-mode
    selection for the CPU simulator.
    """
    params: dict[str, Any] = dict(has_side_effects=True)
    if collective_id is not None:
        params["collective_id"] = collective_id
        # Our comm kernels sequence via DMA semaphores; not every one
        # touches the barrier semaphore the id also scopes.
        params["allow_collective_id_without_custom_barrier"] = True
    if vmem_limit_bytes is not None:
        params["vmem_limit_bytes"] = vmem_limit_bytes
    if dimension_semantics is not None:
        params["dimension_semantics"] = tuple(dimension_semantics)
    kwargs: dict[str, Any] = {}
    if grid is not None:
        kwargs["grid"] = grid
    if cost_estimate is not None:
        kwargs["cost_estimate"] = cost_estimate
    if input_output_aliases is not None:
        kwargs["input_output_aliases"] = input_output_aliases
    return pl.pallas_call(
        kernel,
        out_shape=out_shape,
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=list(scratch_shapes),
        compiler_params=pltpu.CompilerParams(**params),
        interpret=interpret_mode(ctx),
        name=name,
        **kwargs,
    )


def comm_cost(
    flops: int = 0, bytes_accessed: int = 0, transcendentals: int = 0
) -> pl.CostEstimate:
    """FLOPs/bytes annotation for a comm kernel so profiles and XLA's
    scheduler see real costs (parity: the reference's ``launch_metadata``
    hooks, e.g. ``allgather_gemm.py:145-156``, which label each kernel
    launch with its flop/byte counts for nsys traces)."""
    return pl.CostEstimate(
        flops=int(flops),
        bytes_accessed=int(bytes_accessed),
        transcendentals=int(transcendentals),
    )


def _on_tpu(ctx: DistContext | None = None) -> bool:
    """True when kernels will compile through Mosaic (real TPU)."""
    if ctx is not None:
        return ctx.on_tpu
    try:
        return current_context().on_tpu
    except RuntimeError:
        return jax.default_backend() == "tpu"


def device_initiable(axis: str, ctx: DistContext | None = None) -> bool:
    """True when a device-push Pallas kernel is legal on ``axis``: real
    TPU AND the axis stays inside one slice (ICI). DCN-spanning axes
    are host-driven — AUTO dispatchers must fall back to XLA there
    (the 2-level ops in ``collectives/hierarchical.py`` exist for
    exactly that split)."""
    if not _on_tpu(ctx):
        return False
    if ctx is None:
        try:
            ctx = current_context()
        except RuntimeError:
            return True  # single-device scripts: no axis to cross
    return ctx.axis_is_ici(axis)
