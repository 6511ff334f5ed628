"""Analytic GEMM / communication time models for autotuner pruning.

Parity: reference ``kernels/nvidia/gemm_perf_model.py`` (tensor-core
roofline from clock rate × subcores) and ``comm_perf_model.py``
(``estimate_reduce_scatter_time_ms`` / ``estimate_all_gather_time_ms``
from NVLink/NIC bandwidth, :97-116). The TPU translation replaces the
CUDA-capability table with a chip-spec table (MXU TFLOPs, HBM GB/s, ICI
GB/s per link) and the NVLink/NIC split with the ICI/DCN split.

Numbers are public per-chip specs (the same ones the scaling-book recipe
uses for its roofline arithmetic). A device kind the table does not list
is an error. The estimators are pruning heuristics that also run where
no chip is attached (the CPU tests): called without a spec they model
the v5e BY NAME, and nothing derived from that named default is a
device metric.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    name: str
    bf16_tflops: float       # MXU peak, bf16
    int8_tops: float         # MXU peak, int8
    hbm_gbs: float           # HBM bandwidth GB/s
    ici_gbs_per_link: float  # one ICI link, one direction, GB/s
    ici_links: int           # links per chip (torus degree)
    dcn_gbs: float           # per-host DCN bandwidth GB/s (order-of-magnitude)


_SPECS = {
    "v4": ChipSpec("v4", 275.0, 275.0, 1228.0, 45.0, 6, 25.0),
    "v5p": ChipSpec("v5p", 459.0, 918.0, 2765.0, 90.0, 6, 25.0),
    "v5e": ChipSpec("v5e", 197.0, 394.0, 819.0, 45.0, 4, 25.0),
    "v6e": ChipSpec("v6e", 918.0, 1836.0, 1640.0, 90.0, 4, 25.0),
}


@functools.lru_cache()
def chip_spec(device_kind: str | None = None) -> ChipSpec:
    """The spec of the named chip generation, or — with no name — of
    the attached TPU. A kind that matches no generation raises: a
    wrong chip's peaks must never stand in for an unknown one's."""
    if device_kind is None:
        device_kind = jax.devices()[0].device_kind
    kind = device_kind.lower().replace(" ", "")
    for key in ("v6e", "v6lite", "v5p", "v5e", "v5lite", "v4"):
        if key in kind:
            return _SPECS[key.replace("lite", "e")]
    raise ValueError(
        f"no chip spec for device kind {device_kind!r}; known "
        f"generations: {sorted(_SPECS)}"
    )


def measured_anchors(path: str | None = None) -> dict | None:
    """Load recorded on-chip measurements (``perf/MEASURED.json``, or
    the file ``TDT_MEASURED_JSON`` names): a measured HBM bandwidth and
    a measured GEMM at the north-star shape, with their provenance
    inside the file. ``anchored_spec`` turns them into an effective
    ChipSpec. None when no such record exists — none does in this
    round yet.
    """
    if path is None:
        path = os.environ.get("TDT_MEASURED_JSON")
    if path is None:
        here = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        path = os.path.join(here, "perf", "MEASURED.json")
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def anchored_spec(
    anchors: dict | None = None, base: ChipSpec | None = None
) -> tuple[ChipSpec, dict]:
    """Effective ChipSpec derived from measurements, plus metadata.

    - ``hbm_gbs``: the probe-measured number outright.
    - ``bf16_tflops``: effective MXU rate solved from the measured
      north-star GEMM (captures real MXU efficiency and dispatch
      amortization, which a projection fed by peak silently hides).
    - ``ici_gbs_per_link``: unmeasurable on one chip; derated by the
      measured/datasheet HBM fraction as a documented same-fabric-class
      proxy. Error bars from the record's own run-to-run variance.

    Returns ``(spec, meta)`` where ``meta`` carries ``error_bars_frac``
    and per-field provenance strings. Falls back to the datasheet spec
    (with ``anchored: False``) when no measurements are recorded.
    """
    anchors = anchors if anchors is not None else measured_anchors()
    # The record names its chip; with no record the model is the v5e's,
    # by name (this runs where no chip is attached).
    base = base or chip_spec((anchors or {}).get("chip") or "v5e")
    if not anchors:
        return base, {"anchored": False}
    hbm = float(anchors.get("hbm_gbs", base.hbm_gbs))
    hbm_frac = hbm / base.hbm_gbs
    tflops = base.bf16_tflops
    g = anchors.get("gemm_anchor")
    if g:
        ideal_flops = 2.0 * g["m"] * g["n"] * g["k"]
        tflops = ideal_flops / (g["ms"] * 1e-3) / 1e12
    spec = dataclasses.replace(
        base,
        name=base.name + "-anchored",
        hbm_gbs=hbm,
        bf16_tflops=tflops,
        int8_tops=base.int8_tops * (tflops / base.bf16_tflops),
        ici_gbs_per_link=base.ici_gbs_per_link * hbm_frac,
    )
    meta = {
        "anchored": True,
        "error_bars_frac": float(anchors.get("error_bars_frac", 0.3)),
        "provenance": anchors.get("provenance", {}),
        "hbm_frac_of_datasheet": round(hbm_frac, 3),
        "effective_bf16_tflops": round(tflops, 1),
    }
    return spec, meta


def _dtype_tflops(spec: ChipSpec, dtype) -> float:
    itemsize = jnp.dtype(dtype).itemsize
    if itemsize == 1:
        return spec.int8_tops
    if itemsize >= 4:
        return spec.bf16_tflops / 2  # fp32 runs the MXU at half rate
    return spec.bf16_tflops


def estimate_gemm_time_ms(
    m: int, n: int, k: int, dtype=jnp.bfloat16, spec: ChipSpec | None = None
) -> float:
    """Roofline GEMM estimate: max(MXU time, HBM stream time).

    Parity: ``estimate_matmul_time`` (``gemm_perf_model.py``) — there
    compute/load/store terms from tensor-core TFLOPs + DRAM bandwidth;
    here the same two terms against MXU and HBM peaks. MXU efficiency is
    derated for small/ragged shapes (128-alignment), the TPU analog of
    the reference's wave-quantization term.
    """
    spec = spec or chip_spec("v5e")
    itemsize = jnp.dtype(dtype).itemsize
    tflops = _dtype_tflops(spec, dtype)

    def pad(x):  # MXU tiles are 128-aligned; ragged edges burn lanes
        return ((x + 127) // 128) * 128

    eff_flops = 2.0 * pad(m) * pad(n) * pad(k)
    compute_ms = eff_flops / (tflops * 1e12) * 1e3
    bytes_moved = (m * k + k * n) * itemsize + m * n * itemsize
    mem_ms = bytes_moved / (spec.hbm_gbs * 1e9) * 1e3
    return max(compute_ms, mem_ms)


def _ring_bw_gbs(spec: ChipSpec, bidir: bool = True) -> float:
    """Per-chip ring bandwidth over ICI: a 1-D ring uses 2 links per chip
    (one per direction) when the protocol is bidirectional."""
    links = 2 if bidir and spec.ici_links >= 2 else 1
    return spec.ici_gbs_per_link * links


def estimate_reduce_scatter_time_ms(
    nbytes: int,
    world_size: int,
    local_world_size: int | None = None,
    spec: ChipSpec | None = None,
    bidir: bool = True,
) -> float:
    """Ring reduce-scatter estimate over ICI, with a DCN term when the
    axis spans slices.

    Parity: ``estimate_reduce_scatter_time_ms`` (``comm_perf_model.py:97``)
    — intra-node NVLink term + inter-node NIC term, overlapped when
    fullmesh. TPU: intra-slice ICI ring moves (n-1)/n of the payload per
    chip; the inter-slice share rides DCN and dominates when present.
    """
    spec = spec or chip_spec("v5e")
    local = local_world_size or world_size
    intra_ms = (
        nbytes * (local - 1) / local / (_ring_bw_gbs(spec, bidir) * 1e9) * 1e3
    )
    if world_size != local:
        nslices = world_size // local
        inter_ms = nbytes / local / (spec.dcn_gbs * 1e9) * 1e3 * (nslices - 1)
        return intra_ms + inter_ms
    return intra_ms


def estimate_all_gather_time_ms(
    nbytes: int,
    world_size: int,
    local_world_size: int | None = None,
    spec: ChipSpec | None = None,
    bidir: bool = True,
) -> float:
    """Same cost shape as reduce-scatter (parity:
    ``comm_perf_model.py:113-116``). ``nbytes`` is the FULL gathered
    size."""
    return estimate_reduce_scatter_time_ms(
        nbytes, world_size, local_world_size, spec, bidir
    )


def estimate_all_reduce_time_ms(
    nbytes: int,
    world_size: int,
    local_world_size: int | None = None,
    spec: ChipSpec | None = None,
) -> float:
    """Two-shot allreduce = RS + AG of the same payload."""
    return 2.0 * estimate_reduce_scatter_time_ms(
        nbytes, world_size, local_world_size, spec
    )


def estimate_straggler_stall_ms(
    lag_ms: float, step_ms: float, n: int, adaptive: bool
) -> float:
    """Expected exposed stall in AG+GEMM when one uniformly-random rank's
    chunk arrives ``lag_ms`` late (the tolerance the reference's
    arrival-adaptive tile swizzles buy, ``threadblock_swizzle_ag_moe.py``).

    Static ring order meets the laggard's chunk at position
    ``p = (r - me) mod n`` and stalls ``max(0, lag - p*step)`` — for a
    next-door laggard almost the whole lag is exposed. The adaptive
    schedule (``AGGemmConfig(adaptive=True)``) defers any not-yet-landed
    chunk behind every landed one, so the laggard is met at position
    ``n-1``: exposure is only what (n-2) other chunks' compute could
    not cover.

    PRECONDITION of the adaptive formula: the overlap regime —
    ``step_ms`` at least the per-chunk wire time, so every non-laggard
    chunk has landed by the first step boundary. When compute is faster
    than the wire, the kernel's probe can be inconclusive and its
    fallback blocks in ring order (see the config docstring); this
    model then OVERSTATES the adaptive tolerance — don't capacity-plan
    from it outside the compute-bound regime.
    """
    if adaptive:
        return max(0.0, lag_ms - (n - 1) * step_ms)
    stalls = [max(0.0, lag_ms - p * step_ms) for p in range(1, n)]
    return sum(stalls) / len(stalls) if stalls else 0.0


def prune_configs_by_model(configs, est_fn, top_k: int = 8):
    """Keep the ``top_k`` configs by estimated time.

    Parity: the reference prunes its autotune space with the perf models
    (``gemm_perf_model.py`` used via ``triton.autotune`` ``prune_configs_by``).
    ``est_fn(config) -> ms``.
    """
    if len(configs) <= top_k:
        return list(configs)
    return sorted(configs, key=est_fn)[:top_k]
