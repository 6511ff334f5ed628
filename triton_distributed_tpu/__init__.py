"""triton_distributed_tpu — a TPU-native framework for compute–communication
overlapping kernels.

This package provides the capabilities of Triton-distributed (ByteDance Seed's
distributed compiler for overlapping kernels, reference layout documented in
/root/repo/SURVEY.md) re-designed idiomatically for TPU:

- ``runtime``  — mesh/topology, distributed initialization, perf + profiling
  utilities (parity: reference ``python/triton_dist/utils.py``).
- ``language`` — device-side communication primitives for Pallas kernels:
  rank/num_ranks, signal/wait semaphores, remote DMA put/get, put+signal,
  tile barriers (parity: reference ``python/triton_dist/language/`` +
  ``libnvshmem_device.py``, built on ``pltpu.make_async_remote_copy`` and
  ``pltpu.semaphore_signal/wait`` over ICI instead of NVSHMEM).
- ``ops``      — collectives (all-gather, reduce-scatter, all-reduce,
  all-to-all, p2p) and overlapping kernels (AG+GEMM, GEMM+RS, GEMM+AR,
  MoE dispatch/combine, distributed flash-decode, SP attention, ring
  attention) (parity: reference ``python/triton_dist/kernels/``).
- ``parallel`` — TP/EP/SP/PP model-parallel layers (parity: reference
  ``python/triton_dist/layers/``).
- ``models``   — Qwen3 dense + MoE models, KV cache, serving engine
  (parity: reference ``python/triton_dist/models/``).
- ``mega``     — megakernel-style whole-model persistent kernel runtime
  (parity: reference ``python/triton_dist/mega_triton_kernel/``).
- ``tools``    — distributed-aware autotuner, AOT export, trace tooling
  (parity: reference ``python/triton_dist/tools/`` + ``autotuner.py``).
"""

__version__ = "0.1.0"

from triton_distributed_tpu.runtime import (  # noqa: F401
    DistContext,
    current_context,
    initialize_distributed,
    finalize_distributed,
)
