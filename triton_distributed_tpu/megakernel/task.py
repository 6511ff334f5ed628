"""Megakernel task graph: task types, headers, ids, dependencies.

Parity: reference ``mega_triton_kernel/core/task_base.py`` —
``CodeGenKey``:36 (task_type/layer dispatch key), ``TaskIDManager``:75,
``TaskDependency``:112 — and its 8-int device-side task headers read by
the generated megakernel (``core/code_generator.py:92-174``).

TPU redesign: the reference schedules *tile*-granular tasks onto many SMs
and synchronizes them with a shared-memory scoreboard
(``kernels/task_context.py:107``). A TPU chip exposes one sequential
Pallas grid per core, so tasks here are *op*-granular (one task = one
fused op over the whole batch), tile-level parallelism lives INSIDE a
task body as a double-buffered DMA pipeline, and intra-chip dependencies
are discharged by schedule order (the grid is sequential under
``dimension_semantics=("arbitrary",)``) — the scoreboard survives only at
chip boundaries, as DMA-semaphore dataflow in the allreduce task.
"""

from __future__ import annotations

import dataclasses
import enum

import numpy as np

# Device-side header layout: HDR_INTS int32 per task.
# [0] task_type  [1] layer_id  [2] arg0  [3] arg1  [4] task_id
# (rest reserved). task_id rides in the header so the device task
# tracer (docs/observability.md "Device task tracer") can stamp ring
# records with the BUILDER's id, not the schedule position — the two
# differ whenever the scheduler legally reorders independent tasks.
HDR_INTS = 8

# Device trace-ring record layout (obs/kernel_trace.py decodes it):
# TRACE_INTS int32 per (step, task) record, same 8-int width as the
# task headers. ``mid`` is an optional intra-task phase stamp (the AR
# bodies mark when their comm phase hands off); ``flag`` is the
# written marker (the logical clock starts at 1, but a cycle-counter
# clock may legitimately read 0) — a zero flag means the record was
# never written, which is what the decoder's gap-free check keys on.
TRACE_INTS = 8
TR_TASK_ID = 0   # builder task id (header slot 4)
TR_OPCODE = 1    # TaskType value
TR_LAYER = 2     # layer_id
TR_SLOT = 3      # arg0 (e.g. the allreduce parity slot)
TR_BEGIN = 4     # clock at task entry
TR_END = 5       # clock at task exit (epilogue included)
TR_MID = 6       # optional intra-task phase stamp (0 = none)
TR_FLAG = 7      # 1 = record written


class TaskType(enum.IntEnum):
    """Dispatch key (parity: ``CodeGenKey.task_type``).

    Values index the generated ``pl.when`` dispatch chain, mirroring the
    reference's generated if/elif over task types
    (``core/code_generator.py:103-152``).
    """

    EMBED = 0        # x ← embed[tokens]
    NORM = 1         # h ← rms_norm(x) * w;  arg0: 0=ln1  1=ln2  2=final
    QKV_PROJ = 2     # qkv ← h @ wqkv[layer]
    ATTN = 3         # rope + cache append + GQA flash-decode → attn out
    O_PROJ = 4       # h ← attn_out @ wo[layer]   (partial sum over tp)
    FC1 = 5          # mlp ← silu(h @ gate) * (h @ up)
    FC2 = 6          # h ← mlp @ w2[layer]        (partial sum over tp)
    ALLREDUCE = 7    # x ← x + psum(h);  arg0: parity slot
    LM_HEAD = 8      # logits ← rms_norm(x) stage then tiled GEMM
    BARRIER = 9      # standalone cross-chip barrier (stress/test fixture)
    ATTN_PREFILL = 10  # causal self-attn over the S token rows + K/V out
    LOAD_X = 11      # x ← x0 input (prefill: embedding arrives via XLA)
    # Split allreduce (``MegaConfig.overlap_ar``): the producing GEMM's
    # partial is pushed to every peer's workspace slot the moment it is
    # ready (AR_SEND — non-blocking remote puts), and the reduction
    # waits for the inbound partials only AFTER starting the NEXT weight
    # stream's first tile DMA (AR_WAIT) — the megakernel adaptation of
    # the gemm_ar ONE_SHOT overlap (ops/overlap/gemm_ar.py): comm flies
    # under the next task's HBM traffic instead of serializing after
    # the GEMM.
    AR_SEND = 12     # start remote puts of h into peers' cbuf slots
    AR_WAIT = 13     # prefetch next tile-0, wait partials, x += sum
    # MoE decode (Qwen3MoE through the megakernel, docs/megakernel.md
    # "MoE serving"): the dense FC1/FC2 pair is replaced by a router
    # task plus one grouped-GEMM task per LOCAL expert (weights are
    # EP-sharded — each rank streams only the experts it owns, full FFN
    # width), and the EP combine enters the graph as split-phase
    # siblings of AR_SEND/AR_WAIT. On TPU decode the activations are
    # replicated ([B, d] after the attention allreduce) and the router
    # is replicated too, so the DISPATCH half of the reference's EP
    # all-to-all (kernels/nvidia/ep_a2a.py kernel_dispatch_token) is
    # data-free — every rank already holds every token; what crosses
    # the wire is the COMBINE (kernel_combine_token): each rank's
    # weighted sum over its own experts' outputs. A2A_SEND fires those
    # combine puts in two phases — phase 0 the moment the FIRST HALF of
    # the local experts' GEMMs land (so the exchange flies under the
    # second half's expert grouped GEMMs), phase 1 after the rest — and
    # A2A_WAIT blocks only after firing the next weight stream's tile-0
    # DMA (fire_next_tile0, the AR_WAIT overlap lever).
    MOE_GATE = 14    # router: softmax top-k over experts → combine weights
    MOE_FFN = 15     # one local expert's SwiGLU FFN; arg0: local expert id
    A2A_SEND = 16    # start combine puts of a phase partial; arg0: phase
    A2A_WAIT = 17    # prefetch next tile-0, wait partials, x += sum


# Resource class used by the zig-zag scheduler: tasks whose cost is
# dominated by the MXU vs by DMA/ICI traffic (parity role: the
# reference's compute/comm SM partitioning heuristics).
COMM_TASKS = frozenset({
    TaskType.ALLREDUCE, TaskType.BARRIER, TaskType.EMBED,
    TaskType.AR_SEND, TaskType.AR_WAIT,
    TaskType.A2A_SEND, TaskType.A2A_WAIT,
})


@dataclasses.dataclass(frozen=True)
class TaskDependency:
    """Edge producer → consumer (parity: ``TaskDependency``,
    ``core/task_base.py:112``). Tile ranges collapse to whole-task edges
    in the op-granular design."""

    producer: int  # task id


@dataclasses.dataclass
class Task:
    """One schedulable unit (parity: the reference's task records built
    by ``TaskBuilderBase.build_tasks``, ``core/builder.py:62``)."""

    task_id: int
    task_type: TaskType
    layer_id: int = 0
    arg0: int = 0
    arg1: int = 0
    deps: tuple[TaskDependency, ...] = ()

    def header(self, trace: bool = False) -> list[int]:
        # The id column (slot 4) is a tracer-only operand extension:
        # untraced tables stay byte-identical to the pre-tracer layout
        # (nothing untraced reads past slot 3, and launch params must
        # not change when the tracer is off).
        h = [int(self.task_type), self.layer_id, self.arg0, self.arg1,
             self.task_id if trace else 0]
        return h + [0] * (HDR_INTS - len(h))


class TaskIDManager:
    """Monotone task-id allocator (parity: ``TaskIDManager``,
    ``core/task_base.py:75``)."""

    def __init__(self) -> None:
        self._next = 0

    def alloc(self) -> int:
        tid = self._next
        self._next += 1
        return tid

    @property
    def count(self) -> int:
        return self._next


def pack_table(tasks: list[Task], trace: bool = False) -> np.ndarray:
    """Flatten scheduled tasks into the int32 device table the kernel
    scalar-prefetches (parity: the per-SM int32 work queues,
    ``core/scheduler.py:40-63`` — collapsed to one queue for the
    sequential TPU grid). ``trace`` stamps each header's id column
    (slot 4) so the device task tracer can record builder ids; off,
    the table is byte-identical to the pre-tracer layout."""
    if not tasks:
        raise ValueError("empty task list")
    return np.asarray([t.header(trace) for t in tasks], np.int32)
