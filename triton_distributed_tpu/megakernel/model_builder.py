"""ModelBuilder: whole-decode-step task graphs → one compiled megakernel.

Parity: reference ``mega_triton_kernel/models/model_builder.py`` —
``ModelBuilder.make_fc1/make_qkv_proj/make_attn/make_allreduce/…``
:189-352, ``compile()``:372 (schedule + codegen + triton compile),
``run()``:391 (launch the persistent kernel), and its symmetric-tensor
accounting ``create_symm_tensor``:119 (here: the kernel's workspace
output + semaphore scratch, allocated by the pallas_call itself).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from triton_distributed_tpu.megakernel import kernels as _kernels  # noqa: F401  (registers bodies)
from triton_distributed_tpu.megakernel.code_generator import (
    MegaConfig,
    MegaDims,
    build_mega_call,
)
from triton_distributed_tpu.megakernel.scheduler import SchedulePolicy, schedule
from triton_distributed_tpu.megakernel.task import (
    Task,
    TaskDependency,
    TaskIDManager,
    TaskType,
    pack_table,
)
from triton_distributed_tpu.ops.common import next_collective_id
from triton_distributed_tpu.runtime.mesh import DistContext, current_context


class ModelBuilder:
    """Decoder-LM task-graph builder.

    ``make_*`` methods append tasks with explicit dependencies (default:
    the previously appended task — the sequential decode chain); the
    scheduler may then legally reorder independent tasks. ``compile()``
    freezes the graph into one Pallas megakernel.
    """

    def __init__(
        self,
        dims: MegaDims,
        *,
        cfg: MegaConfig | None = None,
        axis: str = "tp",
        ctx: DistContext | None = None,
        wdtype=jnp.bfloat16,
        cdtype=jnp.bfloat16,
    ):
        self.dims = dims
        self.cfg = cfg or MegaConfig()
        self.axis = axis
        self.ctx = ctx or current_context()
        self.wdtype = wdtype
        self.cdtype = cdtype
        self.tasks: list[Task] = []
        self._idm = TaskIDManager()
        self._last: int | None = None

    # -- graph construction (parity: make_* methods :189-352) ------------
    def _add(
        self,
        task_type: TaskType,
        layer: int = 0,
        arg0: int = 0,
        deps: list[int] | None = None,
    ) -> int:
        tid = self._idm.alloc()
        if deps is None:
            deps = [] if self._last is None else [self._last]
        self.tasks.append(
            Task(
                task_id=tid,
                task_type=task_type,
                layer_id=layer,
                arg0=arg0,
                deps=tuple(TaskDependency(d) for d in deps),
            )
        )
        self._last = tid
        return tid

    def make_embed(self, **kw) -> int:
        return self._add(TaskType.EMBED, **kw)

    def make_norm(self, layer: int, which: int, **kw) -> int | None:
        """which: 0 = input layernorm, 1 = post-attn, 2 = final.

        Under ``cfg.fuse_norms`` this is a no-op (returns None): the
        consumers (qkv/fc1/lm_head) compute the norm inline, and a NORM
        task slipping back into ANY graph would double-normalize — the
        guard lives here so no builder can forget it."""
        if self.cfg.fuse_norms:
            return None
        return self._add(TaskType.NORM, layer, arg0=which, **kw)

    def make_qkv_proj(self, layer: int, **kw) -> int:
        return self._add(TaskType.QKV_PROJ, layer, **kw)

    def make_attn(self, layer: int, **kw) -> int:
        return self._add(TaskType.ATTN, layer, **kw)

    def make_o_proj(self, layer: int, **kw) -> int:
        return self._add(TaskType.O_PROJ, layer, **kw)

    def make_fc1(self, layer: int, **kw) -> int:
        return self._add(TaskType.FC1, layer, **kw)

    def make_fc2(self, layer: int, **kw) -> int:
        return self._add(TaskType.FC2, layer, **kw)

    def make_allreduce(self, layer: int = 0, **kw) -> int:
        # Kept even for n_ranks == 1: the body also folds the residual
        # (x += h), degenerating to a plain add with zero remote puts.
        # Under ``cfg.overlap_ar`` (and only with real peers) the
        # exchange splits into AR_SEND (remote puts start the moment
        # the producing GEMM finished) + AR_WAIT (reduction waits only
        # after firing the next weight stream's tile-0 DMA) — the
        # gemm_ar ONE_SHOT overlap adapted to the sequential grid; the
        # n_ranks guard lives HERE so no graph builder pays two task
        # iterations for a single-rank exchange with nothing to hide.
        if self.cfg.overlap_ar and self.dims.n_ranks > 1:
            self._add(TaskType.AR_SEND, layer, **kw)
            return self._add(TaskType.AR_WAIT, layer)
        return self._add(TaskType.ALLREDUCE, layer, **kw)

    def make_moe_gate(self, layer: int, **kw) -> int:
        return self._add(TaskType.MOE_GATE, layer, **kw)

    def make_moe_ffn(self, layer: int, expert: int,
                     handoff: bool = False) -> int:
        """One LOCAL expert's FFN task (``arg0`` = local expert id).
        ``handoff`` marks the last expert of the NON-overlap path: its
        epilogue copies the combine accumulator into ``h`` so the fused
        ALLREDUCE task (which reads ``h``) carries the MoE combine."""
        tid = self._add(TaskType.MOE_FFN, layer, arg0=expert)
        if handoff:
            self.tasks[-1].arg1 = 1
        return tid

    def make_a2a_send(self, layer: int, phase: int) -> int:
        return self._add(TaskType.A2A_SEND, layer, arg0=phase)

    def make_a2a_wait(self, layer: int) -> int:
        return self._add(TaskType.A2A_WAIT, layer)

    def make_lm_head(self, **kw) -> int:
        return self._add(TaskType.LM_HEAD, **kw)

    def make_attn_prefill(self, layer: int, **kw) -> int:
        return self._add(TaskType.ATTN_PREFILL, layer, **kw)

    def make_load_x(self, **kw) -> int:
        return self._add(TaskType.LOAD_X, **kw)

    def make_barrier(self, **kw) -> int:
        return self._add(TaskType.BARRIER, **kw)

    def build_decoder_graph(self) -> None:
        """The standard decode-step chain (parity:
        ``models/qwen3.py:108`` build_fwd). With ``dims.moe`` the MLP
        section becomes router → per-local-expert grouped GEMMs → EP
        combine; under ``cfg.overlap_ar`` the combine splits into the
        A2A_SEND/A2A_WAIT pair with phase 0 fired MID-FFN, so its ICI
        bytes fly under the second half of the expert GEMMs and the
        final wait blocks only after the next weight stream's tile-0
        DMA is in flight (docs/megakernel.md "MoE serving")."""
        if self.dims.n_ranks > 1:
            # Entry barrier: the first ALLREDUCE issues remote puts into
            # peers' VMEM scratch; without this, launch skew could land a
            # put before the peer has entered the kernel (scratch/semaphores
            # still owned by the previous program). Trailing barriers cover
            # all subsequent allreduces within the launch.
            self.make_barrier()
        self.make_embed()
        for l in range(self.dims.num_layers):
            self.make_norm(l, 0)  # no-op under cfg.fuse_norms
            self.make_qkv_proj(l)
            self.make_attn(l)
            self.make_o_proj(l)
            self.make_allreduce(l)
            self.make_norm(l, 1)
            if self.dims.moe:
                self._build_moe_mlp(l)
            else:
                self.make_fc1(l)
                self.make_fc2(l)
                self.make_allreduce(l)
        self.make_norm(0, 2)
        self.make_lm_head()

    def _build_moe_mlp(self, l: int) -> None:
        """The MoE MLP section of one layer: MOE_GATE, the local expert
        GEMM tasks, and the combine — split-phase A2A under
        ``overlap_ar`` (phase 0 after the first half of the experts,
        phase 1 + wait after the rest), the fused ALLREDUCE otherwise
        (the last expert's ``handoff`` hands it the accumulator)."""
        self.make_moe_gate(l)
        epr = self.dims.experts_loc
        overlap = self.cfg.overlap_ar
        split = max(-(-epr // 2), 1)  # ceil — phase 0 covers this many
        for e in range(epr):
            last = e == epr - 1
            self.make_moe_ffn(l, e, handoff=last and not overlap)
            if overlap and e == split - 1:
                self.make_a2a_send(l, phase=0)
        if overlap:
            self.make_a2a_send(l, phase=1)
            self.make_a2a_wait(l)
        else:
            self.make_allreduce(l)

    def build_prefill_graph(self) -> None:
        """The prompt-prefill chain (parity: the reference's prefill
        TaskBuilders, ``model_builder.py:189-352``): same per-layer
        pipeline as decode with causal self-attention over the S token
        rows; the embedding arrives as an input (LOAD_X) and the LM head
        projects only the last real row (arg0=1)."""
        if self.dims.n_ranks > 1:
            self.make_barrier()  # same entry-skew reasoning as decode
        self.make_load_x()
        for l in range(self.dims.num_layers):
            self.make_norm(l, 0)  # no-op under cfg.fuse_norms
            self.make_qkv_proj(l)
            self.make_attn_prefill(l)
            self.make_o_proj(l)
            self.make_allreduce(l)
            self.make_norm(l, 1)
            self.make_fc1(l)
            self.make_fc2(l)
            self.make_allreduce(l)
        self.make_norm(0, 2)
        # The LM head projects only the last real row in prefill graphs
        # (driven by dims.prefill inside lm_head_body, not a task arg).
        self.make_lm_head()

    # -- compile ---------------------------------------------------------
    def compile(
        self, policy: SchedulePolicy = SchedulePolicy.ROUND_ROBIN
    ) -> "CompiledMegaKernel":
        """Schedule + generate the single-kernel program
        (parity: ``ModelBuilder.compile``:372)."""
        order = schedule(self.tasks, policy)
        table = pack_table(order, trace=self.dims.trace)
        run = build_mega_call(
            self.dims,
            self.cfg,
            order,
            axis=self.axis,
            ctx=self.ctx,
            wdtype=self.wdtype,
            cdtype=self.cdtype,
            collective_id=next_collective_id(),
            table=jnp.asarray(table),
        )
        return CompiledMegaKernel(
            builder=self, order=order, per_shard=run
        )


@dataclasses.dataclass
class CompiledMegaKernel:
    """A scheduled, traced megakernel (parity: the compiled
    MEGA_TRITON_KERNEL + its ``run()``, ``model_builder.py:391``)."""

    builder: ModelBuilder
    order: list[Task]
    per_shard: Any  # per-shard callable (inside shard_map)

    @property
    def num_tasks(self) -> int:
        return len(self.order)
