"""Megakernel code generator: one Pallas kernel for a whole task graph.

Parity: reference ``mega_triton_kernel/core/code_generator.py`` —
``make_mega_kernel_src``:31 emits ONE ``@triton.jit`` kernel that loads
8-int task headers and dispatches via generated if/elif :92-174.

TPU redesign: no source-text generation — the "generated kernel" is a
traced closure. The task table is a scalar-prefetch operand (the analog
of the per-SM int32 work queues living in SMEM), the grid is the task
count with ``dimension_semantics=("arbitrary",)`` (sequential, so
schedule order IS the dependency order), and dispatch is a ``pl.when``
chain over exactly the task types the model uses — same shape as the
reference's generated if/elif, but over Mosaic predication.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_distributed_tpu.megakernel.registry import get_body_factory
from triton_distributed_tpu.megakernel.task import (
    TR_BEGIN,
    TR_END,
    TR_FLAG,
    TR_LAYER,
    TR_MID,
    TR_OPCODE,
    TR_SLOT,
    TR_TASK_ID,
    TRACE_INTS,
    Task,
    TaskType,
)
from triton_distributed_tpu.ops.common import interpret_mode, pick_tile
from triton_distributed_tpu.runtime.mesh import DistContext


def _vmem_limit_bytes(
    scratch: list, out_shapes: list, in_vmem_bytes: int = 0
) -> int:
    """Scoped-VMEM limit derived from the resolved kernel footprint.

    Sums the VMEM scratch buffers (the staging depth × tile-width
    product that actually scales with :class:`MegaConfig`), the
    VMEM-resident outputs, and ``in_vmem_bytes`` — the caller's
    analytic total for VMEM-resident in_specs (norm weights, wq8
    scales, prefill prompt block, and the Mosaic-pipelined sampled-
    noise block counted TWICE for double buffering — ADVICE r4: the
    old 1.5× headroom alone under-provisioned sampled/large-B
    configs). Applies 1.5× headroom for Mosaic's own temporaries and
    clamps to [32 MiB, 112 MiB]: the floor keeps tiny configs from
    under-shooting Mosaic's working needs, the cap stays under the
    128 MiB physical VMEM of v5e/v5p. Replaces the old flat 100 MiB
    constant that over-committed smaller-VMEM generations and
    over-asked for default configs (ADVICE r3)."""
    def _nbytes(x) -> int:
        shape = getattr(x, "shape", None)
        dtype = getattr(x, "dtype", None)
        if shape is None or dtype is None:
            return 0
        try:
            itemsize = jnp.dtype(dtype).itemsize
        except TypeError:  # semaphore "dtypes" (dma_sem etc.)
            return 0
        n = 1
        for s in shape:
            n *= int(s)
        return n * itemsize

    footprint = sum(_nbytes(s) for s in scratch)
    footprint += sum(_nbytes(o) for o in out_shapes)
    footprint += in_vmem_bytes
    mib = 1024 * 1024
    return max(32 * mib, min(112 * mib, int(footprint * 1.5) + 8 * mib))


@dataclasses.dataclass(frozen=True)
class MegaDims:
    """Static per-shard geometry of the decode step."""

    batch: int
    d: int
    hq_loc: int
    hkv_loc: int
    head_dim: int
    f_loc: int
    v_loc: int
    num_layers: int
    s_max: int
    n_ranks: int
    rms_eps: float = 1e-6
    rope_theta: float = 1e6
    # Paged-KV mode: page size (0 = dense cache). When set, the KV
    # inputs are page pools [L, P, hkv, page, hd], a page table rides as
    # a scalar-prefetch operand, and the attention block size is the
    # page size (parity: reference paged_kv_cache.py).
    page: int = 0
    # Quantized paged pool (``kv_dtype="int8"``, PR 4's storage mode):
    # the KV pools arrive as int8 codes and two per-page-per-head scale
    # operands ``[L, P, 1, Hkv]`` f32 ride as VMEM-resident inputs (the
    # [L, P, 1, H] layout is the norm-weight trick — dynamic layer/page
    # indices stay on untiled leading dims). The attention task
    # dequantizes each staged page block in-register, so full-width KV
    # never materializes in HBM — the megakernel keeps the int8 pool's
    # bytes/token. Requires ``page`` > 0 (scales live on pages).
    kv_quant: bool = False
    # Pool page count (0 = unknown): only feeds the scoped-VMEM limit
    # accounting for the VMEM-resident scale operands above.
    num_pages: int = 0
    # Prefill mode: ``batch`` is the prompt length S (rows = positions),
    # the embedded prompt arrives as an extra input (LOAD_X task), the
    # cache is not read, K/V come out as [L, hkv, S, hd], and the LM
    # head projects only the last real row → logits [1, v_loc].
    prefill: bool = False
    # Multi-step greedy decode: ``nsteps`` whole decode steps run inside
    # ONE kernel launch (grid = (nsteps, tasks)) — the LM head argmaxes
    # in-kernel (under TP: local argmax + one-shot cross-rank
    # (value, index) exchange) and feeds the token back through SMEM,
    # attention covers the launch's earlier steps from the knew/vnew
    # outputs (the "band"), and the caller appends all nsteps rows at
    # once. Amortizes the per-launch/per-op dispatch cost over nsteps
    # (not measured in this round). Argmax-based: greedy,
    # or temperature sampling via the `sampled` Gumbel noise below.
    nsteps: int = 1
    # GLOBAL real (unpadded) vocab size; 0 = every column real. The
    # in-kernel argmax masks this rank's pad columns (zero weights
    # score 0, which could beat real negative logits) — rank r's real
    # width is clamp(v_real - r*v_loc, 0, v_loc).
    v_real: int = 0
    # Sampled multi-step decode: an extra [nsteps, B, v_loc] noise
    # input rides along and the LM head argmaxes logits + noise — the
    # Gumbel-max trick (noise = temperature * gumbel drawn by the
    # host) turns the greedy machinery into temperature sampling while
    # the RNG stays in JAX-land (reproducible, testable).
    sampled: bool = False
    # In-kernel top-k/top-p filtered sampling (requires ``sampled`` and
    # ``nsteps`` > 1, single-rank only): a per-row sampling config
    # ``sampcfg [B, 4]`` f32 — ``[inv_temperature, top_k_effective,
    # top_p, enable]`` — rides as a VMEM operand and the LM head, after
    # streaming the raw logits, derives the EXACT host filter_logits
    # keep-set by per-row parallel bisection (64 fixed iterations on
    # the scaled-logit axis: the top-k threshold is the largest τ with
    # #{l/T > τ} ≥ k, the top-p threshold the largest τ whose
    # above-mass ≥ p·Z over the top-k survivors — both converge to the
    # float just below the host's cutoff value, so ties survive exactly
    # as in ``models.sampling.filter_logits``), then argmaxes
    # ``logits + noise`` over the kept set. With ``noise =
    # temperature · gumbel`` this IS top-k/top-p temperature sampling
    # (Gumbel-max over the filtered support ≡ categorical over the
    # filtered softmax). Rows with enable=0 keep the whole real vocab —
    # a zero-noise greedy row in a filtered batch stays bit-identical
    # to the greedy build. Single-rank only: the filter needs the full
    # logit row, which under TP is column-sharded across ranks.
    filtered: bool = False
    # Device-side stop-token testing (requires ``page`` and ``nsteps``
    # > 1): a ``stop_tok [B]`` i32 scalar-prefetch operand (-1 = none)
    # and a ``stop_step [1, B]`` i32 SMEM output — the LM head stamps
    # the first step whose sampled token equals the row's stop token
    # (``nsteps`` = never). The caller clamps its KV append counts to
    # ``stop_step + 1`` so rows decoded past a stop route to the trash
    # page, and finished slots retire at the next host drain without a
    # KV-rollback round trip.
    eos: bool = False
    # Race-provocation fixture (parity: the reference's for_correctness
    # sleeps / straggler_option): lag this rank's LM-head argmax
    # exchange so a peer missing a wait reads stale candidates.
    # None = fixture off (straggle_if_rank's own no-op convention).
    straggler_rank: int | None = None
    straggler_nanos: int = 500_000
    # Device task tracer (docs/observability.md "Device task tracer"):
    # the kernel gains an SMEM trace-ring output [nsteps, T, TRACE_INTS]
    # int32 and every grid iteration records its task's
    # (task_id, opcode, layer, slot, begin, end[, mid]) on a monotonic
    # SMEM logical clock (kernels.trace_tick: the installed Pallas has
    # no cycle counter). Off (the default) the operand list,
    # scratch, and traced program are bit-identical to the untraced
    # build — the tracer costs literally nothing when disabled.
    trace: bool = False
    # MoE decode (docs/megakernel.md "MoE serving"): num_experts > 0
    # swaps the dense FC1/FC2 pair for MOE_GATE + one MOE_FFN task per
    # LOCAL expert + the split-phase A2A combine. The w1/w2 operands
    # become EP-sharded per-expert stacks [L, E_loc, d, 2f] / [L,
    # E_loc, f, d] (full FFN width — ``f_loc`` is then the FULL
    # moe_intermediate_size), a replicated router weight [L, d, E]
    # rides as an extra VMEM operand, and the combine workspace gains a
    # phase-0 buffer so two exchanges can be in flight per layer.
    num_experts: int = 0
    moe_top_k: int = 0
    norm_topk: bool = True

    @property
    def moe(self) -> bool:
        return self.num_experts > 0

    @property
    def experts_loc(self) -> int:
        """Local experts per rank (EP shard of the expert axis)."""
        return self.num_experts // self.n_ranks if self.moe else 0

    @property
    def qkv_loc(self) -> int:
        return (self.hq_loc + 2 * self.hkv_loc) * self.head_dim

    @property
    def o_k(self) -> int:
        return self.hq_loc * self.head_dim


@dataclasses.dataclass(frozen=True)
class MegaConfig:
    """Tile configuration (parity: the reference's per-task tile configs
    in its TaskBuilders). Resolved against dims by :func:`resolve`."""

    # Defaults from a v5e sweep on Qwen3-0.6B decode (1024/1024/256 ran
    # 3.0 ms/step vs 4.1 at 512/512): wide tiles amortize the per-tile
    # DMA turnaround in the weight streams; s_blk=512 regresses the KV
    # pipeline. (2048-wide tiles used to fail to compile — that was the
    # 16 MB default scoped-VMEM limit, which build_mega_call now
    # raises; they are sweepable again via perf/mega_tile_sweep.py.)
    tile_n: int = 1024
    tile_k: int = 1024
    s_blk: int = 256
    # Weight-stream staging depth: nbuf-1 DMAs stay in flight ahead of
    # the consuming matmul (2 = classic double buffer). The decode-step
    # weight stream is the whole ladder's floor (~1.2 GB/step at 0.6B);
    # with per-tile control overhead comparable to a 2 MB tile's wire
    # time, a deeper pipeline keeps the HBM controller busy through the
    # scalar-core gaps between tiles.
    nbuf: int = 2
    # int8 weight-only quantized decode: the five projection weights
    # stream as int8 (HALF the HBM bytes of the bf16 step — decode is
    # HBM-bound, so this halves the ladder's floor) with f32
    # per-output-channel scales applied to each tile product before
    # any nonlinearity. Per-channel scales compose exactly with TP:
    # column-sharded weights scale their local columns; row-sharded
    # (o/fc2 partial sums) dequantize per shard BEFORE the allreduce.
    # Activations, norms, embed, KV stay bf16/f32 — weight-only.
    # Callers pass `MegaQwen3.quantized_params()` in place of params.
    wq8: bool = False
    # Cross-task weight prefetch: after each task body, the kernel
    # reads the NEXT task's header and — when it is a weight-streaming
    # task — starts its FIRST tile's DMA into the staging rotation,
    # with an SMEM "preloaded" flag telling that stream to skip its own
    # tile-0 start. Removes the first-tile DMA exposure at every
    # qkv/o/fc1/fc2/lm_head boundary (~5 per layer); the scalar core
    # issues the prefetch while the MXU still runs the current task's
    # trailing matmuls. Requires nbuf >= 2.
    cross_prefetch: bool = False
    # Fold the RMS norms into their consumers (qkv / fc1 / lm_head
    # compute the norm inline from x instead of reading a NORM task's h)
    # — drops 2 tasks per layer + the final norm from the grid, i.e.
    # ~28% of the megakernel's task iterations at 0.6B. The norm math
    # is identical; only the task boundary (grid-iteration dispatch +
    # the consumer's first-DMA latency exposure) goes away. A/B'd by
    # perf/mega_tile_sweep.py before becoming default.
    fuse_norms: bool = False
    # Overlapped TP collectives (the gemm_ar ONE_SHOT pattern adapted
    # to the sequential megakernel grid, ops/overlap/gemm_ar.py): each
    # layer allreduce splits into AR_SEND (remote puts start the moment
    # the producing GEMM's partial is ready) and AR_WAIT (waits the
    # inbound partials only AFTER starting the next weight stream's
    # first tile DMA), so the ICI hop hides under the next task's HBM
    # traffic — decode's actual bottleneck — instead of serializing
    # after the GEMM. The in-window prefetch needs the cross_prefetch
    # flag machinery (the consuming stream must skip its own tile-0
    # start) and pairs best with fuse_norms (the task after AR_WAIT is
    # then the weight stream itself); without cross_prefetch the split
    # still overlaps the puts with task dispatch only. No-op at
    # n_ranks == 1 (the builder emits the fused ALLREDUCE there).
    overlap_ar: bool = False

    @classmethod
    def from_spec(cls, spec: str) -> "MegaConfig":
        """Parse the sweep/bench config-string format
        ``tile_n:tile_k:nbuf[:fuse_norms[:cross_prefetch[:overlap_ar]]]``
        — the ONE parser for both ``perf/mega_tile_sweep.py`` (which
        writes these strings into ``perf/MEGA_TUNED.json``) and
        ``bench.py`` (which reads them back); a shared definition keeps
        the handoff format-compatible."""
        fields = [int(v) for v in spec.split(":")]
        if len(fields) not in (3, 4, 5, 6):
            raise ValueError(
                "want tile_n:tile_k:nbuf[:fuse_norms[:cross_prefetch"
                f"[:overlap_ar]]], got {spec!r}"
            )
        # Validate VALUES here, not just arity: a tuned-file/env spec
        # like "0:1024:2" or a negative tile would otherwise surface as
        # an obscure failure deep inside kernel build.
        if min(fields[:3]) <= 0:
            raise ValueError(
                f"tile_n/tile_k/nbuf must be positive, got {spec!r}"
            )
        if any(f not in (0, 1) for f in fields[3:]):
            raise ValueError(
                f"fuse_norms/cross_prefetch/overlap_ar flags must be 0 "
                f"or 1: {spec!r}"
            )
        return cls(
            tile_n=fields[0], tile_k=fields[1], nbuf=fields[2],
            fuse_norms=bool(fields[3]) if len(fields) > 3 else False,
            cross_prefetch=bool(fields[4]) if len(fields) > 4 else False,
            overlap_ar=bool(fields[5]) if len(fields) > 5 else False,
        )

    def spec(self) -> str:
        """Inverse of :meth:`from_spec` (what the sweep persists)."""
        return (f"{self.tile_n}:{self.tile_k}:{self.nbuf}:"
                f"{int(self.fuse_norms)}:{int(self.cross_prefetch)}:"
                f"{int(self.overlap_ar)}")

    def resolve(self, dims: MegaDims) -> "ResolvedConfig":
        if self.nbuf < 1:
            raise ValueError(f"nbuf must be >= 1, got {self.nbuf}")
        if self.cross_prefetch and self.nbuf < 2:
            # Serial mode starts each tile at its own iteration; there
            # is no rotation slot a prefetched tile could wait in.
            raise ValueError("cross_prefetch requires nbuf >= 2")
        return ResolvedConfig(
            # nbuf=1 is a valid (serial, no-prefetch) degenerate the
            # sweep uses to isolate the prefetch benefit.
            nbuf=self.nbuf,
            cross_prefetch=self.cross_prefetch,
            fuse_norms=self.fuse_norms,
            wq8=self.wq8,
            overlap_ar=self.overlap_ar,
            tn_qkv=pick_tile(dims.qkv_loc, self.tile_n),
            tn_fc1=pick_tile(dims.f_loc, self.tile_n),
            # The vocab axis rarely divides by a wide tile (Qwen3:
            # 151936 = 128·1187), so the LM head streams a wide main
            # tile plus one remainder tile (lm_head_body) instead of
            # collapsing to the largest pow-2 divisor (128-wide tiles
            # halve HBM stream efficiency on the largest weight). The
            # remainder must itself be a 128-multiple for lane
            # alignment, hence the v_loc % 128 gate — Qwen3's v_loc
            # only satisfies it at tp=1 (151936/tp carries a 64/96/48
            # residue); pad the vocab to 128·tp at load time to widen
            # lm tiles under TP.
            tn_lm=(
                min(self.tile_n, dims.v_loc)
                if dims.v_loc % 128 == 0 and self.tile_n % 128 == 0
                else pick_tile(dims.v_loc, self.tile_n)
            ),
            tk_o=pick_tile(dims.o_k, self.tile_k),
            tk_fc2=pick_tile(dims.f_loc, self.tile_k),
            # Paged mode: the KV block IS the page — pick_tile's 128
            # floor must not widen it past the page size.
            s_blk=dims.page or pick_tile(dims.s_max, self.s_blk),
        )


@dataclasses.dataclass(frozen=True)
class ResolvedConfig:
    nbuf: int
    cross_prefetch: bool
    fuse_norms: bool
    wq8: bool
    overlap_ar: bool
    tn_qkv: int
    tn_fc1: int
    tn_lm: int
    tk_o: int
    tk_fc2: int
    s_blk: int

    @property
    def tn_max(self) -> int:
        return max(self.tn_qkv, self.tn_fc1, self.tn_lm)

    @property
    def tk_max(self) -> int:
        return max(self.tk_o, self.tk_fc2)


class KernelCtx:
    """Everything a task body sees: dims, config, header fields, refs.

    Ref attributes are bound by :func:`make_mega_kernel` per trace; the
    names are the contract between the generator and ``kernels.py``.
    """

    def __init__(self, dims: MegaDims, cfg: ResolvedConfig, axis: str,
                 wdtype, cdtype):
        self.dims = dims
        self.cfg = cfg
        self.axis = axis
        self.wdtype = wdtype
        self.cdtype = cdtype
        # traced per-step header fields, bound in the kernel body:
        self.layer: Any = None
        self.arg0: Any = None
        self.arg1: Any = None
        self.table: Any = None  # page table (paged mode only)
        self.step: Any = None   # decode step within the launch (multi-step)
        self.tok_smem: Any = None   # [B] i32 — next-token feedback
        self.toks_out: Any = None   # [nsteps, 1, B] i32 — greedy tokens
        self.noise: Any = None  # [1, B, v_loc] VMEM — this step's noise
        # Filtered-sampling config [B, 4] f32 (None unless dims.filtered):
        # per-row [inv_temperature, top_k_effective, top_p, enable].
        self.sampcfg: Any = None
        # Device stop-token refs (None unless dims.eos): the [B] i32
        # stop-token scalar-prefetch operand and the [1, B] i32 SMEM
        # stop_step output the LM head stamps.
        self.stop_tok: Any = None
        self.stop_out: Any = None
        # cross_prefetch SMEM flags: slot 0 of col/rowstage already
        # holds the current task's tile 0 (started by the previous
        # task's prefetch block; the stream skips its own start).
        self.pre_col: Any = None
        self.pre_row: Any = None
        # wq8 dequant scale refs (None unless cfg.wq8):
        self.sc_qkv: Any = None
        self.sc_o: Any = None
        self.sc_w1: Any = None
        self.sc_w2: Any = None
        self.sc_lm: Any = None
        # int8 paged-pool dequant scales [L, P, 1, Hkv] f32 (None unless
        # dims.kv_quant): the attention task reads scalar (layer, page,
        # head) entries to dequantize staged page blocks in-register.
        self.ksc: Any = None
        self.vsc: Any = None
        # The scalar-prefetched task table + current task index, bound
        # per trace: the AR_WAIT body peeks its successor's header to
        # start that weight stream's tile-0 DMA before blocking on the
        # inbound allreduce partials (cfg.overlap_ar).
        self.task_tab: Any = None
        self.t: Any = None
        # Device task tracer refs (None unless dims.trace): the SMEM
        # trace-ring output and the logical-clock SMEM counter.
        self.trace_out: Any = None
        self.clk: Any = None
        # MoE refs (None unless dims.moe): the replicated router weight
        # [L, d, E], the per-(expert, token) combine weights the gate
        # writes ([E, 1, B] f32 — expert-leading so MOE_FFN's traced
        # expert id indexes an untiled dim, the norm-weight trick), the
        # combine accumulator [B, d] f32, and — under overlap_ar — the
        # phase-0 exchange workspace (a2src/a2buf) with its own DMA
        # semaphores (phase 1 reuses the AR workspace, whose slots the
        # layer's attention allreduce has already quiesced).
        self.wrouter: Any = None
        self.moe_w: Any = None
        self.moe_acc: Any = None
        self.a2src: Any = None
        self.a2buf: Any = None
        self.a2send: Any = None
        self.a2recv: Any = None


def make_mega_kernel(
    dims: MegaDims,
    cfg: ResolvedConfig,
    used_types: tuple[TaskType, ...],
    *,
    axis: str,
    wdtype,
    cdtype,
):
    """Build the kernel function dispatching over ``used_types``."""
    kctx = KernelCtx(dims, cfg, axis, wdtype, cdtype)
    # Build one body closure per used type, in enum order.
    bodies = [(int(t), get_body_factory(t)(kctx)) for t in sorted(used_types)]

    def kernel(
        task_tab, kv_len, tokens,                      # scalar prefetch
        *rest,
    ):
        # Paged mode inserts the page table as a 4th scalar-prefetch
        # operand; eos adds the stop-token row after it (scalar-prefetch
        # — SMEM-resident for the LM head's scalar reads); prefill mode
        # inserts the embedded prompt rows x0 before the weights. The
        # operand order is otherwise identical.
        if dims.page:
            page_tab, *rest = rest
        else:
            page_tab = None
        if dims.eos:
            stop_tok, *rest = rest
        else:
            stop_tok = None
        (
            embed, wqkv, wo, w1, w2, lm_head,              # ANY (HBM)
            ln1, ln2, normf, qn, kn,                       # VMEM (small)
            *rest,
        ) = rest
        if dims.moe:  # replicated router weight, after the norms
            wrouter, *rest = rest
        else:
            wrouter = None
        if cfg.wq8:  # per-output-channel dequant scales, after norms
            sc_qkv, sc_o, sc_w1, sc_w2, sc_lm, *rest = rest
        else:
            sc_qkv = sc_o = sc_w1 = sc_w2 = sc_lm = None
        if dims.prefill:  # embedded prompt rows, after the weights
            x0, *rest = rest
        else:
            x0 = None
        if dims.sampled:  # per-step sampling noise, before the cache
            noise, *rest = rest
        else:
            noise = None
        if dims.filtered:  # per-row sampling config, after the noise
            sampcfg, *rest = rest
        else:
            sampcfg = None
        if dims.kv_quant:  # int8 pool: cache block is (kc, vc, ksc, vsc)
            kc, vc, ksc, vsc, *rest = rest
        else:
            kc, vc, *rest = rest
            ksc = vsc = None
        rest = list(rest)
        if dims.eos:
            # Stop-step output rides after the token output (index 4);
            # popping it first keeps the trace pop's index stable.
            stop_out = rest.pop(4)
        else:
            stop_out = None
        if dims.trace:
            # Trace builds append the SMEM ring after the outputs and
            # the logical-clock counter after the scratch; popping them
            # here keeps the canonical unpack below mode-free.
            trace_out = rest.pop(4)
            clk = rest.pop()
        else:
            trace_out = clk = None
        moe_w = moe_acc = a2src = a2buf = a2send = a2recv = None
        if dims.moe:
            # MoE scratch rides after the canonical block (before the
            # trace clock, already popped): combine weights, combine
            # accumulator, and — under overlap_ar — the phase-0
            # exchange workspace + semaphores.
            if cfg.overlap_ar:
                a2recv = rest.pop()
                a2send = rest.pop()
                a2buf = rest.pop()
                a2src = rest.pop()
            moe_acc = rest.pop()
            moe_w = rest.pop()
        (
            logits, knew_out, vnew_out, toks_out,          # outputs
            x, h, qkv, ao, mlp, estage,                    # VMEM state
            colstage, rowstage, kstage, vstage,            # weight/KV staging
            arsrc, cbuf,                                   # AR staging
            tokrow, tok_smem,                              # token feedback
            pre_col, pre_row,                              # prefetch flags
            wsem, esem, osem, ksem, vsem, arsend, arrecv,  # DMA semaphores
            tsem,
        ) = rest
        t = pl.program_id(1)       # task index within the step
        kctx.step = pl.program_id(0)  # decode step within the launch
        kctx.kv_len = kv_len
        kctx.tokens = tokens
        kctx.table = page_tab
        kctx.task_tab = task_tab
        kctx.t = t
        kctx.ksc, kctx.vsc = ksc, vsc
        kctx.x0 = x0
        kctx.noise = noise
        kctx.sampcfg = sampcfg
        kctx.stop_tok, kctx.stop_out = stop_tok, stop_out
        kctx.toks_out = toks_out
        kctx.embed, kctx.wqkv, kctx.wo = embed, wqkv, wo
        kctx.w1, kctx.w2, kctx.lm_head = w1, w2, lm_head
        kctx.sc_qkv, kctx.sc_o, kctx.sc_w1 = sc_qkv, sc_o, sc_w1
        kctx.sc_w2, kctx.sc_lm = sc_w2, sc_lm
        kctx.ln1, kctx.ln2, kctx.normf = ln1, ln2, normf
        kctx.qn, kctx.kn = qn, kn
        kctx.logits, kctx.kc, kctx.vc = logits, kc, vc
        kctx.knew_out, kctx.vnew_out = knew_out, vnew_out
        kctx.x, kctx.h, kctx.qkv, kctx.ao, kctx.mlp = x, h, qkv, ao, mlp
        kctx.estage, kctx.colstage, kctx.rowstage = estage, colstage, rowstage
        kctx.kstage, kctx.vstage = kstage, vstage
        kctx.arsrc, kctx.cbuf = arsrc, cbuf
        kctx.tokrow, kctx.tok_smem = tokrow, tok_smem
        kctx.pre_col, kctx.pre_row = pre_col, pre_row
        kctx.wsem, kctx.esem, kctx.osem = wsem, esem, osem
        kctx.ksem, kctx.vsem = ksem, vsem
        kctx.arsend, kctx.arrecv = arsend, arrecv
        kctx.tsem = tsem
        kctx.trace_out, kctx.clk = trace_out, clk
        kctx.wrouter = wrouter
        kctx.moe_w, kctx.moe_acc = moe_w, moe_acc
        kctx.a2src, kctx.a2buf = a2src, a2buf
        kctx.a2send, kctx.a2recv = a2send, a2recv

        ttype = task_tab[t, 0]
        kctx.layer = task_tab[t, 1]
        kctx.arg0 = task_tab[t, 2]
        kctx.arg1 = task_tab[t, 3]

        if cfg.cross_prefetch:
            @pl.when(jnp.logical_and(kctx.step == 0, t == 0))
            def _init_flags():
                pre_col[0] = 0
                pre_row[0] = 0

        if dims.trace:
            from triton_distributed_tpu.megakernel.kernels import trace_tick

            @pl.when(jnp.logical_and(kctx.step == 0, t == 0))
            def _init_clk():
                clk[0] = 0

            # Record header fields + begin BEFORE dispatch; mid stays 0
            # unless a body stamps a phase mark (the AR bodies do).
            trace_out[kctx.step, t, TR_TASK_ID] = task_tab[t, 4]
            trace_out[kctx.step, t, TR_OPCODE] = ttype
            trace_out[kctx.step, t, TR_LAYER] = kctx.layer
            trace_out[kctx.step, t, TR_SLOT] = kctx.arg0
            trace_out[kctx.step, t, TR_MID] = 0
            trace_out[kctx.step, t, TR_BEGIN] = trace_tick(kctx)

        for value, body in bodies:
            pl.when(ttype == value)(body)

        if cfg.cross_prefetch:
            # Start the NEXT task's first weight-tile DMA now: the
            # scalar core runs ahead of the MXU, so the copy overlaps
            # this task's trailing matmuls and the next stream skips
            # its own tile-0 start (flag consumed there). Copies must
            # BYTE-MATCH the stream's own copy(0) — same refs, widths,
            # and semaphore — guaranteed by sharing fire_next_tile0
            # with the AR_WAIT body. The last task of a step prefetches
            # nothing (the next grid iteration is the next step's
            # EMBED).
            from triton_distributed_tpu.megakernel.kernels import (
                fire_next_tile0,
            )

            waits = [t for t in (TaskType.AR_WAIT, TaskType.A2A_WAIT)
                     if t in used_types]
            if waits:
                # An AR_WAIT/A2A_WAIT task already fired its
                # successor's tile-0 copy BEFORE blocking on the
                # inbound partials (that early start is the whole
                # overlap); firing it again here would double-start the
                # same DMA descriptor and corrupt the semaphore
                # accounting.
                not_wait = ttype != int(waits[0])
                for w in waits[1:]:
                    not_wait = jnp.logical_and(not_wait, ttype != int(w))
                pl.when(not_wait)(lambda: fire_next_tile0(kctx))
            else:
                fire_next_tile0(kctx)

        if dims.trace:
            # End AFTER the cross_prefetch epilogue: the prefetch fire
            # is part of this task's grid iteration, and the decoder's
            # dependency check needs end[producer] <= begin[consumer]
            # to hold for everything the iteration did.
            trace_out[kctx.step, t, TR_END] = trace_tick(kctx)
            trace_out[kctx.step, t, TR_FLAG] = 1

    return kernel


def build_mega_call(
    dims: MegaDims,
    mcfg: MegaConfig,
    tasks: list[Task],
    *,
    axis: str,
    ctx: DistContext,
    wdtype,
    cdtype,
    collective_id: int,
    table: Any,
):
    """Assemble the pallas_call for a scheduled task list.

    Returns ``f(kv_len, tokens, embed, wqkv, wo, w1, w2, lm_head, ln1,
    ln2, normf, qn, kn, kc, vc) → (logits, knew, vnew)`` — a per-shard
    function to run under ``shard_map``; ``knew``/``vnew`` are the new
    token's K/V rows ``[L, B, hkv, hd]`` for the caller to append.
    """
    cfg = mcfg.resolve(dims)
    used = tuple({t.task_type for t in tasks})
    kernel = make_mega_kernel(
        dims, cfg, used, axis=axis, wdtype=wdtype, cdtype=cdtype,
    )
    B, d = dims.batch, dims.d
    n = dims.n_ranks
    hkv, hd = dims.hkv_loc, dims.head_dim

    grid_spec = pltpu.PrefetchScalarGridSpec(
        # task_tab, kv_len, tokens [+ page_table] [+ stop_tok] — all
        # SMEM-resident scalar prefetch.
        num_scalar_prefetch=3 + int(bool(dims.page)) + int(dims.eos),
        # Outer grid dim = decode steps within the launch (1 unless
        # multi-step): one task table serves every step, the kernel
        # reads the step index from program_id(0).
        grid=(dims.nsteps, len(tasks)),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 6
        + [pl.BlockSpec(memory_space=pltpu.VMEM)] * 5
        # MoE router weight [L, d, E]: VMEM-resident like the norms —
        # MOE_GATE reads the traced layer's [d, E] plane per step.
        + ([pl.BlockSpec(memory_space=pltpu.VMEM)] if dims.moe else [])
        # wq8 dequant scales (~2 MB total at 0.6B): VMEM-resident like
        # the norm weights they sit next to.
        + ([pl.BlockSpec(memory_space=pltpu.VMEM)] * 5 if cfg.wq8 else [])
        + ([pl.BlockSpec(memory_space=pltpu.VMEM)] if dims.prefill else [])
        + (
            # Per-step noise block: Mosaic pipelines the [B, v_loc]
            # slab for step s = program_id(0) into VMEM. (Index maps
            # under PrefetchScalarGridSpec also receive the prefetch
            # refs after the grid indices.)
            [pl.BlockSpec(
                (1, B, dims.v_loc), lambda s, t, *prefetch: (s, 0, 0)
            )]
            if dims.sampled else []
        )
        # Filtered-sampling config [B, 4] f32: VMEM-resident like the
        # norms — the LM head reads the per-row columns post-stream.
        + ([pl.BlockSpec(memory_space=pltpu.VMEM)] if dims.filtered else [])
        + [pl.BlockSpec(memory_space=pl.ANY)] * 2
        # int8 pool scales [L, P, 1, Hkv] f32: VMEM-resident like the
        # norm weights — per-(layer, page, head) scalar reads inside
        # the attention block loop (~L·P·H·4 bytes; counted below).
        + ([pl.BlockSpec(memory_space=pltpu.VMEM)] * 2
           if dims.kv_quant else []),
        out_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),  # logits
            pl.BlockSpec(memory_space=pltpu.VMEM),  # new K rows
            pl.BlockSpec(memory_space=pltpu.VMEM),  # new V rows
            pl.BlockSpec(memory_space=pltpu.VMEM),  # greedy tokens
        ]
        # Stop-step output [1, B]: SMEM — per-row scalar stamps from
        # the LM head, read back by the caller's append clamp.
        + ([pl.BlockSpec(memory_space=pltpu.SMEM)] if dims.eos else [])
        # Trace ring: SMEM, because records are scalar stores at
        # dynamic (step, task) indices — natural on the scalar core,
        # while a VMEM row write at a dynamic sublane offset is exactly
        # the unaligned-slice shape Mosaic rejects. ~NS·T·32 bytes.
        + ([pl.BlockSpec(memory_space=pltpu.SMEM)] if dims.trace else []),
        scratch_shapes=(scratch := [
            pltpu.VMEM((B, d), jnp.float32),                   # x
            pltpu.VMEM((B, d), jnp.float32),                   # h
            pltpu.VMEM((B, dims.qkv_loc), jnp.float32),        # qkv
            pltpu.VMEM((B, dims.o_k), jnp.float32),            # ao
            pltpu.VMEM((B, dims.f_loc), jnp.float32),          # mlp
            # estage + KV staging serve the decode-only EMBED/ATTN
            # tasks; prefill shrinks them to placeholders (B = S would
            # otherwise blow VMEM on buffers no task reads).
            pltpu.VMEM(
                (1, 8, d) if dims.prefill else (B, 8, d), wdtype
            ),                                                 # estage
            pltpu.VMEM((cfg.nbuf, d, cfg.tn_max),
                       jnp.int8 if cfg.wq8 else wdtype),       # colstage
            pltpu.VMEM((cfg.nbuf, cfg.tk_max, d),
                       jnp.int8 if cfg.wq8 else wdtype),       # rowstage
            # int8 pools stage their codes as int8 (dequant happens
            # in-register per block) — half the staging VMEM too.
            pltpu.VMEM(
                (1,) * 5 if dims.prefill
                else (2, B, hkv, cfg.s_blk, hd),
                jnp.int8 if dims.kv_quant else cdtype
            ),                                                 # kstage
            pltpu.VMEM(
                (1,) * 5 if dims.prefill
                else (2, B, hkv, cfg.s_blk, hd),
                jnp.int8 if dims.kv_quant else cdtype
            ),                                                 # vstage
            pltpu.VMEM((B, d), jnp.float32),                   # arsrc
            pltpu.VMEM((n, B, d), jnp.float32),                # cbuf
            # Multi-step token feedback: the LM head's in-kernel argmax
            # lands in tokrow (VMEM), is DMA'd to tok_smem (SMEM) so the
            # next step's EMBED can scalar-read it as a DMA index.
            pltpu.VMEM((1, max(B, 1)), jnp.int32),             # tokrow
            pltpu.SMEM((1, max(B, 1)), jnp.int32),             # tok_smem
            pltpu.SMEM((1,), jnp.int32),                       # pre_col
            pltpu.SMEM((1,), jnp.int32),                       # pre_row
            pltpu.SemaphoreType.DMA((cfg.nbuf,)),              # wsem
            pltpu.SemaphoreType.DMA,                           # esem
            pltpu.SemaphoreType.DMA,                           # osem
            pltpu.SemaphoreType.DMA((2,)),                     # ksem
            pltpu.SemaphoreType.DMA((2,)),                     # vsem
            pltpu.SemaphoreType.DMA,                           # arsend
            pltpu.SemaphoreType.DMA((n,)),                     # arrecv
            pltpu.SemaphoreType.DMA,                           # tsem
        ] + (
            # MoE scratch: combine weights ([E, 1, B] f32,
            # expert-leading for traced-index scalar reads) + combine
            # accumulator, and — under overlap_ar — the phase-0
            # exchange workspace (phase 1 reuses arsrc/cbuf).
            [
                pltpu.VMEM((dims.num_experts, 1, max(B, 1)), jnp.float32),
                pltpu.VMEM((B, d), jnp.float32),               # moe_acc
            ] + ([
                pltpu.VMEM((B, d), jnp.float32),               # a2src
                pltpu.VMEM((n, B, d), jnp.float32),            # a2buf
                pltpu.SemaphoreType.DMA,                       # a2send
                pltpu.SemaphoreType.DMA((n,)),                 # a2recv
            ] if cfg.overlap_ar else [])
            if dims.moe else []
        ) + (
            # Logical trace clock (SMEM counter; see kernels.trace_tick).
            [pltpu.SMEM((1,), jnp.int32)] if dims.trace else []
        )),
    )

    # VMEM-resident in_specs are footprint too (ADVICE r4 — the 1.5×
    # headroom alone under-provisioned sampled/large-B configs): norm
    # weights ln1/ln2 [L,1,d] + normf [1,d] + qn/kn [L,1,hd] in wdtype;
    # wq8 dequant scales (f32: sc_qkv [L,1,qkv_loc], sc_o/sc_w2 local
    # [L,1,d], sc_w1 [L,1,2·f_loc], sc_lm [1,v_loc]); the prefill
    # prompt block [S,d]; and the pipelined sampled-noise block
    # [1,B,v_loc] f32, counted twice for double buffering.
    itw = jnp.dtype(wdtype).itemsize
    in_vmem = itw * (2 * dims.num_layers * d + d
                     + 2 * dims.num_layers * dims.head_dim)
    if dims.moe:
        # Replicated router weight [L, d, E], VMEM-resident.
        in_vmem += itw * dims.num_layers * d * dims.num_experts
    if cfg.wq8:
        in_vmem += 4 * (dims.num_layers
                        * (dims.qkv_loc + 2 * d + 2 * dims.f_loc)
                        + dims.v_loc)
    if dims.prefill:
        in_vmem += itw * B * d
    if dims.sampled:
        in_vmem += 2 * 4 * B * dims.v_loc
    if dims.filtered:
        in_vmem += 4 * 4 * max(B, 1)
    if dims.kv_quant:
        # Per-page-per-head f32 scale planes for K and V (num_pages may
        # be 0 = unknown for shape-polymorphic builds; the 1.5× headroom
        # below absorbs small pools, and engine builds pass the count).
        in_vmem += 2 * 4 * dims.num_layers * dims.num_pages * hkv

    # FLOPs/bytes annotation (parity: the reference's launch_metadata on
    # its megakernel): decode is one pass over every weight shard plus
    # the KV context; flops ≈ 2·B·(weight params) per matmul chain.
    L = dims.num_layers
    # MLP weight traffic: dense streams the f_loc shard; MoE streams
    # every LOCAL expert's full-width FFN (plus the replicated router).
    mlp_w = (
        dims.experts_loc * 3 * dims.d * dims.f_loc
        + dims.d * dims.num_experts
        if dims.moe else 3 * dims.d * dims.f_loc
    )
    wparams = L * (
        dims.d * dims.qkv_loc + dims.o_k * dims.d + mlp_w
    ) + dims.d * dims.v_loc
    kv_elems = 2 * L * B * hkv * dims.s_max * hd
    ns = dims.nsteps
    cost = pl.CostEstimate(
        flops=ns * (2 * B * wparams
                    + 4 * B * L * dims.hq_loc * dims.s_max * hd),
        bytes_accessed=ns * (wparams * jnp.dtype(wdtype).itemsize
                             + kv_elems * jnp.dtype(cdtype).itemsize),
        transcendentals=ns * B * L * (dims.hq_loc * dims.s_max + dims.f_loc),
    )

    call = pl.pallas_call(
        kernel,
        name="tdt_megakernel",
        grid_spec=grid_spec,
        cost_estimate=cost,
        # The kernel reads the KV cache but does not write it: appending
        # one row at a dynamic position inside a (8,128)-tiled cache
        # plane is an unaligned slice Mosaic rejects, so new K/V rows
        # come out as [L, B, hkv, hd] and the caller merges them with
        # one XLA dynamic_update_slice (which aliases in place when the
        # cache is donated).
        out_shape=(out_shapes := [
            jax.ShapeDtypeStruct(
                (1 if dims.prefill else B, dims.v_loc), jnp.float32
            ),
            # Prefill: all S rows per head; decode: one row per
            # (step, b, h) — the step dim doubles as the in-launch
            # attention band (later steps read earlier steps' rows).
            jax.ShapeDtypeStruct(
                (dims.num_layers, hkv, B, hd) if dims.prefill
                else (dims.nsteps, dims.num_layers, B, hkv, hd), cdtype
            ),
            jax.ShapeDtypeStruct(
                (dims.num_layers, hkv, B, hd) if dims.prefill
                else (dims.nsteps, dims.num_layers, B, hkv, hd), cdtype
            ),
            # Greedy tokens per step (multi-step; garbage when the LM
            # head runs in single-step mode and the caller ignores it).
            jax.ShapeDtypeStruct((dims.nsteps, 1, max(B, 1)), jnp.int32),
        ] + (
            # Device stop-step per row: first step whose token hit the
            # row's stop token (nsteps = never). SMEM scalar stamps.
            [jax.ShapeDtypeStruct((1, max(B, 1)), jnp.int32)]
            if dims.eos else []
        ) + (
            # Device trace ring: one TRACE_INTS-int record per
            # (step, task) grid iteration — dense by construction, so
            # the decoder's gap-free check is exact (every flag must
            # read 1). ``len(tasks)`` is the scheduled order's length;
            # obs/kernel_trace.py maps rows back through it.
            [jax.ShapeDtypeStruct(
                (dims.nsteps, len(tasks), TRACE_INTS), jnp.int32
            )]
            if dims.trace else []
        )),
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True,
            dimension_semantics=("arbitrary", "arbitrary"),
            collective_id=collective_id,
            allow_collective_id_without_custom_barrier=True,
            # The default 16 MB scoped-VMEM limit is what made wide
            # tiles (tn=2048) fail to compile: staging alone is
            # nbuf·d·tn·2B per stream direction. Derive the limit from
            # the resolved footprint (scratch staging + VMEM-resident
            # outs) with 1.5x headroom for Mosaic's own temporaries, so
            # default configs keep the small default-ish limit and only
            # wide-tile/deep-nbuf configs raise it — capped at 112 MiB
            # to stay under the 128 MiB physical VMEM of the v5e/v5p
            # generations this targets.
            vmem_limit_bytes=_vmem_limit_bytes(
                scratch, out_shapes, in_vmem
            ),
        ),
        interpret=interpret_mode(ctx),
    )

    if dims.page and dims.prefill:
        raise NotImplementedError("paged prefill: prefill then scatter")
    if dims.sampled and dims.prefill:
        raise NotImplementedError("sampled multi-step: decode only")
    if dims.kv_quant and not dims.page:
        raise ValueError("kv_quant requires the paged cache (scales "
                         "live on pool pages)")
    if dims.filtered:
        if not dims.sampled or dims.nsteps <= 1:
            raise ValueError("filtered sampling rides the sampled "
                             "multi-step LM head (sampled, nsteps > 1)")
        if dims.n_ranks > 1:
            raise NotImplementedError(
                "in-kernel top-k/top-p needs the full logit row, which "
                "TP column-shards across ranks — filtered builds are "
                "single-rank; tp>1 sampled-with-filters rounds keep the "
                "single-step fallback"
            )
    if dims.eos and (not dims.page or dims.nsteps <= 1):
        raise ValueError("device stop-token testing rides the paged "
                         "multi-step decode (page > 0, nsteps > 1)")
    if dims.moe:
        if cfg.wq8:
            raise NotImplementedError(
                "wq8 does not compose with MoE decode yet (per-expert "
                "per-channel scale planes)"
            )
        if dims.prefill:
            raise NotImplementedError(
                "MoE prefill runs through the model path "
                "(Engine._prefill_mode is 'xla' under mode='mega')"
            )
        if dims.num_experts % dims.n_ranks:
            raise ValueError(
                f"num_experts {dims.num_experts} not divisible by "
                f"tp={dims.n_ranks} (EP shards the expert axis)"
            )
        if not dims.moe_top_k:
            raise ValueError("MoE dims need moe_top_k > 0")
    # ``wargs`` = the kernel-args block (weights + norms [+ wq8
    # scales]) followed by the cache operands (kc, vc[, ksc, vsc]) —
    # variadic so the wq8/kv_quant paths' extra scale operands flow
    # through without per-mode signature edits. The caller-facing order
    # is ``(kv_len, tokens, [page_table], [stop_tok], [x0], [noise],
    # [sampcfg], *wargs)``; the mode operands are
    # re-sited into the kernel's canonical operand order here (the
    # scalar-prefetch block up front, x0/noise/sampcfg just before the
    # cache block) — ONE wrapper instead of a per-mode branch ladder,
    # so new mode compositions cannot silently miss a re-site.
    nc = 4 if dims.kv_quant else 2  # trailing cache-block operand count
    n_pre = int(bool(dims.page)) + int(dims.eos)
    n_mid = int(dims.prefill) + int(dims.sampled) + int(dims.filtered)

    def run(kv_len, tokens, *args):
        pre, mid, wargs = (
            args[:n_pre], args[n_pre:n_pre + n_mid], args[n_pre + n_mid:]
        )
        return call(
            table, kv_len, tokens, *pre, *wargs[:-nc], *mid, *wargs[-nc:]
        )

    return run
