"""Device-side megakernel task bodies.

Parity: reference ``mega_triton_kernel/kernels/*`` — the per-task device
code (linear 99, flash_attn 232, norm 227, allreduce 65, …) dispatched by
the generated megakernel, plus ``task_context.py``'s ``Scoreboard``
(:107 ``wait_deps``, :126 ``release_tile``).

TPU redesign (SURVEY.md §7 "megakernel scoreboard" hard part): the
sequential Pallas grid discharges intra-chip dependencies by schedule
order, so no scoreboard polling exists; tile-level overlap lives inside
each body as a double-buffered HBM→VMEM weight pipeline (the DMA engines
fetch tile ``j+1`` while the MXU consumes tile ``j``), and the only
cross-chip task (ALLREDUCE) synchronizes with DMA semaphores — dataflow,
not shared-memory spinning. Activations never touch HBM: the residual
stream ``x``, branch input ``h``, qkv, attention output, and MLP
activations all live in VMEM scratch for the whole decode step, which is
the megakernel's fusion win (the reference keeps them in L2/HBM between
task tiles).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_distributed_tpu import language as dl
from triton_distributed_tpu.megakernel.registry import register_task
from triton_distributed_tpu.megakernel.task import TR_MID, TaskType


# -- device task tracer (docs/observability.md "Device task tracer") ---------
#
# The installed Pallas (jax 0.9.0 ``pallas.tpu``) exposes no cycle
# counter, so the tracer's clock is a LOGICAL one — an SMEM counter
# bumped once per read. The Pallas grid is sequential on a TPU core, so
# the clock is monotonic and race-free by construction, and its values
# order events; they are not durations.


def trace_tick(kctx):
    """One monotonic logical-clock read for a trace-ring record."""
    c = kctx.clk[0] + 1
    kctx.clk[0] = c
    return c


def trace_mid(kctx):
    """Stamp the CURRENT task's optional intra-task phase mark (the
    record's ``mid`` field) — the AR bodies call it where their comm
    phase hands off, so the decoder can split issue-time from blocked
    wait. A Python-level no-op when the build is untraced (the traced
    kernel carries zero extra ops with the tracer off)."""
    if getattr(kctx.dims, "trace", False) and kctx.trace_out is not None:
        kctx.trace_out[kctx.step, kctx.t, TR_MID] = trace_tick(kctx)


def _rms(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    """f32 RMS-norm (matches ``models.qwen.rms_norm``)."""
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * w.astype(jnp.float32)


def _headnorm(t: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    """Per-head QK RMS-norm on ``[r, hd]`` rows (shared by the decode
    and prefill attention tasks)."""
    return t * jax.lax.rsqrt(
        jnp.mean(t * t, axis=-1, keepdims=True) + eps
    ) * w.astype(jnp.float32)


def _make_rope(hd: int, theta: float):
    """RoPE over the full lane width as ``rope(t, ang_{cos,sin})``.

    The angle repeats per half and the rotate-half operand is a lane
    roll + sign flip — one tpu.rotate instead of the unaligned hd/2
    lane slices Mosaic can't form. iota (not arange): concrete arrays
    would be captured consts, which pallas_call rejects; integer iota
    only — Mosaic's tpu.iota verifier rejects float result types.

    Returns ``(angle, rope)``: ``angle(p)`` maps positions ``p``
    (broadcastable against ``[·, hd]``) to the per-lane angle, and
    ``rope(t, ang)`` applies the rotation.
    """
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, hd), 1)
    half = jnp.remainder(lane, hd // 2).astype(jnp.float32)
    inv = 1.0 / (theta ** (2.0 * half / hd))  # [1, hd]
    sign = jnp.where(lane < hd // 2, -1.0, 1.0)

    def angle(p):
        return p.astype(jnp.float32) * inv

    def rope(t, ang):
        rot = pltpu.roll(t, hd // 2, 1) * sign
        return t * jnp.cos(ang) + rot * jnp.sin(ang)

    return angle, rope


def col_tile_copy(stage, sem, w_hbm, k, col0, w, slot):
    """The column-stream's tile DMA descriptor — ONE definition shared
    by ``_stream_cols`` and the cross_prefetch block in
    ``code_generator.py``: a prefetched tile-0 must BYTE-MATCH the
    stream's own ``copy(0)`` (same refs/slices/semaphore) or the wait
    accounting breaks, so both build it here."""
    return pltpu.make_async_copy(
        w_hbm.at[:, pl.ds(col0, w)], stage.at[slot, :k, :w], sem.at[slot]
    )


def row_tile_copy(stage, sem, w_hbm, row0, tk, d, slot):
    """Row-stream analog of :func:`col_tile_copy` (same sharing
    contract)."""
    return pltpu.make_async_copy(
        w_hbm.at[pl.ds(row0, tk), :], stage.at[slot, :tk, :d], sem.at[slot]
    )


# The ONE per-task-type table of tile-0 prefetch descriptors, kept next
# to the task bodies whose streams must match them (each entry mirrors
# its body's ``_stream_cols``/``_stream_rows`` call: same weight ref,
# same tile width, k == dims.d, col0/row0 == 0 — the streams assert
# those invariants when consuming the prefetch flag). The cross_prefetch
# block in ``code_generator.py`` builds its dispatch from this table.
# Entries take ``(nl, na0)`` — the NEXT task's layer id and arg0 (the
# local expert id for MOE_FFN; ignored by the dense entries). MoE
# builds swap the dense FC1/FC2 entries for MOE_FFN: their w1/w2
# operands are per-expert stacks there, and a dense-shaped descriptor
# would not even trace.
def stream_tile0_table(kctx):
    d = kctx.dims.d
    cfg = kctx.cfg
    col, row = [], []
    col.append((TaskType.QKV_PROJ, lambda nl, na0: col_tile_copy(
        kctx.colstage, kctx.wsem, kctx.wqkv.at[nl], d, 0, cfg.tn_qkv, 0)))
    if kctx.dims.moe:
        col.append((TaskType.MOE_FFN, lambda nl, na0: col_tile_copy(
            kctx.colstage, kctx.wsem, kctx.w1.at[nl, na0], d, 0,
            cfg.tn_fc1, 0)))
    else:
        col.append((TaskType.FC1, lambda nl, na0: col_tile_copy(
            kctx.colstage, kctx.wsem, kctx.w1.at[nl], d, 0, cfg.tn_fc1, 0)))
        row.append((TaskType.FC2, lambda nl, na0: row_tile_copy(
            kctx.rowstage, kctx.wsem, kctx.w2.at[nl], 0, cfg.tk_fc2, d, 0)))
    col.append((TaskType.LM_HEAD, lambda nl, na0: col_tile_copy(
        kctx.colstage, kctx.wsem, kctx.lm_head, d, 0, cfg.tn_lm, 0)))
    row.append((TaskType.O_PROJ, lambda nl, na0: row_tile_copy(
        kctx.rowstage, kctx.wsem, kctx.wo.at[nl], 0, cfg.tk_o, d, 0)))
    return col, row


def fire_next_tile0(kctx):
    """Start the NEXT task's first weight-tile DMA and set the
    cross_prefetch handshake flag — THE one implementation of the
    prefetch fire, shared by the generated per-task epilogue
    (``code_generator.py``) and the AR_WAIT/A2A_WAIT bodies (which fire
    it BEFORE blocking on the inbound partials, so the ICI hop hides
    under the next weight stream's tile-0 HBM traffic). Both sites must
    byte-match the stream's own ``copy(0)``; sharing the fire keeps
    that a structural guarantee."""
    T = pl.num_programs(1)
    t = kctx.t

    @pl.when(t + 1 < T)
    def _fire():
        nt = kctx.task_tab[t + 1, 0]
        nl = kctx.task_tab[t + 1, 1]
        na0 = kctx.task_tab[t + 1, 2]
        col_tab, row_tab = stream_tile0_table(kctx)

        for tt, make in col_tab:
            def fire(make=make):
                make(nl, na0).start()
                kctx.pre_col[0] = 1

            pl.when(nt == int(tt))(fire)
        for tt, make in row_tab:
            def fire(make=make):
                make(nl, na0).start()
                kctx.pre_row[0] = 1

            pl.when(nt == int(tt))(fire)


def _stream_cols(kctx, x_f32, w_hbm, n: int, tn: int, consume,
                 col0: int = 0, tail: int = 0, carry=None):
    """Column-streamed GEMM: ``x [B, K] @ w_hbm [K, col0:col0+n*tn]``
    tile-by-tile, plus an optional ``tail``-wide final tile when ``tn``
    doesn't divide the column count (the LM head's vocab axis).

    Depth-``nbuf`` pipelined: up to ``nbuf - 1`` tile DMAs stay in
    flight ahead of the consuming matmul (parity role: the reference
    linear task's tile pipeline,
    ``mega_triton_kernel/kernels/linear.py``); the tail tile joins the
    same pipeline. The weight stream is the decode step's HBM floor —
    per-tile control overhead is comparable to a 2 MB tile's wire time,
    so one-deep prefetch leaves the HBM controller idle between tiles.
    ``consume(j, val)`` sinks each f32 product — ``val.shape[1]`` is
    ``tn`` for main tiles and ``tail`` for the final one. With
    ``carry`` set, ``consume(j, val, carry) -> carry`` threads loop
    state through the tiles (the LM head's running argmax) and the
    final carry is returned.
    """
    stage, sem = kctx.colstage, kctx.wsem
    depth = stage.shape[0]
    k = x_f32.shape[1]
    xa = x_f32.astype(kctx.wdtype)
    stateful = carry is not None
    total = n + (1 if tail else 0)  # tile index n = the tail tile

    def copy(j, slot, w=None):
        w = tn if w is None else w
        return col_tile_copy(stage, sem, w_hbm, k, col0 + j * tn, w, slot)

    def start(j):
        return copy(j, j % depth, tail if j == n else None)

    # Prologue: fill the pipeline (static — n, tail, depth are Python
    # ints here). Under cross_prefetch, tile 0 may already be in flight
    # (started by the previous task's prefetch block with an identical
    # descriptor) — consume the flag and skip the duplicate start.
    if kctx.cfg.cross_prefetch:
        # Prefetched tile-0 descriptors (stream_tile0_table) assume
        # k == d, col0 == 0, and a full-width first tile (n >= 1 — a
        # tail-only stream's copy(0) would be tail-width and break the
        # byte match); fail at trace time instead of corrupting. A
        # hard raise (not assert): under ``python -O`` an assert would
        # vanish and the mismatch would become a silent DMA-descriptor
        # mismatch at run time.
        if not (col0 == 0 and k == kctx.dims.d and n >= 1):
            raise ValueError(
                "cross_prefetch byte-match invariant violated: need "
                f"col0 == 0, k == d ({kctx.dims.d}), n >= 1; got "
                f"col0={col0}, k={k}, n={n}"
            )
        pre = kctx.pre_col[0]
        kctx.pre_col[0] = 0
    for j in range(min(depth - 1, total)):
        if j == 0 and kctx.cfg.cross_prefetch:
            pl.when(pre == 0)(lambda: start(0).start())
        else:
            start(j).start()

    def wtile(slot, w):
        wt = stage[slot, :k, :w]
        # wq8: int8 staging tiles upcast at the MXU's doorstep (VPU op
        # pipelined under the next tile's DMA); scales apply in the
        # sinks, per output column.
        return wt.astype(xa.dtype) if wt.dtype == jnp.int8 else wt

    def body(j, c):
        slot = jax.lax.rem(j, depth)
        p = j + depth - 1  # tile to prefetch, keeping depth-1 in flight

        @pl.when(p < n)
        def _prefetch():
            copy(p, jax.lax.rem(p, depth)).start()

        if tail:
            @pl.when(p == n)
            def _prefetch_tail():
                copy(n, jax.lax.rem(p, depth), tail).start()

        copy(j, slot).wait()
        val = jnp.dot(
            xa, wtile(slot, tn), preferred_element_type=jnp.float32
        )
        if stateful:
            return consume(j, val, c)
        consume(j, val)
        return c

    carry = jax.lax.fori_loop(
        0, n, body, carry if stateful else 0, unroll=False
    )

    if tail:
        slot = n % depth
        if depth == 1:
            # Serial mode starts each tile at its own iteration; the
            # tail has no iteration of its own — start it here.
            copy(n, slot, tail).start()
        copy(n, slot, tail).wait()
        val = jnp.dot(
            xa, wtile(slot, tail), preferred_element_type=jnp.float32
        )
        if stateful:
            carry = consume(n, val, carry)
        else:
            consume(n, val)
    return carry


def _stream_rows(kctx, x_ref, w_hbm, out_ref, n: int, tk: int,
                 scale_row=None, col_scale=None, accumulate=False):
    """Row-streamed GEMM with accumulation: ``out += x [B, K] @ w [K, d]``
    streaming K tiles (o-proj / fc2 shape class). Overwrites ``out_ref``
    unless ``accumulate`` (the MoE expert loop folds every expert's
    weighted output into the same combine accumulator).

    ``x_ref`` must be a (VMEM) ref: the K tile is sliced per step with a
    dynamic ``pl.ds`` on the ref — Mosaic has no lowering for
    ``dynamic_slice`` on register values, only for ref loads.

    ``scale_row`` (wq8): a ``[1, d]`` f32 per-output-channel dequant
    row applied to every tile product — per-column constants distribute
    over the K-tile sum, so per-tile application is exact.

    ``col_scale``: a ``[B, 1]`` f32 per-BATCH-row scale (the MoE
    combine weight: gate probability of this expert per token, 0 for
    unrouted tokens) — per-row constants likewise distribute over the
    K-tile sum.
    """
    stage, sem = kctx.rowstage, kctx.wsem
    depth = stage.shape[0]
    d = out_ref.shape[-1]

    def copy(j, slot):
        return row_tile_copy(stage, sem, w_hbm, j * tk, tk, d, slot)

    # Pipeline fill; under cross_prefetch tile 0 may already be in
    # flight from the previous task's prefetch block (same descriptor).
    if kctx.cfg.cross_prefetch:
        # stream_tile0_table's byte-match assumption; raise (not
        # assert) so the guard survives ``python -O``.
        if d != kctx.dims.d:
            raise ValueError(
                "cross_prefetch byte-match invariant violated: row "
                f"stream width d={d} != model d={kctx.dims.d}"
            )
        pre = kctx.pre_row[0]
        kctx.pre_row[0] = 0
    for j in range(min(depth - 1, n)):
        if j == 0 and kctx.cfg.cross_prefetch:
            pl.when(pre == 0)(lambda: copy(0, 0).start())
        else:
            copy(j, j % depth).start()
    if not accumulate:
        out_ref[...] = jnp.zeros_like(out_ref)

    def body(j, carry):
        slot = jax.lax.rem(j, depth)
        p = j + depth - 1  # keep depth-1 tiles in flight

        @pl.when(p < n)
        def _prefetch():
            copy(p, jax.lax.rem(p, depth)).start()

        copy(j, slot).wait()
        wt = stage[slot, :tk, :d]
        if wt.dtype == jnp.int8:
            wt = wt.astype(kctx.wdtype)
        val = jnp.dot(
            x_ref[:, pl.ds(j * tk, tk)].astype(kctx.wdtype),
            wt,
            preferred_element_type=jnp.float32,
        )
        if scale_row is not None:
            val = val * scale_row
        if col_scale is not None:
            val = val * col_scale
        out_ref[...] = out_ref[...] + val
        return carry

    jax.lax.fori_loop(0, n, body, 0, unroll=False)


def _barrier(kctx):
    """Cross-rank barrier over the kernel's mesh axis."""
    dl.barrier_all(kctx.axis)


def _ar_put_dmas(kctx):
    """The allreduce-workspace put descriptors (this rank's ``arsrc``
    into every peer's ``cbuf[me]`` slot) — ONE definition, because the
    split allreduce starts them in AR_SEND and send-waits them in
    AR_WAIT (a later grid iteration): reconstructed descriptors must
    byte-match or the semaphore accounting breaks (the col_tile_copy
    sharing contract, applied to remote copies)."""
    axis = kctx.axis
    nr = kctx.dims.n_ranks
    me = jax.lax.axis_index(axis)

    def put(p):
        dst = jax.lax.rem(me + p, nr)
        return pltpu.make_async_remote_copy(
            src_ref=kctx.arsrc,
            dst_ref=kctx.cbuf.at[me],
            send_sem=kctx.arsend,
            recv_sem=kctx.arrecv.at[me],
            device_id={axis: dst},
            device_id_type=pltpu.DeviceIdType.MESH,
        )

    return [put(p) for p in range(1, nr)]


def _ar_wait_recvs(kctx):
    """Wait every peer's inbound partial (the receive half of
    :func:`_ar_put_dmas`); afterwards all ``nr`` candidate slots of
    ``cbuf`` are valid."""
    nr = kctx.dims.n_ranks
    me = jax.lax.axis_index(kctx.axis)
    for p in range(1, nr):
        src = jax.lax.rem(me + p, nr)
        pltpu.make_async_copy(
            kctx.cbuf.at[src], kctx.arsrc, kctx.arrecv.at[src]
        ).wait()


def _a2a_put_dmas(kctx):
    """Phase-0 analog of :func:`_ar_put_dmas` over the dedicated MoE
    combine workspace (``a2src``/``a2buf``/``a2send``/``a2recv``): a
    separate buffer pair because phase 0's puts are still in flight
    while the second half of the expert GEMMs overwrites the combine
    accumulator — phase 1 then reuses the standard AR workspace, which
    the layer's attention allreduce has already quiesced. Same
    descriptor-sharing contract as ``_ar_put_dmas`` (A2A_SEND starts
    these, A2A_WAIT send-waits byte-matched reconstructions)."""
    axis = kctx.axis
    nr = kctx.dims.n_ranks
    me = jax.lax.axis_index(axis)

    def put(p):
        dst = jax.lax.rem(me + p, nr)
        return pltpu.make_async_remote_copy(
            src_ref=kctx.a2src,
            dst_ref=kctx.a2buf.at[me],
            send_sem=kctx.a2send,
            recv_sem=kctx.a2recv.at[me],
            device_id={axis: dst},
            device_id_type=pltpu.DeviceIdType.MESH,
        )

    return [put(p) for p in range(1, nr)]


def _a2a_wait_recvs(kctx):
    """Wait every peer's inbound phase-0 combine partial (the receive
    half of :func:`_a2a_put_dmas`)."""
    nr = kctx.dims.n_ranks
    me = jax.lax.axis_index(kctx.axis)
    for p in range(1, nr):
        src = jax.lax.rem(me + p, nr)
        pltpu.make_async_copy(
            kctx.a2buf.at[src], kctx.a2src, kctx.a2recv.at[src]
        ).wait()


def _workspace_bcast(kctx, payload):
    """One-shot broadcast through the allreduce workspace: every rank
    writes ``payload`` ([B, d] f32) to peer slot ``cbuf[me]`` and waits
    for all ``nr`` candidates to land. Returns nothing — read
    ``kctx.cbuf[r]`` afterwards. The caller owns quiescence: traffic
    into cbuf must be fenced (barrier) before the slots are reused.

    Shared by the ALLREDUCE task and the LM head's cross-rank argmax;
    the split AR_SEND/AR_WAIT pair is this same exchange pulled apart
    so independent work can run between the two halves.
    """
    me = jax.lax.axis_index(kctx.axis)
    kctx.arsrc[...] = payload
    kctx.cbuf[me] = payload

    puts = _ar_put_dmas(kctx)
    for dma in puts:
        dma.start()
    _ar_wait_recvs(kctx)
    for dma in puts:
        dma.wait_send()


# -- task bodies -------------------------------------------------------------

@register_task(TaskType.EMBED)
def embed_body(kctx):
    """Token embedding lookup.

    The table arrives as ``[V/8, 8, d]`` (see ``MegaQwen3.build``): a
    single-row slice of the ``[V, d]`` HBM table breaks Mosaic's (8,128)
    tiling (bf16 packs row pairs), so the DMA fetches the aligned 8-row
    group and a one-hot ``[1, 8] @ [8, d]`` matmul selects the row — a
    dynamic sublane extract Mosaic can't otherwise prove aligned.
    """

    def body():
        B = kctx.dims.batch

        def tok(b):
            # Multi-step: steps after the first read the token the LM
            # head's in-kernel argmax fed back through SMEM.
            t = kctx.tokens[b]
            if kctx.dims.nsteps > 1:
                t = jnp.where(kctx.step == 0, t, kctx.tok_smem[0, b])
            return t

        toks = [tok(b) for b in range(B)]

        def group(b):
            return pltpu.make_async_copy(
                kctx.embed.at[toks[b] // 8], kctx.estage.at[b],
                kctx.esem,
            )

        for b in range(B):
            group(b).start()
        for b in range(B):
            group(b).wait()
        sub = jax.lax.broadcasted_iota(jnp.int32, (1, 8), 1)
        for b in range(B):
            onehot = (sub == toks[b] % 8).astype(jnp.float32)
            kctx.x[b:b + 1, :] = jnp.dot(
                onehot, kctx.estage[b].astype(jnp.float32),
                preferred_element_type=jnp.float32,
            )

    return body


def _q8_scale(kctx, sref, layer, col0, val):
    """Apply the per-output-channel dequant scale slice to a tile
    product (``wq8`` only; identity otherwise). ``col0`` is the tile's
    first output column (traced ``j * tn`` is fine — tn is a
    128-multiple, so the lane slice is provably aligned); ``layer`` is
    the traced layer id for per-layer scale planes, None for the LM
    head's single plane."""
    if not kctx.cfg.wq8:
        return val
    w = val.shape[1]
    sl = pl.ds(col0, w)
    s = sref[:, sl] if layer is None else sref[layer, :, sl]
    return val * s


def _normed_input(kctx, which: int):
    """The consumer's [B, d] f32 input: the NORM task's output (``h``)
    normally, or — with ``fuse_norms`` — the norm computed inline from
    the residual ``x`` (which: 0 = ln1/qkv, 1 = ln2/fc1, 2 = final/lm).
    The inline norm is a [B, d] vector op — negligible next to the
    task boundary it replaces."""
    if not kctx.cfg.fuse_norms:
        return kctx.h[...]
    eps = kctx.dims.rms_eps
    xv = kctx.x[...]
    if which == 0:
        return _rms(xv, kctx.ln1[kctx.layer], eps)
    if which == 1:
        return _rms(xv, kctx.ln2[kctx.layer], eps)
    return _rms(xv, kctx.normf[...], eps)


@register_task(TaskType.NORM)
def norm_body(kctx):
    def body():
        eps = kctx.dims.rms_eps
        xv = kctx.x[...]
        # Weights arrive as [L, 1, d] (see MegaQwen3.build): indexing
        # the untiled leading dim with the traced layer id yields a
        # [1, d] vector — a dynamic sublane slice of [L, d] would need
        # an 8-aligned index Mosaic can't prove.
        layer = kctx.layer

        @pl.when(kctx.arg0 == 0)
        def _ln1():
            kctx.h[...] = _rms(xv, kctx.ln1[layer], eps)

        @pl.when(kctx.arg0 == 1)
        def _ln2():
            kctx.h[...] = _rms(xv, kctx.ln2[layer], eps)

        @pl.when(kctx.arg0 == 2)
        def _final():
            kctx.h[...] = _rms(xv, kctx.normf[...], eps)

    return body


@register_task(TaskType.QKV_PROJ)
def qkv_body(kctx):
    def body():
        dims = kctx.dims
        tn = kctx.cfg.tn_qkv
        n = dims.qkv_loc // tn

        def sink(j, val):
            val = _q8_scale(kctx, kctx.sc_qkv, kctx.layer, j * tn, val)
            kctx.qkv[:, pl.ds(j * tn, tn)] = val

        _stream_cols(
            kctx, _normed_input(kctx, 0), kctx.wqkv.at[kctx.layer],
            n, tn, sink,
        )

    return body


@register_task(TaskType.ATTN)
def attn_body(kctx):
    """RoPE + QK-norm + cache append + GQA flash-decode (online softmax
    over double-buffered KV blocks). Parity: reference attn task
    (``mega_triton_kernel/kernels/flash_attn.py``) + paged-KV append."""

    def body():
        dims = kctx.dims
        B, hq, hkv, hd = dims.batch, dims.hq_loc, dims.hkv_loc, dims.head_dim
        g = hq // hkv
        eps, theta = dims.rms_eps, dims.rope_theta
        layer = kctx.layer
        # cache_len masks the cached rows (the cache never holds this
        # launch's rows); pos is the CURRENT token's position — in
        # multi-step launches it advances with the in-launch step
        # (program_id(0), constant 0 in single-step builds).
        cache_len = [kctx.kv_len[b] for b in range(B)]
        pos = [cache_len[b] + kctx.step for b in range(B)]

        # Mosaic has no lane-splitting shape casts ([B, h·hd] → [B, h,
        # hd] is rejected by infer-vector-layout), so heads stay 2-D
        # throughout: per (batch, kv-head) the q group is assembled from
        # [1, hd] lane slices of the qkv vector (offsets are multiples
        # of hd = 128 on real configs) and all attention math runs on
        # [g, ·] tiles.
        qkv = kctx.qkv[...]  # [B, (hq + 2 hkv) hd] f32
        qn = kctx.qn[layer]  # [L, 1, hd] ref → [1, hd]
        kn = kctx.kn[layer]
        angle, rope_fn = _make_rope(hd, theta)

        def headnorm(t, w):
            return _headnorm(t, w, eps)

        def rope(t, p):  # t [r, hd], p scalar position
            return rope_fn(t, angle(p))

        def head(i):  # q head i as [1, hd] rows per batch
            return [
                qkv[b:b + 1, i * hd:(i + 1) * hd] for b in range(B)
            ]

        scale = hd ** -0.5
        # q groups: qg[b][h] = [g, hd], normed + roped + prescaled.
        qg = [
            [
                rope(
                    headnorm(
                        jnp.concatenate(
                            [head(h * g + i)[b] for i in range(g)], axis=0
                        ),
                        qn,
                    ),
                    pos[b],
                ) * scale
                for h in range(hkv)
            ]
            for b in range(B)
        ]

        # New K (normed + roped) and V per (b, kv-head). The cache is
        # NOT written here — appending one row at a dynamic position in
        # a (8,128)-tiled plane is an unaligned slice Mosaic rejects —
        # so the rows go to the knew/vnew outputs (caller appends via
        # XLA dynamic_update_slice) and the new token's own attention
        # contribution is merged analytically after the block loop.
        knew_v: list[list] = []
        vnew_v: list[list] = []
        for b in range(B):
            krow, vrow = [], []
            for h in range(hkv):
                kbh = rope(headnorm(head(hq + h)[b], kn), pos[b])
                vbh = head(hq + hkv + h)[b]
                kctx.knew_out[kctx.step, layer, b, h:h + 1, :] = (
                    kbh.astype(kctx.cdtype)
                )
                kctx.vnew_out[kctx.step, layer, b, h:h + 1, :] = (
                    vbh.astype(kctx.cdtype)
                )
                krow.append(kbh)
                vrow.append(vbh)
            knew_v.append(krow)
            vnew_v.append(vrow)

        # Online-softmax decode over KV blocks, double-buffered. The
        # block loop is bounded by the furthest live position, not
        # s_max — per-step cost is O(kv_len), the fori upper bound is
        # traced (parity role: the reference's split-KV sizing by
        # actual seq len, ``flash_decode.py:130``).
        sblk = kctx.cfg.s_blk
        maxpos = cache_len[0]
        for b in range(1, B):
            maxpos = jnp.maximum(maxpos, cache_len[b])
        nblk = maxpos // sblk + 1  # blocks overlapping [0, maxpos]

        # Dense: one DMA per buffer covering all (b, h) for the block.
        # Paged (kctx.table set): block j of row b is pool page
        # table[b, j] — one [hkv, page, hd] DMA per batch row, with
        # s_blk == page_size (enforced by MegaQwen3.build).
        def kv_dmas(j, slot):
            if kctx.table is None:
                return [
                    pltpu.make_async_copy(
                        kctx.kc.at[layer, :, :, pl.ds(j * sblk, sblk), :],
                        kctx.kstage.at[slot], kctx.ksem.at[slot],
                    ),
                    pltpu.make_async_copy(
                        kctx.vc.at[layer, :, :, pl.ds(j * sblk, sblk), :],
                        kctx.vstage.at[slot], kctx.vsem.at[slot],
                    ),
                ]
            dmas = []
            for b in range(B):
                pid = kctx.table[b, j]
                dmas.append(pltpu.make_async_copy(
                    kctx.kc.at[layer, pid],
                    kctx.kstage.at[slot, b], kctx.ksem.at[slot],
                ))
                dmas.append(pltpu.make_async_copy(
                    kctx.vc.at[layer, pid],
                    kctx.vstage.at[slot, b], kctx.vsem.at[slot],
                ))
            return dmas

        def kv_start(j, slot):
            for dma in kv_dmas(j, slot):
                dma.start()

        def kv_wait(j, slot):
            for dma in kv_dmas(j, slot):
                dma.wait()

        kv_start(0, 0)

        neg = jnp.float32(-1e30)
        nt = (((1,), (1,)), ((), ()))  # q [g, hd] · k [sblk, hd]ᵀ
        init = tuple(
            (
                jnp.full((g, 1), neg, jnp.float32),
                jnp.zeros((g, 1), jnp.float32),
                jnp.zeros((g, hd), jnp.float32),
            )
            for _ in range(B * hkv)
        )

        def blk(j, carry):
            slot = jax.lax.rem(j, 2)

            @pl.when(j + 1 < nblk)
            def _prefetch():
                kv_start(j + 1, 1 - slot)

            kv_wait(j, slot)
            idx = j * sblk + jax.lax.broadcasted_iota(jnp.int32, (1, sblk), 1)

            out = []
            for b in range(B):
                valid = idx < cache_len[b]  # [1, sblk] — cached tokens only
                for h in range(hkv):
                    m, l, acc = carry[b * hkv + h]
                    kb = kctx.kstage[slot, b, h].astype(jnp.float32)
                    vb = kctx.vstage[slot, b, h].astype(jnp.float32)
                    if dims.kv_quant:
                        # int8 pool: dequantize the staged page block
                        # in-register under its (layer, page, head)
                        # scale — scalar reads off the VMEM-resident
                        # [L, P, 1, Hkv] planes ([L, P, 1, H] keeps the
                        # dynamic layer/page indices on untiled leading
                        # dims, the norm-weight trick). Full-width KV
                        # never exists in HBM.
                        pid = kctx.table[b, j]
                        kb = kb * kctx.ksc[layer, pid, 0, h]
                        vb = vb * kctx.vsc[layer, pid, 0, h]
                    s = jax.lax.dot_general(
                        qg[b][h], kb, nt,
                        preferred_element_type=jnp.float32,
                    )  # [g, sblk]
                    s = jnp.where(valid, s, neg)
                    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
                    # Re-mask p: with every position masked (pos lands
                    # on a block boundary) exp(neg - neg) would be 1.
                    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
                    corr = jnp.exp(m - m_new)
                    l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
                    acc = acc * corr + jnp.dot(
                        p, vb, preferred_element_type=jnp.float32
                    )
                    out.append((m_new, l, acc))
            return tuple(out)

        final = jax.lax.fori_loop(0, nblk, blk, init, unroll=False)

        # Multi-step band: this launch's earlier steps' K/V rows live in
        # the knew/vnew outputs (never in the cache) — merge them into
        # the online softmax. Rows at steps >= kctx.step are unwritten
        # (arbitrary bits): the column mask drops their scores and the
        # row mask zeroes their V so no garbage can reach the output.
        NS = dims.nsteps
        if NS > 1:
            merged = []
            bcol = jax.lax.broadcasted_iota(jnp.int32, (1, NS), 1)
            brow = jax.lax.broadcasted_iota(jnp.int32, (NS, 1), 0)
            col_ok = bcol < kctx.step
            row_ok = brow < kctx.step
            for b in range(B):
                for h in range(hkv):
                    m, l, acc = final[b * hkv + h]
                    kband = jnp.concatenate(
                        [
                            kctx.knew_out[s2, layer, b, h:h + 1, :]
                            .astype(jnp.float32)
                            for s2 in range(NS)
                        ],
                        axis=0,
                    )  # [NS, hd]
                    vband = jnp.concatenate(
                        [
                            kctx.vnew_out[s2, layer, b, h:h + 1, :]
                            .astype(jnp.float32)
                            for s2 in range(NS)
                        ],
                        axis=0,
                    )
                    vband = jnp.where(row_ok, vband, 0.0)
                    s_band = jax.lax.dot_general(
                        qg[b][h], kband, nt,
                        preferred_element_type=jnp.float32,
                    )  # [g, NS]
                    s_band = jnp.where(col_ok, s_band, neg)
                    m_new = jnp.maximum(
                        m, jnp.max(s_band, axis=-1, keepdims=True)
                    )
                    p = jnp.where(col_ok, jnp.exp(s_band - m_new), 0.0)
                    corr = jnp.exp(m - m_new)
                    l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
                    acc = acc * corr + jnp.dot(
                        p, vband, preferred_element_type=jnp.float32
                    )
                    merged.append((m_new, l, acc))
            final = tuple(merged)

        # Merge the new token's own K/V contribution (it never entered
        # the cache) and write the normalized output.
        for b in range(B):
            for h in range(hkv):
                m, l, acc = final[b * hkv + h]
                s_self = jax.lax.dot_general(
                    qg[b][h], knew_v[b][h], nt,
                    preferred_element_type=jnp.float32,
                )  # [g, 1]
                m_f = jnp.maximum(m, s_self)
                corr = jnp.exp(m - m_f)
                p_self = jnp.exp(s_self - m_f)
                l = l * corr + p_self
                # Outer product as a K=1 matmul: the [g,1]×[1,hd]
                # vector.broadcast path trips Mosaic's layout inference
                # on the sliced vnew row.
                pv_self = jnp.dot(
                    p_self, vnew_v[b][h], preferred_element_type=jnp.float32
                )
                o = (acc * corr + pv_self) / l  # [g, hd]
                for i in range(g):
                    col = (h * g + i) * hd
                    kctx.ao[b:b + 1, col:col + hd] = o[i:i + 1]

    return body


@register_task(TaskType.LOAD_X)
def load_x_body(kctx):
    """Prefill entry: the embedded prompt rows arrive as a kernel input
    (XLA does the S-row gather — an in-kernel per-row embed DMA would
    need S unrolled dynamic-sublane stores Mosaic can't prove aligned)."""

    def body():
        kctx.x[...] = kctx.x0[...].astype(jnp.float32)

    return body


@register_task(TaskType.ATTN_PREFILL)
def attn_prefill_body(kctx):
    """Causal self-attention over the S prompt rows in the qkv scratch.

    Parity: the reference megakernel's prefill attention tasks
    (``mega_triton_kernel/models/model_builder.py:189-352``). The whole
    [S, S] score tile fits VMEM at prompt scale, so no KV streaming —
    per (kv-head, q-head) everything is 2-D: lane slices of qkv, the
    roll-based RoPE from the decode task applied with per-row
    positions, one masked softmax, and [S, hd] writes of K/V to the
    ``knew``/``vnew`` outputs (the caller scatters them into the cache,
    same contract as decode).
    """

    def body():
        dims = kctx.dims
        S = dims.batch  # prefill: rows are the prompt positions
        hq, hkv, hd = dims.hq_loc, dims.hkv_loc, dims.head_dim
        g = hq // hkv
        eps, theta = dims.rms_eps, dims.rope_theta
        layer = kctx.layer

        qkv = kctx.qkv[...]  # [S, (hq + 2 hkv) hd] f32
        qn = kctx.qn[layer]  # [1, hd]
        kn = kctx.kn[layer]
        angle, rope_fn = _make_rope(hd, theta)
        pos = jax.lax.broadcasted_iota(jnp.int32, (S, 1), 0)
        ang = angle(pos)  # [S, hd] — row r rotated by position r

        def headnorm(t, w):
            return _headnorm(t, w, eps)

        def rope(t):  # [S, hd]
            return rope_fn(t, ang)

        def head(i):  # [S, hd]
            return qkv[:, i * hd:(i + 1) * hd]

        rows = jax.lax.broadcasted_iota(jnp.int32, (S, S), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (S, S), 1)
        causal = cols <= rows
        neg = jnp.float32(-1e30)
        scale = hd ** -0.5
        nt = (((1,), (1,)), ((), ()))

        for h in range(hkv):
            kh = rope(headnorm(head(hq + h), kn))       # [S, hd]
            vh = head(hq + hkv + h)
            kctx.knew_out[layer, h] = kh.astype(kctx.cdtype)
            kctx.vnew_out[layer, h] = vh.astype(kctx.cdtype)
            for i in range(g):
                qi = rope(headnorm(head(h * g + i), qn)) * scale
                s = jax.lax.dot_general(
                    qi, kh, nt, preferred_element_type=jnp.float32
                )  # [S, S]
                s = jnp.where(causal, s, neg)
                m = jnp.max(s, axis=-1, keepdims=True)
                p = jnp.exp(s - m)
                l = jnp.sum(p, axis=-1, keepdims=True)
                o = jnp.dot(
                    p, vh, preferred_element_type=jnp.float32
                ) / l  # [S, hd]
                col = (h * g + i) * hd
                kctx.ao[:, col:col + hd] = o

    return body


@register_task(TaskType.O_PROJ)
def o_proj_body(kctx):
    def body():
        dims = kctx.dims
        tk = kctx.cfg.tk_o
        n = (dims.hq_loc * dims.head_dim) // tk
        scale = kctx.sc_o[kctx.layer] if kctx.cfg.wq8 else None
        _stream_rows(
            kctx, kctx.ao, kctx.wo.at[kctx.layer], kctx.h, n, tk,
            scale_row=scale,
        )

    return body


@register_task(TaskType.FC1)
def fc1_body(kctx):
    """One continuous column stream over the fused ``[d, gate | up]``
    shard layout (``models.qwen._fuse_by_shard``): tiles ``j < n`` are
    gate columns (silu into ``mlp``), tiles ``j >= n`` the matching up
    columns (multiply in place) — silu·mul fused into the sinks, the
    reference's separate activation/elementwise tasks
    (``tasks/activation.py``) fold into this body on TPU."""

    def body():
        dims = kctx.dims
        tn = kctx.cfg.tn_fc1
        n = dims.f_loc // tn
        h = _normed_input(kctx, 1)
        w1 = kctx.w1.at[kctx.layer]

        # ONE continuous stream over the fused [d, gate|up] plane —
        # tiles j < n are gate columns, j >= n the matching up columns
        # (the shard layout guarantees the offset is exactly f_loc).
        # One pipeline fill instead of two per layer, and the depth-nbuf
        # rotation never drains between the passes.
        def sink(j, val):
            # wq8 dequant BEFORE the nonlinearity (val*s is the true
            # product); sc_w1 shares w1's [1, gate|up] column layout so
            # j*tn indexes both regions directly.
            val = _q8_scale(kctx, kctx.sc_w1, kctx.layer, j * tn, val)

            @pl.when(j < n)
            def _gate():
                kctx.mlp[:, pl.ds(j * tn, tn)] = val * jax.lax.logistic(val)

            @pl.when(j >= n)
            def _up():
                sl = pl.ds((j - n) * tn, tn)
                kctx.mlp[:, sl] = kctx.mlp[:, sl] * val

        _stream_cols(kctx, h, w1, 2 * n, tn, sink, col0=0)

    return body


@register_task(TaskType.FC2)
def fc2_body(kctx):
    def body():
        dims = kctx.dims
        tk = kctx.cfg.tk_fc2
        n = dims.f_loc // tk
        scale = kctx.sc_w2[kctx.layer] if kctx.cfg.wq8 else None
        _stream_rows(
            kctx, kctx.mlp, kctx.w2.at[kctx.layer], kctx.h, n, tk,
            scale_row=scale,
        )

    return body


@register_task(TaskType.ALLREDUCE)
def allreduce_body(kctx):
    """``x += psum(h)`` over the tp axis: one-shot broadcast into
    symmetric workspace slots + local reduction, trailing barrier.

    Parity: the reference's in-megakernel allreduce task
    (``tasks/allreduce.py``, ``kernels/allreduce.py``) which likewise
    pushes partials to peer symmetric buffers. The trailing barrier
    bounds cross-rank skew so slot reuse by the NEXT allreduce task is
    race-free — the role the reference's scoreboard release plays.
    """

    def body():
        axis = kctx.axis
        n = kctx.dims.n_ranks
        h = kctx.h[...]
        _workspace_bcast(kctx, h)
        # Tracer phase mark: partials landed — [begin, mid] is the
        # fused exchange's comm phase, [mid, end] the local fold.
        trace_mid(kctx)
        acc = kctx.x[...]
        for r in range(n):
            acc = acc + kctx.cbuf[r]
        kctx.x[...] = acc
        _barrier(kctx)

    return body


@register_task(TaskType.AR_SEND)
def ar_send_body(kctx):
    """First half of the split allreduce (``MegaConfig.overlap_ar``):
    stage this rank's GEMM partial into the workspace and START the
    remote puts — non-blocking, so the ICI transfer proceeds while the
    following grid iterations run. Parity: the gemm_ar ONE_SHOT
    producer's per-tile notify pipelining
    (``ops/overlap/gemm_ar.py::_gemm_ar_one_shot_kernel`` ``_produce``),
    adapted to the sequential megakernel grid — the payload here is the
    whole [B, d] partial (decode batches are tiny; the overlap lever is
    WHEN the put starts, not tiling it)."""

    def body():
        me = jax.lax.axis_index(kctx.axis)
        h = kctx.h[...]
        kctx.arsrc[...] = h
        kctx.cbuf[me] = h
        for dma in _ar_put_dmas(kctx):
            dma.start()
        # Tracer phase mark: every remote put is in flight — the comm
        # window the decoder's overlap-exposure measure opens here.
        trace_mid(kctx)

    return body


@register_task(TaskType.AR_WAIT)
def ar_wait_body(kctx):
    """Second half of the split allreduce: fire the NEXT weight
    stream's tile-0 DMA (the overlap window — the ICI hop from AR_SEND
    hides under that HBM traffic), then wait the inbound partials,
    fold ``x += sum(partials)``, drain the sends, and barrier so the
    workspace slots are reusable by the next exchange (the gemm_ar
    ONE_SHOT ``_reduce``/``_drain`` phases)."""

    def body():
        nr = kctx.dims.n_ranks
        if kctx.cfg.cross_prefetch:
            # Needs the cross_prefetch handshake (the consuming stream
            # must skip its own tile-0 start); without it the split
            # still moves the puts off the critical path.
            fire_next_tile0(kctx)
        # Tracer phase mark: the next stream's tile-0 DMA is issued
        # (the work hidden under the open comm window); [mid, end] is
        # the blocked wait + fold + drain the overlap exists to shrink.
        trace_mid(kctx)
        _ar_wait_recvs(kctx)
        acc = kctx.x[...]
        for r in range(nr):
            acc = acc + kctx.cbuf[r]
        kctx.x[...] = acc
        for dma in _ar_put_dmas(kctx):
            dma.wait_send()
        _barrier(kctx)

    return body


@register_task(TaskType.MOE_GATE)
def moe_gate_body(kctx):
    """MoE router (parity: ``ops/moe/routing.py::router_topk`` —
    softmax over all experts, top-k, optional renormalization): writes
    the per-(expert, token) combine weights to the ``moe_w`` scratch
    and zeroes the combine accumulator the MOE_FFN tasks fold into.

    All math runs in the ``[E, B]`` orientation (experts on the
    sublane axis): the gate needs per-token reductions over experts,
    and this layout gets them as axis-0 reductions without a transpose
    Mosaic would have to relayout. Top-k is the iterative
    max-and-retire loop (k is tiny and static); ties resolve to the
    lowest expert index, matching ``jax.lax.top_k``."""

    def body():
        dims = kctx.dims
        B, E, k = dims.batch, dims.num_experts, dims.moe_top_k
        h_in = _normed_input(kctx, 1)  # [B, d] f32
        if kctx.cfg.fuse_norms:
            # MOE_FFN tasks read the normed input from h (under
            # fuse_norms nothing else wrote it); without fuse_norms the
            # NORM task already put it there.
            kctx.h[...] = h_in
        wr = kctx.wrouter[kctx.layer].astype(jnp.float32)  # [d, E]
        logits = jax.lax.dot_general(
            wr, h_in, (((0,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [E, B]
        m = jnp.max(logits, axis=0, keepdims=True)
        p = jnp.exp(logits - m)
        p = p / jnp.sum(p, axis=0, keepdims=True)  # softmax over experts

        eidx = jax.lax.broadcasted_iota(jnp.int32, (E, B), 0)
        cw = jnp.zeros((E, B), jnp.float32)
        rem = p
        for _ in range(k):
            mv = jnp.max(rem, axis=0, keepdims=True)  # [1, B]
            sel = jnp.min(
                jnp.where(rem == mv, eidx, jnp.int32(1 << 30)),
                axis=0, keepdims=True,
            )
            onehot = eidx == sel
            cw = cw + jnp.where(onehot, rem, 0.0)
            rem = jnp.where(onehot, jnp.float32(-1.0), rem)
        if dims.norm_topk:
            cw = cw / jnp.sum(cw, axis=0, keepdims=True)
        # Row-wise writes into the [E, 1, B] scratch (static unroll —
        # a [E, B] → [E, 1, B] reshape would be a Mosaic relayout).
        for e in range(E):
            kctx.moe_w[e, 0:1, :] = cw[e:e + 1, :]
        kctx.moe_acc[...] = jnp.zeros_like(kctx.moe_acc)

    return body


@register_task(TaskType.MOE_FFN)
def moe_ffn_body(kctx):
    """One LOCAL expert's SwiGLU FFN over every token, weighted into
    the combine accumulator (parity: the expert-segment grouped GEMMs
    of ``moe_reduce_rs.py``/``allgather_group_gemm.py``, one expert per
    task so the tracer sees per-expert windows and the split-phase A2A
    can fire mid-FFN). Experts are EP-sharded: ``arg0`` is the local
    expert id; the combine weight for token b is
    ``moe_w[rank·E_loc + arg0, b]`` — zero for unrouted tokens, whose
    rows then contribute nothing (decode batches are tiny, so dense
    per-expert compute costs the same HBM bytes as a ragged dispatch
    and keeps the weight streams statically shaped)."""

    def body():
        dims = kctx.dims
        B, f = dims.batch, dims.f_loc  # f = FULL expert width under EP
        tn = kctx.cfg.tn_fc1
        n = f // tn
        tk = kctx.cfg.tk_fc2
        n2 = f // tk
        e_loc = kctx.arg0
        layer = kctx.layer
        ge = jax.lax.axis_index(kctx.axis) * dims.experts_loc + e_loc
        # [B, 1] combine-weight column from scalar reads of the
        # expert-leading moe_w scratch (ge is traced on the untiled
        # leading dim — the ksc/vsc scalar-read pattern).
        cw_col = jnp.concatenate(
            [
                jnp.full((1, 1), kctx.moe_w[ge, 0, b], jnp.float32)
                for b in range(B)
            ],
            axis=0,
        )
        h_in = kctx.h[...]  # normed input (MOE_GATE/NORM wrote it)

        # FC1: one continuous column stream over the expert's fused
        # [d, gate|up] plane (the dense fc1_body pattern, per expert).
        def sink(j, val):
            @pl.when(j < n)
            def _gate():
                kctx.mlp[:, pl.ds(j * tn, tn)] = val * jax.lax.logistic(val)

            @pl.when(j >= n)
            def _up():
                sl = pl.ds((j - n) * tn, tn)
                kctx.mlp[:, sl] = kctx.mlp[:, sl] * val

        _stream_cols(kctx, h_in, kctx.w1.at[layer, e_loc], 2 * n, tn, sink)
        # FC2: row stream of the expert's [f, d] down projection,
        # folded into the combine accumulator under the per-token gate
        # weight (per-row constants distribute over the K-tile sum).
        _stream_rows(
            kctx, kctx.mlp, kctx.w2.at[layer, e_loc], kctx.moe_acc,
            n2, tk, col_scale=cw_col, accumulate=True,
        )

        @pl.when(kctx.arg1 == 1)
        def _handoff():
            # Non-overlap path: the LAST local expert hands the combine
            # partial to the fused ALLREDUCE task, which reads h.
            kctx.h[...] = kctx.moe_acc[...]

    return body


@register_task(TaskType.A2A_SEND)
def a2a_send_body(kctx):
    """EP combine send (split-phase sibling of AR_SEND,
    docs/megakernel.md "MoE serving"): push this rank's combine partial
    — the weighted sum of its OWN experts' outputs — to every peer.
    ``arg0`` is the phase: phase 0 fires the moment the first half of
    the local expert GEMMs has landed, so its ICI bytes fly under the
    SECOND half's expert grouped GEMMs (the accumulator restarts at
    zero for them); phase 1 carries the rest and reuses the standard
    AR workspace, whose slots the layer's attention allreduce already
    quiesced. Dispatch needs no wire bytes on TPU decode: activations
    and router are replicated, so every rank already holds every
    token — the reference pays ``kernel_dispatch_token`` because its
    tokens live on their home ranks."""

    def body():
        me = jax.lax.axis_index(kctx.axis)
        payload = kctx.moe_acc[...]

        @pl.when(kctx.arg0 == 0)
        def _phase0():
            kctx.a2src[...] = payload
            kctx.a2buf[me] = payload
            for dma in _a2a_put_dmas(kctx):
                dma.start()
            # Fresh partial for the second half of the experts while
            # phase 0's bytes are in flight.
            kctx.moe_acc[...] = jnp.zeros_like(payload)

        @pl.when(kctx.arg0 == 1)
        def _phase1():
            kctx.arsrc[...] = payload
            kctx.cbuf[me] = payload
            for dma in _ar_put_dmas(kctx):
                dma.start()

        # Tracer phase mark: this phase's puts are in flight — the comm
        # window the decoder's A2A overlap measure opens here.
        trace_mid(kctx)

    return body


@register_task(TaskType.A2A_WAIT)
def a2a_wait_body(kctx):
    """EP combine wait (split-phase sibling of AR_WAIT): fire the NEXT
    weight stream's tile-0 DMA (the overlap lever — the combine's ICI
    hop hides under that HBM traffic), then wait both phases' inbound
    partials, fold ``x += Σ_ranks (phase0 + phase1)``, drain the sends,
    and barrier so both workspaces are reusable."""

    def body():
        nr = kctx.dims.n_ranks
        if kctx.cfg.cross_prefetch:
            fire_next_tile0(kctx)
        # Tracer phase mark: tile-0 is issued; [mid, end] is the
        # blocked wait + fold the overlap exists to shrink.
        trace_mid(kctx)
        _a2a_wait_recvs(kctx)
        _ar_wait_recvs(kctx)
        acc = kctx.x[...]
        for r in range(nr):
            acc = acc + kctx.a2buf[r] + kctx.cbuf[r]
        kctx.x[...] = acc
        for dma in _a2a_put_dmas(kctx):
            dma.wait_send()
        for dma in _ar_put_dmas(kctx):
            dma.wait_send()
        _barrier(kctx)

    return body


def _multi_step_tail(kctx, row, B):
    """Shared multi-step epilogue: publish this step's winning tokens
    (``row`` [1, B]) to the next EMBED (VMEM→SMEM DMA — scalar reads
    need SMEM) and the per-step token output, then — under ``dims.eos``
    — test each winner against its slot's stop token and record the
    FIRST hitting step into the ``stop_step`` SMEM output (``nsteps`` =
    never hit). The stamp is first-hit-wins: once a slot has stopped,
    later steps keep generating (their tokens are clamped host/shard
    side via ``min(n_valid, stop_step + 1)``) but cannot overwrite the
    retire step — that is what lets a finished slot retire without a
    host round trip while the co-batched survivor streams on."""
    dims = kctx.dims
    kctx.tokrow[...] = row
    kctx.toks_out[kctx.step] = row
    if dims.eos:
        ns = jnp.int32(dims.nsteps)
        for b in range(B):
            hit = row[0, b] == kctx.stop_tok[b]
            prev = jnp.where(kctx.step == 0, ns, kctx.stop_out[0, b])
            kctx.stop_out[0, b] = jnp.where(
                jnp.logical_and(hit, prev == ns), kctx.step, prev
            ).astype(jnp.int32)
    cp = pltpu.make_async_copy(kctx.tokrow, kctx.tok_smem, kctx.tsem)
    cp.start()
    cp.wait()


def _filtered_winner(kctx, B, v_real, NEGF):
    """Exact in-kernel top-k/top-p + Gumbel-max winner over the logits
    the tile stream just landed (dims.filtered, single-rank).

    Matches ``sampling.filter_logits`` + noisy argmax BIT-EXACTLY on
    the keep-set by reproducing its thresholds instead of its sorts:
    sorting a [B, v] tile-streamed buffer in-kernel is the expensive
    path, but both filters are threshold rules — top-k keeps
    ``ls >= kth`` (k-th largest, ties survive) and top-p keeps
    ``ls >= cutoff`` (cutoff = smallest kept sorted logit, which
    re-includes its ties) — and a threshold is findable by bisection
    on monotone counts. Per row, in the scaled domain
    ``ls = logits / temperature`` (pad columns at NEGF):

    * top-k: bisect t with invariant ``C(lo) >= k > C(hi)`` where
      ``C(t) = #{ls > t}``; after 64 halvings [lo, hi) brackets the
      k-th largest value so ``ls > lo`` == ``ls >= kth`` exactly
      (counting in f32 is exact below 2^24 >> vocab). Disabled top-k
      rows prefetch k = V → keep-all.
    * top-p: over top-k survivors, weights ``w = exp(ls - max)``; bisect
      with invariant ``H(lo) >= p*Z > H(hi)``, ``H(t) = sum{ls > t} w``,
      Z = sum w; converges to the host's cutoff including its tie
      re-inclusion. Host prep clamps p to [1e-6, 1] so ``H(hi0) = 0 <
      p*Z`` holds at init (Z > 0: the row max always contributes 1).

    64 fixed iterations shrink the bracket to width*2^-64 — far below
    the f32 ulp gap between distinct logits — so the bracket ends
    strictly between adjacent distinct values and the comparison
    ``ls > lo`` is exact, not approximate. Rows with ``enable = 0``
    (greedy or unfiltered-sampled) keep every real column; the winner
    is then argmax over ``logits + noise`` (noise = temperature *
    gumbel, zero for greedy rows) with jnp.argmax's first-occurrence
    tie-break, identical to the unfiltered carry path."""
    lg = kctx.logits[...]  # [B, v_loc] raw f32 (clean output stays)
    gidx = jax.lax.broadcasted_iota(jnp.int32, lg.shape, 1)
    real = gidx < v_real
    inv_t = kctx.sampcfg[:, 0:1]
    kk = kctx.sampcfg[:, 1:2]
    pp = kctx.sampcfg[:, 2:3]
    en = kctx.sampcfg[:, 3:4] > 0.0
    ls = jnp.where(real, lg * inv_t, NEGF)
    mx = jnp.max(ls, axis=-1, keepdims=True)
    mn = jnp.min(jnp.where(real, ls, -NEGF), axis=-1, keepdims=True)

    def bisect(count_ge):
        # Invariant: count_ge(lo) true, count_ge(hi) false.
        def it(_, c):
            lo, hi = c
            mid = 0.5 * (lo + hi)
            take = count_ge(mid)
            return jnp.where(take, mid, lo), jnp.where(take, hi, mid)

        lo, _ = jax.lax.fori_loop(0, 64, it, (mn - 1.0, mx))
        return lo

    lo_k = bisect(
        lambda t: jnp.sum(
            jnp.where(ls > t, 1.0, 0.0), axis=-1, keepdims=True
        ) >= kk
    )
    tk = ls > lo_k
    w = jnp.where(tk, jnp.exp(ls - mx), 0.0)
    z = jnp.sum(w, axis=-1, keepdims=True)
    lo_p = bisect(
        lambda t: jnp.sum(
            jnp.where(ls > t, w, 0.0), axis=-1, keepdims=True
        ) >= pp * z
    )
    keep = jnp.where(en, jnp.logical_and(tk, ls > lo_p), real)
    score = jnp.where(keep, lg + kctx.noise[0], NEGF)
    bestv = jnp.max(score, axis=-1, keepdims=True)
    return jnp.min(
        jnp.where(score == bestv, gidx, jnp.int32(1 << 30)),
        axis=-1, keepdims=True,
    )


@register_task(TaskType.LM_HEAD)
def lm_head_body(kctx):
    def body():
        dims = kctx.dims
        tn = kctx.cfg.tn_lm
        n = dims.v_loc // tn

        if dims.prefill:
            # Project only the last real prompt row (position
            # kv_len[0] - 1): a one-hot [1, S] @ [S, d] row select —
            # logits over all S rows would be an [S, v_loc] output.
            S = dims.batch
            sel = jax.lax.broadcasted_iota(jnp.int32, (1, S), 1)
            onehot = (sel == kctx.kv_len[0] - 1).astype(jnp.float32)
            x_in = jnp.dot(
                onehot, _normed_input(kctx, 2),
                preferred_element_type=jnp.float32,
            )  # [1, d]
        else:
            x_in = _normed_input(kctx, 2)

        # Tail tile when tn doesn't divide v_loc (wide lm tiles on an
        # unround vocab axis); must stay a 128-multiple for lane
        # alignment — guaranteed by the resolve() gate.
        rem = dims.v_loc - n * tn

        if dims.nsteps > 1:
            # Multi-step greedy: a running argmax threads through the
            # tile stream; the winning index feeds the next step's
            # EMBED via VMEM→SMEM DMA (scalar reads need SMEM) and the
            # per-step token output. Tie-break matches jnp.argmax
            # (first occurrence: min index within a tile, strict > for
            # later tiles; under TP, lower ranks hold lower global
            # indices and the ascending exchange loop keeps strict >).
            B = x_in.shape[0]
            nr = dims.n_ranks
            NEGF = jnp.float32(-3.0e38)
            v_total = dims.v_real or nr * dims.v_loc
            if nr > 1:
                me = jax.lax.axis_index(kctx.axis)
                # This rank's real (unpadded) column count.
                v_real = jnp.clip(v_total - me * dims.v_loc, 0, dims.v_loc)
            else:
                v_real = min(v_total, dims.v_loc)

            if dims.filtered:
                # Filtered sampling (dims.filtered, single-rank): the
                # stream writes raw logits only — no running carry; a
                # filter threshold cannot be known until every tile has
                # landed — then the post-stream pass derives the exact
                # host keep-set by per-row bisection and argmaxes
                # logits + noise over it (_filtered_winner).
                def fsink(j, val):
                    val = _q8_scale(kctx, kctx.sc_lm, None, j * tn, val)
                    kctx.logits[:, pl.ds(j * tn, val.shape[1])] = val

                _stream_cols(
                    kctx, x_in, kctx.lm_head, n, tn, fsink, tail=rem
                )
                besti = _filtered_winner(kctx, B, v_real, NEGF)
                row = jnp.concatenate(
                    [besti[b:b + 1, :] for b in range(B)], axis=1
                )  # [1, B]
                _multi_step_tail(kctx, row, B)
                return

            def sink(j, val, carry):
                val = _q8_scale(kctx, kctx.sc_lm, None, j * tn, val)
                kctx.logits[:, pl.ds(j * tn, val.shape[1])] = val
                bestv, besti = carry
                if dims.sampled:
                    # Gumbel-max sampling: argmax over logits + noise
                    # (noise = temperature * gumbel, host-drawn). The
                    # logits OUTPUT stays clean — noise only perturbs
                    # the argmax.
                    val = val + kctx.noise[0, :, pl.ds(j * tn, val.shape[1])]
                gidx = j * tn + jax.lax.broadcasted_iota(
                    jnp.int32, (B, val.shape[1]), 1
                )
                masked = jnp.where(gidx < v_real, val, NEGF)
                tmax = jnp.max(masked, axis=-1, keepdims=True)
                tidx = jnp.min(
                    jnp.where(masked == tmax, gidx, jnp.int32(1 << 30)),
                    axis=-1, keepdims=True,
                )
                upd = tmax > bestv
                return (
                    jnp.where(upd, tmax, bestv),
                    jnp.where(upd, tidx, besti),
                )

            init = (
                jnp.full((B, 1), NEGF, jnp.float32),
                jnp.zeros((B, 1), jnp.int32),
            )
            bestv, besti = _stream_cols(
                kctx, x_in, kctx.lm_head, n, tn, sink, tail=rem, carry=init
            )

            if nr > 1:
                # Cross-rank argmax: every rank one-shot-broadcasts its
                # (best value, best GLOBAL index) pair through the
                # allreduce workspace (quiesced: the preceding
                # allreduce task ends with a barrier) and reduces all
                # nr candidates identically.
                gbesti = (me * dims.v_loc + besti).astype(jnp.float32)
                d = kctx.arsrc.shape[1]
                pad = jnp.zeros((B, d - 2), jnp.float32)
                cand = jnp.concatenate([bestv, gbesti, pad], axis=1)
                # Race fixture (no-op when straggler_rank is None): lag
                # this rank's candidate push so any consumer missing
                # its wait reads stale slots.
                dl.straggle_if_rank(
                    dims.straggler_rank, kctx.axis, dims.straggler_nanos
                )
                _workspace_bcast(kctx, cand)
                bestv = kctx.cbuf[0, :, 0:1]
                besti = kctx.cbuf[0, :, 1:2].astype(jnp.int32)
                for r in range(1, nr):
                    v_r = kctx.cbuf[r, :, 0:1]
                    i_r = kctx.cbuf[r, :, 1:2].astype(jnp.int32)
                    upd = v_r > bestv
                    bestv = jnp.where(upd, v_r, bestv)
                    besti = jnp.where(upd, i_r, besti)
                # Slot reuse fence: the next step's exchange (or
                # allreduce) must not land before every rank has read
                # this round's candidates.
                _barrier(kctx)

            row = jnp.concatenate(
                [besti[b:b + 1, :] for b in range(B)], axis=1
            )  # [1, B]
            _multi_step_tail(kctx, row, B)
        else:
            def sink(j, val):
                val = _q8_scale(kctx, kctx.sc_lm, None, j * tn, val)
                kctx.logits[:, pl.ds(j * tn, val.shape[1])] = val

            _stream_cols(kctx, x_in, kctx.lm_head, n, tn, sink, tail=rem)

    return body


@register_task(TaskType.BARRIER)
def barrier_body(kctx):
    def body():
        _barrier(kctx)

    return body


