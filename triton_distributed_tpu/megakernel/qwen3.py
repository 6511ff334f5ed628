"""Qwen3 megakernel model: the whole TP decode step as ONE Pallas kernel.

Parity: reference ``mega_triton_kernel/models/qwen3.py`` —
``Qwen3Model``:108 building fc1/qkv/attn/allreduce/… tasks for every
layer and running the persistent kernel per decode step (the top rung of
the reference's decode ladder, ``docs/mega_triton_kernel.md:27-37``).

Reuses :class:`~triton_distributed_tpu.models.qwen.Qwen3` for parameters
and sharding, so the megakernel is a drop-in third decode mode next to
``xla`` / ``pallas``.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from triton_distributed_tpu.megakernel.code_generator import MegaConfig, MegaDims
from triton_distributed_tpu.megakernel.model_builder import ModelBuilder
from triton_distributed_tpu.megakernel.scheduler import SchedulePolicy
from triton_distributed_tpu.models.kv_cache import KVCache, cache_specs
from triton_distributed_tpu.models.paged_kv_cache import (
    PagedKVCache,
    paged_cache_specs,
)
from triton_distributed_tpu.models import paged_kv_cache as _paged
from triton_distributed_tpu.models.qwen import Qwen3, Qwen3Params, pad_vocab
from triton_distributed_tpu.runtime.pytree import register_param_dataclass


@dataclasses.dataclass
class Q8Params:
    """Weight-only int8 megakernel parameters (``MegaConfig.wq8``).

    The five projection weights are symmetric per-OUTPUT-channel int8
    (scale = max|w| / 127 over the contraction axis, computed per TP
    shard — column shards scale their local columns; row shards
    (``wo``/``w2``, partial sums) carry a per-RANK scale plane stacked
    on a tp-sharded axis and dequantize before the allreduce, which is
    exact). Everything else (embed, norms) stays full precision —
    including ``embed`` when the checkpoint ties it to ``lm_head``:
    the tied tensor is stored twice, once bf16 for the gather and once
    int8 for the head stream.
    """

    embed: jax.Array    # [V, d] full precision
    wqkv: jax.Array     # [L, d, qkv_loc] int8
    wo: jax.Array       # [L, o_k, d] int8
    w1: jax.Array       # [L, d, 2*f_loc] int8
    w2: jax.Array       # [L, f_loc, d] int8
    lm_head: jax.Array  # [d, v_loc] int8
    sc_qkv: jax.Array   # [L, 1, qkv_loc] f32
    sc_o: jax.Array     # [L, tp, d] f32 globally; [L, 1, d] per shard
    sc_w1: jax.Array    # [L, 1, 2*f_loc] f32
    sc_w2: jax.Array    # [L, tp, d] f32 globally; [L, 1, d] per shard
    sc_lm: jax.Array    # [1, v_loc] f32
    ln1: jax.Array
    ln2: jax.Array
    norm: jax.Array
    qn: jax.Array
    kn: jax.Array


register_param_dataclass(Q8Params, [
    "embed", "wqkv", "wo", "w1", "w2", "lm_head",
    "sc_qkv", "sc_o", "sc_w1", "sc_w2", "sc_lm",
    "ln1", "ln2", "norm", "qn", "kn",
])


def _quantize_shard(params: Qwen3Params) -> Q8Params:
    """Per-shard quantization (runs inside shard_map, jitted once)."""
    lp = params.layers

    def q(w, axis):
        s = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=axis, keepdims=True)
        s = jnp.maximum(s / 127.0, 1e-12)
        wi = jnp.clip(
            jnp.round(w.astype(jnp.float32) / s), -127, 127
        ).astype(jnp.int8)
        return wi, s

    wqkv8, sq = q(lp.attn.wqkv, 1)
    wo8, so = q(lp.attn.wo, 1)
    w18, s1 = q(lp.mlp.w1, 1)
    w28, s2 = q(lp.mlp.w2, 1)
    lm8, slm = q(params.lm_head, 0)
    return Q8Params(
        embed=params.embed, wqkv=wqkv8, wo=wo8, w1=w18, w2=w28,
        lm_head=lm8, sc_qkv=sq, sc_o=so, sc_w1=s1, sc_w2=s2, sc_lm=slm,
        ln1=lp.ln1, ln2=lp.ln2, norm=params.norm,
        qn=lp.attn.q_norm, kn=lp.attn.k_norm,
    )


@dataclasses.dataclass
class MoEMegaParams:
    """EP-resharded megakernel parameters for Qwen3MoE decode.

    The serving model keeps the TP expert sharding (every rank holds
    its ``f_loc`` column shard of EVERY expert — ``layers/tp_moe.py``),
    which is what the unfused decode path runs. The megernel's MoE
    graph instead streams whole experts (one MOE_FFN task per LOCAL
    expert, full FFN width) so the combine exchange carries true
    per-expert-owner partials — EXPERT-parallel sharding. This pytree
    is that resharding, built once from ``model.params`` device-side
    (the Q8Params pattern): w1/w2 gather their f shards and keep only
    this rank's ``E/n`` experts — per-rank HBM bytes are unchanged
    (E·3df/n either way) — and the router stays replicated.
    """

    embed: jax.Array    # [V, d] replicated
    wqkv: jax.Array     # [L, d, qkv_loc]
    wo: jax.Array       # [L, o_k, d]
    w1: jax.Array       # [L, E_loc, d, 2f] — gate|up fused, FULL width
    w2: jax.Array       # [L, E_loc, f, d]
    wrouter: jax.Array  # [L, d, E] replicated
    lm_head: jax.Array  # [d, v_loc]
    ln1: jax.Array
    ln2: jax.Array
    norm: jax.Array
    qn: jax.Array
    kn: jax.Array


register_param_dataclass(MoEMegaParams, [
    "embed", "wqkv", "wo", "w1", "w2", "wrouter", "lm_head",
    "ln1", "ln2", "norm", "qn", "kn",
])


def _moe_reshard_shard(params: Qwen3Params, *, axis: str, n: int):
    """Per-shard TP→EP expert resharding (runs inside shard_map,
    jitted once): an expert↔f-shard ALL-TO-ALL — rank r sends its f
    columns of expert group g to rank g and receives every rank's f
    columns of ITS group — then restore the gate-contiguous [d, 2f]
    fused layout. All-to-all (not gather-then-slice) keeps peak memory
    at the FINAL size: a full [L, E, d, 2f] gather would transiently
    hold n× each rank's steady-state MLP bytes, which at production
    expert counts is exactly the HBM a 1/n-sized shard plan doesn't
    have."""
    lp = params.layers
    mlp = lp.mlp  # TPMoEParams
    L, E, d, two_f_loc = mlp.w1.shape
    f_loc = two_f_loc // 2
    epr = E // n
    if n > 1:
        # w1 [L, E, d, 2f_loc] → [L, E/n, d, n·2f_loc], received
        # f-shards concatenated in source-rank order: [g0|u0|g1|u1|…].
        w1_ep = jax.lax.all_to_all(
            mlp.w1, axis, split_axis=1, concat_axis=3, tiled=True
        )
        # Reorder to [gate_full | up_full] (shard slices concatenate
        # back into the original column order).
        w1_ep = w1_ep.reshape(L, epr, d, n, 2, f_loc)
        w1_ep = jnp.swapaxes(w1_ep, 3, 4).reshape(
            L, epr, d, 2 * n * f_loc
        )
        # w2 [L, E, f_loc, d] → [L, E/n, f, d] (plain f split: rank
        # order IS the original row order, no reorder needed).
        w2_ep = jax.lax.all_to_all(
            mlp.w2, axis, split_axis=1, concat_axis=2, tiled=True
        )
    else:
        w1_ep, w2_ep = mlp.w1, mlp.w2
    return MoEMegaParams(
        embed=params.embed, wqkv=lp.attn.wqkv, wo=lp.attn.wo,
        w1=w1_ep, w2=w2_ep, wrouter=mlp.w_router,
        lm_head=params.lm_head, ln1=lp.ln1, ln2=lp.ln2,
        norm=params.norm, qn=lp.attn.q_norm, kn=lp.attn.k_norm,
    )


class MegaQwen3:
    """Megakernel decode wrapper around a (loaded) :class:`Qwen3`."""

    def __init__(
        self,
        model: Qwen3,
        *,
        cfg: MegaConfig | None = None,
        policy: SchedulePolicy = SchedulePolicy.ROUND_ROBIN,
    ):
        if model.params is None and not (cfg and cfg.wq8):
            # wq8 decode can run from Q8Params alone (see
            # :meth:`quantized_init` — int8 synthesis that never
            # materializes the bf16 tree); every other path needs the
            # model loaded.
            raise ValueError("load or init Qwen3 params first")
        self.model = model
        self.cfg = cfg or MegaConfig()
        self.policy = policy
        self._jit: dict = {}
        # Scheduled orders by decode_multi_fn cache key (trace
        # consumers read them back via multi_task_order).
        self._orders: dict = {}
        self._last_multi_order = None

    def _dims(
        self, batch: int, s_max: int, page: int = 0,
        kv_quant: bool = False, num_pages: int = 0,
        trace: bool = False,
    ) -> MegaDims:
        m = self.model
        c = m.cfg
        n = m.ctx.axis_size(m.axis)
        # The lm_head's vocab axis is padded to 128·tp by set_params;
        # v_loc follows the padded width (the step wrappers slice the
        # pad logits back off). Without loaded params (the wq8
        # synthetic path) the same padding is computed from the config.
        if m.params is not None:
            v_pad = m.params.lm_head.shape[1]
        else:
            v_pad = pad_vocab(c.vocab_size, n)
        moe = c.num_experts > 0
        return MegaDims(
            batch=batch,
            d=c.hidden_size,
            hq_loc=m.dims.hq_loc,
            hkv_loc=m.dims.hkv_loc,
            head_dim=c.head_dim,
            # MoE streams whole (EP-sharded) experts: f_loc is then the
            # FULL per-expert FFN width, not a TP column shard.
            f_loc=(c.moe_intermediate_size if moe
                   else c.intermediate_size // n),
            v_loc=v_pad // n,
            num_layers=c.num_layers,
            s_max=s_max,
            n_ranks=n,
            rms_eps=c.rms_eps,
            rope_theta=c.rope_theta,
            page=page,
            kv_quant=kv_quant,
            num_pages=num_pages,
            trace=trace,
            num_experts=c.num_experts,
            moe_top_k=c.num_experts_per_tok,
            norm_topk=c.norm_topk_prob,
        )

    @staticmethod
    def _scale_args(cache: PagedKVCache, kv_quant: bool):
        """The trailing scale operands of a quantized pool call:
        ``[L, P, H]`` scale planes reshaped to ``[L, P, 1, H]`` so the
        kernel's dynamic layer/page indices stay on untiled leading
        dims (the norm-weight layout trick)."""
        if not kv_quant:
            return ()
        return (
            cache.k_scale[:, :, None, :], cache.v_scale[:, :, None, :]
        )

    def build(
        self, batch: int, s_max: int, page: int = 0,
        kv_quant: bool = False, num_pages: int = 0,
        trace: bool = False,
    ):
        """Build + schedule the task graph and jit the SPMD step
        (parity: ``Qwen3Model.build_fwd`` + ``compile``). ``page`` > 0
        builds the paged-cache variant (KV read through the page table,
        attention block size = page size); ``kv_quant`` reads an int8
        pool through its per-page scales (dequant in-kernel, appends
        through the quantized_row_scatter protocol — full-width KV
        never materializes). ``trace`` adds the device task tracer's
        ring output (docs/observability.md "Device task tracer"): the
        step then returns ``(logits, cache, trace [tp, 1, T, 8])``;
        untraced builds keep the exact PR 7 operand list and contract."""
        m = self.model
        dims = self._dims(batch, s_max, page, kv_quant, num_pages, trace)
        # (s_blk == page is enforced by MegaConfig.resolve when
        # dims.page is set — single owner of that invariant.)
        mb = ModelBuilder(
            dims, cfg=self.cfg, axis=m.axis, ctx=m.ctx,
            wdtype=m.cfg.dtype, cdtype=m.cfg.dtype,
        )
        mb.build_decoder_graph()
        compiled = mb.compile(self.policy)
        per_shard = compiled.per_shard
        ax = m.axis

        kernel_args, pspecs = self._args_and_specs()

        if page:
            def shard_fn(params: Qwen3Params, tokens, cache: PagedKVCache):
                outs = per_shard(
                    cache.kv_len, tokens, cache.page_table,
                    *kernel_args(params), cache.k_pages, cache.v_pages,
                    *self._scale_args(cache, kv_quant),
                )
                logits, k_rows, v_rows, _toks = outs[:4]
                # Page-table append of the new rows [L, B, hkv, hd]
                # (the kernel never writes the pool — same reasoning as
                # the dense path below; [0] drops the step dim of the
                # single-step build). On a quantized pool, append runs
                # the ONE scale-protocol implementation
                # (quantized_row_scatter: offset-0 reset, grow+requant).
                new_cache = _paged.append(cache, k_rows[0], v_rows[0])
                if trace:  # per-rank ring, stacked on a tp leading dim
                    return logits, new_cache, outs[4][None]
                return logits, new_cache

            specs = paged_cache_specs(ax, quantized=kv_quant)
        else:
            def shard_fn(params: Qwen3Params, tokens, cache: KVCache):
                outs = per_shard(
                    cache.kv_len, tokens,
                    *kernel_args(params), cache.k, cache.v,
                )
                logits, k_rows, v_rows, _toks = outs[:4]
                k_rows, v_rows = k_rows[0], v_rows[0]  # single-step build
                # Append the new rows [L, B, hkv, hd] at each row's
                # position — one dynamic_update_slice per batch row; XLA
                # updates the donated cache in place (the kernel cannot:
                # a one-row write at a dynamic offset in a tiled cache
                # plane is an unaligned slice Mosaic rejects).
                k_new, v_new = cache.k, cache.v
                B = tokens.shape[0]
                for b in range(B):
                    at = (0, b, 0, cache.kv_len[b], 0)
                    k_new = jax.lax.dynamic_update_slice(
                        k_new, k_rows[:, b, :, None, :][:, None], at
                    )
                    v_new = jax.lax.dynamic_update_slice(
                        v_new, v_rows[:, b, :, None, :][:, None], at
                    )
                new_cache = KVCache(
                    k=k_new, v=v_new, kv_len=cache.kv_len + 1
                )
                if trace:
                    return logits, new_cache, outs[4][None]
                return logits, new_cache

            specs = cache_specs(ax)

        out_specs = (P(None, ax), specs)
        if trace:
            out_specs += (P(ax),)
        g = m.ctx.shard_map(
            shard_fn,
            in_specs=(pspecs, P(), specs),
            out_specs=out_specs,
        )
        V = m.cfg.vocab_size

        def tdt_mega_step(params, tokens, cache):
            outs = g(params, tokens, cache)
            # Drop vocab-pad logits (zero-weight columns score 0 and
            # could beat real logits under greedy sampling).
            return (outs[0][:, :V], *outs[1:])

        step = jax.jit(tdt_mega_step, donate_argnums=(2,))
        return compiled, step, tdt_mega_step

    def _q8_specs(self) -> Q8Params:
        ax = self.model.axis
        return Q8Params(
            embed=P(), wqkv=P(None, None, ax), wo=P(None, ax, None),
            w1=P(None, None, ax), w2=P(None, ax, None), lm_head=P(None, ax),
            sc_qkv=P(None, None, ax),
            # Row-sharded weights carry per-RANK scales: local [L, 1, d]
            # planes stack on a tp-sharded middle axis.
            sc_o=P(None, ax, None),
            sc_w1=P(None, None, ax),
            sc_w2=P(None, ax, None),
            sc_lm=P(None, ax),
            ln1=P(), ln2=P(), norm=P(), qn=P(), kn=P(),
        )

    def quantized_params(self) -> Q8Params:
        """The int8 weight pytree ``wq8`` steps take IN PLACE of
        ``model.params`` (quantized once, device-side, per shard;
        cached on this instance)."""
        if getattr(self, "_q8", None) is None:
            m = self.model
            if m.params is None:
                raise ValueError(
                    "no bf16 params to quantize — load/init the model "
                    "first, or synthesize int8 directly with "
                    "quantized_init()"
                )
            f = m.ctx.shard_map(
                _quantize_shard,
                in_specs=(m.param_specs,),
                out_specs=self._q8_specs(),
            )

            def tdt_mega_quantize(params):
                return f(params)

            self._q8 = jax.jit(tdt_mega_quantize)(m.params)
            jax.block_until_ready(self._q8)
        return self._q8

    def quantized_init(self, key: jax.Array) -> Q8Params:
        """SYNTHETIC per-channel-int8 parameters, generated device-side
        WITHOUT ever materializing the bf16 tree — the path that puts
        an 8B-geometry model on one 16 GB v5e (the bf16 tree alone,
        ~16.4 GB, would exceed HBM; the reference serves 8B across
        8×H800 = 640 GB, ``docs/mega_triton_kernel.md:27-31``).

        Weights are uniform int8 with init-scale-magnitude uniform
        scales, so every DMA/tile/dequant path is production-shaped but
        the logits carry no knowledge — this exists for geometry/perf
        evidence. The cross-checks still bind: single- and multi-step
        chains must agree token-for-token over the same synthetic
        weights. Requires ``MegaConfig(wq8=True)``; fills the same
        cache :meth:`quantized_params` reads."""
        if not self.cfg.wq8:
            raise ValueError("quantized_init requires MegaConfig(wq8=True)")
        m = self.model
        c = m.cfg
        n = m.ctx.axis_size(m.axis)
        hd, d, L, f = c.head_dim, c.hidden_size, c.num_layers, \
            c.intermediate_size
        qkv = (c.num_q_heads + 2 * c.num_kv_heads) * hd
        o_k = c.num_q_heads * hd
        v_pad = pad_vocab(c.vocab_size, n)
        dt = c.dtype

        def tdt_mega_quantized_init(k):
            ks = iter(jax.random.split(k, 7))

            def w8(*shape):
                return jax.random.randint(
                    next(ks), shape, -127, 128, jnp.int8
                )

            def sc(*shape):
                return jnp.full(shape, 0.02 / 127.0, jnp.float32)

            return Q8Params(
                embed=(jax.random.normal(
                    next(ks), (c.vocab_size, d), jnp.float32
                ) * 0.02).astype(dt),
                wqkv=w8(L, d, qkv), wo=w8(L, o_k, d),
                w1=w8(L, d, 2 * f), w2=w8(L, f, d),
                lm_head=w8(d, v_pad),
                sc_qkv=sc(L, 1, qkv), sc_o=sc(L, n, d),
                sc_w1=sc(L, 1, 2 * f), sc_w2=sc(L, n, d),
                sc_lm=sc(1, v_pad),
                ln1=jnp.ones((L, d), dt), ln2=jnp.ones((L, d), dt),
                norm=jnp.ones((d,), dt),
                qn=jnp.ones((L, hd), dt), kn=jnp.ones((L, hd), dt),
            )

        shardings = jax.tree.map(
            lambda s: m.ctx.sharding(*s), self._q8_specs(),
            is_leaf=lambda x: isinstance(x, P),
        )
        self._q8 = jax.jit(
            tdt_mega_quantized_init, out_shardings=shardings
        )(key)
        jax.block_until_ready(self._q8)
        return self._q8

    @staticmethod
    def _kernel_args_q8(q: Q8Params):
        V, d = q.embed.shape
        if V % 8:
            raise ValueError(f"megakernel needs vocab_size % 8 == 0, got {V}")
        return (
            q.embed.reshape(V // 8, 8, d),
            q.wqkv, q.wo, q.w1, q.w2, q.lm_head,
            q.ln1[:, None, :], q.ln2[:, None, :], q.norm[None, :],
            q.qn[:, None, :], q.kn[:, None, :],
            q.sc_qkv, q.sc_o, q.sc_w1, q.sc_w2, q.sc_lm,
        )

    @staticmethod
    def _kernel_args(params: Qwen3Params):
        lp = params.layers
        V, d = params.embed.shape
        if V % 8:
            raise ValueError(
                f"megakernel needs vocab_size % 8 == 0, got {V}"
            )
        # Per-layer norm weights go in as [L, 1, d] / [L, 1, hd]:
        # the kernel indexes the layer with a traced scalar, and
        # Mosaic only allows dynamic indices on untiled leading
        # dims (a dynamic sublane slice of a [L, d] ref needs a
        # statically 8-aligned index it can't prove).
        return (
            params.embed.reshape(V // 8, 8, d),
            lp.attn.wqkv, lp.attn.wo, lp.mlp.w1, lp.mlp.w2,
            params.lm_head,
            lp.ln1[:, None, :], lp.ln2[:, None, :], params.norm[None, :],
            lp.attn.q_norm[:, None, :], lp.attn.k_norm[:, None, :],
        )

    def _built(self, batch: int, s_max: int, page: int = 0,
               kv_quant: bool = False, num_pages: int = 0,
               trace: bool = False):
        key = (batch, s_max, page, kv_quant, num_pages, trace)
        if key not in self._jit:
            self._jit[key] = self.build(*key)
        return self._jit[key]

    def decode_step(self, tokens: jax.Array, cache):
        """One decode step for the whole batch: ``tokens [B] int32 →
        (logits [B, V] f32, cache)`` — the megakernel rung of the decode
        ladder. Accepts a dense :class:`KVCache` or a
        :class:`PagedKVCache` (pool read through the page table —
        int8-quantized pools dequantize in-kernel via their per-page
        scales)."""
        b = int(tokens.shape[0])
        if isinstance(cache, PagedKVCache):
            page = int(cache.k_pages.shape[3])
            s_max = int(cache.page_table.shape[1]) * page
            step = self._built(
                b, s_max, page, cache.quantized,
                int(cache.k_pages.shape[1]),
            )[1]
        else:
            step = self._built(b, int(cache.k.shape[3]))[1]
        return step(self._step_params(), tokens, cache)

    @property
    def _is_moe(self) -> bool:
        return self.model.cfg.num_experts > 0

    def _args_and_specs(self):
        """(kernel_args fn, shard_map param specs) for this model/cfg:
        Q8Params under ``wq8``, the EP-resharded :class:`MoEMegaParams`
        for MoE models, the plain model tree otherwise."""
        if self.cfg.wq8:
            if self._is_moe:
                raise NotImplementedError(
                    "wq8 does not compose with MoE decode yet"
                )
            return self._kernel_args_q8, self._q8_specs()
        if self._is_moe:
            return self._kernel_args_moe, self._moe_specs()
        return self._kernel_args, self.model.param_specs

    def _moe_specs(self) -> MoEMegaParams:
        ax = self.model.axis
        return MoEMegaParams(
            embed=P(), wqkv=P(None, None, ax), wo=P(None, ax, None),
            # EP: the expert axis is the sharded one; each rank's slice
            # holds its E/n experts at FULL width.
            w1=P(None, ax, None, None), w2=P(None, ax, None, None),
            wrouter=P(), lm_head=P(None, ax),
            ln1=P(), ln2=P(), norm=P(), qn=P(), kn=P(),
        )

    def moe_params(self) -> MoEMegaParams:
        """The EP-resharded pytree MoE steps take in place of
        ``model.params`` (resharded once, device-side, per shard;
        cached on this instance — the ``quantized_params`` pattern)."""
        if getattr(self, "_moe_p", None) is None:
            m = self.model
            if m.params is None:
                raise ValueError("load or init the MoE model first")
            n = m.ctx.axis_size(m.axis)
            if m.cfg.num_experts % n:
                raise ValueError(
                    f"num_experts {m.cfg.num_experts} not divisible by "
                    f"tp={n} (the megakernel EP-shards the expert axis)"
                )
            f = m.ctx.shard_map(
                functools.partial(_moe_reshard_shard, axis=m.axis, n=n),
                in_specs=(m.param_specs,),
                out_specs=self._moe_specs(),
            )

            def tdt_mega_moe_reshard(params):
                return f(params)

            self._moe_p = jax.jit(tdt_mega_moe_reshard)(m.params)
            jax.block_until_ready(self._moe_p)
        return self._moe_p

    @staticmethod
    def _kernel_args_moe(mp: MoEMegaParams):
        V, d = mp.embed.shape
        if V % 8:
            raise ValueError(
                f"megakernel needs vocab_size % 8 == 0, got {V}"
            )
        return (
            mp.embed.reshape(V // 8, 8, d),
            mp.wqkv, mp.wo, mp.w1, mp.w2, mp.lm_head,
            mp.ln1[:, None, :], mp.ln2[:, None, :], mp.norm[None, :],
            mp.qn[:, None, :], mp.kn[:, None, :],
            # Router weight rides after the norms ([L, d, E] — leading
            # L untiled so the gate can index the traced layer).
            mp.wrouter,
        )

    def _step_params(self):
        """What the built steps take as their first argument: the int8
        pytree under ``wq8``, the EP-resharded MoE tree for MoE models,
        the model's params otherwise."""
        if self.cfg.wq8:
            return self.quantized_params()
        if self._is_moe:
            return self.moe_params()
        return self.model.params

    def decode_fn(self, batch: int, s_max: int, page: int = 0,
                  kv_quant: bool = False, num_pages: int = 0,
                  trace: bool = False):
        """The raw (unjitted) step ``f(params, tokens, cache) →
        (logits, cache)`` — same contract as ``Qwen3.decode_fn``, so
        callers can chain steps inside one jit (``lax.fori_loop`` greedy
        decode) instead of dispatching per step. ``trace`` appends the
        device trace ring to the returns (docs/observability.md)."""
        return self._built(batch, s_max, page, kv_quant, num_pages,
                           trace)[2]

    # -- multi-step greedy decode ----------------------------------------
    def build_multi(
        self, batch: int, s_max: int, nsteps: int, sampled: bool = False,
        page: int = 0, straggler_rank: int | None = None,
        kv_quant: bool = False, num_pages: int = 0,
        valid_arg: bool = False, trace: bool = False,
        filtered: bool = False, eos: bool = False,
    ):
        """``nsteps`` greedy decode steps in ONE kernel launch.

        The LM head argmaxes in-kernel (under TP: local argmax then a
        one-shot cross-rank (value, index) exchange over ICI) and feeds
        the token back through SMEM; attention covers the launch's
        earlier steps from the knew/vnew outputs (the in-launch band);
        the caller appends all ``nsteps`` K/V rows with one contiguous
        dynamic_update_slice per batch row. Amortizes the
        per-launch/per-op dispatch cost over ``nsteps`` (its size on the
        chip is not measured in this round).

        ``sampled=True`` adds a ``noise [nsteps, B, V_pad]`` argument
        (column-sharded under TP) and the in-kernel argmax runs over
        ``logits + noise`` — with ``noise = temperature * gumbel`` this
        IS temperature sampling (Gumbel-max trick), with the RNG in
        JAX-land; the returned logits stay clean.

        ``page`` > 0 builds the paged-cache variant (pool reads through
        the page table; all ``nsteps`` new rows land with ONE scatter
        via :func:`paged_kv_cache.append_n`). ``sampled`` composes with
        ``page`` (the serving fast path: Gumbel-noise sampling over the
        paged pool), and ``kv_quant`` reads an int8 pool through its
        per-page scales — the in-launch attention band keeps the
        launch's own rows at full precision (they are quantized once,
        by the trailing ``append_n`` scatter; docs/megakernel.md
        "Serving fast path").

        Caller contract: ``kv_len[b] + nsteps <= s_max`` for every row
        — the dense append is a ``dynamic_update_slice``, whose clamped
        start would silently overwrite cached rows past capacity (the
        Engine gates its multi launches on this).

        ``filtered=True`` (requires ``sampled``, single-rank) adds a
        ``sampcfg [B, 4]`` f32 argument ``[1/temperature,
        top_k_effective, top_p, enable]`` and the in-kernel winner runs
        over the exact host top-k/top-p keep-set (bisection —
        kernels._filtered_winner); ``eos=True`` adds ``stop_tok [B]``
        i32 (-1 = none) + ``halt [B]`` i32 arguments and appends
        ``(stop_step [B], halt_out [B])`` to the returns: the kernel
        records each slot's FIRST EOS-hitting step (``nsteps`` = never),
        the shard fn clamps that slot's appended rows to ``stop_step +
        1`` and a carried ``halt`` flag zeroes halted slots' appends in
        later launches (resident pipelining — docs/megakernel.md
        "Resident decode").
        """
        if eos and not page:
            raise ValueError("eos mode rides the paged serving path only")
        if eos and not valid_arg:
            raise ValueError("eos needs valid_arg: device retire clamps "
                             "the per-slot kept-row counts")
        m = self.model
        V = m.cfg.vocab_size
        base = self._dims(batch, s_max, page, kv_quant, num_pages, trace)
        dims = dataclasses.replace(
            base, nsteps=nsteps, v_real=V, sampled=sampled,
            straggler_rank=straggler_rank, filtered=filtered, eos=eos,
        )
        mb = ModelBuilder(
            dims, cfg=self.cfg, axis=m.axis, ctx=m.ctx,
            wdtype=m.cfg.dtype, cdtype=m.cfg.dtype,
        )
        mb.build_decoder_graph()
        compiled = mb.compile(self.policy)
        per_shard = compiled.per_shard
        # Scheduled order, retrievable by trace consumers: the ring
        # decoder's dependency check (obs/kernel_trace.validate_ring)
        # needs the scoreboard edges of THIS build.
        self._last_multi_order = compiled.order
        ax = m.axis
        kernel_args, pspecs = self._args_and_specs()

        if page:
            def shard_fn(params: Qwen3Params, tokens,
                         cache: PagedKVCache, *extra):
                # Serving extras, in argument order (all optional):
                # n_valid, stop_tok, halt, noise, sampcfg.
                ex = list(extra)
                n_valid = ex.pop(0) if valid_arg else None
                stop_tok = ex.pop(0) if eos else None
                halt = ex.pop(0) if eos else None
                pre = [stop_tok] if eos else []
                outs = per_shard(
                    cache.kv_len, tokens, cache.page_table, *pre, *ex,
                    *kernel_args(params), cache.k_pages, cache.v_pages,
                    *self._scale_args(cache, kv_quant),
                )
                logits, k_rows, v_rows, toks = outs[:4]
                idx = 4
                # k_rows [NS, L, B, hkv, hd] → [L, B, hkv, NS, hd]:
                # one scatter lands all nsteps rows in the pool (int8
                # pools quantize them here, through append_n's
                # quantized_row_scatter protocol; guaranteed-overshoot
                # rows of finishing slots route to the trash page so
                # retiring pages' scales never cover garbage).
                k_rows = jnp.transpose(k_rows, (1, 2, 3, 0, 4))
                v_rows = jnp.transpose(v_rows, (1, 2, 3, 0, 4))
                if eos:
                    # Device-side retire: clamp a hitting slot's kept
                    # rows to its first EOS step (+1 keeps the EOS
                    # row itself); slots halted by a PREVIOUS launch
                    # (resident pipelining issued this one before the
                    # hit drained) append nothing — their overshoot
                    # rows route to the trash page.
                    ss = outs[idx][0]  # [B]; nsteps = never hit
                    idx += 1
                    keep = jnp.minimum(n_valid, ss + 1) * (1 - halt)
                    halt_out = jnp.maximum(
                        halt, (ss < nsteps).astype(jnp.int32)
                    )
                    ret = (
                        toks[:, 0, :], logits,
                        _paged.append_n(cache, k_rows, v_rows, keep),
                        ss, halt_out,
                    )
                else:
                    ret = (
                        toks[:, 0, :], logits,
                        _paged.append_n(cache, k_rows, v_rows, n_valid),
                    )
                if trace:  # per-rank ring, stacked on a tp leading dim
                    ret += (outs[idx][None],)
                return ret

            specs = paged_cache_specs(ax, quantized=kv_quant)
        else:
            def shard_fn(params: Qwen3Params, tokens, cache: KVCache,
                         *extra):  # noise?, sampcfg? — kernel mid order
                outs = per_shard(
                    cache.kv_len, tokens, *extra,
                    *kernel_args(params), cache.k, cache.v,
                )
                logits, k_rows, v_rows, toks = outs[:4]
                # k_rows [NS, L, B, hkv, hd] → [L, B, hkv, NS, hd]: all
                # nsteps rows land with ONE contiguous update per batch
                # row.
                k_rows = jnp.transpose(k_rows, (1, 2, 3, 0, 4))
                v_rows = jnp.transpose(v_rows, (1, 2, 3, 0, 4))
                k_new, v_new = cache.k, cache.v
                B = tokens.shape[0]
                for b in range(B):
                    at = (0, b, 0, cache.kv_len[b], 0)
                    k_new = jax.lax.dynamic_update_slice(
                        k_new, k_rows[:, b:b + 1], at
                    )
                    v_new = jax.lax.dynamic_update_slice(
                        v_new, v_rows[:, b:b + 1], at
                    )
                ret = (toks[:, 0, :], logits, KVCache(
                    k=k_new, v=v_new, kv_len=cache.kv_len + nsteps
                ))
                if trace:
                    ret += (outs[4][None],)
                return ret

            specs = cache_specs(ax)

        if valid_arg and not page:
            raise ValueError("valid_arg rides the paged append only")
        extra_specs = (P(),) if valid_arg else ()
        extra_specs += (P(), P()) if eos else ()      # stop_tok, halt
        extra_specs += (P(None, None, ax),) if sampled else ()
        extra_specs += (P(),) if filtered else ()     # sampcfg [B, 4]
        out_specs = (P(), P(None, ax), specs)
        if eos:
            out_specs += (P(), P())                   # stop_step, halt
        if trace:
            out_specs += (P(ax),)
        g = m.ctx.shard_map(
            shard_fn,
            in_specs=(pspecs, P(), specs, *extra_specs),
            out_specs=out_specs,
        )

        def tdt_mega_round(params, tokens, cache, *extra):
            toks, logits, *rest = g(params, tokens, cache, *extra)
            # toks [nsteps, B]; logits are the LAST step's (pad cols
            # dropped as in the single-step path). Trace builds append
            # the device ring [tp, NS, T, 8] as a fourth return.
            return (toks, logits[:, :V], *rest)

        # Donated cache: the nsteps-row dynamic_update_slice aliases in
        # place instead of copying the whole KV cache per launch (same
        # reasoning as the single-step build).
        return jax.jit(tdt_mega_round, donate_argnums=(2,))

    def decode_multi_fn(
        self, batch: int, s_max: int, nsteps: int, sampled: bool = False,
        page: int = 0, kv_quant: bool = False, num_pages: int = 0,
        valid_arg: bool = False, trace: bool = False,
        filtered: bool = False, eos: bool = False,
    ):
        """Jitted multi-step fn ``f(params, tokens, cache[, n_valid]
        [, noise]) → (tokens [nsteps, B], last_logits [B, V], cache
        advanced nsteps)``; the cache argument is DONATED. With
        ``sampled``, ``noise [nsteps, B, V_pad]`` f32 perturbs the
        in-kernel argmax (Gumbel-max sampling — per-slot temperatures
        ride in the noise magnitudes); ``page`` > 0 takes a
        :class:`PagedKVCache`, and ``kv_quant`` an int8 pool (both
        compose with ``sampled``). ``valid_arg`` adds the serving
        loop's ``n_valid [B]`` kept-row counts (guaranteed-overshoot
        rows route to the trash page — see ``append_n``). ``trace``
        appends the device task ring ``[tp, NS, T, 8]`` to the returns
        (docs/observability.md "Device task tracer"). ``filtered``/
        ``eos`` are the resident-serving modes — see
        :meth:`build_multi`. Cached per the full option tuple."""
        key = self._multi_key(batch, s_max, nsteps, sampled, page,
                              kv_quant, num_pages, valid_arg, trace,
                              filtered, eos)
        if key not in self._jit:
            self._jit[key] = self.build_multi(
                batch, s_max, nsteps, sampled, page,
                kv_quant=kv_quant, num_pages=num_pages,
                valid_arg=valid_arg, trace=trace,
                filtered=filtered, eos=eos,
            )
            # Scheduled order for this build, for trace consumers
            # (obs/kernel_trace.validate_ring's dependency check).
            self._orders[key] = self._last_multi_order
        return self._jit[key]

    @staticmethod
    def _multi_key(batch, s_max, nsteps, sampled=False, page=0,
                   kv_quant=False, num_pages=0, valid_arg=False,
                   trace=False, filtered=False, eos=False):
        """The ONE multi-build cache key — shared by
        :meth:`decode_multi_fn` and :meth:`multi_task_order` so the
        two can never disagree on what identifies a build."""
        return ("multi", batch, s_max, nsteps, sampled, page, kv_quant,
                num_pages, valid_arg, trace, filtered, eos)

    def multi_task_order(self, *args, **kw):
        """The scheduled task order of a multi-step build — same
        signature as :meth:`decode_multi_fn` (builds on first use).
        Ring consumers pass it to ``validate_ring`` so the decoder can
        check every scoreboard edge against the device clock."""
        self.decode_multi_fn(*args, **kw)
        return self._orders[self._multi_key(*args, **kw)]

    # -- prefill ---------------------------------------------------------
    def _build_prefill(self, s: int):
        """Build the prompt-prefill megakernel for an S-token prompt
        (parity: the reference's prefill TaskBuilders,
        ``model_builder.py:189-352``)."""
        if self._is_moe:
            raise NotImplementedError(
                "MoE prefill runs through the model path — the serving "
                "engines prefill with mode='xla' under mode='mega' "
                "(MegaDispatch._prefill_mode)"
            )
        m = self.model
        dims = dataclasses.replace(self._dims(s, s), prefill=True)
        mb = ModelBuilder(
            dims, cfg=self.cfg, axis=m.axis, ctx=m.ctx,
            wdtype=m.cfg.dtype, cdtype=m.cfg.dtype,
        )
        mb.build_prefill_graph()
        per_shard = mb.compile(self.policy).per_shard
        ax = m.axis
        wq8 = self.cfg.wq8
        kernel_args = self._kernel_args_q8 if wq8 else self._kernel_args
        pspecs = self._q8_specs() if wq8 else m.param_specs

        def shard_fn(params, tokens, true_len, cache: KVCache):
            x0 = jnp.take(params.embed, tokens, axis=0)  # [S, d] XLA gather
            logits, k_rows, v_rows, _toks = per_shard(
                true_len[None], jnp.zeros((1,), jnp.int32), x0,
                *kernel_args(params),
                # The prefill kernel never reads the cache; tiny
                # placeholders keep the operand list uniform.
                jnp.zeros((1, 1, 1, 8, 128), m.cfg.dtype),
                jnp.zeros((1, 1, 1, 8, 128), m.cfg.dtype),
            )
            # k_rows [L, hkv, S, hd] → cache entry 0, positions [0, S).
            k_new = jax.lax.dynamic_update_slice(
                cache.k, k_rows[:, None].astype(cache.k.dtype), (0, 0, 0, 0, 0)
            )
            v_new = jax.lax.dynamic_update_slice(
                cache.v, v_rows[:, None].astype(cache.v.dtype), (0, 0, 0, 0, 0)
            )
            kv_len = cache.kv_len.at[0].set(true_len)
            return logits[0], KVCache(k=k_new, v=v_new, kv_len=kv_len)

        g = m.ctx.shard_map(
            shard_fn,
            in_specs=(pspecs, P(), P(), cache_specs(ax)),
            out_specs=(P(ax), cache_specs(ax)),
        )
        V = m.cfg.vocab_size

        def tdt_mega_prompt(params, tokens, true_len, cache):
            logits, cache = g(params, tokens, true_len, cache)
            return logits[:V], cache  # drop vocab-pad logits

        return jax.jit(tdt_mega_prompt)

    def prefill(self, tokens: jax.Array, cache: KVCache, *, true_len=None):
        """Prefill one prompt (``tokens [S]``) through the megakernel;
        returns (last-real-token logits [V], cache with entry 0 filled)
        — the same return contract as ``Qwen3.prefill``. ``true_len``
        is keyword-only (there is no ``mode`` parameter here; the
        megakernel IS the mode)."""
        s = int(tokens.shape[0])
        key = ("prefill", s)
        if key not in self._jit:
            self._jit[key] = self._build_prefill(s)
        if true_len is None:
            true_len = s
        return self._jit[key](
            self._step_params(), tokens, jnp.asarray(true_len, jnp.int32),
            cache,
        )
