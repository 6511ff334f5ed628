"""The DeepSeek-V3 language block (``rednote-hilab/dots.vlm1.inst``'s
language model): latent attention in every layer over a paged latent
cache, ``first_k_dense`` leading SwiGLU layers, then expert layers with
a group-limited sigmoid router, a shared expert, and the share of the
routed experts this rank holds (docs/serving.md "Latent attention and
one rank's share").

It is :class:`Qwen3`'s serving skeleton (embedding, the paged layer scan
of :meth:`Qwen3._scan_layers_paged`, final norm, head, the jitted step
and chunk programs under the same names) over two groups of like
layers, dense then expert, which share ONE carried pool and one running
layer index. One chip holds a whole data-parallel attention replica and
its expert share: ``tp`` is 1, and the paths this model has no program
for (the dense cache, ``mega``, an int8 pool, speculation) are refused
by name.

Weights are the seed's, drawn ONE MATRIX AT A TIME so that no program
ever holds a whole stacked tensor in float32 (at the served cut the
expert weights alone are 1.9 G elements): the tensors of
:func:`weight_layout` in order, each split over its leading axes (layer,
expert) into its ``[rows, cols]`` matrices, and matrix ``j`` of them all
takes key ``j`` of ``jax.random.split(key, total)``: a normal matrix
scaled by ``rows ** -0.5`` (the embedding and the router's bias, a
vector a layer, by 0.02) and rounded to the configuration's dtype (the
bias stays float32); gate and up are one fused matrix ``[d, 2 f]``, gate
first; every norm scale is one. ``benchmark/reference_latent_moe.py``
draws the same numbers on its own.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from triton_distributed_tpu.layers.mla_attn import (
    MLA_FIELDS,
    MLADims,
    MLAParams,
    mla_decode_paged,
    mla_prefill_paged_chunk,
)
from triton_distributed_tpu.layers.moe_share import (
    MoEShareDims,
    MoEShareParams,
    moe_share_fwd,
)
from triton_distributed_tpu.layers.tp_mlp import TPMLPParams, tp_mlp_fwd
from triton_distributed_tpu.models.config import ModelConfig
from triton_distributed_tpu.models.qwen import (
    CountedPagedStep,
    Mode,
    Qwen3,
    Qwen3LayerParams,
    rms_norm,
)
from triton_distributed_tpu.runtime.mesh import DistContext
from triton_distributed_tpu.runtime.pytree import register_param_dataclass

def weight_layout(cfg: ModelConfig) -> list:
    """``(name, leading axes, matrix shape, scale or None)`` of every
    drawn tensor, in the order the seed's keys are handed out; ``d.`` is
    the leading dense layers' group, ``s.`` the expert layers'. ``None``
    scales by ``rows ** -0.5``."""
    d, h = cfg.hidden_size, cfg.num_q_heads
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    ld = cfg.first_k_dense
    ls = cfg.num_layers - ld
    held = cfg.experts_held or cfg.num_experts
    f = cfg.moe_intermediate_size
    fs = f * cfg.n_shared_experts

    def attn(g, n):
        return [
            (f"{g}.wq_a", (n,), (d, cfg.q_lora_rank), None),
            (f"{g}.wq_b", (n,), (cfg.q_lora_rank, h * qk), None),
            (f"{g}.wkv_a", (n,),
             (d, cfg.kv_lora_rank + cfg.qk_rope_head_dim), None),
            (f"{g}.wk_b", (n,),
             (cfg.kv_lora_rank, h * cfg.qk_nope_head_dim), None),
            (f"{g}.wv_b", (n,), (cfg.kv_lora_rank, h * cfg.v_head_dim), None),
            (f"{g}.wo", (n,), (h * cfg.v_head_dim, d), None),
        ]

    return [
        *attn("d", ld),
        ("d.w1", (ld,), (d, 2 * cfg.intermediate_size), None),
        ("d.w2", (ld,), (cfg.intermediate_size, d), None),
        *attn("s", ls),
        ("s.router", (ls,), (d, cfg.num_experts), None),
        ("s.bias", (ls,), (cfg.num_experts,), 0.02),
        ("s.w1", (ls, held), (d, 2 * f), None),
        ("s.w2", (ls, held), (f, d), None),
        ("s.shared_w1", (ls,), (d, 2 * fs), None),
        ("s.shared_w2", (ls,), (fs, d), None),
        ("embed", (), (cfg.vocab_size, d), 0.02),
        ("lm_head", (), (d, cfg.vocab_size), None),
    ]


@dataclasses.dataclass
class LatentMoEParams:
    embed: jax.Array             # [V, d]: the vocabulary rows held here
    dense: Qwen3LayerParams      # leading layers, stacked [Ld, ...]
    sparse: Qwen3LayerParams     # expert layers, stacked [Ls, ...]
    norm: jax.Array              # [d]
    lm_head: jax.Array           # [d, V]


register_param_dataclass(
    LatentMoEParams, ["embed", "dense", "sparse", "norm", "lm_head"])


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def tdt_draw_weights(keys, lead: tuple, mat: tuple, scale: float, dtype: str):
    """One tensor ``[*lead, *mat]``: matrix ``j`` from ``keys[j]``, one
    matrix an iteration."""
    def one(k):
        return (jax.random.normal(k, mat, jnp.float32) * scale).astype(dtype)

    return jax.lax.map(one, keys).reshape(*lead, *mat)


class LatentMoE(CountedPagedStep, Qwen3):
    """Latent attention + (dense | expert-share) feed-forward layers on
    :class:`Qwen3`'s paged serving programs."""

    # The step's two int32 sums, as the engine's ledger names them:
    # rows routed to held experts, held experts that got a row.
    step_counts = ("moe_decode_local_rows", "moe_decode_experts_touched")

    def __init__(self, cfg: ModelConfig, *, axis: str = "tp",
                 ctx: DistContext | None = None):
        super().__init__(cfg, axis=axis, ctx=ctx)
        if self.ctx.axis_size(axis) != 1:
            raise ValueError(
                f"--tp {self.ctx.axis_size(axis)}: {cfg.model_name} serves "
                "one data-parallel attention replica a chip (--tp 1); its "
                "experts are split by --experts-held / --expert-offset")
        if not 0 < cfg.first_k_dense < cfg.num_layers:
            raise ValueError(
                f"first_k_dense {cfg.first_k_dense} of {cfg.num_layers} "
                "layers: a leading dense group and an expert group are "
                "both needed")
        held = cfg.experts_held or cfg.num_experts
        if cfg.expert_offset + held > cfg.num_experts:
            raise ValueError(
                f"experts [{cfg.expert_offset}, {cfg.expert_offset + held}) "
                f"are not among the router's {cfg.num_experts}")
        self.mla = MLADims.of(cfg)
        self.share = MoEShareDims.of(cfg)

    # -- parameters --------------------------------------------------------
    def _layer_specs(self, mlp) -> Qwen3LayerParams:
        return Qwen3LayerParams(
            ln1=P(), attn=MLAParams(**{f: P() for f in MLA_FIELDS}),
            ln2=P(), mlp=mlp)

    @property
    def param_specs(self) -> LatentMoEParams:
        dense = TPMLPParams(w1=P(), w2=P())
        return LatentMoEParams(
            embed=P(),
            dense=self._layer_specs(dense),
            sparse=self._layer_specs(MoEShareParams(
                w_router=P(), bias=P(), w1=P(), w2=P(),
                shared=TPMLPParams(w1=P(), w2=P()))),
            norm=P(), lm_head=P(None, self.axis),
        )

    def init_params(self, key: jax.Array) -> LatentMoEParams:
        """The seed's weights (recipe: the module's docstring), ONE
        program a tensor: the float32 temporaries of a tensor's largest
        matrix (4.2 GB for the dense layer's gate|up) are gone before
        the next tensor is made, so 9.1 GB of weights are built under a
        peak of 11 GB. In one program XLA keeps 8.8 GB of them alive at
        once beside the outputs, and the chip's 15.75 GB do not hold it."""
        cfg = self.cfg
        d, dt = cfg.hidden_size, cfg.dtype
        layout = weight_layout(cfg)
        total = sum(math.prod(lead) for _, lead, _, _ in layout)
        keys = jax.random.split(key, total)
        if not isinstance(keys, jax.core.Tracer):
            keys = jax.device_put(keys, self.ctx.sharding())
        w, at = {}, 0
        for name, lead, mat, scale in layout:
            n = math.prod(lead)
            w[name] = tdt_draw_weights(
                keys[at: at + n], lead, mat,
                scale if scale is not None else mat[-2] ** -0.5,
                "float32" if name == "s.bias" else jnp.dtype(dt).name)
            at += n

        def layers(g, mlp):
            n = w[f"{g}.wo"].shape[0]
            return Qwen3LayerParams(
                ln1=jnp.ones((n, d), dt),
                attn=MLAParams(
                    q_norm=jnp.ones((n, cfg.q_lora_rank), dt),
                    kv_norm=jnp.ones((n, cfg.kv_lora_rank), dt),
                    **{f: w[f"{g}.{f}"] for f in MLA_FIELDS
                       if not f.endswith("norm")}),
                ln2=jnp.ones((n, d), dt), mlp=mlp)

        params = LatentMoEParams(
            embed=w["embed"],
            dense=layers("d", TPMLPParams(w1=w["d.w1"], w2=w["d.w2"])),
            sparse=layers("s", MoEShareParams(
                w_router=w["s.router"], bias=w["s.bias"],
                w1=w["s.w1"], w2=w["s.w2"],
                shared=TPMLPParams(w1=w["s.shared_w1"],
                                   w2=w["s.shared_w2"]))),
            norm=jnp.ones((d,), dt),
            lm_head=w["lm_head"],
        )
        if isinstance(keys, jax.core.Tracer):  # eval_shape: shapes only
            self.params = self._pad_lm_head(params)
            return self.params
        return self.set_params(params)

    @staticmethod
    def round_chunk(n: int) -> int:
        """Chunk widths this model compiles (``prefix_cache.round_chunk``
        for the engines): above 512 tokens a multiple of 512. A chunk
        program takes 9-21 s to compile at hidden 7168 and document
        prompts spread over 21 multiples of 128; padding a prompt by at
        most 511 tokens keeps a first set-up to six of them."""
        from triton_distributed_tpu.models.prefix_cache import round_chunk

        return -(-n // 512) * 512 if n > 512 else round_chunk(n)

    # -- per-shard forward bodies ------------------------------------------
    def _layer_groups(self, params, live=None) -> list:
        """The dense group, and the expert group with its routed
        experts' stacked ``w1`` / ``w2`` taken OUT of the scanned
        parameters: the ffn closes over them whole and names its layer
        of the group, so that a decode step reads the touched experts in
        place (``moe_share_fwd``'s ``layer``). Scanned as ``xs``, a
        layer's 16 experts are copied out for the kernel in every step."""
        def dense(mp, h, ar, aux, layer):
            return tp_mlp_fwd(mp, h, axis=self.axis, mode=ar,
                              ctx=self.ctx), aux

        moe = params.sparse.mlp

        def sparse(mp, h, ar, aux, layer):
            y, counts = moe_share_fwd(
                dataclasses.replace(mp, w1=moe.w1, w2=moe.w2), h, self.share,
                live, layer - self.cfg.first_k_dense)
            return y, aux + counts

        scanned = dataclasses.replace(
            params.sparse, mlp=dataclasses.replace(moe, w1=None, w2=None))
        return [(params.dense, dense), (scanned, sparse)]

    def _scan(self, params, x, cache, attn, mode, live=None):
        """The two groups through the one pool; returns what
        :meth:`_scan_layers_paged` returns, ``aux`` the expert layers'
        summed counts ``[2]`` (rows to held experts, held experts
        touched) over the rows ``live`` marks."""
        return self._scan_layers_paged(
            params, x, cache, attn, mode, jnp.zeros((2,), jnp.int32),
            self._layer_groups(params, live))

    def _decode_shard_paged(self, params, tokens, cache, *, mode: Mode):
        """One decode step over the latent pool, per shard: ``(logits,
        cache, counts)``. A slot counts while its table maps a page
        (an empty slot's row points at the trash page, 0)."""
        from triton_distributed_tpu.models.paged_kv_cache import PagedKVCache
        from triton_distributed_tpu.ops.attention import paged_decode_walk

        walk = paged_decode_walk(
            cache.kv_len + 1, cache.k_pages.shape[3],
            cache.page_table.shape[1],
        )

        def attn(ap, h, kp, vp, layer, ks, vs, ar):
            out, kp, vp = mla_decode_paged(
                ap, h, kp, vp, layer, cache.page_table, cache.kv_len,
                self.mla, walk=walk)
            return out, kp, vp, ks, vs

        x, k_new, v_new, _, _, counts, _ = self._scan(
            params, self._embed(params, tokens), cache, attn, mode,
            live=cache.page_table[:, 0] != 0)
        x = rms_norm(x, params.norm, self.cfg.rms_eps)
        return self._logits(params, x), PagedKVCache(
            k_pages=k_new, v_pages=v_new, page_table=cache.page_table,
            kv_len=cache.kv_len + 1,
        ), counts

    def _prefill_chunk_shard(
        self, params, tokens, cache, slot, q_offset, new_len, last_idx,
        tree_mask=None, tree_depth=None,
        *, mode: Mode, kv_pages: int | None = None,
        all_logits: bool = False,
    ):
        """Chunked-prefill one slot of the latent pool (the expanded
        attention path); :meth:`Qwen3._prefill_chunk_shard`'s contract
        without the speculative tree."""
        from triton_distributed_tpu.models.paged_kv_cache import PagedKVCache

        if tree_mask is not None or all_logits:
            raise ValueError(
                f"--speculative: {self.cfg.model_name} has no verify chunk "
                "(its prediction layer is not served)")
        table_row = cache.page_table[slot]

        def attn(ap, h, kp, vp, layer, ks, vs, ar):
            out, kp, vp = mla_prefill_paged_chunk(
                ap, h, kp, vp, layer, table_row, q_offset, self.mla,
                kv_pages=kv_pages)
            return out, kp, vp, ks, vs

        x, k_new, v_new, _, _, _, _ = self._scan(
            params, self._embed(params, tokens), cache, attn, mode)
        x = rms_norm(x, params.norm, self.cfg.rms_eps)
        logits = self._logits(params, jnp.take(x, last_idx, axis=0)[None])[0]
        return logits, PagedKVCache(
            k_pages=k_new, v_pages=v_new, page_table=cache.page_table,
            kv_len=cache.kv_len.at[slot].set(new_len.astype(jnp.int32)),
        )

    # -- jitted entry points -------------------------------------------------
    def prefill_paged_chunk(self, tokens, slot, q_offset, new_len, last_idx,
                            cache, mode: Mode = "xla", kv_pages=None, **kw):
        """:meth:`Qwen3.prefill_paged_chunk` over the slot's WHOLE table
        row, whatever gather bucket the caller asks for: one program a
        chunk width. Rebuilding keys and values for all 4,096 rows costs
        0.7 ms a layer and the causal skip leaves the rest alone, while a
        bucket makes a second program of every width whose chunk starts a
        few tokens in (a partial first-page hit of the radix cache: 9-28 s
        of compile in the middle of serving, PERF.md "PR 35")."""
        return super().prefill_paged_chunk(
            tokens, slot, q_offset, new_len, last_idx, cache, mode,
            kv_pages=None, **kw)

    def decode_fn_paged(self, mode: Mode = "xla", quantized: bool = False):
        from triton_distributed_tpu.models.paged_kv_cache import (
            paged_cache_specs,
        )

        if quantized:
            raise ValueError("--kv-dtype int8 has no latent-pool path")
        return self.ctx.shard_map(
            functools.partial(self._decode_shard_paged, mode=mode),
            in_specs=(self.param_specs, P(), paged_cache_specs(self.axis)),
            out_specs=(P(), paged_cache_specs(self.axis), P()),
        )

    def _no_dense_cache(self, *_, **__):
        raise ValueError(
            f"{self.cfg.model_name} has no dense-cache path: serve it "
            "through the paged latent pool (--continuous, or --replicas N)")

    decode_fn = prefill = prefill_batched = new_cache = _no_dense_cache
