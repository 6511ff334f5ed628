"""A hybrid of Mamba-2 layers and GQA attention layers
(``ibm-granite/granite-4.0-h-micro``, Hugging Face
``GraniteMoeHybridForCausalLM`` without routed experts), served whole
on one chip (docs/serving.md "Recurrent state beside pages").

The layer list is DECLARED (``cfg.layer_types``, a kind a layer) and
everything follows from it: a run of like layers is one scan group of
:class:`Qwen3`'s paged skeleton (m5, a, m9, a, m9, a, m9, a, m4 as
published), an attention run through the paged pool, which holds the
attention layers only, a Mamba-2 run through the per-slot recurrent
state that rides the same carry, each kind indexed by ITS layers
(:meth:`Qwen3._scan_layers_paged`). Every layer has the same SwiGLU. An
attention layer has no positions, no q/k norm and the stated softmax
scale (``TPAttnDims``); the block is ``h += r mixer(rms(h))``, ``h += r
mlp(rms(h))`` with the residual multiplier ``r``, the input ``embed[id]
x embedding_multiplier``, the output ``rms(h) W_head /
logits_scaling``.

What a slot keeps (``cfg.slot_keeps``): K/V pages for the attention
layers AND a recurrent state, which is not pages. So this model has the
single-step programs over a full-width pool on one chip, and the paths
with no recurrent-state program are refused by the flag that asks: the
dense cache, ``mega``, an int8 pool, speculation (no roll-back of a
state), slot export, ``tp > 1``.

Weights are the seed's, ONE MATRIX A KEY as :mod:`latent_moe` draws
them: the tensors of :func:`weight_layout` in order, each split over its
leading (layer) axis, matrix ``j`` of them all from key ``j`` of
``jax.random.split(key, total)``; a matrix is normal times ``rows **
-0.5`` (the embedding 0.02; the convolution's ``[taps, channels]``
therefore ``taps ** -0.5``) rounded to the served dtype. What is no
matrix takes the published initial ranges, float32: ``A = uniform(1,
16)`` (``A_log`` its log), ``dt_bias`` the inverse softplus of a
log-uniform step in ``[1e-3, 1e-1]``, ``D`` one, the convolution's bias
nought, every norm scale one. q, k and v are one matrix ``[d, (hq + 2
hkv) hd]``, gate and up one ``[d, 2 f]``. The head is drawn on its own
though the published model ties it to the embedding (random tied
weights would make every token predict itself).
``benchmark/reference_hybrid_ssm.py`` draws the same numbers on its own.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from triton_distributed_tpu.layers.mamba2 import (
    MAMBA2_FIELDS,
    Mamba2Dims,
    Mamba2Params,
    mamba2_chunk,
    mamba2_decode,
)
from triton_distributed_tpu.layers.tp_attn import (
    TPAttnParams,
    tp_attn_decode_paged,
    tp_attn_prefill_paged_chunk,
)
from triton_distributed_tpu.layers.tp_mlp import TPMLPParams
from triton_distributed_tpu.models.config import ModelConfig
from triton_distributed_tpu.models.latent_moe import tdt_draw_weights
from triton_distributed_tpu.models.qwen import (
    CountedPagedStep,
    Mode,
    Qwen3,
    Qwen3LayerParams,
    rms_norm,
)
from triton_distributed_tpu.ops.ssm.decode import live_rows
from triton_distributed_tpu.runtime.mesh import DistContext
from triton_distributed_tpu.runtime.pytree import register_param_dataclass

DT_MIN, DT_MAX = 1e-3, 1e-1
A_MIN, A_MAX = 1.0, 16.0


def layer_runs(layer_types) -> list:
    """``[(kind, first layer, layers)]``: the runs of like layers."""
    runs, at = [], 0
    for kind, group in itertools.groupby(layer_types):
        n = len(list(group))
        runs.append((kind, at, n))
        at += n
    return runs


def weight_layout(cfg: ModelConfig) -> list:
    """``(name, layers, shape, scale or kind)`` of every drawn tensor,
    in the order the seed's keys are handed out: ``m.`` over the Mamba-2
    layers, ``a.`` over the attention layers, ``f.`` over all layers,
    each in layer order. A scale of ``None`` is ``rows ** -0.5``;
    ``"a"`` and ``"dt"`` are the two vectors with a range of their
    own."""
    d, f = cfg.hidden_size, cfg.intermediate_size
    m = Mamba2Dims.of(cfg)
    lm, la = cfg.mamba_layers, cfg.attention_layers
    qkv = (cfg.num_q_heads + 2 * cfg.num_kv_heads) * cfg.head_dim
    return [
        ("m.w_in", lm, (d, m.in_dim), None),
        ("m.conv_w", lm, (m.taps, m.conv_dim), None),
        ("m.a_log", lm, (m.heads,), "a"),
        ("m.dt_bias", lm, (m.heads,), "dt"),
        ("m.w_out", lm, (m.inner, d), None),
        ("a.wqkv", la, (d, qkv), None),
        ("a.wo", la, (cfg.num_q_heads * cfg.head_dim, d), None),
        ("f.w1", cfg.num_layers, (d, 2 * f), None),
        ("f.w2", cfg.num_layers, (f, d), None),
        ("embed", 1, (cfg.vocab_size, d), 0.02),
        ("lm_head", 1, (d, cfg.vocab_size), None),
    ]


@functools.partial(jax.jit, static_argnums=(1, 2))
def tdt_draw_vectors(keys, width: int, kind: str):
    """``[len(keys), width]`` float32: ``log A`` with ``A`` uniform in
    ``[1, 16)`` (``"a"``), or the inverse softplus of a step log-uniform
    in ``[1e-3, 1e-1)`` (``"dt"``), a vector a key."""
    u = jax.vmap(lambda k: jax.random.uniform(k, (width,), jnp.float32))(keys)
    if kind == "a":
        return jnp.log(A_MIN + u * (A_MAX - A_MIN))
    dt = jnp.exp(math.log(DT_MIN) + u * (math.log(DT_MAX) - math.log(DT_MIN)))
    return dt + jnp.log(-jnp.expm1(-dt))


@dataclasses.dataclass
class HybridSSMParams:
    embed: jax.Array    # [V, d]
    runs: tuple         # a Qwen3LayerParams a run of like layers, stacked
    norm: jax.Array     # [d]
    lm_head: jax.Array  # [d, V]


register_param_dataclass(HybridSSMParams, ["embed", "runs", "norm", "lm_head"])


class HybridSSM(CountedPagedStep, Qwen3):
    """Mamba-2 and attention layers, as the layer list declares them,
    on :class:`Qwen3`'s paged serving programs."""

    # What the step's int32 sums beside its logits count, as the
    # engine's ledger names them (``ContinuousEngine._emit_step``).
    step_counts = ("ssm_decode_rows",)

    def __init__(self, cfg: ModelConfig, *, axis: str = "tp",
                 ctx: DistContext | None = None):
        super().__init__(cfg, axis=axis, ctx=ctx)
        if self.ctx.axis_size(axis) != 1:
            raise ValueError(
                f"--tp {self.ctx.axis_size(axis)}: {cfg.model_name} is "
                "served whole on one chip (--tp 1): its recurrent state "
                "and its one group of B / C have no sharded program")
        if len(cfg.layer_types) != cfg.num_layers or set(
                cfg.layer_types) - {"attention", "mamba"}:
            raise ValueError(
                f"layer_types names {len(cfg.layer_types)} layers "
                f"{sorted(set(cfg.layer_types))} for num_layers "
                f"{cfg.num_layers}: one of 'attention' / 'mamba' a layer")
        if not cfg.mamba_layers or not cfg.attention_layers:
            raise ValueError(
                f"{cfg.model_name}: a hybrid has layers of both kinds")
        self.mamba = Mamba2Dims.of(cfg)
        self.runs = layer_runs(cfg.layer_types)

    # -- parameters --------------------------------------------------------
    @property
    def param_specs(self) -> HybridSSMParams:
        mixers = {
            "mamba": Mamba2Params(**{f: P() for f in MAMBA2_FIELDS}),
            "attention": TPAttnParams(wqkv=P(), wo=P(), q_norm=None,
                                      k_norm=None),
        }
        return HybridSSMParams(
            embed=P(),
            runs=tuple(
                Qwen3LayerParams(ln1=P(), attn=mixers[kind], ln2=P(),
                                 mlp=TPMLPParams(w1=P(), w2=P()))
                for kind, _, _ in self.runs),
            norm=P(), lm_head=P(None, self.axis),
        )

    def init_params(self, key: jax.Array) -> HybridSSMParams:
        """The seed's weights (recipe: the module's docstring), one
        program a tensor and run of layers, as :class:`LatentMoE` makes
        its own: no program holds a whole stacked tensor in float32."""
        cfg, m = self.cfg, self.mamba
        d, dt = cfg.hidden_size, cfg.dtype
        layout = weight_layout(cfg)
        keys = jax.random.split(key, sum(n for _, n, _, _ in layout))
        shapes_only = isinstance(keys, jax.core.Tracer)
        if not shapes_only:
            keys = jax.device_put(keys, self.ctx.sharding())
        first, at = {}, 0
        for name, n, _, _ in layout:
            first[name] = at
            at += n
        spec = {name: (shape, how) for name, _, shape, how in layout}

        def draw(name, start=0, n=1):
            """Layers ``[start, start + n)`` of the tensor ``name``."""
            shape, how = spec[name]
            ks = keys[first[name] + start: first[name] + start + n]
            if how in ("a", "dt"):
                return tdt_draw_vectors(ks, shape[0], how)
            return tdt_draw_weights(
                ks, (n,), shape, how if how is not None else shape[-2] ** -0.5,
                jnp.dtype(dt).name)

        runs, seen = [], {"mamba": 0, "attention": 0}
        for kind, start, n in self.runs:
            k0 = seen[kind]
            seen[kind] += n
            if kind == "mamba":
                mixer = Mamba2Params(
                    w_in=draw("m.w_in", k0, n),
                    conv_w=draw("m.conv_w", k0, n),
                    conv_b=jnp.zeros((n, m.conv_dim), dt),
                    dt_bias=draw("m.dt_bias", k0, n),
                    a_log=draw("m.a_log", k0, n),
                    d_skip=jnp.ones((n, m.heads), jnp.float32),
                    norm=jnp.ones((n, m.inner), dt),
                    w_out=draw("m.w_out", k0, n))
            else:
                mixer = TPAttnParams(
                    wqkv=draw("a.wqkv", k0, n), wo=draw("a.wo", k0, n),
                    q_norm=None, k_norm=None)
            runs.append(Qwen3LayerParams(
                ln1=jnp.ones((n, d), dt), attn=mixer,
                ln2=jnp.ones((n, d), dt),
                mlp=TPMLPParams(w1=draw("f.w1", start, n),
                                w2=draw("f.w2", start, n))))
        params = HybridSSMParams(
            embed=draw("embed")[0], runs=tuple(runs),
            norm=jnp.ones((d,), dt), lm_head=draw("lm_head")[0])
        if shapes_only:  # eval_shape
            self.params = self._pad_lm_head(params)
            return self.params
        return self.set_params(params)

    def round_chunk(self, n: int) -> int:
        """Chunk widths this model compiles: what the tiles take
        (``prefix_cache.round_chunk``), rounded up to whole blocks of
        the chunked recurrence (256 as published: a prompt of up to
        1,024 tokens is one of four programs)."""
        from triton_distributed_tpu.models.prefix_cache import round_chunk

        block = self.mamba.chunk
        return -(-round_chunk(n) // block) * block

    # -- per-shard forward bodies ------------------------------------------
    def _embed(self, params, tokens):
        return super()._embed(params, tokens) * self.cfg.embedding_multiplier

    def _logits(self, params, x):
        return super()._logits(params, x) / self.cfg.logits_scaling

    def _layer_groups(self, params, mixer=None) -> list:
        """A group a run of like layers; a Mamba-2 run brings ``mixer``
        (the program's: a decode step's or a chunk's), an attention run
        takes the program's attention."""
        def ffn(mp, h, ar, aux, layer):
            return self._mlp_fwd(mp, h, ar), aux

        return [
            (layers, ffn) if kind == "attention" else (layers, ffn, mixer)
            for (kind, _, _), layers in zip(self.runs, params.runs)
        ]

    def _decode_shard_paged(self, params, tokens, cache, *, mode: Mode):
        """One decode step, per shard: ``(logits, cache, counts [1])``.
        Every row is computed; only the rows ``cache.live`` marks (the
        slots the engine has in flight) move their recurrent state,
        and ``counts`` says how many those were."""
        from triton_distributed_tpu.ops.attention import paged_decode_walk

        walk = paged_decode_walk(
            cache.kv_len + 1, cache.k_pages.shape[3],
            cache.page_table.shape[1],
        )
        rows, n = live_rows(cache.live)

        def attn(ap, h, kp, vp, layer, ks, vs, ar):
            return tp_attn_decode_paged(
                ap, h, kp, vp, layer, cache.page_table, cache.kv_len,
                self.dims, axis=self.axis, mode=ar, ctx=self.ctx, walk=walk,
            )

        def mixer(mp, h, state, layer):
            return mamba2_decode(mp, h, state, layer, self.mamba,
                                 live=cache.live, rows=rows, n=n)

        x, k_new, v_new, _, _, _, (ssm, conv) = self._scan_layers_paged(
            params, self._embed(params, tokens), cache, attn, mode,
            groups=self._layer_groups(params, mixer))
        x = rms_norm(x, params.norm, self.cfg.rms_eps)
        return self._logits(params, x), dataclasses.replace(
            cache, k_pages=k_new, v_pages=v_new, kv_len=cache.kv_len + 1,
            ssm_state=ssm, conv_state=conv,
        ), jnp.reshape(n, (1,))

    def _prefill_chunk_shard(
        self, params, tokens, cache, slot, q_offset, new_len, last_idx,
        tree_mask=None, tree_depth=None,
        *, mode: Mode, kv_pages: int | None = None,
        all_logits: bool = False,
    ):
        """Chunked-prefill one slot: :meth:`Qwen3._prefill_chunk_shard`'s
        contract without the speculative tree. The slot's recurrent
        state is carried over from the chunk before (zeros at
        ``q_offset == 0``), moved by the real positions only, and
        written back absolutely."""
        if tree_mask is not None or all_logits:
            raise ValueError(
                f"--speculative: {self.cfg.model_name} has no verify chunk "
                "(a rejected draft cannot be rolled back out of a "
                "recurrent state)")
        table_row = cache.page_table[slot]

        def attn(ap, h, kp, vp, layer, ks, vs, ar):
            return tp_attn_prefill_paged_chunk(
                ap, h, kp, vp, layer, table_row, q_offset, self.dims,
                kv_pages=kv_pages, axis=self.axis, mode=ar, ctx=self.ctx,
                q_end=new_len,
            )

        def mixer(mp, h, state, layer):
            return mamba2_chunk(mp, h, state, layer, self.mamba, slot=slot,
                                q_offset=q_offset, n_real=new_len - q_offset)

        x, k_new, v_new, _, _, _, (ssm, conv) = self._scan_layers_paged(
            params, self._embed(params, tokens), cache, attn, mode,
            groups=self._layer_groups(params, mixer))
        x = rms_norm(x, params.norm, self.cfg.rms_eps)
        logits = self._logits(params, jnp.take(x, last_idx, axis=0)[None])[0]
        return logits, dataclasses.replace(
            cache, k_pages=k_new, v_pages=v_new,
            kv_len=cache.kv_len.at[slot].set(new_len.astype(jnp.int32)),
            ssm_state=ssm, conv_state=conv,
        )

    # -- jitted entry points -------------------------------------------------
    def decode_fn_paged(self, mode: Mode = "xla", quantized: bool = False):
        from triton_distributed_tpu.models.paged_kv_cache import (
            paged_cache_specs,
        )

        if quantized:
            raise ValueError(
                f"--kv-dtype int8: {self.cfg.model_name} has no int8 path")
        specs = paged_cache_specs(self.axis, recurrent=True)
        return self.ctx.shard_map(
            functools.partial(self._decode_shard_paged, mode=mode),
            in_specs=(self.param_specs, P(), specs),
            out_specs=(P(), specs, P()),
        )

    def _no_dense_cache(self, *_, **__):
        raise ValueError(
            f"{self.cfg.model_name} has no dense-cache path: serve it "
            "through the paged pool and its per-slot recurrent state "
            "(--continuous, or --replicas N)")

    decode_fn = prefill = prefill_batched = new_cache = _no_dense_cache
