"""Qwen3 (dense) tensor-parallel model.

Parity: reference ``models/qwen.py`` — ``Qwen3`` with per-layer fwd modes
``torch`` / ``triton_dist`` / ``triton_dist_AR`` (:84-96), prefill
``inference``:209 and the decode path driven by ``Engine``
(``models/engine.py``). Weight names follow the HF Qwen3 checkpoint so
:func:`load_hf_state_dict` maps 1:1.

TPU design: the whole forward is ONE per-shard SPMD program under
``shard_map`` + ``jax.jit`` — every device runs the same trace on its
weight shards (column/row-parallel), the analog of the reference's
one-process-per-GPU torchrun SPMD. Layers are stacked on a leading L axis
and driven by ``lax.scan`` (one compile for all layers); the jitted,
donated decode step is the CUDA-graph analog (``engine.py:75-105``).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Literal

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint
from jax.sharding import PartitionSpec as P

from triton_distributed_tpu.layers.tp_attn import (
    TPAttnDims,
    TPAttnParams,
    tp_attn_decode,
    tp_attn_decode_paged,
    tp_attn_prefill,
    tp_attn_prefill_paged_chunk,
)
from triton_distributed_tpu.layers.tp_mlp import TPMLPParams, tp_mlp_fwd
from triton_distributed_tpu.models.config import ModelConfig
from triton_distributed_tpu.models.kv_cache import KVCache, cache_specs, init_cache
from triton_distributed_tpu.runtime.mesh import DistContext, current_context
from triton_distributed_tpu.runtime.pytree import register_param_dataclass

Mode = Literal["xla", "pallas"]


@dataclasses.dataclass
class Qwen3LayerParams:
    ln1: jax.Array  # [d] input_layernorm
    attn: TPAttnParams
    ln2: jax.Array  # [d] post_attention_layernorm
    mlp: TPMLPParams


@dataclasses.dataclass
class Qwen3Params:
    embed: jax.Array    # [V, d] replicated
    layers: Qwen3LayerParams  # leaves stacked with leading [L, ...]
    norm: jax.Array     # [d]
    lm_head: jax.Array  # [d, V] column-sharded


for _cls, _fields in ((Qwen3LayerParams, ["ln1", "attn", "ln2", "mlp"]),
                      (Qwen3Params, ["embed", "layers", "norm", "lm_head"])):
    register_param_dataclass(_cls, _fields)


def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * w.astype(jnp.float32)).astype(x.dtype)


def pad_vocab(v: int, n: int) -> int:
    """Vocab width padded to a multiple of 128·tp, so each shard's
    column count is lane-aligned (Qwen3's 151936 = 2^7·1187 leaves a
    64/96/48 residue at tp=2/4/8). The ONE definition behind
    ``_pad_lm_head``, ``MegaQwen3._dims``'s unloaded fallback, and
    ``quantized_init``."""
    align = 128 * n
    return -(-v // align) * align


class Qwen3:
    """Host-level model wrapper (parity: reference ``Qwen3``,
    ``models/qwen.py``). Holds sharded params + jitted SPMD programs."""

    def __init__(
        self,
        cfg: ModelConfig,
        *,
        axis: str = "tp",
        ctx: DistContext | None = None,
    ):
        self.cfg = cfg
        self.ctx = ctx or current_context()
        self.axis = axis
        n = self.ctx.axis_size(axis)
        if cfg.num_q_heads % n or cfg.num_kv_heads % n:
            raise ValueError(f"heads not divisible by tp={n}")
        if cfg.intermediate_size % n:
            raise ValueError(f"d_ff not divisible by tp={n}")
        self.dims = TPAttnDims(
            hq_loc=cfg.num_q_heads // n,
            hkv_loc=cfg.num_kv_heads // n,
            head_dim=cfg.head_dim,
            rope_theta=cfg.rope_theta if cfg.rope else None,
            sm_scale=cfg.attention_multiplier or None,
        )
        self.params: Qwen3Params | None = None
        self._decode_jit: dict = {}
        self._prefill_jit: dict = {}

    # -- parameter construction ------------------------------------------
    @property
    def param_specs(self) -> Qwen3Params:
        ax = self.axis
        return Qwen3Params(
            embed=P(),
            layers=Qwen3LayerParams(
                ln1=P(),
                attn=TPAttnParams(
                    wqkv=P(None, None, ax), wo=P(None, ax, None),
                    q_norm=P(), k_norm=P(),
                ),
                ln2=P(),
                mlp=TPMLPParams(w1=P(None, None, ax), w2=P(None, ax, None)),
            ),
            norm=P(),
            lm_head=P(None, ax),
        )

    def init_params(self, key: jax.Array) -> Qwen3Params:
        """Random init (tests/benchmarks; parity: the reference's
        ``rand_fill`` paths used in its perf scripts)."""
        cfg = self.cfg
        n = self.ctx.axis_size(self.axis)
        hd, d = cfg.head_dim, cfg.hidden_size
        L = cfg.num_layers
        dt = cfg.dtype

        def build(key):
            ks = iter(jax.random.split(key, 9))

            def rnd(k, *shape, scale=None):
                scale = scale if scale is not None else shape[-2] ** -0.5
                return (
                    jax.random.normal(k, shape, jnp.float32) * scale
                ).astype(dt)

            # Fused qkv, laid out per shard [q_loc | k_loc | v_loc].
            wq = rnd(next(ks), L, d, cfg.num_q_heads * hd)
            wk = rnd(next(ks), L, d, cfg.num_kv_heads * hd)
            wv = rnd(next(ks), L, d, cfg.num_kv_heads * hd)
            wqkv = _fuse_by_shard([wq, wk, wv], n)
            gate = rnd(next(ks), L, d, cfg.intermediate_size)
            up = rnd(next(ks), L, d, cfg.intermediate_size)
            w1 = _fuse_by_shard([gate, up], n)
            return Qwen3Params(
                embed=rnd(next(ks), cfg.vocab_size, d, scale=0.02),
                layers=Qwen3LayerParams(
                    ln1=jnp.ones((L, d), dt),
                    attn=TPAttnParams(
                        wqkv=wqkv,
                        wo=rnd(next(ks), L, cfg.num_q_heads * hd, d),
                        q_norm=jnp.ones((L, hd), dt),
                        k_norm=jnp.ones((L, hd), dt),
                    ),
                    ln2=jnp.ones((L, d), dt),
                    mlp=TPMLPParams(
                        w1=w1, w2=rnd(next(ks), L, cfg.intermediate_size, d)
                    ),
                ),
                norm=jnp.ones((d,), dt),
                lm_head=rnd(next(ks), d, cfg.vocab_size),
            )

        return self._set_params_jit(build, key)

    def _pad_lm_head(self, params: Qwen3Params) -> Qwen3Params:
        # Pad the LM head's vocab axis to a multiple of 128·tp: each
        # shard's column count becomes a 128-multiple, so tiled kernels
        # (the megakernel's wide lm stream) stay lane-aligned under TP
        # (Qwen3's 151936 = 2^7·1187 leaves a 64/96/48 residue at
        # tp=2/4/8). ``_logits`` slices the pads back off — zero-weight
        # columns would otherwise score 0 and could beat real logits.
        v = params.lm_head.shape[1]
        vp = pad_vocab(v, self.ctx.axis_size(self.axis))
        if vp != v:
            params = dataclasses.replace(
                params, lm_head=jnp.pad(params.lm_head, ((0, 0), (0, vp - v)))
            )
        return params

    @property
    def param_shardings(self):
        return jax.tree.map(
            lambda s: self.ctx.sharding(*s),
            self.param_specs,
            is_leaf=lambda x: isinstance(x, P),
        )

    def _set_params_jit(self, build, key: jax.Array) -> Qwen3Params:
        """Generate + pad + shard the whole param pytree in ONE compiled
        program, executed device-side: one compile (one compile-cache
        entry) instead of ~50 eager dispatches, and the weights never
        transit the host."""
        def tdt_set_params(k):
            return self._pad_lm_head(build(k))

        self.params = jax.jit(
            tdt_set_params, out_shardings=self.param_shardings
        )(key)
        return self.params

    def set_params(self, params: Qwen3Params) -> Qwen3Params:
        params = self._pad_lm_head(params)
        self.params = jax.tree.map(
            jax.device_put, params, self.param_shardings
        )
        return self.params

    # -- per-shard forward bodies ----------------------------------------
    def _mlp_fwd(self, mlp_params, h: jax.Array, mode) -> jax.Array:
        """Per-shard MLP call — overridden by the MoE model."""
        return tp_mlp_fwd(mlp_params, h, axis=self.axis, mode=mode, ctx=self.ctx)

    def _embed(self, params: Qwen3Params, tokens: jax.Array) -> jax.Array:
        return jnp.take(params.embed, tokens, axis=0)

    def _logits(self, params: Qwen3Params, x: jax.Array) -> jax.Array:
        """[B, d] → full logits [B, V] (lm_head column-sharded + gather;
        vocab padding from ``set_params`` sliced back off)."""
        loc = jnp.dot(
            x, params.lm_head, preferred_element_type=jnp.float32
        )
        full = jax.lax.all_gather(loc, self.axis, axis=1, tiled=True)
        return full[:, : self.cfg.vocab_size]

    def _decode_shard(self, params, tokens, cache: KVCache, *, mode: Mode):
        """One decode step, per-shard: ``tokens [B]`` → logits [B, V]."""
        cfg = self.cfg
        x = self._embed(params, tokens)
        ar = "pallas_ar" if mode == "pallas" else "xla_ar"

        def layer_fn(carry, inp):
            x = carry
            lp, kc, vc = inp
            h = rms_norm(x, lp.ln1, cfg.rms_eps)
            a, kc, vc = tp_attn_decode(
                lp.attn, h, kc, vc, cache.kv_len, self.dims,
                axis=self.axis, mode=ar, ctx=self.ctx,
            )
            x = x + a
            h = rms_norm(x, lp.ln2, cfg.rms_eps)
            x = x + self._mlp_fwd(lp.mlp, h, ar)
            return x, (kc, vc)

        x, (k_new, v_new) = jax.lax.scan(
            layer_fn, x, (params.layers, cache.k, cache.v)
        )
        x = rms_norm(x, params.norm, cfg.rms_eps)
        logits = self._logits(params, x)
        return logits, KVCache(k=k_new, v=v_new, kv_len=cache.kv_len + 1)

    def _layer_groups(self, params) -> list:
        """The model's layers as groups of like layers, in order: each
        ``(stacked layer params, ffn)`` with ``ffn(mlp params, h, mode,
        aux, layer) -> (y, aux)``, ``layer`` the running index over all
        groups; a group whose MIXER is not the program's attention adds
        it as a third item (:meth:`_scan_layers_paged`). One group here;
        a model whose leading layers differ from the rest (dense before
        expert layers), or whose layer list declares another mixer for
        some (recurrent layers between attention layers), returns one
        group a run of like layers, and :meth:`_scan_layers_paged` runs
        them through the one carried cache under running indices."""
        return [(
            params.layers,
            lambda mp, h, ar, aux, layer: (self._mlp_fwd(mp, h, ar), aux),
        )]

    def _scan_layers_paged(self, params, x, cache, attn_fn, mode: Mode,
                           aux=None, groups=None):
        """The layer scan of every program over a :class:`PagedKVCache`.

        The pools (and an int8 pool's scales; ``None`` on a full-width
        one, which ``lax.scan`` threads through as an empty subtree)
        ride the scan's CARRY whole, ``[L, P, hkv_loc, page, hd]``, and
        the layer index comes in as an ``xs`` scalar: ``attn_fn(attn
        params, h, k_pages, v_pages, layer, k_scale, v_scale, ar)``
        writes its rows and reads its pages in place at
        (layer, page) and hands the same arrays back. So the donated
        pool IS the loop's buffer and a step moves only the rows and
        pages it touches. Never pass the pool as ``xs``/``ys``: XLA then
        slices each layer's pool out of the input, stacks it into a
        second pool and copies that onto the donated one — at Qwen3-4B
        with 4 slots 14 ms of a 27 ms decode step, and 2.3 GiB of
        temporaries (docs/serving.md "Paged KV cache").

        One scan a group of :meth:`_layer_groups`, each carrying the
        same cache on. A group takes its mixer as it takes its ffn:
        ``(layers, ffn)`` runs ``attn_fn`` over the pool, ``(layers,
        ffn, mixer)`` runs ``mixer(mixer params, h, state, layer) ->
        (y, state)`` over the cache's recurrent state (``(ssm_state,
        conv_state)``, ``None`` where the model keeps none), which
        rides the same carry under the same layout pin and is read and
        written in place at (layer, slot). Each kind of state is
        indexed by ITS layers: a mixer's ``layer`` counts the layers
        before it that keep its kind (where every layer is an attention
        layer that is the running index itself). ``aux`` is whatever
        the groups' ffns thread through the layers (an expert layer's
        counts; ``None`` otherwise); ``groups`` overrides
        :meth:`_layer_groups`.

        Returns ``(x, k_pages, v_pages, k_scale, v_scale, aux, state)``.
        """
        cfg = self.cfg
        ar = "pallas_ar" if mode == "pallas" else "xla_ar"
        res = cfg.residual_multiplier

        def pin(p):
            return with_layout_constraint(p, Layout(tuple(range(p.ndim))))

        state = None if cache.ssm_state is None else (
            cache.ssm_state, cache.conv_state)
        carry = (x, cache.k_pages, cache.v_pages, cache.k_scale,
                 cache.v_scale, aux, state)
        start = pooled = stateful = 0
        for layers, ffn, *mixer in groups or self._layer_groups(params):
            mixer = mixer[0] if mixer else None
            n = jax.tree.leaves(layers)[0].shape[0]
            # Layers before this group that keep the OTHER kind of state.
            skip = start - (stateful if mixer else pooled)

            def layer_fn(carry, inp, ffn=ffn, mixer=mixer, skip=skip):
                x, kp, vp, ks, vs, aux, state = carry
                # Pin the carried pool row-major, the layout it is
                # donated in: left free, XLA gives the loop's pool the
                # layout of a chunk's transposed update and re-lays the
                # whole pool out before and after the loop.
                kp, vp = (pin(p) for p in (kp, vp))
                if state is not None:
                    state = tuple(pin(p) for p in state)
                lp, layer = inp
                h = rms_norm(x, lp.ln1, cfg.rms_eps)
                if mixer is None:
                    a, kp, vp, ks, vs = attn_fn(
                        lp.attn, h, kp, vp, layer - skip if skip else layer,
                        ks, vs, ar
                    )
                else:
                    a, state = mixer(lp.attn, h, state, layer - skip)
                x = x + (a if res == 1.0 else res * a)
                h = rms_norm(x, lp.ln2, cfg.rms_eps)
                y, aux = ffn(lp.mlp, h, ar, aux, layer)
                x = x + (y if res == 1.0 else res * y)
                return (x, kp, vp, ks, vs, aux, state), None

            carry, _ = jax.lax.scan(
                layer_fn, carry,
                (layers, jnp.arange(start, start + n, dtype=jnp.int32)),
            )
            start += n
            if mixer:
                stateful += n
            else:
                pooled += n
        return carry

    def _decode_shard_paged(self, params, tokens, cache, *, mode: Mode):
        """One decode step over a :class:`PagedKVCache`, per-shard.

        Same layer math as :meth:`_decode_shard`, but the attention
        appends through the page table and reads the pool directly
        (``paged_flash_decode``), in place at (layer, page)
        (:meth:`_scan_layers_paged`). Parity: the reference megakernel's
        paged decode (``mega_triton_kernel/models/paged_kv_cache.py``).
        """
        from triton_distributed_tpu.models.paged_kv_cache import PagedKVCache
        from triton_distributed_tpu.ops.attention import paged_decode_walk

        # The attention kernel's grid (its live (slot, page) pairs, each
        # row's appended token included) follows kv_len alone: derived
        # here, once a step, not in each of the scan's layers.
        walk = paged_decode_walk(
            cache.kv_len + 1, cache.k_pages.shape[3],
            cache.page_table.shape[1],
        )

        def attn(ap, h, kp, vp, layer, ks, vs, ar):
            return tp_attn_decode_paged(
                ap, h, kp, vp, layer, cache.page_table, cache.kv_len,
                self.dims, axis=self.axis, mode=ar, ctx=self.ctx,
                k_scale=ks, v_scale=vs, walk=walk,
            )

        x, k_new, v_new, ks_new, vs_new, _, _ = self._scan_layers_paged(
            params, self._embed(params, tokens), cache, attn, mode
        )
        x = rms_norm(x, params.norm, self.cfg.rms_eps)
        logits = self._logits(params, x)
        return logits, PagedKVCache(
            k_pages=k_new, v_pages=v_new,
            page_table=cache.page_table, kv_len=cache.kv_len + 1,
            k_scale=ks_new, v_scale=vs_new,
        )

    def _prefill_batch_shard(
        self, params, tokens, cache: KVCache, true_lens, *, mode: Mode
    ):
        """Prefill ``B_rows`` sequences in ONE program, per-shard (the
        single-sequence :meth:`prefill` is the B_rows=1 case).

        ``tokens [B_rows, s_loc]``: each row's sequence slice;
        activations stay sequence-sharded through all layers (ag_gemm
        gathers rows on the fly — reference ``dist_triton_fwd`` layout).
        Rows run as a ``lax.scan`` (sequential on device — prefill rows
        are compute-bound, so row parallelism buys little — but one
        dispatch replaces the host loop the reference engine also pays,
        ``models/engine.py:113``). ``true_lens[i]`` is row i's real
        prompt length: positions past it are right-padding, inert under
        causal masking; logits are taken at ``true_lens[i] - 1`` and
        ``kv_len[i]`` set to ``true_lens[i]`` so decode overwrites the
        pad KV slots. Each row computes its K/V stack without touching
        the cache; one batched write lands entries [0, B_rows) (the
        cache batch may be larger).
        """
        cfg = self.cfg
        me = jax.lax.axis_index(self.axis)
        s_loc = tokens.shape[1]

        def row_fn(_, inp):
            toks, true_len = inp
            x = self._embed(params, toks)  # [s_loc, d]

            def layer_fn(x, lp):
                h = rms_norm(x, lp.ln1, cfg.rms_eps)
                a, k_full, v_full = tp_attn_prefill(
                    lp.attn, h, self.dims, axis=self.axis, mode=mode,
                    ctx=self.ctx,
                )
                x = x + a
                h = rms_norm(x, lp.ln2, cfg.rms_eps)
                x = x + self._mlp_fwd(lp.mlp, h, mode)
                return x, (k_full, v_full)

            x, (k_all, v_all) = jax.lax.scan(layer_fn, x, params.layers)
            x = rms_norm(x, params.norm, cfg.rms_eps)
            # The last real token lives at global position true_len - 1
            # on shard (idx // s_loc); select its row, broadcast by psum.
            idx = true_len - 1
            own = jnp.where(me == idx // s_loc, 1.0, 0.0).astype(jnp.float32)
            row = jnp.take(x, idx % s_loc, axis=0)
            x_last = jax.lax.psum(row.astype(jnp.float32) * own, self.axis)
            logits = self._logits(params, x_last[None].astype(x.dtype))[0]
            # k_all [L, hkv_loc, S, hd] per row.
            return None, (logits, k_all, v_all)

        _, (logits, ks, vs) = jax.lax.scan(row_fn, None, (tokens, true_lens))
        # ks [B_rows, L, hkv, S, hd] → [L, B_rows, hkv, S, hd] at [0, S).
        k_new = jax.lax.dynamic_update_slice(
            cache.k, jnp.swapaxes(ks, 0, 1).astype(cache.k.dtype),
            (0, 0, 0, 0, 0),
        )
        v_new = jax.lax.dynamic_update_slice(
            cache.v, jnp.swapaxes(vs, 0, 1).astype(cache.v.dtype),
            (0, 0, 0, 0, 0),
        )
        kv_len = jax.lax.dynamic_update_slice(cache.kv_len, true_lens, (0,))
        return logits, KVCache(k=k_new, v=v_new, kv_len=kv_len)

    def _prefill_chunk_shard(
        self, params, tokens, cache, slot, q_offset, new_len, last_idx,
        tree_mask=None, tree_depth=None,
        *, mode: Mode, kv_pages: int | None = None,
        all_logits: bool = False,
    ):
        """Chunked-prefill one slot of a :class:`PagedKVCache`, per-shard.

        ``tokens [C]`` is one suffix chunk (right-padded; pads write
        masked/overwritten KV and are causally inert), ``q_offset`` the
        slot's already-cached length, ``new_len`` the slot's kv_len after
        this chunk (set absolutely, so interleaved decode steps bumping
        the in-flight slot's counter can never leave it skewed), and
        ``last_idx`` the chunk index whose logits are returned (the
        prompt's last real token on the final chunk; ignored upstream on
        earlier chunks). Same layer scan as :meth:`_decode_shard_paged`
        (:meth:`_scan_layers_paged`: the pool in the carry, addressed at
        (layer, page)) with chunk attention against prefix pages + chunk.

        ``tree_mask [C, C]``/``tree_depth [C]`` put the chunk in tree
        mode (speculative tree verify): rows are draft-tree nodes in DFS
        storage order, ``tree_mask[i, j]`` is 0 where node j is an
        ancestor-or-self of node i and ``-1e30`` otherwise (sibling
        branches never attend to each other), and each node ropes at
        ``q_offset + tree_depth[i]`` — its position on its OWN root
        path — while its KV still scatters at storage ``q_offset + i``.
        The [C, C] mask expands to the gathered dense view's [C, S_kv]
        additive bias once here (prefix columns fully visible, columns
        past the chunk left to causality), shared by every layer.
        """
        cfg = self.cfg
        x = self._embed(params, tokens)  # [C, d]
        table_row = cache.page_table[slot]
        rope_pos = attn_bias = None
        if tree_mask is not None:
            c = tokens.shape[0]
            page = cache.k_pages.shape[3]
            pps = table_row.shape[0] if kv_pages is None else kv_pages
            cols = jnp.arange(pps * page, dtype=jnp.int32)
            rel = jnp.clip(cols - q_offset, 0, c - 1)
            in_chunk = (cols >= q_offset) & (cols < q_offset + c)
            attn_bias = jnp.where(
                in_chunk[None, :],
                jnp.take(tree_mask.astype(jnp.float32), rel, axis=1),
                0.0,
            )  # [C, S_kv]
            rope_pos = q_offset + tree_depth

        def attn(ap, h, kp, vp, layer, ks, vs, ar):
            return tp_attn_prefill_paged_chunk(
                ap, h, kp, vp, layer, table_row, q_offset, self.dims,
                kv_pages=kv_pages, axis=self.axis, mode=ar, ctx=self.ctx,
                k_scale=ks, v_scale=vs, q_end=new_len,
                rope_pos=rope_pos, attn_bias=attn_bias,
            )

        x, k_new, v_new, ks_new, vs_new, _, _ = self._scan_layers_paged(
            params, x, cache, attn, mode
        )
        x = rms_norm(x, params.norm, cfg.rms_eps)
        if all_logits:
            # Per-position logits [C, V] — the speculative verifier
            # scores every drafted token from ONE chunk forward.
            logits = self._logits(params, x)
        else:
            x_last = jnp.take(x, last_idx, axis=0)
            logits = self._logits(params, x_last[None])[0]
        from triton_distributed_tpu.models.paged_kv_cache import PagedKVCache

        return logits, PagedKVCache(
            k_pages=k_new, v_pages=v_new, page_table=cache.page_table,
            kv_len=cache.kv_len.at[slot].set(new_len.astype(jnp.int32)),
            k_scale=ks_new, v_scale=vs_new,
        )

    def prefill_paged_chunk(
        self,
        tokens,          # [C] int32 — one (padded) suffix chunk
        slot: int,
        q_offset: int,
        new_len: int,
        last_idx: int,
        cache,           # PagedKVCache
        mode: Mode = "xla",
        kv_pages: int | None = None,
        all_logits: bool = False,
        tree_mask=None,   # [C, C] f32 — 0 visible / -1e30 masked
        tree_depth=None,  # [C] int32 — per-node depth below q_offset
    ):
        """Jitted chunked prefill of ``slot``'s suffix over the paged
        pool — the prefix-cache data plane: matched prefix pages are
        attended, only the chunk is computed. Keyed on chunk width and
        the ``kv_pages`` gather bucket only (offset/slot/lengths are
        traced), so a handful of compiled programs serve every
        admission. Returns ``(last_idx logits [V], cache)`` — or
        ``(per-position logits [C, V], cache)`` with ``all_logits=True``
        (the speculative verify path scores every chunk position).
        ``tree_mask``/``tree_depth`` (passed together) run the chunk as
        a speculative draft TREE under a tree-attention mask — one
        compiled tree program per chunk width, the mask and depths ride
        as traced operands."""
        from triton_distributed_tpu.models.paged_kv_cache import (
            paged_cache_specs,
        )

        quant = cache.k_scale is not None
        recurrent = cache.ssm_state is not None
        tree = tree_mask is not None
        if tree != (tree_depth is not None):
            raise ValueError("tree_mask and tree_depth go together")
        key = ("chunk", mode, int(tokens.shape[0]), kv_pages, all_logits,
               quant, tree)
        if key not in self._prefill_jit:
            tree_specs = (P(), P()) if tree else ()
            f = self.ctx.shard_map(
                functools.partial(self._prefill_chunk_shard, mode=mode,
                                  kv_pages=kv_pages, all_logits=all_logits),
                in_specs=(
                    self.param_specs, P(),
                    paged_cache_specs(self.axis, quant, recurrent),
                    P(), P(), P(), P(), *tree_specs,
                ),
                out_specs=(P(), paged_cache_specs(self.axis, quant,
                                                  recurrent)),
            )
            def tdt_prefill_chunk(p, t, c, s, o, n, li, *tr):
                return f(p, t, c, s, o, n, li, *tr)

            self._prefill_jit[key] = jax.jit(
                tdt_prefill_chunk, donate_argnums=(2,)
            )
        tree_args = ()
        if tree:
            tree_args = (jnp.asarray(tree_mask, jnp.float32),
                         jnp.asarray(tree_depth, jnp.int32))
        return self._prefill_jit[key](
            self.params, jnp.asarray(tokens, jnp.int32), cache,
            jnp.asarray(slot, jnp.int32), jnp.asarray(q_offset, jnp.int32),
            jnp.asarray(new_len, jnp.int32), jnp.asarray(last_idx, jnp.int32),
            *tree_args,
        )

    # -- jitted SPMD entry points ----------------------------------------
    def decode_fn(self, mode: Mode = "xla"):
        """The un-jitted shard_map'd step ``(params, tokens, cache) →
        (logits, cache)`` — composable inside callers' own jit/scan
        (bench chains steps through ``lax.fori_loop``)."""
        return self.ctx.shard_map(
            functools.partial(self._decode_shard, mode=mode),
            in_specs=(self.param_specs, P(), cache_specs(self.axis)),
            out_specs=(P(), cache_specs(self.axis)),
        )

    def decode_fn_paged(self, mode: Mode = "xla", quantized: bool = False):
        """Paged-cache analog of :meth:`decode_fn`:
        ``(params, tokens, PagedKVCache) → (logits, PagedKVCache)``.
        ``quantized`` matches an int8 pool's pytree (scale leaves ride
        the shard_map specs)."""
        from triton_distributed_tpu.models.paged_kv_cache import (
            paged_cache_specs,
        )

        return self.ctx.shard_map(
            functools.partial(self._decode_shard_paged, mode=mode),
            in_specs=(self.param_specs, P(),
                      paged_cache_specs(self.axis, quantized)),
            out_specs=(P(), paged_cache_specs(self.axis, quantized)),
        )

    def decode_step(self, tokens: jax.Array, cache, mode: Mode = "xla"):
        """Jitted one-token step for the whole batch (CUDA-graph analog).
        ``tokens [B]`` int32 → ``(logits [B, V] f32, cache)``. Accepts a
        dense :class:`KVCache` or a :class:`PagedKVCache` (full-width or
        int8-quantized — keyed separately, the pytrees differ)."""
        from triton_distributed_tpu.models.paged_kv_cache import PagedKVCache

        paged = isinstance(cache, PagedKVCache)
        quant = paged and cache.k_scale is not None
        key = (mode, "paged", quant) if paged else mode
        if key not in self._decode_jit:
            f = (
                self.decode_fn_paged(mode, quantized=quant) if paged
                else self.decode_fn(mode)
            )
            # The one program whose name holds "decode": the
            # benchmark's readers select the step by that word.
            def tdt_decode_step(p, t, c):
                return f(p, t, c)

            self._decode_jit[key] = jax.jit(
                tdt_decode_step, donate_argnums=(2,)
            )
        return self._decode_jit[key](self.params, tokens, cache)

    def prefill(
        self,
        tokens: jax.Array,
        cache: KVCache,
        mode: Mode = "xla",
        true_len: jax.Array | int | None = None,
    ):
        """Prefill one sequence (``tokens [S]``, S divisible by tp;
        right-pad to reach divisibility and pass the real length as
        ``true_len`` — trailing pads are inert under causal masking).
        Returns (last-real-token logits [V], cache with entry 0 filled).
        The B_rows=1 case of :meth:`prefill_batched` (one forward path)."""
        key = (mode, int(tokens.shape[0]))
        if true_len is None:
            true_len = tokens.shape[0]
        if key not in self._prefill_jit:
            f = self.ctx.shard_map(
                functools.partial(self._prefill_batch_shard, mode=mode),
                in_specs=(
                    self.param_specs, P(None, self.axis),
                    cache_specs(self.axis), P(),
                ),
                out_specs=(P(), cache_specs(self.axis)),
            )
            # No cache donation here: callers may alias slices of a
            # larger cache — donating would delete their buffer. The
            # per-token donation win lives in decode_step.
            def tdt_prefill_batched(p, t, c, tl):
                return f(p, t[None], c, tl[None])

            self._prefill_jit[key] = jax.jit(tdt_prefill_batched)
        logits, cache = self._prefill_jit[key](
            self.params, tokens, cache, jnp.asarray(true_len, jnp.int32)
        )
        return logits[0], cache

    def prefill_batched(
        self,
        tokens: jax.Array,  # [B, S] int32, S divisible by tp
        cache: KVCache,
        mode: Mode = "xla",
        true_lens: jax.Array | None = None,
    ):
        """Prefill every sequence of the batch in ONE jitted program
        (row scan on device; see ``_prefill_batch_shard``). Returns
        (last-real-token logits [B, V], filled cache)."""
        b, s = tokens.shape
        if true_lens is None:
            true_lens = jnp.full((b,), s, jnp.int32)
        key = ("batched", mode, b, s)
        if key not in self._prefill_jit:
            f = self.ctx.shard_map(
                functools.partial(self._prefill_batch_shard, mode=mode),
                in_specs=(
                    self.param_specs, P(None, self.axis),
                    cache_specs(self.axis), P(),
                ),
                out_specs=(P(), cache_specs(self.axis)),
            )
            def tdt_prefill_batched(p, t, c, tl):
                return f(p, t, c, tl)

            self._prefill_jit[key] = jax.jit(
                tdt_prefill_batched, donate_argnums=(2,)
            )
        return self._prefill_jit[key](
            self.params, tokens, cache, jnp.asarray(true_lens, jnp.int32)
        )

    def new_cache(self, batch_size: int, max_length: int | None = None) -> KVCache:
        return init_cache(
            self.cfg, batch_size, self.ctx, self.axis, max_length
        )


class CountedPagedStep:
    """Mixed into a model whose ONE decode program, over the paged
    cache, returns int32 sums beside its logits (``_decode_shard_paged``
    gives ``(logits, cache, counts)``; ``step_counts`` names each sum
    as the engine's ledger has it). The engine fetches them with the
    step's tokens (``_StepLaunch.counts``)."""

    step_counts: tuple = ()

    def decode_step_counted(self, tokens, cache, mode: Mode = "xla"):
        """:meth:`Qwen3.decode_step` with the step's sums beside the
        logits: ``(logits, cache, counts)``. THE decode program of the
        model (one jit, named ``tdt_decode_step`` like every model's)."""
        from triton_distributed_tpu.models.paged_kv_cache import PagedKVCache

        if not isinstance(cache, PagedKVCache):
            raise ValueError(
                f"{self.cfg.model_name} decodes over the paged pool "
                "only (--continuous, or --replicas N)")
        key = (mode, "paged")
        if key not in self._decode_jit:
            f = self.decode_fn_paged(mode)

            def tdt_decode_step(p, t, c):
                return f(p, t, c)

            self._decode_jit[key] = jax.jit(
                tdt_decode_step, donate_argnums=(2,))
        return self._decode_jit[key](self.params, tokens, cache)

    def decode_step(self, tokens, cache, mode: Mode = "xla"):
        logits, cache, _ = self.decode_step_counted(tokens, cache, mode)
        return logits, cache


def _fuse_by_shard(parts: list[jax.Array], n: int) -> jax.Array:
    """Stack column-parallel weights so each device shard is the
    concatenation of its slice of every part: ``[L, d, sum(cols)]`` with
    per-shard layout ``[p0_loc | p1_loc | ...]``."""
    L, d = parts[0].shape[:2]
    split = [p.reshape(L, d, n, p.shape[2] // n) for p in parts]
    fused = jnp.concatenate(split, axis=3)  # [L, d, n, sum_loc]
    return fused.reshape(L, d, fused.shape[2] * fused.shape[3])


def load_hf_state_dict(cfg: ModelConfig, state: dict, n: int) -> Qwen3Params:
    """Map an HF Qwen3 state dict (numpy/jnp arrays, torch layout
    ``weight [out, in]``) to :class:`Qwen3Params` (parity: reference
    weight loading, ``models/qwen.py:147-165``)."""
    L = cfg.num_layers

    def get(name):
        return jnp.asarray(state[name]).astype(cfg.dtype)

    def stack(fmt, transpose=True):
        ws = [get(fmt.format(i)) for i in range(L)]
        ws = [w.T if transpose else w for w in ws]
        return jnp.stack(ws)

    wq = stack("model.layers.{}.self_attn.q_proj.weight")
    wk = stack("model.layers.{}.self_attn.k_proj.weight")
    wv = stack("model.layers.{}.self_attn.v_proj.weight")
    gate = stack("model.layers.{}.mlp.gate_proj.weight")
    up = stack("model.layers.{}.mlp.up_proj.weight")
    embed = get("model.embed_tokens.weight")
    lm_head = (
        embed.T
        if cfg.tie_word_embeddings
        else get("lm_head.weight").T
    )
    return Qwen3Params(
        embed=embed,
        layers=Qwen3LayerParams(
            ln1=stack("model.layers.{}.input_layernorm.weight", transpose=False),
            attn=TPAttnParams(
                wqkv=_fuse_by_shard([wq, wk, wv], n),
                wo=stack("model.layers.{}.self_attn.o_proj.weight"),
                q_norm=stack(
                    "model.layers.{}.self_attn.q_norm.weight", transpose=False
                ),
                k_norm=stack(
                    "model.layers.{}.self_attn.k_norm.weight", transpose=False
                ),
            ),
            ln2=stack(
                "model.layers.{}.post_attention_layernorm.weight", transpose=False
            ),
            mlp=TPMLPParams(
                w1=_fuse_by_shard([gate, up], n),
                w2=stack("model.layers.{}.mlp.down_proj.weight"),
            ),
        ),
        norm=get("model.norm.weight"),
        lm_head=lm_head,
    )
