"""Mosaic lowering proof for every Pallas kernel (VERDICT r1 #2).

Every comm/overlap/attention kernel — and the megakernel — must LOWER
for the TPU platform, not just run in interpret mode. ``jax.export``
with ``platforms=["tpu"]`` drives the real Mosaic lowering rules from
the CPU host: tracing errors, unsupported Mosaic constructs at the
lowering layer, and shape/memory-space violations all surface here.
(Lowering stops short of the Mosaic compile inside libtpu:
tests/test_chip_compile.py runs that, for a described chip, on the
main path's kernels.)

Technique: patch the context's topology to claim ``platform="tpu"`` so
``ctx.pallas_interpret()`` returns False (kernels take the Mosaic path),
then export a jitted shard_map'd call with sharded ShapeDtypeStructs.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import export
from jax.sharding import PartitionSpec as P

from triton_distributed_tpu.runtime import mesh as mesh_mod


@pytest.fixture
def tpu_ctx():
    """8-device tp mesh whose topology claims TPU (forces Mosaic path)."""
    ctx = mesh_mod.initialize_distributed(tp=8)
    ctx.topology = dataclasses.replace(ctx.topology, platform="tpu")
    yield ctx
    mesh_mod.finalize_distributed()


@pytest.fixture
def tpu_ctx4():
    ctx = mesh_mod.initialize_distributed(
        tp=4, devices=jax.devices()[:4]
    )
    ctx.topology = dataclasses.replace(ctx.topology, platform="tpu")
    yield ctx
    mesh_mod.finalize_distributed()


@pytest.fixture
def tpu_ctx1():
    ctx = mesh_mod.initialize_distributed(
        tp=1, devices=jax.devices()[:1]
    )
    ctx.topology = dataclasses.replace(ctx.topology, platform="tpu")
    yield ctx
    mesh_mod.finalize_distributed()


def _lower(ctx, fn, *specs):
    """Export ``fn`` for TPU; any Mosaic lowering rejection raises."""
    exp = export.export(jax.jit(fn), platforms=["tpu"])(*specs)
    assert len(exp.mlir_module_serialized) > 0
    return exp


def _sds(ctx, shape, spec, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=ctx.sharding(*spec))


# -- collectives ----------------------------------------------------------

class TestCollectivesLower:
    @pytest.mark.parametrize(
        "method", ["pallas_ring", "pallas_bidir_ring", "pallas_full_mesh"]
    )
    def test_all_gather(self, tpu_ctx, method):
        from triton_distributed_tpu.ops.collectives.all_gather import (
            AllGatherMethod, all_gather,
        )

        f = tpu_ctx.shard_map(
            functools.partial(
                all_gather, axis="tp", method=AllGatherMethod(method),
                ctx=tpu_ctx,
            ),
            in_specs=P("tp", None),
            out_specs=P(None, None),
        )
        _lower(tpu_ctx, f, _sds(tpu_ctx, (8 * 16, 128), ("tp", None)))

    @pytest.mark.parametrize(
        "method", ["one_shot", "pallas_ring", "pallas_ring_hbm"]
    )
    def test_reduce_scatter(self, tpu_ctx, method):
        from triton_distributed_tpu.ops.collectives.reduce_scatter import (
            ReduceScatterMethod, reduce_scatter,
        )

        f = tpu_ctx.shard_map(
            functools.partial(
                reduce_scatter, axis="tp",
                method=ReduceScatterMethod(method), ctx=tpu_ctx,
            ),
            in_specs=P(None, None),
            out_specs=P("tp", None),
        )
        _lower(tpu_ctx, f, _sds(tpu_ctx, (8 * 16, 128), (None, None)))

    @pytest.mark.parametrize("method", ["one_shot", "two_shot"])
    def test_all_reduce(self, tpu_ctx, method):
        from triton_distributed_tpu.ops.collectives.all_reduce import (
            AllReduceMethod, all_reduce,
        )

        f = tpu_ctx.shard_map(
            functools.partial(
                all_reduce, axis="tp", method=AllReduceMethod(method),
                ctx=tpu_ctx,
            ),
            in_specs=P(None, None),
            out_specs=P(None, None),
        )
        _lower(tpu_ctx, f, _sds(tpu_ctx, (16, 128), (None, None)))

    def test_broadcast(self, tpu_ctx):
        from triton_distributed_tpu.ops.collectives.broadcast import (
            BroadcastMethod, broadcast,
        )

        f = tpu_ctx.shard_map(
            functools.partial(
                broadcast, axis="tp", root=0,
                method=BroadcastMethod.ONE_SHOT, ctx=tpu_ctx,
            ),
            in_specs=P(None, None),
            out_specs=P(None, None),
        )
        _lower(tpu_ctx, f, _sds(tpu_ctx, (16, 128), (None, None)))

    def test_all_to_all(self, tpu_ctx):
        from triton_distributed_tpu.ops.collectives.all_to_all import all_to_all

        f = tpu_ctx.shard_map(
            functools.partial(
                all_to_all, axis="tp", method="pallas", ctx=tpu_ctx
            ),
            in_specs=P("tp", None),
            out_specs=P("tp", None),
        )
        _lower(tpu_ctx, f, _sds(tpu_ctx, (8 * 8, 128), ("tp", None)))


# -- overlap kernels ------------------------------------------------------

class TestOverlapLower:
    def test_ag_gemm(self, tpu_ctx):
        from triton_distributed_tpu.ops.overlap import AGGemmConfig, ag_gemm

        f = tpu_ctx.shard_map(
            functools.partial(
                ag_gemm, axis="tp", config=AGGemmConfig(tile_n=128),
                ctx=tpu_ctx,
            ),
            in_specs=(P("tp", None), P(None, "tp")),
            out_specs=P(None, "tp"),
        )
        _lower(
            tpu_ctx, f,
            _sds(tpu_ctx, (8 * 16, 128), ("tp", None)),
            _sds(tpu_ctx, (128, 8 * 128), (None, "tp")),
        )

    def test_ag_gemm_adaptive(self, tpu_ctx):
        """Arrival-adaptive schedule (semaphore_read probe + SMEM order
        output) must trace and lower for TPU — it has no interpret
        path, so this is its only off-chip gate."""
        from triton_distributed_tpu.ops.overlap import AGGemmConfig, ag_gemm

        f = tpu_ctx.shard_map(
            functools.partial(
                ag_gemm, axis="tp",
                config=AGGemmConfig(tile_n=128, adaptive=True),
                ctx=tpu_ctx,
            ),
            in_specs=(P("tp", None), P(None, "tp")),
            out_specs=P(None, "tp"),
        )
        _lower(
            tpu_ctx, f,
            _sds(tpu_ctx, (8 * 16, 128), ("tp", None)),
            _sds(tpu_ctx, (128, 8 * 128), (None, "tp")),
        )

    def test_gemm_rs_bidir_fp8(self, tpu_ctx):
        """Dual-ring + fp8 wire hop lowering."""
        import jax.numpy as jnp

        from triton_distributed_tpu.ops.overlap import GemmRSConfig, gemm_rs

        f = tpu_ctx.shard_map(
            functools.partial(
                gemm_rs, axis="tp",
                config=GemmRSConfig(
                    tile_n=128, tile_m=8, bidir=True,
                    wire_dtype=jnp.float8_e4m3fn,
                ),
                ctx=tpu_ctx,
            ),
            in_specs=(P(None, "tp"), P("tp", None)),
            out_specs=P("tp", None),
        )
        _lower(
            tpu_ctx, f,
            _sds(tpu_ctx, (8 * 16, 8 * 128), (None, "tp")),
            _sds(tpu_ctx, (8 * 128, 128), ("tp", None)),
        )

    def test_gemm_rs(self, tpu_ctx):
        from triton_distributed_tpu.ops.overlap import GemmRSConfig, gemm_rs

        f = tpu_ctx.shard_map(
            functools.partial(
                gemm_rs, axis="tp", config=GemmRSConfig(tile_n=128),
                ctx=tpu_ctx,
            ),
            in_specs=(P(None, "tp"), P("tp", None)),
            out_specs=P("tp", None),
        )
        _lower(
            tpu_ctx, f,
            _sds(tpu_ctx, (8 * 16, 8 * 32), (None, "tp")),
            _sds(tpu_ctx, (8 * 32, 128), ("tp", None)),
        )

    @pytest.mark.parametrize("method", ["one_shot", "two_shot"])
    def test_gemm_ar(self, tpu_ctx, method):
        from triton_distributed_tpu.ops.overlap import (
            GemmARConfig, GemmARMethod, gemm_ar,
        )

        f = tpu_ctx.shard_map(
            functools.partial(
                gemm_ar, axis="tp", method=GemmARMethod(method),
                config=GemmARConfig(tile_n=128), ctx=tpu_ctx,
            ),
            in_specs=(P(None, "tp"), P("tp", None)),
            out_specs=P(None, None),
        )
        _lower(
            tpu_ctx, f,
            _sds(tpu_ctx, (16, 8 * 32), (None, "tp")),
            _sds(tpu_ctx, (8 * 32, 128), ("tp", None)),
        )


# -- attention ------------------------------------------------------------

class TestAttentionLower:
    def test_flash_attention(self, tpu_ctx):
        # Single-device kernel: export unsharded (1 logical device) —
        # a sharded export would ask XLA to auto-partition the Mosaic
        # custom call, which is unsupported by design.
        from triton_distributed_tpu.ops.attention import flash_attention

        def f(q, k, v):
            return flash_attention(
                q, k, v, causal=True, block_q=128, block_k=128
            )

        s = jax.ShapeDtypeStruct((1, 4, 256, 128), jnp.float32)
        _lower(tpu_ctx, f, s, s, s)

    def test_flash_decode(self, tpu_ctx):
        from triton_distributed_tpu.ops.attention import flash_decode

        def f(q, k, v, kv_len):
            return flash_decode(q, k, v, kv_len, chunk_k=128)

        kv = jax.ShapeDtypeStruct((2, 2, 512, 128), jnp.float32)
        _lower(
            tpu_ctx, f,
            jax.ShapeDtypeStruct((2, 8, 128), jnp.float32),
            kv, kv,
            jax.ShapeDtypeStruct((2,), jnp.int32),
        )

    def test_distributed_flash_decode(self, tpu_ctx):
        from triton_distributed_tpu.ops.attention import distributed_flash_decode

        f = tpu_ctx.shard_map(
            functools.partial(
                distributed_flash_decode, axis="tp", chunk_k=128
            ),
            in_specs=(
                P(), P(None, None, "tp", None), P(None, None, "tp", None), P(),
            ),
            out_specs=P(),
        )
        _lower(
            tpu_ctx, f,
            _sds(tpu_ctx, (2, 8, 128), ()),
            _sds(tpu_ctx, (2, 2, 8 * 128, 128), (None, None, "tp", None)),
            _sds(tpu_ctx, (2, 2, 8 * 128, 128), (None, None, "tp", None)),
            jax.ShapeDtypeStruct((2,), jnp.int32),
        )

    def test_sp_ag_attention(self, tpu_ctx4):
        from triton_distributed_tpu.ops.attention import sp_ag_attention

        f = tpu_ctx4.shard_map(
            functools.partial(
                sp_ag_attention, axis="tp", block_q=64, ctx=tpu_ctx4
            ),
            in_specs=(P(None, "tp", None),) * 3,
            out_specs=P(None, "tp", None),
        )
        _lower(
            tpu_ctx4, f,
            *[_sds(tpu_ctx4, (4, 256, 128), (None, "tp", None))] * 3,
        )

    def test_ring_attention(self, tpu_ctx4):
        from triton_distributed_tpu.ops.attention import ring_attention

        f = tpu_ctx4.shard_map(
            functools.partial(
                ring_attention, axis="tp", causal=True, block_q=64,
                block_k=64,
            ),
            in_specs=(P(None, "tp", None),) * 3,
            out_specs=P(None, "tp", None),
        )
        _lower(
            tpu_ctx4, f,
            *[_sds(tpu_ctx4, (4, 256, 128), (None, "tp", None))] * 3,
        )


# -- p2p / pp -------------------------------------------------------------

class TestP2PLower:
    def test_pp_shift(self, tpu_ctx):
        from triton_distributed_tpu.parallel import pp_shift

        f = tpu_ctx.shard_map(
            functools.partial(pp_shift, axis="tp", method="pallas"),
            in_specs=P("tp", None),
            out_specs=P("tp", None),
        )
        _lower(tpu_ctx, f, _sds(tpu_ctx, (8 * 8, 128), ("tp", None)))


# -- megakernel -----------------------------------------------------------

class TestMegakernelLower:
    def test_mega_decode_step(self, tpu_ctx4):
        from triton_distributed_tpu.megakernel import MegaQwen3
        from triton_distributed_tpu.models import AutoLLM

        model = AutoLLM.from_pretrained("tiny", ctx=tpu_ctx4)
        mega = MegaQwen3(model)
        _, step, _ = mega.build(1, 64)
        cache = jax.eval_shape(lambda: model.new_cache(1, 64))
        tok = jax.ShapeDtypeStruct((1,), jnp.int32)
        params = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
            model.params,
        )
        exp = export.export(step, platforms=["tpu"])(params, tok, cache)
        assert len(exp.mlir_module_serialized) > 0

    def test_mega_tuned_config_lowers(self, tpu_ctx4):
        """The sweep-promotable config (deep staging + fused norms +
        cross-task prefetch) must lower for TPU — the trace-level gate
        for the MEGA_TUNED.json path (Mosaic itself only runs on chip;
        see module docstring)."""
        from triton_distributed_tpu.megakernel import MegaQwen3
        from triton_distributed_tpu.megakernel.code_generator import (
            MegaConfig,
        )
        from triton_distributed_tpu.models import AutoLLM

        model = AutoLLM.from_pretrained("tiny", ctx=tpu_ctx4)
        mega = MegaQwen3(
            model,
            cfg=MegaConfig(nbuf=4, fuse_norms=True, cross_prefetch=True),
        )
        f = jax.jit(mega.build_multi(1, 64, 2))
        cache = jax.eval_shape(lambda: model.new_cache(1, 64))
        tok = jax.ShapeDtypeStruct((1,), jnp.int32)
        params = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
            model.params,
        )
        exp = export.export(f, platforms=["tpu"])(params, tok, cache)
        assert len(exp.mlir_module_serialized) > 0

    def test_mega_serving_fast_path_lowers(self, tpu_ctx4):
        """The PR 7 serving-config pieces must lower for TPU: int8
        paged pool (per-page scale operands + in-register dequant in
        the attention task) and the split AR_SEND/AR_WAIT overlapped
        collectives with their REAL barrier/semaphore machinery — the
        interpret path skips barriers (kctx.interpret), so only a
        TPU-targeted trace walks them. Single-step build: the
        multi-step (in-kernel argmax) lowering is blocked at seed by
        this jax's Mosaic integer-reduction gap (see the xfailing
        multi tests above), and every piece NEW in PR 7 except the
        argmax rides the single-step program too."""
        from triton_distributed_tpu.megakernel import MegaQwen3
        from triton_distributed_tpu.megakernel.code_generator import (
            MegaConfig,
        )
        from triton_distributed_tpu.models import AutoLLM
        from triton_distributed_tpu.models.paged_kv_cache import (
            PagedKVCache,
        )

        model = AutoLLM.from_pretrained("tiny", ctx=tpu_ctx4)
        mega = MegaQwen3(model, cfg=MegaConfig(
            fuse_norms=True, cross_prefetch=True, overlap_ar=True
        ))
        B, page, pps, P_ = 2, 16, 4, 9
        _, f, _ = mega.build(
            B, page * pps, page, kv_quant=True, num_pages=P_,
        )
        cfg = model.cfg
        shape = (cfg.num_layers, P_, cfg.num_kv_heads, page,
                 cfg.head_dim)
        pool_sh = tpu_ctx4.sharding(None, None, "tp", None, None)
        sc_sh = tpu_ctx4.sharding(None, None, "tp")
        rep = tpu_ctx4.sharding()
        cache = PagedKVCache(
            k_pages=jax.ShapeDtypeStruct(shape, jnp.int8,
                                         sharding=pool_sh),
            v_pages=jax.ShapeDtypeStruct(shape, jnp.int8,
                                         sharding=pool_sh),
            page_table=jax.ShapeDtypeStruct((B, pps), jnp.int32,
                                            sharding=rep),
            kv_len=jax.ShapeDtypeStruct((B,), jnp.int32, sharding=rep),
            k_scale=jax.ShapeDtypeStruct(
                (cfg.num_layers, P_, cfg.num_kv_heads), jnp.float32,
                sharding=sc_sh,
            ),
            v_scale=jax.ShapeDtypeStruct(
                (cfg.num_layers, P_, cfg.num_kv_heads), jnp.float32,
                sharding=sc_sh,
            ),
        )
        tok = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=rep)
        params = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=x.sharding
            ),
            model.params,
        )
        exp = export.export(f, platforms=["tpu"])(params, tok, cache)
        assert len(exp.mlir_module_serialized) > 0

    def test_mega_wq8_lowers(self, tpu_ctx4):
        """Weight-only int8 decode must lower for TPU (int8 staging
        tiles, VMEM scale operands, upcast-at-MXU dots)."""
        from triton_distributed_tpu.megakernel import MegaQwen3
        from triton_distributed_tpu.megakernel.code_generator import (
            MegaConfig,
        )
        from triton_distributed_tpu.models import AutoLLM

        model = AutoLLM.from_pretrained("tiny", ctx=tpu_ctx4)
        mega = MegaQwen3(model, cfg=MegaConfig(wq8=True))
        qp = mega.quantized_params()
        f = jax.jit(mega.build_multi(1, 64, 2))
        cache = jax.eval_shape(lambda: model.new_cache(1, 64))
        tok = jax.ShapeDtypeStruct((1,), jnp.int32)
        qspec = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
            qp,
        )
        exp = export.export(f, platforms=["tpu"])(qspec, tok, cache)
        assert len(exp.mlir_module_serialized) > 0


class TestBaselineShapesLower:
    """The survey north-star shapes (M=8192, K=4096, N=12288, tp=8,
    bf16 — VERDICT r1 #3/#5) must lower for TPU: tiled staging keeps
    VMEM bounded no matter how big m_per × K grows."""

    def test_ag_gemm_baseline_shape(self, tpu_ctx):
        from triton_distributed_tpu.ops.overlap import ag_gemm
        from triton_distributed_tpu.ops.overlap.ag_gemm import (
            create_ag_gemm_context,
        )

        M, K, N = 8192, 4096, 12288
        cfg = create_ag_gemm_context(M // 8, N // 8, K, jnp.bfloat16)
        # Staging stays VMEM-bounded regardless of shard size (the
        # sweep-tuned budget caps the A double buffer, not the shard).
        from triton_distributed_tpu.ops.overlap.ag_gemm import _AG_STAGE_BUDGET

        assert cfg.tile_m * K * 2 <= _AG_STAGE_BUDGET
        big = create_ag_gemm_context(1 << 20, N // 8, K, jnp.bfloat16)
        assert big.tile_m * K * 2 <= _AG_STAGE_BUDGET
        from triton_distributed_tpu.ops.overlap import AGGemmConfig

        # Lower both the tuned config and an explicitly chunked one
        # (tile_m < m_per → num_i > 1) so the multi-M-tile staging path
        # keeps TPU-lowering coverage now that the tuned default stages
        # the whole 1024-row shard in one tile.
        for c in (cfg, AGGemmConfig(tile_n=512, tile_m=256)):
            f = tpu_ctx.shard_map(
                functools.partial(ag_gemm, axis="tp", config=c, ctx=tpu_ctx),
                in_specs=(P("tp", None), P(None, "tp")),
                out_specs=P(None, "tp"),
            )
            _lower(
                tpu_ctx, f,
                _sds(tpu_ctx, (M, K), ("tp", None), jnp.bfloat16),
                _sds(tpu_ctx, (K, N), (None, "tp"), jnp.bfloat16),
            )

    def test_gemm_rs_baseline_shape(self, tpu_ctx):
        from triton_distributed_tpu.ops.overlap import gemm_rs
        from triton_distributed_tpu.ops.overlap.gemm_rs import (
            create_gemm_rs_context,
        )

        M, K, N = 8192, 12288, 4096  # down-proj: k_loc = K/8
        cfg = create_gemm_rs_context(M, N, K // 8, jnp.bfloat16, n_ranks=8)
        f = tpu_ctx.shard_map(
            functools.partial(gemm_rs, axis="tp", config=cfg, ctx=tpu_ctx),
            in_specs=(P(None, "tp"), P("tp", None)),
            out_specs=P("tp", None),
        )
        _lower(
            tpu_ctx, f,
            _sds(tpu_ctx, (M, K), (None, "tp"), jnp.bfloat16),
            _sds(tpu_ctx, (K, N), ("tp", None), jnp.bfloat16),
        )


class TestLowLatencyLower:
    def test_ll_all_gather_barrier_free(self, tpu_ctx):
        """The TPU (barrier-free, ack-semaphore) variant must lower."""
        from triton_distributed_tpu.ops import (
            ll_all_gather, ll_all_gather_workspace,
        )

        def body(x, ws, phase):
            return ll_all_gather(
                x, ws, phase, axis="tp", ctx=tpu_ctx, barrier_free=True
            )

        f = tpu_ctx.shard_map(
            body,
            in_specs=(P("tp", None), P(), P()),
            out_specs=(P(None, None), P()),
        )
        ws = jax.eval_shape(
            lambda: ll_all_gather_workspace(8, 16, 128, jnp.float32)
        )
        ws = jax.ShapeDtypeStruct(ws.shape, ws.dtype, sharding=tpu_ctx.sharding())
        _lower(
            tpu_ctx, f,
            _sds(tpu_ctx, (8 * 16, 128), ("tp", None)),
            ws,
            jax.ShapeDtypeStruct((), jnp.int32, sharding=tpu_ctx.sharding()),
        )

    @pytest.mark.parametrize("nranks", [1, 4])
    @pytest.mark.parametrize("sampled", [False, True])
    def test_mega_multi_step_decode(self, request, nranks, sampled):
        """The multi-step kernel (2-D grid, SMEM token feedback, band
        attention, in-kernel argmax) must lower for TPU — including the
        tp>1 cross-rank argmax exchange and the Gumbel-noise input."""
        from triton_distributed_tpu.megakernel import MegaQwen3
        from triton_distributed_tpu.models import AutoLLM

        ctx = request.getfixturevalue(f"tpu_ctx{nranks}")
        model = AutoLLM.from_pretrained("tiny", ctx=ctx)
        mega = MegaQwen3(model)
        f = jax.jit(mega.build_multi(1, 64, 4, sampled=sampled))
        cache = jax.eval_shape(lambda: model.new_cache(1, 64))
        tok = jax.ShapeDtypeStruct((1,), jnp.int32)
        params = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
            model.params,
        )
        args = [params, tok, cache]
        if sampled:
            v_pad = model.params.lm_head.shape[1]
            args.append(
                jax.ShapeDtypeStruct((4, 1, v_pad), jnp.float32)
            )
        exp = export.export(f, platforms=["tpu"])(*args)
        assert len(exp.mlir_module_serialized) > 0


class TestBidirRSLower:
    def test_reduce_scatter_bidir(self, tpu_ctx):
        import functools

        from triton_distributed_tpu.ops.collectives.reduce_scatter import (
            ReduceScatterMethod,
            reduce_scatter,
        )

        f = tpu_ctx.shard_map(
            functools.partial(
                reduce_scatter, axis="tp",
                method=ReduceScatterMethod.PALLAS_BIDIR_RING, ctx=tpu_ctx,
            ),
            in_specs=P(None, None),
            out_specs=P("tp", None),
        )
        _lower(tpu_ctx, f, _sds(tpu_ctx, (8 * 8, 128), (None, None)))


class TestEPExchangeLower:
    def test_ep_exchange(self, tpu_ctx):
        """The device-initiated EP transport is the AUTO default on real
        TPU — its Mosaic lowering (dynamic-trip fori_loop waits,
        put_signal under pl.when, SMEM scalar bounds) needs an off-chip
        gate like every other TPU-only kernel."""
        import functools

        import jax.numpy as jnp

        from triton_distributed_tpu.ops.moe.ep_exchange import ep_exchange

        n = 8

        def body(rows, splits, counts):
            return ep_exchange(rows, splits, counts, axis="tp", ctx=tpu_ctx)

        f = tpu_ctx.shard_map(
            functools.partial(body),
            in_specs=(P(None, None, None), P(None), P(None)),
            out_specs=P(None, None, None),
        )
        _lower(
            tpu_ctx, f,
            _sds(tpu_ctx, (n, 64, 256), (None, None, None), jnp.uint8),
            _sds(tpu_ctx, (n,), (None,), jnp.int32),
            _sds(tpu_ctx, (n,), (None,), jnp.int32),
        )

    def test_ep_moe_ffn_pallas(self, tpu_ctx):
        """Whole EP MoE layer with the device transport lowers."""
        import functools

        from triton_distributed_tpu.ops.moe import ep_moe_ffn

        f = tpu_ctx.shard_map(
            functools.partial(
                ep_moe_ffn, k=2, axis="tp", method="pallas", ctx=tpu_ctx
            ),
            in_specs=(P("tp", None), P(), P("tp", None, None),
                      P("tp", None, None)),
            out_specs=P("tp", None),
        )
        _lower(
            tpu_ctx, f,
            _sds(tpu_ctx, (8 * 8, 128), ("tp", None)),
            _sds(tpu_ctx, (128, 16), (None, None)),
            _sds(tpu_ctx, (16, 128, 2 * 128), ("tp", None, None)),
            _sds(tpu_ctx, (16, 128, 128), ("tp", None, None)),
        )


class TestHeadlineGeometryLower:
    """Qwen3-1.7B / Qwen3-4B geometry: their per-layer dims
    (d=2048/2560, o_k=4096, f=6144/9728) must lower BEFORE chip time
    is spent on them. Layers/vocab are reduced — they change tile
    counts, not tile shapes."""

    @pytest.mark.parametrize("preset", ["Qwen/Qwen3-1.7B", "Qwen/Qwen3-4B"])
    def test_mega_multi_lowers(self, tpu_ctx1, preset):
        from triton_distributed_tpu.megakernel import MegaQwen3
        from triton_distributed_tpu.models import AutoLLM

        model = AutoLLM.from_pretrained(
            preset, ctx=tpu_ctx1, max_length=128,
            num_layers=2, vocab_size=32768,
        )
        mega = MegaQwen3(model)
        f = jax.jit(mega.build_multi(1, 128, 4))
        cache = jax.eval_shape(lambda: model.new_cache(1, 128))
        tok = jax.ShapeDtypeStruct((1,), jnp.int32)
        params = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=x.sharding
            ),
            model.params,
        )
        exp = export.export(f, platforms=["tpu"])(params, tok, cache)
        assert len(exp.mlir_module_serialized) > 0

    def test_mega_q8_synth_8b_geometry_lowers(self, tpu_ctx1):
        """The beyond-HBM path (perf/ladder_q8_synth.py): 8B-geometry
        wq8 decode from synthesized Q8Params, no bf16 tree."""
        from triton_distributed_tpu.megakernel import MegaQwen3
        from triton_distributed_tpu.megakernel.code_generator import (
            MegaConfig,
        )
        from triton_distributed_tpu.models.config import get_config
        from triton_distributed_tpu.models.qwen import Qwen3

        cfg = get_config(
            "Qwen/Qwen3-8B", max_length=128,
            num_layers=2, vocab_size=32768,
        )
        model = Qwen3(cfg, ctx=tpu_ctx1)  # params stay None
        mega = MegaQwen3(model, cfg=MegaConfig(wq8=True))
        qp = mega.quantized_init(jax.random.PRNGKey(0))
        f = jax.jit(mega.build_multi(1, 128, 4))
        cache = jax.eval_shape(lambda: model.new_cache(1, 128))
        tok = jax.ShapeDtypeStruct((1,), jnp.int32)
        qshapes = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=x.sharding
            ),
            qp,
        )
        exp = export.export(f, platforms=["tpu"])(qshapes, tok, cache)
        assert len(exp.mlir_module_serialized) > 0
