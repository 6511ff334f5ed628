"""The main path's kernels, compiled by the TPU's own compiler for a
described (not attached) v5e at the widths the chip run uses.

Interpret mode and ``jax.export`` lowering (tests/test_tpu_lowering.py)
both stop short of Mosaic's compile, which is where a block below the
(8, 128) tile, an unaligned slice or too much VMEM is refused. These
compiles cost no chip time and guard every later PR. A compile that
passes is not a chip run: ``chip_smoke.py`` is that.

The topology is described inside the module-scoped ``topo`` fixture —
never at import, in a ``skipif`` or in ``parametrize`` — because only
one process at a time may load the TPU library: under xdist every
worker imports this file, and only the worker that runs it may load
it. Everything compiles in this process, with the persistent compile
cache off (an entry written for a described chip cannot be read back
without one, and warns) and with the program's own matmul precision:
conftest.py asks for "highest" for the CPU's numerics, which Mosaic
refuses on bf16 operands and which no chip run sets.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from triton_distributed_tpu.runtime import mesh as mesh_mod

# Qwen3-4B head geometry on one chip, ContinuousEngine's default pool.
HQ, HKV, D, PAGE = 32, 8, 128, 128
BATCH, PPS = 4, 32
NUM_PAGES = BATCH * PPS + 1
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure to describe means skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cache_was = jax.config.jax_enable_compilation_cache
    precision_was = jax.config.jax_default_matmul_precision
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_default_matmul_precision", None)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", cache_was)
    jax.config.update("jax_default_matmul_precision", precision_was)
    compilation_cache.reset_cache()


@pytest.fixture
def chip(topo):
    """A one-chip context on the described device: ``on_tpu`` is true,
    so every kernel takes its Mosaic branch."""
    ctx = mesh_mod.initialize_distributed(tp=1, devices=[topo.devices[0]])
    assert ctx.on_tpu and ctx.pallas_interpret() is False
    yield ctx
    mesh_mod.finalize_distributed()


@pytest.fixture
def chips4(topo):
    ctx = mesh_mod.initialize_distributed(tp=4, devices=list(topo.devices))
    assert ctx.topology.mesh_rung == "snake_ring"
    yield ctx
    mesh_mod.finalize_distributed()


def sds(ctx, shape, dtype, *spec):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=ctx.sharding(*spec))


def compile_for_chip(fn, *args) -> str:
    """Compile; returns the optimized HLO text. Raises what the chip's
    compiler would raise."""
    return jax.jit(fn).lower(*args).compile().as_text()


def pool(ctx, dtype):
    return sds(ctx, (NUM_PAGES, HKV, PAGE, D), dtype)


@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_paged_flash_decode(chip, kv):
    from triton_distributed_tpu.ops.attention.flash_decode import (
        paged_flash_decode,
    )

    args = [sds(chip, (BATCH, HQ, D), BF16),
            pool(chip, BF16 if kv == "bf16" else jnp.int8),
            pool(chip, BF16 if kv == "bf16" else jnp.int8),
            sds(chip, (BATCH, PPS), jnp.int32),
            sds(chip, (BATCH,), jnp.int32)]
    fn = paged_flash_decode
    if kv == "int8":
        scale = sds(chip, (NUM_PAGES, HKV), jnp.float32)
        args += [scale, scale]
        fn = lambda q, k, v, t, n, ks, vs: paged_flash_decode(  # noqa: E731
            q, k, v, t, n, k_scale=ks, v_scale=vs)
    assert "tpu_custom_call" in compile_for_chip(fn, *args)


@pytest.mark.parametrize(
    "variant", ["prefill", "traced_offset_chunk", "int8_chunk", "tree_bias"]
)
def test_flash_attention(chip, variant):
    from triton_distributed_tpu.ops.attention.flash_attention import (
        flash_attention,
    )

    s, c = 512, 256
    kv_dtype = jnp.int8 if variant == "int8_chunk" else BF16
    k = sds(chip, (1, HKV, s, D), kv_dtype)
    off = sds(chip, (), jnp.int32)
    if variant == "prefill":
        fn, args = flash_attention, [sds(chip, (1, HQ, s, D), BF16), k, k]
    elif variant == "traced_offset_chunk":
        fn = lambda q, k, v, o: flash_attention(  # noqa: E731
            q, k, v, kv_offset=o)
        args = [sds(chip, (1, HQ, c, D), BF16), k, k, off]
    elif variant == "int8_chunk":
        scale = sds(chip, (1, HKV, s // PAGE), jnp.float32)
        fn = lambda q, k, v, o, ks, vs: flash_attention(  # noqa: E731
            q, k, v, kv_offset=o, block_k=PAGE, k_scale=ks, v_scale=vs)
        args = [sds(chip, (1, HQ, c, D), BF16), k, k, off, scale, scale]
    else:  # a 16-node speculative draft tree
        fn = lambda q, k, v, o, b: flash_attention(  # noqa: E731
            q, k, v, kv_offset=o, bias=b)
        args = [sds(chip, (1, HQ, 16, D), BF16), k, k, off,
                sds(chip, (16, s), jnp.float32)]
    assert "tpu_custom_call" in compile_for_chip(fn, *args)


def test_megakernel_decode_launch(chip):
    """The serving megakernel program at Qwen3-4B, all 36 layers and
    the 151,936-row LM head: one 8-step launch over the paged pool."""
    from triton_distributed_tpu.megakernel import MegaQwen3
    from triton_distributed_tpu.megakernel.code_generator import MegaConfig
    from triton_distributed_tpu.models.config import get_config
    from triton_distributed_tpu.models.paged_kv_cache import PagedKVCache
    from triton_distributed_tpu.models.qwen import Qwen3

    cfg = get_config("Qwen/Qwen3-4B")
    model = Qwen3(cfg, ctx=chip)
    shapes = jax.eval_shape(model.init_params, jax.random.key(0))
    model.params = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, model.param_shardings,
    )
    # The serving default (models/engine.py MegaDispatch._mega_model).
    mega = MegaQwen3(model, cfg=MegaConfig(
        fuse_norms=True, cross_prefetch=True, overlap_ar=True))
    launch = mega.decode_multi_fn(
        BATCH, cfg.max_length, 8, page=PAGE, num_pages=NUM_PAGES,
        valid_arg=True,
    )
    pages = sds(chip, (cfg.num_layers, NUM_PAGES, HKV, PAGE, D), BF16)
    cache = PagedKVCache(
        k_pages=pages, v_pages=pages,
        page_table=sds(chip, (BATCH, PPS), jnp.int32),
        kv_len=sds(chip, (BATCH,), jnp.int32),
    )
    batch = sds(chip, (BATCH,), jnp.int32)
    compiled = launch.lower(model.params, batch, cache, batch).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # Weights + pool + temporaries fit the v5e's 16 GB with room.
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes) < 14 << 30


# Qwen3-8B at tp=4: hidden 4096, fused qkv 6144, o-proj K 4096.
D8, QKV8, OK8 = 4096, 6144, 4096


def shard_fn(ctx, fn, in_specs, out_specs, **kw):
    return ctx.shard_map(
        functools.partial(fn, axis="tp", ctx=ctx, **kw),
        in_specs=in_specs, out_specs=out_specs,
    )


def test_ag_gemm(chips4):
    from triton_distributed_tpu.ops.overlap.ag_gemm import ag_gemm

    fn = shard_fn(chips4, ag_gemm, (P("tp", None), P(None, "tp")),
                  P(None, "tp"))
    assert "tpu_custom_call" in compile_for_chip(
        fn, sds(chips4, (1024, D8), BF16, "tp", None),
        sds(chips4, (D8, QKV8), BF16, None, "tp"))


def test_gemm_rs(chips4):
    from triton_distributed_tpu.ops.overlap.gemm_rs import gemm_rs

    fn = shard_fn(chips4, gemm_rs, (P(None, "tp"), P("tp", None)),
                  P("tp", None))
    assert "tpu_custom_call" in compile_for_chip(
        fn, sds(chips4, (1024, OK8), BF16, None, "tp"),
        sds(chips4, (OK8, D8), BF16, "tp", None))


@pytest.mark.parametrize("rows,kernels", [
    (4, 1),     # a decode batch: the one-shot kernel
    (256, 2),   # a prefill chunk: ring gemm_rs + ring all-gather
    # Rows no kernel's row slicing admits (Mosaic: "Slice shape ...
    # must be aligned to tiling") go to XLA instead of being refused:
    (1, 0),     # --max-batch 1
    (96, 0),    # a 96-token chunk: 24-row ring chunks, 12-row halves
])
def test_gemm_ar_auto(chips4, rows, kernels):
    from triton_distributed_tpu.ops.overlap.gemm_ar import gemm_ar

    fn = shard_fn(chips4, gemm_ar, (P(None, "tp"), P("tp", None)),
                  P(None, None))
    text = compile_for_chip(
        fn, sds(chips4, (rows, OK8), BF16, None, "tp"),
        sds(chips4, (OK8, D8), BF16, "tp", None))
    assert text.count("tpu_custom_call") == kernels
