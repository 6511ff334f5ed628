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
import math
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


@pytest.mark.parametrize("kv,pool_form,slots,hkv", [
    ("bf16", "one_layer", BATCH, HKV),
    ("int8", "one_layer", BATCH, HKV),
    ("bf16", "layer_of_pool", BATCH, HKV),   # the benchmark's cells
    ("int8", "layer_of_pool", BATCH, HKV),
    ("bf16", "layer_of_pool", 8, HKV),       # --max-batch 8
    ("int8", "layer_of_pool", 8, HKV),
    ("bf16", "layer_of_pool", 8, HKV // 4),  # one shard of --tp 4
    ("int8", "layer_of_pool", 8, HKV // 4),
])
def test_paged_flash_decode(chip, kv, pool_form, slots, hkv):
    """``layer_of_pool``: the served form — the whole 36-layer pool and
    a traced layer index, which rides into the kernel inside the page
    table (``table + layer * P`` over the ``[L * P, ...]`` view). One
    kernel at every shape: its blocks follow what it sees in its
    operands (``(1, Hkv, page, d)``: all of a page's heads)."""
    from triton_distributed_tpu.ops.attention.flash_decode import (
        paged_flash_decode,
    )

    lead = (36,) if pool_form == "layer_of_pool" else ()
    kv_dtype = BF16 if kv == "bf16" else jnp.int8
    pool_shape = (*lead, slots * PPS + 1, hkv, PAGE, D)
    pages = sds(chip, pool_shape, kv_dtype)
    args = [sds(chip, (slots, hkv * (HQ // HKV), D), BF16), pages, pages,
            sds(chip, (slots, PPS), jnp.int32),
            sds(chip, (slots,), jnp.int32)]
    if lead:
        args.append(sds(chip, (), jnp.int32))
    if kv == "int8":
        scale = sds(chip, pool_shape[:-2], jnp.float32)
        args += [scale, scale]

    def fn(q, k, v, t, n, *rest):
        layer = rest[0] if lead else None
        ks, vs = rest[-2:] if kv == "int8" else (None, None)
        return paged_flash_decode(
            q, k, v, t, n, layer=layer, k_scale=ks, v_scale=vs)

    text = compile_for_chip(fn, *args)
    assert "tpu_custom_call" in text
    # The kernel reads pages of the pool it was given: nothing
    # pool-shaped is sliced or copied for it, and the K / V operands of
    # the custom call are the pool's own bytes (its [L * P, ...] view).
    assert not _pool_shaped_moves(text, pool_shape, kv)
    call = next(ln for ln in text.splitlines() if "custom-call(" in ln
                and "tpu_custom_call" in ln)
    flat = ",".join(map(str, (math.prod(pool_shape[:-3]), *pool_shape[-3:])))
    assert call.count(f"{'bf16' if kv == 'bf16' else 's8'}[{flat}]") == 2


def _described():
    """prof/described.py, the builder's tool that prints the same lists."""
    import importlib.util
    import pathlib

    path = pathlib.Path(__file__).parent.parent / "prof" / "described.py"
    spec = importlib.util.spec_from_file_location("prof_described", path)
    described = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(described)
    return described


def _pool_shaped_moves(hlo_text, pool_shape, kv="bf16"):
    """Instructions that copy or slice pool-sized data."""
    return _described().pool_shaped_moves(
        hlo_text, pool_shape, "bf16" if kv == "bf16" else "s8")


@pytest.fixture
def qwen3_4b(chip):
    """Qwen3-4B on the described chip with parameter SHAPES (a
    described device holds no arrays)."""
    from triton_distributed_tpu.models.config import get_config
    from triton_distributed_tpu.models.qwen import Qwen3

    model = Qwen3(get_config("Qwen/Qwen3-4B"), ctx=chip)
    shapes = jax.eval_shape(model.init_params, jax.random.key(0))
    model.params = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, model.param_shardings,
    )
    return model


@pytest.mark.parametrize("program,slots,kv", [
    ("decode", 4, "bf16"),   # the benchmark's cells
    ("decode", 8, "bf16"),   # refused until PR 27: 17.31 GB of 15.75
    ("chunk", 4, "bf16"),    # one width of tdt_prefill_chunk
    ("chunk", 8, "bf16"),
    ("decode", 4, "int8"),
    ("chunk", 4, "int8"),
])
def test_served_step_addresses_pool_in_place(chip, qwen3_4b, program, slots,
                                             kv):
    """The served decode step and chunk prefill at Qwen3-4B with a
    donated cache: the pool rides the layer scan's carry and is written
    and read in place at (layer, page). A scan that takes it as
    ``xs``/``ys`` slices every layer's pool out, stacks it back and
    copies the stack onto the donated buffer: 2.3 GiB of temporaries and
    half the decode step at 4 slots, and no fit at 8."""
    from triton_distributed_tpu.models.paged_kv_cache import (
        PagedKVCache,
        paged_cache_specs,
    )

    model, quant = qwen3_4b, kv == "int8"
    cfg = model.cfg
    pool_shape = (cfg.num_layers, slots * PPS + 1, HKV, PAGE, D)
    pages = sds(chip, pool_shape, jnp.int8 if quant else BF16)
    scale = sds(chip, pool_shape[:3], jnp.float32) if quant else None
    cache = PagedKVCache(
        k_pages=pages, v_pages=pages,
        page_table=sds(chip, (slots, PPS), jnp.int32),
        kv_len=sds(chip, (slots,), jnp.int32),
        k_scale=scale, v_scale=scale,
    )
    i32 = sds(chip, (), jnp.int32)
    if program == "decode":
        fn = model.decode_fn_paged("xla", quantized=quant)
        args = (model.params, sds(chip, (slots,), jnp.int32), cache)
    else:
        specs = paged_cache_specs("tp", quant)
        fn = chip.shard_map(
            functools.partial(model._prefill_chunk_shard, mode="xla",
                              kv_pages=2),
            in_specs=(model.param_specs, P(), specs, P(), P(), P(), P()),
            out_specs=(P(), specs),
        )
        args = (model.params, sds(chip, (256,), jnp.int32), cache,
                i32, i32, i32, i32)
    compiled = jax.jit(fn, donate_argnums=(2,)).lower(*args).compile()
    mem = compiled.memory_analysis()
    # The donated pool is the output: no second pool among the
    # temporaries, and the program fits the v5e's HBM.
    assert mem.temp_size_in_bytes < 64 << 20
    assert mem.alias_size_in_bytes >= 2 * pages.size * pages.dtype.itemsize
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert not _pool_shaped_moves(text, pool_shape, kv)


# dots.vlm1.inst's language model cut to one chip's share, as the cell
# `dots-vlm1-ep16.docs-closed` serves it (benchmark/configs).
DOTS_CUT = dict(num_layers=5, first_k_dense=1, experts_held=16,
                vocab_size=16160)
DOTS_SLOTS = 32


def test_mla_paged_decode_kernel(chip):
    """``tdt_mla_decode_paged`` at the published widths: 128 heads over
    a latent row of 512 + 64, page 128, the whole five-layer pool and a
    traced layer index."""
    from triton_distributed_tpu.ops.attention.mla_decode import (
        mla_paged_decode,
    )

    pages = DOTS_SLOTS * PPS + 1
    text = compile_for_chip(
        lambda ql, qr, c, r, t, n, l: mla_paged_decode(
            ql, qr, c, r, t, n, sm_scale=0.1352, layer=l),
        sds(chip, (DOTS_SLOTS, 128, 512), BF16),
        sds(chip, (DOTS_SLOTS, 128, 64), BF16),
        sds(chip, (5, pages, 1, PAGE, 512), BF16),
        sds(chip, (5, pages, 1, 64, PAGE), BF16),
        sds(chip, (DOTS_SLOTS, PPS), jnp.int32),
        sds(chip, (DOTS_SLOTS,), jnp.int32), sds(chip, (), jnp.int32))
    assert "tdt_mla_decode_paged" in text and "tpu_custom_call" in text


def test_moe_decode_experts_kernel(chip):
    """``tdt_moe_decode_experts`` at the published widths: 32 rows of
    7168 against the four expert layers' 16 held experts of 2048,
    stacked, with a traced layer, list and count."""
    from triton_distributed_tpu.ops.moe.decode_experts import (
        moe_decode_experts,
    )

    text = compile_for_chip(
        lambda x, g, e, n, w1, w2, l: moe_decode_experts(
            x, g, e, n, w1, w2, layer=l),
        sds(chip, (DOTS_SLOTS, 7168), BF16),
        sds(chip, (DOTS_SLOTS, 16), jnp.float32),
        sds(chip, (16,), jnp.int32), sds(chip, (), jnp.int32),
        sds(chip, (4, 16, 7168, 4096), BF16),
        sds(chip, (4, 16, 2048, 7168), BF16), sds(chip, (), jnp.int32))
    assert "tdt_moe_decode_experts" in text and "tpu_custom_call" in text
    # The stacked weights reach the kernel as they are: no copy of them.
    assert not _described().moves_of_shapes(
        text, {"4,16,7168,4096", "64,7168,4096", "4,16,2048,7168",
               "64,2048,7168"})


@pytest.fixture
def dots_share(chip):
    """The cut preset on the described chip, parameter shapes only."""
    from triton_distributed_tpu.models.config import get_config
    from triton_distributed_tpu.models.latent_moe import LatentMoE

    model = LatentMoE(
        get_config("rednote-hilab/dots.vlm1.inst", **DOTS_CUT), ctx=chip)
    shapes = jax.eval_shape(model.init_params, jax.random.key(0))
    model.params = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, model.param_shardings,
    )
    return model


@pytest.mark.parametrize("program", ["decode", "chunk3584", "weights"])
def test_latent_share_fits_the_chip(chip, dots_share, program):
    """The 32-slot decode step, the widest chunk the cell sends (3,584
    tokens over the slot's 32 pages) and the weight-init program, at the
    cell's shapes: under the v5e's 15.75 GB, the latent pool carried in
    place (its rotary part transposed, the page axis on the lanes: 1,152
    bytes a token a layer in HBM, none of it padding)."""
    from triton_distributed_tpu.models.paged_kv_cache import (
        PagedKVCache,
        paged_cache_specs,
    )

    model = dots_share
    pages = DOTS_SLOTS * PPS + 1
    lat = sds(chip, (5, pages, 1, PAGE, 512), BF16)
    rot = sds(chip, (5, pages, 1, 64, PAGE), BF16)
    cache = PagedKVCache(
        k_pages=lat, v_pages=rot,
        page_table=sds(chip, (DOTS_SLOTS, PPS), jnp.int32),
        kv_len=sds(chip, (DOTS_SLOTS,), jnp.int32),
    )
    i32 = sds(chip, (), jnp.int32)
    donate = (2,)
    if program == "decode":
        fn = model.decode_fn_paged("xla")
        args = (model.params, sds(chip, (DOTS_SLOTS,), jnp.int32), cache)
    elif program == "chunk3584":
        specs = paged_cache_specs("tp")
        fn = chip.shard_map(
            functools.partial(model._prefill_chunk_shard, mode="xla",
                              kv_pages=PPS),
            in_specs=(model.param_specs, P(), specs, P(), P(), P(), P()),
            out_specs=(P(), specs),
        )
        args = (model.params, sds(chip, (3584,), jnp.int32), cache,
                i32, i32, i32, i32)
    else:
        # One program a tensor (LatentMoE.init_params): what is built so
        # far plus the next tensor's temporaries never passes the chip.
        from triton_distributed_tpu.models.latent_moe import (
            tdt_draw_weights,
            weight_layout,
        )

        built = peak = 0
        for name, lead, mat, scale in weight_layout(model.cfg):
            keys = jax.eval_shape(
                lambda: jax.random.split(jax.random.key(0), math.prod(lead)))
            mem = tdt_draw_weights.lower(
                keys, lead, mat, scale or mat[-2] ** -0.5, "bfloat16",
            ).compile().memory_analysis()
            built += mem.output_size_in_bytes
            peak = max(peak, built + mem.temp_size_in_bytes)
        print("weights built %.3f peak %.3f GB" % (built / 1e9, peak / 1e9))
        assert 9.0e9 < built < 9.3e9 and peak < 15.75e9
        return
    compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
    mem = compiled.memory_analysis()
    total = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    print(program, "args %.3f temp %.3f out %.3f alias %.3f GB" % tuple(
        x / 1e9 for x in (mem.argument_size_in_bytes, mem.temp_size_in_bytes,
                          mem.output_size_in_bytes, mem.alias_size_in_bytes)))
    assert total < 15.75e9
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert not _pool_shaped_moves(text, lat.shape, "bf16")
    assert not _pool_shaped_moves(text, rot.shape, "bf16")
    if program == "decode":
        # The step's experts are read in place, the ones its rows chose:
        # no layer's 16 are sliced out of the group's stacked weights
        # (as ``lax.scan`` ``xs`` they were, 1.4 GB a layer a step).
        assert "tdt_moe_decode_experts" in text
        assert not _described().moves_of_shapes(
            text, {"16,7168,4096", "1,16,7168,4096", "16,2048,7168",
                   "1,16,2048,7168"})


GRANITE = "ibm-granite/granite-4.0-h-micro"
GRANITE_SLOTS = 32
STATE = (36, GRANITE_SLOTS, 64, 64, 128)


def test_ssm_decode_kernel(chip):
    """``tdt_ssm_decode`` at the published widths: 32 slots' states of
    36 layers, aliased: the program holds no second state, whole or a
    layer's."""
    from triton_distributed_tpu.ops.ssm.decode import ssm_decode

    f32 = jnp.float32
    compiled = jax.jit(
        lambda s, da, dx, b, c, rows, n, layer: ssm_decode(
            s, da, dx, b, c, rows, n, layer=layer),
        donate_argnums=(0,),
    ).lower(
        sds(chip, STATE, f32), sds(chip, (GRANITE_SLOTS, 64), f32),
        sds(chip, (GRANITE_SLOTS, 64, 64), f32),
        sds(chip, (GRANITE_SLOTS, 128), f32),
        sds(chip, (GRANITE_SLOTS, 128), f32),
        sds(chip, (GRANITE_SLOTS,), jnp.int32), sds(chip, (), jnp.int32),
        sds(chip, (), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tdt_ssm_decode" in text and "tpu_custom_call" in text
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 4e6
    assert mem.alias_size_in_bytes >= math.prod(STATE) * 4
    assert not _described().state_shaped_moves(text, STATE)


@pytest.fixture
def granite(chip):
    """The published preset on the described chip, parameter shapes only."""
    from triton_distributed_tpu.models.config import get_config
    from triton_distributed_tpu.models.hybrid_ssm import HybridSSM

    model = HybridSSM(get_config(GRANITE), ctx=chip)
    shapes = jax.eval_shape(model.init_params, jax.random.key(0))
    model.params = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, model.param_shardings,
    )
    return model


@pytest.mark.parametrize("program", ["decode", "chunk1024"])
def test_hybrid_fits_the_chip_and_moves_neither_state_nor_pool(
        chip, granite, program):
    """granite-4.0-h-micro whole: the 32-slot decode step and the widest
    chunk the cell sends (1,024 tokens), ``head_dim`` 64 through both
    attention kernels over pool rows padded to 128 lanes. Arguments as
    reckoned: 6.79 GB of weights (the head drawn apart), 32 x 76.4 MB of
    state, a 2.15 GB pool for 4 layers = 11.4 GB (9.9 + the padding and
    the second table); the temporaries hold no copy of the state array
    or of the pool, whole or a layer's."""
    from triton_distributed_tpu.models.paged_kv_cache import (
        PagedKVCache,
        paged_cache_specs,
        recurrent_state_shapes,
    )

    model, cfg = granite, granite.cfg
    pages = GRANITE_SLOTS * PPS + 1
    pool = (4, pages, 8, PAGE, 128)
    ssm, conv = recurrent_state_shapes(cfg, GRANITE_SLOTS)
    assert ssm == STATE and conv == (36, 3, GRANITE_SLOTS, 4352)
    cache = PagedKVCache(
        k_pages=sds(chip, pool, BF16), v_pages=sds(chip, pool, BF16),
        page_table=sds(chip, (GRANITE_SLOTS, PPS), jnp.int32),
        kv_len=sds(chip, (GRANITE_SLOTS,), jnp.int32),
        ssm_state=sds(chip, ssm, jnp.float32),
        conv_state=sds(chip, conv, BF16),
        live=sds(chip, (GRANITE_SLOTS,), jnp.bool_),
    )
    i32 = sds(chip, (), jnp.int32)
    if program == "decode":
        fn = model.decode_fn_paged("xla")
        args = (model.params, sds(chip, (GRANITE_SLOTS,), jnp.int32), cache)
    else:
        specs = paged_cache_specs("tp", recurrent=True)
        fn = chip.shard_map(
            functools.partial(model._prefill_chunk_shard, mode="xla",
                              kv_pages=8),
            in_specs=(model.param_specs, P(), specs, P(), P(), P(), P()),
            out_specs=(P(), specs),
        )
        args = (model.params, sds(chip, (1024,), jnp.int32), cache,
                i32, i32, i32, i32)
    compiled = jax.jit(fn, donate_argnums=(2,)).lower(*args).compile()
    mem = compiled.memory_analysis()
    print(program, "args %.3f temp %.3f alias %.3f GB" % tuple(
        x / 1e9 for x in (mem.argument_size_in_bytes, mem.temp_size_in_bytes,
                          mem.alias_size_in_bytes)))
    assert 11.3e9 < mem.argument_size_in_bytes < 11.5e9
    assert mem.temp_size_in_bytes < (0.02e9 if program == "decode"
                                     else 0.2e9)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75e9
    text = compiled.as_text()
    assert "tdt_flash_attention" in text if program != "decode" else (
        "tdt_flash_decode_paged" in text and "tdt_ssm_decode" in text)
    assert not _pool_shaped_moves(text, pool, "bf16")
    assert not _described().state_shaped_moves(text, ssm)


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


def test_megakernel_decode_launch(chip, qwen3_4b):
    """The serving megakernel program at Qwen3-4B, all 36 layers and
    the 151,936-row LM head: one 8-step launch over the paged pool."""
    from triton_distributed_tpu.megakernel import MegaQwen3
    from triton_distributed_tpu.megakernel.code_generator import MegaConfig
    from triton_distributed_tpu.models.paged_kv_cache import PagedKVCache

    model, cfg = qwen3_4b, qwen3_4b.cfg
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
