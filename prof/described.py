"""Described-chip compile of the served step programs: memory, and the
ops whose result is pool-shaped (no chip; a compile is not a chip run).

    python prof/described.py <model> <tp> <mode> <slots,slots,...> [bf16|int8] [key=int ...]
    python prof/described.py Qwen/Qwen3-4B 1 xla 4,8
    python prof/described.py rednote-hilab/dots.vlm1.inst 1 xla 32 bf16 \
        num_layers=5 first_k_dense=1 experts_held=16 vocab_size=16160
    python prof/described.py ibm-granite/granite-4.0-h-micro 1 xla 32

For each slot count it compiles the served decode step and one chunk
prefill (the dense decoder: 256 tokens, 2 gathered pages; the latent
expert model: 2,048 tokens over the whole table row; the hybrid: 1,024
tokens, 8 pages) with a donated cache for a described v5e; ``key=int``
pairs cut a preset as ``run_server``'s flags do. It prints what ``memory_analysis`` says beside the
``copy`` / ``dynamic-slice`` / fusion instructions whose result has the
shape of the whole KV pool or of one layer of it, and a hash of each
program's lowered text (``tdt_finite_greedy``'s too): equal hashes on
two checkouts unpacked, in turn, at the SAME path mean the same
programs (a Pallas kernel's serialized body names its source file and
line; callers' lines are kept out of it). The pool is addressed
in place by (layer, page) (``Qwen3._scan_layers_paged``): there are none,
and ``temp`` is a megabyte, as long as no layer scan takes the pool as
``xs`` and nothing scatters rows into it (layers/tp_attn.py "in-place
writers"). Tracked in git though ``prof/`` is scratch: the test imports
:func:`pool_shaped_moves` from here.
"""
import hashlib
import os
import re
import sys

PAGE = 128


def pool_shaped_moves(hlo_text: str, pool_shape, dtype: str = "bf16") -> list:
    """The optimized HLO's instructions that MOVE pool-sized data: a
    ``copy``, ``dynamic-slice`` or fusion whose result is the whole pool
    ``[L, P, H, page, hd]`` (or its ``[L * P, H, page, hd]`` view) or one
    layer of it (``dtype`` as HLO spells it: ``bf16``, ``s8``). Returns
    ``"<kind> <name>"`` strings.

    Not counted, because they move rows or pages and alias the pool they
    write: a bare ``dynamic-update-slice``, and a fusion that lists
    ``aliasing_operands`` (the chunk write's fused page read-merge-write,
    an int8 pool's requantising scatter). Parameters, tuples and bitcasts
    move nothing. tests/test_chip_compile.py holds the served programs
    to an empty list."""
    shapes = {",".join(map(str, pool_shape[i:]))
              for i in range(len(pool_shape) - 3)}
    if len(pool_shape) == 5:  # the kernel's [L * P, H, page, hd] view
        shapes.add(",".join(map(str, (pool_shape[0] * pool_shape[1],
                                      *pool_shape[2:]))))
    return moves_of_shapes(hlo_text, shapes, dtype)


def state_shaped_moves(hlo_text: str, state_shape) -> list:
    """The same for a float32 recurrent state ``[Lm, B, H, P, N]``: the
    whole of it, its ``[Lm * B, H, P, N]`` view, or one layer of it."""
    lm, b, *rest = state_shape
    shapes = {",".join(map(str, s))
              for s in (state_shape, (lm * b, *rest), (b, *rest))}
    return moves_of_shapes(hlo_text, shapes, "f32")


def moves_of_shapes(hlo_text: str, shapes, dtype: str = "bf16") -> list:
    """The ``copy`` / ``dynamic-slice`` / fusion instructions whose
    result has one of ``shapes`` (each ``"16,7168,4096"``): a layer's
    stacked experts sliced out of a group's weights, as well as a pool
    (:func:`pool_shaped_moves`, which says what is not counted)."""
    # "%x = bf16[..]{layout} copy(" and the tuple form "%x = (bf16[..]{..},
    # bf16[..]{..}) fusion(": the kind is the word before the first "("
    # after the shapes, with no "=" between.
    pat = re.compile(
        r"%(\S+) = \(?" + dtype + r"\[(?:"
        + "|".join(map(re.escape, shapes))
        + r")\][^=]*? (copy|dynamic-slice|fusion)\("
    )
    moves = []
    for line in hlo_text.splitlines():
        m = pat.search(line)
        if m and '"aliasing_operands":{"lists":[{' not in line:
            moves.append(f"{m.group(2)} {m.group(1)}")
    return moves


def main(argv):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies

    jax.config.update("jax_enable_compilation_cache", False)
    # Locations name the op's own frame, not its callers': the hash
    # then does not move with a line added above a caller.
    jax.config.update("jax_include_full_tracebacks_in_locations", False)

    from triton_distributed_tpu.models.config import get_config
    from triton_distributed_tpu.models.continuous import tdt_finite_greedy
    from triton_distributed_tpu.models.paged_kv_cache import (
        PagedKVCache,
        paged_cache_specs,
    )
    from triton_distributed_tpu.runtime import mesh as mesh_mod

    name, tp, mode = argv[1], int(argv[2]), argv[3]
    kv = argv[5] if len(argv) > 5 else "bf16"
    quant = kv == "int8"
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    ctx = mesh_mod.initialize_distributed(tp=tp, devices=list(topo.devices)[:tp])
    cfg = get_config(name, **{k: int(v) for k, v in
                              (a.split("=") for a in argv[6:])})
    # The model's class, its chunk and the pool's shapes from the
    # configuration's own fields (what a parent checkout lacks reads as
    # absent: this file is copied over it to compare hashes).
    recurrent = bool(getattr(cfg, "mamba_layers", 0))
    pool_layers = getattr(cfg, "attention_layers", cfg.num_layers)
    if recurrent:
        from triton_distributed_tpu.models.hybrid_ssm import HybridSSM as Model
        chunk_tokens, chunk_pages = 1024, 8
    elif cfg.kv_lora_rank:
        from triton_distributed_tpu.models.latent_moe import LatentMoE as Model
        chunk_tokens, chunk_pages = 2048, None
    else:
        from triton_distributed_tpu.models.qwen import Qwen3 as Model
        chunk_tokens, chunk_pages = 256, 2
    model = Model(cfg, ctx=ctx)
    shapes = jax.eval_shape(model.init_params, jax.random.key(0))
    params = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, model.param_shardings,
    )
    pps = cfg.max_length // PAGE
    specs = (paged_cache_specs("tp", quant, True) if recurrent
             else paged_cache_specs("tp", quant))

    def sds(shape, dt, spec=()):
        return jax.ShapeDtypeStruct(shape, dt, sharding=ctx.sharding(*spec))

    for b in (int(x) for x in argv[4].split(",")):
        row = getattr(cfg, "pool_row_dim", cfg.head_dim)
        pool_shape = (pool_layers, b * pps + 1, cfg.num_kv_heads // tp,
                      PAGE, row)
        glob = (pool_layers, b * pps + 1, cfg.num_kv_heads, PAGE, row)
        v_glob = glob
        if cfg.kv_lora_rank:  # one latent row a token, the rotary part
            pool_shape = glob = (*glob[:2], 1, PAGE, cfg.kv_lora_rank)
            v_glob = (*glob[:3], cfg.qk_rope_head_dim, PAGE)  # transposed
        dt = jnp.int8 if quant else jnp.bfloat16
        scale = sds(glob[:3], jnp.float32, specs.k_scale) if quant else None
        state = {}
        if recurrent:
            from triton_distributed_tpu.models.paged_kv_cache import (
                recurrent_state_shapes,
            )

            ssm, conv = recurrent_state_shapes(cfg, b)
            state = dict(ssm_state=sds(ssm, jnp.float32),
                         conv_state=sds(conv, jnp.bfloat16),
                         live=sds((b,), jnp.bool_))
        cache = PagedKVCache(
            k_pages=sds(glob, dt, specs.k_pages),
            v_pages=sds(v_glob, dt, specs.v_pages),
            page_table=sds((b, pps), jnp.int32), kv_len=sds((b,), jnp.int32),
            k_scale=scale, v_scale=scale, **state,
        )
        i32 = sds((), jnp.int32)
        step = model.decode_fn_paged(mode, quantized=quant)
        chunk = ctx.shard_map(
            lambda p, t, c, s, o, n, li: model._prefill_chunk_shard(
                p, t, c, s, o, n, li, mode=mode, kv_pages=chunk_pages),
            in_specs=(model.param_specs, jax.P(), specs, jax.P(), jax.P(),
                      jax.P(), jax.P()),
            out_specs=(jax.P(), specs),
        )
        programs = (
            ("decode", step, (params, sds((b,), jnp.int32), cache), (2,)),
            (f"chunk{chunk_tokens}", chunk,
             (params, sds((chunk_tokens,), jnp.int32), cache,
              i32, i32, i32, i32), (2,)),
            ("finite_greedy", tdt_finite_greedy,
             (sds((b, cfg.vocab_size), jnp.float32),), ()),
        )
        for label, fn, args, donate in programs:
            try:
                # A cached trace keeps the location of its FIRST call
                # site: without this the chunk's kernel names a line of
                # the decode step's caller.
                jax.clear_caches()
                lowered = jax.jit(fn, donate_argnums=donate).lower(*args)
                c = lowered.compile()
            except Exception as e:  # noqa: BLE001 — report what the compiler refuses
                print(name, "tp", tp, mode, kv, "B", b, label, "REFUSED:",
                      str(e).splitlines()[0][:200], flush=True)
                continue
            m = c.memory_analysis()
            txt = c.as_text()
            gb = 1e9
            print(
                name, "tp", tp, mode, kv, "B", b, f"{label}:",
                "args %.3f GB" % (m.argument_size_in_bytes / gb),
                "temp %.4f GB" % (m.temp_size_in_bytes / gb),
                "alias %.3f GB" % (m.alias_size_in_bytes / gb),
                "| custom calls", txt.count("tpu_custom_call"),
                "all-reduce", txt.count("all-reduce("),
                "| pool-shaped moves:",
                (pool_shaped_moves(txt, pool_shape, "s8" if quant else "bf16")
                 + (state_shaped_moves(txt, ssm) if recurrent else []))
                or 0,
                "| lowered sha256",
                hashlib.sha256(lowered.as_text().encode()).hexdigest()[:16],
                flush=True,
            )


if __name__ == "__main__":
    main(sys.argv)
