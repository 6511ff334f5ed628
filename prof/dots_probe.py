"""Chip probe of the cut dots.vlm1.inst share: init time and memory, a
decode step's device time by operation at 32 slots by load (32, 13, 8
and one live rows; every held expert forced into the kernel's list),
chunk prefill times, and (RAGGED=1) what ragged_dot costs by how many
experts get rows. Run from the tree to probe (a parent unpacked in
.parent_src is probed by `cd .parent_src && python ../prof/dots_probe.py`).

    python prof/dots_probe.py <tag>
    REHEARSE=1 SLOTS=4 STEPS=2 JAX_PLATFORMS=cpu python prof/dots_probe.py reh
"""
import dataclasses
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.getcwd())

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import xplane
from triton_distributed_tpu.models import AutoLLM
from triton_distributed_tpu.models.paged_kv_cache import init_paged_cache
from triton_distributed_tpu.runtime.mesh import initialize_distributed

SLOTS = int(os.environ.get("SLOTS", 32))
STEPS = int(os.environ.get("STEPS", 20))


def mem():
    return {k: round(v / 1e9, 3) for k, v in
            (jax.devices()[0].memory_stats() or {}).items()
            if k in ("bytes_in_use", "peak_bytes_in_use")}


def timed(fn, *args, reps=10):
    out = fn(*args)
    jax.block_until_ready(out)
    ts = []
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t)
    return sorted(ts)[len(ts) // 2] * 1e3


def ragged(result):
    d, f, e = 7168, 2048, 16
    k = jax.random.split(jax.random.key(0), 3)
    w1 = (jax.random.normal(k[0], (e, d, 2 * f), jnp.float32) * 0.01).astype(jnp.bfloat16)
    out = {}
    for rows, sizes_name, sizes in (
        (256, "one_expert_16rows", [16] + [0] * 15),
        (256, "one_expert_256rows", [256] + [0] * 15),
        (256, "ten_experts_16rows", [2] * 6 + [1] * 4 + [0] * 6),
        (256, "all16_16rows", [1] * 16),
        (256, "none", [0] * 16),
        (32, "m32_ten_experts", [2] * 6 + [1] * 4 + [0] * 6),
        (32, "m32_all16", [2] * 16),
        (28672, "prefill_1792_of_28672", [112] * 16),
        (3584, "prefill_1792_of_3584", [112] * 16),
        (1792, "prefill_1792_exact", [112] * 16),
    ):
        x = (jax.random.normal(k[1], (rows, d), jnp.float32)).astype(jnp.bfloat16)
        gs = jnp.asarray(sizes, jnp.int32)
        fn = jax.jit(lambda x, w, g: jax.lax.ragged_dot(
            x, w, g, preferred_element_type=jnp.float32).astype(jnp.bfloat16))
        out[sizes_name] = round(timed(fn, x, w1, gs), 4)
    result["ragged_dot_w1_ms"] = out
    print("ragged", json.dumps(out), flush=True)


def main():
    tag = sys.argv[1]
    dev = jax.devices()[0]
    REH = os.environ.get("REHEARSE")
    assert dev.platform == "tpu" or REH, dev
    result = {"tag": tag, "device": dev.device_kind, "slots": SLOTS}
    if os.environ.get("RAGGED"): ragged(result)
    ctx = initialize_distributed(tp=1, devices=jax.devices()[:1])
    t0 = time.perf_counter()
    model = (AutoLLM.from_pretrained("tiny-mla-moe", ctx=ctx, seed=1) if REH
             else AutoLLM.from_pretrained(
        "rednote-hilab/dots.vlm1.inst", ctx=ctx, seed=1, num_layers=5,
        first_k_dense=1, experts_held=16, vocab_size=16160))
    jax.block_until_ready(model.params)
    result["init_s"] = round(time.perf_counter() - t0, 2)
    result["after_init"] = mem()
    print("init", result["init_s"], result["after_init"], flush=True)
    cfg = model.cfg
    cache, _ = init_paged_cache(
        cfg, SLOTS, ctx, num_pages=SLOTS * 32 + 1, max_length=4096,
        page_size=128) if not REH else init_paged_cache(
        cfg, SLOTS, ctx, num_pages=SLOTS * 16 + 1, max_length=256,
        page_size=16)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, SLOTS), jnp.int32)
    t0 = time.perf_counter()
    logits, cache, counts = model.decode_step_counted(tokens, cache, "xla")
    np.asarray(logits)
    result["decode_compile_s"] = round(time.perf_counter() - t0, 2)
    result["after_decode"] = mem()
    table_full = np.asarray(cache.page_table) + 1  # page 0 is the trash page
    live13 = np.zeros(SLOTS, bool)
    live13[rng.choice(SLOTS, min(13, SLOTS), replace=False)] = True
    lens_sets = {
        "ctx2300": (rng.integers(1200, 3400, SLOTS).tolist(), None, False),
        "ctx1100": ([1100] * SLOTS, None, False),
        "ctx3900": ([3900] * SLOTS, None, False),
        # The cell's mean load: 13 slots hold a request, the others'
        # table rows map no page (the trash page, 0).
        "thirteen_live": (np.where(live13, rng.integers(1200, 3400, SLOTS), 0).tolist(), live13, False),
        "eight_live": ((rng.integers(1200, 3400, 8).tolist() + [0] * SLOTS)[:SLOTS], np.arange(SLOTS) < 8, False),
        "one_live": ([2300] + [0] * (SLOTS - 1), np.arange(SLOTS) < 1, False),
        # Every held expert of every layer in the list (a saturated
        # stage), whatever the rows chose: the kernel's worst case.
        "all16_forced": (rng.integers(1200, 3400, SLOTS).tolist(), None, True),
    }
    from triton_distributed_tpu.layers import moe_share
    for name, (lens, live, forced) in lens_sets.items():
        if REH:
            lens = [l // 20 for l in lens]
        if forced and not hasattr(moe_share, "touched_experts"):
            continue  # the parent: every step reads all 16
        if forced:
            moe_share.touched_experts = lambda chosen: (
                jnp.arange(chosen.shape[0], dtype=jnp.int32),
                jnp.int32(chosen.shape[0]))
            model._decode_jit.clear()
        table = jnp.asarray(table_full if live is None else np.where(
            np.asarray(live)[:, None], table_full, 0))
        cache = dataclasses.replace(cache, kv_len=jnp.asarray(lens, jnp.int32), page_table=table)
        for _ in range(3):
            logits, cache, counts = model.decode_step_counted(tokens, cache, "xla")
        np.asarray(logits)
        cache = dataclasses.replace(cache, kv_len=jnp.asarray(lens, jnp.int32))
        with tempfile.TemporaryDirectory(prefix="dots_probe_") as tdir:
            jax.profiler.start_trace(tdir)
            t0 = time.perf_counter()
            per_step = []
            for _ in range(STEPS):
                logits, cache, counts = model.decode_step_counted(
                    tokens, cache, "xla")
                per_step.append(counts)
            np.asarray(logits)
            wall = time.perf_counter() - t0
            jax.profiler.stop_trace()
            tr = xplane.reduce_dir(tdir, 1)
        own = {} if REH else tr.self_seconds()
        mods = [(0, 0, 1)] if REH else tr.modules("decode")
        durs = sorted(e[2] for e in mods)
        ops = {k: round(v / STEPS * 1e3, 4) for k, v in
               sorted(own.items(), key=lambda kv: -kv[1])[:24]}
        kern = sum(v for k, v in own.items() if "tdt_mla_decode_paged" in k)
        moe = sum(v for k, v in own.items() if "tdt_moe_decode_experts" in k)
        touched = [int(np.asarray(c)[1]) for c in per_step]
        result[name] = {
            "mean_len": float(np.mean(lens)),
            "step_ms_median": durs[len(durs) // 2] / 1e6,
            "steps_traced": len(mods),
            "wall_ms_a_step": wall / STEPS * 1e3,
            "kernel_ms_a_step": kern / STEPS * 1e3,
            "moe_kernel_ms_a_step": moe / STEPS * 1e3,
            "touched_a_step_mean": float(np.mean(touched)),
            "moe_ms_a_touched_expert": (moe / max(sum(touched), 1) * 1e3
                                        if not forced else moe / (STEPS * 64) * 1e3),
            "counts_last_step": np.asarray(counts).tolist(),
            "ops_ms_a_step": ops,
        }
        print(tag, name, json.dumps(result[name]), flush=True)
    cache = dataclasses.replace(cache, page_table=jnp.asarray(table_full))
    if REH:
        return
    # Chunk prefill: compile, then time, at two widths.
    for width, pages in ((2048, 16), (3584, 32)):
        buf = rng.integers(0, cfg.vocab_size, width).astype(np.int32)
        t0 = time.perf_counter()
        lg, cache = model.prefill_paged_chunk(
            buf, 0, 0, width, width - 1, cache, "xla", kv_pages=pages)
        np.asarray(lg)
        comp = time.perf_counter() - t0
        ts = []
        for _ in range(3):
            t0 = time.perf_counter()
            lg, cache = model.prefill_paged_chunk(
                buf, 0, 0, width, width - 1, cache, "xla", kv_pages=pages)
            np.asarray(lg)
            ts.append(time.perf_counter() - t0)
        result[f"chunk{width}"] = {"compile_s": round(comp, 2),
                                   "ms": round(sorted(ts)[1] * 1e3, 2)}
        print(tag, f"chunk{width}", result[f"chunk{width}"], mem(), flush=True)
    with tempfile.TemporaryDirectory(prefix="dots_probe_") as tdir:
        jax.profiler.start_trace(tdir)
        lg, cache = model.prefill_paged_chunk(
            buf, 0, 0, width, width - 1, cache, "xla", kv_pages=pages)
        np.asarray(lg)
        jax.profiler.stop_trace()
        tr = xplane.reduce_dir(tdir, 1)
    own = tr.self_seconds()
    result["chunk3584_ops_ms"] = {k: round(v * 1e3, 3) for k, v in
                                  sorted(own.items(), key=lambda kv: -kv[1])[:16]}
    print(tag, "chunk_ops", json.dumps(result["chunk3584_ops_ms"]), flush=True)
    result["final"] = mem()
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", f"dots_probe_{tag}.json"), "w") as f:
        json.dump(result, f, indent=1)


if __name__ == "__main__":
    main()
