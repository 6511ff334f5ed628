"""Chip probe of ``tdt_flash_decode_paged``, the dense paged decode
kernel: its device time alone (a layer scan of calls over a pool of the
served shape, exact against ``gqa_decode_reference`` in bf16 and int8)
and inside the served decode step, at Qwen3-4B's geometry (4 slots, 1 /
2 / 4 live at contexts of 180-700) and at granite-4.0-h-micro's (32
slots, 1 / 13 / 28 / 32 rows in flight at contexts of 300-700, 64-wide
heads on pool rows padded to 128), for each group of pages a grid step
in ``GROUP_SWEEP`` (set as the module constant, the step's programs
traced afresh). A tree whose kernel takes no group (no
``GROUP_PAGES``) is probed once, as it is: the probe runs the package of
the directory it is run FROM, so the parent unpacked beside the change
is probed by a copy of this same file.

    python prof/dense_decode_probe.py <tag> [kernel] [qwen] [granite]
    GROUP_SWEEP=2,4,8 python prof/dense_decode_probe.py <tag> kernel qwen
    REHEARSE=1 JAX_PLATFORMS=cpu python prof/dense_decode_probe.py reh

Result lines go to standard output and, merged over the parts one call
runs, to ``chiprun_out/dense_decode_probe_<tag>.json`` under the working
directory. Run the granite part in a process of its own: its weights and
Qwen3-4B's do not fit the chip together.
"""
import dataclasses
import importlib
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
from triton_distributed_tpu.models.config import get_config
from triton_distributed_tpu.models.paged_kv_cache import init_paged_cache
from triton_distributed_tpu.models.qwen import Qwen3
from triton_distributed_tpu.runtime.mesh import initialize_distributed

fd = importlib.import_module("triton_distributed_tpu.ops.attention.flash_decode")

REH = bool(os.environ.get("REHEARSE"))
STEPS = 2 if REH else 20
KERNEL = "tdt_flash_decode_paged"
PAGE = 16 if REH else 128
PPS = 8 if REH else 32
# Qwen3-4B's contexts by case, 4 slots: an empty row holds 0, as the
# engine's idle rows do (the step attends kv_len + 1).
QWEN_LENS = {
    "one_live": [411, 0, 0, 0],
    "two_live": [411, 300, 0, 0],
    "four_live": [411, 300, 520, 180],
    "four_live_b": [650, 330, 520, 700],
}
GRANITE_ROWS = (1, 13, 28, 32)


def groups() -> list:
    """The groups to sweep, or ``[None]`` for a kernel that takes none."""
    if not hasattr(fd, "GROUP_PAGES"):
        return [None]
    return [int(g) for g in os.environ.get("GROUP_SWEEP", "2,4,8").split(",")]


def set_group(g):
    if g is not None:
        fd.GROUP_PAGES = g
    jax.clear_caches()


def traced(fn):
    """``(device seconds by operation, decode programs' durations, wall
    seconds)`` of one call of ``fn`` under the profiler."""
    if REH:
        jax.block_until_ready(fn())
        return {}, [1], 0.0
    with tempfile.TemporaryDirectory(prefix="dense_decode_probe_") as tdir:
        jax.profiler.start_trace(tdir)
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        wall = time.perf_counter() - t0
        jax.profiler.stop_trace()
        tr = xplane.reduce_dir(tdir, 1)
    return tr.self_seconds(), sorted(e[2] for e in tr.modules("decode")), wall


def kernel_ms(own: dict) -> float:
    return sum(v for k, v in own.items() if KERNEL in k) * 1e3


def walk_of(kv_len, page, pps):
    """The tree's hoisted walk: the grouped one where the tree has it."""
    make = getattr(fd, "dense_decode_walk", None) or fd.paged_decode_walk
    return make(kv_len, page, pps)


def kernel_alone(result: dict):
    """The kernel over a layer scan of calls, by geometry and lengths,
    and its error against the plain formula (float32, "highest")."""
    rng = np.random.default_rng(0)
    geoms = {
        "qwen": (4, 4 if REH else 36, dict(QWEN_LENS)),
        "granite": (8 if REH else 32, 4, {
            f"rows{n}": [int(x) for x in np.where(
                np.arange(8 if REH else 32) < n,
                rng.integers(300, 700, 8 if REH else 32), 0)]
            for n in ((1, 3) if REH else GRANITE_ROWS)}),
    }
    hkv, gq, d = (2, 2, 128) if REH else (8, 4, 128)
    out = {}
    for geom, (slots, layers, cases) in geoms.items():
        p = slots * PPS + 1
        ks = jax.random.split(jax.random.key(1), 5)
        shape = (layers, p, hkv, PAGE, d)
        kp = jax.random.normal(ks[0], shape, jnp.bfloat16)
        vp = jax.random.normal(ks[1], shape, jnp.bfloat16)
        q = jax.random.normal(ks[2], (slots, hkv * gq, d), jnp.bfloat16)
        table = jnp.asarray(
            1 + np.arange(slots * PPS).reshape(slots, PPS), jnp.int32)

        def scan(q, kp, vp, kv_len):
            walk = walk_of(kv_len, PAGE, PPS)

            def one(tot, layer):
                o = fd.paged_flash_decode(q, kp, vp, table, kv_len,
                                          layer=layer, walk=walk)
                return tot + jnp.sum(o.astype(jnp.float32)), None

            return jax.lax.scan(one, jnp.float32(0),
                                jnp.arange(layers, dtype=jnp.int32))[0]

        for g in groups():
            set_group(g)
            f = jax.jit(scan)
            for name, lens in cases.items():
                kv_len = jnp.asarray(lens, jnp.int32) + 1
                jax.block_until_ready(f(q, kp, vp, kv_len))
                own, _, _ = traced(lambda: [f(q, kp, vp, kv_len)
                                            for _ in range(STEPS)])
                ms = kernel_ms(own) / STEPS / layers
                pages = int(np.sum(np.clip(-(-np.asarray(kv_len) // PAGE),
                                           1, PPS)))
                out[f"{geom}.{name}.G{g}"] = {
                    "lens": lens, "ms_a_layer": round(ms, 5),
                    "pages": pages,
                    "grid_steps": int(walk_of(kv_len, PAGE, PPS)[2]),
                    "gb_s": round(pages * hkv * PAGE * d * 2 * 2 / ms / 1e6, 1)
                    if ms else 0,
                }
                print(result["tag"], "kernel", f"{geom}.{name}.G{g}",
                      json.dumps(out[f"{geom}.{name}.G{g}"]), flush=True)
            # Exact against the plain formula, bf16 and int8, on one
            # layer of the pool at the last lengths.
            kv_len = jnp.asarray(list(cases.values())[-1], jnp.int32) + 1
            with jax.default_matmul_precision("highest"):
                want = fd.gqa_decode_reference(
                    q, fd.pages_to_dense(kp[1], table),
                    fd.pages_to_dense(vp[1], table), kv_len)
            got = jax.jit(lambda: fd.paged_flash_decode(
                q, kp, vp, table, kv_len, layer=1))()
            err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                        - want.astype(jnp.float32))))
            codes = [jnp.clip(jnp.round(a[1].astype(jnp.float32) * 40),
                              -127, 127).astype(jnp.int8) for a in (kp, vp)]
            scales = [jax.random.uniform(k, shape[1:3], jnp.float32,
                                         0.01, 0.04) for k in ks[3:]]
            deq = [fd.pages_to_dense(c, table).astype(jnp.float32)
                   * fd.scales_to_dense(s, table, PAGE)[..., None]
                   for c, s in zip(codes, scales)]
            with jax.default_matmul_precision("highest"):
                want8 = fd.gqa_decode_reference(q, *deq, kv_len)
            got8 = jax.jit(lambda: fd.paged_flash_decode(
                q, *codes, table, kv_len, k_scale=scales[0],
                v_scale=scales[1]))()
            err8 = float(jnp.max(jnp.abs(got8.astype(jnp.float32)
                                         - want8.astype(jnp.float32))))
            out[f"{geom}.max_abs_err.G{g}"] = {"bf16": err, "int8": err8}
            print(result["tag"], "kernel", f"{geom}.max_abs_err.G{g}",
                  json.dumps(out[f"{geom}.max_abs_err.G{g}"]), flush=True)
        del kp, vp
    result["kernel"] = out


def step_cases(result: dict, part: str, model, cache, cases, counted: bool):
    """The served decode step at each case's rows, for each group: the
    kernel's device time a step and the step's."""
    out = {}
    tokens = jnp.arange(cache.kv_len.shape[0], dtype=jnp.int32) + 1
    table_full = np.asarray(cache.page_table)

    def step(cache):
        if counted:
            logits, cache, _ = model.decode_step_counted(tokens, cache, "xla")
        else:
            logits, cache = model.decode_step(tokens, cache, "xla")
        return logits, cache

    for g in groups():
        set_group(g)
        model._decode_jit.clear()
        for name, lens in cases.items():
            live = np.asarray(lens) > 0

            def reset(cache):
                # Fresh arrays: the step donates its cache.
                state = {"live": jnp.asarray(live)} if counted else {}
                return dataclasses.replace(
                    cache, kv_len=jnp.asarray(lens, jnp.int32),
                    page_table=jnp.asarray(
                        np.where(live[:, None], table_full, 0)), **state)

            cache = reset(cache)
            for _ in range(3):
                logits, cache = step(cache)
            np.asarray(logits)
            cache = reset(cache)
            holder = {"c": cache}

            def run():
                for _ in range(STEPS):
                    lg, holder["c"] = step(holder["c"])
                return lg

            own, durs, wall = traced(run)
            cache = holder["c"]
            out[f"{name}.G{g}"] = {
                "lens": lens,
                "step_ms_median": durs[len(durs) // 2] / 1e6,
                "steps_traced": len(durs),
                "wall_ms_a_step": wall / STEPS * 1e3,
                "kernel_ms_a_step": kernel_ms(own) / STEPS,
            }
            print(result["tag"], part, f"{name}.G{g}",
                  json.dumps(out[f"{name}.G{g}"]), flush=True)
    result[part] = out


def qwen_step(result: dict, ctx):
    cfg = get_config("tiny" if REH else "Qwen/Qwen3-4B")
    model = Qwen3(cfg, ctx=ctx)
    model.init_params(jax.random.key(0))
    cache, _ = init_paged_cache(cfg, 4, ctx, num_pages=4 * PPS + 1,
                                max_length=PPS * PAGE, page_size=PAGE)
    # The table maps every slot; an empty row's is the trash page's.
    cache = dataclasses.replace(cache, page_table=jnp.asarray(
        1 + np.arange(4 * PPS).reshape(4, PPS), jnp.int32))
    cases = {k: [x // 10 if REH else x for x in v]
             for k, v in QWEN_LENS.items()}
    step_cases(result, "qwen", model, cache, cases, counted=False)


def granite_step(result: dict, ctx):
    model = AutoLLM.from_pretrained(
        "tiny-hybrid" if REH else "ibm-granite/granite-4.0-h-micro",
        ctx=ctx, seed=1)
    cfg = model.cfg
    slots = 4 if REH else 32
    cache, _ = init_paged_cache(cfg, slots, ctx, num_pages=slots * PPS + 1,
                                max_length=PPS * PAGE, page_size=PAGE)
    cache = dataclasses.replace(cache, page_table=jnp.asarray(
        1 + np.arange(slots * PPS).reshape(slots, PPS), jnp.int32))
    rng = np.random.default_rng(0)
    cases = {}
    for n in ((1, 3) if REH else GRANITE_ROWS):
        lens = np.where(np.arange(slots) < n,
                        rng.integers(300, 700, slots), 0)
        cases[f"rows{n}"] = [int(x) // (10 if REH else 1) for x in lens]
    step_cases(result, "granite", model, cache, cases, counted=True)


def main():
    tag, parts = sys.argv[1], sys.argv[2:] or ["kernel", "qwen"]
    dev = jax.devices()[0]
    assert dev.platform == "tpu" or REH, dev
    result = {"tag": tag, "device": dev.device_kind,
              "groups": groups(), "parts": parts}
    ctx = initialize_distributed(tp=1, devices=jax.devices()[:1])
    for part in parts:
        {"kernel": kernel_alone, "qwen": lambda r: qwen_step(r, ctx),
         "granite": lambda r: granite_step(r, ctx)}[part](result)
    os.makedirs("chiprun_out", exist_ok=True)
    path = os.path.join("chiprun_out", f"dense_decode_probe_{tag}.json")
    if os.path.exists(path):
        with open(path) as f:
            result = {**json.load(f), **result}
    with open(path, "w") as f:
        json.dump(result, f, indent=1)


if __name__ == "__main__":
    main()
