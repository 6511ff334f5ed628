"""Chip probe of granite-4.0-h-micro whole on one chip: init time and
memory, ``tdt_ssm_decode`` alone (against the plain einsum, and by the
heads a grid step takes), a decode step's device time by operation at 32
slots with 32, 13 and one row in flight, and the chunk widths' times.
Run from the tree to probe.

    python prof/granite_probe.py <tag>
    REHEARSE=1 SLOTS=4 STEPS=2 JAX_PLATFORMS=cpu python prof/granite_probe.py reh
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
from triton_distributed_tpu.ops.ssm import decode as ssm
from triton_distributed_tpu.runtime.mesh import initialize_distributed

SLOTS = int(os.environ.get("SLOTS", 32))
STEPS = int(os.environ.get("STEPS", 20))
REH = bool(os.environ.get("REHEARSE"))


def mem():
    return {k: round(v / 1e9, 3) for k, v in
            (jax.devices()[0].memory_stats() or {}).items()
            if k in ("bytes_in_use", "peak_bytes_in_use")}


def traced(fn):
    """Device seconds by operation of one call of ``fn`` under the
    profiler."""
    with tempfile.TemporaryDirectory(prefix="granite_probe_") as tdir:
        jax.profiler.start_trace(tdir)
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        wall = time.perf_counter() - t0
        jax.profiler.stop_trace()
        tr = xplane.reduce_dir(tdir, 1)
    return tr, wall


def kernel_alone(result, cfg):
    """The kernel over every recurrent layer in one scan, by rows in
    flight and by heads a grid step, against the einsum."""
    lm, h, p, n = (cfg.mamba_layers, cfg.mamba_n_heads, cfg.mamba_d_head,
                   cfg.mamba_d_state)
    ks = jax.random.split(jax.random.key(0), 5)
    state = jax.random.normal(ks[0], (lm, SLOTS, h, p, n), jnp.float32)
    da = jax.random.uniform(ks[1], (SLOTS, h), jnp.float32, 0.5, 1.0)
    dx = jax.random.normal(ks[2], (SLOTS, h, p), jnp.float32)
    b = jax.random.normal(ks[3], (SLOTS, n), jnp.float32)
    c = jax.random.normal(ks[4], (SLOTS, n), jnp.float32)

    def scan_all(state, live):
        rows, cnt = ssm.live_rows(live)

        def one(st, layer):
            y, st = ssm.ssm_decode(st, da, dx, b, c, rows, cnt, layer=layer)
            return st, jnp.sum(y)

        return jax.lax.scan(one, state, jnp.arange(lm, dtype=jnp.int32))

    out = {}
    live_all = jnp.ones((SLOTS,), bool)
    state_bytes = 2 * h * p * n * 4
    with jax.default_matmul_precision("highest"):  # the golden's einsum
        want_y, want_s = ssm.ssm_decode_reference(
            state[0], da, dx, b, c, live_all)
    got_y, got_s = jax.jit(lambda s: ssm.ssm_decode(
        s, da, dx, b, c, *ssm.live_rows(live_all), layer=0))(state)
    out["max_abs_err_y"] = float(jnp.max(jnp.abs(got_y - want_y)))
    out["max_abs_err_state"] = float(jnp.max(jnp.abs(got_s[0] - want_s)))
    out["untouched_layer_equal"] = bool(jnp.array_equal(got_s[1], state[1]))
    del got_s
    for heads in ((16, 64) if not REH else (h,)):
        ssm.HEADS = heads
        for live_n in (SLOTS, min(13, SLOTS), 1, 0):
            live = jnp.arange(SLOTS) < live_n
            f = jax.jit(scan_all, donate_argnums=(0,))
            state, _ = f(state, live)
            jax.block_until_ready(state)
            if REH:
                continue
            holder = {}

            def run():
                holder["s"], tot = f(state, live)
                return tot

            tr, _ = traced(run)
            state = holder["s"]
            sec = sum(v for k, v in tr.self_seconds().items()
                      if "tdt_ssm_decode" in k)
            out[f"heads{heads}_rows{live_n}"] = {
                "ms_a_layer": sec / lm * 1e3,
                "gb_s": live_n * state_bytes * lm / sec / 1e9 if sec else 0}
    ssm.HEADS = 16
    result["kernel"] = out
    print("kernel", json.dumps(out), flush=True)


def main():
    tag = sys.argv[1]
    dev = jax.devices()[0]
    assert dev.platform == "tpu" or REH, dev
    result = {"tag": tag, "device": dev.device_kind, "slots": SLOTS}
    ctx = initialize_distributed(tp=1, devices=jax.devices()[:1])
    t0 = time.perf_counter()
    model = AutoLLM.from_pretrained(
        "tiny-hybrid" if REH else "ibm-granite/granite-4.0-h-micro",
        ctx=ctx, seed=1)
    jax.block_until_ready(model.params)
    cfg = model.cfg
    result["init_s"] = round(time.perf_counter() - t0, 2)
    result["after_init"] = mem()
    print("init", result["init_s"], result["after_init"], flush=True)
    if os.environ.get("KERNEL", "1") == "1":
        kernel_alone(result, cfg)
    if os.environ.get("KERNEL") == "only":
        kernel_alone(result, cfg)
        os.makedirs("chiprun_out", exist_ok=True)
        with open(os.path.join("chiprun_out", f"granite_probe_{tag}.json"),
                  "w") as f:
            json.dump(result, f, indent=1)
        return
    page = 16 if REH else 128
    cache, _ = init_paged_cache(
        cfg, SLOTS, ctx, num_pages=SLOTS * (cfg.max_length // page) + 1,
        max_length=cfg.max_length, page_size=page)
    result["after_cache"] = mem()
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, SLOTS), jnp.int32)
    table_full = np.asarray(cache.page_table) + 1  # page 0 is the trash page
    t0 = time.perf_counter()
    logits, cache, counts = model.decode_step_counted(tokens, cache, "xla")
    np.asarray(logits)
    result["decode_compile_s"] = round(time.perf_counter() - t0, 2)
    result["after_decode"] = mem()
    print("decode compiled", result["decode_compile_s"], mem(), flush=True)
    for name, live_n in (("rows32", SLOTS), ("rows13", min(13, SLOTS)),
                         ("rows1", 1)):
        live = np.arange(SLOTS) < live_n
        lens = np.where(live, rng.integers(100, 900, SLOTS), 0)
        if REH:
            lens = lens // 10

        def reset(cache):
            return dataclasses.replace(
                cache, kv_len=jnp.asarray(lens, jnp.int32),
                page_table=jnp.asarray(np.where(live[:, None], table_full, 0)),
                live=jnp.asarray(live))

        cache = reset(cache)
        for _ in range(3):
            logits, cache, counts = model.decode_step_counted(
                tokens, cache, "xla")
        np.asarray(logits)
        cache = reset(cache)
        holder = {"c": cache}

        def run():
            for _ in range(STEPS):
                lg, holder["c"], holder["n"] = model.decode_step_counted(
                    tokens, holder["c"], "xla")
            return lg

        tr, wall = traced(run)
        cache = holder["c"]
        own = {} if REH else tr.self_seconds()
        mods = [(0, 0, 1)] if REH else tr.modules("decode")
        durs = sorted(e[2] for e in mods)
        kern = sum(v for k, v in own.items() if "tdt_ssm_decode" in k)
        attn = sum(v for k, v in own.items() if "tdt_flash_decode_paged" in k)
        moved = [k for k in own if "dynamic_update_slice" not in k
                 and "tdt_" not in k
                 and ("f32[36,32,64,64,128]" in k
                      or "f32[1152,64,64,128]" in k or "bf16[4,1025" in k)]
        result[name] = {
            "mean_len": float(lens.sum() / max(live_n, 1)),
            "step_ms_median": durs[len(durs) // 2] / 1e6,
            "steps_traced": len(mods),
            "wall_ms_a_step": wall / STEPS * 1e3,
            "ssm_kernel_ms_a_step": kern / STEPS * 1e3,
            "ssm_kernel_gb_s": (live_n * 2 * 64 * 64 * 128 * 4 * 36 * STEPS
                                / kern / 1e9 if kern else 0),
            "attn_kernel_ms_a_step": attn / STEPS * 1e3,
            "rows_last_step": np.asarray(holder["n"]).tolist(),
            "state_or_pool_shaped_ops": moved,
            "ops_ms_a_step": {k: round(v / STEPS * 1e3, 4) for k, v in
                              sorted(own.items(), key=lambda kv: -kv[1])[:28]},
        }
        print(tag, name, json.dumps(result[name]), flush=True)
    cache = dataclasses.replace(
        cache, page_table=jnp.asarray(table_full),
        live=jnp.zeros((SLOTS,), bool))
    for width in ((256, 512, 768, 1024) if not REH else (16,)):
        buf = rng.integers(0, cfg.vocab_size, width).astype(np.int32)
        t0 = time.perf_counter()
        lg, cache = model.prefill_paged_chunk(
            buf, 0, 0, width - 5, width - 6, cache, "xla",
            kv_pages=max(width // page, 1))
        np.asarray(lg)
        comp = time.perf_counter() - t0
        ts = []
        for _ in range(3):
            t0 = time.perf_counter()
            lg, cache = model.prefill_paged_chunk(
                buf, 0, 0, width - 5, width - 6, cache, "xla",
                kv_pages=max(width // page, 1))
            np.asarray(lg)
            ts.append(time.perf_counter() - t0)
        result[f"chunk{width}"] = {"compile_s": round(comp, 2),
                                   "ms": round(sorted(ts)[1] * 1e3, 2)}
        print(tag, f"chunk{width}", result[f"chunk{width}"], mem(), flush=True)
    if not REH:
        holder = {"c": cache}

        def run_chunk():
            lg, holder["c"] = model.prefill_paged_chunk(
                buf, 0, 0, width - 5, width - 6, holder["c"], "xla",
                kv_pages=width // page)
            return lg

        tr, _ = traced(run_chunk)
        result["chunk1024_ops_ms"] = {
            k: round(v * 1e3, 3) for k, v in
            sorted(tr.self_seconds().items(), key=lambda kv: -kv[1])[:20]}
        print(tag, "chunk_ops", json.dumps(result["chunk1024_ops_ms"]),
              flush=True)
    result["final"] = mem()
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", f"granite_probe_{tag}.json"),
              "w") as f:
        json.dump(result, f, indent=1)


if __name__ == "__main__":
    main()
