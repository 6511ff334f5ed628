"""Per-component decode-step profile — locate the ms/step gap on chip
(ROADMAP S2; not run on the chip in this round).

This harness times each weight-streaming component of the Qwen3-0.6B
decode step IN ISOLATION (chained in one fori_loop with a data
dependency so per-launch cost amortizes and XLA cannot CSE the
iterations), yielding achieved GB/s per matvec shape. The sum of
component floors vs the measured full-step rungs splits the gap into
"shape-level inefficiency" (XLA/Mosaic matvec quality per weight
matrix) vs "step-level overhead" (everything between the matmuls:
norms, rope, attention, collectives, scheduling).

Decode analog of the reference's per-op perf models
(``kernels/nvidia/gemm_perf_model.py:247`` — analytic floors used to
explain measured ladders, ``docs/mega_triton_kernel.md:27-37``).

Usage: python perf/decode_profile.py [--steps 64] [--out -]
"""

import argparse
import functools
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Qwen3-0.6B geometry (models/config.py PRESETS) at tp=1.
D, F, HQ, HKV, HD, L = 1024, 3072, 16, 8, 128, 28
# lm_head width at tp=1: 151936 is already 128-aligned, so _pad_lm_head
# (models/qwen.py) adds nothing. (tp>1 pads to a 128·tp multiple —
# recompute, don't reuse this constant, for a tp>1 profile.)
V_PAD = 151936

COMPONENTS = {
    # name: (d_in, d_out, per-layer count)
    "qkv": (D, HQ * HD + 2 * HKV * HD, L),
    "o_proj": (HQ * HD, D, L),
    "mlp_in": (D, 2 * F, L),   # fused gate+up
    "mlp_down": (F, D, L),
    "lm_head": (D, V_PAD, 1),
}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--steps", type=int, default=64,
                   help="chained iterations per component")
    p.add_argument("--batch", type=int, default=1)
    p.add_argument("--out", default="-")
    p.add_argument("--cpu", action="store_true",
                   help="force the CPU backend (smoke only: checks "
                        "the script runs, its numbers mean nothing)")
    args = p.parse_args(argv)

    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    from triton_distributed_tpu.runtime.utils import median_time

    out = sys.stdout if args.out == "-" else open(args.out, "a")

    def emit(rec):
        out.write(json.dumps(rec) + "\n")
        out.flush()

    platform = jax.devices()[0].platform
    emit({"profile": "decode_components",
          "device": jax.devices()[0].device_kind, "platform": platform,
          "steps": args.steps, "batch": args.batch})

    # Fixed per-execution cost (dispatch + fetch): timed on a trivial
    # program. Every "total/steps" number carries RT/steps of this on
    # top of true device time; the slope timings below subtract it out
    # instead.
    triv = jax.jit(lambda x: x + 1)
    x8 = jnp.zeros((8, 128))
    np.asarray(triv(x8))
    rt = median_time(lambda: np.asarray(triv(x8)))
    emit({"component": "fixed_dispatch_roundtrip", "ms": round(rt * 1e3, 3)})

    # Device-side init: no bulk host->device transfer (same reason
    # Qwen3._set_params_jit exists).
    key = jax.random.PRNGKey(0)

    def timed_matvec(d_in, d_out):
        w = jax.jit(
            lambda k: jax.random.normal(k, (d_in, d_out), jnp.bfloat16) * 0.02
        )(key)
        x0 = jax.jit(
            lambda k: jax.random.normal(k, (args.batch, d_in), jnp.bfloat16)
        )(key)
        jax.block_until_ready((w, x0))

        @functools.partial(jax.jit, static_argnums=2)
        def chain(x, w, steps):
            def body(_, x):
                y = jnp.dot(x, w, preferred_element_type=jnp.float32)
                # Data dependency: next x depends on the FULL product
                # (sum fences every output column) but stays d_in-wide.
                return x + (jnp.sum(y) * jnp.bfloat16(1e-8)).astype(x.dtype)

            return jax.lax.fori_loop(0, steps, body, x)

        # Slope timing: (T(2s) - T(s)) / s cancels the fixed dispatch
        # round-trip that total/steps folds in.
        t1 = median_time(lambda: np.asarray(chain(x0, w, args.steps)))
        t2 = median_time(lambda: np.asarray(chain(x0, w, 2 * args.steps)))
        sec = (t2 - t1) / args.steps
        # Host-clock noise can push the slope to ~0 or negative; flag
        # it rather than report absurd bandwidth.
        return sec, int(w.size * 2), sec * args.steps < 0.2 * t1

    total_floor_ms = 0.0
    any_noisy = False
    for name, (d_in, d_out, count) in COMPONENTS.items():
        sec, wbytes, noisy = timed_matvec(d_in, d_out)
        ms_step = max(sec, 0.0) * 1e3 * count
        total_floor_ms += ms_step
        rec = {"component": name, "shape": [d_in, d_out], "count": count,
               "ms_per_call": round(sec * 1e3, 4),
               "achieved_gbs": (None if noisy or sec <= 0
                                else round(wbytes / sec / 1e9, 1)),
               "ms_per_step_total": round(ms_step, 4)}
        if noisy:
            any_noisy = True
            rec["unreliable"] = "slope < 20% of base time — host-clock noise"
        emit(rec)

    # Attention component (the one non-matvec weight-class cost in the
    # step): flash-decode over the 0.6B ctx=512 cache, slope-timed the
    # same way — completes the floor split (norms/rope are VPU-bound
    # and fold into whatever they fuse with).
    from triton_distributed_tpu.ops.attention.flash_decode import (
        flash_decode,
    )

    S = 512
    q0 = jax.jit(lambda k: jax.random.normal(
        k, (args.batch, HQ, HD), jnp.bfloat16))(key)
    kc = jax.jit(lambda k: jax.random.normal(
        k, (args.batch, HKV, S, HD), jnp.bfloat16))(key)
    vc = jax.jit(lambda k: jax.random.normal(
        k, (args.batch, HKV, S, HD), jnp.bfloat16))(key)
    klen = jnp.full((args.batch,), S, jnp.int32)
    jax.block_until_ready((q0, kc, vc))

    @functools.partial(jax.jit, static_argnums=4)
    def attn_chain(q, kc, vc, kl, steps):
        def body(_, q):
            o = flash_decode(q, kc, vc, kl)
            return q + (jnp.sum(o) * jnp.bfloat16(1e-8)).astype(q.dtype)

        return jax.lax.fori_loop(0, steps, body, q)

    ta1 = median_time(
        lambda: np.asarray(attn_chain(q0, kc, vc, klen, args.steps)))
    ta2 = median_time(
        lambda: np.asarray(attn_chain(q0, kc, vc, klen, 2 * args.steps)))
    a_sec = (ta2 - ta1) / args.steps
    attn_bytes = int(kc.size + vc.size) * 2  # K+V read once per step
    a_ms_step = max(a_sec, 0.0) * 1e3 * L
    a_noisy = a_sec * args.steps < 0.2 * ta1
    rec = {"component": "attention", "shape": [HQ, HKV, S, HD],
           "count": L,
           "ms_per_call": round(a_sec * 1e3, 4),
           # Same convention as the matvec records: a noise-dominated
           # slope must not report an absurd bandwidth.
           "achieved_gbs": (None if a_noisy or a_sec <= 0
                            else round(attn_bytes / a_sec / 1e9, 1)),
           "ms_per_step_total": round(a_ms_step, 4)}
    total_floor_ms += a_ms_step
    if a_noisy:
        any_noisy = True
        rec["unreliable"] = "slope < 20% of base time — host-clock noise"
    emit(rec)

    # Per-grid-iteration overhead of a Pallas kernel: the megakernel
    # dispatches ~200 task iterations per decode step, so N µs/iter is
    # N*0.2 ms/step of pure scheduling. Slope over two grid sizes on a
    # near-empty arbitrary-semantics kernel (same dispatch machinery as
    # the megakernel's task loop, none of its work).
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def _tick_kernel(o_ref):
        o_ref[0, 0] = (pl.program_id(0) + 1).astype(jnp.float32)

    def grid_run(t):
        call = pl.pallas_call(
            _tick_kernel,
            grid=(t,),
            out_shape=jax.ShapeDtypeStruct((1, 128), jnp.float32),
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
            ),
            interpret=platform == "cpu",
        )
        f = jax.jit(call)
        return lambda: np.asarray(f())  # median_time warms up itself

    g1, g2 = (64, 192) if platform == "cpu" else (512, 1536)
    tg1 = median_time(grid_run(g1))
    tg2 = median_time(grid_run(g2))
    rec = {"component": "pallas_grid_iter_overhead",
           "us_per_iter": round((tg2 - tg1) / (g2 - g1) * 1e6, 2),
           "grid_sizes": [g1, g2]}
    # The matvec guard (slope vs base) doesn't transfer here: tg1 is
    # dominated by the fixed dispatch round-trip, not the measured
    # work, so a small TRUE per-iter overhead would trip it every run.
    # Only a non-positive slope is definitely noise; it does not taint
    # the (independent) matvec floor.
    if tg2 <= tg1:
        rec["unreliable"] = "non-positive slope — host-clock noise"
    emit(rec)

    # HBM stream anchor: one big reduction (pure read bandwidth, no MXU).
    big = jax.jit(
        lambda k: jax.random.normal(k, (64, 1024, 4096), jnp.bfloat16)
    )(key)
    jax.block_until_ready(big)

    @jax.jit
    def stream(x):
        return jnp.sum(x, dtype=jnp.float32)

    sec = median_time(lambda: np.asarray(stream(big)))
    emit({"component": "hbm_stream", "bytes": int(big.size * 2),
          "achieved_gbs": round(big.size * 2 / sec / 1e9, 1)})

    # KV-attention bytes are small at ctx=512 (~30 MB) but the gather +
    # softmax pipeline has fixed cost; time one flash-decode call class.
    summary = {
        "component_floor_ms_per_step": round(total_floor_ms, 3),
        "note": ("floor = sum of isolated matvec + attention times; "
                 "the full-step rungs add norms/rope/feedback/"
                 "scheduling — compare with bench.py ladder"),
    }
    if any_noisy:
        summary["unreliable"] = (
            "one or more component slopes were noise-dominated; "
            "the floor understates — re-run in a quieter window"
        )
    emit({"summary": summary})
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
