"""Overlap-efficiency benchmark: how much comm does AG+GEMM / GEMM+RS hide?

The north-star metric (BASELINE.md / reference ``README.md:190-205``
charts): fused-overlap time vs (pure GEMM, GEMM + blocking collective).
Comm-hidden fraction = (T_blocking − T_overlap) / T_comm — 1.0 means the
collective costs nothing extra.

Two modes:

1. **Measured (single chip)**: at tp=1 there is no comm, but the rung
   that CAN regress is the orchestrated Pallas kernel's compute path vs
   the plain XLA GEMM — kernel overhead, staging pipeline stalls. We
   measure that ratio (``kernel_efficiency``). Timing follows bench.py
   (data-dependent chaining inside one jit, host fetch as fence).
2. **Analytic (tp=8 projection)**: the perf model
   (``tools/perf_model.py``, parity with the reference's
   ``comm_perf_model.py``) prices GEMM and ring collectives at the
   survey north-star shapes and projects the hidden fraction the fused
   kernels target: T_overlap ≈ max(T_gemm, T_comm) + per-step latency.

Usage:
    python perf/overlap_efficiency.py [--cpu] [--m 8192 --k 4096 --n 12288]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def measured_kernel_efficiency(args, jax, jnp, np):
    """tp=1: fused-kernel GEMM path vs plain XLA dot (no comm rung)."""
    from triton_distributed_tpu.ops.overlap import AGGemmConfig, ag_gemm_op
    from triton_distributed_tpu.ops.overlap.ag_gemm import create_ag_gemm_context
    from triton_distributed_tpu.runtime.mesh import initialize_distributed

    ctx = initialize_distributed(tp=1, devices=jax.devices()[:1])
    m, k, n = args.m, args.k, args.n
    dt = jnp.bfloat16 if not args.cpu else jnp.float32
    key = jax.random.key(0)
    a = jax.random.normal(key, (m, k), jnp.float32).astype(dt)
    b = jax.random.normal(key, (k, n), jnp.float32).astype(dt)

    iters = 8

    def make(f):
        # Chain iterations with a data dependency; fence by host fetch.
        # Two traps make naive chaining flatter the pure-XLA rung:
        # (1) `a + acc * 0` folds, letting XLA hoist the GEMM out of the
        #     loop — the perturbation below is sub-ulp but not foldable;
        # (2) carrying `out[0, 0]` leaves all but one dot product DEAD —
        #     XLA can slice through the GEMM and compute a vector dot.
        #     `jnp.sum(out)` keeps every element live (the side-effecting
        #     Pallas rung never had this hazard, which silently skews the
        #     comparison).
        def chained(a, b):
            def body(_, acc):
                out = f(a + (acc * 1e-30).astype(a.dtype), b)
                return jnp.sum(out.astype(jnp.float32))

            return jax.lax.fori_loop(0, iters, body, jnp.float32(0))

        run = jax.jit(chained)
        np.asarray(run(a, b))  # compile + warm
        return run

    # Interleave the rungs and take medians: asynchronous dispatch can
    # let one call's work leak into the next measurement window (an inflated
    # rep immediately followed by an impossibly fast one), so min() over
    # sequential reps is untrustworthy.
    cfg = create_ag_gemm_context(m, n, k, dt)
    runs = {
        # Cast back to the input dtype so both rungs pay the same
        # epilogue (the fused kernel's output is bf16).
        "xla": make(
            lambda a, b: jnp.dot(
                a, b, preferred_element_type=jnp.float32
            ).astype(a.dtype)
        ),
        "fused": make(lambda a, b: ag_gemm_op(a, b, "tp", cfg, ctx)),
    }
    samples = {name: [] for name in runs}
    for _ in range(7):
        for name, run in runs.items():
            t0 = time.perf_counter()
            np.asarray(run(a, b))
            samples[name].append((time.perf_counter() - t0) / iters * 1e3)

    def median(xs):
        xs = sorted(xs)
        return xs[len(xs) // 2]

    t_xla = median(samples["xla"])
    t_fused = median(samples["fused"])
    return {
        "xla_gemm_ms": round(t_xla, 3),
        "fused_kernel_ms": round(t_fused, 3),
        "kernel_efficiency": round(t_xla / max(t_fused, 1e-9), 4),
    }


def analytic_projection(args, jnp):
    """tp=8 projection from the perf model, anchored to measured
    hardware when a record exists: the spec's HBM/MXU/ICI rates then
    come from ``perf/MEASURED.json`` via ``anchored_spec`` rather than
    datasheet peaks, with the record's error bars. No record exists in
    this round, so the projection is from the datasheet spec and says
    ``anchored: false``."""
    from triton_distributed_tpu.tools.perf_model import (
        anchored_spec,
        chip_spec,
        estimate_all_gather_time_ms,
        estimate_gemm_time_ms,
        estimate_reduce_scatter_time_ms,
    )

    tp = args.tp
    m, k, n = args.m, args.k, args.n
    dt = jnp.bfloat16
    if args.datasheet:
        spec, meta = chip_spec(args.chip), {"anchored": False}
    else:
        spec, meta = anchored_spec()
    ebar = meta.get("error_bars_frac", 0.0)
    out = {"anchoring": meta}

    def entry(t_gemm, t_comm):
        # Fused: compute starts on the local chunk immediately;
        # per-chunk arrival latency exposes ~1/tp of the shorter leg.
        def frac_at(tc):
            t_o = max(t_gemm, tc) + min(t_gemm, tc) / tp
            return (t_gemm + tc - t_o) / max(tc, 1e-9)

        t_overlap = max(t_gemm, t_comm) + min(t_gemm, t_comm) / tp
        t_blocking = t_gemm + t_comm
        # Error bars perturb the COMM leg only — MEASURED.json's ±30%
        # belongs to the unmeasurable-ICI proxy; the gemm anchor is a
        # stable within-process median. Both endpoints recompute the
        # whole expression consistently (in the compute-bound regime the
        # fraction is flat at 1 - 1/tp, so the range collapses there).
        endpoints = sorted(
            (frac_at(t_comm * (1 + ebar)), frac_at(t_comm * (1 - ebar)))
        )
        return {
            "gemm_ms": round(t_gemm, 3),
            "comm_ms": round(t_comm, 3),
            "blocking_ms": round(t_blocking, 3),
            "overlap_ms": round(t_overlap, 3),
            "comm_hidden_frac": round(frac_at(t_comm), 4),
            "comm_hidden_frac_range": [
                round(endpoints[0], 4), round(endpoints[1], 4)
            ],
        }

    # AG+GEMM: gather A rows [m, k], each device computes [m, k]@[k, n/tp].
    out["ag_gemm"] = entry(
        estimate_gemm_time_ms(m, n // tp, k, dt, spec),
        estimate_all_gather_time_ms(m * k * 2, tp, spec=spec),
    )

    # GEMM+RS: [m, k/tp]@[k/tp, n] partials reduced+scattered over rows.
    # Three kernel variants (ops/overlap/gemm_rs.py GemmRSConfig):
    # single ring (one ICI direction), counter-rotating dual rings
    # (both directions = the model's bidir rate), and dual rings with
    # the fp8 wire hop (half the bytes again).
    t_gemm = estimate_gemm_time_ms(m, n, k // tp, dt, spec)
    rs_bytes = m * n * 2
    out["gemm_rs_unidir"] = entry(
        t_gemm,
        estimate_reduce_scatter_time_ms(rs_bytes, tp, spec=spec, bidir=False),
    )
    t_rs_bidir = estimate_reduce_scatter_time_ms(rs_bytes, tp, spec=spec)
    out["gemm_rs"] = entry(t_gemm, t_rs_bidir)
    out["gemm_rs_fp8_wire"] = entry(t_gemm, t_rs_bidir / 2)
    out["chip"] = spec.name
    return out


def model_validation(args, jnp):
    """Model-vs-measured at tp=1 (the verdict's ≤15% gate): predict the
    north-star GEMM and fused-kernel times from the anchored spec and
    compare against the RECORDED on-chip medians in MEASURED.json."""
    from triton_distributed_tpu.tools.perf_model import (
        anchored_spec,
        estimate_gemm_time_ms,
        measured_anchors,
    )

    anchors = measured_anchors()
    g = (anchors or {}).get("gemm_anchor")
    if not g:
        return {"available": False}
    spec, _ = anchored_spec(anchors)
    pred = estimate_gemm_time_ms(g["m"], g["n"], g["k"], jnp.bfloat16, spec)
    rows = {
        "xla_gemm": {"measured_ms": g["ms"], "model_ms": round(pred, 3)},
    }
    if "fused_ms" in g:
        # The fused kernel runs the same GEMM through the manual staging
        # pipeline; the model charges the same roofline (measured
        # kernel_efficiency 0.95-0.97 — within the model's resolution).
        rows["fused_kernel"] = {
            "measured_ms": g["fused_ms"], "model_ms": round(pred, 3),
        }
    for r in rows.values():
        r["rel_err"] = round(abs(r["model_ms"] - r["measured_ms"])
                             / r["measured_ms"], 4)
    return {
        "available": True, "tp1": rows,
        "max_rel_err": max(r["rel_err"] for r in rows.values()),
        "note": (
            "xla_gemm IS the anchor (rel_err 0 by construction); the "
            "fused_kernel row is the independent check. Add non-anchor "
            "shapes on the next on-chip session for a stronger gate."
        ),
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--m", type=int, default=8192)
    p.add_argument("--k", type=int, default=4096)
    p.add_argument("--n", type=int, default=12288)
    p.add_argument("--tp", type=int, default=8)
    p.add_argument("--chip", default=None, help="chip kind for the model")
    p.add_argument("--cpu", action="store_true")
    p.add_argument("--skip-measure", action="store_true")
    p.add_argument("--datasheet", action="store_true",
                   help="use datasheet peaks instead of measured anchors")
    args = p.parse_args(argv)

    if args.cpu:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=1"
        )
        import jax

        jax.config.update("jax_platforms", "cpu")
    import jax
    import jax.numpy as jnp
    import numpy as np

    result = {
        "shapes": {"m": args.m, "k": args.k, "n": args.n, "tp": args.tp},
        "projection_tp8": analytic_projection(args, jnp),
        "model_validation": model_validation(args, jnp),
    }
    if not args.skip_measure:
        result["measured_tp1"] = measured_kernel_efficiency(args, jax, jnp, np)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
