"""Slope-timed plain-GEMM MFU at multiple shapes (ROADMAP S1).

A perf model anchored on ONE measured GEMM is near-circular, and that
single point caps every overlap projection. This harness measures ≥3
INDEPENDENT shapes with slope timing — T(2n)-T(n) over chained,
data-dependent iterations inside one jit — so every fixed per-execution
cost cancels, and A/Bs the levers that usually explain a TPU MFU
deficit (not run on the chip in this round):

  * accumulation dtype (``preferred_element_type`` f32 vs bf16),
  * ``jax.lax.Precision`` (DEFAULT vs HIGHEST),
  * operand layout (contracting-dim order: ``a @ b`` vs ``(bT.T) @ b``).

Output: one JSON line per (shape, variant) with achieved TF/s and MFU,
plus a summary with the best-variant MFU per shape — either ≥70% MFU
is reachable with some variant (then the perf model and overlap
projections move to THAT configuration), or the deficit reproduces
across shapes/variants and is a platform cap to document.

Methodology matches the reference's analytic-vs-measured GEMM framing
(``kernels/nvidia/gemm_perf_model.py``) and de-circularizes the ≤15%
validation gate (VERDICT r3 task 6) by adding non-anchor points.

Usage: python perf/gemm_mfu.py [--shapes 4096,4096,4096;8192,4096,12288]
"""

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# (M, K, N) — north-star GEMM+RS anchor, its AG+GEMM mirror, a square
# anchor, two Qwen3-TP decode/prefill shapes (non-anchor points for
# the perf-model validation), and a tall-M shape (does feeding the MXU
# a longer M dimension move the MFU?).
DEFAULT_SHAPES = ("4096,4096,4096;8192,4096,12288;8192,12288,4096;"
                  "2048,2048,8192;512,1024,3072;16384,4096,4096")

_PEAK_TFS = {
    # bf16 dense peak per chip. v5e: 197 TF/s (public spec, also
    # BASELINE.json); v5p: 459; v4: 275; v6e: 918.
    "v5 lite": 197.0,
    "v5e": 197.0,
    "v5p": 459.0,
    "v4": 275.0,
    "v6 lite": 918.0,
    "v6e": 918.0,
}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--shapes", default=DEFAULT_SHAPES,
                   help="semicolon list of M,K,N")
    p.add_argument("--iters", type=int, default=8,
                   help="chained iterations for the base timing (the "
                        "slope uses iters and 2*iters)")
    p.add_argument("--reps", type=int, default=5)
    p.add_argument("--quick", action="store_true",
                   help="first two shapes, DEFAULT-precision variants "
                        "only (a short chip call)")
    p.add_argument("--cpu", action="store_true")
    args = p.parse_args(argv)

    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    from triton_distributed_tpu.runtime.utils import median_time

    kind = jax.devices()[0].device_kind.lower()
    peak = next((v for k, v in _PEAK_TFS.items() if k in kind), 197.0)
    platform = jax.devices()[0].platform
    print(json.dumps({"profile": "gemm_mfu", "device": kind,
                      "platform": platform, "peak_tfs": peak}), flush=True)

    key = jax.random.PRNGKey(0)

    def chained(iters, m, k, n, *, acc, prec, layout):
        """Build a runner: ``iters`` GEMMs chained by a non-foldable
        scalar carry (``jnp.sum(out)`` fences every output element —
        carrying one element lets XLA DCE-slice the GEMM to a single
        dot product)."""
        # Device-side init: no bulk host->device transfer.
        a = jax.jit(lambda s: jax.random.normal(
            s, (m, k), jnp.bfloat16) * 0.02)(key)
        if layout == "kt":
            bmat = jax.jit(lambda s: jax.random.normal(
                s, (n, k), jnp.bfloat16) * 0.02)(jax.random.fold_in(key, 1))
        else:
            bmat = jax.jit(lambda s: jax.random.normal(
                s, (k, n), jnp.bfloat16) * 0.02)(jax.random.fold_in(key, 1))
        jax.block_until_ready((a, bmat))

        import functools

        @functools.partial(jax.jit, static_argnums=2)
        def run(a, b, iters):
            def body(_, carry):
                x, s = carry
                bm = b.T if layout == "kt" else b
                out = jnp.dot(x, bm, preferred_element_type=acc,
                              precision=prec)
                s2 = jnp.sum(out, dtype=jnp.float32)
                # Fold the previous sum back into ONE input element so
                # iterations are data-dependent but the operand dtype,
                # shape, and magnitude are unchanged.
                x = x.at[0, 0].add((s2 * 1e-20).astype(x.dtype))
                return x, s + s2

            return jax.lax.fori_loop(
                0, iters, body, (a, jnp.float32(0)))[1]

        return lambda: np.asarray(run(a, bmat, iters))

    shapes = []
    for tok in args.shapes.split(";"):
        m, k, n = (int(v) for v in tok.split(","))
        shapes.append((m, k, n))
    if args.quick:
        shapes = shapes[:2]

    variants = [
        ("f32acc", dict(acc=jnp.float32, prec=None, layout="kn")),
        ("bf16acc", dict(acc=jnp.bfloat16, prec=None, layout="kn")),
        ("f32acc_kt", dict(acc=jnp.float32, prec=None, layout="kt")),
    ]
    if not args.quick:
        variants.append(
            ("highest", dict(acc=jnp.float32,
                             prec=jax.lax.Precision.HIGHEST, layout="kn")))

    best = {}
    for (m, k, n) in shapes:
        flops = 2.0 * m * k * n
        for vname, kw in variants:
            try:
                r1 = chained(args.iters, m, k, n, **kw)
                r2 = chained(2 * args.iters, m, k, n, **kw)
                t1 = median_time(r1, reps=args.reps)
                t2 = median_time(r2, reps=args.reps)
                sec = (t2 - t1) / args.iters
                rec = {"shape": [m, k, n], "variant": vname,
                       "ms": round(sec * 1e3, 3),
                       "base_ms": round(t1 * 1e3, 1)}
                if sec <= 0 or sec * args.iters < 0.1 * t1:
                    rec["unreliable"] = "slope noise-dominated"
                else:
                    tfs = flops / sec / 1e12
                    rec["tfs"] = round(tfs, 1)
                    rec["mfu"] = round(tfs / peak, 3)
                    cur = best.get((m, k, n))
                    if cur is None or tfs > cur[1]:
                        best[(m, k, n)] = (vname, tfs, sec * 1e3)
                print(json.dumps(rec), flush=True)
            except Exception as e:
                print(json.dumps({
                    "shape": [m, k, n], "variant": vname,
                    "error": f"{type(e).__name__}: {e}"[:200],
                }), flush=True)

    summary = {
        "best_mfu_by_shape": {
            f"{m}x{k}x{n}": {"variant": v, "tfs": round(t, 1),
                             "mfu": round(t / peak, 3)}
            for (m, k, n), (v, t, _ms) in best.items()
        },
        "note": ("mfu >= 0.7 for some variant => retune the perf model "
                 "to that variant; a uniform deficit across shapes and "
                 "variants => platform cap, record it in PERF.md"),
    }
    # Self-contained perf-model validation: predicted vs best-variant
    # measured per shape, so one run produces the full de-circularized
    # table on its own. Only meaningful on the chip the anchors
    # describe.
    if platform != "cpu" and best:
        # Best-effort: a post-processing failure (malformed anchors
        # file etc.) must never discard the measurements of a chip
        # call — the per-variant loop above catches exceptions for
        # exactly this reason.
        try:
            from triton_distributed_tpu.tools.perf_model import (
                anchored_spec,
                estimate_gemm_time_ms,
                measured_anchors,
            )

            spec, meta = anchored_spec()
            ga = (measured_anchors() or {}).get("gemm_anchor") or {}
            anchor_mkn = (ga.get("m"), ga.get("k"), ga.get("n"))
            validation = {}
            for (m, k, n), (_v, _t, ms) in best.items():
                if not ms or ms <= 0:
                    continue  # only slope-reliable rows reach best
                model_ms = estimate_gemm_time_ms(m, n, k, spec=spec)
                rel = abs(model_ms - ms) / ms
                row = {"measured_ms": round(ms, 3),
                       "model_ms": round(model_ms, 3),
                       "rel_err": round(rel, 3),
                       "within_15pct_gate": rel <= 0.15}
                if (m, k, n) == anchor_mkn:
                    # The shape the model's TF/s was solved from —
                    # listed for reference, excluded from
                    # independent-point counts (stays correct if the
                    # anchors file is retuned to another shape).
                    row["anchor_shape"] = True
                validation[f"{m}x{k}x{n}"] = row
            summary["model_validation"] = {
                "anchored": meta.get("anchored", False),
                "points": validation,
                "note": ("these measurements are slope-timed (fixed "
                         "per-call cost cancelled); a systematic "
                         "model-slow bias means the anchor absorbed "
                         "per-call cost — retune anchors from slope "
                         "numbers then"),
            }
        except Exception as e:
            summary["model_validation"] = {
                "error": f"{type(e).__name__}: {e}"[:200],
            }
    print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
