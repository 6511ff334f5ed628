"""Tile-config sweep for the fused overlap kernels on the real chip.

The tp=1 compute path of ``ag_gemm`` is a pure staged GEMM — the rung
where manual staging can lose to XLA. B is streamed once per (step,
M-tile) pair, so HBM traffic scales with ``m_per / tile_m``; this sweep
finds the (tile_m, tile_n) that closes the gap to the XLA GEMM.

Timing follows bench.py (chained iterations inside one jit, host fetch
as fence).

Usage:
    python perf/sweep_overlap_tiles.py [--m 8192 --k 4096 --n 12288]
"""

import argparse
import itertools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--m", type=int, default=8192)
    p.add_argument("--k", type=int, default=4096)
    p.add_argument("--n", type=int, default=12288)
    p.add_argument("--op", default="ag_gemm", choices=["ag_gemm", "gemm_rs"])
    p.add_argument("--iters", type=int, default=8)
    p.add_argument("--deadline-s", type=float, default=1800,
                   help="stop starting new configs past this wall "
                        "budget and report best-so-far (compiling "
                        "every config can outlast a chip call; "
                        "0 disables)")
    p.add_argument("--cpu", action="store_true")
    args = p.parse_args(argv)
    t_start = time.time()

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

    from triton_distributed_tpu.ops.overlap import (
        AGGemmConfig,
        GemmRSConfig,
        ag_gemm_op,
        gemm_rs_op,
    )
    from triton_distributed_tpu.runtime.mesh import initialize_distributed

    ctx = initialize_distributed(tp=1, devices=jax.devices()[:1])
    m, k, n = args.m, args.k, args.n
    dt = jnp.float32 if args.cpu else jnp.bfloat16
    key = jax.random.key(0)
    a = jax.random.normal(key, (m, k), jnp.float32).astype(dt)
    b = jax.random.normal(key, (k, n), jnp.float32).astype(dt)

    def timed(f, iters=args.iters):
        def chained(a, b):
            def body(_, acc):
                # Sub-ulp perturbation: data-dependent but not foldable.
                # Sum (not element-pick) carry: every output element stays
                # live, so XLA can't DCE-slice the GEMM (see
                # overlap_efficiency.py).
                out = f(a + (acc * 1e-30).astype(a.dtype), b)
                return jnp.sum(out.astype(jnp.float32))

            return jax.lax.fori_loop(0, iters, body, jnp.float32(0))

        run = jax.jit(chained)
        np.asarray(run(a, b))  # compile + warm
        # Median, not min: one call's device work can leak into the
        # next measurement window (an inflated rep followed by an
        # impossibly fast one) — min() latches onto the leak.
        ts = []
        for _ in range(5):
            t0 = time.perf_counter()
            np.asarray(run(a, b))
            ts.append((time.perf_counter() - t0) / iters)
        return sorted(ts)[len(ts) // 2] * 1e3

    t_xla = timed(
        lambda a, b: jnp.dot(a, b, preferred_element_type=jnp.float32).astype(dt)
    )
    print(json.dumps({"config": "xla", "ms": round(t_xla, 3)}), flush=True)

    results = []
    tile_ms = [256, 512, 1024, 2048]
    tile_ns = [512, 1024, 1536]
    for tile_m, tile_n in itertools.product(tile_ms, tile_ns):
        if args.deadline_s and time.time() - t_start > args.deadline_s:
            print(json.dumps({"deadline_s": args.deadline_s,
                              "stopped_at": f"tm{tile_m}_tn{tile_n}"}),
                  flush=True)
            break
        if m % tile_m or n % tile_n:
            continue
        itemsize = jnp.dtype(dt).itemsize
        vmem = (2 * tile_m * k + 2 * k * tile_n + 2 * tile_m * tile_n) * itemsize
        if vmem > 110 * 1024 * 1024:
            continue
        if args.op == "ag_gemm":
            cfg = AGGemmConfig(tile_n=tile_n, tile_m=tile_m)
            f = lambda a, b, cfg=cfg: ag_gemm_op(a, b, "tp", cfg, ctx)
        else:
            # force_kernel: without it the tp=1 path short-circuits to a
            # plain XLA dot and the sweep times XLA at every config.
            cfg = GemmRSConfig(tile_n=tile_n, tile_m=tile_m,
                               force_kernel=True)
            f = lambda a, b, cfg=cfg: gemm_rs_op(a, b, "tp", cfg, ctx)
        try:
            ms = timed(f)
        except Exception as e:
            print(
                json.dumps(
                    {
                        "config": f"tm{tile_m}_tn{tile_n}",
                        "error": f"{type(e).__name__}: {e}"[:200],
                    }
                ),
                flush=True,
            )
            continue
        rec = {
            "config": f"tm{tile_m}_tn{tile_n}",
            "ms": round(ms, 3),
            "efficiency": round(t_xla / ms, 4),
        }
        results.append(rec)
        print(json.dumps(rec), flush=True)

    if results:
        best = min(results, key=lambda r: r["ms"])
        print(json.dumps({"best": best, "xla_ms": round(t_xla, 3)}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
