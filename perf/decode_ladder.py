"""Decode ladder benchmark: eager → jit → overlap-AR → megakernel.

Parity: the reference's headline table (``docs/mega_triton_kernel.md:27-37``)
— Qwen3 decode ms/step under torch eager / +cudagraph / triton_dist_AR /
megakernel. TPU rungs:

  eager      un-jitted per-step dispatch (torch-eager analog)
  jit        jitted decode step (CUDA-graph analog)
  pallas     jit + Pallas overlap ops (GEMM+AR decode; triton_dist_AR analog)
  mega       whole step as ONE Pallas kernel (megakernel analog)

Timing follows bench.py's rules: steps are chained with a data
dependency inside one jit where possible, and the fence is fetching
bytes to host (the value has to exist before the clock stops).
For the eager/mega rungs (host loop per step) we fetch the final token
each iteration batch.

Usage:
    python perf/decode_ladder.py --model tiny --batch 1 --ctx 512 --steps 32
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--model", default="tiny")
    p.add_argument("--batch", type=int, default=1)
    p.add_argument("--ctx", type=int, default=512)
    p.add_argument("--steps", type=int, default=32)
    p.add_argument("--ns", type=int, default=8,
                   help="multi-step launch width (steps per kernel launch)")
    p.add_argument("--tp", type=int, default=1)
    p.add_argument("--cpu", action="store_true", help="simulated CPU mesh")
    p.add_argument("--rungs", default="eager,jit,pallas,mega,mega_multi")
    args = p.parse_args(argv)

    if args.cpu:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8"
        )
        import jax

        jax.config.update("jax_platforms", "cpu")
    import jax
    import jax.numpy as jnp
    import numpy as np

    from triton_distributed_tpu.megakernel import MegaQwen3
    from triton_distributed_tpu.models import AutoLLM
    from triton_distributed_tpu.runtime.mesh import initialize_distributed

    ctx = initialize_distributed(tp=args.tp, devices=jax.devices()[: args.tp])
    model = AutoLLM.from_pretrained(args.model, ctx=ctx)
    B, S = args.batch, args.ctx

    def fresh_cache():
        c = model.new_cache(B, max_length=max(2 * S, S + args.steps + 8))
        c.kv_len = c.kv_len + S  # pretend S tokens prefilled
        return c

    tok0 = jnp.ones((B,), jnp.int32)
    results = {}
    rungs = args.rungs.split(",")

    def time_host_loop(step_fn, cache, steps):
        tok = tok0
        t0 = time.perf_counter()
        for _ in range(steps):
            logits, cache = step_fn(tok, cache)
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
        np.asarray(tok)  # host fetch = the only reliable fence
        return (time.perf_counter() - t0) / steps * 1e3

    if "eager" in rungs:
        f = model.decode_fn("xla")  # shard_map'd, un-jitted

        def eager_step(tok, cache):
            return f(model.params, tok, cache)

        time_host_loop(eager_step, fresh_cache(), 2)  # warm
        results["eager"] = time_host_loop(eager_step, fresh_cache(), max(args.steps // 8, 2))

    for name, mode in (("jit", "xla"), ("pallas", "pallas")):
        if name not in rungs:
            continue

        def jit_step(tok, cache, mode=mode):
            return model.decode_step(tok, cache, mode)

        time_host_loop(jit_step, fresh_cache(), 3)  # warm/compile
        results[name] = time_host_loop(jit_step, fresh_cache(), args.steps)

    if "mega" in rungs:
        mega = MegaQwen3(model)
        time_host_loop(mega.decode_step, fresh_cache(), 3)
        results["mega"] = time_host_loop(mega.decode_step, fresh_cache(), args.steps)

    if "mega_multi" in rungs:
        # NS greedy steps per launch (in-kernel argmax) — the rung that
        # amortizes the per-launch dispatch tax.
        mega = MegaQwen3(model)
        NS = min(args.ns, args.steps)
        c0 = fresh_cache()
        fn = mega.decode_multi_fn(B, int(c0.k.shape[3]), NS)

        def multi_loop(cache, launches):
            tok = tok0
            t0 = time.perf_counter()
            for _ in range(launches):
                toks, _lg, cache = fn(model.params, tok, cache)
                tok = toks[-1]
            np.asarray(tok)
            return (time.perf_counter() - t0) / (launches * NS) * 1e3

        multi_loop(c0, 1)  # warm/compile (c0 reused, then donated away)
        results["mega_multi"] = multi_loop(
            fresh_cache(), max(args.steps // NS, 1)
        )

    print(json.dumps({
        "model": args.model, "batch": B, "ctx": S, "tp": args.tp,
        "ms_per_step": {k: round(v, 3) for k, v in results.items()},
    }))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
