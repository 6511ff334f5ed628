"""On-chip randomized stress subset — Mosaic races interpret can't see.

VERDICT r3 task 8: the interpret-mode stress suite (tests/test_stress.py)
proves semantics but runs a simulator; only the real chip exercises
Mosaic's actual DMA/semaphore interleavings. This script loops the
tp=1-runnable hot paths with fresh random data each iteration (the
reference's stress pattern: ``stress_test_ag_gemm.py:54-81`` —
randomized loop, fixed shapes so nothing recompiles, golden check every
iteration):

  * megakernel multi-step decode — NS-step chain tokens must be
    BIT-IDENTICAL to the single-step chain (same kernel math, different
    launch structure: any staging/feedback race diverges them);
  * wq8 int8 decode — same single-vs-multi identity on the quantized
    kernels;
  * flash-decode — Pallas split-KV kernel vs the pure-XLA golden at
    randomized kv_len (tolerance; exercises the chunked softmax DMAs).

Exit 0 only on zero failures across all iterations.

Usage: python perf/onchip_stress.py [--iters 20]
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--iters", type=int, default=20)
    p.add_argument("--steps", type=int, default=16)
    p.add_argument("--ns", type=int, default=8)
    p.add_argument("--layers", type=int, default=4,
                   help="reduced depth (a quick run; geometry stays "
                        "true 0.6B so tiles/DMAs are production-shaped)")
    p.add_argument("--cpu", action="store_true")
    args = p.parse_args(argv)

    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    from triton_distributed_tpu.models import AutoLLM
    from triton_distributed_tpu.runtime.mesh import initialize_distributed

    t0 = time.time()
    ctx = initialize_distributed(tp=1, devices=jax.devices()[:1])
    model = AutoLLM.from_pretrained(
        "Qwen/Qwen3-0.6B", ctx=ctx, max_length=1024,
        num_layers=args.layers, vocab_size=32768,
    )
    jax.block_until_ready(model.params)

    from perf._chain import (
        multi_step_chain,
        prepare_decode_state,
        single_step_chain,
    )
    from triton_distributed_tpu.megakernel import MegaQwen3
    from triton_distributed_tpu.megakernel.code_generator import MegaConfig

    steps, ns = args.steps, args.ns
    failures = []

    def log(rec):
        print(json.dumps(rec), flush=True)

    # --- megakernel single-vs-multi (bf16 and wq8) --------------------
    for label, cfg in (("mega_bf16", None),
                       ("mega_q8", MegaConfig(wq8=True))):
        mega = MegaQwen3(model, cfg=cfg)
        params = (mega.quantized_params() if label == "mega_q8"
                  else mega._step_params())
        tok0, cache0, s_max = prepare_decode_state(model)
        sstep = mega.decode_fn(1, s_max)
        mstep = mega.decode_multi_fn(1, s_max, ns)
        n_fail = 0
        for it in range(args.iters):
            # Fresh random greedy start: perturb the starting token
            # (cache contents follow from the model's own prefill; the
            # race surface is the decode chain itself). Shapes are
            # fixed, so nothing recompiles across iterations.
            tok = jnp.asarray([(7 * it + 3) % 32768], jnp.int32)
            s_seq = single_step_chain(sstep, params, tok, cache0, steps)()
            m_seq = multi_step_chain(mstep, ns, params, tok, cache0, steps)()
            if (s_seq != m_seq).any():
                n_fail += 1
                failures.append({"path": label, "iter": it,
                                 "single": s_seq.tolist(),
                                 "multi": m_seq.tolist()})
        log({"path": label, "iters": args.iters, "failures": n_fail,
             "elapsed_s": round(time.time() - t0, 1)})

    # --- flash-decode vs golden at randomized kv_len ------------------
    from triton_distributed_tpu.ops.attention.flash_decode import (
        flash_decode,
        gqa_decode_reference,
    )

    B, HQ, HKV, HD, S = 2, 16, 8, 128, 512
    key = jax.random.PRNGKey(0)

    @jax.jit
    def fresh(key):
        kq, kk, kv = jax.random.split(key, 3)
        q = jax.random.normal(kq, (B, HQ, HD), jnp.bfloat16)
        kc = jax.random.normal(kk, (B, HKV, S, HD), jnp.bfloat16)
        vc = jax.random.normal(kv, (B, HKV, S, HD), jnp.bfloat16)
        return q, kc, vc

    fd = jax.jit(lambda q, kc, vc, kl: flash_decode(q, kc, vc, kl))
    gold_f = jax.jit(
        lambda q, kc, vc, kl: gqa_decode_reference(q, kc, vc, kl))
    n_fail = 0
    rng = np.random.default_rng(0)
    for it in range(args.iters):
        key = jax.random.fold_in(key, it)
        q, kc, vc = fresh(key)
        kl = jnp.asarray(rng.integers(1, S + 1, size=(B,)), jnp.int32)
        got = np.asarray(fd(q, kc, vc, kl), np.float32)
        want = np.asarray(gold_f(q, kc, vc, kl), np.float32)
        if np.isnan(got).any() or np.abs(got - want).max() > 2e-2:
            n_fail += 1
            failures.append({
                "path": "flash_decode", "iter": it,
                "kv_len": kl.tolist(),
                "max_err": float(np.abs(got - want).max()),
            })
    log({"path": "flash_decode", "iters": args.iters, "failures": n_fail,
         "elapsed_s": round(time.time() - t0, 1)})

    log({"summary": {"total_failures": len(failures),
                     "failures": failures[:5],
                     "platform": jax.devices()[0].platform,
                     "wall_s": round(time.time() - t0, 1)}})
    return 0 if not failures else 1


if __name__ == "__main__":
    raise SystemExit(main())
