"""Prefix-cache microbench: TTFT + prefill work avoided, cold vs warm.

CPU-runnable (``JAX_PLATFORMS=cpu``, tiny model, interpret-mode
kernels): the measured quantity is the serving-path ALGORITHMIC win —
prefill tokens actually computed and time-to-first-token — on a
shared-system-prompt workload, the traffic shape the radix cache exists
for. Wall-clock numbers on CPU are indicative only (interpret-mode tax);
``prefill_work_avoided_frac`` is platform-independent and transfers to
the chip directly (prefill cost grows linear-plus in prefix length).

TTFT is measured exactly: each request is served with ``gen_len=1``, so
``run()`` returns right after admission emits the first token — prefill
plus one sampling step, the part the prefix cache shortens.

Output follows the perf/ convention: one JSON object with a
``provenance`` block, printed to stdout and written to
``perf/PREFIX_CACHE.json``.

Usage:  JAX_PLATFORMS=cpu python perf/prefix_cache_bench.py
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("TDT_AUTOTUNE_CACHE", "0")
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=4"
)

import jax  # noqa: E402

if jax.default_backend() != "tpu":
    jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

from triton_distributed_tpu.runtime import mesh as mesh_mod  # noqa: E402

# Workload shape: one shared system prompt, per-user suffixes.
SYSTEM_PROMPT_TOKENS = 96
USER_SUFFIX_TOKENS = 16
NUM_USERS = 4
PAGE_SIZE = 16
MAX_LENGTH = 256
PREFILL_CHUNK = 32


def serve_arrivals(eng, prompts):
    """Serve each prompt as its own arrival (one ``run()`` per request,
    ``gen_len=1``), timing each and summing the per-run counters."""
    ttfts, prefilled, hits = [], 0, 0
    for p in prompts:
        t0 = time.perf_counter()
        eng.run([(p, 1)])
        ttfts.append(time.perf_counter() - t0)
        st = eng.last_stats
        prefilled += st["prefill_tokens"]
        hits += st["prefix_hit_tokens"]
    return ttfts, prefilled, hits


def main() -> int:
    from triton_distributed_tpu.models import AutoLLM
    from triton_distributed_tpu.models.continuous import ContinuousEngine

    ctx = mesh_mod.initialize_distributed(
        tp=min(4, len(jax.devices())), devices=jax.devices()[:4]
    )
    model = AutoLLM.from_pretrained("tiny", ctx=ctx, max_length=MAX_LENGTH)
    rng = np.random.default_rng(0)
    system = rng.integers(1, 200, size=SYSTEM_PROMPT_TOKENS).astype(np.int32)
    prompts = [
        np.concatenate(
            [system,
             rng.integers(1, 200, size=USER_SUFFIX_TOKENS).astype(np.int32)]
        )
        for _ in range(NUM_USERS)
    ]
    prompt_tokens = sum(len(p) for p in prompts)

    def build(prefix_cache: bool) -> ContinuousEngine:
        return ContinuousEngine(
            model, max_batch=2, page_size=PAGE_SIZE, max_length=MAX_LENGTH,
            prefix_cache=prefix_cache, prefill_chunk=PREFILL_CHUNK,
        )

    # Warmup both arms with the FULL arrival set: chunk programs are
    # keyed on (width, kv-gather bucket), and warm arrivals hit bucket
    # combinations cold arrivals never do — every shape must compile
    # outside the timings (the jit cache lives on the model, so it
    # carries to the timed engines).
    serve_arrivals(build(True), prompts)
    serve_arrivals(build(False), prompts[:1])

    # COLD: no prefix cache — every arrival prefills its full prompt.
    cold_ttfts, cold_prefill, _ = serve_arrivals(build(False), prompts)

    # WARM: arrival 1 seeds the radix tree; 2..N map the shared system
    # prompt's pages and prefill only their user suffix.
    warm = build(True)
    warm_ttfts, warm_prefill, warm_hits = serve_arrivals(warm, prompts)

    avoided = 1.0 - warm_prefill / max(cold_prefill, 1)
    steady_cold = float(np.mean(cold_ttfts[1:]))
    steady_warm = float(np.mean(warm_ttfts[1:]))  # [0] is the cold seed
    result = {
        "metric": "prefix_cache_ttft_and_prefill_work",
        "workload": {
            "system_prompt_tokens": SYSTEM_PROMPT_TOKENS,
            "user_suffix_tokens": USER_SUFFIX_TOKENS,
            "num_users": NUM_USERS,
            "page_size": PAGE_SIZE,
            "prefill_chunk": PREFILL_CHUNK,
        },
        "platform": jax.default_backend(),
        "cold": {
            "ttft_s_mean": round(float(np.mean(cold_ttfts)), 4),
            "ttft_s_steady": round(steady_cold, 4),
            "prefill_tokens": int(cold_prefill),
            "prompt_tokens": int(prompt_tokens),
        },
        "warm": {
            "ttft_s_mean": round(float(np.mean(warm_ttfts)), 4),
            "ttft_s_steady": round(steady_warm, 4),
            "prefill_tokens": int(warm_prefill),
            "hit_tokens": int(warm_hits),
            "cow_pages": int(warm.prefix.stats["cow_pages"]),
            "hit_rate": round(warm.prefix.hit_rate, 3),
            "tree_pages": warm.prefix.node_count,
        },
        "prefill_work_avoided_frac": round(avoided, 4),
        "ttft_speedup_steady": round(steady_cold / max(steady_warm, 1e-9), 3),
        "provenance": {
            "harness": "perf/prefix_cache_bench.py — per-arrival "
            "ContinuousEngine.run(gen_len=1) calls against a persistent "
            "radix tree (tiny model, chunked prefill); ttft_s_steady "
            "drops the first arrival (cold seed / residual compile)",
            "caveat": "CPU wall-clock is interpret-mode-taxed and "
            "advisory; prefill_work_avoided_frac is the "
            "platform-independent lever (prefill cost ∝ prefix length)",
        },
    }
    print(json.dumps(result), flush=True)
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "PREFIX_CACHE.json")
    with open(out, "w") as f:
        f.write(json.dumps(result, indent=2) + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
