"""Speculative-decode microbench: target forwards per emitted token.

CPU-runnable (``JAX_PLATFORMS=cpu``, tiny model): the measured quantity
is the ALGORITHMIC win — how many target-model forward steps the engine
pays per emitted token — on a repetitive/structured workload, the
traffic shape self-drafting speculation exists for (templated answers,
code, greedy cycles). Plain decode pays exactly one forward per token;
speculative decode pays one forward per ACCEPTED-RUN of tokens, so
``steps_per_token`` drops toward ``1 / (K + 1)`` as acceptance rises.
Wall-clock on CPU is advisory (each verify chunk is a wider forward
than a single decode step — the chip-level win needs the chunk forward
to cost ~one decode step, which holds when decode is
memory-bandwidth-bound); ``step_reduction`` is the platform-independent
lever.

Output follows the perf/ convention: one JSON object with a
``provenance`` block, printed to stdout and written to
``perf/SPEC_DECODE.json`` (same shape as ``perf/PREFIX_CACHE.json`` so
the bench-trajectory tooling picks both up).

Usage:  JAX_PLATFORMS=cpu python perf/spec_decode_bench.py
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

# Workload shape: a multi-turn session — each turn's prompt is the
# conversation so far (system motif + every previous answer), the
# canonical prompt-lookup traffic: continuation/regeneration output
# overlaps spans ALREADY IN THE PROMPT, so the n-gram drafter reads the
# future out of the history. Turn 1 is cold (no overlap — measures the
# drafter's graceful degradation too).
MOTIF_TOKENS = 8
MOTIF_REPEATS = 3
NUM_TURNS = 3
GEN_LEN = 64
SPEC_K = 6
PAGE_SIZE = 16
MAX_LENGTH = 256
# Tree arm: a linear K=6 verify already pays a 16-row chunk
# (round_chunk(7) — bf16 sublane tile), so the tree spends the SAME
# chunk's pad rows on real draft nodes: budget round_chunk(K+1) = 16
# nodes, per-branch depth up to TREE_K, up to TREE_WIDTH branches.
# Equal verify-program width makes steps-per-token comparable 1:1.
TREE_K = 15
TREE_WIDTH = 4


def serve_session(eng):
    """Serve NUM_TURNS turns (clean per-step accounting: one active
    slot ⇒ one emitted token per target forward in the baseline arm),
    each turn asked TWICE — the agent-loop shape (perf/loadgen.py's
    "agentic" class): a retry/regeneration re-derives an answer whose
    chain the radix tree already holds, so the radix drafter reads the
    continuation straight out of the cache while the n-gram drafter
    still works from self-repetition alone. The next turn's prompt
    extends the conversation with the (first) answer. Greedy serving
    makes every arm walk the identical token stream (asserted in
    ``main`` — the speedup only counts if the bits match), so the arms
    stay comparable token-for-token."""
    rng = np.random.default_rng(0)
    motif = rng.integers(1, 200, size=MOTIF_TOKENS).astype(np.int32)
    prompt = np.tile(motif, MOTIF_REPEATS)
    steps = emitted = 0
    streams = []
    t0 = time.perf_counter()
    for _turn in range(NUM_TURNS):
        for _ask in range(2):  # ask, then the agent-loop re-ask
            outs = eng.run([(prompt, GEN_LEN)])
            st = eng.last_stats
            steps += st.get("target_steps",
                            st["decode_steps"] + st["spec_verify_steps"])
            emitted += len(outs[0])
            streams.append([int(t) for t in outs[0]])
        prompt = np.concatenate([prompt, outs[0].astype(np.int32)])
    return steps, emitted, time.perf_counter() - t0, streams


def main() -> int:
    from triton_distributed_tpu.models import AutoLLM
    from triton_distributed_tpu.models.continuous import ContinuousEngine

    ctx = mesh_mod.initialize_distributed(
        tp=min(4, len(jax.devices())), devices=jax.devices()[:4]
    )
    model = AutoLLM.from_pretrained("tiny", ctx=ctx, max_length=MAX_LENGTH)

    def build(speculative: int, width: int = 1) -> ContinuousEngine:
        # Every arm runs the prefix cache + chunked prefill (turn i+1's
        # prompt extends turn i's — the radix tree eats the prefill,
        # speculation eats the decode; the arms differ ONLY in
        # speculation, and arbitrary-length turn prompts admit through
        # the chunk path).
        return ContinuousEngine(
            model, max_batch=1, page_size=PAGE_SIZE, max_length=MAX_LENGTH,
            prefix_cache=True, prefill_chunk=32, speculative=speculative,
            spec_width=width,
        )

    # Warmup all arms (chunk/decode program compiles stay out of the
    # timings; the jit cache lives on the model and carries over).
    serve_session(build(SPEC_K))
    serve_session(build(0))
    serve_session(build(TREE_K, TREE_WIDTH))

    base_steps, base_tokens, base_s, base_streams = serve_session(build(0))
    spec = build(SPEC_K)
    spec_steps, spec_tokens, spec_s, spec_streams = serve_session(spec)
    st = spec.last_stats
    tree = build(TREE_K, TREE_WIDTH)
    assert tree._spec_tree, "tree arm must run the tree path"
    tree_steps, tree_tokens, tree_s, tree_streams = serve_session(tree)
    tt = tree.last_stats

    # The gate: a speedup only counts over the EXACT token stream plain
    # greedy decode emits — any divergence voids the measurement.
    assert spec_streams == base_streams, "linear arm diverged from greedy"
    assert tree_streams == base_streams, "tree arm diverged from greedy"
    assert tt["spec_tree_rounds"] > 0, "tree arm never drafted a tree"

    base_spt = base_steps / max(base_tokens, 1)
    spec_spt = spec_steps / max(spec_tokens, 1)
    tree_spt = tree_steps / max(tree_tokens, 1)
    reduction = base_spt / max(spec_spt, 1e-9)
    tree_reduction = base_spt / max(tree_spt, 1e-9)
    result = {
        "metric": "spec_decode_target_steps_per_token",
        "workload": {
            "motif_tokens": MOTIF_TOKENS,
            "motif_repeats": MOTIF_REPEATS,
            "num_turns": NUM_TURNS,
            "gen_len": GEN_LEN,
            "speculative_k": SPEC_K,
            "tree_k": TREE_K,
            "tree_width": TREE_WIDTH,
            "page_size": PAGE_SIZE,
        },
        "platform": jax.default_backend(),
        "baseline": {
            "target_steps": int(base_steps),
            "emitted_tokens": int(base_tokens),
            "steps_per_token": round(base_spt, 4),
            "wall_s": round(base_s, 3),
        },
        "speculative": {
            "target_steps": int(spec_steps),
            "emitted_tokens": int(spec_tokens),
            "steps_per_token": round(spec_spt, 4),
            "tokens_per_step": round(1.0 / max(spec_spt, 1e-9), 3),
            "accept_rate": round(st["spec_accept_rate"], 3),
            "draft_tokens": int(st["spec_draft_tokens"]),
            "rollback_tokens": int(st["spec_rollback_tokens"]),
            "wall_s": round(spec_s, 3),
        },
        "tree": {
            "target_steps": int(tree_steps),
            "emitted_tokens": int(tree_tokens),
            "steps_per_token": round(tree_spt, 4),
            "tokens_per_step": round(1.0 / max(tree_spt, 1e-9), 3),
            "accept_rate": round(tt["spec_accept_rate"], 3),
            "draft_tokens": int(tt["spec_draft_tokens"]),
            "rollback_tokens": int(tt["spec_rollback_tokens"]),
            "tree_rounds": int(tt["spec_tree_rounds"]),
            "tree_nodes": int(tt["spec_tree_nodes"]),
            "branch_accepts": int(tt["spec_tree_branch_accepts"]),
            "wall_s": round(tree_s, 3),
        },
        "step_reduction": round(reduction, 3),
        "tree_step_reduction": round(tree_reduction, 3),
        "provenance": {
            "harness": "perf/spec_decode_bench.py — a multi-turn "
            "session (each turn's prompt = conversation so far) served "
            "turn-per-run with max_batch=1 (one emitted token per "
            "target forward in the baseline arm); the speculative arm "
            "drafts K=6 from each request's own n-gram history — turn "
            "1 is cold, later turns draft from answer spans already in "
            "the prompt (the canonical prompt-lookup traffic); the "
            "tree arm drafts K=15×width-4 tries from the radix tree in "
            "the SAME 16-row verify chunk the linear arm pads (budget "
            "round_chunk(K+1)) — both speculative arms are gated on "
            "asserted bit-identity with the baseline's token streams",
            "caveat": "CPU wall-clock is advisory (a verify chunk is a "
            "wider forward than one decode step); step_reduction is "
            "the platform-independent lever — it bounds the chip-level "
            "speedup when decode steps are launch/bandwidth-bound",
        },
    }
    print(json.dumps(result), flush=True)
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "SPEC_DECODE.json")
    with open(out, "w") as f:
        f.write(json.dumps(result, indent=2) + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
