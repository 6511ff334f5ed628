"""What the traced part of the window asked of the model: counts from
the program's live counters, contexts from the client's records. Shared
by the per-layer readers, so that they all count the same work."""

from __future__ import annotations

from benchmark import server, xplane

GENERATED = "tdt_engine_generated_tokens_total"
DECODE_STEPS = "tdt_engine_decode_steps_total"
PREFILL_TOKENS = "tdt_engine_prefill_tokens_total"
PREFILL_CHUNKS = "tdt_engine_prefill_chunks_total"


def traced(ctx: dict) -> dict | None:
    """Counter deltas over the traced span, with the decoded tokens'
    contexts and the prefilled prompts' lengths as the client saw them
    in the same span. None when the run traced nothing."""
    if "counters_trace_1" not in ctx:
        return None
    if "_traced" in ctx:
        return ctx["_traced"]
    d = server.delta(ctx["counters_trace_1"], ctx["counters_trace_0"])
    t0, t1 = ctx["trace_t0"], ctx["trace_t1"]
    contexts, prompts = [], []
    for rec in ctx["records"]:
        if not rec.ok:
            continue
        for j, t in enumerate(rec.token_ts):
            if t0 <= t <= t1:
                if j == 0:
                    prompts.append(rec.prompt_len)  # prefill made token 0
                else:
                    contexts.append(rec.prompt_len + j)
    out = {
        "generated": d.get(GENERATED, 0),
        "decode_steps": d.get(DECODE_STEPS, 0),
        "prefill_tokens": d.get(PREFILL_TOKENS, 0),
        "prefill_chunks": d.get(PREFILL_CHUNKS, 0),
        "client_decode_tokens": len(contexts),
        "client_context_sum": sum(contexts),
        "client_prompts": prompts,
    }
    ctx["_traced"] = out
    return out


def decode_work(ctx: dict) -> tuple | None:
    """(steps, tokens, context_sum) of the traced span's decode steps:
    steps and tokens from the program's counters, the mean context of a
    decoded token from the client's records."""
    w = traced(ctx)
    if not w or w["decode_steps"] <= 0 or not w["client_decode_tokens"]:
        return None
    # Token 0 of a request comes from its prefill, not from a step.
    tokens = max(w["generated"] - len(w["client_prompts"]), 0)
    mean_ctx = w["client_context_sum"] / w["client_decode_tokens"]
    return w["decode_steps"], tokens, int(mean_ctx * tokens)


def prefill_work(ctx: dict) -> tuple | None:
    """(prompt lengths, chunks): the client's prompts whose first token
    fell in the span, scaled to the program's prefill-token count."""
    w = traced(ctx)
    if not w or w["prefill_tokens"] <= 0 or not w["client_prompts"]:
        return None
    scale = w["prefill_tokens"] / sum(w["client_prompts"])
    # Scaling the COUNT of prompts, not their lengths, keeps the
    # attention term's square honest.
    n = max(int(round(len(w["client_prompts"]) * scale)), 1)
    lens = (w["client_prompts"] * (n // len(w["client_prompts"]) + 1))[:n]
    return lens, max(w["prefill_chunks"], 1)


def flops(ctx: dict) -> float | None:
    """FLOPs the traced span's prefill and decoded tokens require, by
    the configuration's own work counts."""
    config, work = ctx["cell"].config, ctx["cell"].work
    dec, pre = decode_work(ctx), prefill_work(ctx)
    if dec is None and pre is None:
        return None
    total = 0.0
    if dec:
        total += work.decode_flops(config, dec[1], dec[2])
    if pre:
        total += work.prefill_flops(config, pre[0])
    return total


def step_launches(ctx: dict, pattern: str, counted: str,
                  exclude: str | None = None) -> list:
    """The traced launches ``(start, dur)`` of a counted kind of step.
    Programs are taken by name where ``pattern`` matches one; the
    program under test names all its jitted steps ``jit__lambda``, so
    otherwise the step is found by its count: the program launched as
    often as the counter ``counted`` moved in the traced span (for
    prefill, every other program of a millisecond or more, if together
    they were launched that often)."""
    tr, w = ctx["trace"], traced(ctx)
    named = tr.modules(pattern)
    if named:
        return [(s, d) for _, s, d in named]
    if not w or w[counted] <= 0:
        return []
    progs = tr.programs(min_mean_ms=1.0)
    if exclude is None:
        name = tr.program_launched(w[counted])
        return progs.get(name, []) if name else []
    skip = tr.program_launched(w[exclude]) if w[exclude] > 0 else None
    rest = [e for k, v in progs.items() if k != skip for e in v]
    return sorted(rest) if xplane.near(len(rest), w[counted]) else []
