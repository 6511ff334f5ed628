"""Shared serving-stats schema.

``Engine.last_stats`` (a plain dict rebuilt per serve) and
``ContinuousEngine.last_stats`` (a property over live counters) grew
independently across PRs 1–4 and drifted silently — a dashboard keyed
on one engine's shape broke on the other. The CORE key set below is
the contract both engines MUST expose (asserted by
``tests/test_obs.py::test_core_stats_keys_unified``); everything else
remains engine-specific.

=====================  ================================================
``decode_steps``       batched decode device programs run — verify
                       chunks excluded; speculative serving counts
                       those in ``spec_verify_steps``, and BOTH engines
                       expose ``target_steps = decode_steps +
                       spec_verify_steps`` when speculation is on (the
                       "target forwards" a throughput model needs)
``prefill_tokens``     prompt tokens actually prefilled (prefix-cache
                       hits excluded — this is work DONE, not accepted)
``generated_tokens``   tokens emitted to callers (partials included)
``kv_bytes_per_token`` per-token KV footprint of the active cache
``kv_dtype``           KV storage dtype (the PR 4 quantization knob)
=====================  ================================================
"""

from __future__ import annotations

CORE_STATS_KEYS = (
    "decode_steps",
    "prefill_tokens",
    "generated_tokens",
    "kv_bytes_per_token",
    "kv_dtype",
)


def missing_core_stats(stats: dict) -> list[str]:
    """Core keys absent from ``stats`` (empty == conforming)."""
    return [k for k in CORE_STATS_KEYS if k not in stats]


# Registry metric (name, help) for each serving counter mirrored into
# the process metrics registry (docs/observability.md). ONE table for
# both engines: Registry._get_or_create keeps the first help string it
# sees for a name, so duplicated literals would drift silently with
# engine construction order.
STAT_METRICS = {
    "admitted": ("tdt_engine_admitted_total",
                 "Requests admitted to a decode slot."),
    "decode_steps": ("tdt_engine_decode_steps_total",
                     "Batched decode device programs run."),
    "prefill_tokens": ("tdt_engine_prefill_tokens_total",
                       "Prompt tokens prefilled (prefix hits excluded)."),
    "prefill_chunks": ("tdt_engine_prefill_chunks_total",
                       "Chunked-prefill programs run."),
    "prefix_hit_tokens": ("tdt_engine_prefix_hit_tokens_total",
                          "Prompt tokens served from the radix tree."),
    "pages_cow_copied": ("tdt_engine_pages_cow_total",
                         "Pages COW-cloned at admission."),
    "admission_stalls": ("tdt_engine_admission_stalls_total",
                         "Admission scans stalled for pool pages."),
    "generated_tokens": ("tdt_engine_generated_tokens_total",
                         "Tokens emitted (partials included)."),
    "spec_verify_steps": ("tdt_engine_spec_verify_steps_total",
                          "Speculative verify chunk programs run."),
    "spec_draft_tokens": ("tdt_engine_spec_draft_tokens_total",
                          "Draft tokens proposed."),
    "spec_accepted_tokens": ("tdt_engine_spec_accepted_tokens_total",
                             "Draft tokens accepted by verify."),
    "spec_rollback_tokens": ("tdt_engine_spec_rollback_tokens_total",
                             "Draft tokens rolled back after verify."),
    # Tree speculation (docs/serving.md "Speculative decoding"): multi-
    # branch draft trees verified in one forward. ``nodes`` counts
    # drafted trie nodes (root excluded — they are the spec_draft_tokens
    # of tree rounds), ``depth`` accumulates each tree's deepest drafted
    # path (divide by rounds for the mean), and ``branch_accepts``
    # counts rounds whose accepted path left the primary branch — the
    # rounds a linear draft would have lost outright.
    "spec_tree_rounds": ("tdt_spec_tree_rounds_total",
                         "Tree-speculation verify rounds (multi-branch "
                         "draft chunks)."),
    "spec_tree_nodes": ("tdt_spec_tree_nodes_total",
                        "Draft tree nodes verified (root excluded)."),
    "spec_tree_depth": ("tdt_spec_tree_depth_total",
                        "Cumulative deepest-drafted-path depth across "
                        "tree rounds."),
    "spec_tree_branch_accepts": ("tdt_spec_tree_branch_accepts_total",
                                 "Tree rounds whose accepted path left "
                                 "the primary branch (commit needed a "
                                 "KV row-move)."),
    "failed_requests": ("tdt_engine_failed_requests_total",
                        "Requests finished with a non-ok status "
                        "(client cancellations excluded — those count "
                        "in cancelled_requests)."),
    "cancelled_requests": ("tdt_engine_cancelled_requests_total",
                           "Requests torn down by a client "
                           "cancellation (the cancel verb or a "
                           "mid-stream disconnect)."),
    "shed_requests": ("tdt_engine_shed_requests_total",
                      "Requests shed by the bounded admission queue."),
    "deadline_expired": ("tdt_engine_deadline_expired_total",
                         "Requests failed on a wall-clock deadline."),
    "nonfinite_logits": ("tdt_engine_nonfinite_logits_total",
                         "Steps guarded for non-finite logits."),
    "decode_faults": ("tdt_engine_decode_faults_total",
                      "Exceptions isolated by the decode-phase step "
                      "guard."),
    # One-step lookahead of the single-step decode round (docs/
    # serving.md "The decode loop"): its share of ``decode_steps`` is
    # the engagement rate.
    "lookahead_steps": ("tdt_engine_lookahead_steps_total",
                        "Decode steps dispatched before the previous "
                        "step's tokens were fetched."),
    "lookahead_discarded": ("tdt_engine_lookahead_discarded_total",
                            "Slot-tokens of an in-flight step thrown "
                            "away because the slot ended first."),
    # Megakernel serving fast path (docs/megakernel.md "Serving fast
    # path"): NS-step fused launches vs the rounds that had to fall
    # back to single-step decode (max_length tail, top-k/top-p slots).
    "mega_launches": ("tdt_mega_launches_total",
                      "Megakernel NS-step decode launches."),
    "mega_fallback_steps": ("tdt_mega_single_step_fallbacks_total",
                            "Mega-mode rounds served by the single-step "
                            "fallback (tail or filtered sampling)."),
    # Device task tracer (docs/observability.md "Device task tracer").
    "mega_trace_launches": ("tdt_mega_trace_launches_total",
                            "Megakernel launches whose device trace "
                            "ring was decoded."),
    # Resident decode (docs/megakernel.md "Resident decode"): in-kernel
    # filtered sampling, batch-bucket launch programs, and device-side
    # stop-token retire.
    "mega_device_retires": ("tdt_mega_device_retires_total",
                            "Slots retired by the in-kernel stop-token "
                            "test (no host round trip)."),
    "mega_resident_rounds": ("tdt_mega_resident_rounds_total",
                             "Resident-session rounds issued before "
                             "the previous round's drain (pipelined "
                             "dispatch)."),
    "mega_bucket_launches": ("tdt_mega_bucket_launches_total",
                             "Mega launches served by a batch-bucket "
                             "program narrower than max_batch."),
    "mega_filtered_rounds": ("tdt_mega_filtered_rounds_total",
                             "Mega rounds sampled in-kernel through "
                             "the top-k/top-p bisection filter "
                             "(previously single-step fallbacks)."),
    # MoE serving (docs/serving.md "MoE serving"): token positions
    # routed through the expert FFN × top_k, and EP all-to-all drops —
    # the serving paths are LOSSLESS (splits-exchange protocol /
    # full-expert streaming), so a nonzero drop count is always a
    # detected error surfaced from ``DispatchState.num_dropped``
    # (ops/moe/ep_a2a.py), never silent truncation.
    "moe_routed_tokens": ("tdt_moe_routed_tokens_total",
                          "Expert assignments routed (token positions "
                          "through the MoE FFN × top_k)."),
    # One rank's share of an expert layer (docs/serving.md "Latent
    # attention and one rank's share"): two int32 sums every decode step
    # returns beside its tokens, over all expert layers and live rows.
    "moe_decode_local_rows": ("tdt_moe_decode_local_rows_total",
                              "Decode-step rows routed to an expert held "
                              "on this rank (all expert layers)."),
    "moe_decode_experts_touched": ("tdt_moe_decode_experts_touched_total",
                                   "Held experts that got at least one "
                                   "row, summed over expert layers and "
                                   "decode steps."),
    # A model with recurrent layers (docs/serving.md "Recurrent state
    # beside pages"): the int32 sum every decode step returns beside
    # its tokens, the rows IN FLIGHT whose state the step moved.
    "ssm_decode_rows": ("tdt_ssm_decode_rows_total",
                        "Rows whose recurrent state a decode step "
                        "advanced (each once a step, whatever the number "
                        "of recurrent layers)."),
    "a2a_dropped": ("tdt_moe_a2a_dropped_total",
                    "EP all-to-all assignments dropped (capacity-mode "
                    "overflow; 0 on the lossless serving paths)."),
    # Durable KV tier (docs/serving.md "Tiered KV"): radix evictions
    # spilled to host-RAM/disk instead of dropped, and admissions whose
    # prefix coverage was extended by faulting those pages back —
    # cheaper than re-prefilling them.
    "tier_spilled_pages": ("tdt_tier_spilled_pages_total",
                           "Evicted radix pages exported to the KV "
                           "tier instead of dropped."),
    "tier_hits": ("tdt_tier_hits_total",
                  "Admissions whose prefix coverage was extended by "
                  "the KV tier (≥1 page faulted back)."),
    "tier_faults": ("tdt_tier_faulted_pages_total",
                    "Pages faulted back from the KV tier into HBM "
                    "(written via write_page, mapped as tree pages)."),
    "tier_bytes": ("tdt_tier_bytes_faulted_total",
                   "Payload bytes faulted back from the KV tier."),
    "tier_remote_pages": ("tdt_tier_remote_pages_total",
                          "Tier pages faulted back from a PEER replica "
                          "over the KV fabric (subset of "
                          "tdt_tier_faulted_pages_total)."),
}

# Extra registry names mirroring the SAME counter as a STAT_METRICS
# entry — fleet spec-health dashboards key on the short ``tdt_spec_*``
# family while the per-engine ``tdt_engine_spec_*`` names stay the
# drill-down. ``_bump`` increments every handle of a key, so the alias
# can never drift from its primary.
STAT_METRIC_ALIASES = {
    "spec_draft_tokens": (
        ("tdt_spec_draft_tokens_total",
         "Draft tokens proposed (alias of "
         "tdt_engine_spec_draft_tokens_total for fleet spec-health "
         "dashboards)."),
    ),
    "spec_rollback_tokens": (
        ("tdt_spec_rollback_tokens_total",
         "Draft tokens rolled back after verify (alias of "
         "tdt_engine_spec_rollback_tokens_total for fleet spec-health "
         "dashboards)."),
    ),
}
