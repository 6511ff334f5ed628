"""Speculative decoding: drafter/verifier units, KV rollback helpers,
the distribution-preservation statistical proof, and engine-level
bit-identity of speculative greedy decode against the plain path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from triton_distributed_tpu.models import sampling
from triton_distributed_tpu.models.engine import Engine
from triton_distributed_tpu.models.paged_kv_cache import (
    PagePool,
    gather_bucket,
    truncate_pages,
)
from triton_distributed_tpu.models.speculative import (
    NGramDraft,
    SpecState,
    cap_draft,
    verify_greedy,
    verify_sampled,
)


# -- drafter ---------------------------------------------------------------


def test_ngram_draft_proposes_previous_continuation():
    d = NGramDraft(max_ngram=3, min_ngram=1)
    d.observe([1, 2, 3, 9, 1, 2, 3])
    # Tail trigram (1,2,3) last continued with 9, 1, 2, ...
    assert d.propose(3) == [9, 1, 2]
    assert d.propose(1) == [9]


def test_ngram_draft_prefers_longest_ngram():
    d = NGramDraft(max_ngram=2, min_ngram=1)
    # Unigram "2" continues with 7 early on; bigram (1, 2) continues
    # with 5 — the bigram match must win over the unigram one.
    d.observe([2, 7, 1, 2, 5, 0, 1, 2])
    assert d.propose(1) == [5]


def test_ngram_draft_no_match_is_empty():
    d = NGramDraft()
    d.observe([1, 2, 3, 4])
    assert d.propose(4) == []       # no token repeats: nothing to look up
    assert d.propose(0) == []
    assert NGramDraft().propose(3) == []  # empty history


def test_ngram_draft_truncates_near_end():
    d = NGramDraft(max_ngram=1)
    d.observe([4, 4])
    # The previous "4" ends at position 1; its continuation is just the
    # final token.
    assert d.propose(5) == [4]


def test_spec_state_adaptive_k():
    st = SpecState(8, k_min=1)
    assert st.k == 8
    st.record(8, 8)
    assert st.k == 8                # capped at k_max
    st.record(8, 3)
    assert st.k == 4                # reset to accepted-run + 1
    st.record(4, 0)
    st.record(2, 0)
    st.record(1, 0)
    assert st.k == 1                # floored at k_min
    st.record(1, 1)
    assert st.k == 3                # full accept grows by 2
    assert st.proposed == 24 and st.accepted == 12
    assert st.accept_rate == pytest.approx(0.5)
    st.record(0, 0)                 # empty drafts never move K
    assert st.k == 3


def test_cap_draft_budget_and_capacity():
    # Budget: never draft past gen budget (emission is draft+1).
    assert cap_draft(8, kv_len=0, budget=4, max_length=1024) == 3
    # Capacity: the padded chunk must fit under max_length.
    assert cap_draft(8, kv_len=100, budget=100, max_length=128) == 8
    assert cap_draft(31, kv_len=96, budget=100, max_length=128) == 31
    assert cap_draft(32, kv_len=96, budget=100, max_length=128) == 31
    # Only a 16-wide chunk fits: 15 drafts + pending pad to exactly 16.
    assert cap_draft(8, kv_len=112, budget=100, max_length=128) == 8
    assert cap_draft(16, kv_len=112, budget=100, max_length=128) == 15
    # Not even the zero-draft 16-wide chunk fits.
    assert cap_draft(8, kv_len=120, budget=100, max_length=128) == -1


# -- verify rules ----------------------------------------------------------


def _one_hotish(seq, v=8, sharp=50.0):
    """Logits [len(seq), v] whose argmax at row i is seq[i]."""
    out = np.zeros((len(seq), v), np.float32)
    for i, t in enumerate(seq):
        out[i, t] = sharp
    return out


def test_verify_greedy_accepts_matching_prefix():
    # Target argmaxes: 3, 5, 2, 7 — draft [3, 5, 9] accepts 2 then
    # corrects with the target's own token at the mismatch position.
    logits = _one_hotish([3, 5, 2, 7])
    a, nxt = verify_greedy(logits, [3, 5, 9])
    assert (a, nxt) == (2, 2)
    a, nxt = verify_greedy(logits, [3, 5, 2])
    assert (a, nxt) == (3, 7)       # full accept → bonus token
    a, nxt = verify_greedy(logits, [])
    assert (a, nxt) == (0, 3)       # zero-draft chunk == plain decode


def test_verify_sampled_preserves_target_distribution():
    """The acceptance-criteria statistical test: with a fixed draft
    token, the FIRST emitted token's empirical distribution over many
    keys must match the filtered target distribution — rejection
    sampling changes latency, never the law."""
    rng = np.random.default_rng(0)
    v = 8
    logits = np.asarray(rng.normal(size=(2, v)) * 1.5, np.float32)
    t, p, k = 0.9, 0.95, 6
    target = np.asarray(sampling.target_probs(
        jnp.asarray(logits[0]), t, p, k), np.float64)
    draft_tok = int(np.argsort(target)[-2])  # plausible but not argmax
    n = 4000
    counts = np.zeros(v, np.int64)
    accepted = 0
    for i in range(n):
        a, nxt, _ = verify_sampled(
            logits, [draft_tok], jax.random.key(i), t, p, k
        )
        first = draft_tok if a >= 1 else nxt
        counts[first] += 1
        accepted += a
    emp = counts / n
    assert np.abs(emp - target).sum() / 2 < 0.05  # total variation
    # Acceptance rate of a delta proposal is exactly p(d).
    assert accepted / n == pytest.approx(float(target[draft_tok]), abs=0.04)


def test_verify_sampled_rejects_zero_probability_draft():
    # A draft outside the filtered support must always be rejected and
    # the replacement drawn from the target support.
    logits = _one_hotish([3], v=8, sharp=50.0)
    for i in range(16):
        a, nxt, _ = verify_sampled(logits, [6], jax.random.key(i), 1.0)
        assert a == 0 and nxt == 3


# -- KV rollback helpers ---------------------------------------------------


def test_truncate_pages_releases_past_keep_len():
    pool = PagePool(8)
    pages = pool.allocate(4)
    free0 = len(pool.free)
    kept = truncate_pages(pool, pages, keep_tokens=33, page_size=16)
    assert kept == pages[:3]        # ceil(33/16) = 3 pages survive
    assert len(pool.free) == free0 + 1


def test_truncate_pages_boundary_and_noop():
    pool = PagePool(8)
    pages = pool.allocate(4)
    # Exactly on a page boundary: keep exactly keep/page pages.
    assert truncate_pages(pool, list(pages), 32, 16) == pages[:2]
    pool.release(pages[:2])
    pages = pool.allocate(4)
    free0 = len(pool.free)
    # keep_tokens covering (or exceeding) the list: no-op.
    assert truncate_pages(pool, pages, 64, 16) == pages
    assert truncate_pages(pool, pages, 999, 16) == pages
    assert len(pool.free) == free0
    # keep_tokens=0 releases everything (the eviction path).
    assert truncate_pages(pool, pages, 0, 16) == []
    assert len(pool.free) == free0 + 4


def test_truncate_pages_protects_shared_prefix():
    pool = PagePool(8)
    pages = pool.allocate(4)
    free0 = len(pool.free)
    # Shared prefix pages (owned by the radix tree) never release here,
    # even when keep_tokens would drop them.
    kept = truncate_pages(pool, pages, 0, 16, shared=2)
    assert kept == pages[:2]
    assert len(pool.free) == free0 + 2
    with pytest.raises(ValueError, match="shared"):
        truncate_pages(pool, pages, 0, 16, shared=7)


def test_gather_bucket_powers_of_two():
    assert gather_bucket(1, 16, 8) == 1
    assert gather_bucket(16, 16, 8) == 1
    assert gather_bucket(17, 16, 8) == 2
    assert gather_bucket(33, 16, 8) == 4
    assert gather_bucket(120, 16, 8) == 8
    assert gather_bucket(999, 16, 8) == 8  # capped at pages_per_seq


def test_rollback_kv_truncates_one_slot(own_model):
    from triton_distributed_tpu.models.paged_kv_cache import (
        init_paged_cache,
        rollback_kv,
    )

    cache, _pool = init_paged_cache(
        own_model.cfg, 2, own_model.ctx, own_model.axis, max_length=64,
        page_size=16,
    )
    cache.kv_len.block_until_ready()
    import dataclasses

    cache = dataclasses.replace(
        cache, kv_len=jnp.asarray([40, 25], jnp.int32)
    )
    cache = rollback_kv(cache, 0, 33)
    np.testing.assert_array_equal(np.asarray(cache.kv_len), [33, 25])


# -- engine integration ----------------------------------------------------


def test_continuous_speculative_greedy_bit_identical(own_model):
    """The headline exactness proof: speculative greedy decode emits
    the same tokens as plain decode, for repetitive (high-accept) and
    chaotic (rollback-heavy) prompts, and releases every page."""
    from triton_distributed_tpu.models.continuous import ContinuousEngine

    prompts = [
        np.asarray([5, 9, 2, 4] * 4, np.int32),     # repetitive
        np.asarray([7, 1, 3, 8, 6, 2, 4, 9], np.int32),
        np.asarray([11, 12, 13, 14], np.int32),
    ]
    gens = [12, 6, 5]
    golds = [
        Engine(own_model, temperature=0.0).serve(
            p[None], gen_len=g)[0, len(p):]
        for p, g in zip(prompts, gens)
    ]
    eng = ContinuousEngine(
        own_model, max_batch=2, page_size=16, max_length=128, speculative=4
    )
    free0 = len(eng.pool.free)
    outs = eng.run(list(zip(prompts, gens)))
    for got, gold in zip(outs, golds):
        np.testing.assert_array_equal(got, np.asarray(gold))
    assert len(eng.pool.free) == free0
    st = eng.last_stats
    # Ledger consistency: every rejected draft token was rolled back,
    # and target_steps is the verify + batched-decode total.
    assert st["spec_rollback_tokens"] == (
        st["spec_draft_tokens"] - st["spec_accepted_tokens"]
    )
    assert st["target_steps"] == (
        st["decode_steps"] + st["spec_verify_steps"]
    )
    assert st["spec_accepted_tokens"] > 0  # the repetitive prompt drafted


def test_engine_paged_speculative_greedy_bit_identical(own_model):
    prompts = np.asarray(
        [[5, 9, 2, 4] * 2, [7, 1, 3, 8, 6, 2, 4, 9]], np.int32
    )
    gold = Engine(own_model, temperature=0.0).serve(prompts, gen_len=10)
    eng = Engine(
        own_model, temperature=0.0, paged=True, page_size=16, speculative=4
    )
    out = eng.serve(prompts, gen_len=10, max_length=128)
    np.testing.assert_array_equal(out, gold)
    st = eng.last_stats
    assert st["spec_verify_steps"] >= 1
    # Per-row ledger: each verify emits accepted+1 for its row, each
    # batched fallback step emits 1 for EVERY row.
    assert (
        st["spec_accepted_tokens"]
        + st["spec_verify_steps"]
        + 2 * st["spec_decode_steps"]
        == 2 * 9
    )
    assert st["target_steps"] == (
        st["spec_verify_steps"] + st["spec_decode_steps"]
    )
    assert st["spec_tokens_per_step"] >= 1.0


def test_speculative_with_prefix_cache_warm_identical(own_model):
    """speculative=K coexists with prefix_cache=True: warm arrivals map
    shared pages AND speculate, still bit-identical to the dense
    golden."""
    from triton_distributed_tpu.models.continuous import ContinuousEngine

    p = np.asarray([5, 9, 2, 4] * 4, np.int32)
    gold = Engine(own_model, temperature=0.0).serve(
        p[None], gen_len=12)[0, 16:]
    eng = ContinuousEngine(
        own_model, max_batch=2, page_size=16, max_length=128, speculative=4,
        prefix_cache=True, prefill_chunk=16,
    )
    for _ in range(2):  # second arrival is the warm (shared-prefix) one
        outs = eng.run([(p, 12)])
        np.testing.assert_array_equal(outs[0], gold)
    assert eng.last_stats["prefix_hit_tokens"] > 0
    assert eng.last_stats["spec_accepted_tokens"] > 0


def test_speculative_smoke_fast(own_model):
    """Tier-1 CPU smoke (CI satellite): a short speculative run on both
    engines completes, bit-identical, with the counters present."""
    from triton_distributed_tpu.models.continuous import ContinuousEngine

    p = np.asarray([5, 9, 2, 4, 5, 9, 2, 4], np.int32)
    gold = Engine(own_model, temperature=0.0).serve(p[None], gen_len=6)
    eng = ContinuousEngine(
        own_model, max_batch=1, page_size=16, max_length=64, speculative=3
    )
    out = eng.run([(p, 6)])[0]
    np.testing.assert_array_equal(out, gold[0, 8:])
    for key in ("spec_verify_steps", "spec_accept_rate", "target_steps",
                "spec_rollback_tokens"):
        assert key in eng.last_stats


def test_speculative_requires_paged_and_non_mega(own_model):
    with pytest.raises(ValueError, match="paged"):
        Engine(own_model, speculative=2)
    with pytest.raises(ValueError, match="mega"):
        # A page that does not tile max_length as well: the refusal of the
        # flags comes before the one of the geometry.
        Engine(own_model, speculative=2, paged=True, mode="mega",
               page_size=48)
    from triton_distributed_tpu.models.continuous import ContinuousEngine

    with pytest.raises(ValueError, match="mega"):
        ContinuousEngine(own_model, mode="mega", speculative=2)


def test_continuous_speculative_sampled_lengths_and_ledger(own_model):
    """Sampled speculative serving: right lengths, ledger consistent
    (the distribution proof itself is the verify_sampled test)."""
    from triton_distributed_tpu.models.continuous import ContinuousEngine

    p = np.asarray([5, 9, 2, 4] * 2, np.int32)
    eng = ContinuousEngine(
        own_model, max_batch=2, page_size=16, max_length=64, speculative=3,
        temperature=0.8, top_p=0.9, top_k=8,
    )
    outs = eng.run([(p, 8), (p, 5)])
    assert [len(o) for o in outs] == [8, 5]
    st = eng.last_stats
    assert st["spec_rollback_tokens"] == (
        st["spec_draft_tokens"] - st["spec_accepted_tokens"]
    )
