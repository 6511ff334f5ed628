"""Tree speculation (ISSUE 16): multi-branch draft tries from the radix
tree verified in ONE chunked forward under a tree-attention mask.

Covers the trie builder (shape / budget / mask / rope depths), the
greedy and sampled tree-verify walks (including the distribution-
preservation statistical proof for the sampled walk), the row-move
COMMIT primitive, the radix/tier continuation proposers, the adaptive
width×depth controller, and engine-level bit-identity of tree-
speculative greedy decode against the plain path — including a forced
non-first-branch accept that exercises ``move_kv_rows`` end to end,
seeded-sampled replay across a slot migration with trees on, and the
``spec.verify`` fault seams on the tree path.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from triton_distributed_tpu.models import sampling
from triton_distributed_tpu.models.engine import Engine
from triton_distributed_tpu.models.paged_kv_cache import (
    PagePool,
    init_paged_cache,
    move_kv_rows,
)
from triton_distributed_tpu.models.prefix_cache import PrefixCache
from triton_distributed_tpu.models.speculative import (
    SpecState,
    TreeDraft,
    verify_tree_greedy,
    verify_tree_sampled,
)

# Repetitive motif → the radix tree (and the n-gram fallback) actually
# drafts; 4-token period keeps page boundaries interesting at ps=16.
MOTIF = [5, 9, 2, 4]


def golden(model, prompt, gen):
    return Engine(model, temperature=0.0).serve(
        np.asarray([prompt], np.int32), gen_len=gen
    )[0, len(prompt):]


# -- TreeDraft: trie shape, budget, mask, rope depths ----------------------


def test_tree_draft_trie_shape_and_budget():
    """``add_path`` builds a prefix-sharing trie in DFS insertion order
    (parent index < child index — the invariant the leftward row-move
    commit rests on) and stops at the node budget."""
    t = TreeDraft(5)
    assert t.add_path([1, 2, 3]) == 3
    assert t.add_path([1, 4]) == 1      # shares the [1] prefix
    assert t.add_path([7]) == 1
    assert t.tokens == [5, 1, 2, 3, 4, 7]
    assert t.parent == [-1, 0, 1, 2, 1, 0]
    assert t.depth == [0, 1, 2, 3, 2, 1]
    assert not t.is_chain
    assert t.num_drafted == 5 and t.max_depth == 3
    for i, p in enumerate(t.parent[1:], 1):
        assert p < i  # DFS order: storage index ≥ depth
    # Budget truncates, never overflows.
    b = TreeDraft(5)
    assert b.add_path([1, 2, 3, 4, 5], budget=4) == 3
    assert len(b) == 4
    assert b.add_path([1, 9], budget=4) == 0  # full: nothing added
    # Single-path trees are chains (the engines fall back to the
    # linear drafter so non-branching candidates change NOTHING).
    c = TreeDraft(5)
    c.add_path([1, 2, 3])
    assert c.is_chain and c.chain_tokens() == [1, 2, 3]


def test_tree_draft_mask_and_depths():
    """The additive bias lets a node see exactly its root path (so
    sibling branches never attend to each other) and pad rows stay
    plain-causal; ``depths`` ropes every node at its DEPTH — the
    property that makes committed rows bit-identical to
    linearly-written ones."""
    t = TreeDraft(5)
    t.add_path([1, 2, 3])
    t.add_path([1, 4])
    t.add_path([7])
    m = t.mask(8)
    assert m.shape == (8, 8) and m.dtype == np.float32
    # Node 3 (path 5→1→2→3) sees its ancestors, not the [1,4]/[7] limbs.
    assert all(m[3, j] == 0.0 for j in (0, 1, 2, 3))
    assert m[3, 4] < 0 and m[3, 5] < 0
    # Node 4 (path 5→1→4) skips the sibling subtree it forked from.
    assert m[4, 0] == 0.0 and m[4, 1] == 0.0 and m[4, 4] == 0.0
    assert m[4, 2] < 0 and m[4, 3] < 0
    # Pad rows (i ≥ n) are causal so the kernel never sees a
    # fully-masked row.
    assert (m[6, :7] == 0.0).all() and m[6, 7] < 0
    np.testing.assert_array_equal(t.depths(8), [0, 1, 2, 3, 2, 1, 6, 7])


# -- verify walks ----------------------------------------------------------


def test_verify_tree_greedy_walk():
    """The greedy walk draws the target token FIRST (argmax) and only
    then looks for a matching drafted child — acceptance is a
    consequence of the target's choice, never the other way around."""
    t = TreeDraft(5)
    t.add_path([1, 2, 3])
    t.add_path([1, 4])
    t.add_path([7])
    logits = np.full((6, 10), -5.0, np.float32)
    logits[0, 1] = 5.0   # root: target picks 1 → descend node 1
    logits[1, 4] = 5.0   # node 1: target picks 4 → descend node 4
    logits[4, 9] = 5.0   # node 4: target picks 9 → no child, stop
    path, emitted = verify_tree_greedy(logits, t)
    assert path == [1, 4] and emitted == [1, 4, 9]
    # Immediate miss: zero nodes accepted, one token still emitted
    # (the verify forward is never wasted).
    logits[0, 1] = -5.0
    logits[0, 8] = 5.0
    path, emitted = verify_tree_greedy(logits, t)
    assert path == [] and emitted == [8]


def test_verify_tree_sampled_matches_target_distribution():
    """Distribution preservation for the sampled walk: each emitted
    token is drawn from ``target_probs`` of ITS node's logits before
    any accept/descend decision, so the emitted stream's law is
    independent of the draft tree's shape — empirical first-token
    frequencies converge to ``target_probs(logits[0])`` and are
    bit-identical between two different trees under the same keys."""
    rng = np.random.default_rng(7)
    t, p, k = 0.8, 0.9, 5
    wide = TreeDraft(5)
    wide.add_path([1, 2])
    wide.add_path([3, 4])
    wide.add_path([6])
    narrow = TreeDraft(5)
    narrow.add_path([2, 2])
    logits = rng.normal(size=(len(wide), 8)).astype(np.float32) * 2.0
    probs = np.asarray(
        sampling.target_probs(jnp.asarray(logits[0]), t, p, k), np.float64
    )
    n = 1200
    keys = jax.random.split(jax.random.key(11), n)
    first, first_narrow = [], []
    for kk in keys:
        it = iter(jax.random.split(kk, 4))
        _, em = verify_tree_sampled(logits, wide, lambda: next(it), t, p, k)
        first.append(em[0])
        it = iter(jax.random.split(kk, 4))
        _, em = verify_tree_sampled(
            logits[: len(narrow)], narrow, lambda: next(it), t, p, k
        )
        first_narrow.append(em[0])
    emp = np.bincount(first, minlength=8) / n
    assert set(np.nonzero(emp)[0]) <= set(np.nonzero(probs > 0)[0])
    assert np.abs(emp - probs).sum() / 2 < 0.05  # total variation
    # Same keys → same first draw, whatever was drafted.
    assert first == first_narrow


def test_spec_state_record_tree_width_controller():
    """The accept ledger drives BOTH axes: full-depth accepts widen and
    deepen, partial accepts re-aim the depth, zero-accept rounds narrow
    the tree toward the linear chain."""
    st = SpecState(8, w_max=4)
    assert st.width == 4 and st.k == 8  # optimistic start, like k
    st.record_tree(nodes=6, depth=4, accepted=1)    # partial
    assert st.k == 2 and st.width == 4              # re-aim k, keep w
    st.record_tree(nodes=6, depth=3, accepted=3)    # full depth
    assert st.k == 4 and st.width == 4              # k grows, w capped
    st.width = 2
    st.record_tree(nodes=6, depth=3, accepted=3)
    assert st.k == 6 and st.width == 3              # widen on full depth
    st.record_tree(nodes=6, depth=4, accepted=0)    # dry round
    assert st.k == st.k_min and st.width == 2
    for _ in range(5):
        st.record_tree(nodes=6, depth=4, accepted=0)
    assert st.width == 1 and st.k == st.k_min       # floors hold
    assert st.proposed == 54 and st.accepted == 7   # ledger accumulates


# -- the commit primitive --------------------------------------------------


def test_move_kv_rows_permutes_rows_and_refuses_quantized(own_model):
    """``move_kv_rows`` relocates exactly the named token rows (both K
    and V, every layer, across page boundaries), leaves every other
    slot and row untouched, and refuses quantized pools (whose per-page
    scales would make a row hop a requantization event)."""
    cache, _pool = init_paged_cache(
        own_model.cfg, 2, own_model.ctx, own_model.axis, max_length=64,
        page_size=16,
    )
    shape = cache.k_pages.shape
    rng = np.random.default_rng(3)
    kp = rng.normal(size=shape).astype(np.float32)
    vp = rng.normal(size=shape).astype(np.float32)
    cache = dataclasses.replace(
        cache,
        k_pages=jnp.asarray(kp, cache.k_pages.dtype),
        v_pages=jnp.asarray(vp, cache.v_pages.dtype),
    )
    table = np.asarray(cache.page_table)
    # A tree accept: survivors at storage rows 17,20,21 compact to
    # 9,10,11 — crossing the page-1/page-0 boundary of slot 0.
    src, dst = [17, 20, 21], [9, 10, 11]
    before_k = np.asarray(cache.k_pages, np.float32).copy()
    before_v = np.asarray(cache.v_pages, np.float32).copy()

    def rows(arr, slot, positions):
        ps = shape[3]
        return np.stack([
            arr[:, table[slot, p // ps], :, p % ps, :] for p in positions
        ])

    exp_k, exp_v = rows(before_k, 0, src), rows(before_v, 0, src)
    cache = move_kv_rows(cache, 0, src, dst)
    after_k = np.asarray(cache.k_pages, np.float32)
    after_v = np.asarray(cache.v_pages, np.float32)
    np.testing.assert_array_equal(rows(after_k, 0, dst), exp_k)
    np.testing.assert_array_equal(rows(after_v, 0, dst), exp_v)
    # Slot 1 and slot 0's non-dst rows are untouched.
    np.testing.assert_array_equal(rows(after_k, 1, dst), rows(before_k, 1, dst))
    untouched = [p for p in range(32) if p not in dst]
    np.testing.assert_array_equal(
        rows(after_k, 0, untouched), rows(before_k, 0, untouched)
    )
    np.testing.assert_array_equal(
        rows(after_v, 0, untouched), rows(before_v, 0, untouched)
    )
    # No-op move lists return the cache unchanged (no traced program).
    same = move_kv_rows(cache, 0, [9, 10], [9, 10])
    assert same is cache
    with pytest.raises(ValueError, match="mismatch"):
        move_kv_rows(cache, 0, [1, 2], [1])
    qcache, _qp = init_paged_cache(
        own_model.cfg, 2, own_model.ctx, own_model.axis,
        max_length=64, page_size=16, kv_dtype="int8",
    )
    with pytest.raises(ValueError, match="quantized"):
        move_kv_rows(qcache, 0, [17], [9])


# -- continuation proposers ------------------------------------------------


def test_propose_continuations_radix_walk_and_tiers():
    """The radix proposer walks the FULL history exactly (any mismatch
    → no radix paths — stale branches must not draft), fans out
    recency-first at the frontier, and scans tier chains as a flat
    prefix population; the whole read leaves pins/stats/LRU untouched."""
    pool = PagePool(32)
    pc = PrefixCache(pool, 4)
    pc.insert_chain(pc.root, [1, 2, 3, 4, 5, 6, 7, 8], pool.allocate(2))
    pc.insert_chain(
        pc.root, [1, 2, 3, 4, 9, 9, 9, 9, 9, 9], pool.allocate(3)
    )
    free0 = len(pool.free)
    paths = pc.propose_continuations([1, 2, 3, 4], width=3, depth=4)
    assert sorted(paths) == [[5, 6, 7, 8], [9, 9, 9, 9]]
    # History ending mid-chunk: the chunk tail is the forced stem.
    paths = pc.propose_continuations([1, 2], width=3, depth=4)
    assert sorted(paths) == [[3, 4, 5, 6], [3, 4, 9, 9]]
    # width caps the fan-out; depth truncates each path.
    assert pc.propose_continuations([1, 2, 3, 4], width=1, depth=2) in (
        [[5, 6]], [[9, 9]]
    )
    # Unknown or diverging history proposes nothing.
    assert pc.propose_continuations([42], width=3, depth=4) == []
    assert pc.propose_continuations([1, 2, 7], width=3, depth=4) == []
    # Tier chains: flat scan of evicted-but-resident prefixes.
    paths = pc.propose_continuations(
        [7, 7], width=2, depth=3,
        tier_chains=[[7, 7, 1, 2, 3, 4], [8, 8], [7, 7]],
    )
    assert paths == [[1, 2, 3]]  # strict-extension matches only
    # Pure read: no pages moved, no pins taken.
    assert len(pool.free) == free0
    assert all(n.refcount == 0 for n in pc.walk())


def test_tier_resident_chains_memoized():
    """``PageStore.resident_chains`` decodes only the header chain of
    RAM-resident prefix entries, and its memo invalidates on every
    membership mutation (insert, delete, clear)."""
    from triton_distributed_tpu.models import kv_tier

    tier = kv_tier.PageStore(capacity_bytes=1 << 20)
    assert tier.resident_chains() == []
    z = np.zeros((1, 1, 4, 8), np.float32)
    for chain in ([1, 2, 3, 4], [5, 6, 7, 8]):
        assert tier.put(
            kv_tier.PREFIX_KIND, kv_tier.chain_digest(chain),
            kv_tier.prefix_payload(chain, 4, None, z, z),
        )
    got = tier.resident_chains()
    assert sorted(got) == [[1, 2, 3, 4], [5, 6, 7, 8]]
    assert tier.resident_chains() is got  # memo hit, no rescan
    tier.delete(kv_tier.PREFIX_KIND, kv_tier.chain_digest([1, 2, 3, 4]))
    assert tier.resident_chains() == [[5, 6, 7, 8]]
    tier.clear()
    assert tier.resident_chains() == []
    # Snapshot-kind entries never surface as draft chains.
    tier.put(kv_tier.SNAP_KIND, "s1", {"chain": [9, 9]})
    assert tier.resident_chains() == []


# -- engine integration: greedy bit-identity -------------------------------


def test_continuous_tree_greedy_bit_identical(own_model):
    """The headline exactness proof for trees: a warmed radix makes the
    drafter propose real multi-branch trees, and the emitted stream
    stays bit-identical to plain greedy decode — with the rollback
    ledger balanced and every page released."""
    from triton_distributed_tpu.models.continuous import ContinuousEngine

    p1 = np.asarray(MOTIF * 5 + [3, 5], np.int32)
    p2 = np.asarray(MOTIF * 5 + [9], np.int32)
    g = 16
    golds = [golden(own_model, list(p), g) for p in (p1, p2)]
    eng = ContinuousEngine(
        own_model, max_batch=2, page_size=16, max_length=128,
        speculative=4, spec_width=4, prefix_cache=True,
    )
    assert eng._spec_tree
    free0 = len(eng.pool.free)
    outs = eng.run([(p1, g)])          # warm pass populates the radix
    np.testing.assert_array_equal(outs[0], np.asarray(golds[0]))
    outs = eng.run([(p1, g), (p2, g)])  # warm radix → real trees
    for got, gold in zip(outs, golds):
        np.testing.assert_array_equal(got, np.asarray(gold))
    st = eng.last_stats
    assert st["spec_tree_rounds"] > 0
    assert st["spec_tree_nodes"] >= st["spec_tree_rounds"]
    assert st["spec_tree_depth"] >= st["spec_tree_rounds"]
    assert st["spec_rollback_tokens"] == (
        st["spec_draft_tokens"] - st["spec_accepted_tokens"]
    )
    assert st["target_steps"] == st["decode_steps"] + st["spec_verify_steps"]
    assert eng.audit() == []
    # Pages not held by the radix tree are all back in the pool.
    assert len(eng.pool.free) + eng.prefix.node_count == free0
    assert all(n.refcount == 0 for n in eng.prefix.walk())


def test_engine_paged_tree_greedy_bit_identical(own_model):
    """The fixed-batch paged Engine grows the same tree arm: its
    persistent radix (prefix_cache=True) feeds the drafter on repeat
    serves, greedy output stays bit-identical, and the ledger closes."""
    # An APERIODIC motif: the n-gram fallback and the radix walk then
    # disagree about the continuation, so the draft really branches
    # (a 4-periodic prompt collapses every proposal into one chain).
    motif = np.random.default_rng(0).integers(1, 50, size=7).tolist()
    p = motif * 4 + [3, 5]
    g = 24
    gold = golden(own_model, p, g)
    eng = Engine(
        own_model, temperature=0.0, paged=True, page_size=16,
        speculative=4, spec_width=4, prefix_cache=True,
    )
    assert eng._spec_tree
    for _ in range(2):  # serve 2 re-walks the radix serve 1 populated
        out = eng.serve(np.asarray([p], np.int32), gen_len=g)[0, len(p):]
        np.testing.assert_array_equal(out, np.asarray(gold))
    st = eng.last_stats
    assert st["spec_tree_rounds"] > 0
    assert st["spec_rollback_tokens"] == (
        st["spec_draft_tokens"] - st["spec_accepted_tokens"]
    )


def test_tree_branch_accept_row_moves_bit_identical(own_model, monkeypatch):
    """Force the target down a NON-first branch every round: the decoy
    branch occupies the early storage rows, so every accept must
    relocate KV rows (``spec_tree_branch_accepts`` counts the moves) —
    and the output must STILL be bit-identical to plain greedy decode,
    proving moved rows equal linearly-written rows."""
    p = MOTIF * 5 + [3, 5]
    g = 24
    gold = [int(t) for t in golden(own_model, p, g)]
    full = list(p) + gold
    vocab = own_model.cfg.vocab_size

    def decoy_first(self, tokens, *, width, depth, tier_chains=None):
        pos = len(tokens)
        true = full[pos:pos + depth]
        if len(true) < 2:
            return []
        wrong = max(1, (true[0] + 1) % vocab)
        return [[wrong] * len(true), true]

    monkeypatch.setattr(
        PrefixCache, "propose_continuations", decoy_first
    )
    eng = Engine(
        own_model, temperature=0.0, paged=True, page_size=16,
        speculative=4, spec_width=4, prefix_cache=True,
    )
    out = eng.serve(np.asarray([p], np.int32), gen_len=g)[0, len(p):]
    np.testing.assert_array_equal(out, np.asarray(gold))
    st = eng.last_stats
    assert st["spec_tree_rounds"] > 0
    assert st["spec_tree_branch_accepts"] > 0  # rows actually moved
    assert st["spec_accepted_tokens"] > 0


# -- sampled replay + migration -------------------------------------------


def test_tree_sampled_replay_and_migration_bit_exact(own_model):
    """Seeded-sampled decode with trees ON is reproducible and survives
    a mid-flight slot migration bit-exactly: the sampled walk draws one
    key per EMITTED token (draft-shape independent), and the snapshot
    carries the PRNG counter plus the width controller's state."""
    from triton_distributed_tpu.models.continuous import (
        ContinuousEngine,
        Request,
    )

    kw = dict(
        max_batch=2, page_size=16, max_length=128, prefix_cache=True,
        speculative=4, spec_width=4, temperature=0.8, seed=11,
    )
    prompts = [np.asarray(MOTIF * 4, np.int32),
               np.asarray(MOTIF * 3 + [7, 7], np.int32)]
    gens = [14, 12]
    work = list(zip(prompts, gens))

    def fresh():
        eng = ContinuousEngine(own_model, **kw)
        assert eng._spec_tree
        return eng

    gold_eng = fresh()
    gold = [r.tokens.tolist() for r in gold_eng.run(work, results=True)]
    assert gold_eng.last_stats["spec_tree_rounds"] >= 0
    # Same seeds, fresh engine → bit-identical replay.
    assert [r.tokens.tolist()
            for r in fresh().run(work, results=True)] == gold
    # Export mid-flight, import into a cold engine: still bit-exact.
    A = fresh()
    A.request_handoff(after_rounds=3)
    res1 = A.run(work, results=True)
    assert all(r.status == "migrated" for r in res1)
    assert A.audit() == []
    B = fresh()
    resume = [Request(p, g, snapshot=r.snapshot)
              for (p, g), r in zip(work, res1)]
    res2 = B.run(resume, results=True)
    assert [r.tokens.tolist() for r in res2] == gold
    assert B.audit() == []


# -- fault seams on the tree path -----------------------------------------


def _tree_engine(model, **kw):
    from triton_distributed_tpu.models.continuous import ContinuousEngine

    kw.setdefault("max_batch", 1)
    kw.setdefault("page_size", 16)
    kw.setdefault("max_length", 128)
    kw.setdefault("speculative", 4)
    kw.setdefault("spec_width", 4)
    kw.setdefault("prefix_cache", True)
    return model, ContinuousEngine(model, **kw)


def test_tree_verify_fault_isolated(own_model):
    """A tree verify that raises fails only its own request; the engine
    serves the next request normally and every audit stays clean (the
    failed slot's un-committed tree rows are reclaimed wholesale)."""
    from triton_distributed_tpu.runtime.faults import FaultPlan

    model, eng = _tree_engine(own_model)
    rep = np.asarray(MOTIF * 4, np.int32)
    gold = golden(model, list(rep), 8)
    eng.run([(rep, 8)])  # warm the radix so verifies run on trees
    with FaultPlan().verify_exc(at=1):
        results = eng.run([(rep, 8), (rep, 8)], results=True)
    assert results[0].status == "failed"
    assert results[1].ok
    np.testing.assert_array_equal(results[1].tokens, gold)
    assert eng.audit() == []
    assert all(n.refcount == 0 for n in eng.prefix.walk())


def test_tree_verify_nan_logits_guarded(own_model):
    """Non-finite logits in a tree-verify chunk fail that request with
    a structured ``nan_logits`` — never argmax'd into accepted tokens,
    and never a poisoned pool."""
    from triton_distributed_tpu.runtime.faults import FaultPlan

    model, eng = _tree_engine(own_model)
    rep = np.asarray(MOTIF * 4, np.int32)
    gold = golden(model, list(rep), 8)
    eng.run([(rep, 8)])

    def nanify(value, _ctx):
        value = np.array(value, np.float32)
        value[0] = np.nan
        return value

    with FaultPlan().on("spec.logits", at=1, mutate=nanify):
        results = eng.run([(rep, 8), (rep, 8)], results=True)
    assert results[0].status == "nan_logits"
    assert results[1].ok
    np.testing.assert_array_equal(results[1].tokens, gold)
    assert eng.last_stats["nonfinite_logits"] == 1
    assert eng.audit() == []


# -- observability ---------------------------------------------------------


def test_tree_metrics_exposed_on_the_wire(own_model):
    """Acceptance (ISSUE 16): the tree counters, the ``tdt_spec_*``
    counter aliases for the draft/rollback ledger, and the accept-rate
    gauge all surface through ``{"cmd": "metrics"}``."""
    from triton_distributed_tpu.serving.server import ModelServer, request

    _model, eng = _tree_engine(own_model, max_batch=2)
    server = ModelServer(eng).start()
    try:
        prompt = (MOTIF * 4)
        for _ in range(2):  # second pass drafts from the warm radix
            r = request(server.host, server.port,
                        {"requests": [prompt], "gen_lens": [8]})
            assert r["results"][0]["status"] == "ok"
        m = request(server.host, server.port, {"cmd": "metrics"})
        snap = m["metrics"]
        for name in ("tdt_spec_tree_rounds_total",
                     "tdt_spec_tree_nodes_total",
                     "tdt_spec_tree_depth_total",
                     "tdt_spec_tree_branch_accepts_total",
                     "tdt_spec_draft_tokens_total",
                     "tdt_spec_rollback_tokens_total"):
            assert name in m["prometheus"], name
            assert snap[name]["type"] == "counter", name
        st = eng.last_stats
        series = snap["tdt_spec_draft_tokens_total"]["series"]
        assert series and series[0]["value"] >= st["spec_draft_tokens"]
        gauge = snap["tdt_spec_accept_rate"]
        assert gauge["type"] == "gauge"
        rate = gauge["series"][0]["value"]
        assert 0.0 <= rate <= 1.0
        # The trace ring carries the tree-verify spans.
        ev = request(server.host, server.port, {"cmd": "events", "since": 0})
        assert any(e["kind"] == "spec_verify" for e in ev["events"])
    finally:
        request(server.host, server.port, {"cmd": "shutdown"})
        server.shutdown()


# -- loadgen: the agentic continuation class ------------------------------


def test_loadgen_agentic_class_and_trace_compat():
    """The seeded ``"agentic"`` class reshapes its requests into
    prefix+motif×repeats continuations (the shape tree drafting feeds
    on) while every OTHER row — and every spec without the class — is
    bit-identical to the pre-agentic generator."""
    from perf.loadgen import LoadSpec, generate_trace

    base = LoadSpec(n_requests=24, seed=3)
    mixed = dataclasses.replace(
        base, class_mix=(("interactive", 2.0), ("agentic", 1.0)),
        agentic_motif=5, agentic_repeats=3,
    )
    plain, agentic = generate_trace(base), generate_trace(mixed)
    # Mix-less spec: trace unchanged by the feature landing at all.
    assert plain == generate_trace(LoadSpec(n_requests=24, seed=3))
    ag_rows = [r for r in agentic if r["slo_class"] == "agentic"]
    assert ag_rows, "mix produced no agentic rows at this seed"
    prefix_len = base.prefix_len
    motifs = {}
    for row, old in zip(agentic, plain):
        assert row["t"] == old["t"] and row["prefix_id"] == old["prefix_id"]
        if row["slo_class"] != "agentic":
            # Non-agentic rows keep the exact pre-mix prompt.
            assert row["prompt"] == old["prompt"]
            continue
        prefix = row["prompt"][:prefix_len]
        assert prefix == old["prompt"][:prefix_len]
        tail = row["prompt"][prefix_len:]
        assert len(tail) == 5 * 3
        assert tail == tail[:5] * 3  # the motif repeats verbatim
        motifs.setdefault(row["prefix_id"], tail[:5])
        # One motif PER PREFIX: shared across requests → radix reuse.
        assert motifs[row["prefix_id"]] == tail[:5]
    # A mix WITHOUT the agentic class leaves prompts untouched too.
    other = generate_trace(dataclasses.replace(
        base, class_mix=(("interactive", 1.0), ("batch", 1.0))
    ))
    assert [r["prompt"] for r in other] == [r["prompt"] for r in plain]
