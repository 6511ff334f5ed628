"""Prefix cache (radix KV reuse) + chunked prefill tests.

Three layers of evidence:

- host-level radix-tree semantics (match/COW/dedupe/LRU/refcounts) and a
  randomized admit/cancel/finish stress asserting the pool invariant —
  no model, so these run in milliseconds;
- engine-level reuse proofs on the tiny model: suffix-only prefill
  (counted via ``last_stats``), bit-identical warm-vs-cold outputs,
  COW partial-tail matches, chunked-prefill interleaving, and
  eviction-pressure equivalence against dense goldens;
- the serving server's continuous-batching route.
"""

import numpy as np
import pytest

from triton_distributed_tpu.models.paged_kv_cache import PagePool
from triton_distributed_tpu.models.prefix_cache import PrefixCache


def make_pool(n):
    pool = PagePool(n + 1)
    pool.free = [p for p in pool.free if p != 0]  # page 0 = trash
    return pool, len(pool.free)


def pool_pages(pool, cache, in_flight_private=()):
    """Every page exactly once across free list / tree / in-flight."""
    owned = list(pool.free)
    owned += [n.page for n in cache.walk()]
    for pages in in_flight_private:
        owned += list(pages)
    return owned


class TestRadixTree:
    PS = 4

    def test_match_insert_dedupe_refcount(self):
        pool, cap = make_pool(16)
        pc = PrefixCache(pool, self.PS)
        toks = list(range(100, 110))  # 2.5 pages
        pages = pool.allocate(3)
        pc.insert_chain(pc.root, toks, pages)
        assert pc.node_count == 3
        assert len(pool.free) + pc.node_count == cap

        # Full-page prefix shares; the partial tail COW-matches.
        m = pc.match(toks + [1, 2, 3])
        assert [n.page for n in m.nodes] == pages[:2]
        assert m.matched_len == 10 and m.cow_len == 2
        assert all(n.refcount == 1 for n in m.nodes)
        assert m.cow_node.refcount == 1
        pc.release_match(m)
        assert all(n.refcount == 0 for n in pc.walk())

        # Matching is capped at len-1: at least one token must prefill.
        m2 = pc.match(toks[: self.PS])
        assert m2.matched_len == self.PS - 1 and m2.cow_len == self.PS - 1
        pc.release_match(m2)

        # Re-inserting an identical chain releases the duplicate pages.
        dup = pool.allocate(3)
        pc.insert_chain(pc.root, toks, dup)
        assert pc.node_count == 3  # nothing new
        assert len(pool.free) + pc.node_count == cap
        assert pc.stats["deduped_pages"] >= 2

        uniq = pool_pages(pool, pc)
        assert len(uniq) == len(set(uniq)) == cap

    def test_partial_tail_upgrade(self):
        pool, cap = make_pool(16)
        pc = PrefixCache(pool, self.PS)
        pc.insert_chain(pc.root, [1, 2, 3, 4, 5, 6], pool.allocate(2))
        # Longer chain over the same prefix upgrades the partial tail
        # node in place (its page is released, ours adopted).
        pc.insert_chain(pc.root, [1, 2, 3, 4, 5, 6, 7, 8, 9],
                        pool.allocate(3))
        m = pc.match([1, 2, 3, 4, 5, 6, 7, 8, 9, 0])
        assert m.matched_len == 9  # 2 full pages + 1-token cow
        pc.release_match(m)
        assert len(pool.free) + pc.node_count == cap

    def test_lru_eviction_order_and_pinning(self):
        pool, cap = make_pool(8)
        pc = PrefixCache(pool, self.PS)
        a = [1] * self.PS * 2
        b = [2] * self.PS * 2
        pc.insert_chain(pc.root, a, pool.allocate(2))
        pc.insert_chain(pc.root, b, pool.allocate(2))
        # Touch chain a — b becomes LRU.
        pc.release_match(pc.match(a + [9]))
        assert len(pool.free) == cap - 4
        got = pc.allocate(cap - 4 + 1)  # forces one eviction
        assert got is not None and pc.stats["evicted_pages"] >= 1
        # b's tail leaf went first.
        assert any(n.chunk[0] == 1 for n in pc.walk())
        remaining = [n for n in pc.walk() if n.chunk[0] == 2]
        assert len(remaining) < 2
        pool.release(got)

        # Pinned chains never evict: match+hold a, demand everything.
        m = pc.match(a + [9])
        before = len(pool.free)
        assert pc.allocate(before + pc.node_count) is None  # can't cover
        assert all(n.refcount == 0 or n.chunk[0] == 1 for n in pc.walk())
        pc.release_match(m)

    def test_stress_admit_cancel_finish_invariant(self):
        """Randomized interleavings must never leak, double-free, or
        alias a page: free + tree + in-flight private == capacity after
        every operation."""
        rng = np.random.default_rng(0)
        pool, cap = make_pool(24)
        pc = PrefixCache(pool, self.PS)
        bases = [list(rng.integers(1, 50, size=12)) for _ in range(3)]
        in_flight = []  # (match, private_pages, tokens, gen)

        def check():
            owned = pool_pages(
                pool, pc, [p for _, p, _, _ in in_flight]
            )
            assert len(owned) == cap, (len(owned), cap)
            assert len(set(owned)) == cap, "page aliased/double-freed"

        for step in range(400):
            op = rng.random()
            if op < 0.5 and len(in_flight) < 4:  # admit
                base = bases[rng.integers(len(bases))]
                tokens = base[: rng.integers(2, len(base) + 1)] + list(
                    rng.integers(1, 50, size=rng.integers(0, 6))
                )
                gen = int(rng.integers(1, 6))
                need = -(-(len(tokens) + gen) // self.PS)
                m = pc.match(tokens)
                priv = pc.allocate(need - len(m.nodes))
                if priv is None:
                    pc.release_match(m)
                else:
                    pc.finish_cow(m)  # cow dst = priv[0], "copied"
                    in_flight.append((m, priv, tokens, gen))
            elif in_flight:
                idx = int(rng.integers(len(in_flight)))
                m, priv, tokens, gen = in_flight.pop(idx)
                if op < 0.75:  # finish: donate pages to the tree
                    cached = len(tokens) + gen - 1
                    toks = tokens + list(
                        rng.integers(1, 50, size=gen - 1)
                    )
                    parent = m.nodes[-1] if m.nodes else pc.root
                    pc.insert_chain(
                        parent, toks[len(m.nodes) * self.PS : cached], priv
                    )
                else:  # cancel: straight back to the pool
                    pool.release(priv)
                for node in m.nodes:
                    pc.release_node(node)
            check()
        # Drain: everything lands in tree or free list, all unpinned.
        for m, priv, _, _ in in_flight:
            pool.release(priv)
            for node in m.nodes:
                pc.release_node(node)
        in_flight = []
        check()
        assert all(n.refcount == 0 for n in pc.walk())
        # Full eviction returns every page.
        pc.evict_until(cap)
        assert len(pool.free) == cap


class TestEnginePrefixReuse:
    def _goldens(self, model, reqs):
        from triton_distributed_tpu.models.engine import Engine

        return [
            Engine(model, temperature=0.0).serve(p[None], gen_len=g)[0, len(p):]
            for p, g in reqs
        ]

    def test_prefix_reuse_skips_recompute(self, own_model):
        """Second request sharing an N-page prefix performs suffix-only
        prefill (prefill_tokens counter) with outputs bit-identical to
        the cold-cache path."""
        from triton_distributed_tpu.models.continuous import ContinuousEngine

        shared = np.asarray(
            [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8, 9, 7, 9, 3] * 2, np.int32
        )  # 32 tokens = 2 pages at page_size=16
        pA = np.concatenate([shared, np.asarray([10, 11, 12, 13], np.int32)])
        pB = np.concatenate([shared, np.asarray([20, 21, 22, 23], np.int32)])
        goldA, goldB = self._goldens(own_model, [(pA, 4), (pB, 4)])

        eng = ContinuousEngine(
            own_model, max_batch=2, page_size=16, max_length=64,
            prefix_cache=True,
        )
        outA = eng.run([(pA, 4)])
        assert eng.last_stats["prefill_tokens"] == len(pA)  # cold: all
        assert eng.last_stats["prefix_hit_tokens"] == 0
        outB = eng.run([(pB, 4)])
        st = eng.last_stats
        assert st["prefix_hit_tokens"] == 32      # both shared pages
        assert st["prefill_tokens"] == 4          # suffix only
        np.testing.assert_array_equal(outA[0], goldA)
        np.testing.assert_array_equal(outB[0], goldB)

        # Bit-identical to the cold-cache path: a fresh engine serving B
        # from scratch produces the same tokens.
        cold = ContinuousEngine(
            own_model, max_batch=2, page_size=16, max_length=64,
            prefix_cache=True,
        )
        np.testing.assert_array_equal(cold.run([(pB, 4)])[0], outB[0])

        # Leak-free: every page is in the tree or the free list.
        assert len(eng.pool.free) + eng.prefix.node_count == eng._capacity

    def test_cow_partial_tail_match(self, own_model):
        """A prefix ending inside a cached page is reused via COW: the
        page is cloned, matched positions count, outputs stay golden."""
        from triton_distributed_tpu.models.continuous import ContinuousEngine

        rng = np.random.default_rng(3)
        head = rng.integers(1, 200, size=18).astype(np.int32)  # 1.125 pages
        pA = np.concatenate([head, np.asarray([10, 11], np.int32)])
        pB = np.concatenate([head, np.asarray([20, 21], np.int32)])
        (goldB,) = self._goldens(own_model, [(pB, 4)])

        eng = ContinuousEngine(
            own_model, max_batch=2, page_size=16, max_length=64,
            prefix_cache=True,
        )
        eng.run([(pA, 4)])
        outB = eng.run([(pB, 4)])
        st = eng.last_stats
        assert st["prefix_hit_tokens"] == 18  # 1 full page + 2-token COW
        assert st["pages_cow_copied"] == 1
        np.testing.assert_array_equal(outB[0], goldB)

    def test_chunked_prefill_interleaves_decodes(self, own_model):
        """A long cold prompt admitted in chunks never blocks the
        running request's decode; outputs match dense goldens."""
        from triton_distributed_tpu.models.continuous import ContinuousEngine

        rng = np.random.default_rng(7)
        long_p = rng.integers(1, 200, size=40).astype(np.int32)
        short_p = np.asarray([5, 9, 2, 4], np.int32)
        goldS, goldL = self._goldens(own_model, [(short_p, 8), (long_p, 3)])

        eng = ContinuousEngine(
            own_model, max_batch=2, page_size=16, max_length=64,
            prefix_cache=True, prefill_chunk=16,
        )
        outs = eng.run([(short_p, 8), (long_p, 3)])
        np.testing.assert_array_equal(outs[0], goldS)
        np.testing.assert_array_equal(outs[1], goldL)
        # 40-token prompt at chunk 16 → 3 chunks (+1 for the short one).
        assert eng.last_stats["prefill_chunks"] >= 4

    def test_eviction_pressure_equivalence(self, own_model):
        """Pool sized to force LRU eviction: repeated shared-prefix
        serving never double-frees, leaks, or serves a stale page —
        outputs stay equal to the dense goldens every round."""
        from triton_distributed_tpu.models.continuous import ContinuousEngine

        rng = np.random.default_rng(11)
        prefixes = [
            rng.integers(1, 200, size=16).astype(np.int32) for _ in range(3)
        ]
        reqs = []
        for i, pre in enumerate(prefixes):
            tail = rng.integers(1, 200, size=4 + i).astype(np.int32)
            reqs.append((np.concatenate([pre, tail]), 3))
        golds = self._goldens(own_model, reqs)

        # 2 slots × 3 pages/req worst case, but only 7 pages: admission
        # must evict cached chains to serve new prefixes.
        eng = ContinuousEngine(
            own_model, max_batch=2, page_size=16, max_length=64,
            prefix_cache=True, num_pages=7,
        )
        for round_ in range(3):
            outs = eng.run(reqs)
            for got, gold in zip(outs, golds):
                np.testing.assert_array_equal(got, gold)
            assert (
                len(eng.pool.free) + eng.prefix.node_count == eng._capacity
            )
            owned = pool_pages(eng.pool, eng.prefix)
            assert len(owned) == len(set(owned))
        assert eng.prefix.stats["evicted_pages"] > 0

    def test_engine_paged_prefix_across_serves(self, own_model):
        """Engine(paged, prefix_cache): the tree persists across serve()
        calls — the second call prefills only the uncached suffix and
        returns the same tokens as a cold engine."""
        from triton_distributed_tpu.models.engine import Engine

        shared = np.asarray(
            [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8, 9, 7, 9, 3], np.int32
        )
        pA = np.concatenate([shared, np.asarray([10, 11, 12, 13], np.int32)])
        pB = np.concatenate([shared, np.asarray([20, 21, 22, 23], np.int32)])
        gold = Engine(own_model, temperature=0.0).serve(pB[None], gen_len=4)

        eng = Engine(
            own_model, temperature=0.0, paged=True, page_size=16,
            prefix_cache=True,
        )
        eng.serve(pA[None], gen_len=4, max_length=64)
        assert eng.last_stats["prefix_hit_tokens"] == 0
        out = eng.serve(pB[None], gen_len=4, max_length=64)
        np.testing.assert_array_equal(out, gold)
        assert eng.last_stats["prefix_hit_tokens"] == 16
        assert eng.last_stats["prefill_tokens"] == 4

    def test_engine_paged_prefix_boundary_capacity(self, own_model):
        """true_len + gen_len - 1 == max_length (the last sampled token
        is never appended) must serve: page reservation counts written
        positions, not prompt+gen."""
        from triton_distributed_tpu.models.engine import Engine

        prompt = np.arange(1, 62, dtype=np.int32)[None]  # 61 tokens
        gold = Engine(own_model, temperature=0.0).serve(
            prompt, gen_len=4, max_length=64
        )
        eng = Engine(
            own_model, temperature=0.0, paged=True, page_size=16,
            prefix_cache=True, prefill_chunk=61,  # unrounded width too
        )
        out = eng.serve(prompt, gen_len=4, max_length=64)  # 61+4-1 = 64
        np.testing.assert_array_equal(out, gold)

    def test_engine_cow_pin_cannot_starve_pool(self, own_model):
        """A COW pin covers none of the row's page budget; when it alone
        starves allocation the engine degrades (drop COW, then cold)
        instead of crashing — outputs stay golden."""
        from triton_distributed_tpu.models.engine import Engine

        p1 = np.arange(1, 25, dtype=np.int32)[None]  # 24 tokens, pps=2
        eng = Engine(
            own_model, temperature=0.0, paged=True, page_size=16,
            prefix_cache=True,
        )
        eng.serve(p1, gen_len=4, max_length=32)
        # Shares 8 tokens with the cached full page → COW pin; the
        # 2-page pool can't hold the pin + 2 fresh pages.
        p2 = np.concatenate(
            [p1[0][:8], 90 + np.arange(16, dtype=np.int32)]
        )[None]
        gold = Engine(own_model, temperature=0.0).serve(
            p2, gen_len=4, max_length=32
        )
        np.testing.assert_array_equal(
            eng.serve(p2, gen_len=4, max_length=32), gold
        )

    def test_engine_prefix_requires_paged(self, own_model):
        from triton_distributed_tpu.models.engine import Engine

        with pytest.raises(ValueError, match="requires paged"):
            Engine(own_model, prefix_cache=True)

    def test_randomized_engine_page_accounting(self, own_model):
        """Random admit/finish interleavings across runs (mixed lengths,
        eos early-exit) keep the pool invariant: free + tree == capacity
        with no aliased pages."""
        from triton_distributed_tpu.models.continuous import ContinuousEngine

        rng = np.random.default_rng(5)
        eng = ContinuousEngine(
            own_model, max_batch=2, page_size=16, max_length=64,
            prefix_cache=True, num_pages=9,
        )
        base = rng.integers(1, 200, size=20).astype(np.int32)
        for round_ in range(3):
            reqs = []
            for _ in range(int(rng.integers(1, 4))):
                cut = int(rng.integers(1, len(base)))
                tail = rng.integers(1, 200, size=int(rng.integers(0, 5)))
                prompt = np.concatenate([base[:cut], tail]).astype(np.int32)
                reqs.append((prompt, int(rng.integers(1, 5))))
            eng.run(reqs)
            assert (
                len(eng.pool.free) + eng.prefix.node_count == eng._capacity
            )
            owned = pool_pages(eng.pool, eng.prefix)
            assert len(owned) == len(set(owned))
            assert all(n.refcount == 0 for n in eng.prefix.walk())


def test_server_continuous_round_trip(own_model):
    """The model server routes 'requests' payloads to the continuous
    engine and reports prefix-cache stats."""
    from triton_distributed_tpu.models.continuous import ContinuousEngine
    from triton_distributed_tpu.serving import ModelServer, request

    eng = ContinuousEngine(
        own_model, max_batch=2, page_size=16, max_length=64, prefix_cache=True
    )
    prompts = [[5, 9, 2, 4], [5, 9, 2, 4, 7, 1, 3, 8]]
    gold = eng.run([(np.asarray(p, np.int32), 3) for p in prompts])

    server = ModelServer(eng).start()
    try:
        resp = request(
            server.host, server.port,
            {"requests": prompts, "gen_lens": [3, 3]},
        )
        for got, g in zip(resp["outputs"], gold):
            np.testing.assert_array_equal(np.asarray(got, np.int32), g)
        assert "prefix_hit_rate" in resp["stats"]
        stats = request(server.host, server.port, {"cmd": "stats"})["stats"]
        assert "prefill_tokens" in stats
    finally:
        server.shutdown()
