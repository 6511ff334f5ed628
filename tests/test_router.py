"""Multi-engine serving tier tests (docs/scale-out.md): the
prefix-affinity router over replicated continuous engines.

Layers of evidence:

- host-level digest semantics (``prefix_digest``/``digest_match_len``)
  with no model — milliseconds;
- router-level routing proofs on the tiny model: outputs bit-exact vs
  dense per-request goldens through the replica fleet, affinity
  landing repeats on the cached replica, shed-aware skipping,
  graceful drain;
- the chaos layer (ISSUE-6 acceptance): a replica killed through the
  ``replica.run`` fault seam has every routed request re-routed and
  finished with a clean status, surviving replicas' outputs bit-exact,
  all engine/pool audits clean — and the no-survivor case fails with
  a structured status instead of hanging or dropping.
"""

import jax
import numpy as np
import pytest

from triton_distributed_tpu.models import AutoLLM
from triton_distributed_tpu.models.engine import Engine
from triton_distributed_tpu.models.paged_kv_cache import PagePool
from triton_distributed_tpu.models.prefix_cache import (
    PrefixCache,
    digest_match_len,
)
from triton_distributed_tpu.runtime import mesh as mesh_mod


@pytest.fixture(scope="module")
def tier_model():
    """ONE tiny model (and mesh) for the whole module: engines are
    cheap but compiled programs cache per model instance, and every
    test here uses the same shapes — per-test models would recompile
    identical programs in a wall-clock-bound suite. On four devices and
    not ``own_model``'s one: two replicas' workers run kernels at once,
    and the Pallas interpreter is not re-entrant on ONE device
    ("Revisited block"); a four-device program takes them in turn."""
    ctx = mesh_mod.initialize_distributed(tp=4, devices=jax.devices()[:4])
    model = AutoLLM.from_pretrained("tiny", ctx=ctx)
    yield model
    mesh_mod.finalize_distributed()


def make_router(model, n=2, **kw):
    from triton_distributed_tpu.models.continuous import ContinuousEngine
    from triton_distributed_tpu.serving.router import Router

    engines = [
        ContinuousEngine(
            model, max_batch=2, page_size=16, max_length=64,
            prefix_cache=True,
        )
        for _ in range(n)
    ]
    return Router(engines, **kw)


def goldens(model, prompts, gens):
    eng = Engine(model, temperature=0.0)
    return [
        np.asarray(eng.serve(p[None], gen_len=g)[0, len(p):])
        for p, g in zip(prompts, gens)
    ]


PROMPTS = [
    np.asarray([5, 9, 2, 4], np.int32),
    np.asarray([7, 1, 3, 8, 6, 2, 4, 9], np.int32),
    np.asarray([11, 12, 13, 14], np.int32),
]
GENS = [4, 3, 4]


# -- host-level digest semantics (no model) -----------------------------


def test_prefix_digest_and_match_len():
    pool = PagePool(17)
    pool.free = [p for p in pool.free if p != 0]
    pc = PrefixCache(pool, 4)
    toks = list(range(100, 110))  # 2 full pages + a 2-token tail
    pc.insert_chain(pc.root, toks, pool.allocate(3))

    digest = pc.prefix_digest()
    # Exact chain: full match counts every cached token.
    assert digest_match_len(digest, toks) == 10
    # Longer prompt: only the cached prefix counts.
    assert digest_match_len(digest, toks + [1, 2, 3]) == 10
    # Divergence inside the partial tail counts the matched positions.
    assert digest_match_len(digest, toks[:9] + [999]) == 9
    # Divergence inside a full page stops without descending.
    assert digest_match_len(digest, toks[:2] + [999, 999]) == 2
    # Cold prompt / empty digest.
    assert digest_match_len(digest, [999, 998]) == 0
    assert digest_match_len([], toks) == 0
    assert digest_match_len(None, toks) == 0

    # The digest is a SNAPSHOT: evicting the tree doesn't mutate it.
    pc.flush()
    assert pc.node_count == 0
    assert pc.prefix_digest() == []
    assert digest_match_len(digest, toks) == 10


# -- routing over the tiny model ----------------------------------------


def test_router_outputs_match_goldens(tier_model):
    """Mixed requests through a 2-replica fleet: every output bit-exact
    vs the dense per-request goldens, results in submission order,
    audits clean, fleet stats aggregated cumulatively."""
    model = tier_model
    golds = goldens(model, PROMPTS, GENS)
    router = make_router(model, 2)
    try:
        results = router.run(list(zip(PROMPTS, GENS)), results=True)
        for r, gold in zip(results, golds):
            assert r.status == "ok"
            np.testing.assert_array_equal(r.tokens, gold)
        st = router.last_stats
        assert st["generated_tokens"] == sum(GENS)
        assert st["router"]["routed"] == 3
        assert st["router"]["healthy_replicas"] == 2
        assert router.audit() == []

        # Legacy (results=False) interface returns arrays in order.
        outs = router.run(list(zip(PROMPTS, GENS)))
        for got, gold in zip(outs, golds):
            np.testing.assert_array_equal(got, gold)
    finally:
        router.shutdown()


def test_router_affinity_lands_on_cached_replica(tier_model):
    """A repeated prompt routes to the replica whose radix tree cached
    it (the router-side digest mirror), not round-robin: the seeded
    replica serves every repeat and the engine-level prefix counters
    prove pages were actually reused."""
    model = tier_model
    p = np.asarray(list(range(40, 72)), np.int32)  # 2 full pages
    router = make_router(model, 2)
    try:
        router.run([(p, 2)], results=True)
        assert sum(r.runs for r in router.replicas) == 1
        seeded = next(r for r in router.replicas if r.runs == 1)
        assert seeded.match_len(p) >= 16  # mirror sees the population

        for _ in range(2):
            res = router.run([(p, 2)], results=True)
            assert res[0].status == "ok"
        st = router.last_stats["router"]
        assert st["affinity_hits"] == 2
        assert st["affinity_hit_tokens"] >= 32
        assert seeded.runs == 3  # every repeat landed on the cache
        assert seeded.totals["prefix_hit_tokens"] > 0
    finally:
        router.shutdown()


def test_router_shed_aware_skips_overloaded(tier_model):
    """A replica at its pending bound is skipped BEFORE the request
    bounces: with r0 saturated every request lands on r1; with both
    saturated the router still queues (least-loaded) instead of
    dropping."""
    model = tier_model
    router = make_router(model, 2)
    try:
        r0, r1 = router.replicas
        r0.max_pending = 0  # permanently "overloaded" for routing
        results = router.run(list(zip(PROMPTS, GENS)), results=True)
        assert all(r.status == "ok" for r in results)
        assert r0.runs == 0 and r1.served == 3
        assert router.last_stats["router"]["shed_skips"] >= 3

        r1.max_pending = 0  # everything saturated: queue, don't drop
        res = router.run([(PROMPTS[0], 2)], results=True)
        assert res[0].status == "ok"
    finally:
        router.shutdown()


def test_router_drain_replica(tier_model):
    """Graceful drain: the drained replica finishes its work, flushes
    its radix pages back to the pool, refuses new tickets, and the
    fleet keeps serving on the survivor."""
    model = tier_model
    router = make_router(model, 2)
    try:
        router.run(list(zip(PROMPTS, GENS)), results=True)
        name = router.replicas[0].name
        assert router.drain_replica(name)
        r0 = router.replica(name)
        assert r0.state == "drained"
        assert r0.engine.prefix.node_count == 0  # tree flushed
        assert len(r0.engine.pool.free) == r0.engine._capacity
        from triton_distributed_tpu.serving.replica import Ticket

        assert not r0.submit(Ticket(PROMPTS[0], 1))
        res = router.run([(PROMPTS[0], 2)], results=True)
        assert res[0].status == "ok"
        assert router.last_stats["router"]["healthy_replicas"] == 1
        assert router.audit() == []
    finally:
        router.shutdown()


# -- chaos: replica kill / hang / no survivors --------------------------


def test_router_replica_kill_reroutes_bit_exact(tier_model, fresh_telemetry):
    """ISSUE-6 acceptance: every request routed to a killed replica is
    re-routed and finishes ok; outputs (survivors AND re-routed) are
    bit-exact vs the dense goldens; the dead replica's engine audits
    clean (its run() teardown released everything)."""
    from triton_distributed_tpu.obs import events as obs_events
    from triton_distributed_tpu.runtime.faults import FaultPlan

    model = tier_model
    golds = goldens(model, PROMPTS, GENS)
    router = make_router(model, 2)
    try:
        plan = FaultPlan(seed=7).kill_replica(replica="r0")
        with plan:
            results = router.run(list(zip(PROMPTS, GENS)), results=True)
        assert plan.fired and plan.fired[0][0] == "replica.run"
        for r, gold in zip(results, golds):
            assert r.status == "ok", (r.status, r.reason)
            np.testing.assert_array_equal(r.tokens, gold)
        st = router.last_stats["router"]
        assert st["reroutes"] >= 1
        assert router.replica("r0").state == "dead"
        assert router.replica("r1").state == "healthy"
        assert router.audit() == []  # dead engine released everything
        kinds = [e.kind for e in obs_events.default_ring().tail(0)[0]]
        assert "replica_dead" in kinds and "reroute" in kinds
        assert "fault" in kinds  # the injection itself is in the ring

        # The fleet keeps serving on the survivor after the kill.
        res = router.run([(PROMPTS[0], GENS[0])], results=True)
        assert res[0].status == "ok"
        np.testing.assert_array_equal(res[0].tokens, golds[0])
    finally:
        router.shutdown()


def test_router_kill_without_survivors_fails_clean(tier_model):
    """No healthy replica left: requests fail with a structured PR 3
    status (never dropped, never hung), and the re-route ledger shows
    the attempts."""
    from triton_distributed_tpu.runtime.faults import FaultPlan

    model = tier_model
    router = make_router(model, 1)
    try:
        with FaultPlan(seed=3).kill_replica(replica="r0"):
            results = router.run([(PROMPTS[0], 2)], results=True)
        assert results[0].status == "failed"
        assert "routing failed" in results[0].reason
        assert len(results[0].tokens) == 0
        assert router.last_stats["router"]["failed_no_replica"] == 1
        assert router.audit() == []
    finally:
        router.shutdown()


def test_router_timeout_marks_replica_and_reroutes(tier_model, monkeypatch):
    """Router-observed timeout (the hang arm of the seam): a replica
    stalled past ``request_timeout_s`` is taken out of rotation and
    the ticket retries on a survivor; the late run's results latch
    harmlessly. No margin here is the machine's: the hang lasts until
    the test ends it, and the survivor is not on the clock."""
    import threading
    import types

    from triton_distributed_tpu.runtime import faults

    golds = goldens(tier_model, [PROMPTS[0]], [2])
    router = make_router(tier_model, 2)
    # The seam's stall waits for the test, not for seconds to pass.
    wake = threading.Event()
    monkeypatch.setattr(faults, "time", types.SimpleNamespace(sleep=wake.wait))
    reroute = router._reroute

    def patient_reroute(ticket, reason, source=None):
        router.request_timeout_s = 120.0  # however slow the survivor is
        reroute(ticket, reason, source=source)

    router._reroute = patient_reroute
    try:
        # Warm the decode/prefill programs (jit cache lives on the
        # model, shared by both replicas) BEFORE arming the timeout:
        # a cold compile must not read as a hung replica.
        router.run([(PROMPTS[0], 2)], results=True)
        router.request_timeout_s = 0.5
        plan = faults.FaultPlan(seed=5).hang_replica(60.0, replica="r0")
        with plan:
            results = router.run([(PROMPTS[0], 2)], results=True)
            assert [seam for seam, _, _ in plan.fired] == ["replica.run"]
            assert results[0].status == "ok"
            np.testing.assert_array_equal(results[0].tokens, golds[0])
            dead = [r for r in router.replicas if r.state == "dead"]
            assert len(dead) == 1 and "timeout" in dead[0].last_error
            assert "0.5s" in dead[0].last_error
            assert router.last_stats["router"]["reroutes"] >= 1
            # Wait out the hung worker INSIDE the plan scope: it wakes,
            # runs its batch late (results latch-ignored), and exits.
            wake.set()
            dead[0].join(timeout=30)
    finally:
        wake.set()
        router.shutdown()
    assert router.audit() == []


def test_router_results_false_raises_on_failures(tier_model):
    """The legacy interface keeps the engine contract: failures raise
    RequestFailedError with per-request statuses attached."""
    from triton_distributed_tpu.models.continuous import (
        RequestFailedError,
    )
    from triton_distributed_tpu.runtime.faults import FaultPlan

    model = tier_model
    router = make_router(model, 1)
    try:
        with FaultPlan(seed=2).kill_replica(replica="r0"):
            with pytest.raises(RequestFailedError, match="failed"):
                router.run([(PROMPTS[0], 2)])
    finally:
        router.shutdown()


@pytest.mark.slow
def test_router_mega_int8_fleet_bit_exact(tier_model):
    """PR 7 compose: a fleet of ``mode="mega"`` int8 replicas behind
    the Router serves bit-exact vs per-request unfused int8 goldens,
    with fused launches actually happening on the replicas (the fast
    path survives the serving tier's threading and re-dispatch)."""
    from triton_distributed_tpu.models.continuous import ContinuousEngine
    from triton_distributed_tpu.serving.router import Router

    model = tier_model

    def engine(mode):
        return ContinuousEngine(
            model, max_batch=2, page_size=16, max_length=64, mode=mode,
            kv_dtype="int8", prefix_cache=True,
        )

    # Disjoint prompts: no cross-request prefix reuse, so per-request
    # fresh-engine goldens hold regardless of where the router lands
    # each request.
    golds = [
        engine("xla").run([(p, g)])[0] for p, g in zip(PROMPTS, GENS)
    ]
    replicas = [engine("mega") for _ in range(2)]
    router = Router(replicas)
    try:
        results = router.run(list(zip(PROMPTS, GENS)), results=True)
        for r, gold in zip(results, golds):
            assert r.status == "ok"
            np.testing.assert_array_equal(r.tokens, gold)
        assert sum(e.stats["mega_launches"] for e in replicas) > 0
        assert router.audit() == []
    finally:
        router.shutdown()


# -- through the wire ----------------------------------------------------


def test_router_through_server(tier_model):
    """ModelServer(Router(...)): the wire protocol is unchanged, the
    stats payload carries the router ledger, drain_grace_s is
    surfaced, and the metrics verb scrapes the tdt_router_* series."""
    from triton_distributed_tpu.serving import ModelServer, request

    model = tier_model
    golds = goldens(model, PROMPTS[:2], GENS[:2])
    router = make_router(model, 2, drain_grace_s=1.5)
    server = ModelServer(router, drain_grace_s=1.5).start()
    try:
        resp = request(
            server.host, server.port,
            {"requests": [p.tolist() for p in PROMPTS[:2]],
             "gen_lens": GENS[:2]},
        )
        assert [r["status"] for r in resp["results"]] == ["ok", "ok"]
        for out, gold in zip(resp["outputs"], golds):
            np.testing.assert_array_equal(np.asarray(out, np.int32), gold)
        assert resp["stats"]["router"]["routed"] >= 2

        stats = request(server.host, server.port, {"cmd": "stats"})
        assert stats["stats"]["server"]["drain_grace_s"] == 1.5
        assert "replicas" in stats["stats"]["router"]

        m = request(server.host, server.port, {"cmd": "metrics"})
        assert "tdt_router_requests_total" in m["prometheus"]
    finally:
        server.shutdown()  # drains the router's replicas too
    assert all(r.state != "healthy" for r in router.replicas)
    assert router.audit() == []


def test_router_server_concurrent_payloads(tier_model):
    """A Router-backed server dispatches generation payloads WITHOUT
    the engine lock (concurrent_safe): two payloads from two
    connections complete concurrently across the fleet."""
    import threading

    from triton_distributed_tpu.serving import ModelServer, request

    model = tier_model
    router = make_router(model, 2)
    server = ModelServer(router).start()
    try:
        done = {}

        def gen(i, p, g):
            done[i] = request(
                server.host, server.port,
                {"requests": [p.tolist()], "gen_lens": [g]}, timeout=120,
            )

        threads = [
            threading.Thread(target=gen, args=(i, PROMPTS[i], GENS[i]),
                             daemon=True)
            for i in range(2)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        golds = goldens(model, PROMPTS[:2], GENS[:2])
        for i in range(2):
            assert done[i]["results"][0]["status"] == "ok"
            np.testing.assert_array_equal(
                np.asarray(done[i]["outputs"][0], np.int32), golds[i]
            )
    finally:
        server.shutdown()


def test_replace_add_replica_under_concurrent_submissions(
        fresh_telemetry):
    """ISSUE-10 satellite: ``replace_replica``/``add_replica`` while
    submissions are in flight — generation-suffixed names stay unique,
    retired replicas keep resolving (late hop judgments), and the
    fleet's cumulative totals count every delivered token exactly once
    (no double-counting across the swap)."""
    import threading as _threading

    from triton_distributed_tpu.models.stub import (
        StubEngine,
        stub_generate,
    )
    from triton_distributed_tpu.serving.replica import EngineReplica
    from triton_distributed_tpu.serving.router import Router

    def stub_replica(name):
        return EngineReplica(
            StubEngine(num_pages=64, page_size=4), name=name,
        )

    # r0's engine blocks on a test-controlled gate: its in-flight batch
    # provably CANNOT latch before the swap's re-route claims run, so
    # the exactly-once totals check below is deterministic — and the
    # late batch still completes inside the test (latch-losing,
    # excluded from totals by the DEAD accounting rule).
    gate = _threading.Event()

    class GatedStub(StubEngine):
        def run(self, reqs, *, results=False):
            gate.wait(30)
            return super().run(reqs, results=results)

    r0 = EngineReplica(GatedStub(num_pages=64, page_size=4), name="r0")
    router = Router([r0, stub_replica("r1")], max_reroutes=3)
    prompts = [np.arange(i + 1, i + 7, dtype=np.int32) for i in range(6)]
    gens = [5 + (i % 3) for i in range(6)]
    golds = [stub_generate(p, g) for p, g in zip(prompts, gens)]
    results = {}
    barrier = _threading.Barrier(len(prompts) + 1)

    def submit(i):
        barrier.wait(timeout=30)
        results[i] = router.run([(prompts[i], gens[i])], results=True)[0]

    threads = [
        _threading.Thread(target=submit, args=(i,), daemon=True)
        for i in range(len(prompts))
    ]
    for t in threads:
        t.start()
    barrier.wait(timeout=30)
    # Mid-flight: kill r0 (its orphans re-route), swap in its
    # generation-suffixed successor, and grow the rotation.
    dead = router.replica("r0")
    orphans = dead.mark_unhealthy("operator kill for swap test")
    router._on_replica_failure(dead, orphans)
    retired = router.replace_replica("r0", stub_replica("r0#1"))
    assert retired is dead
    router.add_replica(stub_replica("r2"))
    with pytest.raises(ValueError, match="already live"):
        router.add_replica(stub_replica("r0#1"))
    for t in threads:
        t.join(timeout=60)
    # Every submission delivered, bit-exact.
    assert sorted(results) == list(range(len(prompts)))
    for i, r in results.items():
        assert r.status == "ok", (i, r.status, r.reason)
        assert r.tokens.tolist() == golds[i]
    # Names stay unique across live + retired.
    names = [r.name for r in router.replicas]
    assert sorted(names) == sorted(set(names))
    assert "r0#1" in names and "r2" in names
    # The retired replica keeps resolving (late hop stamps need it).
    assert router.replica("r0") is dead
    assert router.last_stats["router"]["retired_replicas"] == 1
    # Release the dead replica's wedged batch: it latch-loses and the
    # DEAD rule keeps it out of the ledger.
    gate.set()
    dead.join(timeout=30)
    assert dead.runs == 0 and dead.totals["generated_tokens"] == 0
    # Fleet totals count each delivered token exactly once: re-routed
    # work counts where it actually ran, the duplicate late batch is
    # excluded.
    delivered = sum(len(r.tokens) for r in results.values())
    assert router.last_stats["generated_tokens"] == delivered
    router.shutdown()
