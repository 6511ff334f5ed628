"""Chaos suite: deterministic fault injection against the serving stack.

Acceptance bar (ISSUE 3): for every `FaultPlan` seam — pool
exhaustion, decode-step exceptions, NaN logits, oversized requests,
client disconnects — the engine completes the remaining requests, the
failed request returns a STRUCTURED error with its partial output, and
the pool/radix audit reports zero leaked/double-owned pages afterward;
the server answers `ping` throughout. The conftest autouse fixture
re-audits every engine after each test, so a leak in any recovery path
fails here, loudly.
"""

import threading
import time

import numpy as np
import pytest

from triton_distributed_tpu.models.continuous import (
    ContinuousEngine,
    Request,
    RequestFailedError,
)
from triton_distributed_tpu.models.engine import Engine
from triton_distributed_tpu.runtime.faults import (
    FaultError,
    FaultPlan,
    fault_point,
    mutate_point,
)

P_A = [5, 9, 2, 4]
P_B = [7, 1, 3, 8, 6, 2, 4, 9]


def tiny_engine(model, **kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("page_size", 16)
    kw.setdefault("max_length", 64)
    return model, ContinuousEngine(model, **kw)


_GOLDEN: dict = {}


def golden(model, prompt, gen):
    """What the module's one model decodes greedily, served once."""
    key = (tuple(prompt), gen)
    if key not in _GOLDEN:
        _GOLDEN[key] = Engine(model, temperature=0.0).serve(
            np.asarray([prompt], np.int32), gen_len=gen
        )[0, len(prompt):]
    return _GOLDEN[key]


# -- FaultPlan semantics (pure host-side) --------------------------------


def test_faultplan_determinism_and_counting():
    """Same seed + same call order → identical firing pattern; `at`,
    `times`, and `match` filters behave; mutation rules transform."""

    def firings(seed):
        plan = FaultPlan(seed).on("s", prob=0.5, times=100)
        got = []
        for i in range(50):
            try:
                plan.fire("s", i=i)
            except FaultError:
                got.append(i)
        return got

    assert firings(7) == firings(7)
    assert firings(7) != firings(8)  # seeded, not constant

    plan = FaultPlan().on("x", at=(2, 4), times=2)
    hits = []
    for i in range(5):
        try:
            plan.fire("x")
        except FaultError:
            hits.append(i)
    assert hits == [1, 3]
    assert [h for _, h, _ in plan.fired] == [2, 4]

    plan = FaultPlan().on("y", at=1, step=3)  # match filter on ctx
    plan.fire("y", step=0)  # hit 1 but step mismatch → no fire
    with pytest.raises(FaultError):
        FaultPlan().on("z", at=1).fire("z")

    plan = FaultPlan().on("m", at=2, times=5, mutate=lambda v, ctx: v + 1)
    assert plan.mutate("m", 10) == 10   # hit 1: untouched
    assert plan.mutate("m", 10) == 11   # hit 2: mutated

    # A ctx key colliding with the telemetry event's own fields (or
    # emit's positional ``kind``) must not TypeError out of the
    # injection site — the ctx value survives under a ctx_ prefix.
    with pytest.raises(FaultError):
        FaultPlan().on("c", at=1).fire("c", hit="ctx-collides",
                                       kind="timeout")
    from triton_distributed_tpu.obs import events as obs_events
    ev = [e for e in obs_events.default_ring().tail(0)[0]
          if e.kind == "fault" and e.fields.get("seam") == "c"]
    if ev:  # ring enabled in this run
        assert ev[-1].fields["ctx_kind"] == "timeout"
        assert ev[-1].fields["ctx_hit"] == "ctx-collides"
        assert ev[-1].fields["hit"] == 1
    plan2 = FaultPlan().on("m2", at=1, mutate=lambda v, ctx: v * 2)
    assert plan2.mutate("m2", 3, kind="k", hit="h") == 6  # no TypeError


def test_fault_points_inert_without_plan():
    fault_point("engine.decode", step=0)
    assert mutate_point("engine.logits", 42) == 42
    with FaultPlan().on("only.this", at=1):
        fault_point("engine.decode", step=0)  # unarmed seam: no-op


def test_faultplan_nested_activation_refused():
    with FaultPlan():
        with pytest.raises(RuntimeError, match="already active"):
            FaultPlan().__enter__()


# -- engine chaos: every seam leaves a clean, serviceable engine ---------


def test_pool_exhaustion_isolated(own_model):
    """An injected pool-exhaustion failure at admission fails ONLY that
    request; the others complete bit-exact and the audit is clean."""
    model, eng = tiny_engine(own_model, max_batch=1)
    gold_a = golden(model, P_A, 4)
    reqs = [(np.asarray(P_A, np.int32), 4)] * 3
    with FaultPlan().exhaust_pool(at=2):  # 2nd admission's allocate
        results = eng.run(reqs, results=True)
    statuses = [r.status for r in results]
    assert statuses.count("failed") == 1
    assert statuses.count("ok") == 2
    for r in results:
        if r.ok:
            np.testing.assert_array_equal(r.tokens, gold_a)
        else:
            assert "exhausted" in r.reason
            assert len(r.tokens) == 0  # failed before its first token
    assert eng.audit() == []
    assert len(eng.pool.free) == eng._capacity
    # Engine reusable after the fault: a clean run matches the golden.
    np.testing.assert_array_equal(eng.run([(P_A, 4)])[0], gold_a)


def test_decode_exception_slot_attributed(own_model):
    """A decode fault carrying slot attribution evicts exactly that
    request (partial output, structured error); its batchmate's greedy
    stream is untouched."""
    model, eng = tiny_engine(own_model)
    gold_b = golden(model, P_B, 6)
    with FaultPlan().decode_exc(at=3, slot=0):
        results = eng.run(
            [(np.asarray(P_A, np.int32), 6),
             (np.asarray(P_B, np.int32), 6)],
            results=True,
        )
    assert results[0].status == "failed"
    assert "injected" in results[0].reason
    assert 0 < len(results[0].tokens) < 6  # partial output survived
    assert results[1].ok
    np.testing.assert_array_equal(results[1].tokens, gold_b)
    assert eng.last_stats["decode_faults"] == 1
    assert eng.audit() == []


def test_decode_exception_unattributed_poisons_batch(own_model):
    """A decode fault with NO slot attribution fails every in-flight
    request — but queued requests still serve and the engine stays
    clean."""
    model, eng = tiny_engine(own_model, max_batch=1)
    gold_a = golden(model, P_A, 4)
    with FaultPlan().decode_exc(at=2):
        results = eng.run(
            [(np.asarray(P_A, np.int32), 4),
             (np.asarray(P_A, np.int32), 4)],
            results=True,
        )
    assert results[0].status == "failed"
    assert results[1].ok  # admitted after the fault, served normally
    np.testing.assert_array_equal(results[1].tokens, gold_a)
    assert eng.audit() == []


def test_nan_logits_guard(own_model):
    """Injected NaN logits fail only the poisoned slot (structured
    `nan_logits`, counted in last_stats) — never silently sampled."""
    model, eng = tiny_engine(own_model)
    gold_b = golden(model, P_B, 6)
    with FaultPlan().nan_logits(at=2, slot=0):
        results = eng.run(
            [(np.asarray(P_A, np.int32), 6),
             (np.asarray(P_B, np.int32), 6)],
            results=True,
        )
    assert results[0].status == "nan_logits"
    assert "non-finite" in results[0].reason
    err = results[0].error  # structured RequestError channel
    assert err is not None and err.status == "nan_logits"
    assert results[1].ok and results[1].error is None
    np.testing.assert_array_equal(results[1].tokens, gold_b)
    assert eng.last_stats["nonfinite_logits"] == 1
    assert eng.audit() == []


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
@pytest.mark.parametrize("prefix_cache", [False, True])
@pytest.mark.parametrize(
    "oversized,why",
    [((60, 16), "exceeds max_length"),     # 76 tokens > max_length 64
     ((40, 16), "pool capacity is 3"),     # fits a row: 4 pages of 3
     ((70, 16), "exceeds max_length")],    # both: the length is named
    ids=["max_length", "pool", "both"])
def test_oversized_request_isolated(own_model, oversized, why,
                                    prefix_cache, kv_dtype):
    """A request that can never fit (too long for a table row, too many
    pages for the pool, or both) gets a structured `unservable` result
    (results mode) while the rest of the batch is served token for
    token; legacy mode still raises ValueError up front. There is one
    admission path: what does not fit is refused, never split."""
    def engine():
        return ContinuousEngine(
            own_model, max_batch=2, page_size=16, max_length=64,
            num_pages=3, prefix_cache=prefix_cache, kv_dtype=kv_dtype,
        )
    fits = [(np.asarray(P_A, np.int32), 4), (np.asarray(P_B, np.int32), 4)]
    gold = engine().run(fits)
    if kv_dtype is None:
        np.testing.assert_array_equal(gold[0], golden(own_model, P_A, 4))
    big = (np.zeros(oversized[0], np.int32), oversized[1])
    eng = engine()
    results = eng.run([fits[0], big, fits[1]], results=True)
    for res, want in zip((results[0], results[2]), gold):
        assert res.ok and res.error is None
        np.testing.assert_array_equal(res.tokens, want)
    assert results[1].status == "unservable"
    assert why in results[1].reason
    err = results[1].error  # structured RequestError channel
    assert err is not None and err.status == "unservable"
    assert len(results[1].tokens) == 0
    assert eng.last_stats["failed_requests"] == 1
    with pytest.raises(ValueError, match=why):
        eng.run([big])
    assert eng.audit() == []


def test_deadline_and_load_shedding(own_model):
    """deadline_s=0 expires before admission (structured
    `deadline_exceeded`); max_queue sheds excess load as `overloaded`;
    the surviving request is unaffected."""
    model, eng = tiny_engine(own_model, max_batch=1, max_queue=2)
    gold_a = golden(model, P_A, 4)
    results = eng.run(
        [
            Request(np.asarray(P_A, np.int32), 4),
            Request(np.asarray(P_A, np.int32), 4, deadline_s=0.0),
            Request(np.asarray(P_A, np.int32), 4),  # beyond max_queue=2
        ],
        results=True,
    )
    assert results[0].ok
    np.testing.assert_array_equal(results[0].tokens, gold_a)
    assert results[1].status == "deadline_exceeded"
    assert results[2].status == "overloaded"
    assert "retry" in results[2].reason
    stats = eng.last_stats
    assert stats["deadline_expired"] == 1
    assert stats["shed_requests"] == 1
    assert eng.audit() == []


def test_legacy_run_raises_structured_failure(own_model):
    """run(results=False) finishes the survivors, tears the failure
    down cleanly, and raises RequestFailedError carrying it."""
    model, eng = tiny_engine(own_model)
    with FaultPlan().nan_logits(at=2, slot=0):
        with pytest.raises(RequestFailedError, match="nan_logits"):
            eng.run([(np.asarray(P_A, np.int32), 6),
                     (np.asarray(P_B, np.int32), 6)])
    assert eng.audit() == []


def test_prefix_cache_fault_isolation(own_model):
    """Faults on a prefix-cache engine release every pin: a failed
    admission drops its match refcounts and the tree/pool partition
    stays exact (the leak this PR exists to catch)."""
    model, eng = tiny_engine(own_model, prefix_cache=True, num_pages=12
    )
    warm = np.asarray(P_B * 3, np.int32)  # 24 tokens: populates the tree
    eng.run([(warm, 4)])
    assert eng.prefix.node_count > 0
    with FaultPlan().admit_exc(at=1):
        results = eng.run(
            [(warm, 4), (np.asarray(P_A, np.int32), 4)], results=True
        )
    assert results[0].status == "failed"
    assert results[1].ok
    assert eng.audit() == []
    assert all(n.refcount == 0 for n in eng.prefix.walk())
    # The tree survived the fault: a clean warm run still hits it.
    out = eng.run([(warm, 4)], results=True)
    assert out[0].ok and eng.last_stats["prefix_hit_tokens"] > 0


def test_pool_exhaustion_mid_prefix_admission(own_model):
    """Pool exhaustion raised INSIDE prefix admission (after the match
    pinned tree nodes) must release those pins on the failure path."""
    model, eng = tiny_engine(own_model, prefix_cache=True, num_pages=12
    )
    warm = np.asarray(P_B * 3, np.int32)
    eng.run([(warm, 4)])
    with FaultPlan().exhaust_pool(at=1):
        results = eng.run([(warm, 4)], results=True)
    assert results[0].status == "failed"
    assert "exhausted" in results[0].reason
    assert eng.audit() == []
    assert all(n.refcount == 0 for n in eng.prefix.walk())


def test_spec_verify_fault_isolated(own_model):
    """A speculative verify that raises fails only its own request;
    the engine then serves the next request normally."""
    model, eng = tiny_engine(own_model, max_batch=1, speculative=3)
    rep = np.asarray(P_A * 2, np.int32)  # repetitive → drafts fire
    gold = golden(model, list(rep), 6)
    with FaultPlan().verify_exc(at=1):
        results = eng.run([(rep, 6), (rep, 6)], results=True)
    assert results[0].status == "failed"
    assert results[1].ok
    np.testing.assert_array_equal(results[1].tokens, gold)
    assert eng.audit() == []


def test_spec_verify_nan_logits_guarded(own_model):
    """Non-finite logits inside a speculative verify chunk must fail
    that request with a structured `nan_logits` (counted), never be
    silently argmax'd into accepted tokens."""
    import numpy as _np

    model, eng = tiny_engine(own_model, max_batch=1, speculative=3)
    rep = np.asarray(P_A * 2, np.int32)
    gold = golden(model, list(rep), 6)

    def nanify(value, _ctx):
        value = _np.array(value, _np.float32)
        value[0] = _np.nan
        return value

    with FaultPlan().on("spec.logits", at=1, mutate=nanify):
        results = eng.run([(rep, 6), (rep, 6)], results=True)
    assert results[0].status == "nan_logits"
    assert results[1].ok
    np.testing.assert_array_equal(results[1].tokens, gold)
    assert eng.last_stats["nonfinite_logits"] == 1
    assert eng.audit() == []


def test_engine_reusable_after_fault_storm(own_model):
    """One engine, three different fault runs back to back, then a
    clean run: output bit-exact, zero leaked pages — the crash-safe
    teardown really is crash-safe."""
    model, eng = tiny_engine(own_model, max_batch=1)
    gold_a = golden(model, P_A, 4)
    for plan in (
        FaultPlan().exhaust_pool(at=1),
        FaultPlan().decode_exc(at=1),
        FaultPlan().nan_logits(at=1, slot=0),
    ):
        with plan:
            results = eng.run([(np.asarray(P_A, np.int32), 4)],
                              results=True)
        assert not results[0].ok
        assert eng.audit() == []
        assert len(eng.pool.free) == eng._capacity
    np.testing.assert_array_equal(eng.run([(P_A, 4)])[0], gold_a)


def test_all_deadlines_expire_with_queued_request(own_model):
    """Regression: the active request expires mid-decode AND the queued
    request's deadline is already gone — run() must return two
    structured deadline_exceeded results, not crash popping an empty
    queue after _try_admit drained it."""
    model, eng = tiny_engine(own_model, max_batch=1)
    results = eng.run(
        [
            Request(np.asarray(P_A, np.int32), 48, deadline_s=0.2),
            Request(np.asarray(P_A, np.int32), 4, deadline_s=0.0),
        ],
        results=True,
    )
    assert [r.status for r in results] == ["deadline_exceeded"] * 2
    assert eng.audit() == []


def test_server_recv_fault_counted(own_model):
    """Regression: a raise-style fault on the server.recv seam (a
    RuntimeError, not an OSError) must be absorbed by the connection
    thread AND counted as a conn error — never a silent thread death."""
    from triton_distributed_tpu.serving import ModelServer, request

    model, eng = tiny_engine(own_model)
    server = ModelServer(eng).start()
    try:
        with FaultPlan().on("server.recv", at=1):
            with pytest.raises((ConnectionError, OSError)):
                request(server.host, server.port, {"cmd": "ping"},
                        timeout=5)
        assert request(server.host, server.port, {"cmd": "ping"})["ok"]
        stats = request(server.host, server.port, {"cmd": "stats"})
        assert stats["stats"]["server"]["conn_errors"] >= 1
    finally:
        server.shutdown()


# -- server chaos --------------------------------------------------------


def test_server_serviceable_through_chaos(own_model):
    """The acceptance scenario end to end: while a faulted generation
    runs, ping answers from another connection; a dropped connection
    (injected mid-response) is survived + counted, and the client-side
    retry/backoff recovers; per-request failures ride the structured
    results channel."""
    from triton_distributed_tpu.serving import ModelServer, request

    model, eng = tiny_engine(own_model)
    server = ModelServer(eng).start()
    try:
        pings: list[bool] = []
        stop = threading.Event()

        def prober():
            while not stop.is_set():
                try:
                    pings.append(request(
                        server.host, server.port, {"cmd": "ping"},
                        timeout=5.0,
                    )["ok"])
                except Exception:
                    pings.append(False)
                time.sleep(0.01)

        t = threading.Thread(target=prober, daemon=True)
        # Phase 1: NaN fault mid-generation, pings probing concurrently
        # (they bypass the engine lock, so they answer mid-payload).
        with FaultPlan().nan_logits(at=2, slot=0):
            t.start()
            resp = request(
                server.host, server.port,
                {"requests": [P_A, P_B], "gen_lens": [6, 6]},
            )
            statuses = [r["status"] for r in resp["results"]]
            assert statuses[0] == "nan_logits" and statuses[1] == "ok"
            stop.set()
            t.join(timeout=5)
        assert pings and all(pings)  # ping answered THROUGHOUT
        # Phase 2: the next response write is dropped mid-stream (no
        # prober — the injection counts raw sends); the client-side
        # retry/backoff recovers on a fresh connection.
        with FaultPlan().drop_connection(at=1):
            resp2 = request(
                server.host, server.port,
                {"requests": [P_A], "gen_lens": [2]},
                retries=3, backoff_s=0.05,
            )
        assert resp2["results"][0]["status"] == "ok"
        stats = request(server.host, server.port, {"cmd": "stats"})
        assert stats["stats"]["server"]["conn_errors"] >= 1
        assert eng.audit() == []
    finally:
        server.shutdown()


def test_server_deadline_payload(own_model):
    """deadline_s rides the requests payload down to the engine."""
    from triton_distributed_tpu.serving import ModelServer, request

    model, eng = tiny_engine(own_model)
    server = ModelServer(eng).start()
    try:
        resp = request(
            server.host, server.port,
            {"requests": [P_A, P_A], "gen_lens": [4, 4],
             "deadline_s": [None, 0.0]},
        )
        assert resp["results"][0]["status"] == "ok"
        assert resp["results"][1]["status"] == "deadline_exceeded"
    finally:
        server.shutdown()


def test_chaos_counters_and_events_fire(own_model, fresh_telemetry):
    """ISSUE 5 satellite: chaos scenarios leave matching telemetry —
    the shed/deadline/nan counters in the metrics registry AND the
    corresponding shed/deadline/nan_guard/fault events in the ring,
    each consistent with the engine's own last_stats ledger."""
    from triton_distributed_tpu.obs import events as obs_events
    from triton_distributed_tpu.obs import metrics as obs_metrics

    model, eng = tiny_engine(own_model, max_batch=1, max_queue=2)
    with FaultPlan().nan_logits(at=2, slot=0):
        results = eng.run(
            [
                Request(np.asarray(P_A, np.int32), 6),  # poisoned
                Request(np.asarray(P_B, np.int32), 4, deadline_s=0.0),
                Request(np.asarray(P_A, np.int32), 4),  # > max_queue
            ],
            results=True,
        )
    assert [r.status for r in results] == [
        "nan_logits", "deadline_exceeded", "overloaded"
    ]
    assert eng.audit() == []

    # Counters mirror last_stats exactly (registry cleared above).
    def val(name):
        m = obs_metrics.default_registry().get(name)
        return m.value() if m is not None else 0

    stats = eng.last_stats
    assert (val("tdt_engine_shed_requests_total")
            == stats["shed_requests"] == 1)
    assert (val("tdt_engine_deadline_expired_total")
            == stats["deadline_expired"] == 1)
    assert (val("tdt_engine_nonfinite_logits_total")
            == stats["nonfinite_logits"] == 1)
    assert (val("tdt_engine_failed_requests_total")
            == stats["failed_requests"] == 3)

    # Status-labeled request totals pick up the full taxonomy mix.
    totals = obs_metrics.default_registry().get("tdt_requests_total")
    for status in ("nan_logits", "deadline_exceeded", "overloaded"):
        assert totals.value(status=status) == 1, status

    # Events: the injected fault itself plus each failure's kind.
    evts, _ = obs_events.default_ring().tail(0)
    kinds = [e.kind for e in evts]
    assert "fault" in kinds       # runtime/faults.py activation
    assert "shed" in kinds        # overloaded
    assert "deadline" in kinds    # deadline_exceeded
    assert "nan_guard" in kinds   # nan_logits
    fault = next(e for e in evts if e.kind == "fault")
    assert fault.fields["seam"] == "engine.logits"
    # Seqs are strictly increasing — the ring is tail-consistent
    # even after a chaos run.
    seqs = [e.seq for e in evts]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
