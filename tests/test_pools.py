"""Elastic pool control-plane tests (docs/scale-out.md "Disaggregated
pools & autoscaling"): role-typed replica pools, SLO-aware scheduling,
and the goodput-driven autoscaler.

Layers of evidence:

- the pure half (serving/pools.py): role helpers, the decode placement
  score's match-vs-pressure trade, pool-shape/gauge publication, and
  the Scheduler's priority ordering, token-budget waves, and
  deadline-aware shedding — milliseconds, plain fakes;
- the autoscaler control loop on a FAKE fleet (the duck surface the
  class documents): hysteresis, cooldown, min/max bounds, the
  crash-loop-breaker parked veto, the respawn-in-progress guard, and
  the drain-timeout → deferred-retire path, all via deterministic
  ``tick(now=...)`` calls;
- the router's ``policy="pools"`` on in-process stub replicas: fresh
  work prefills on the prefill pool, hands off, and decodes on the
  decode pool — outputs bit-exact, zero duplicate tokens, the pool
  shape surfaced through stats;
- the batched handoff-sweep export on the tiny model: one
  ``export_slots_batch`` gather produces snapshots IDENTICAL (modulo
  the export wall stamp) to per-slot serial exports, and both resume
  bit-exact;
- CLI guardrails: the pool flags refuse, by flag name, every path
  that would silently ignore them (the PR 12 convention);
- chaos (needs_procs): SIGKILL of a prefill-pool replica mid-handoff
  finishes bit-exact on the decode pool via snapshot reroute; a live
  autoscaler scales a stub fleet UP under a burst and DOWN
  mid-generation with a lossless drain (zero lost/duplicate tokens,
  audits clean).
"""

import signal
import subprocess
import sys
import threading
import time
import types

import numpy as np
import pytest

from triton_distributed_tpu.models.stub import StubEngine, stub_generate
from triton_distributed_tpu.obs import events as obs_events
from triton_distributed_tpu.obs import metrics as obs_metrics
from triton_distributed_tpu.serving import pools
from triton_distributed_tpu.serving.autoscaler import Autoscaler
from triton_distributed_tpu.serving.replica import (
    DRAINED,
    HEALTHY,
    EngineReplica,
)
from triton_distributed_tpu.serving.router import Router


def _can_spawn() -> bool:
    try:
        return subprocess.run(
            [sys.executable, "-c", "pass"], timeout=60
        ).returncode == 0
    except Exception:  # noqa: BLE001 — any failure means "cannot"
        return False


_SPAWN_OK = _can_spawn()
needs_procs = pytest.mark.skipif(
    not _SPAWN_OK or not hasattr(signal, "SIGKILL"),
    reason="child-process spawning unavailable on this platform",
)

STUB_PROMPTS = [
    np.arange(1, 9, dtype=np.int32),
    np.arange(20, 30, dtype=np.int32),
]
STUB_GENS = [50, 40]
STUB_GOLDS = [stub_generate(p, g) for p, g in zip(STUB_PROMPTS, STUB_GENS)]


# -- fakes ------------------------------------------------------------------


class _Rep:
    """The replica duck surface pools.py documents."""

    def __init__(self, name, role, *, pending=0, max_pending=8,
                 free_pages=0, state=HEALTHY):
        self.name = name
        self.role = role
        self.pending = pending
        self.max_pending = max_pending
        self.free_pages = free_pages
        self.state = state
        self.down = False

    def match_len(self, toks):
        return 0


class _FakeRouter:
    def __init__(self, reps):
        self.replicas = reps
        self.stats = {"shed_skips": 0}
        self.drained = []
        self.drain_ok = True

    def drain_replica(self, name, grace_s=None, *, handoff=False):
        self.drained.append((name, handoff))
        for r in self.replicas:
            if r.name == name:
                r.state = DRAINED if self.drain_ok else "draining"
        return self.drain_ok


class _FakeFleet:
    """The fleet duck surface the Autoscaler documents."""

    def __init__(self, reps):
        self.router = _FakeRouter(reps)
        self.parked = set()
        self.fail_spawn = False
        self.added = []
        self.retired = []

    def pool_slots(self, role):
        return [
            {"name": r.name, "parked": r.name in self.parked,
             "down": r.down, "replica_name": r.name,
             "replica_state": r.state, "pending": r.pending}
            for r in self.router.replicas if r.role == role
        ]

    def add_slot(self, spec):
        if self.fail_spawn:
            raise RuntimeError("spawn refused")
        rep = _Rep(spec.name, spec.role)
        self.router.replicas.append(rep)
        self.added.append(spec.name)
        return rep

    def retire_slot(self, name):
        self.retired.append(name)
        self.router.replicas = [
            r for r in self.router.replicas if r.name != name
        ]
        return True


def _spec_factory(role, name):
    return types.SimpleNamespace(role=role, name=name)


class _T:
    """The ticket duck surface Scheduler.plan consumes."""

    def __init__(self, prompt_len, gen_len=8, slo_class=None,
                 snap_out=None, deadline_s=None, enqueue_t=None):
        self.prompt = list(range(1, prompt_len + 1))
        self.gen_len = gen_len
        self.slo_class = slo_class
        self.snapshot = (None if snap_out is None
                         else {"out": list(snap_out)})
        self.deadline_s = deadline_s
        self.enqueue_t = enqueue_t


# -- pure half: roles, scoring, gauges --------------------------------------


def test_role_helpers_and_validation():
    p = _Rep("p", pools.PREFILL)
    d = _Rep("d", pools.DECODE)
    m = _Rep("m", pools.MIXED)
    legacy = types.SimpleNamespace(pending=0)  # never declared a role
    assert pools.replica_role(legacy) == pools.MIXED
    assert pools.replica_role(types.SimpleNamespace(role="weird")) \
        == pools.MIXED
    assert pools.prefill_capable(p) and not pools.decode_capable(p)
    assert pools.decode_capable(d) and not pools.prefill_capable(d)
    assert pools.prefill_capable(m) and pools.decode_capable(m)
    assert pools.validate_role("prefill") == "prefill"
    with pytest.raises(ValueError, match="role"):
        pools.validate_role("gpu")
    # Occupancy clamps to [0, 1] and survives max_pending=0.
    assert pools.occupancy(_Rep("x", "mixed", pending=4)) == 0.5
    assert pools.occupancy(
        _Rep("x", "mixed", pending=99, max_pending=8)) == 1.0
    assert pools.occupancy(
        _Rep("x", "mixed", pending=1, max_pending=0)) == 1.0


def test_decode_score_weighs_match_against_pressure():
    idle = _Rep("idle", pools.DECODE, pending=0, free_pages=10)
    busy = _Rep("busy", pools.DECODE, pending=8, free_pages=0)
    # A saturated replica with a PERFECT match still beats an idle one
    # with none (2*1 - 1 > 0)...
    assert pools.decode_score(busy, 10, 10) \
        > pools.decode_score(idle, 0, 10)
    # ...but a SHORT match loses to idleness: pressure breaks
    # monopolies (2*0.3 - 1 < 0).
    assert pools.decode_score(busy, 3, 10) \
        < pools.decode_score(idle, 0, 10)
    # The free-page term breaks ties between equal matches and is
    # normalized by the pool max (and disabled when max_free == 0).
    a = _Rep("a", pools.DECODE, pending=0, free_pages=10)
    b = _Rep("b", pools.DECODE, pending=0, free_pages=2)
    assert pools.decode_score(a, 5, 10, max_free=10) \
        > pools.decode_score(b, 5, 10, max_free=10)
    assert pools.decode_score(a, 5, 10) == pools.decode_score(b, 5, 10)


def test_pool_shape_and_gauges(fresh_telemetry):
    reps = [
        _Rep("p0", pools.PREFILL, pending=4, free_pages=8),
        _Rep("p1", pools.PREFILL, pending=2, free_pages=4,
             state="draining"),
        _Rep("d0", pools.DECODE, pending=8, free_pages=2),
        _Rep("m0", pools.MIXED),
    ]
    shape = pools.pool_shape(reps)
    assert shape["prefill"] == {"replicas": 2, "healthy": 1}
    assert shape["decode"] == {"replicas": 1, "healthy": 1}
    assert shape["mixed"] == {"replicas": 1, "healthy": 1}
    reg = obs_metrics.default_registry()
    out = pools.publish_pool_gauges(reps, reg)
    # Healthy replicas only: the draining p1 is not capacity.
    assert out["prefill"] == {"replicas": 1, "pending": 4,
                              "free_pages": 8, "occupancy": 0.5}
    assert out["decode"]["occupancy"] == 1.0
    g = reg.get("tdt_pool_occupancy")
    assert g.value(role="prefill") == 0.5
    assert g.value(role="decode") == 1.0
    assert reg.get("tdt_pool_replicas").value(role="prefill") == 1
    assert reg.get("tdt_pool_free_pages").value(role="decode") == 2


# -- scheduler --------------------------------------------------------------


def test_scheduler_priority_and_budget_waves():
    sched = pools.Scheduler(class_priority={"gold": 0, "bulk": 1},
                            prefill_token_budget=8,
                            decode_token_budget=5)
    bulk = _T(6, slo_class="bulk")
    gold = _T(4, slo_class="gold")
    unknown = _T(2, slo_class="other")  # ranks after every named class
    waves, shed = sched.plan([bulk, gold, unknown], now=0.0)
    assert shed == []
    # gold runs first; bulk(6) would blow the 8-token budget after
    # gold(4), so it defers; unknown(2) back-fills... no — waves are
    # greedy IN ORDER, so unknown rides the second wave with bulk.
    assert waves[0] == [gold]
    assert waves[1] == [bulk, unknown]
    # An oversize ticket still gets a wave of its own: budgets pace,
    # they never starve.
    huge = _T(50)
    waves, _ = sched.plan([_T(3), huge], now=0.0)
    assert [len(w) for w in waves] == [1, 1] and waves[1] == [huge]
    # Snapshot tickets cost their REMAINING generation against the
    # decode budget: 8-gen with 5 already out costs 3, twice fits the
    # 5-token decode budget only once.
    s1 = _T(4, gen_len=8, snap_out=[1, 2, 3, 4, 5])
    s2 = _T(4, gen_len=8, snap_out=[1, 2, 3, 4, 5])
    waves, _ = sched.plan([s1, s2], now=0.0)
    assert [len(w) for w in waves] == [1, 1]
    # Zero budgets = no pacing at all.
    waves, _ = pools.Scheduler().plan([_T(100), _T(100)], now=0.0)
    assert [len(w) for w in waves] == [2]


def test_scheduler_sheds_past_deadline(fresh_telemetry):
    sched = pools.Scheduler()
    dead = _T(4, slo_class="bulk", deadline_s=0.5, enqueue_t=10.0)
    alive = _T(4, deadline_s=100.0, enqueue_t=10.0)
    unstamped = _T(4, deadline_s=0.5)  # no enqueue stamp: never shed
    waves, shed = sched.plan([dead, alive, unstamped], now=20.0)
    assert shed == [dead]
    assert waves == [[alive, unstamped]]
    reg = obs_metrics.default_registry()
    sched.record_plan(waves, shed, reg)
    assert reg.get("tdt_pool_sched_shed_total").value(
        slo_class="bulk") == 1
    evts, _ = obs_events.default_ring().tail(kind="sched_shed")
    assert evts and evts[-1].fields["count"] == 1
    assert evts[-1].fields["classes"] == ["bulk"]
    # Deferred counter: everything past the first wave.
    sched2 = pools.Scheduler(prefill_token_budget=4)
    waves, shed = sched2.plan([_T(4), _T(4), _T(4)], now=0.0)
    sched2.record_plan(waves, shed, reg)
    assert reg.get("tdt_pool_sched_deferred_total").value() == 2


# -- autoscaler on the fake fleet -------------------------------------------


def test_autoscaler_scale_up_cooldown_and_max(fresh_telemetry):
    fleet = _FakeFleet([_Rep("p0", pools.PREFILL, pending=8)])
    scaler = Autoscaler(fleet, _spec_factory,
                        pool_bounds={"prefill": (1, 3)},
                        cooldown_s=4.0, down_ticks=2)
    d = scaler.tick(now=0.0)
    assert [x["action"] for x in d] == ["scale_up"]
    assert fleet.added == ["prefill-as1"]
    # Keep the pool hot so the next intent is still "up".
    fleet.router.replicas[-1].pending = 8
    d = scaler.tick(now=1.0)
    assert [x["action"] for x in d] == ["skip"]
    assert d[0]["reason"] == "cooldown"
    d = scaler.tick(now=5.0)
    assert [x["action"] for x in d] == ["scale_up"]
    fleet.router.replicas[-1].pending = 8
    d = scaler.tick(now=10.0)
    assert d[0]["reason"] == "at_max"
    reg = obs_metrics.default_registry()
    assert reg.get("tdt_autoscaler_decisions_total").value(
        action="scale_up", role="prefill") == 2
    assert reg.get("tdt_autoscaler_skips_total").value(
        reason="cooldown") == 1
    assert reg.get("tdt_autoscaler_pool_size").value(role="prefill") == 3
    evts, _ = obs_events.default_ring().tail(kind="autoscale")
    assert sum(e.fields["action"] == "scale_up" for e in evts) == 2
    assert scaler.stats["scale_ups"] == 2 and scaler.stats["skips"] == 2


def test_autoscaler_scale_down_hysteresis_and_min(fresh_telemetry):
    fleet = _FakeFleet([
        _Rep("d0", pools.DECODE, pending=0),
        _Rep("d1", pools.DECODE, pending=1),
    ])
    scaler = Autoscaler(fleet, _spec_factory,
                        pool_bounds={"decode": (1, 3)},
                        cooldown_s=0.0, down_ticks=2)
    # Hysteresis: one calm tick is not enough.
    assert scaler.tick(now=0.0) == []
    d = scaler.tick(now=1.0)
    assert [x["action"] for x in d] == ["scale_down"]
    # Victim = least-pending healthy; drained synchronously → retired.
    assert d[0]["replica"] == "d0" and d[0]["drained"] is True
    assert fleet.router.drained == [("d0", True)]
    assert fleet.retired == ["d0"]
    # At the floor: calm ticks now skip with at_min.
    scaler.tick(now=2.0)
    d = scaler.tick(now=3.0)
    assert d and d[0]["reason"] == "at_min"
    reg = obs_metrics.default_registry()
    assert reg.get("tdt_autoscaler_decisions_total").value(
        action="scale_down", role="decode") == 1


def test_autoscaler_drain_timeout_defers_retire(fresh_telemetry):
    fleet = _FakeFleet([
        _Rep("d0", pools.DECODE, pending=0),
        _Rep("d1", pools.DECODE, pending=0),
    ])
    fleet.router.drain_ok = False  # drain "times out": still draining
    scaler = Autoscaler(fleet, _spec_factory,
                        pool_bounds={"decode": (1, 2)},
                        cooldown_s=0.0, down_ticks=1)
    d = scaler.tick(now=0.0)
    assert d[0]["action"] == "scale_down" and d[0]["drained"] is False
    assert fleet.retired == []  # in-flight work is never killed
    # The victim's worker finishes draining; the next tick reaps it.
    for r in fleet.router.replicas:
        if r.name == d[0]["replica"]:
            r.state = DRAINED
    d2 = scaler.tick(now=1.0)
    assert {"action": "retired", "role": "decode",
            "replica": d[0]["replica"]} in d2
    assert fleet.retired == [d[0]["replica"]]


def test_autoscaler_parked_and_respawn_vetoes(fresh_telemetry):
    # Parked slot: the crash-loop breaker owns this pool — scale-up
    # must not fight it.
    fleet = _FakeFleet([
        _Rep("p0", pools.PREFILL, pending=8),
        _Rep("p1", pools.PREFILL, pending=8),
    ])
    fleet.parked.add("p1")
    scaler = Autoscaler(fleet, _spec_factory,
                        pool_bounds={"prefill": (1, 4)},
                        cooldown_s=0.0, down_ticks=1)
    d = scaler.tick(now=0.0)
    assert d[0] == {"action": "skip", "role": "prefill",
                    "reason": "parked"}
    assert fleet.added == []
    # A slot mid-respawn: adding capacity would race the supervisor.
    fleet.parked.clear()
    fleet.router.replicas[1].down = True
    d = scaler.tick(now=1.0)
    assert d[0]["reason"] == "respawn_in_progress"
    # Spawn failure is data, not an exception out of the loop.
    fleet.router.replicas[1].down = False
    fleet.fail_spawn = True
    d = scaler.tick(now=2.0)
    assert d[0]["reason"] == "spawn_failed:RuntimeError"
    reg = obs_metrics.default_registry()
    assert reg.get("tdt_autoscaler_skips_total").value(
        reason="parked") == 1
    assert scaler.stats["scale_ups"] == 0


def test_autoscaler_validates_bounds_and_thresholds():
    fleet = _FakeFleet([])
    with pytest.raises(ValueError, match="role"):
        Autoscaler(fleet, _spec_factory, pool_bounds={"gpu": (1, 2)})
    with pytest.raises(ValueError, match="bounds"):
        Autoscaler(fleet, _spec_factory, pool_bounds={"mixed": (3, 1)})
    with pytest.raises(ValueError, match="occupancy"):
        Autoscaler(fleet, _spec_factory, pool_bounds={"mixed": (1, 2)},
                   up_occupancy=0.2, down_occupancy=0.5)


def test_autoscaler_urgency_overrides_calm_occupancy(fresh_telemetry):
    """SLO violations and router shed-skips force the scale-up path
    even when raw occupancy reads calm: TTFT indicts prefill,
    TPOT/e2e the decode pool."""
    reg = obs_metrics.default_registry()
    viol = reg.counter(
        "tdt_slo_violations_total",
        "Per-deadline SLO violations.", labels=("slo_class", "deadline"))
    fleet = _FakeFleet([
        _Rep("p0", pools.PREFILL, pending=0),
        _Rep("d0", pools.DECODE, pending=0),
    ])
    scaler = Autoscaler(fleet, _spec_factory,
                        pool_bounds={"prefill": (1, 2),
                                     "decode": (1, 2)},
                        cooldown_s=0.0, down_ticks=99)
    assert scaler.tick(now=0.0) == []  # calm fleet, no violations
    viol.inc(slo_class="default", deadline="ttft")
    d = scaler.tick(now=1.0)
    assert [(x["action"], x["role"]) for x in d] == [
        ("scale_up", "prefill")]
    viol.inc(slo_class="default", deadline="tpot")
    d = scaler.tick(now=2.0)
    assert [(x["action"], x["role"]) for x in d] == [
        ("scale_up", "decode")]
    # Deltas, not totals: a quiet tick after the burst takes no action.
    assert scaler.tick(now=3.0) == []


# -- router policy="pools" on in-process stubs ------------------------------


def _stub_replica(name, role, *, delay_s=0.0, num_pages=64):
    return EngineReplica(
        StubEngine(num_pages=num_pages, page_size=4, delay_s=delay_s),
        name=name, role=role,
    )


def test_pools_policy_disaggregates_bit_exact(fresh_telemetry):
    """The tentpole's routing half: fresh requests prefill on the
    prefill pool, hand off through the snapshot machinery, and decode
    on the decode pool — outputs bit-exact, zero duplicate tokens."""
    reps = [_stub_replica("p0", "prefill"), _stub_replica("d0", "decode")]
    router = Router(reps, policy="pools", max_reroutes=3)
    res = router.run(list(zip(STUB_PROMPTS, STUB_GENS)), results=True)
    for r, g in zip(res, STUB_GOLDS):
        assert r.status == "ok", (r.status, r.reason)
        assert r.tokens.tolist() == g
    assert router.stats["pool_prefill"] >= 2
    assert router.stats["pool_decode"] >= 2
    assert router.stats["prefill_migrations"] >= 2
    # Zero duplicates: every token generated exactly once fleet-wide
    # (restored tokens count as migrated_in, never re-generated).
    agg = router.last_stats
    assert agg["generated_tokens"] == sum(STUB_GENS)
    assert agg["migrated_in_tokens"] >= 1
    # The pool shape surfaces through the stats path server_stats uses.
    shape = agg["router"]["pools"]
    assert shape["prefill"] == {"replicas": 1, "healthy": 1}
    assert shape["decode"] == {"replicas": 1, "healthy": 1}
    assert router.audit() == []
    router.shutdown()


def test_pools_policy_single_replica_serves_end_to_end():
    """Degraded shapes stay correct: with no decode-capable target the
    prefill replica serves end-to-end (no handoff), roles steer but
    never strand."""
    router = Router([_stub_replica("solo", "prefill")], policy="pools")
    res = router.run([(STUB_PROMPTS[0], 6)], results=True)
    assert res[0].status == "ok"
    assert res[0].tokens.tolist() == stub_generate(STUB_PROMPTS[0], 6)
    assert router.stats["migrations"] == 0  # nowhere to hand off to
    router.shutdown()


def test_pools_decode_placement_prefers_match_then_pressure():
    """Snapshot tickets score onto the decode pool by decode_score:
    the digest-matching replica wins when idle; see
    test_decode_score_weighs_match_against_pressure for the pressure
    flip (exercised pure — replica pending is thread-owned here)."""
    from triton_distributed_tpu.serving.replica import Ticket

    reps = [_stub_replica("d0", "decode"), _stub_replica("d1", "decode")]
    router = Router(reps, policy="pools")
    # Warm d1's radix with the prompt so its digest matches.
    warm = router.replica("d1")
    warm.submit(Ticket(STUB_PROMPTS[0], 4))
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline and not warm.match_len(
            [int(t) for t in STUB_PROMPTS[0]]):
        time.sleep(0.01)
    assert warm.match_len([int(t) for t in STUB_PROMPTS[0]]) > 0
    t = Ticket(STUB_PROMPTS[0], STUB_GENS[0])
    t.snapshot = {"stub": True, "prompt": [int(x) for x in
                                           STUB_PROMPTS[0]],
                  "out": stub_generate(STUB_PROMPTS[0], 3),
                  "gen_len": STUB_GENS[0], "trace_id": None,
                  "exported_at": 0.0}
    rep, matched, decision = router._pick(t)
    assert decision == "pool_decode"
    assert rep.name == "d1" and matched > 0
    router.shutdown()


def test_router_scheduler_sheds_past_deadline_before_dispatch(
        fresh_telemetry):
    """Router.run with a Scheduler completes already-past-SLO tickets
    as deadline_exceeded WITHOUT spending a dispatch hop; everything
    else serves bit-exact."""
    from triton_distributed_tpu.models.continuous import Request
    from triton_distributed_tpu.obs.timeline import Timeline

    sched = pools.Scheduler(class_priority={"gold": 0, "bulk": 1})
    router = Router([_stub_replica("m0", "mixed")], policy="affinity",
                    scheduler=sched)
    tl = Timeline()
    tl.enqueue_t = time.monotonic() - 10.0  # enqueued long ago
    dead = Request(STUB_PROMPTS[0], 6, deadline_s=0.01, timeline=tl,
                   slo_class="bulk")
    live = Request(STUB_PROMPTS[1], 6, slo_class="gold")
    res = router.run([dead, live], results=True)
    assert res[0].status == "deadline_exceeded"
    assert "shed by pool scheduler" in res[0].reason
    assert len(res[0].tokens) == 0
    assert res[1].status == "ok"
    assert res[1].tokens.tolist() == stub_generate(STUB_PROMPTS[1], 6)
    assert router.stats["sched_sheds"] == 1
    assert router.stats["routed"] == 1  # the shed ticket never routed
    reg = obs_metrics.default_registry()
    assert reg.get("tdt_pool_sched_shed_total").value(
        slo_class="bulk") == 1
    router.shutdown()


# -- loadgen class mix ------------------------------------------------------


def test_loadgen_class_mix_deterministic_and_trace_compatible():
    from perf.loadgen import LoadSpec, generate_trace

    mix = (("gold", 1.0), ("bulk", 3.0))
    spec = LoadSpec(rate=5.0, n_requests=80, seed=3, class_mix=mix)
    t1 = generate_trace(spec)
    assert t1 == generate_trace(spec)  # seeded, replay-identical
    counts = {}
    for row in t1:
        counts[row["slo_class"]] = counts.get(row["slo_class"], 0) + 1
    assert set(counts) == {"gold", "bulk"}
    assert counts["bulk"] > counts["gold"]  # 3:1 weighting shows
    # Trace-identity contract: a mix-less spec's trace is bit-identical
    # to the mixed one everywhere EXCEPT slo_class (class draws come
    # after every pre-existing rng draw).
    base = generate_trace(LoadSpec(rate=5.0, n_requests=80, seed=3))
    for a, b in zip(base, t1):
        a2, b2 = dict(a), dict(b)
        a2.pop("slo_class"), b2.pop("slo_class")
        assert a2 == b2
    assert all(r["slo_class"] == "default" for r in base)
    with pytest.raises(ValueError, match="class_mix"):
        generate_trace(LoadSpec(n_requests=4,
                                class_mix=(("x", 0.0),)))


# -- stub capacity model ----------------------------------------------------


def test_stub_max_batch_capacity_model():
    """``max_batch`` bounds the stub's per-round decode slots: an
    over-cap batch costs one delay_s per chunk (finite replica
    throughput — what perf/pools_bench.py saturates), while tokens
    stay bit-exact and cap-independent."""
    import time as _time

    from triton_distributed_tpu.models.stub import (
        StubEngine,
        stub_generate,
    )

    reqs = [(STUB_PROMPTS[0], 5)] * 8
    gold = stub_generate(STUB_PROMPTS[0], 5)

    t0 = _time.perf_counter()
    outs = StubEngine(delay_s=0.15).run(reqs)
    one_round = _time.perf_counter() - t0
    assert all(list(o) == gold for o in outs)

    capped = StubEngine(delay_s=0.15, max_batch=2)
    t0 = _time.perf_counter()
    outs = capped.run(reqs)
    four_rounds = _time.perf_counter() - t0
    assert all(list(o) == gold for o in outs)
    # 8 requests / cap 2 = 4 rounds of wall floor vs 1 uncapped.
    assert four_rounds > 3 * 0.15 > one_round
    assert capped.run([]) == []

    with pytest.raises(ValueError, match="max_batch"):
        StubEngine(max_batch=-1)


# -- CLI guardrails ---------------------------------------------------------


def test_serving_cli_pool_flag_guardrails():
    """Both serving CLIs refuse the pool flags, by flag name and
    BEFORE loading anything, on every path that would silently ignore
    them (the PR 12 --tier-* convention)."""
    import os

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from perf import serve_demo
    from triton_distributed_tpu.serving import run_server

    common = [
        # One role without the other: nowhere to hand prefills.
        ["--model", "stub", "--prefill-replicas", "1"],
        ["--model", "stub", "--decode-replicas", "1"],
        # The pool flags size the fleet themselves.
        ["--model", "stub", "--prefill-replicas", "1",
         "--decode-replicas", "1", "--fleet", "2"],
        # In-process --replicas would drop the role tags.
        ["--model", "stub", "--prefill-replicas", "1",
         "--decode-replicas", "1", "--replicas", "2"],
        # --autoscale without a pool fleet has nothing to resize.
        ["--model", "stub", "--autoscale"],
    ]
    for main in (serve_demo.main, run_server.main):
        for flags in common:
            with pytest.raises(SystemExit) as ei:
                main(flags)
            assert ei.value.code == 2, flags  # argparse p.error
    # run_server only: an explicit non-pools policy ignores the roles.
    with pytest.raises(SystemExit) as ei:
        run_server.main(["--model", "stub", "--prefill-replicas", "1",
                         "--decode-replicas", "1",
                         "--policy", "round_robin"])
    assert ei.value.code == 2


# -- batched handoff export (tiny model) ------------------------------------


MODEL_PROMPTS = [
    np.arange(1, 20, dtype=np.int32),
    np.arange(30, 42, dtype=np.int32),
]
MODEL_GENS = [12, 10]


def _model_engine(model, **kw):
    from triton_distributed_tpu.models.continuous import ContinuousEngine

    kw.setdefault("max_batch", 2)
    kw.setdefault("page_size", 16)
    kw.setdefault("prefix_cache", True)
    return ContinuousEngine(model, **kw)


def test_batched_handoff_export_matches_serial(own_model, monkeypatch):
    """The handoff-batching satellite: one export_slots_batch gather
    over a sweep's slots produces snapshots IDENTICAL (modulo the
    export wall stamp) to per-slot serial exports, and the batched
    snapshots resume bit-exact."""
    from triton_distributed_tpu.models import slot_state
    from triton_distributed_tpu.models.continuous import Request

    work = list(zip(MODEL_PROMPTS, MODEL_GENS))
    golds = [r.tokens.tolist() for r in
             _model_engine(own_model).run(work, results=True)]
    calls = []
    orig = slot_state.export_slots_batch
    monkeypatch.setattr(
        slot_state, "export_slots_batch",
        lambda eng, slots, **kw: (calls.append(list(slots)),
                                  orig(eng, slots, **kw))[1])
    snaps = {}
    for batched in (True, False):
        eng = _model_engine(own_model, handoff_batch=batched)
        eng.request_handoff(after_rounds=3)
        res = eng.run(work, results=True)
        assert all(r.status == "migrated" for r in res), [
            (r.status, r.reason) for r in res
        ]
        assert eng.audit() == []
        snaps[batched] = [r.snapshot for r in res]
    assert len(calls) == 1 and len(calls[0]) == 2  # one sweep, 2 slots
    # Bit-identical wire payloads modulo the export wall stamp and the
    # engine-global trace counter (fresh per engine by design).
    for sb, ss in zip(snaps[True], snaps[False]):
        db, ds = dict(sb), dict(ss)
        for k in ("exported_at", "trace_id"):
            db.pop(k), ds.pop(k)
        assert db == ds
    # And the batched snapshots resume bit-exact.
    B = _model_engine(own_model)
    res2 = B.run([Request(p, g, snapshot=s)
                  for (p, g), s in zip(work, snaps[True])], results=True)
    for r, g in zip(res2, golds):
        assert r.status == "ok" and r.tokens.tolist() == g
    assert B.audit() == []


def test_handoff_sweep_degrades_to_serial_on_batch_failure(
        own_model, monkeypatch):
    """A failing batch gather must not fail the drain: the sweep
    degrades to per-slot serial exports and stays lossless."""
    from triton_distributed_tpu.models import slot_state
    from triton_distributed_tpu.models.continuous import Request

    monkeypatch.setattr(
        slot_state, "export_slots_batch",
        lambda *a, **kw: (_ for _ in ()).throw(RuntimeError("boom")))
    work = list(zip(MODEL_PROMPTS, MODEL_GENS))
    eng = _model_engine(own_model, handoff_batch=True)
    eng.request_handoff(after_rounds=3)
    res = eng.run(work, results=True)
    assert all(r.status == "migrated" for r in res)
    assert eng.audit() == []
    B = _model_engine(own_model)
    res2 = B.run([Request(p, g, snapshot=r.snapshot)
                  for (p, g), r in zip(work, res)], results=True)
    golds = [r.tokens.tolist() for r in
             _model_engine(own_model).run(work, results=True)]
    for r, g in zip(res2, golds):
        assert r.status == "ok" and r.tokens.tolist() == g


# -- chaos: live fleets -----------------------------------------------------


def _pool_specs(delay_s):
    from triton_distributed_tpu.serving.supervisor import stub_spec

    return [
        stub_spec("p0", delay_s=delay_s, page_size=4, num_pages=64,
                  role="prefill"),
        stub_spec("d0", delay_s=delay_s, page_size=4, num_pages=64,
                  role="decode"),
        stub_spec("d1", delay_s=delay_s, page_size=4, num_pages=64,
                  role="decode"),
    ]


@needs_procs
def test_pools_fleet_sigkill_prefill_mid_handoff(fresh_telemetry):
    """Chaos-under-elasticity: SIGKILL the prefill-pool replica while
    requests are mid prefill/handoff — the decode pool finishes every
    request bit-exact via snapshot reroute, survivors audit clean."""
    from triton_distributed_tpu.runtime.faults import FaultPlan
    from triton_distributed_tpu.serving.supervisor import FleetSupervisor

    sup = FleetSupervisor(
        _pool_specs(delay_s=1.2), policy="pools",
        heartbeat_s=0.05, heartbeat_timeout_s=2.0,
        respawn_backoff_s=0.2, spawn_timeout_s=120.0,
        snapshot_s=0.05,
    )
    try:
        router = sup.start()
        plan = FaultPlan(seed=11).kill_proc(replica="p0", after_s=0.4)
        with plan:
            res = router.run(
                list(zip(STUB_PROMPTS, STUB_GENS)), results=True
            )
        assert plan.fired and plan.fired[0][0] == "proc.kill"
        for r, g in zip(res, STUB_GOLDS):
            assert r.status == "ok", (r.status, r.reason)
            assert r.tokens.tolist() == g
        # The decode pool did the finishing: scored pool_decode hops
        # landed (post-handoff or post-reroute).
        assert router.stats["pool_decode"] >= 1
        assert router.audit() == []
    finally:
        sup.shutdown()


@needs_procs
def test_autoscaler_live_scale_up_and_lossless_scale_down(
        fresh_telemetry):
    """The live elasticity loop: a burst saturates the one-replica
    fleet and a tick scales UP through the supervisor's spawn path
    (the new child joins routing); with the pool calm but work still
    in flight, a tick scales DOWN via the lossless handoff drain —
    zero lost or duplicate tokens, audits clean, decisions visible as
    ``autoscale`` events."""
    from triton_distributed_tpu.serving.supervisor import (
        FleetSupervisor,
        stub_spec,
    )

    def spec(name, role="mixed"):
        return stub_spec(name, delay_s=2.0, page_size=4, num_pages=64,
                         role=role)

    sup = FleetSupervisor(
        [spec("m0")], heartbeat_s=0.05, heartbeat_timeout_s=10.0,
        respawn_backoff_s=0.2, spawn_timeout_s=120.0,
    )
    scaler = None
    try:
        router = sup.start()
        scaler = Autoscaler(
            sup, lambda role, name: spec(name, role),
            pool_bounds={"mixed": (1, 2)},
            cooldown_s=0.0, down_ticks=1,
            up_occupancy=0.6, down_occupancy=0.3,
            drain_grace_s=60.0,
        )
        # Phase 1 — burst: 6 long requests pile onto m0 (max_pending
        # 8 → occupancy 0.75 ≥ 0.6).
        burst = [(np.arange(10 * i + 1, 10 * i + 7, dtype=np.int32), 8)
                 for i in range(6)]
        out = {}

        def run_burst():
            out["burst"] = router.run(burst, results=True)

        th = threading.Thread(target=run_burst, daemon=True)
        th.start()
        deadline = time.monotonic() + 60
        while (time.monotonic() < deadline
               and router.replicas[0].pending < 5):
            time.sleep(0.01)
        assert router.replicas[0].pending >= 5
        d1 = scaler.tick()
        assert any(x["action"] == "scale_up" for x in d1), d1
        assert len(sup.stats()["slots"]) == 2
        assert len(router.replicas) == 2  # joined routing
        th.join(120)
        for (p, g), r in zip(burst, out["burst"]):
            assert r.status == "ok", (r.status, r.reason)
            assert r.tokens.tolist() == stub_generate(p, g)
        # Phase 2 — calm but mid-generation: two long requests spread
        # over the two replicas (occupancy 0.125 ≤ 0.3); the calm tick
        # drains the least-loaded replica losslessly while its slot is
        # still generating.
        def run_tail():
            out["tail"] = router.run(
                list(zip(STUB_PROMPTS, STUB_GENS)), results=True)

        th2 = threading.Thread(target=run_tail, daemon=True)
        th2.start()
        deadline = time.monotonic() + 60
        while (time.monotonic() < deadline
               and sum(r.pending for r in router.replicas) < 2):
            time.sleep(0.01)
        d2 = scaler.tick()
        downs = [x for x in d2 if x["action"] == "scale_down"]
        assert downs and downs[0]["drained"] is True, d2
        th2.join(120)
        for r, g in zip(out["tail"], STUB_GOLDS):
            assert r.status == "ok", (r.status, r.reason)
            assert r.tokens.tolist() == g
        # Zero duplicates fleet-wide: every token generated exactly
        # once (handoff-restored tokens count migrated_in, never
        # re-generated) — the lossless-drain ledger.
        agg = router.last_stats
        total = sum(g for _, g in burst) + sum(STUB_GENS)
        assert agg["generated_tokens"] == total
        assert len(sup.stats()["slots"]) == 1  # victim retired
        assert router.audit() == []
        evts, _ = obs_events.default_ring().tail(kind="autoscale")
        actions = {e.fields["action"] for e in evts}
        assert {"scale_up", "scale_down"} <= actions
        evts, _ = obs_events.default_ring().tail(kind="slot_retired")
        assert evts
        assert scaler.stats["scale_ups"] >= 1
        assert scaler.stats["scale_downs"] >= 1
    finally:
        if scaler is not None:
            scaler.stop()
        sup.shutdown()
