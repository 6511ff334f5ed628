"""Process-fleet supervision tests (docs/scale-out.md "Process
fleet"): wire-protocol replicas, heartbeats, crash respawn, and
bit-exact in-flight recovery.

Layers of evidence:

- pure ticket-latch races and retry-backoff math — milliseconds, no
  processes;
- a single stub-replica child behind ``RemoteReplica``: wire round
  trip bit-exact vs the stub's pure generator, affinity digest over
  the wire, remote audit, structured no-survivor failure on a dropped
  wire;
- the chaos layer (ISSUE-9 acceptance): a replica process SIGKILLed
  MID-BATCH through the seeded ``proc.kill`` seam has every in-flight
  ticket re-routed and finished bit-exact, survivors audit clean, and
  the supervisor respawns the slot — which then serves a routed
  request under a fresh prefix digest. SIGSTOP drives both the
  heartbeat-wedge classification and the true multi-process latch
  race (two completions for one ticket id; the late one discards).

Every process test spawns ``run_server --model stub`` children
(models/stub.py: real radix control plane, hash "model", no model
load) and synchronizes on conditions with deadlines — never on bare
sleeps. The whole file skips where child processes cannot be spawned.
"""

import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from triton_distributed_tpu.models.continuous import RequestResult
from triton_distributed_tpu.models.stub import StubEngine, stub_generate
from triton_distributed_tpu.runtime.faults import FaultPlan
from triton_distributed_tpu.serving.replica import Ticket


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

PROMPTS = [
    np.arange(1, 9, dtype=np.int32),
    np.arange(20, 30, dtype=np.int32),
    np.arange(40, 46, dtype=np.int32),
]
GENS = [5, 4, 3]
GOLDS = [stub_generate(p, g) for p, g in zip(PROMPTS, GENS)]


def _stub_specs(n, delay_s=0.4):
    from triton_distributed_tpu.serving.supervisor import stub_spec

    return [
        stub_spec(f"r{i}", delay_s=delay_s, page_size=4, num_pages=64)
        for i in range(n)
    ]


def _spawn_fleet(n, delay_s=0.4, spawn_timeout_s=120.0):
    """N unmanaged RemoteReplicas (no supervisor), spawned in
    parallel; returns the replica list."""
    from triton_distributed_tpu.serving.supervisor import spawn_replica

    out = {}

    def boot(i, spec):
        out[i] = spawn_replica(spec, spawn_timeout_s=spawn_timeout_s)

    threads = [
        threading.Thread(target=boot, args=(i, s), daemon=True)
        for i, s in enumerate(_stub_specs(n, delay_s))
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=150)  # past boot's own spawn_timeout_s
    assert len(out) == n, f"only {len(out)}/{n} replicas spawned"
    return [out[i] for i in range(n)]


def _reap(replicas):
    for r in replicas:
        proc = getattr(r, "proc", None)
        if proc is not None and proc.poll() is None:
            proc.kill()
            proc.wait(timeout=10)


# -- pure: ticket latch races and backoff math ---------------------------


def test_ticket_latch_first_and_claim_races():
    """The at-least-once contract in miniature: exactly one completion
    latches per ticket id, and the per-hop reroute claim can neither
    double-dispatch nor strand a ticket."""
    t = Ticket(PROMPTS[0], 4)
    assert t.tid and t.tid != Ticket(PROMPTS[0], 4).tid  # unique ids
    r1 = RequestResult(np.asarray([1, 2], np.int32))
    r2 = RequestResult(np.asarray([9, 9], np.int32), "failed", "late")
    assert t.complete(r1) is True
    # Second completion for the SAME ticket id (the dead replica
    # actually finished): discarded, first result untouched.
    assert t.complete(r2) is False
    assert t.result is r1
    # A latched ticket can never be claimed for re-dispatch.
    assert t.claim_reroute("r0") is False

    # Per-hop claim: the death callback and the timeout path race to
    # re-route the same hop; exactly one wins.
    t2 = Ticket(PROMPTS[0], 4)
    t2.replica_history.append("r0")
    assert t2.claim_reroute("r0") is True
    assert t2.claim_reroute("r0") is False  # same hop, second claimant
    assert t2.reroutes == 1
    # Re-dispatched to r1: a LATE claim against the old hop loses...
    t2.replica_history.append("r1")
    assert t2.claim_reroute("r0") is False
    # ...but r1's own failure can still claim its hop (no strand).
    assert t2.claim_reroute("r1") is True
    assert t2.reroutes == 2


def test_retry_backoff_cap_and_jitter():
    """ISSUE-9 satellite: the client retry delay is capped at
    ``max_backoff_s`` and jittered ±20%, so a respawning fleet never
    sees a synchronized retry storm."""
    from triton_distributed_tpu.serving.server import _retry_backoff

    for attempt in range(12):
        d = _retry_backoff(attempt, 0.25, 1.0)
        base = min(0.25 * (2 ** attempt), 1.0)
        assert 0.8 * base <= d <= 1.2 * base
        assert d <= 1.2  # the cap holds however far attempts run
    # Deep attempts land in the capped jitter band, not at one point.
    deep = {round(_retry_backoff(20, 0.25, 1.0), 6) for _ in range(32)}
    assert all(0.8 <= d <= 1.2 for d in deep)
    assert len(deep) > 1  # jitter actually jitters


def test_request_retries_against_fake_shedding_server(monkeypatch):
    """The cap through the real retry loop: a fake server that always
    sheds (no retry_after_s hint) drives ``request(retries=3)``
    through capped, jittered local backoff; the recorded sleeps never
    exceed 1.2 × max_backoff_s."""
    import json
    import socket as socket_mod

    from triton_distributed_tpu.serving import server as server_mod

    srv = socket_mod.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(8)
    host, port = srv.getsockname()
    stop = threading.Event()

    def shed_forever():
        srv.settimeout(0.2)
        while not stop.is_set():
            try:
                conn, _ = srv.accept()
            except socket_mod.timeout:
                continue
            with conn, conn.makefile("rwb") as f:
                if f.readline():
                    f.write(json.dumps(
                        {"error": {"status": "overloaded",
                                   "reason": "always shedding"}}
                    ).encode() + b"\n")
                    f.flush()

    th = threading.Thread(target=shed_forever, daemon=True)
    th.start()
    slept = []

    class _TimeShim:
        """server_mod-local stand-in: recording sleep, real clocks —
        patching the module ATTRIBUTE keeps the global time module
        untouched for every other thread."""

        sleep = staticmethod(lambda s: slept.append(s))
        monotonic = staticmethod(time.monotonic)

    monkeypatch.setattr(server_mod, "time", _TimeShim)
    try:
        with pytest.raises(RuntimeError, match="overloaded"):
            server_mod.request(
                host, port, {"cmd": "nope"}, timeout=10,
                retries=3, backoff_s=0.5, max_backoff_s=0.6,
            )
    finally:
        stop.set()
        th.join(timeout=5)
        srv.close()
    assert len(slept) == 3  # one backoff per retry
    assert all(s <= 0.6 * 1.2 + 1e-9 for s in slept)
    # attempts 1+ would be 1.0/2.0 uncapped — the cap actually bit.
    assert all(s >= 0.4 * 0.8 for s in slept)


def test_wire_fault_menu_units():
    """The new FaultPlan conveniences arm the seams they claim."""
    from triton_distributed_tpu.runtime.faults import mutate_point

    with FaultPlan(seed=1).garble_wire("recv", replica="rX"):
        # Probe traffic is NOT matched by default — a supervisor
        # heartbeat must never race a batch-targeted rule for the hit.
        probe = mutate_point("wire.recv", b'{"ok": true}\n',
                             replica="rX", what="probe")
        assert probe == b'{"ok": true}\n'
        out = mutate_point("wire.recv", b'{"ok": true}\n',
                           replica="rX", what="batch")
        assert out == bytes(reversed(b'{"ok": true}\n'))
    with FaultPlan(seed=1).drop_wire("send", replica="rX"):
        with pytest.raises(ConnectionResetError):
            mutate_point("wire.send", b"payload", replica="rX",
                         what="batch")
    # A kill rule against a replica with no pid yet is a no-op.
    with FaultPlan(seed=1).kill_proc(replica="rX"):
        assert mutate_point("proc.kill", None, replica="rX") is None
    with pytest.raises(ValueError, match="side"):
        FaultPlan().drop_wire("sideways")


# -- one child: wire round trip, affinity, audit, no-survivor -----------


@needs_procs
def test_remote_replica_roundtrip_and_no_survivor(fresh_telemetry):
    """One stub child behind RemoteReplica + Router: outputs bit-exact
    vs the pure generator, the digest piggyback feeds affinity, the
    audit verb answers over the wire, the front ModelServer composes —
    and a dropped wire with no survivors fails structured, never
    hangs."""
    from triton_distributed_tpu.serving import ModelServer, request
    from triton_distributed_tpu.serving.router import Router

    reps = _spawn_fleet(1, delay_s=0.0)
    router = Router(reps)
    try:
        res = router.run(list(zip(PROMPTS, GENS)), results=True)
        for r, gold in zip(res, GOLDS):
            assert r.status == "ok", (r.status, r.reason)
            assert r.tokens.tolist() == gold
        # Digest piggyback: the replica's published mirror now scores
        # the same prompt as cached (affinity over the wire).
        assert reps[0].match_len(PROMPTS[0]) > 0
        res = router.run([(PROMPTS[0], GENS[0])], results=True)
        assert res[0].tokens.tolist() == GOLDS[0]
        assert router.last_stats["router"]["affinity_hits"] >= 1
        # Fleet totals aggregate the child's stats over the wire.
        assert router.last_stats["generated_tokens"] == sum(GENS) + GENS[0]
        # Remote audit: the child's pool/radix invariants, via the verb.
        assert router.audit() == []
        # healthz: cheap liveness with drain-vs-death state.
        assert reps[0].healthz() == {"ok": True, "state": "serving"}

        # Front server over the remote fleet: the full double-wire path.
        front = ModelServer(router).start()
        try:
            resp = request(
                front.host, front.port,
                {"requests": [PROMPTS[1].tolist()], "gen_lens": [GENS[1]]},
            )
            assert resp["outputs"][0] == GOLDS[1]
            assert resp["stats"]["router"]["routed"] >= 5
        finally:
            front._shutdown.set()

        # Wire drop with NO survivors: structured failure, no hang.
        with FaultPlan(seed=5).drop_wire(
            "recv", replica="r0", times=99
        ) as plan:
            res = router.run([(PROMPTS[2], 2)], results=True)
        assert plan.fired
        assert res[0].status == "failed"
        assert "routing failed" in res[0].reason
        assert reps[0].state == "dead"
        assert "wire failure" in reps[0].last_error
    finally:
        router.shutdown()
        _reap(reps)


# -- chaos: SIGKILL mid-batch, respawn, rejoin ---------------------------


@needs_procs
def test_fleet_sigkill_mid_batch_recovers_and_respawns(fresh_telemetry):
    """ISSUE-9 acceptance: a replica process SIGKILLed mid-batch (the
    seeded ``proc.kill`` seam fires the instant its batch is on the
    wire) yields bit-exact survivor outputs and clean survivor audits;
    the supervisor classifies the crash, respawns the slot with a
    fresh name and digest, and the respawned replica serves a routed
    request."""
    from triton_distributed_tpu.obs import events as obs_events
    from triton_distributed_tpu.serving.supervisor import FleetSupervisor

    sup = FleetSupervisor(
        _stub_specs(2, delay_s=0.4),
        heartbeat_s=0.1, heartbeat_timeout_s=2.0,
        respawn_backoff_s=0.2, spawn_timeout_s=120.0,
    )
    try:
        router = sup.start()
        plan = FaultPlan(seed=7).kill_proc(replica="r0")
        with plan:
            res = router.run(list(zip(PROMPTS, GENS)), results=True)
        assert plan.fired and plan.fired[0][0] == "proc.kill"
        # 100% of in-flight requests recovered, bit-exact (the
        # ticket-id dedup makes the at-least-once overlap safe).
        for r, gold in zip(res, GOLDS):
            assert r.status == "ok", (r.status, r.reason)
            assert r.tokens.tolist() == gold
        st = router.last_stats["router"]
        assert st["reroutes"] >= 1
        assert router.replica("r1").state == "healthy"
        # Survivors audit clean over the wire.
        assert router.audit() == []

        # The supervisor respawns the slot; the new replica joins
        # under a fresh generation name with a FRESH (empty) digest.
        assert sup.wait_healthy(2, timeout_s=60)
        names = [r.name for r in router.replicas]
        assert "r0#1" in names and "r1" in names
        reborn = router.replica("r0#1")
        assert reborn.match_len(PROMPTS[0]) == 0  # fresh digest
        assert router.last_stats["router"]["retired_replicas"] == 1

        # The respawned replica serves a routed request: drain the
        # survivor so routing MUST land on the newcomer.
        assert router.drain_replica("r1", grace_s=30)
        res = router.run([(PROMPTS[0], GENS[0])], results=True)
        assert res[0].status == "ok"
        assert res[0].tokens.tolist() == GOLDS[0]
        assert reborn.served >= 1

        kinds = [e.kind for e in obs_events.default_ring().tail(0)[0]]
        for k in ("fault", "replica_dead", "reroute",
                  "replica_proc_failed", "replica_respawn"):
            assert k in kinds, f"missing {k} in {set(kinds)}"
        ledger = sup.stats()["slots"][0]
        assert ledger["generation"] == 1 and ledger["respawns"] == 1
        from triton_distributed_tpu.obs import metrics as obs_metrics

        snap = obs_metrics.default_registry().snapshot()
        fails = snap["tdt_supervisor_failures_total"]["series"]
        assert any(
            s["labels"]["replica"] == "r0" and s["value"] >= 1
            for s in fails
        )
        spawns = snap["tdt_supervisor_respawns_total"]["series"]
        assert [s["value"] for s in spawns
                if s["labels"]["replica"] == "r0"] == [1]
    finally:
        sup.shutdown()


@needs_procs
def test_fleet_hang_latch_race_two_completions(fresh_telemetry):
    """ISSUE-9 satellite: the true multi-process latch race. A child
    SIGSTOPped mid-batch trips the router's request timeout; the
    ticket re-routes and completes on the survivor. SIGCONT then lets
    the wedged child finish and push a SECOND completion for the same
    ticket id up the still-open connection — it latch-loses, the
    result is unchanged, and the duplicate batch never enters fleet
    accounting."""
    from triton_distributed_tpu.serving.router import Router

    reps = _spawn_fleet(2, delay_s=0.3)
    r0, r1 = reps
    router = Router(reps, request_timeout_s=1.5)
    try:
        plan = FaultPlan(seed=3).hang_proc(replica="r0")
        with plan:
            res = router.run([(PROMPTS[0], GENS[0])], results=True)
            assert plan.fired
            assert res[0].status == "ok"
            assert res[0].tokens.tolist() == GOLDS[0]
            assert r0.state == "dead" and "timeout" in r0.last_error
            assert router.stats["reroutes"] >= 1
            first = res[0]
            # Wake the wedged child: its late response arrives on the
            # worker's still-open socket and must be discarded by id.
            os.kill(r0.pid, signal.SIGCONT)
            r0.join(timeout=60)
        assert res[0] is first  # the latch never moved
        assert res[0].tokens.tolist() == GOLDS[0]
        # The duplicate batch stayed out of the dead replica's ledger.
        assert r0.served == 0 and r0.runs == 0
        assert r0.totals["generated_tokens"] == 0
        assert router.audit() == []  # survivor clean; dead skipped
    finally:
        router.shutdown()
        _reap(reps)


@needs_procs
def test_supervisor_heartbeat_wedge_classified(fresh_telemetry):
    """A wedged-but-alive process (SIGSTOP, no batch in flight) is
    detectable ONLY by the heartbeat deadline: the supervisor
    classifies ``heartbeat_timeout``, SIGKILLs the zombie, and
    respawns the slot."""
    from triton_distributed_tpu.serving.supervisor import FleetSupervisor

    sup = FleetSupervisor(
        _stub_specs(2, delay_s=0.0),
        heartbeat_s=0.1, heartbeat_timeout_s=1.0, heartbeat_misses=2,
        respawn_backoff_s=0.2, spawn_timeout_s=120.0,
    )
    try:
        router = sup.start()
        # Let the first beats land so the wedge is a state CHANGE.
        assert sup.wait_for(
            lambda: sup.slot("r0").last_beat_t is not None, 30
        )
        os.kill(router.replica("r0").pid, signal.SIGSTOP)
        assert sup.wait_for(
            lambda: (sup.slot("r0").last_failure or "").startswith(
                "heartbeat_timeout"
            ),
            timeout_s=30,
        ), sup.stats()
        # The zombie was killed and the slot respawned.
        assert sup.wait_healthy(2, timeout_s=60)
        res = router.run([(PROMPTS[0], 2)], results=True)
        assert res[0].status == "ok"
        assert res[0].tokens.tolist() == stub_generate(PROMPTS[0], 2)
    finally:
        sup.shutdown()


@needs_procs
def test_crash_loop_circuit_breaker_parks(fresh_telemetry):
    """A slot that can never come up (its child exits before binding)
    burns its crash budget and is PARKED — event + counter fire and
    the fleet keeps serving degraded on the survivor instead of
    spinning on doomed spawns."""
    from triton_distributed_tpu.obs import events as obs_events
    from triton_distributed_tpu.serving.supervisor import (
        FleetSupervisor,
        ReplicaSpec,
    )

    bad = ReplicaSpec("bad", [sys.executable, "-c", "pass"])
    sup = FleetSupervisor(
        _stub_specs(1, delay_s=0.0) + [bad],
        heartbeat_s=0.05, spawn_timeout_s=15.0,
        respawn_backoff_s=0.1, max_backoff_s=0.2,
        crash_limit=2, crash_window_s=60.0,
    )
    try:
        router = sup.start()
        assert [r.name for r in router.replicas] == ["r0"]
        assert sup.wait_for(lambda: sup.slot("bad").parked, 60), \
            sup.stats()
        assert sup.slot("bad").last_failure.startswith("spawn")
        # Degraded but serving.
        res = router.run([(PROMPTS[0], 2)], results=True)
        assert res[0].status == "ok"
        kinds = [e.kind for e in obs_events.default_ring().tail(0)[0]]
        assert "replica_parked" in kinds
        from triton_distributed_tpu.obs import metrics as obs_metrics

        snap = obs_metrics.default_registry().snapshot()
        parked = snap["tdt_supervisor_parked_replicas"]["series"]
        assert parked == [{"labels": {}, "value": 1}]
    finally:
        sup.shutdown()
