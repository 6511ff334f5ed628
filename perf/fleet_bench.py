"""Process-fleet failure/recovery bench: detection, recovery, respawn.

Measures the ISSUE-9 acceptance quantities on a real 3-replica LOCAL
process fleet (stub-engine children — models/stub.py — behind the
production wire server, router, and supervisor; the control plane,
wire protocol, ticket recovery, and supervisor machinery are all the
production code paths):

- **detection latency** — SIGKILL (the seeded ``proc.kill`` seam,
  fired the instant a batch hits the wire) → the router marking the
  replica dead (``replica_dead`` event);
- **recovery latency** — kill → the first re-routed ticket completing
  on a survivor;
- **in-flight recovery rate** — re-routed tickets finishing ``ok``
  and BIT-EXACT vs the stub's pure generator, over all tickets
  orphaned by the kill (target 100%: the ticket-id wire dedup makes
  the at-least-once overlap safe);
- **respawn → rejoin** — the supervisor's ``replica_respawn`` event →
  the reborn replica completing a routed request.

A second arm re-runs perf/router_bench.py's shared-prefix workload
(3 groups × 4 arrivals) over REMOTE replicas to confirm the affinity
result survives the process boundary: the fleet radix hit rate must
match ROUTER.json's in-process 0.75 (the radix tree and digest
protocol are the production classes in the children; only the model
is stubbed, and hit rate is a pure control-plane quantity).

Output follows the perf/ convention: one JSON object with a
``provenance`` block, printed to stdout and written to
``perf/FLEET.json``.

Usage:  JAX_PLATFORMS=cpu python perf/fleet_bench.py
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("TDT_AUTOTUNE_CACHE", "0")

import numpy as np  # noqa: E402

REPLICAS = 3
BATCH_DELAY_S = 0.3
PAGE_SIZE = 16

# Affinity arm: the exact perf/router_bench.py workload shape.
GROUPS = 3
ARRIVALS_PER_GROUP = 4
SYSTEM_PROMPT_TOKENS = 64
USER_SUFFIX_TOKENS = 16


def build_prompts() -> list[np.ndarray]:
    rng = np.random.default_rng(0)
    systems = [
        rng.integers(1, 200, size=SYSTEM_PROMPT_TOKENS).astype(np.int32)
        for _ in range(GROUPS)
    ]
    prompts = []
    for _ in range(ARRIVALS_PER_GROUP):
        for g in range(GROUPS):
            prompts.append(np.concatenate([
                systems[g],
                rng.integers(1, 200, size=USER_SUFFIX_TOKENS).astype(
                    np.int32
                ),
            ]))
    return prompts


def failure_arm() -> dict:
    from triton_distributed_tpu.models.stub import stub_generate
    from triton_distributed_tpu.obs import events as obs_events
    from triton_distributed_tpu.runtime.faults import FaultPlan
    from triton_distributed_tpu.serving.replica import Ticket
    from triton_distributed_tpu.serving.supervisor import (
        FleetSupervisor,
        stub_spec,
    )

    obs_events.default_ring().clear()
    sup = FleetSupervisor(
        [stub_spec(f"r{i}", delay_s=BATCH_DELAY_S, page_size=PAGE_SIZE)
         for i in range(REPLICAS)],
        heartbeat_s=0.1, heartbeat_timeout_s=2.0,
        respawn_backoff_s=0.2, spawn_timeout_s=180.0,
    )
    t_up0 = time.monotonic()
    router = sup.start()
    spawn_s = time.monotonic() - t_up0
    rng = np.random.default_rng(1)
    prompts = [
        rng.integers(1, 200, size=24).astype(np.int32) for _ in range(9)
    ]
    gens = [6] * len(prompts)
    golds = [stub_generate(p, g) for p, g in zip(prompts, gens)]
    try:
        # Warm wave: every replica serves, digests publish.
        res = router.run(list(zip(prompts[:3], gens[:3])), results=True)
        assert all(r.status == "ok" for r in res)

        # Kill wave: dispatch tickets individually (round-robin lands
        # work on every replica), arm the seam, record per-ticket
        # completion stamps from waiter threads.
        plan = FaultPlan(seed=7).kill_proc(replica="r0")
        tickets = [Ticket.of((p, g)) for p, g in zip(prompts, gens)]
        done_at = [0.0] * len(tickets)

        def waiter(i: int) -> None:
            tickets[i].wait()
            done_at[i] = time.monotonic()

        threads = [
            threading.Thread(target=waiter, args=(i,), daemon=True)
            for i in range(len(tickets))
        ]
        with plan:
            for th in threads:
                th.start()
            for t in tickets:
                router._dispatch(t)
            for th in threads:
                th.join(timeout=120)
        assert plan.fired, "kill seam never fired"

        # Event-ring stamps share the monotonic clock with done_at.
        evts, _ = obs_events.default_ring().tail(0)
        t_kill = next(
            e.t for e in evts
            if e.kind == "fault" and e.fields.get("seam") == "proc.kill"
        )
        t_dead = next(
            e.t for e in evts if e.kind == "replica_dead"
        )
        rerouted = [
            (t, at, gold) for t, at, gold in zip(tickets, done_at, golds)
            if t.reroutes > 0
        ]
        ok_exact = [
            t for t, _, gold in rerouted
            if t.result.status == "ok"
            and t.result.tokens.tolist() == gold
        ]
        recovery_s = (
            min(at for _, at, _ in rerouted) - t_kill if rerouted
            else None
        )
        # Every non-rerouted ticket must be bit-exact too (survivors).
        survivors_exact = all(
            t.result.status == "ok" and t.result.tokens.tolist() == gold
            for t, at, gold in zip(tickets, done_at, golds)
            if t.reroutes == 0
        )

        # Respawn → rejoin: wait for the slot to come back, then force
        # routing onto the reborn replica and time its first serve.
        assert sup.wait_healthy(REPLICAS, timeout_s=120)
        t_respawn = next(
            e.t for e in obs_events.default_ring().tail(0)[0]
            if e.kind == "replica_respawn"
        )
        reborn = router.replica("r0#1")
        # Survivor audits BEFORE draining them (a drained child exits;
        # there is nothing left to connect to afterwards).
        audit = router.audit()
        for name in ("r1", "r2"):
            router.drain_replica(name, grace_s=30)
        res = router.run([(prompts[0], gens[0])], results=True)
        t_rejoin_serve = time.monotonic()
        assert res[0].status == "ok"
        assert res[0].tokens.tolist() == golds[0]
        assert reborn.served >= 1
        return {
            "replicas": REPLICAS,
            "batch_delay_s": BATCH_DELAY_S,
            "fleet_spawn_s": round(spawn_s, 3),
            "inflight_tickets_at_kill": len(tickets),
            "rerouted_tickets": len(rerouted),
            "rerouted_recovered_ok_bit_exact": len(ok_exact),
            "inflight_recovery_rate": (
                round(len(ok_exact) / len(rerouted), 4) if rerouted
                else None
            ),
            "survivors_bit_exact": bool(survivors_exact),
            "detection_s": round(t_dead - t_kill, 4),
            "recovery_s": round(recovery_s, 4),
            "respawn_to_rejoin_s": round(t_rejoin_serve - t_respawn, 4),
            "kill_to_rejoin_s": round(t_rejoin_serve - t_kill, 4),
            "router": {
                k: v for k, v in router.last_stats["router"].items()
                if isinstance(v, (int, float, str))
            },
            "supervisor": sup.stats()["slots"],
            "survivor_audit_problems": audit,
        }
    finally:
        sup.shutdown()


def affinity_arm(policy: str) -> dict:
    from triton_distributed_tpu.serving.router import Router
    from triton_distributed_tpu.serving.supervisor import (
        spawn_replica,
        stub_spec,
    )

    reps = {}

    def boot(i):
        reps[i] = spawn_replica(
            stub_spec(f"r{i}", delay_s=0.0, page_size=PAGE_SIZE,
                      num_pages=256),
            spawn_timeout_s=180.0,
        )

    threads = [threading.Thread(target=boot, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    router = Router([reps[0], reps[1]], policy=policy)
    prompts = build_prompts()
    try:
        ttfts = []
        for p in prompts:
            t0 = time.perf_counter()
            res = router.run([(p, 1)], results=True)
            ttfts.append(time.perf_counter() - t0)
            assert res[0].status == "ok", res[0]
        # Children report their cumulative tree stats in every batch
        # response; after the last arrival the mirrors are current.
        lookups = hits = hit_tokens = 0
        for r in router.replicas:
            st = r.engine.last_stats.get("prefix_cache", {})
            lookups += st.get("lookups", 0)
            hits += st.get("hits", 0)
            hit_tokens += st.get("hit_tokens", 0)
        rstats = router.last_stats["router"]
        return {
            "policy": policy,
            "radix_hit_rate": round(hits / max(lookups, 1), 4),
            "radix_hit_tokens": int(hit_tokens),
            "prefill_tokens_computed": int(
                router.last_stats["prefill_tokens"]
            ),
            "ttft_s_mean": round(float(np.mean(ttfts)), 4),
            "per_replica_served": [r.served for r in router.replicas],
            "router": {
                k: rstats[k]
                for k in ("routed", "affinity_hits",
                          "affinity_hit_tokens", "least_loaded",
                          "round_robin", "reroutes")
            },
        }
    finally:
        router.shutdown()
        for r in reps.values():
            if r.proc is not None and r.proc.poll() is None:
                r.proc.kill()
                r.proc.wait(timeout=10)


def main() -> int:
    t0 = time.time()
    failure = failure_arm()
    aff = affinity_arm("affinity")
    rr = affinity_arm("round_robin")
    in_process_rate = None
    router_json = os.path.join(os.path.dirname(__file__), "ROUTER.json")
    if os.path.exists(router_json):
        with open(router_json) as f:
            in_process_rate = (
                json.load(f).get("affinity", {}).get("radix_hit_rate")
            )
    out = {
        "metric": "process_fleet_failure_recovery",
        "platform": "cpu",
        "failure": failure,
        "affinity": aff,
        "round_robin": rr,
        "affinity_matches_in_process": (
            in_process_rate is not None
            and abs(aff["radix_hit_rate"] - in_process_rate) <= 0.02
        ),
        "in_process_affinity_hit_rate": in_process_rate,
        "bench_wall_s": round(time.time() - t0, 1),
        "provenance": {
            "harness": (
                "perf/fleet_bench.py — 3 stub-engine replica processes "
                "(run_server --model stub; real radix control plane + "
                "wire server) under FleetSupervisor; SIGKILL via the "
                "seeded proc.kill seam mid-batch; stamps from the "
                "shared-monotonic event ring (fault/replica_dead/"
                "replica_respawn) and per-ticket waiter threads; "
                "affinity arms replay perf/router_bench.py's workload "
                "over 2 remote replicas"
            ),
            "caveat": (
                "wall-clock latencies include the stub's synthetic "
                f"{BATCH_DELAY_S}s batch floor (detection waits for "
                "the in-flight batch's socket to die, exactly as with "
                "a real model — subtract the floor for the pure "
                "supervision overhead); radix hit rates are "
                "control-plane-exact and platform-independent"
            ),
        },
    }
    text = json.dumps(out, indent=1)
    print(text)
    path = os.path.join(os.path.dirname(__file__), "FLEET.json")
    with open(path, "w") as f:
        f.write(text + "\n")
    print(f"\nwrote {path}", file=sys.stderr)
    ok = (
        failure["inflight_recovery_rate"] == 1.0
        and failure["survivors_bit_exact"]
        and not failure["survivor_audit_problems"]
        and out["affinity_matches_in_process"]
    )
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
