"""Live slot migration tests (docs/scale-out.md "Slot migration &
handoff"): portable in-flight request state, lossless drain handoff,
and snapshot-based crash recovery.

Layers of evidence:

- pure wire-codec and prefix-delta math — milliseconds, no model;
- engine-level bit-exactness on the tiny model (the ISSUE-10
  acceptance core): a request exported mid-generation and imported
  into a SECOND engine produces remaining tokens bit-identical to the
  un-migrated run — bf16 and int8 pools, greedy and seeded sampling,
  with and without a shared radix prefix on the target — pool/radix
  audits clean on both engines (the conftest autouse fixture re-audits
  every live engine after every test);
- kill-mid-migration seams on both ends: a failed export keeps the
  slot decoding locally (handoff stays lossless), a failed import
  falls back to replay-from-prompt (same tokens, counted fallback);
- the serving tier on the deterministic stub: ``handoff=True`` drain
  completes every in-flight request with zero duplicate emissions
  (latch-first tickets), ``migrate_after_prefill`` runs prefill and
  decode on different replicas;
- the chaos layer (needs_procs): a replica process SIGKILLed
  MID-GENERATION with supervisor snapshots enabled resumes victims
  from the last snapshot (tokens-saved counter on the survivor —
  measurably less re-generation than PR 9's replay), a SIGKILL of the
  MIGRATION TARGET re-routes again and still lands bit-exact, and a
  handoff drain over the wire loses nothing.
"""

import dataclasses
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from triton_distributed_tpu.models.stub import StubEngine, stub_generate
from triton_distributed_tpu.runtime.faults import FaultPlan


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
    np.arange(1, 20, dtype=np.int32),
    np.arange(30, 42, dtype=np.int32),
]
GENS = [8, 7]


def make_engine(model, **kw):
    from triton_distributed_tpu.models.continuous import ContinuousEngine

    kw.setdefault("max_batch", 2)
    kw.setdefault("page_size", 16)
    kw.setdefault("prefix_cache", True)
    return ContinuousEngine(model, **kw)


def migrate_run(model, eng_kw, *, after_rounds=4, delta_digest=None,
                reqs=None):
    """Export every request after ``after_rounds`` scheduling rounds on
    engine A, import into a fresh engine B, return (final results,
    stage-1 results, engine B)."""
    from triton_distributed_tpu.models import slot_state
    from triton_distributed_tpu.models.continuous import Request

    A = make_engine(model, **eng_kw)
    A.request_handoff(after_rounds=after_rounds)
    work = reqs or list(zip(PROMPTS, GENS))
    res1 = A.run(work, results=True)
    assert all(r.status == "migrated" for r in res1), [
        (r.status, r.reason) for r in res1
    ]
    assert A.audit() == []
    B = make_engine(model, **eng_kw)
    resume = []
    for (p, g), r in zip(work, res1):
        snap = r.snapshot
        if delta_digest is not None:
            full = slot_state.SlotSnapshot.from_wire(snap)
            thin = slot_state.prefix_delta(full, delta_digest)
            assert thin.from_prefix_pages > full.from_prefix_pages
            assert thin.payload_bytes() < full.payload_bytes()
            snap = thin.to_wire()
        resume.append(Request(p, g, snapshot=snap))
    res2 = B.run(resume, results=True)
    assert B.audit() == []
    return res2, res1, B


# -- pure: wire codec + delta math ----------------------------------------


def test_snapshot_wire_roundtrip_and_validation():
    from triton_distributed_tpu.models.slot_state import (
        SlotSnapshot,
        SnapshotError,
    )

    snap = SlotSnapshot(
        prompt=np.arange(5, dtype=np.int32), out=[7, 8], gen_len=6,
        kv_len=6, page_size=4, kv_dtype="int8",
        k_pages=np.ones((2, 2, 1, 4, 8), np.int8),
        v_pages=np.full((2, 2, 1, 4, 8), 3, np.int8),
        k_scale=np.ones((2, 2, 1), np.float32) * 0.5,
        v_scale=np.ones((2, 2, 1), np.float32),
        key_data=np.asarray([1, 2], np.uint32), key_step=9,
        spec={"k": 3, "proposed": 10, "accepted": 4},
        trace_id="req-x", exported_at=123.5,
    )
    back = SlotSnapshot.from_wire(snap.to_wire())
    np.testing.assert_array_equal(back.prompt, snap.prompt)
    assert back.out == snap.out and back.kv_len == snap.kv_len
    np.testing.assert_array_equal(back.k_pages, snap.k_pages)
    np.testing.assert_array_equal(back.v_scale, snap.v_scale)
    np.testing.assert_array_equal(back.key_data, snap.key_data)
    assert back.key_step == 9 and back.spec["k"] == 3
    assert back.trace_id == "req-x" and back.exported_at == 123.5
    assert back.payload_bytes() == snap.payload_bytes()
    # bf16 pages survive the codec byte-exactly.
    import ml_dtypes

    bf = np.arange(2 * 1 * 1 * 4 * 8, dtype=np.float32).reshape(
        2, 1, 1, 4, 8).astype(ml_dtypes.bfloat16)
    snap2 = dataclasses.replace(
        snap, kv_dtype=None, k_pages=bf, v_pages=bf, k_scale=None,
        v_scale=None,
    )
    back2 = SlotSnapshot.from_wire(snap2.to_wire())
    assert back2.k_pages.dtype == bf.dtype
    np.testing.assert_array_equal(
        back2.k_pages.view(np.uint16), bf.view(np.uint16)
    )
    # Malformed payloads raise SnapshotError (the fallback trigger),
    # never a bare KeyError/ValueError.
    with pytest.raises(SnapshotError):
        SlotSnapshot.from_wire({"prompt": [1]})
    bad = snap.to_wire()
    bad["k_pages"]["b64"] = "!!!not-base64!!!"
    with pytest.raises(SnapshotError):
        SlotSnapshot.from_wire(bad).k_pages  # decode is eager


def test_prefix_delta_math():
    from triton_distributed_tpu.models.slot_state import (
        SlotSnapshot,
        prefix_delta,
    )

    prompt = np.arange(10, dtype=np.int32)
    snap = SlotSnapshot(
        prompt=prompt, out=[50, 51, 52], gen_len=8, kv_len=12,
        page_size=4, kv_dtype=None,
        k_pages=np.zeros((1, 3, 1, 4, 2), np.float32),
        v_pages=np.zeros((1, 3, 1, 4, 2), np.float32),
    )
    assert snap.valid_pages == 3
    assert snap.chain == list(range(10)) + [50, 51]
    # A digest covering the first 8 chain tokens == 2 full pages.
    digest = [[snap.chain[:4], [[snap.chain[4:8], []]]]]
    thin = prefix_delta(snap, digest)
    assert thin.from_prefix_pages == 2
    assert thin.k_pages.shape[1] == 1
    # No coverage → unchanged object.
    assert prefix_delta(snap, []) is snap


# -- engine level: bit-exact migration (the acceptance core) --------------


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_migration_bit_exact_greedy(own_model, kv_dtype):
    """Exported mid-generation → imported into a second engine →
    remaining greedy tokens bit-identical to the un-migrated run, on
    both pool dtypes; audits clean on both engines."""
    kw = {"kv_dtype": kv_dtype}
    gold = [
        r.tokens.tolist()
        for r in make_engine(own_model, **kw).run(
            list(zip(PROMPTS, GENS)), results=True
        )
    ]
    res2, res1, B = migrate_run(own_model, kw)
    assert [r.tokens.tolist() for r in res2] == gold
    # Work actually carried over: stage 1 generated > 0 tokens and the
    # target restored them without re-generating.
    assert all(len(r.tokens) > 0 for r in res1)
    st = B.last_stats
    assert st["migrated_in"] == len(PROMPTS)
    assert st["migrated_in_tokens"] == sum(len(r.tokens) for r in res1)
    assert st["migration_fallbacks"] == 0


def test_migration_bit_exact_seeded_sampling(own_model):
    """Seeded-sampled continuation is bit-identical too: the snapshot
    carries the per-request PRNG key + draw counter, so the target
    replays the exact draws the source would have made (int8 pool —
    the stricter case)."""
    kw = {"kv_dtype": "int8", "temperature": 0.8, "seed": 11}
    gold = [
        r.tokens.tolist()
        for r in make_engine(own_model, **kw).run(
            list(zip(PROMPTS, GENS)), results=True
        )
    ]
    res2, _res1, _B = migrate_run(own_model, kw)
    assert [r.tokens.tolist() for r in res2] == gold
    # And a migrated sampled run is reproducible end to end.
    res3, _, _ = migrate_run(own_model, kw)
    assert [r.tokens.tolist() for r in res3] == gold


def test_migration_prefix_delta_on_warm_target(own_model):
    """When the target already caches the prefix (it served the same
    request before), only the non-shared page suffix ships — and the
    continuation stays bit-identical while the import pins the shared
    pages out of the target's radix tree."""
    kw = {"kv_dtype": "int8"}
    gold = [
        r.tokens.tolist()
        for r in make_engine(own_model, **kw).run(
            list(zip(PROMPTS, GENS)), results=True
        )
    ]
    warm = make_engine(own_model, **kw)
    warm.run(list(zip(PROMPTS, GENS)), results=True)
    digest = warm.prefix_digest()
    assert digest  # the tree actually holds the chains

    from triton_distributed_tpu.models import slot_state
    from triton_distributed_tpu.models.continuous import Request

    A = make_engine(own_model, **kw)
    A.request_handoff(after_rounds=4)
    res1 = A.run(list(zip(PROMPTS, GENS)), results=True)
    assert all(r.status == "migrated" for r in res1)
    resume = []
    for (p, g), r in zip(list(zip(PROMPTS, GENS)), res1):
        full = slot_state.SlotSnapshot.from_wire(r.snapshot)
        thin = slot_state.prefix_delta(full, digest)
        assert thin.from_prefix_pages > 0
        assert thin.payload_bytes() < full.payload_bytes()
        resume.append(Request(p, g, snapshot=thin.to_wire()))
    res2 = warm.run(resume, results=True)
    assert [r.tokens.tolist() for r in res2] == gold
    assert warm.last_stats["migration_fallbacks"] == 0
    assert warm.audit() == [] and A.audit() == []


def test_stale_prefix_delta_falls_back_to_replay(own_model):
    """A prefix-delta snapshot whose omitted pages the target no longer
    caches (fresh tree) cannot be reconstructed: the import falls back
    to a full replay from the prompt — same final tokens, counted
    fallback, clean audits."""
    kw = {"kv_dtype": None}
    gold = [
        r.tokens.tolist()
        for r in make_engine(own_model, **kw).run(
            list(zip(PROMPTS, GENS)), results=True
        )
    ]
    warm = make_engine(own_model, **kw)
    warm.run(list(zip(PROMPTS, GENS)), results=True)
    res2, res1, B = migrate_run(
        own_model, kw, delta_digest=warm.prefix_digest()
    )
    # B's tree is EMPTY — every delta import must have fallen back.
    assert [r.tokens.tolist() for r in res2] == gold
    assert B.last_stats["migration_fallbacks"] == len(PROMPTS)
    assert B.last_stats["migrated_in"] == 0


def test_migration_chaos_seams(own_model):
    """Kill-mid-migration on either end, deterministically: a failed
    EXPORT keeps the slot decoding locally (the handoff drain stays
    lossless — everything still completes with the right tokens); a
    failed IMPORT falls back to replay-from-prompt (same tokens,
    counted). Audits stay clean on every engine involved."""
    from triton_distributed_tpu.models.continuous import Request

    kw = {"kv_dtype": "int8"}
    gold = [
        r.tokens.tolist()
        for r in make_engine(own_model, **kw).run(
            list(zip(PROMPTS, GENS)), results=True
        )
    ]
    # Export end dies: every export attempt fails → the handoff sweep
    # can migrate nothing, both requests FINISH on the draining engine.
    A = make_engine(own_model, **kw)
    A.request_handoff(after_rounds=4)
    with FaultPlan(seed=3).fail_export(at=0, times=999) as plan:
        res = A.run(list(zip(PROMPTS, GENS)), results=True)
    assert plan.fired
    assert [r.status for r in res] == ["ok", "ok"]
    assert [r.tokens.tolist() for r in res] == gold
    assert A.audit() == []

    # Import end dies: the resume falls back to a full replay.
    A2 = make_engine(own_model, **kw)
    A2.request_handoff(after_rounds=4)
    res1 = A2.run(list(zip(PROMPTS, GENS)), results=True)
    assert all(r.status == "migrated" for r in res1)
    B = make_engine(own_model, **kw)
    with FaultPlan(seed=4).fail_import(at=0, times=999) as plan:
        res2 = B.run(
            [
                Request(p, g, snapshot=r.snapshot)
                for (p, g), r in zip(list(zip(PROMPTS, GENS)), res1)
            ],
            results=True,
        )
    assert plan.fired
    assert [r.tokens.tolist() for r in res2] == gold
    assert B.last_stats["migration_fallbacks"] == len(PROMPTS)
    assert B.audit() == [] and A2.audit() == []


def test_prefill_only_exports_after_admission(own_model):
    """``prefill_only`` (the prefill→decode handoff's engine half):
    admission runs, ONE token emits, the slot exports — and a second
    engine finishes the decode bit-identically."""
    from triton_distributed_tpu.models.continuous import Request

    kw = {"kv_dtype": None}
    gold = [
        r.tokens.tolist()
        for r in make_engine(own_model, **kw).run(
            list(zip(PROMPTS, GENS)), results=True
        )
    ]
    A = make_engine(own_model, **kw)
    res1 = A.run(
        [Request(p, g, prefill_only=True)
         for p, g in zip(PROMPTS, GENS)],
        results=True,
    )
    assert all(r.status == "migrated" for r in res1)
    assert all(len(r.tokens) == 1 for r in res1)  # the admission token
    B = make_engine(own_model, **kw)
    res2 = B.run(
        [Request(p, g, snapshot=r.snapshot)
         for (p, g), r in zip(list(zip(PROMPTS, GENS)), res1)],
        results=True,
    )
    assert [r.tokens.tolist() for r in res2] == gold
    assert A.audit() == [] and B.audit() == []


# -- serving tier on the stub: drain handoff + prefill policy -------------


STUB_PROMPTS = [
    np.arange(1, 9, dtype=np.int32),
    np.arange(20, 30, dtype=np.int32),
]
STUB_GENS = [50, 40]
STUB_GOLDS = [stub_generate(p, g) for p, g in zip(STUB_PROMPTS, STUB_GENS)]


def _stub_replicas(n, delay_s=0.0, prefix="r"):
    from triton_distributed_tpu.serving.replica import EngineReplica

    return [
        EngineReplica(
            StubEngine(num_pages=64, page_size=4, delay_s=delay_s),
            name=f"{prefix}{i}",
        )
        for i in range(n)
    ]


def test_handoff_drain_losless_zero_duplicates(fresh_telemetry):
    """ISSUE-10 acceptance: ``handoff=True`` drain completes every
    in-flight request — bit-exact, exactly once (latch-first tickets
    make a duplicate emission structurally impossible; we additionally
    assert the fleet's generated totals count each token once) — and
    the source replica drains cleanly with real work carried over."""
    from triton_distributed_tpu.serving.router import Router

    reps = _stub_replicas(2, delay_s=1.0)
    router = Router(reps, max_reroutes=3)
    out = {}

    def run():
        out["res"] = router.run(
            list(zip(STUB_PROMPTS, STUB_GENS)), results=True
        )

    th = threading.Thread(target=run, daemon=True)
    th.start()
    # Deterministic sync: drain only once a replica has published
    # snapshot progress of >= 3 generated tokens (condition, not sleep).
    deadline = time.monotonic() + 30
    src = None
    while time.monotonic() < deadline and src is None:
        for r in reps:
            if any(
                len(s["out"]) >= 3
                for s in r.engine.export_slots().values()
            ):
                src = r
                break
        time.sleep(0.005)
    assert src is not None, "no replica reached 3 tokens in time"
    assert router.drain_replica(src.name, grace_s=30, handoff=True)
    th.join(60)
    res = out["res"]
    for r, g in zip(res, STUB_GOLDS):
        assert r.status == "ok", (r.status, r.reason)
        assert r.tokens.tolist() == g
    assert src.state == "drained"
    assert router.stats["migrations"] >= 1
    # Zero duplicate emissions: every token counted exactly once
    # across the fleet (restored tokens are NOT re-counted as
    # generated), using the replicas' cumulative totals — last_stats
    # only covers each replica's final batch.
    gen = sum(r.totals["generated_tokens"] for r in reps)
    restored = sum(r.totals["migrated_in_tokens"] for r in reps)
    assert gen == sum(STUB_GENS)
    assert restored >= 3  # the drained slot's progress carried over
    assert router.audit() == []
    router.shutdown()


def test_migrate_after_prefill_policy(fresh_telemetry):
    """The ``migrate_after_prefill`` routing policy: prefill on one
    replica, decode on ANOTHER via the same export/import path —
    outputs bit-exact, both replicas did real work."""
    from triton_distributed_tpu.serving.router import Router

    reps = _stub_replicas(2, prefix="p")
    router = Router(reps, policy="migrate_after_prefill", max_reroutes=3)
    res = router.run(list(zip(STUB_PROMPTS, STUB_GENS)), results=True)
    for r, g in zip(res, STUB_GOLDS):
        assert r.status == "ok", (r.status, r.reason)
        assert r.tokens.tolist() == g
    assert router.stats["prefill_migrations"] >= 1
    # Prefill landed on one replica, decode on the other: both ran.
    assert all(r.runs >= 1 for r in reps)
    # The decode hop landed AWAY from the prefill hop every time.
    assert router.stats["migrations"] == router.stats["prefill_migrations"]
    assert router.audit() == []
    router.shutdown()


def test_stub_snapshot_fallback_on_corrupt_snapshot():
    """A garbled/stale snapshot (mid-transfer corruption) degrades to
    replay: the output is still the full correct generation."""
    from triton_distributed_tpu.serving.replica import Ticket
    from triton_distributed_tpu.serving.router import Router

    reps = _stub_replicas(1, prefix="c")
    router = Router(reps)
    t = Ticket(STUB_PROMPTS[0], STUB_GENS[0])
    t.snapshot = {"prompt": [9, 9, 9], "out": [1, 2]}  # wrong prompt
    router._dispatch(t)
    assert t.wait(30)
    assert t.result.status == "ok"
    assert t.result.tokens.tolist() == STUB_GOLDS[0]
    assert reps[0].engine.last_stats["migration_fallbacks"] == 1
    router.shutdown()


# -- chaos: process fleet (stub children over the wire) -------------------


def _fleet_specs(n, delay_s):
    from triton_distributed_tpu.serving.supervisor import stub_spec

    return [
        stub_spec(f"r{i}", delay_s=delay_s, page_size=4, num_pages=64)
        for i in range(n)
    ]


@needs_procs
def test_fleet_sigkill_snapshot_resume(fresh_telemetry):
    """ISSUE-10 acceptance: SIGKILL mid-generation with supervisor
    snapshots enabled resumes victims from the last snapshot — final
    outputs bit-exact, the snapshot-resume counter fires, and the
    SURVIVOR's tokens-saved counter (scraped through its metrics verb)
    proves measurably fewer tokens were re-generated than PR 9's
    replay recovery (which re-generates all of them)."""
    from triton_distributed_tpu.obs import metrics as obs_metrics
    from triton_distributed_tpu.serving.supervisor import FleetSupervisor

    sup = FleetSupervisor(
        _fleet_specs(2, delay_s=1.2),
        heartbeat_s=0.05, heartbeat_timeout_s=2.0,
        respawn_backoff_s=0.2, spawn_timeout_s=120.0,
        snapshot_s=0.05,
    )
    try:
        router = sup.start()
        plan = FaultPlan(seed=7).kill_proc(replica="r0", after_s=0.5)
        with plan:
            res = router.run(
                list(zip(STUB_PROMPTS, STUB_GENS)), results=True
            )
        assert plan.fired and plan.fired[0][0] == "proc.kill"
        for r, g in zip(res, STUB_GOLDS):
            assert r.status == "ok", (r.status, r.reason)
            assert r.tokens.tolist() == g
        snap = obs_metrics.default_registry().snapshot()
        resumes = snap["tdt_supervisor_snapshot_resumes_total"]["series"]
        assert sum(s["value"] for s in resumes) >= 1, resumes
        # Tokens saved, measured ON the serving side: the survivor's
        # import counted every restored token.
        saved = 0
        for rep in router.replicas:
            if rep.state != "healthy":
                continue
            m = rep._remote.call({"cmd": "metrics"})
            series = m["metrics"].get(
                "tdt_migration_tokens_saved_total", {}
            ).get("series", [])
            saved += sum(s["value"] for s in series)
        assert saved >= 1, "snapshot resume saved no generation work"
        assert router.audit() == []
    finally:
        sup.shutdown()


@needs_procs
def test_fleet_sigkill_migration_target(fresh_telemetry):
    """SIGKILL the MIGRATION TARGET: the first kill orphans the ticket
    (it resumes-from-snapshot on a second replica), the second kill
    takes that target down mid-import — the ticket re-routes once more
    and still completes bit-exact; survivors audit clean."""
    from triton_distributed_tpu.serving.supervisor import FleetSupervisor

    sup = FleetSupervisor(
        _fleet_specs(3, delay_s=1.0),
        heartbeat_s=0.05, heartbeat_timeout_s=2.0,
        respawn_backoff_s=0.2, spawn_timeout_s=120.0,
        snapshot_s=0.05,
    )
    try:
        router = sup.start()
        # Hit 1 = the original batch (killed mid-generation); hit 2 =
        # the re-dispatched, snapshot-carrying batch (the target).
        plan = (FaultPlan(seed=9)
                .kill_proc(replica="r0", after_s=0.4)
                .kill_proc(at=2))
        with plan:
            res = router.run([(STUB_PROMPTS[0], STUB_GENS[0])],
                             results=True)
        assert len(plan.fired) >= 2, plan.fired
        assert res[0].status == "ok", (res[0].status, res[0].reason)
        assert res[0].tokens.tolist() == STUB_GOLDS[0]
        assert router.audit() == []  # survivors clean; dead skipped
    finally:
        sup.shutdown()


@needs_procs
def test_remote_handoff_drain_over_the_wire(fresh_telemetry):
    """Lossless drain across the process boundary: the ``handoff``
    verb stops the child's in-flight batch, its snapshots ride the
    response, and the router re-admits on the survivor — zero tokens
    of work lost, zero duplicates."""
    from triton_distributed_tpu.serving.router import Router

    # Unmanaged remote replicas (no supervisor), the test_fleet.py way.
    from triton_distributed_tpu.serving.supervisor import spawn_replica

    out = {}

    def boot(i, spec):
        out[i] = spawn_replica(spec, spawn_timeout_s=120.0)

    threads = [
        threading.Thread(target=boot, args=(i, s), daemon=True)
        for i, s in enumerate(_fleet_specs(2, delay_s=1.2))
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=150)  # past boot's own spawn_timeout_s
    assert len(out) == 2
    reps = [out[0], out[1]]
    router = Router(reps, max_reroutes=3)
    try:
        res_box = {}

        def run():
            res_box["res"] = router.run(
                list(zip(STUB_PROMPTS, STUB_GENS)), results=True
            )

        th = threading.Thread(target=run, daemon=True)
        th.start()
        # Wait for real progress on whichever child holds a batch.
        deadline = time.monotonic() + 30
        src = None
        while time.monotonic() < deadline and src is None:
            for r in reps:
                try:
                    snaps = r.export_slots(timeout=2.0)
                except Exception:  # noqa: BLE001 — child still booting
                    continue
                if any(len(s.get("out") or []) >= 3
                       for s in snaps.values()):
                    src = r
                    break
            time.sleep(0.01)
        assert src is not None, "no child published progress in time"
        assert router.drain_replica(src.name, grace_s=30, handoff=True)
        th.join(60)
        res = res_box["res"]
        for r, g in zip(res, STUB_GOLDS):
            assert r.status == "ok", (r.status, r.reason)
            assert r.tokens.tolist() == g
        assert router.stats["migrations"] >= 1
        assert src.state == "drained"
        # The survivor restored the drained slot's tokens.
        other = [r for r in reps if r is not src][0]
        m = other._remote.call({"cmd": "metrics"})
        series = m["metrics"].get(
            "tdt_migration_tokens_saved_total", {}
        ).get("series", [])
        assert sum(s["value"] for s in series) >= 3
    finally:
        router.shutdown()
        for r in reps:
            proc = getattr(r, "proc", None)
            if proc is not None and proc.poll() is None:
                proc.kill()
                proc.wait(timeout=10)


def test_import_fallback_preserves_seeded_draws(own_model):
    """Code-review fix: the replay fallback restores the snapshot's
    per-request PRNG key (draw counter reset to 0), so even a FAILED
    import of a seeded-sampled request replays bit-identically to the
    un-migrated run."""
    from triton_distributed_tpu.models.continuous import Request

    kw = {"temperature": 0.8, "seed": 5}
    gold = [
        r.tokens.tolist()
        for r in make_engine(own_model, **kw).run(
            list(zip(PROMPTS, GENS)), results=True
        )
    ]
    A = make_engine(own_model, **kw)
    A.request_handoff(after_rounds=4)
    res1 = A.run(list(zip(PROMPTS, GENS)), results=True)
    assert all(r.status == "migrated" for r in res1)
    B = make_engine(own_model, **kw)
    with FaultPlan(seed=6).fail_import(at=0, times=999) as plan:
        res2 = B.run(
            [Request(p, g, snapshot=r.snapshot)
             for (p, g), r in zip(list(zip(PROMPTS, GENS)), res1)],
            results=True,
        )
    assert plan.fired
    assert B.last_stats["migration_fallbacks"] == len(PROMPTS)
    assert [r.tokens.tolist() for r in res2] == gold


def test_handoff_drain_without_survivors_finishes_locally(
        fresh_telemetry):
    """Code-review fix: ``drain_replica(handoff=True)`` with no OTHER
    healthy replica degrades to the finishing drain — the in-flight
    work completes here instead of being exported into a void."""
    from triton_distributed_tpu.serving.router import Router

    reps = _stub_replicas(1, delay_s=0.5, prefix="solo")
    router = Router(reps, max_reroutes=3)
    out = {}

    def run():
        out["res"] = router.run(
            [(STUB_PROMPTS[0], STUB_GENS[0])], results=True
        )

    th = threading.Thread(target=run, daemon=True)
    th.start()
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline and reps[0]._inflight == 0:
        time.sleep(0.005)
    assert router.drain_replica("solo0", grace_s=30, handoff=True)
    th.join(60)
    res = out["res"]
    assert res[0].status == "ok", (res[0].status, res[0].reason)
    assert res[0].tokens.tolist() == STUB_GOLDS[0]
    assert router.stats["migrations"] == 0  # nothing was exported
    assert reps[0].state == "drained"
    router.shutdown()
