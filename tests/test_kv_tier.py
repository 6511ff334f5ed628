"""Durable KV tier tests (docs/serving.md "Tiered KV",
docs/scale-out.md "Durable snapshots").

Layers of evidence:

- pure :class:`PageStore` semantics — codec/integrity, RAM LRU within
  capacity, disk atomicity + reload, and the containment contract
  (corrupted/truncated/missing entries NEVER yield wrong bits) plus
  the seeded ``tier.put``/``tier.get`` fault seams — milliseconds, no
  model;
- engine-level spill/fault-back on the tiny model: eviction demotes
  full radix pages to the tier, a revisited prefix faults them back
  cheaper than re-prefill, outputs stay bit-exact vs tier-less
  goldens under bf16 AND int8 pools, corrupted entries degrade to
  re-prefill, and a randomized spill/fault-back stress keeps the
  pool/radix/tier audits clean (the conftest autouse auditor runs
  ``ContinuousEngine.audit`` — now tier-aware — after every test);
- crash durability: an engine whose run is killed mid-generation
  leaves checksummed snapshots on disk that a FRESH engine resumes
  bit-exactly, and (the PR 10 chaos suite's missing case) a stub
  process fleet whose supervisor AND children die is rebooted over
  the same ``resume_dir`` and finishes the re-submitted requests
  bit-exactly from the persisted snapshots.
"""

import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from triton_distributed_tpu.models import kv_tier
from triton_distributed_tpu.models.kv_tier import (
    PREFIX_KIND,
    SNAP_KIND,
    PageStore,
    TierIntegrityError,
    chain_digest,
    request_digest,
)
from triton_distributed_tpu.runtime.faults import FaultPlan


def _can_spawn() -> bool:
    try:
        return subprocess.run(
            [sys.executable, "-c", "pass"], timeout=60
        ).returncode == 0
    except Exception:  # noqa: BLE001 — any failure means "cannot"
        return False


needs_procs = pytest.mark.skipif(
    not _can_spawn() or not hasattr(signal, "SIGKILL"),
    reason="child-process spawning unavailable on this platform",
)


# -- pure store: codec, LRU, disk, integrity, seams ------------------------


def test_digests_and_entry_codec():
    """Digests are stable, chain-exact, and collision-separated from
    request digests; the entry codec round-trips and every tamper
    class raises :class:`TierIntegrityError` instead of decoding."""
    assert chain_digest([1, 2, 3]) == chain_digest((1, 2, 3))
    assert chain_digest([1, 2, 3]) != chain_digest([1, 2, 4])
    assert request_digest([1, 2], 4) != request_digest([1, 2], 5)
    assert request_digest([1, 2], 4) == request_digest(
        np.asarray([1, 2], np.int32), 4
    )

    blob = kv_tier._encode("snap", "t1", {"a": [1, 2], "b": None})
    assert kv_tier._decode("snap", "t1", blob) == {"a": [1, 2], "b": None}
    with pytest.raises(TierIntegrityError, match="magic"):
        kv_tier._decode("snap", "t1", b"garbage")
    with pytest.raises(TierIntegrityError, match="truncated"):
        kv_tier._decode("snap", "t1", blob[:-2])
    with pytest.raises(TierIntegrityError, match="checksum"):
        flipped = bytearray(blob)
        flipped[-3] ^= 0xFF
        kv_tier._decode("snap", "t1", bytes(flipped))
    with pytest.raises(TierIntegrityError, match="expected"):
        kv_tier._decode("snap", "OTHER", blob)  # key mismatch
    with pytest.raises(TierIntegrityError, match="expected"):
        kv_tier._decode("prefix", "t1", blob)  # kind mismatch


def test_pagestore_lru_capacity_and_stats():
    """RAM-only store: hits/misses count, LRU eviction keeps bytes
    under capacity and evicts oldest-first, delete removes, audit is
    clean throughout."""
    s = PageStore(capacity_bytes=4096)
    assert s.get(SNAP_KIND, "absent") is None
    assert s.stats["misses"] == 1
    for i in range(4):
        assert s.put(SNAP_KIND, f"k{i}", {"pad": "x" * 256, "i": i})
    assert s.get(SNAP_KIND, "k0")["i"] == 0  # k0 is now most-recent
    assert s.stats["hits"] == 1
    # Push past capacity: k1 (the LRU) goes, k0 (touched) survives.
    big = {"pad": "y" * 3100}
    assert s.put(SNAP_KIND, "big", big)
    assert s.ram_bytes <= 4096
    assert s.stats["evictions"] >= 1
    assert s.get(SNAP_KIND, "k0")["i"] == 0
    assert s.get(SNAP_KIND, "k1") is None  # evicted (no disk tier)
    # An entry larger than the whole capacity is refused, not wedged.
    assert s.put(SNAP_KIND, "huge", {"pad": "z" * 8192}) is False
    assert s.stats["refused"] == 1
    s.delete(SNAP_KIND, "k0")
    assert s.get(SNAP_KIND, "k0") is None
    assert s.audit() == []
    snap = s.snapshot()
    assert snap["puts"] == 5 and snap["ram_bytes"] == s.ram_bytes


def test_pagestore_may_contain_guard(tmp_path):
    """``may_contain`` is the hot-path emptiness guard: False until the
    first successful put of that kind (per kind, monotone — deletes
    never reset it), seeded from disk at construction so a fresh
    process over a populated dir counts its predecessor's entries,
    and True for unknown kinds (conservative)."""
    s = PageStore(capacity_bytes=4096)
    assert not s.may_contain(PREFIX_KIND)
    assert not s.may_contain(SNAP_KIND)
    assert s.may_contain("unknown-kind")  # never under-probe
    # A refused put (oversized) leaves the store provably empty.
    assert s.put(SNAP_KIND, "huge", {"pad": "z" * 8192}) is False
    assert not s.may_contain(SNAP_KIND)
    assert s.put(SNAP_KIND, "t", {"a": 1})
    assert s.may_contain(SNAP_KIND)
    assert not s.may_contain(PREFIX_KIND)  # per-kind, not global
    s.delete(SNAP_KIND, "t")
    assert s.may_contain(SNAP_KIND)  # monotone: stays flipped
    # Disk prescan: a fresh store over a dir a prior process populated
    # reports non-empty without any put of its own.
    d = str(tmp_path / "tier")
    PageStore(capacity_bytes=4096, dir=d).put(
        PREFIX_KIND, chain_digest([1, 2]), {"chain": [1, 2]}
    )
    fresh = PageStore(capacity_bytes=4096, dir=d)
    assert fresh.may_contain(PREFIX_KIND)
    assert not fresh.may_contain(SNAP_KIND)


def test_pagestore_disk_persistence_and_atomicity(tmp_path):
    """Disk tier: entries survive into a FRESH store over the same dir
    (the restart path), RAM-evicted entries are still served from disk
    (and promoted), writes never leave a live ``.tmp``, and
    ``clear()`` empties both tiers."""
    d = str(tmp_path / "tier")
    s = PageStore(capacity_bytes=1 << 20, dir=d)
    for i in range(3):
        assert s.put(SNAP_KIND, f"t{i}", {"out": [i], "gen_len": 9,
                                          "prompt": [1, i]})
    assert s.put(PREFIX_KIND, chain_digest([5, 6]), {"chain": [5, 6]})
    # No tmp files linger after the atomic renames.
    leftovers = [
        f for root, _, files in os.walk(d) for f in files if ".tmp" in f
    ]
    assert leftovers == []
    # A fresh store sees every durable entry, by key.
    s2 = PageStore(capacity_bytes=1 << 20, dir=d)
    assert s2.keys(SNAP_KIND) == ["t0", "t1", "t2"]
    assert s2.get(SNAP_KIND, "t1")["out"] == [1]
    assert s2.stats["disk_hits"] == 1
    # RAM eviction demotes, not destroys: a tiny-RAM store still
    # serves from disk and promotes back into RAM.
    s3 = PageStore(capacity_bytes=600, dir=d)
    for i in range(8):
        s3.put(SNAP_KIND, f"fat{i}", {"pad": "x" * 300, "i": i})
    assert s3.stats["evictions"] >= 1
    assert s3.get(SNAP_KIND, "fat0")["i"] == 0  # from disk
    assert s3.stats["disk_hits"] >= 1
    # clear(): both tiers empty; prefix kind untouched by snap clear.
    removed = s3.clear(SNAP_KIND)
    assert removed > 0
    assert s3.keys(SNAP_KIND) == []
    assert PageStore(dir=d).keys(PREFIX_KIND) != []
    # fsync=False (the engine-owned scheduling-loop shape) still
    # round-trips through a fresh store: the atomic rename alone
    # carries process-crash durability.
    d2 = str(tmp_path / "nosync")
    s4 = PageStore(capacity_bytes=1 << 20, dir=d2, fsync=False)
    assert s4.put(SNAP_KIND, "ns", {"out": [7]})
    assert PageStore(dir=d2).get(SNAP_KIND, "ns")["out"] == [7]
    # Disk-bound prunes are PERMANENT deletions and count separately
    # from the (lossless) RAM LRU demotions.
    d3 = str(tmp_path / "bounded")
    s5 = PageStore(capacity_bytes=1 << 20, dir=d3,
                   disk_capacity_bytes=1200)
    for i in range(6):
        s5.put(SNAP_KIND, f"b{i}", {"pad": "y" * 300, "i": i})
    assert s5.stats["disk_evictions"] >= 1
    assert s5.stats["evictions"] == 0  # RAM had room: no demotions
    assert len(PageStore(dir=d3).keys(SNAP_KIND)) < 6  # gone from disk


def test_pagestore_integrity_containment(tmp_path):
    """The acceptance contract in miniature: corrupted bytes, a
    truncated file, a vanished file, and foreign garbage ALL read as
    None with the entry dropped and counted — wrong bits can never
    come out of ``get``."""
    from triton_distributed_tpu.obs import events as obs_events

    d = str(tmp_path / "tier")
    s = PageStore(capacity_bytes=1 << 20, dir=d)
    for name in ("corrupt", "truncate", "vanish", "garbage"):
        s.put(SNAP_KIND, name, {"payload": name * 8})

    path = PageStore(dir=d)._path(SNAP_KIND, "corrupt")
    raw = open(path, "rb").read()
    flipped = bytearray(raw)
    flipped[len(flipped) // 2] ^= 0xFF
    open(path, "wb").write(bytes(flipped))
    t_path = PageStore(dir=d)._path(SNAP_KIND, "truncate")
    open(t_path, "wb").write(open(t_path, "rb").read()[:-5])
    os.unlink(PageStore(dir=d)._path(SNAP_KIND, "vanish"))
    g_path = PageStore(dir=d)._path(SNAP_KIND, "garbage")
    open(g_path, "wb").write(b"not a tier entry at all")

    fresh = PageStore(capacity_bytes=1 << 20, dir=d)
    assert fresh.get(SNAP_KIND, "corrupt") is None
    assert fresh.get(SNAP_KIND, "truncate") is None
    assert fresh.get(SNAP_KIND, "vanish") is None
    assert fresh.get(SNAP_KIND, "garbage") is None
    assert fresh.stats["drops"] == 3  # vanish is a plain miss
    assert fresh.stats["misses"] == 1
    # Dropped entries are gone from disk too — the next lookup is a
    # clean miss, not a repeated integrity failure.
    assert fresh.get(SNAP_KIND, "corrupt") is None
    assert fresh.stats["misses"] == 2
    events, _ = obs_events.default_ring().tail(0, kind="tier_drop")
    assert len(events) >= 3
    # RAM-side corruption is detected the same way (entries are stored
    # as their checksummed wire bytes in BOTH tiers).
    r = PageStore(capacity_bytes=1 << 20)
    r.put(SNAP_KIND, "ram", {"x": 1})
    blob = bytearray(r._ram[(SNAP_KIND, "ram")])
    blob[len(blob) // 2] ^= 0xFF
    r._ram[(SNAP_KIND, "ram")] = bytes(blob)
    assert r.get(SNAP_KIND, "ram") is None
    assert r.stats["drops"] == 1


def test_tier_fault_seams():
    """The seeded ``tier.put``/``tier.get`` seams: refuse (put → False
    and the entry is NOT stored; get → transient miss, entry kept),
    corrupt (checksum drops the entry), slow (stalls, then proceeds) —
    and every firing is logged on the plan."""
    s = PageStore(capacity_bytes=1 << 20)
    with FaultPlan(seed=1).refuse_tier("put") as plan:
        assert s.put(SNAP_KIND, "a", {"x": 1}) is False
    assert plan.fired and s.stats["refused"] == 1
    assert s.get(SNAP_KIND, "a") is None

    s.put(SNAP_KIND, "b", {"x": 2})
    with FaultPlan(seed=1).refuse_tier("get") as plan:
        assert s.get(SNAP_KIND, "b") is None
    assert plan.fired and s.stats["errors"] == 1
    assert s.get(SNAP_KIND, "b") == {"x": 2}  # the entry survived

    with FaultPlan(seed=1).corrupt_tier("get") as plan:
        assert s.get(SNAP_KIND, "b") is None
    assert plan.fired and s.stats["drops"] == 1
    assert s.get(SNAP_KIND, "b") is None  # corrupt → dropped for good

    s.put(SNAP_KIND, "c", {"x": 3})
    with FaultPlan(seed=1).slow_tier(0.05, "get") as plan:
        t0 = time.monotonic()
        assert s.get(SNAP_KIND, "c") == {"x": 3}
        assert time.monotonic() - t0 >= 0.05
    assert plan.fired

    # Corruption injected at PUT time is caught at the next get.
    with FaultPlan(seed=1).corrupt_tier("put"):
        assert s.put(SNAP_KIND, "d", {"x": 4}) is True
    assert s.get(SNAP_KIND, "d") is None
    with pytest.raises(ValueError, match="op"):
        FaultPlan().refuse_tier("sideways")


# -- engine: spill, fault-back, containment, stress ------------------------


def _mk_reqs(rng, n_prefixes=2, prefix_tokens=32, tail=4, gen=3):
    reqs = []
    for _ in range(n_prefixes):
        pre = rng.integers(1, 200, size=prefix_tokens).astype(np.int32)
        t = rng.integers(1, 200, size=tail).astype(np.int32)
        reqs.append((np.concatenate([pre, t]), gen))
    return reqs


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_engine_spill_and_fault_back_bitexact(tp4_model, kv_dtype):
    """Eviction under pool pressure spills full radix pages to the
    tier; re-admitting the evicted prefix faults them back (suffix-only
    prefill, counted) with outputs bit-identical to a tier-less
    engine. The same proof on an int8 pool — codes + per-page scales
    travel as a pair. On the four-device model: each shard's one K/V
    head of a page leaves and comes back to its own shard (the
    module's other cases keep all four heads on one device)."""
    from triton_distributed_tpu.models.continuous import ContinuousEngine

    rng = np.random.default_rng(0)
    r1, r2 = _mk_reqs(rng)
    golds = [
        ContinuousEngine(
            tp4_model, max_batch=1, page_size=16, max_length=64,
            prefix_cache=True, kv_dtype=kv_dtype,
        ).run([r])[0]
        for r in (r1, r2)
    ]
    # 4-page pool: serving r2 must evict r1's chain — through the
    # tier instead of to nothing.
    eng = ContinuousEngine(
        tp4_model, max_batch=1, page_size=16, max_length=64,
        prefix_cache=True, num_pages=4, kv_dtype=kv_dtype,
        tier_bytes=32 << 20,
    )
    np.testing.assert_array_equal(eng.run([r1])[0], golds[0])
    np.testing.assert_array_equal(eng.run([r2])[0], golds[1])
    assert eng.last_stats["tier_spilled_pages"] >= 1
    np.testing.assert_array_equal(eng.run([r1])[0], golds[0])
    st = eng.last_stats
    assert st["tier_hits"] >= 1 and st["tier_faults"] >= 1
    assert st["tier_bytes"] > 0
    # Fault-back beat re-prefill: only the un-faulted suffix ran
    # through the prefill path.
    assert st["prefill_tokens"] < len(r1[0])
    assert st["prefix_hit_tokens"] >= 16
    assert st["tier"]["hits"] >= 1
    assert eng.audit() == []


def test_engine_tier_weight_identity(own_model):
    """Durable entries are valid under the weights that produced them,
    never across a checkpoint swap: a prefix entry whose model
    fingerprint differs is refused at fault-back (dropped; admission
    re-prefills bit-exactly), and a snapshot carrying a foreign
    fingerprint degrades to a bit-exact replay instead of importing
    old-weight KV."""
    from triton_distributed_tpu.models.continuous import (
        ContinuousEngine,
        Request,
    )

    rng = np.random.default_rng(3)
    r1, r2 = _mk_reqs(rng)
    gold = ContinuousEngine(
        own_model, max_batch=1, page_size=16, max_length=64,
        prefix_cache=True,
    ).run([r1])[0]

    eng = ContinuousEngine(
        own_model, max_batch=1, page_size=16, max_length=64,
        prefix_cache=True, num_pages=4, tier_bytes=32 << 20,
    )
    np.testing.assert_array_equal(eng.run([r1])[0], gold)
    eng.run([r2])  # evict r1's chain through the tier
    assert eng.last_stats["tier_spilled_pages"] >= 1
    # Rewrite every prefix entry as if another checkpoint produced it.
    for key in eng.tier.keys(PREFIX_KIND):
        payload = eng.tier.get(PREFIX_KIND, key)
        payload["model_fp"] = "other-weights"
        assert eng.tier.put(PREFIX_KIND, key, payload)
    np.testing.assert_array_equal(eng.run([r1])[0], gold)  # re-prefilled
    assert eng.last_stats["tier_faults"] == 0
    assert eng.audit() == []

    # Snapshot side: crash a shared-store engine mid-generation, then
    # import its stamped leftover into same-weights engines — clean
    # fingerprint resumes, foreign fingerprint replays; both bit-exact.
    prompt = np.arange(1, 20, dtype=np.int32)
    gold2 = ContinuousEngine(
        own_model, max_batch=1, page_size=16, max_length=64,
        prefix_cache=True,
    ).run([(prompt, 6)])[0]
    shared = PageStore(capacity_bytes=1 << 20)
    crasher = ContinuousEngine(
        own_model, max_batch=1, page_size=16, max_length=64,
        prefix_cache=True, snapshot_every=1, tier=shared,
    )
    with FaultPlan(seed=5).on("engine.decode", at=3,
                              exc=KeyboardInterrupt()):
        with pytest.raises(KeyboardInterrupt):
            crasher.run(
                [Request(prompt, 6, ticket_id="tkt-w")], results=True
            )
    assert crasher.audit() == []
    snap = shared.get(SNAP_KIND, "tkt-w")
    assert snap is not None and snap.get("model_fp")

    ok = ContinuousEngine(
        own_model, max_batch=1, page_size=16, max_length=64,
        prefix_cache=True, tier_bytes=1 << 20,
    )
    out = ok.run([Request(prompt, 6, snapshot=dict(snap))], results=True)
    np.testing.assert_array_equal(out[0].tokens, gold2)
    assert ok.last_stats["migrated_in"] == 1

    bad = dict(snap)
    bad["model_fp"] = "other-weights"
    ok2 = ContinuousEngine(
        own_model, max_batch=1, page_size=16, max_length=64,
        prefix_cache=True, tier_bytes=1 << 20,
    )
    out2 = ok2.run([Request(prompt, 6, snapshot=bad)], results=True)
    assert out2[0].status == "ok"
    np.testing.assert_array_equal(out2[0].tokens, gold2)
    assert ok2.last_stats["migration_fallbacks"] >= 1
    assert ok2.last_stats["migrated_in"] == 0


def test_engine_shared_tier_mismatch_skips_not_deletes(own_model):
    """A mismatched probe against a SHARED store (``tier=``) degrades
    locally but never destroys the other engine's valid entry: an int8
    engine walking a bf16 engine's spilled chain re-prefills (zero
    faults), the entries survive, and the bf16 engine still faults
    them back afterwards. (Owned stores DO delete on mismatch —
    covered by the weight-identity test.)"""
    from triton_distributed_tpu.models.continuous import ContinuousEngine

    rng = np.random.default_rng(7)
    r1, r2 = _mk_reqs(rng)
    mk = dict(max_batch=1, page_size=16, max_length=64,
              prefix_cache=True)
    gold1, gold2 = (
        ContinuousEngine(own_model, **mk).run([r])[0] for r in (r1, r2)
    )
    gold1_i8 = ContinuousEngine(
        own_model, kv_dtype="int8", **mk
    ).run([r1])[0]

    shared = PageStore(capacity_bytes=32 << 20)
    a = ContinuousEngine(own_model, num_pages=4, tier=shared, **mk)
    np.testing.assert_array_equal(a.run([r1])[0], gold1)
    np.testing.assert_array_equal(a.run([r2])[0], gold2)  # spills r1
    assert a.last_stats["tier_spilled_pages"] >= 1
    keys_before = set(shared.keys(PREFIX_KIND))
    assert keys_before

    b = ContinuousEngine(own_model, kv_dtype="int8", tier=shared, **mk)
    np.testing.assert_array_equal(b.run([r1])[0], gold1_i8)
    assert b.last_stats["tier_hits"] == 0
    assert b.last_stats["tier_faults"] == 0
    assert set(shared.keys(PREFIX_KIND)) == keys_before  # intact

    np.testing.assert_array_equal(a.run([r1])[0], gold1)
    assert a.last_stats["tier_hits"] >= 1  # A still faults back
    assert a.audit() == [] and b.audit() == []


def test_engine_tier_events_and_metrics(own_model, fresh_telemetry):
    """The tier ledger is mirrored into the registry and the event
    ring: spills, fault-backs, and the tdt_tier_* series line up with
    ``last_stats``."""
    from triton_distributed_tpu.models.continuous import ContinuousEngine
    from triton_distributed_tpu.obs import events as obs_events
    from triton_distributed_tpu.obs import metrics as obs_metrics

    rng = np.random.default_rng(1)
    r1, r2 = _mk_reqs(rng)
    eng = ContinuousEngine(
        own_model, max_batch=1, page_size=16, max_length=64,
        prefix_cache=True, num_pages=4, tier_bytes=32 << 20,
    )
    eng.run([r1])
    eng.run([r2])
    eng.run([r1])
    kinds = [e.kind for e in obs_events.default_ring().tail(0)[0]]
    assert "tier_spill" in kinds and "tier_fault" in kinds
    snap = obs_metrics.default_registry().snapshot()
    spilled = snap["tdt_tier_spilled_pages_total"]["series"][0]["value"]
    faulted = snap["tdt_tier_faulted_pages_total"]["series"][0]["value"]
    assert spilled >= 1 and faulted >= 1
    # ISSUE-12 satellite: the deployed tier knobs ride
    # server_stats.engine next to kv_dtype.
    from triton_distributed_tpu.serving import ModelServer

    srv = ModelServer(eng)
    try:
        est = srv.server_stats["engine"]
        assert est["tier_bytes"] == 32 << 20
        assert est["tier_dir"] is None
        assert "kv_dtype" in est
    finally:
        srv._sock.close()


def test_engine_corrupt_tier_degrades_to_prefill(own_model):
    """Failure containment: every tier entry corrupted in place still
    yields BIT-EXACT outputs — the checksum drops each entry and the
    admission re-prefills (tier_faults stays 0, drops count up)."""
    from triton_distributed_tpu.models.continuous import ContinuousEngine

    rng = np.random.default_rng(2)
    r1, r2 = _mk_reqs(rng)
    gold = ContinuousEngine(
        own_model, max_batch=1, page_size=16, max_length=64,
        prefix_cache=True,
    ).run([r1])[0]
    eng = ContinuousEngine(
        own_model, max_batch=1, page_size=16, max_length=64,
        prefix_cache=True, num_pages=4, tier_bytes=32 << 20,
    )
    eng.run([r1])
    eng.run([r2])  # evicts + spills r1's chain
    assert eng.tier.snapshot()["ram_entries"] >= 1
    # Corrupt EVERY stored entry in place (RAM tier, no disk here).
    with eng.tier._lock:
        for k, blob in list(eng.tier._ram.items()):
            b = bytearray(blob)
            b[len(b) // 2] ^= 0xFF
            eng.tier._ram[k] = bytes(b)
    np.testing.assert_array_equal(eng.run([r1])[0], gold)
    st = eng.last_stats
    assert st["tier_faults"] == 0
    assert st["tier"]["drops"] >= 1
    assert st["prefill_tokens"] >= len(r1[0]) - 16  # re-prefilled
    assert eng.audit() == []


def test_engine_tier_fault_seams_degrade(own_model):
    """Injected tier faults at the engine level: a refused spill
    behaves like the pre-tier drop, a refused fault-back read like a
    miss — outputs bit-exact either way."""
    from triton_distributed_tpu.models.continuous import ContinuousEngine

    rng = np.random.default_rng(3)
    r1, r2 = _mk_reqs(rng)
    gold = ContinuousEngine(
        own_model, max_batch=1, page_size=16, max_length=64,
        prefix_cache=True,
    ).run([r1])[0]
    eng = ContinuousEngine(
        own_model, max_batch=1, page_size=16, max_length=64,
        prefix_cache=True, num_pages=4, tier_bytes=32 << 20,
    )
    eng.run([r1])
    with FaultPlan(seed=4).refuse_tier("put", times=99) as plan:
        eng.run([r2])  # every spill refused
    assert plan.fired
    assert eng.last_stats["tier_spilled_pages"] == 0
    np.testing.assert_array_equal(eng.run([r1])[0], gold)  # re-prefill
    # Now let spills through, then refuse the reads.
    eng.run([r2])
    assert eng.last_stats["tier_spilled_pages"] >= 1
    with FaultPlan(seed=4).refuse_tier("get", times=99) as plan:
        np.testing.assert_array_equal(eng.run([r1])[0], gold)
    assert plan.fired
    assert eng.last_stats["tier_faults"] == 0
    assert eng.audit() == []


def test_engine_randomized_spill_faultback_stress(own_model):
    """Randomized shared-prefix traffic over a pool far smaller than
    the population, tier on: every output equals its tier-less golden,
    and the pool partition (free ∪ slots ∪ tree) plus the tier audits
    stay clean after every round (the autouse fixture re-audits at
    teardown)."""
    from triton_distributed_tpu.models.continuous import ContinuousEngine

    rng = np.random.default_rng(5)
    bases = [
        rng.integers(1, 200, size=32).astype(np.int32) for _ in range(3)
    ]
    golden_engine = ContinuousEngine(
        own_model, max_batch=2, page_size=16, max_length=64,
        prefix_cache=True,
    )
    eng = ContinuousEngine(
        own_model, max_batch=2, page_size=16, max_length=64,
        prefix_cache=True, num_pages=6, tier_bytes=32 << 20,
    )
    golds: dict = {}
    for _ in range(6):
        base = bases[int(rng.integers(len(bases)))]
        cut = int(rng.integers(16, len(base) + 1))
        tail = rng.integers(1, 200, size=int(rng.integers(1, 4)))
        prompt = np.concatenate([base[:cut], tail]).astype(np.int32)
        gen = int(rng.integers(1, 4))
        key = (tuple(int(t) for t in prompt), gen)
        if key not in golds:
            golds[key] = golden_engine.run([(prompt, gen)])[0]
        out = eng.run([(prompt, gen)])[0]
        np.testing.assert_array_equal(out, golds[key])
        assert eng.audit() == []
        owned = list(eng.pool.free) + [
            n.page for n in eng.prefix.walk()
        ]
        assert len(owned) == len(set(owned))
    assert eng.last_stats["tier"]["puts"] >= 1  # the tier actually ran


def test_audit_catches_tier_chain_drift(own_model):
    """The tier-residency audit cross-check: an entry whose payload
    chain no longer matches its digest key (or a tree node's chain) is
    reported — the drift that would fault wrong KV back under a prompt
    if it went unseen."""
    from triton_distributed_tpu.models.continuous import ContinuousEngine

    rng = np.random.default_rng(6)
    r1, _ = _mk_reqs(rng)
    eng = ContinuousEngine(
        own_model, max_batch=1, page_size=16, max_length=64,
        prefix_cache=True, tier_bytes=32 << 20,
    )
    eng.run([r1])
    # Fabricate a drifted entry: correct checksum, wrong chain for the
    # digest key it is stored under.
    chain = [int(t) for t in r1[0][:16]]
    key = chain_digest(chain)
    bad = kv_tier.prefix_payload(
        [9] * 16, 16, None,
        np.zeros((2, 4, 16, 32), np.float32),
        np.zeros((2, 4, 16, 32), np.float32),
    )
    blob = kv_tier._encode(PREFIX_KIND, key, bad)
    with eng.tier._lock:
        eng.tier._ram[(PREFIX_KIND, key)] = blob
        eng.tier._ram_bytes += len(blob)
    problems = eng.audit()
    assert any("digest key" in p or "different token chain" in p
               for p in problems), problems
    eng.tier.delete(PREFIX_KIND, key)  # leave the engine clean
    assert eng.audit() == []


# -- crash durability: engine snapshots on disk ----------------------------


def test_engine_snapshot_buffer_survives_crash(own_model, tmp_path):
    """``snapshot_every`` + a disk tier: a run killed mid-generation
    leaves checksummed snapshots on disk; a FRESH engine (new process
    stand-in) imports the leftover and finishes BIT-EXACTLY vs an
    uninterrupted golden — the engine-side half of supervisor-restart
    recovery."""
    from triton_distributed_tpu.models.continuous import (
        ContinuousEngine,
        Request,
    )

    prompt = np.arange(1, 20, dtype=np.int32)
    gold = ContinuousEngine(
        own_model, max_batch=1, page_size=16, max_length=64,
        prefix_cache=True,
    ).run([(prompt, 8)])[0]

    d = str(tmp_path / "tier")
    eng = ContinuousEngine(
        own_model, max_batch=1, page_size=16, max_length=64,
        prefix_cache=True, snapshot_every=1, tier_dir=d,
    )
    # The crash must END the loop (a structured in-process failure
    # would keep running and prune its own buffer — correct, but not
    # a crash): KeyboardInterrupt escapes the decode step guard's
    # Exception boundary exactly like a process-killing signal, and
    # the durable entries written at earlier round boundaries stay.
    with FaultPlan(seed=7).on(
        "engine.decode", at=5, exc=KeyboardInterrupt()
    ):
        with pytest.raises(KeyboardInterrupt):
            eng.run(
                [Request(prompt, 8, ticket_id="tkt-1")], results=True
            )
    assert eng.audit() == []  # the abort teardown left the pool clean

    # A fresh store over the same dir (what a restarted process sees)
    # holds the last pre-crash snapshot, integrity-checked.
    store = PageStore(dir=d)
    assert store.keys(SNAP_KIND) == ["tkt-1"]
    snap = store.get(SNAP_KIND, "tkt-1")
    assert snap is not None and len(snap["out"]) >= 1

    fresh = ContinuousEngine(
        own_model, max_batch=1, page_size=16, max_length=64,
        prefix_cache=True,
    )
    out = fresh.run([Request(prompt, 8, snapshot=snap)], results=True)
    assert out[0].status == "ok"
    np.testing.assert_array_equal(out[0].tokens, gold)
    st = fresh.last_stats
    assert st["migrated_in"] == 1 and st["migrated_in_tokens"] >= 1

    # A RESPAWNED process over the same dir (fresh object: empty
    # _tier_snap_keys) clears its crashed predecessor's leftovers at
    # its first run() start — entries mean "crash", never "history";
    # without the owned-store clear they'd accumulate per crash cycle.
    respawn = ContinuousEngine(
        own_model, max_batch=1, page_size=16, max_length=64,
        prefix_cache=True, snapshot_every=1, tier_dir=d,
    )
    respawn.run([Request(prompt, 2, ticket_id="tkt-2")], results=True)
    assert "tkt-1" not in PageStore(dir=d).keys(SNAP_KIND)
    respawn.run([(prompt, 1)])
    assert PageStore(dir=d).keys(SNAP_KIND) == []

    # A SHARED store (tier= passed in) is NOT ours to sweep: run()
    # start deletes only this engine's own keys, never a sibling
    # replica's live snapshots.
    shared = PageStore(capacity_bytes=1 << 20)
    shared.put(SNAP_KIND, "sibling-tkt", {"out": [1]})
    ContinuousEngine(
        own_model, max_batch=1, page_size=16, max_length=64,
        prefix_cache=True, tier=shared,
    ).run([(prompt, 1)])
    assert shared.get(SNAP_KIND, "sibling-tkt") is not None


# -- supervisor: pull visibility + restart resume --------------------------


def test_supervisor_pull_failure_visible(fresh_telemetry):
    """ISSUE-12 satellite: a failed snapshot pull is COUNTED and
    evented (it used to vanish into a bare ``continue``) — a
    permanently wedged exporter shows as a monotone
    tdt_supervisor_snapshot_pull_failures_total ramp."""
    from triton_distributed_tpu.obs import events as obs_events
    from triton_distributed_tpu.obs import metrics as obs_metrics
    from triton_distributed_tpu.serving.supervisor import (
        FleetSupervisor,
        ReplicaSpec,
    )

    sup = FleetSupervisor(
        [ReplicaSpec("r0", ["true"])], snapshot_s=0.01,
    )

    class _Wedged:
        name = "r0#0"
        state = "healthy"

        def export_slots(self, timeout=None):
            raise ConnectionResetError("exporter wedged")

    sup._slots[0].replica = _Wedged()
    sup._pull_snapshots()
    sup._pull_snapshots()
    snap = obs_metrics.default_registry().snapshot()
    series = snap["tdt_supervisor_snapshot_pull_failures_total"]["series"]
    assert [s["value"] for s in series
            if s["labels"]["replica"] == "r0"] == [2]
    events, _ = obs_events.default_ring().tail(
        0, kind="snapshot_pull_failed"
    )
    assert len(events) == 2
    assert "exporter wedged" in events[-1].fields["reason"]

    # A non-dict answer counts too (a half-broken exporter).
    class _Wrong(_Wedged):
        def export_slots(self, timeout=None):
            return ["not", "a", "dict"]

    sup._slots[0].replica = _Wrong()
    sup._pull_snapshots()
    snap = obs_metrics.default_registry().snapshot()
    series = snap["tdt_supervisor_snapshot_pull_failures_total"]["series"]
    assert [s["value"] for s in series
            if s["labels"]["replica"] == "r0"] == [3]


@needs_procs
def test_supervisor_restart_resume_bitexact(tmp_path, fresh_telemetry):
    """ISSUE-12 acceptance (the PR 10 chaos suite's missing case): a
    stub fleet with snapshot pulls persisted under ``resume_dir`` is
    killed mid-batch — children SIGKILLed, supervisor abandoned
    (never drained, so the store keeps its leftovers). A NEW
    supervisor boots over the same dir, the requests are re-submitted
    (fresh ticket ids), and every one finishes BIT-EXACT against the
    stub's pure generator with tokens restored from the persisted
    snapshots rather than regenerated."""
    from triton_distributed_tpu.models.stub import stub_generate
    from triton_distributed_tpu.obs import events as obs_events
    from triton_distributed_tpu.serving.supervisor import (
        FleetSupervisor,
        stub_spec,
    )

    resume = str(tmp_path / "resume")
    prompts = [np.arange(1, 9, dtype=np.int32),
               np.arange(20, 30, dtype=np.int32)]
    gens = [8, 8]
    golds = [stub_generate(p, g) for p, g in zip(prompts, gens)]

    def mk_sup():
        return FleetSupervisor(
            [stub_spec("r0", delay_s=2.5, page_size=4, num_pages=64)],
            heartbeat_s=0.05, snapshot_s=0.05, resume_dir=resume,
            spawn_timeout_s=120.0,
        )

    sup = mk_sup()
    router = sup.start()
    results: dict = {}

    def drive():
        results["res"] = router.run(
            list(zip(prompts, gens)), results=True
        )

    th = threading.Thread(target=drive, daemon=True)
    th.start()
    # Wait until the durable store holds real MID-generation progress
    # (some request with 0 < out < gen_len persisted), then "crash"
    # everything: SIGKILL the child, abandon the supervisor WITHOUT
    # drain (a drain would clear the store — leftovers mean crash).
    store = PageStore(dir=resume)

    def progressed():
        for k in store.keys(SNAP_KIND):
            snap = store.peek(SNAP_KIND, k) or {}
            out = snap.get("out") or []
            if 0 < len(out) < int(snap.get("gen_len", 0)):
                return True
        return False

    assert sup.wait_for(progressed, timeout_s=60), store.keys(SNAP_KIND)
    sup._stop.set()  # the monitor must not respawn into the "crash"
    if sup._thread is not None:
        sup._thread.join(timeout=10)
    proc = router.replicas[0].proc
    os.kill(router.replicas[0].pid, signal.SIGKILL)
    proc.wait(timeout=10)
    th.join(timeout=60)
    assert not th.is_alive()
    # The in-flight work failed (no survivor to re-route to) — its
    # progress now lives ONLY in the durable store.
    assert any(r.status != "ok" for r in results["res"])
    assert len(PageStore(dir=resume).keys(SNAP_KIND)) >= 1

    # Reboot over the same dir; re-submit the same requests (new
    # ticket ids — the digest match is what finds the leftovers).
    sup2 = mk_sup()
    try:
        router2 = sup2.start()
        res2 = router2.run(list(zip(prompts, gens)), results=True)
        for r, gold in zip(res2, golds):
            assert r.status == "ok", (r.status, r.reason)
            assert r.tokens.tolist() == gold
        # Tokens were RESTORED, not regenerated: the fleet's cumulative
        # migrated_in ledger proves the snapshots were consumed.
        st = router2.last_stats
        assert st["migrated_in_tokens"] >= 1
        events, _ = obs_events.default_ring().tail(
            0, kind="snapshot_resume"
        )
        assert any(e.fields.get("restart") for e in events)
        # Consumed leftovers are deleted — a third submission of the
        # same prompts decodes fresh (still bit-exact, of course).
        res3 = router2.run(list(zip(prompts, gens)), results=True)
        for r, gold in zip(res3, golds):
            assert r.tokens.tolist() == gold
    finally:
        sup2.shutdown()
    # The CLEAN shutdown cleared the resume store.
    assert PageStore(dir=resume).keys(SNAP_KIND) == []
