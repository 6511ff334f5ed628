"""KV fabric tests (docs/scale-out.md "KV fabric").

Layers of evidence:

- pure store/client semantics — ``PageStore.digest()`` memoization and
  invalidation, ``tier_digest_match_len`` page walks, and the
  ``FabricClient``'s bounded degradation (dead peers, hung peers past
  the deadline, refused probes with cooldown) — milliseconds, no model;
- the wire serve side: ``tier_probe``/``tier_get`` verbs on a live
  ``ModelServer`` answering digest-keyed probes and serving the
  store's checksummed bytes verbatim, with every malformed request
  refused as ``bad_request``;
- engine-level peer fault-back on the tiny model: a local tier miss
  pulled from a PEER replica's tier (in-process and over the wire)
  with outputs bit-exact vs tier-less goldens, and the acceptance
  contract that a remote entry can NEVER produce wrong bits —
  checksum-tamper, stale-geometry, and foreign-fingerprint entries all
  degrade to re-prefill through the UNCHANGED PR 12 validation path;
- placement: the router's tier-affinity decision and the pools decode
  score's tier term; warm boot from a shared disk tier; fleet-scope
  metric merging of the ``tdt_tier_*``/``tdt_fabric_*`` families.
"""

import socket
import time

import numpy as np
import pytest

from triton_distributed_tpu.models import kv_tier
from triton_distributed_tpu.models.kv_tier import (
    PREFIX_KIND,
    SNAP_KIND,
    FabricClient,
    LocalFabricPeer,
    PageStore,
    WireFabricPeer,
    chain_digest,
    tier_digest_match_len,
)
from triton_distributed_tpu.runtime.faults import FaultPlan


MK = dict(max_batch=1, page_size=16, max_length=64, prefix_cache=True)


def _mk_reqs(rng, n=2, prefix_tokens=32, tail=4, gen=3):
    reqs = []
    for _ in range(n):
        pre = rng.integers(1, 200, size=prefix_tokens).astype(np.int32)
        t = rng.integers(1, 200, size=tail).astype(np.int32)
        reqs.append((np.concatenate([pre, t]), gen))
    return reqs


def _spill_engine(model, r1, **kw):
    """A tight-pool engine that has served ``r1`` and then a 4-page
    evictor prompt — r1's WHOLE chain (both full pages) now lives in
    its TIER, not its radix tree. A 3-page evictor is not enough: LRU
    spills the leaf only, and a peer's contiguous fault-back walk
    would break at the still-tree-resident first page."""
    from triton_distributed_tpu.models.continuous import ContinuousEngine

    evict = _mk_reqs(np.random.default_rng(987), n=1, prefix_tokens=48)[0]
    eng = ContinuousEngine(
        model, num_pages=4, tier_bytes=32 << 20, **MK, **kw
    )
    eng.run([r1])
    eng.run([evict])
    toks = [int(t) for t in r1[0]]
    assert eng.tier.contains(PREFIX_KIND, chain_digest(toks[:16]))
    assert eng.tier.contains(PREFIX_KIND, chain_digest(toks[:32]))
    return eng


# -- pure: digest, match walk, client degradation --------------------------


def test_pagestore_digest_summary_and_memoization():
    """``digest()`` summarizes RAM-resident prefix chains (truncated
    keys, per-kind counts, a set hash) and is memoized on the mutation
    counter: unchanged stores return the SAME object, every mutation
    class (put/delete/clear) invalidates it."""
    s = PageStore(capacity_bytes=1 << 20)
    d0 = s.digest()
    assert d0["chains"] == [] and d0["counts"] == {}
    assert s.digest() is d0  # memoized while untouched

    k1 = chain_digest([1, 2, 3])
    k2 = chain_digest([9, 8, 7])
    assert s.put(PREFIX_KIND, k1, {"chain": [1, 2, 3]})
    d1 = s.digest()
    assert d1 is not d0 and d1["hash"] != d0["hash"]
    assert d1["chains"] == [k1[:16]]
    assert d1["counts"] == {PREFIX_KIND: 1}
    assert s.digest() is d1

    assert s.put(PREFIX_KIND, k2, {"chain": [9, 8, 7]})
    assert s.put(SNAP_KIND, "t1", {"out": [1]})
    d2 = s.digest()
    assert d2["chains"] == sorted([k1[:16], k2[:16]])
    assert d2["counts"] == {PREFIX_KIND: 2, SNAP_KIND: 1}
    assert "t1"[:16] not in d2["chains"]  # snap entries never listed

    s.delete(PREFIX_KIND, k1)
    d3 = s.digest()
    assert d3["chains"] == [k2[:16]] and d3["hash"] != d2["hash"]
    s.clear()
    assert s.digest()["chains"] == []


def test_tier_digest_match_len():
    """Whole-page walk against a published digest: contiguous pages
    from the root count, the first absent page stops the walk, at
    least one token is always left to prefill, and malformed digests
    read as 0 (placement falls back to radix affinity)."""
    toks = list(range(1, 40))  # 39 tokens, ps=16 → pages at 16, 32
    full = {
        "ps": 16,
        "chains": [chain_digest(toks[:16])[:16],
                   chain_digest(toks[:32])[:16]],
    }
    assert tier_digest_match_len(full, toks) == 32
    first_only = {"ps": 16, "chains": [chain_digest(toks[:16])[:16]]}
    assert tier_digest_match_len(first_only, toks) == 16
    # Second page present but FIRST absent: contiguity is required.
    second_only = {"ps": 16, "chains": [chain_digest(toks[:32])[:16]]}
    assert tier_digest_match_len(second_only, toks) == 0
    # A fully-covered prompt still leaves one token to prefill.
    assert tier_digest_match_len(full, toks[:32]) == 16
    # Malformed/missing digests degrade to 0, never raise.
    assert tier_digest_match_len(None, toks) == 0
    assert tier_digest_match_len({}, toks) == 0
    assert tier_digest_match_len({"ps": 0, "chains": ["x"]}, toks) == 0
    assert tier_digest_match_len({"ps": "no", "chains": ["x"]}, toks) == 0
    assert tier_digest_match_len({"ps": 16, "chains": []}, toks) == 0
    assert tier_digest_match_len({"ps": 16}, toks) == 0


def test_fabric_client_fetch_and_degradation():
    """Pure client semantics: a fetch returns the peer entry DECODED
    (the codec is the transport); a dead wire peer, a refused probe
    (with cooldown), and a hung pull past the deadline all degrade to
    None without wedging — and every failure is counted."""
    store = PageStore(capacity_bytes=1 << 20)
    key = chain_digest([4, 5, 6])
    payload = {"chain": [4, 5, 6], "page_size": 16}
    assert store.put(PREFIX_KIND, key, payload)

    fc = FabricClient(pull_timeout_s=5.0, cooldown_s=60.0)
    assert fc.fetch(PREFIX_KIND, key) is None  # peerless: inert
    fc.set_peers([LocalFabricPeer("a", store)])
    assert fc.fetch(PREFIX_KIND, key) == payload
    assert fc.fetch(PREFIX_KIND, "absent-key") is None  # fleet miss
    assert fc.stats["remote_hits"] == 1
    assert fc.stats["pull_bytes"] > 0

    # Dead wire peer: the connect refuses, the fetch degrades, the
    # peer cools down (the second fetch never re-probes it).
    sock = socket.socket()
    sock.bind(("127.0.0.1", 0))
    dead_port = sock.getsockname()[1]
    sock.close()
    fc2 = FabricClient(pull_timeout_s=2.0, cooldown_s=60.0)
    fc2.set_wire_peers([
        {"name": "dead", "host": "127.0.0.1", "port": dead_port},
        {"junk": True},  # malformed row: skipped, not fatal
    ])
    assert len(fc2.peers) == 1
    assert fc2.fetch(PREFIX_KIND, key) is None
    assert fc2.stats["pull_failures"] == 1
    probes = fc2.stats["probes"]
    assert fc2.fetch(PREFIX_KIND, key) is None  # cooled: skipped
    assert fc2.stats["probes"] == probes

    # Refused probe cools the peer the same way.
    fc3 = FabricClient(pull_timeout_s=2.0, cooldown_s=60.0)
    fc3.set_peers([LocalFabricPeer("a", store)])
    with FaultPlan(seed=1).refuse_fabric(op="probe") as plan:
        assert fc3.fetch(PREFIX_KIND, key) is None
    assert plan.fired and fc3.stats["pull_failures"] == 1
    assert fc3.fetch(PREFIX_KIND, key) is None  # still cooling

    # Hung pull: valid bytes arriving PAST the deadline are dropped —
    # honoring them would make the timeout advisory.
    fc4 = FabricClient(pull_timeout_s=0.05, cooldown_s=0.0)
    fc4.set_peers([LocalFabricPeer("a", store)])
    with FaultPlan(seed=1).slow_fabric(0.2) as plan:
        t0 = time.monotonic()
        assert fc4.fetch(PREFIX_KIND, key) is None
    assert plan.fired and time.monotonic() - t0 < 2.0
    assert fc4.stats["remote_hits"] == 0
    assert fc4.stats["pull_failures"] >= 1
    assert fc4.fetch(PREFIX_KIND, key) == payload  # healthy again


def test_pools_decode_score_tier_term():
    """Only tier coverage BEYOND the radix match scores (pages the
    radix holds would never fault back), at TIER_MATCH_WEIGHT — a
    pure-tier full match exactly offsets full occupancy, and a radix
    match still beats a tier match of the same length."""
    from triton_distributed_tpu.serving import pools

    class Rep:
        pending = 0
        max_pending = 8
        free_pages = 0

    r = Rep()
    base = pools.decode_score(r, 0, 32)
    assert pools.decode_score(r, 0, 32, tier_matched=32) == pytest.approx(
        base + pools.TIER_MATCH_WEIGHT
    )
    # Tier coverage the radix already has adds nothing.
    assert pools.decode_score(r, 16, 32, tier_matched=16) == \
        pools.decode_score(r, 16, 32)
    assert pools.decode_score(r, 16, 32, tier_matched=8) == \
        pools.decode_score(r, 16, 32)
    # Radix outranks tier at equal coverage.
    assert pools.decode_score(r, 32, 32) > \
        pools.decode_score(r, 0, 32, tier_matched=32)
    # A saturated replica with a pure-tier full match scores 0 — even
    # with an idle cold one (score 0): tier wins only with headroom.
    sat = Rep()
    sat.pending = 8
    assert pools.decode_score(sat, 0, 32, tier_matched=32) == \
        pytest.approx(0.0)


def test_fleet_scope_tier_fabric_metrics_merge():
    """Satellite (e): merging per-replica expositions keeps each
    child's tdt_tier_*/tdt_fabric_* series intact under its replica
    label — summing across replicas IS the fleet total."""
    from triton_distributed_tpu.obs.metrics import (
        Registry,
        merge_expositions,
        prometheus_text,
    )

    regs = {"r0": Registry(), "r1": Registry()}
    vals = {"r0": {"tdt_tier_hits_total": 3,
                   "tdt_fabric_remote_hits_total": 2,
                   "tdt_fabric_pull_bytes_total": 512,
                   "tdt_tier_remote_pages_total": 2},
            "r1": {"tdt_tier_hits_total": 5,
                   "tdt_fabric_remote_hits_total": 0,
                   "tdt_fabric_pull_bytes_total": 0,
                   "tdt_tier_remote_pages_total": 0}}
    for name, reg in regs.items():
        for metric, v in vals[name].items():
            reg.counter(metric, "test").inc(v)
    merged = merge_expositions(
        {name: prometheus_text(reg) for name, reg in regs.items()},
        label="replica",
    )
    series = {}
    for line in merged.splitlines():
        if line and not line.startswith("#"):
            k, v = line.rsplit(" ", 1)
            series[k] = float(v)
    for name in regs:
        for metric, v in vals[name].items():
            assert series[f'{metric}{{replica="{name}"}}'] == v
    for metric in vals["r0"]:
        total = sum(v for k, v in series.items() if k.startswith(metric))
        assert total == vals["r0"][metric] + vals["r1"][metric]


# -- wire verbs ------------------------------------------------------------


def test_wire_tier_verbs(own_model):
    """``tier_probe`` answers digest membership without touching the
    store's stats/LRU; ``tier_get`` serves the store's wire bytes
    VERBATIM; malformed requests, foreign kinds, and tier-less engines
    all refuse as ``bad_request``."""
    from triton_distributed_tpu.models.continuous import ContinuousEngine
    from triton_distributed_tpu.serving.server import ModelServer, request

    rng = np.random.default_rng(11)
    [r1] = _mk_reqs(rng, n=1)
    eng = _spill_engine(own_model, r1)
    keys = [k for k in eng.tier.keys(PREFIX_KIND)]
    assert keys
    hits_before = eng.tier.stats["hits"]
    srv = ModelServer(eng).start()
    try:
        resp = request(srv.host, srv.port,
                       {"cmd": "tier_probe", "keys": keys + ["absent"]})
        assert resp["have"] == [True] * len(keys) + [False]
        assert eng.tier.stats["hits"] == hits_before  # no LRU/stat touch

        got = request(srv.host, srv.port,
                      {"cmd": "tier_get", "key": keys[0]})
        assert got["found"]
        import base64

        blob = base64.b64decode(got["blob"], validate=True)
        assert blob == eng.tier.get_blob(PREFIX_KIND, keys[0])
        # The served bytes decode through the PR 12 codec under the
        # SAME key — the codec is the transport.
        payload = kv_tier._decode(PREFIX_KIND, keys[0], blob)
        assert chain_digest(payload["chain"]) == keys[0]
        miss = request(srv.host, srv.port,
                       {"cmd": "tier_get", "key": "absent"})
        assert miss == {"found": False}

        for bad in (
            {"cmd": "tier_probe"},  # no keys
            {"cmd": "tier_probe", "keys": []},
            {"cmd": "tier_probe", "keys": [1, 2]},
            {"cmd": "tier_probe", "keys": ["k"] * 257},  # over bound
            {"cmd": "tier_probe", "keys": ["k"], "kind": "snap"},
            {"cmd": "tier_get"},  # no key
            {"cmd": "tier_get", "key": keys[0], "kind": "snap"},
            {"cmd": "tier_peers", "peers": "not-a-list"},
        ):
            with pytest.raises(RuntimeError, match="bad_request"):
                request(srv.host, srv.port, bad)
    finally:
        request(srv.host, srv.port, {"cmd": "shutdown"}, timeout=10.0)
        srv.shutdown()

    # A tier-less engine refuses the whole verb family by name.
    bare = ContinuousEngine(own_model, **MK)
    srv2 = ModelServer(bare).start()
    try:
        with pytest.raises(RuntimeError, match="bad_request.*tier"):
            request(srv2.host, srv2.port,
                    {"cmd": "tier_probe", "keys": ["k"]})
        with pytest.raises(RuntimeError, match="bad_request"):
            request(srv2.host, srv2.port,
                    {"cmd": "tier_peers", "peers": []})
    finally:
        request(srv2.host, srv2.port, {"cmd": "shutdown"}, timeout=10.0)
        srv2.shutdown()


# -- engine: peer fault-back, containment ----------------------------------


def test_fabric_local_miss_remote_hit_bitexact(tp4_model,
                                               fresh_telemetry):
    """The tentpole in-process: engine B's LOCAL tier is cold, its
    peer's tier holds the chain — admission pulls it through the
    fabric, grafts it, and the output is bit-exact vs a tier-less
    golden. The validated entry is ADOPTED into B's tier. On the
    four-device model: the pulled page is grafted into a SHARDED pool
    (the module's other cases keep all four K/V heads on one device)."""
    from triton_distributed_tpu.models.continuous import ContinuousEngine
    from triton_distributed_tpu.obs import events as obs_events
    from triton_distributed_tpu.obs import metrics as obs_metrics

    rng = np.random.default_rng(21)
    [r1] = _mk_reqs(rng, n=1)
    gold = ContinuousEngine(tp4_model, **MK).run([r1])[0]
    a = _spill_engine(tp4_model, r1)

    fc = FabricClient()
    fc.set_peers([LocalFabricPeer("a", a.tier)])
    b = ContinuousEngine(
        tp4_model, tier_bytes=32 << 20, fabric=fc, **MK
    )
    assert not b.tier.may_contain(PREFIX_KIND)  # cold local tier
    np.testing.assert_array_equal(b.run([r1])[0], gold)
    st = b.last_stats
    assert st["tier_remote_pages"] >= 1
    assert st["tier_hits"] >= 1
    assert st["fabric"]["remote_hits"] >= 1
    assert st["prefill_tokens"] < len(r1[0])  # beat re-prefill
    # Adoption: the pulled entries now answer locally (and to peers).
    assert b.tier.may_contain(PREFIX_KIND)
    assert any(b.tier.contains(PREFIX_KIND, k)
               for k in a.tier.keys(PREFIX_KIND))
    kinds = [e.kind for e in obs_events.default_ring().tail(0)[0]]
    assert "fabric_pull" in kinds
    snap = obs_metrics.default_registry().snapshot()
    assert snap["tdt_fabric_remote_hits_total"]["series"][0]["value"] >= 1
    assert snap["tdt_tier_remote_pages_total"]["series"][0]["value"] >= 1
    assert a.audit() == [] and b.audit() == []


def test_fabric_wire_pull_bitexact(own_model):
    """The same pull over the WIRE: peer A behind a live ModelServer,
    B's client wired by tier_peers dicts — first batch on a cold B is
    bit-exact with remote pages faulted through tier_probe/tier_get."""
    from triton_distributed_tpu.models.continuous import ContinuousEngine
    from triton_distributed_tpu.serving.server import ModelServer, request

    rng = np.random.default_rng(31)
    [r1] = _mk_reqs(rng, n=1)
    gold = ContinuousEngine(own_model, **MK).run([r1])[0]
    a = _spill_engine(own_model, r1)
    srv = ModelServer(a).start()
    try:
        fc = FabricClient(pull_timeout_s=5.0)
        b = ContinuousEngine(
            own_model, tier_bytes=32 << 20, fabric=fc, **MK
        )
        # Wire the peer table THROUGH the verb (the supervisor
        # broadcast path) against B's own server.
        srv_b = ModelServer(b).start()
        try:
            resp = request(srv_b.host, srv_b.port, {
                "cmd": "tier_peers",
                "peers": [{"name": "a", "host": srv.host,
                           "port": srv.port}],
            })
            assert resp == {"ok": True, "peers": 1}
            out = request(srv_b.host, srv_b.port, {
                "requests": [np.asarray(r1[0]).tolist()],
                "gen_lens": [r1[1]],
            })
            np.testing.assert_array_equal(out["outputs"][0], gold)
            assert out["stats"]["tier_remote_pages"] >= 1
            assert out["stats"]["fabric"]["remote_hits"] >= 1
        finally:
            request(srv_b.host, srv_b.port, {"cmd": "shutdown"},
                    timeout=10.0)
            srv_b.shutdown()
    finally:
        request(srv.host, srv.port, {"cmd": "shutdown"}, timeout=10.0)
        srv.shutdown()
    assert a.audit() == [] and b.audit() == []


def test_fabric_corrupt_remote_degrades_bitexact(own_model):
    """Chaos: a garbled remote entry dies at the client's CRC check —
    the SAME containment boundary a corrupt local entry crosses — and
    the admission re-prefills bit-exactly. No remote page lands."""
    from triton_distributed_tpu.models.continuous import ContinuousEngine

    rng = np.random.default_rng(41)
    [r1] = _mk_reqs(rng, n=1)
    gold = ContinuousEngine(own_model, **MK).run([r1])[0]
    a = _spill_engine(own_model, r1)
    keys_before = set(a.tier.keys(PREFIX_KIND))

    fc = FabricClient()
    fc.set_peers([LocalFabricPeer("a", a.tier)])
    b = ContinuousEngine(
        own_model, tier_bytes=32 << 20, fabric=fc, **MK
    )
    with FaultPlan(seed=1).corrupt_fabric(times=8) as plan:
        np.testing.assert_array_equal(b.run([r1])[0], gold)
    assert plan.fired
    st = b.last_stats
    assert st["tier_remote_pages"] == 0
    assert st["fabric"]["pull_failures"] >= 1
    assert st["prefill_tokens"] >= len(r1[0]) - MK["page_size"]
    # The PEER's entry is untouched (nothing local to delete, and the
    # fabric never deletes remotely) — the fault was in transit.
    assert set(a.tier.keys(PREFIX_KIND)) == keys_before
    assert a.audit() == [] and b.audit() == []


def test_fabric_hung_and_dead_peer_not_blocking(own_model):
    """A hung peer trips the fetch deadline (late valid bytes are
    discarded) and a dead peer degrades to the local-miss path —
    admission completes bit-exactly either way, promptly."""
    from triton_distributed_tpu.models.continuous import ContinuousEngine

    rng = np.random.default_rng(51)
    [r1] = _mk_reqs(rng, n=1)
    gold = ContinuousEngine(own_model, **MK).run([r1])[0]
    a = _spill_engine(own_model, r1)

    fc = FabricClient(pull_timeout_s=0.05, cooldown_s=60.0)
    fc.set_peers([LocalFabricPeer("a", a.tier)])
    b = ContinuousEngine(
        own_model, tier_bytes=32 << 20, fabric=fc, **MK
    )
    with FaultPlan(seed=1).slow_fabric(0.3, times=8) as plan:
        t0 = time.monotonic()
        np.testing.assert_array_equal(b.run([r1])[0], gold)
    assert plan.fired
    assert time.monotonic() - t0 < 30.0  # stalled pulls never pile up
    assert b.last_stats["tier_remote_pages"] == 0
    assert b.last_stats["fabric"]["pull_failures"] >= 1

    # Dead peer (nothing listening): connect refuses, the peer cools
    # down, the run degrades to plain re-prefill.
    sock = socket.socket()
    sock.bind(("127.0.0.1", 0))
    port = sock.getsockname()[1]
    sock.close()
    fc2 = FabricClient(pull_timeout_s=0.5, cooldown_s=60.0)
    fc2.set_peers([WireFabricPeer("dead", "127.0.0.1", port)])
    c = ContinuousEngine(
        own_model, tier_bytes=32 << 20, fabric=fc2, **MK
    )
    np.testing.assert_array_equal(c.run([r1])[0], gold)
    assert c.last_stats["tier_remote_pages"] == 0
    assert fc2.stats["pull_failures"] >= 1
    assert a.audit() == [] and b.audit() == [] and c.audit() == []


def test_fabric_never_wrong_bits_matrix(own_model):
    """The acceptance contract: checksum-tampered, stale-geometry, and
    foreign-fingerprint peer entries ALL degrade to bit-exact
    re-prefill — the PR 12 validation path runs unchanged on remote
    payloads, and no fabric failure ever deletes the peer's entry."""
    from triton_distributed_tpu.models.continuous import ContinuousEngine

    rng = np.random.default_rng(61)
    [r1] = _mk_reqs(rng, n=1)
    gold = ContinuousEngine(own_model, **MK).run([r1])[0]

    def cold_puller(peer_store):
        fc = FabricClient()
        fc.set_peers([LocalFabricPeer("a", peer_store)])
        return ContinuousEngine(
            own_model, tier_bytes=32 << 20, fabric=fc, **MK
        )

    # 1) checksum-tamper: flip a byte in every peer RAM blob.
    a1 = _spill_engine(own_model, r1)
    with a1.tier._lock:
        for k, blob in list(a1.tier._ram.items()):
            bb = bytearray(blob)
            bb[len(bb) // 2] ^= 0xFF
            a1.tier._ram[k] = bytes(bb)
    b1 = cold_puller(a1.tier)
    np.testing.assert_array_equal(b1.run([r1])[0], gold)
    assert b1.last_stats["tier_remote_pages"] == 0
    assert b1.fabric.stats["pull_failures"] >= 1
    a1.tier.clear()  # drop the hand-garbled blobs before the audit

    # 2) stale geometry: a peer entry spilled under page_size 8 does
    #    not key-match this engine's 16-token page chains at all —
    #    and a re-stamped wrong-geometry payload under the RIGHT key
    #    fails the engine's page_size check after a clean pull.
    a2 = _spill_engine(own_model, r1)
    for k in a2.tier.keys(PREFIX_KIND):
        payload = a2.tier.get(PREFIX_KIND, k)
        payload["page_size"] = 8
        assert a2.tier.put(PREFIX_KIND, k, payload)
    b2 = cold_puller(a2.tier)
    np.testing.assert_array_equal(b2.run([r1])[0], gold)
    assert b2.last_stats["tier_remote_pages"] == 0
    assert b2.fabric.stats["remote_hits"] >= 1  # pulled clean, THEN refused
    # The peer's entries survived the refusal (nothing local to delete).
    assert a2.tier.keys(PREFIX_KIND)

    # 3) foreign model fingerprint (a tier_dir outliving a checkpoint
    #    swap, served over the fabric): refused at the same check.
    a3 = _spill_engine(own_model, r1)
    for k in a3.tier.keys(PREFIX_KIND):
        payload = a3.tier.get(PREFIX_KIND, k)
        payload["model_fp"] = "other-weights"
        assert a3.tier.put(PREFIX_KIND, k, payload)
    b3 = cold_puller(a3.tier)
    np.testing.assert_array_equal(b3.run([r1])[0], gold)
    assert b3.last_stats["tier_remote_pages"] == 0
    for eng in (a1, b1, a2, b2, a3, b3):
        assert eng.audit() == []




# -- placement & warm boot -------------------------------------------------


def test_router_tier_affinity_placement(own_model):
    """The router scores TIER coverage alongside radix coverage: a
    prompt whose pages live only in a replica's tier routes back to
    that replica as ``tier_affinity`` (and faults back there) instead
    of landing least-loaded on a cold one."""
    from triton_distributed_tpu.models.continuous import ContinuousEngine
    from triton_distributed_tpu.serving.router import Router

    rng = np.random.default_rng(71)
    [(p, gen)] = _mk_reqs(rng, n=1)
    gold = ContinuousEngine(own_model, **MK).run([(p, gen)])[0]

    # e0 serves p, then a 4-page prompt evicts p's chain to its TIER.
    e0 = _spill_engine(own_model, (p, gen))
    assert e0.tier.may_contain(PREFIX_KIND)
    toks = [int(t) for t in p]
    assert tier_digest_match_len(e0.tier_digest(), toks) >= 16
    e1 = ContinuousEngine(own_model, tier_bytes=32 << 20, **MK)

    router = Router([e0, e1])
    try:
        # The replicas' published tier digests steer the decision.
        r0 = next(r for r in router.replicas if r.engine is e0)
        assert r0.tier_match_len(toks) >= 16
        assert r0.match_len(toks) < r0.tier_match_len(toks)
        res = router.run([(p, gen)], results=True)
        assert res[0].status == "ok"
        np.testing.assert_array_equal(res[0].tokens, gold)
        st = router.last_stats["router"]
        assert st["tier_affinity_hits"] == 1
        assert st["tier_affinity_hit_tokens"] >= 16
        # It landed on e0 and faulted back from e0's LOCAL tier.
        assert e0.last_stats["tier_hits"] >= 1
        assert router.audit() == []
    finally:
        router.shutdown()


def test_warm_boot_from_shared_dir(own_model, tmp_path):
    """The scale-up arm in miniature: a FRESH engine over the pool's
    shared tier dir (the ``--tier-shared`` shape) serves its FIRST
    batch from the predecessors' spills — tier hits on batch one,
    bit-exact output."""
    from triton_distributed_tpu.models.continuous import ContinuousEngine

    d = str(tmp_path / "fabric")
    rng = np.random.default_rng(81)
    [r1] = _mk_reqs(rng, n=1)
    gold = ContinuousEngine(own_model, **MK).run([r1])[0]
    a = _spill_engine(own_model, r1, tier_dir=d)  # whole chain on disk

    fresh = ContinuousEngine(
        own_model, tier_bytes=32 << 20, tier_dir=d, **MK
    )
    assert fresh.tier.may_contain(PREFIX_KIND)  # disk prescan: warm
    np.testing.assert_array_equal(fresh.run([r1])[0], gold)
    st = fresh.last_stats
    assert st["tier_hits"] >= 1 and st["tier_faults"] >= 1
    assert st["prefill_tokens"] < len(r1[0])  # warm boot beat re-prefill
    assert a.audit() == [] and fresh.audit() == []
