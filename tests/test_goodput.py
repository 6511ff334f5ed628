"""SLO goodput yardstick tests (docs/observability.md "SLO goodput",
docs/serving.md "Streaming & cancellation").

Layers of evidence:

- the streaming WIRE GRAMMAR on a stub server: per-request frame
  indices strictly increasing from 0, monotone wire stamps, a summary
  whose outputs equal the streamed tokens, the pure reference
  generator, AND a non-streaming request for the same payload —
  streaming changes transport, never tokens;
- client-driven cancellation: mid-stream via the cancel verb frees
  the slot's pages (audit clean, pool partition whole) and returns
  the partial tokens with status ``cancelled``; the same verb aborts
  queued and in-flight requests through a REAL ``ContinuousEngine``
  (tiny model) with ``tdt_requests_total{status="cancelled"}`` and a
  ``cancel`` event; the cancel-vs-natural-finish race is sequenced
  deterministically through the ``engine.cancel`` seam;
- chaos: an injected ``stream.send`` drop mid-stream reads as a
  client disconnect — the payload's requests cancel, the engine
  survives bit-exact for the next connection, audits clean;
- loadgen determinism: same seed → same trace, save/load round-trip,
  Zipf head concentration, bursty arrival clumping;
- SLO math: spec evaluation, outcome counting, goodput, the
  missing-duration-on-failure rule, cancelled-excluded denominator;
- exposition merge: replica labels injected (escaping included),
  HELP/TYPE once, values preserved — the pure half of the fleet
  scrape; and (where child processes spawn) the ISSUE-13 acceptance:
  one ``{"cmd": "metrics", "scope": "fleet"}`` scrape against a live
  stub fleet whose per-replica series equal the children's own
  scrapes, plus a replica-tagged ``fleet_seq``-stitched event stream.
"""

import re
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from triton_distributed_tpu.models.stub import StubEngine, stub_generate
from triton_distributed_tpu.obs import metrics as obs_metrics
from triton_distributed_tpu.obs import slo as obs_slo
from triton_distributed_tpu.obs.timeline import Timeline
from triton_distributed_tpu.runtime.faults import FaultPlan
from triton_distributed_tpu.serving.server import (
    ModelServer,
    request,
    request_stream,
)


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

PROMPT = list(range(1, 9))


def _stub_server(**kw):
    eng = StubEngine(num_pages=64, page_size=4,
                     delay_s=kw.pop("delay_s", 0.0))
    server = ModelServer(eng, **kw).start()
    return eng, server


def _pool_whole(eng: StubEngine) -> bool:
    return (len(eng.pool.free) + eng.prefix.node_count
            == eng.pool.num_pages)


# -- streaming wire grammar ------------------------------------------------


def test_stream_wire_grammar_and_token_identity():
    eng, server = _stub_server()
    try:
        payload = {"requests": [PROMPT, list(range(40, 46))],
                   "gen_lens": [6, 4], "ticket_ids": ["a", "b"]}
        frames = list(request_stream(server.host, server.port, payload))
        summary = frames[-1]
        tokens = frames[:-1]
        assert summary["frame"] == "summary"
        assert all(f["frame"] == "token" for f in tokens)
        # Per-request indices strictly increasing from 0; stamps
        # monotone in arrival order (one wire, one clock).
        per_tid: dict = {}
        last_t = 0.0
        for f in tokens:
            assert f["t"] >= last_t
            last_t = f["t"]
            assert f["i"] == len(per_tid.setdefault(f["tid"], []))
            per_tid[f["tid"]].append(f["token"])
        golds = [stub_generate(PROMPT, 6),
                 stub_generate(list(range(40, 46)), 4)]
        assert per_tid["a"] == golds[0] == summary["outputs"][0]
        assert per_tid["b"] == golds[1] == summary["outputs"][1]
        assert summary["ticket_ids"] == ["a", "b"]
        # Wire-side latency entries: TTFT always, TPOT with >= 2 tokens.
        for w in summary["wire"]:
            assert w["ttft_s"] is not None and w["ttft_s"] >= 0
            assert w["tpot_s"] is not None
            assert w["outcome"] == "met"  # no deadlines configured
        # Streaming never changes tokens: the non-streaming response
        # for the same payload is identical.
        plain = request(server.host, server.port, {
            "requests": payload["requests"],
            "gen_lens": payload["gen_lens"],
        })
        assert plain["outputs"] == summary["outputs"]
        assert eng.audit() == [] and _pool_whole(eng)
    finally:
        server.shutdown()


def test_stream_assigns_ticket_ids_when_absent():
    eng, server = _stub_server()
    try:
        frames = list(request_stream(
            server.host, server.port,
            {"requests": [PROMPT], "gen_lens": [3]},
        ))
        summary = frames[-1]
        tids = summary["ticket_ids"]
        assert len(tids) == 1 and isinstance(tids[0], str) and tids[0]
        assert all(f["tid"] == tids[0] for f in frames[:-1])
    finally:
        server.shutdown()


def test_stream_refused_on_fixed_batch_payload():
    eng, server = _stub_server()
    try:
        with pytest.raises(RuntimeError, match="bad_request"):
            list(request_stream(
                server.host, server.port,
                {"input_ids": [PROMPT], "gen_len": 4},
            ))
    finally:
        server.shutdown()


# -- cancellation ----------------------------------------------------------


def test_cancel_mid_stream_frees_pages():
    """ISSUE-13 acceptance: a mid-stream client cancellation tears
    the slot down with a clean audit and pages returned to the pool."""
    eng, server = _stub_server(delay_s=2.0)
    try:
        got: list = []
        done = threading.Event()

        def run():
            try:
                for f in request_stream(
                    server.host, server.port,
                    {"requests": [PROMPT], "gen_lens": [40],
                     "ticket_ids": ["c1"]}, timeout=60,
                ):
                    got.append(f)
            finally:
                done.set()

        t = threading.Thread(target=run, daemon=True)
        t.start()
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and len([
            f for f in got
            if isinstance(f, dict) and f.get("frame") == "token"
        ]) < 2:
            time.sleep(0.01)
        # Second connection, mid-generation: the verb is engine-lock-free.
        resp = request(server.host, server.port,
                       {"cmd": "cancel", "ticket_ids": ["c1"]})
        assert resp["ok"] and resp["requested"] == 1
        assert done.wait(30)
        summary = got[-1]
        assert summary["frame"] == "summary"
        assert summary["results"][0]["status"] == "cancelled"
        n_out = len(summary["outputs"][0])
        assert 0 < n_out < 40
        # Partial tokens are the true prefix of the full generation.
        assert summary["outputs"][0] == stub_generate(PROMPT, 40)[:n_out]
        assert summary["wire"][0]["outcome"] == "cancelled"
        assert eng.last_stats["cancelled_requests"] == 1
        assert eng.audit() == [] and _pool_whole(eng)
    finally:
        server.shutdown()


def test_cancel_through_continuous_engine(own_model, fresh_telemetry):
    """The non-streaming satellite: the cancel set aborts queued AND
    in-flight requests through a REAL ContinuousEngine — today
    ``aborted`` only fired on loop teardown. Deterministic: the
    in-flight cancel is issued from the victim's own on_token callback
    (applied at the next scheduling round), the queued cancel is
    pre-armed before run()."""
    from triton_distributed_tpu.models.continuous import (
        ContinuousEngine,
        Request,
    )
    from triton_distributed_tpu.obs import events as obs_events

    eng = ContinuousEngine(own_model, max_batch=2, page_size=16,
                           max_length=64, prefix_cache=True)
    prompts = [np.arange(1, 9, dtype=np.int32),
               np.arange(20, 28, dtype=np.int32),
               np.arange(30, 38, dtype=np.int32)]
    # Golden for the surviving request, solo.
    [gold] = eng.run([Request(prompts[2], 6)], results=True)
    assert gold.status == "ok" and len(gold.tokens) == 6

    victim = Request(prompts[0], 8, ticket_id="vic")
    victim.on_token = (
        lambda i, tok: eng.cancel(["vic", "queued"]) if i == 1
        else None
    )
    survivor = Request(prompts[2], 6, ticket_id="srv")
    # max_batch=2: the third request queues; its id is cancelled
    # mid-flight by the victim's callback above. The engine.cancel
    # seam sequences the application deterministically (the
    # cancel-vs-finish race's chaos handle) — assert it fired.
    queued = Request(prompts[1], 6, ticket_id="queued")
    plan = FaultPlan(seed=5).slow_cancel(0.01, at=1)
    with plan:
        results = eng.run([victim, survivor, queued], results=True)
    assert ("engine.cancel" in [s for s, _, _ in plan.fired])
    assert results[0].status == "cancelled"
    assert 2 <= len(results[0].tokens) < 8  # partial tokens kept
    assert results[1].status == "ok"
    assert results[1].tokens.tolist() == gold.tokens.tolist()
    assert results[2].status == "cancelled"
    assert len(results[2].tokens) == 0  # never admitted
    assert eng.stats["cancelled_requests"] == 2
    assert eng.stats["failed_requests"] == 0
    assert eng.audit() == []
    # Telemetry: the status label + the cancel events.
    reqs = obs_metrics.default_registry().get("tdt_requests_total")
    assert reqs.value(status="cancelled") == 2
    evts, _ = obs_events.default_ring().tail(kind="cancel")
    assert len(evts) >= 2  # the verb-level + per-request events


def test_cancel_through_router_by_client_id():
    """Through a Router a client id rides as ``client_tid`` NEXT TO
    the ticket's unique wire id (so reused ids can't conflate a child
    batch): the cancel verb must still find and tear down the
    in-flight request by the id the client holds."""
    from triton_distributed_tpu.serving.router import Router

    eng = StubEngine(num_pages=64, page_size=4, delay_s=2.0)
    router = Router([eng])
    server = ModelServer(router).start()
    try:
        got: list = []
        done = threading.Event()

        def run():
            try:
                for f in request_stream(
                    server.host, server.port,
                    {"requests": [PROMPT], "gen_lens": [40],
                     "ticket_ids": ["rc1"]}, timeout=60,
                ):
                    got.append(f)
            finally:
                done.set()

        threading.Thread(target=run, daemon=True).start()
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and len(got) < 2:
            time.sleep(0.01)
        request(server.host, server.port,
                {"cmd": "cancel", "ticket_ids": ["rc1"]})
        assert done.wait(30)
        summary = got[-1]
        assert summary["frame"] == "summary"
        assert summary["results"][0]["status"] == "cancelled"
        assert summary["ticket_ids"] == ["rc1"]  # client id echoed
        assert 0 < len(summary["outputs"][0]) < 40
        assert eng.audit() == [] and _pool_whole(eng)
    finally:
        server.shutdown()


def test_cancel_race_with_finish_is_clean():
    """Cancel racing a slot's natural finish: issued at the LAST
    token, so by the time the engine looks the request already
    finished — the cancel must simply lose (full tokens delivered,
    nothing leaks, audit clean)."""
    eng = StubEngine(num_pages=64, page_size=4)
    from triton_distributed_tpu.models.continuous import Request

    req = Request(np.asarray(PROMPT, np.int32), 4, ticket_id="late")
    req.on_token = (
        lambda i, tok: eng.cancel(["late"]) if i == 3 else None
    )
    [r] = eng.run([req], results=True)
    assert r.status == "ok"
    assert r.tokens.tolist() == stub_generate(PROMPT, 4)
    assert eng.audit() == [] and _pool_whole(eng)


def test_stream_drop_chaos_cancels_and_server_survives():
    """An injected ``stream.send`` drop mid-stream reads as a client
    disconnect: the sink goes broken, the payload's requests cancel
    (pages home), the summary still reports the truth on the (here
    still-healthy) socket, and the NEXT request is served bit-exact —
    the chaos contract."""
    eng, server = _stub_server(delay_s=0.5)
    try:
        plan = FaultPlan(seed=7).drop_stream(at=3)
        with plan:
            frames = list(request_stream(
                server.host, server.port,
                {"requests": [PROMPT], "gen_lens": [40],
                 "ticket_ids": ["d1"]}, timeout=60,
            ))
        assert [s for s, _, _ in plan.fired] == ["stream.send"]
        # Exactly 2 frames made the wire (the 3rd write "failed").
        tokens = [f for f in frames if f.get("frame") == "token"]
        assert len(tokens) == 2
        summary = frames[-1]
        assert summary["frame"] == "summary"
        assert summary["results"][0]["status"] == "cancelled"
        assert len(summary["outputs"][0]) < 40
        assert eng.last_stats["cancelled_requests"] == 1
        assert eng.audit() == [] and _pool_whole(eng)
        # Survivor: a fresh request on a fresh connection, bit-exact.
        r = request(server.host, server.port,
                    {"requests": [PROMPT], "gen_lens": [5]})
        assert r["outputs"][0] == stub_generate(PROMPT, 5)
    finally:
        server.shutdown()


def test_stream_resume_from_snapshot_streams_live(fresh_telemetry):
    """A payload-carried snapshot seeds the stream sink: post-resume
    tokens stream LIVE from the snapshot's index (the client already
    holds the restored prefix), and the summary still carries the
    full output."""
    eng, server = _stub_server()
    try:
        restored = stub_generate(PROMPT, 3)
        snap = {"stub": True, "prompt": list(PROMPT), "out": restored,
                "gen_len": 8, "trace_id": None, "exported_at": 0.0}
        frames = list(request_stream(server.host, server.port, {
            "requests": [PROMPT], "gen_lens": [8],
            "snapshots": [snap],
        }))
        tokens = [f for f in frames if f.get("frame") == "token"]
        summary = frames[-1]
        # Frames start AT the resume index — nothing re-sent, nothing
        # deferred to a summary burst.
        assert [f["i"] for f in tokens] == [3, 4, 5, 6, 7]
        assert summary["outputs"][0] == stub_generate(PROMPT, 8)
        assert summary["results"][0]["status"] == "ok"
    finally:
        server.shutdown()


def test_migrated_results_not_judged(fresh_telemetry):
    """A handoff export (status ``migrated``) is NON-terminal: it must
    not count as an SLO miss — the re-dispatched completion is judged
    exactly once."""
    eng, server = _stub_server()
    try:
        eng.request_handoff()  # the batch exports instead of finishing
        r = request(server.host, server.port,
                    {"requests": [PROMPT], "gen_lens": [6]})
        assert r["results"][0]["status"] == "migrated"
        slo = request(server.host, server.port, {"cmd": "slo"})["slo"]
        cls = slo["classes"]["default"]
        assert cls["missed"] == 0 and cls["met"] == 0
    finally:
        server.shutdown()


# -- load generator --------------------------------------------------------


def test_loadgen_deterministic_and_replayable(tmp_path):
    from perf.loadgen import (
        LoadSpec,
        generate_trace,
        load_trace,
        save_trace,
    )

    spec = LoadSpec(rate=5.0, n_requests=64, cancel_frac=0.25, seed=11)
    t1 = generate_trace(spec)
    t2 = generate_trace(spec)
    assert t1 == t2  # same seed → same trace, byte for byte
    assert t1 != generate_trace(LoadSpec(rate=5.0, n_requests=64,
                                         cancel_frac=0.25, seed=12))
    path = tmp_path / "run.loadtrace.jsonl"
    save_trace(str(path), t1, spec)
    loaded, spec_dict = load_trace(str(path))
    assert loaded == t1
    assert spec_dict["seed"] == 11
    # Zipf head: the most common prefix dominates a uniform share.
    from collections import Counter

    counts = Counter(r["prefix_id"] for r in t1)
    assert counts.most_common(1)[0][1] > len(t1) / spec.prefix_pool * 2
    # Long-tail output lengths stay in bounds; cancels marked.
    assert all(spec.gen_min <= r["gen_len"] <= spec.gen_max for r in t1)
    n_cancel = sum(r["cancel_after"] is not None for r in t1)
    assert 0 < n_cancel < len(t1)
    # Arrivals sorted; bursty process clumps them.
    assert [r["t"] for r in t1] == sorted(r["t"] for r in t1)
    bursty = generate_trace(LoadSpec(rate=5.0, n_requests=32,
                                     process="bursty", burst_size=4,
                                     seed=11))
    gaps = np.diff([r["t"] for r in bursty])
    assert (gaps == 0).sum() >= len(bursty) // 2  # in-burst arrivals


def _doc_spec(**kw):
    import perf.loadgen as lg

    kw.setdefault("n_requests", 12)
    kw.setdefault("seed", 3)
    kw.setdefault("doc_min", 64)
    kw.setdefault("doc_max", 96)
    return lg.LoadSpec(**kw)


def test_document_class_draws():
    """The document class lands 10k-scale bodies (shrunk here) on its
    rows only, deterministically per seed."""
    import perf.loadgen as lg

    spec = _doc_spec(
        class_mix=(("interactive", 2.0), ("document", 1.0))
    )
    a = lg.generate_trace(spec)
    b = lg.generate_trace(spec)
    assert a == b  # same-seed-identical
    docs = [r for r in a if r["slo_class"] == "document"]
    rest = [r for r in a if r["slo_class"] != "document"]
    assert docs and rest
    for r in docs:
        assert len(r["prompt"]) >= spec.prefix_len + spec.doc_min
    for r in rest:
        assert len(r["prompt"]) <= spec.prefix_len + spec.suffix_max


def test_document_class_stream_compatible():
    """The rng-stream contract: document draws land strictly AFTER all
    pre-existing draws, so a mix WITHOUT the class consumes the stream
    exactly as before — and the doc knobs are inert on such specs."""
    import perf.loadgen as lg

    base = _doc_spec(class_mix=(("interactive", 1.0),))
    tweaked = _doc_spec(
        class_mix=(("interactive", 1.0),), doc_min=100, doc_max=200
    )
    assert lg.generate_trace(base) == lg.generate_trace(tweaked)
    # Adding the document class changes only class labels and the
    # relabeled rows' prompts — arrivals and gen_lens are upstream
    # draws and stay identical.
    mixed = lg.generate_trace(
        _doc_spec(class_mix=(("interactive", 1.0), ("document", 1.0)))
    )
    plain = lg.generate_trace(base)
    assert [r["t"] for r in mixed] == [r["t"] for r in plain]
    assert [r["gen_len"] for r in mixed] == [r["gen_len"] for r in plain]


def test_document_class_jsonl_roundtrip(tmp_path):
    """save_trace → load_trace is lossless for document rows, and
    parse_classes speaks the CLI wire format."""
    import perf.loadgen as lg

    assert lg.parse_classes("interactive:4,document:1") == (
        ("interactive", 4.0), ("document", 1.0),
    )
    assert lg.parse_classes("document") == (("document", 1.0),)
    assert lg.parse_classes("") == ()
    spec = _doc_spec(
        class_mix=(("interactive", 1.0), ("document", 1.0))
    )
    trace = lg.generate_trace(spec)
    path = str(tmp_path / "doc.jsonl")
    lg.save_trace(path, trace, spec)
    back, spec_dict = lg.load_trace(path)
    assert back == trace
    assert spec_dict["doc_min"] == spec.doc_min
    assert tuple(map(tuple, spec_dict["class_mix"])) == spec.class_mix


# -- SLO math --------------------------------------------------------------


def _wire_tl(ttft=0.1, n=5, tpot=0.02, status="ok", enq=100.0):
    tl = Timeline()
    tl.enqueue_t = enq
    t = enq + ttft
    for _ in range(n):
        tl.first_token_t = tl.first_token_t or t
        tl.token_ts.append(t)
        t += tpot
    tl.tokens_out = n
    tl.finish_t = None
    tl.status = None
    tl.finish(status)
    # finish() stamped wall time; pin it for deterministic e2e math.
    tl.finish_t = t
    return tl


def test_slo_spec_evaluation_and_goodput(fresh_telemetry):
    reg = obs_metrics.default_registry()
    spec = obs_slo.SLOSpec("interactive", ttft_s=0.2, tpot_s=0.05,
                           e2e_s=1.0)
    assert obs_slo.observe_wire(_wire_tl(), spec, reg) == "met"
    assert obs_slo.observe_wire(_wire_tl(ttft=0.5), spec, reg) == "missed"
    assert obs_slo.observe_wire(
        _wire_tl(tpot=0.2), spec, reg) == "missed"
    # A FAILED request with an unmeasurable deadline counts violated
    # (shedding must not read as goodput)...
    failed = Timeline()
    failed.enqueue_t = 1.0
    failed.finish("overloaded")
    assert obs_slo.observe_wire(failed, spec, reg) == "missed"
    # ...but an OK request missing only inapplicable durations passes
    # on what IS measured (1-token answer: no TPOT).
    one = _wire_tl(n=1)
    assert obs_slo.observe_wire(one, spec, reg) == "met"
    # Cancelled: counted, excluded from the goodput denominator.
    assert obs_slo.observe_wire(
        _wire_tl(status="cancelled"), spec, reg) == "cancelled"
    assert obs_slo.goodput("interactive", reg) == pytest.approx(2 / 5)
    snap = obs_slo.snapshot({"interactive": spec}, reg)
    cls = snap["classes"]["interactive"]
    assert cls["met"] == 2 and cls["missed"] == 3
    assert cls["cancelled"] == 1
    assert cls["violations"]["ttft"] >= 2  # ttft=0.5 + the failed one
    assert cls["ttft_p50_s"] is not None
    assert snap["specs"]["interactive"]["ttft_s"] == 0.2


def test_server_surfaces_slo_spec_and_verb(fresh_telemetry):
    eng = StubEngine(num_pages=64, page_size=4)
    server = ModelServer(
        eng, slo=obs_slo.SLOSpec("default", ttft_s=10.0)
    ).start()
    try:
        stats = request(server.host, server.port, {"cmd": "stats"})
        assert stats["stats"]["server"]["engine"]["slo"]["default"][
            "ttft_s"] == 10.0
        list(request_stream(server.host, server.port,
                            {"requests": [PROMPT], "gen_lens": [4]}))
        slo = request(server.host, server.port, {"cmd": "slo"})["slo"]
        assert slo["classes"]["default"]["met"] == 1
        assert slo["classes"]["default"]["goodput"] == 1.0
    finally:
        server.shutdown()


# -- fleet-scope aggregation -----------------------------------------------

_SAMPLE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[a-zA-Z_][a-zA-Z0-9_]*="
    r"\"(?:[^\"\\\n]|\\[\\\"n])*\""
    r"(,[a-zA-Z_][a-zA-Z0-9_]*=\"(?:[^\"\\\n]|\\[\\\"n])*\")*\})? "
    r"-?[0-9.e+-]+(\s[0-9]+)?$"
)


def _parse_series(text: str) -> dict:
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        assert _SAMPLE.match(line), f"bad exposition line: {line!r}"
        name_labels, value = line.rsplit(" ", 1)
        out[name_labels] = float(value)
    return out


def test_merge_expositions_labels_escaping_and_values():
    from triton_distributed_tpu.obs.metrics import merge_expositions

    a = ("# HELP x_total things\n# TYPE x_total counter\n"
         'x_total{verb="ping"} 3\nx_total{verb="stats"} 1\n'
         "# TYPE h histogram\n"
         'h_bucket{le="1"} 2\nh_bucket{le="+Inf"} 2\n'
         "h_sum 0.5\nh_count 2\n")
    b = ("# HELP x_total things\n# TYPE x_total counter\n"
         'x_total{verb="ping"} 4\n')
    merged = merge_expositions({'r0#2"\\': a, "r1": b}, label="replica")
    series = _parse_series(merged)
    # Replica label injected first, value preserved, escapes legal.
    assert series['x_total{replica="r0#2\\"\\\\",verb="ping"}'] == 3
    assert series['x_total{replica="r1",verb="ping"}'] == 4
    # Histogram children follow their family; sums ride through.
    assert series['h_bucket{replica="r0#2\\"\\\\",le="+Inf"}'] == 2
    assert series['h_sum{replica="r0#2\\"\\\\"}'] == 0.5
    # HELP/TYPE once per family.
    assert merged.count("# TYPE x_total counter") == 1
    # Summing across replica labels reproduces the children's totals.
    ping_sum = sum(v for k, v in series.items()
                   if k.startswith("x_total") and 'verb="ping"' in k)
    assert ping_sum == 7


@needs_procs
def test_fleet_scope_scrape_sums_and_stitched_events():
    """ISSUE-13 acceptance: one fleet-scope scrape returns a valid
    Prometheus exposition whose per-replica series equal the
    children's own scrapes; fleet events come back replica-tagged and
    fleet_seq-stitched."""
    from triton_distributed_tpu.serving.supervisor import (
        FleetSupervisor,
        stub_spec,
    )

    # round_robin: BOTH children serve (affinity would pin repeats to
    # one); 6-page pools force radix evictions by the 3rd request per
    # child, so the children's own event rings carry prefix_evict
    # events for the stitched stream.
    sup = FleetSupervisor([
        stub_spec(f"r{i}", delay_s=0.0, num_pages=6, page_size=4)
        for i in range(2)
    ], policy="round_robin")
    router = sup.start()
    server = ModelServer(router).start()
    try:
        assert sup.wait_healthy(2, timeout_s=120)
        for k in range(8):
            prompt = [10 * k + j for j in range(1, 9)]
            r = request(server.host, server.port,
                        {"requests": [prompt], "gen_lens": [4]},
                        timeout=120)
            assert r["outputs"][0] == stub_generate(prompt, 4)
        fleet = request(server.host, server.port,
                        {"cmd": "metrics", "scope": "fleet"},
                        timeout=120)
        assert fleet["scope"] == "fleet"
        assert sorted(fleet["replicas"]) == ["r0", "r1"]
        assert fleet["errors"] == {}
        merged = _parse_series(fleet["prometheus"])  # validates grammar
        # Per-replica series must equal each child's OWN scrape (no
        # generation traffic ran in between; the requests-verb counter
        # is stable across the probe scrapes).
        for slot in sup._slots:
            rep = slot.replica
            own = request(rep._remote.host, rep._remote.port,
                          {"cmd": "metrics"}, timeout=120)
            own_series = _parse_series(own["prometheus"])
            key = 'tdt_server_requests_total{verb="requests"}'
            want = own_series.get(key)
            assert want is not None and want >= 1
            got = merged.get(
                f'tdt_server_requests_total{{replica="{rep.name}",'
                f'verb="requests"}}'
            )
            assert got == want, (rep.name, got, want)
        # The front's own series ride along under replica="router";
        # series already carrying a replica label (the router's
        # per-child ledger) keep THEIRS — no duplicate label names.
        assert any(k.startswith('tdt_server_requests_total{'
                                'replica="router"')
                   for k in merged)
        assert not any(k.count('replica="') > 1 for k in merged)
        # Fleet events: replica-tagged, fleet_seq strictly increasing,
        # child events present (the tiny pools evicted), and the
        # per-child cursors page forward (a second scrape re-returns
        # no child events).
        ev = request(server.host, server.port,
                     {"cmd": "events", "scope": "fleet"}, timeout=120)
        rows = ev["events"]
        assert rows, "fleet events empty after traffic"
        seqs = [e["fleet_seq"] for e in rows]
        assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
        replicas = {e["replica"] for e in rows}
        assert "router" in replicas
        assert replicas & {"r0", "r1"}, rows
        ev2 = request(server.host, server.port,
                      {"cmd": "events", "scope": "fleet"}, timeout=120)
        ev2_replicas = {e["replica"] for e in ev2["events"]}
        assert "r0" not in ev2_replicas and "r1" not in ev2_replicas
    finally:
        server.shutdown()
        sup.shutdown()
