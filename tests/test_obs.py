"""Telemetry subsystem tests (ISSUE 5, docs/observability.md).

Covers the four obs/ pillars and their serving integration:
histogram bucket math against numpy percentiles, registry
thread-safety, event-ring overflow/seq continuity, Prometheus
exposition grammar, per-request timelines (TTFT/TPOT/queue-wait/e2e
with PR 3 status labels) from a real multi-request
``ContinuousEngine.run()``, the unified core ``last_stats`` schema,
``trace_span``'s numeric-native event-ring mirror, and the server's
``metrics``/``events`` verbs — including a scrape answered
MID-generation.
"""

import re
import threading
import time

import numpy as np
import pytest

from triton_distributed_tpu import obs
from triton_distributed_tpu.obs import events as obs_events
from triton_distributed_tpu.obs import metrics as obs_metrics
from triton_distributed_tpu.obs.metrics import (
    Registry,
    log_buckets,
    prometheus_text,
)
from triton_distributed_tpu.obs.timeline import Timeline, observe_request


@pytest.fixture(autouse=True)
def _fresh_telemetry(fresh_telemetry):
    """Every test here asserts absolute totals — make the shared
    reset fixture (tests/conftest.py) autouse file-wide."""
    yield


# -- metrics registry ------------------------------------------------------


def test_counter_gauge_basics():
    reg = Registry(enabled=True)
    c = reg.counter("t_total", "help", labels=("verb",))
    c.inc(verb="a")
    c.inc(2, verb="a")
    c.inc(verb="b")
    assert c.value(verb="a") == 3 and c.value(verb="b") == 1
    with pytest.raises(ValueError):
        c.inc(-1, verb="a")  # counters only go up
    with pytest.raises(ValueError):
        c.inc(wrong="label")
    g = reg.gauge("t_gauge")
    g.set(5)
    g.add(-2)
    assert g.value() == 3
    # Same name + kind + labels: the SAME family (engines re-register).
    assert reg.counter("t_total", labels=("verb",)) is c
    with pytest.raises(ValueError):
        reg.gauge("t_total")  # kind mismatch
    with pytest.raises(ValueError):
        reg.counter("t_total", labels=("other",))  # label mismatch
    h = reg.histogram("t_seconds", buckets=(1.0, 10.0))
    assert reg.histogram("t_seconds", buckets=(1.0, 10.0)) is h
    with pytest.raises(ValueError):
        reg.histogram("t_seconds", buckets=(1.0, 100.0))  # bucket mismatch


def test_histogram_percentiles_vs_numpy():
    """Bucket-derived p50/p90/p99 stay within one log-bucket's width of
    exact numpy percentiles — the accuracy contract fixed edges buy."""
    per_decade = 4
    factor = 10 ** (1 / per_decade)
    reg = Registry(enabled=True)
    h = reg.histogram(
        "t_lat", buckets=log_buckets(1e-4, 100.0, per_decade)
    )
    rng = np.random.default_rng(7)
    samples = rng.lognormal(mean=-3.0, sigma=1.2, size=20_000)
    for s in samples:
        h.observe(float(s))
    assert h.count() == len(samples)
    for q in (0.50, 0.90, 0.99):
        est = h.quantile(q)
        true = float(np.percentile(samples, q * 100))
        assert true / factor <= est <= true * factor, (
            f"p{int(q * 100)}: est {est} vs true {true}"
        )
    # Empty series has no quantiles.
    assert reg.histogram("t_empty").quantile(0.5) is None


def test_histogram_overflow_bucket_clamps():
    reg = Registry(enabled=True)
    h = reg.histogram("t_of", buckets=(1.0, 10.0))
    h.observe(1e9)
    assert h.quantile(0.5) == 10.0  # clamped to the last finite edge
    snap = reg.snapshot()["t_of"]["series"][0]
    assert snap["count"] == 1 and snap["buckets"]["counts"][-1] == 1


def test_registry_thread_safety():
    """Concurrent increments/observations from many threads lose
    nothing: totals are exact, not approximate."""
    reg = Registry(enabled=True)
    c = reg.counter("t_total")
    h = reg.histogram("t_h", buckets=(1.0, 2.0, 4.0))
    N, T = 5_000, 8

    def work():
        for i in range(N):
            c.inc()
            h.observe(float(i % 5))

    threads = [threading.Thread(target=work) for _ in range(T)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert c.value() == N * T
    assert h.count() == N * T


def test_disabled_mode_is_noop():
    obs.set_enabled(False)
    obs_metrics.counter("t_off_total").inc(5)
    obs_metrics.histogram("t_off_h").observe(1.0)
    seq = obs_events.emit("e", x=1)
    assert seq == 0
    obs.set_enabled(True)
    assert obs_metrics.counter("t_off_total").value() == 0
    assert obs_metrics.histogram("t_off_h").count() == 0
    assert obs_events.default_ring().tail(0)[0] == []


# -- exposition grammar ----------------------------------------------------

_COMMENT_RE = re.compile(r"^# (HELP|TYPE) [a-zA-Z_:][a-zA-Z0-9_:]*( .*)?$")
_SAMPLE_RE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"
    r'(\{[a-zA-Z_][a-zA-Z0-9_]*="(\\.|[^"\\])*"'
    r'(,[a-zA-Z_][a-zA-Z0-9_]*="(\\.|[^"\\])*")*\})?'
    r" (-?[0-9]+(\.[0-9]+)?([eE][+-]?[0-9]+)?|[+-]Inf|NaN)$"
)


def assert_prometheus_parses(text: str) -> dict:
    """Every line matches the exposition grammar; returns
    ``{metric_name: [sample lines]}`` for follow-on assertions."""
    samples: dict = {}
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("#"):
            assert _COMMENT_RE.match(line), f"bad comment line: {line!r}"
            continue
        assert _SAMPLE_RE.match(line), f"bad sample line: {line!r}"
        name = re.split(r"[{ ]", line, 1)[0]
        samples.setdefault(name, []).append(line)
    return samples


def test_prometheus_text_grammar_and_consistency():
    reg = Registry(enabled=True)
    reg.counter("t_req_total", "requests", labels=("verb",)).inc(
        3, verb="ping"
    )
    # Label values needing escapes must not break the grammar.
    reg.counter("t_req_total", labels=("verb",)).inc(
        verb='we"ird\\label\nvalue'
    )
    reg.gauge("t_pages", "free pages").set(17.5)
    h = reg.histogram("t_lat_seconds", "latency", labels=("status",),
                      buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):
        h.observe(v, status="ok")
    text = prometheus_text(reg)
    samples = assert_prometheus_parses(text)
    assert "t_req_total" in samples and "t_pages" in samples
    # Histogram exposition: cumulative buckets, +Inf == _count.
    buckets = samples["t_lat_seconds_bucket"]
    counts = [int(line.rsplit(" ", 1)[1]) for line in buckets]
    assert counts == sorted(counts), "bucket counts must be cumulative"
    assert 'le="+Inf"' in buckets[-1]
    count_line = samples["t_lat_seconds_count"][0]
    assert int(count_line.rsplit(" ", 1)[1]) == counts[-1] == 5
    sum_line = samples["t_lat_seconds_sum"][0]
    assert float(sum_line.rsplit(" ", 1)[1]) == pytest.approx(56.05)


# -- event ring ------------------------------------------------------------


def test_ring_overflow_and_seq_continuity():
    ring = obs_events.EventRing(capacity=16, enabled=True)
    for i in range(100):
        ring.emit("tick", i=i)
    evts, dropped = ring.tail(0)
    assert len(evts) == 16 and dropped == 84
    seqs = [e.seq for e in evts]
    assert seqs == list(range(85, 101)), "survivors are the NEWEST 16"
    assert [e.fields["i"] for e in evts] == list(range(84, 100))
    # Drop-free incremental tailing: a consumer keeping up sees gaps
    # of exactly zero.
    last = seqs[-1]
    ring.emit("tick", i=100)
    evts2, dropped2 = ring.tail(last)
    assert dropped2 == 0 and [e.seq for e in evts2] == [last + 1]
    # A consumer that stalled past capacity sees the drop count.
    for i in range(40):
        ring.emit("tick", i=200 + i)
    evts3, dropped3 = ring.tail(last + 1)
    assert dropped3 == 40 - 16 + 0 and len(evts3) == 16
    # limit is a page size: it keeps the OLDEST available, dropped
    # counts only ring-overwritten events, and paging on the returned
    # seqs walks the whole backlog without skipping anything.
    evts4, dropped4 = ring.tail(0, limit=4)
    assert len(evts4) == 4
    assert dropped4 == evts4[0].seq - 1  # only the overwritten prefix
    paged = list(evts4)
    while True:
        page, d = ring.tail(paged[-1].seq, limit=4)
        assert d == 0  # nothing overwritten mid-pagination
        if not page:
            break
        paged.extend(page)
    full, _ = ring.tail(evts4[0].seq - 1)
    assert [e.seq for e in paged] == [e.seq for e in full]
    # A negative cursor clamps to 0 — never phantom `dropped` counts
    # beyond what the ring actually overwrote.
    neg_evts, neg_dropped = ring.tail(-100)
    zero_evts, zero_dropped = ring.tail(0)
    assert [e.seq for e in neg_evts] == [e.seq for e in zero_evts]
    assert neg_dropped == zero_dropped


def test_ring_timestamps_monotonic():
    ring = obs_events.EventRing(capacity=8, enabled=True)
    ring.emit("a")
    time.sleep(0.002)
    ring.emit("b")
    evts, _ = ring.tail(0)
    assert evts[0].t <= evts[1].t


def test_ring_tail_kind_filter():
    """ISSUE 8 satellite: ``tail(kind=...)`` pulls one event stream
    server-side. The filter applies after the drop count (overwritten
    events' kinds are unknowable) and before ``limit`` (a page is
    ``limit`` MATCHING events)."""
    ring = obs_events.EventRing(capacity=32, enabled=True)
    for i in range(10):
        ring.emit("span", i=i)
        ring.emit("fault", i=i)
    spans, dropped = ring.tail(0, kind="span")
    assert dropped == 0 and len(spans) == 10
    assert all(e.kind == "span" for e in spans)
    assert [e.fields["i"] for e in spans] == list(range(10))
    # limit counts MATCHING events, not scanned events.
    page, _ = ring.tail(0, limit=3, kind="fault")
    assert [e.fields["i"] for e in page] == [0, 1, 2]
    assert all(e.kind == "fault" for e in page)
    # Paging by the returned seq walks the filtered stream completely.
    got = list(page)
    while True:
        page, d = ring.tail(got[-1].seq, limit=3, kind="fault")
        assert d == 0
        if not page:
            break
        got.extend(page)
    assert [e.fields["i"] for e in got] == list(range(10))
    # No matches at all: empty page, drop count still exact.
    none, d = ring.tail(0, kind="nope")
    assert none == [] and d == 0
    # Drop accounting is unchanged by the filter: overflow the ring.
    ring2 = obs_events.EventRing(capacity=8, enabled=True)
    for i in range(20):
        ring2.emit("a" if i % 2 else "b", i=i)
    filt, dropped2 = ring2.tail(0, kind="a")
    allv, dropped_all = ring2.tail(0)
    assert dropped2 == dropped_all == 12
    assert [e.fields["i"] for e in filt] == [
        e.fields["i"] for e in allv if e.kind == "a"
    ]


# -- trace_span → event ring -------------------------------------------------


def test_trace_span_numeric_args_survive_in_ring():
    """Regression (ISSUE 5 satellite): float span args — e.g. spec
    accept rates — must land in the event ring as NUMBERS, whatever
    the profiler's metadata does with them."""
    from triton_distributed_tpu.runtime.profiling import trace_span

    with trace_span("t:span", slot=3, rate=0.375, tag=[1, 2]):
        pass
    evts, _ = obs_events.default_ring().tail(0)
    spans = [e for e in evts if e.kind == "span"
             and e.fields.get("name") == "t:span"]
    assert len(spans) == 1
    f = spans[0].fields
    assert f["slot"] == 3 and isinstance(f["slot"], int)
    assert f["rate"] == 0.375 and isinstance(f["rate"], float)
    assert f["tag"] == "[1, 2]"  # non-numerics stringify
    assert isinstance(f["dur_s"], float) and f["dur_s"] >= 0.0
    # _ring=False: sites with a dedicated richer event (spec_verify)
    # opt out of the duplicate span entry.
    with trace_span("t:quiet", slot=1, _ring=False):
        pass
    evts, _ = obs_events.default_ring().tail(0)
    assert not any(e.fields.get("name") == "t:quiet" for e in evts
                   if e.kind == "span")
    # Arg keys colliding with the event's own fields survive under a
    # ctx_ prefix instead of silently dropping the span event.
    with trace_span("t:clash", dur_s=9.0, kind="x"):
        pass
    evts, _ = obs_events.default_ring().tail(0)
    clash = [e for e in evts if e.kind == "span"
             and e.fields.get("name") == "t:clash"]
    assert len(clash) == 1
    assert clash[0].fields["ctx_dur_s"] == 9.0
    assert clash[0].fields["ctx_kind"] == "x"
    assert clash[0].fields["dur_s"] >= 0.0


@pytest.mark.parametrize("where", ["construct", "enter", "exit"])
def test_trace_span_profiler_failure_never_sinks_body(monkeypatch, where):
    """A profiler API mismatch costs the span, never the body: one
    conversion, one ``try`` (the type ladder went with PR 26). The
    ring mirror keeps the span and its native numerics all the same."""
    from triton_distributed_tpu.runtime import profiling

    seen = []

    class Broken:
        def __init__(self, name, **kwargs):
            seen.append(kwargs)
            if where == "construct":
                raise RuntimeError("profiler API mismatch")

        def __enter__(self):
            if where == "enter":
                raise RuntimeError("profiler API mismatch")
            return self

        def __exit__(self, *exc):
            if where == "exit":
                raise RuntimeError("profiler API mismatch")
            return False

    monkeypatch.setattr(profiling.jax.profiler, "TraceAnnotation", Broken)
    ran = []
    with profiling.trace_span("t:broken", rate=0.5, slot=3, tag=[1]):
        ran.append(1)
    assert ran == [1]
    # One construction, typed primitives kept, the rest stringified.
    assert seen == [{"rate": 0.5, "slot": 3, "tag": "[1]"}]
    evts, _ = obs_events.default_ring().tail(0)
    mine = [e for e in evts if e.kind == "span"
            and e.fields.get("name") == "t:broken"]
    assert len(mine) == 1 and mine[0].fields["rate"] == 0.5
    with pytest.raises(KeyError):
        with profiling.trace_span("t:raises"):
            raise KeyError("body exceptions propagate")


# -- timelines ---------------------------------------------------------------


def test_timeline_math_and_latch_once():
    tl = Timeline()
    tl.enqueue_t = 100.0
    tl.admit_t = 100.5
    tl.first_chunk_t = 100.75
    tl.first_token_t = 101.0
    tl.finish_t = 103.0
    tl.tokens_out = 5
    assert tl.queue_wait_s == 0.5
    assert tl.prefill_dispatch_s == 0.25
    assert tl.ttft_s == 1.0
    assert tl.e2e_s == 3.0
    assert tl.tpot_s == pytest.approx(2.0 / 4)
    # The latch is on status: first finish() wins, and the manually
    # set finish_t stamp is kept (stamps latch on first write).
    assert tl.finish("ok") is True
    assert tl.finish_t == 103.0
    tl2 = Timeline()
    tl2.stamp_enqueue()
    assert tl2.finish("failed") is True
    assert tl2.finish("ok") is False and tl2.status == "failed"
    # A 1-token request has no decode phase → no TPOT sample.
    tl3 = Timeline()
    tl3.enqueue_t, tl3.first_token_t, tl3.finish_t = 0.0, 1.0, 2.0
    tl3.tokens_out = 1
    assert tl3.tpot_s is None


def test_observe_request_skips_missing_stamps():
    reg = Registry(enabled=True)
    tl = Timeline()
    tl.stamp_enqueue()
    tl.finish("overloaded")  # shed: never admitted, no first token
    observe_request(tl, reg)
    snap = reg.snapshot()
    assert snap["tdt_requests_total"]["series"][0]["labels"] == {
        "status": "overloaded"
    }
    assert "tdt_request_ttft_seconds" not in snap


# -- engine integration ------------------------------------------------------


def _tiny_continuous(model, **kw):
    from triton_distributed_tpu.models.continuous import ContinuousEngine

    kw.setdefault("max_batch", 2)
    kw.setdefault("page_size", 16)
    kw.setdefault("max_length", 64)
    return model, ContinuousEngine(model, **kw)


def test_continuous_run_populates_latency_histograms(own_model):
    """Acceptance (ISSUE 5): TTFT/TPOT/queue-wait/e2e histograms with
    p50/p90/p99 appear for a multi-request run, labeled with PR 3
    finish statuses."""
    from triton_distributed_tpu.models.continuous import Request

    _model, eng = _tiny_continuous(own_model)
    reqs = [
        Request(np.asarray([5, 9, 2, 4], np.int32), 8),
        Request(np.asarray([7, 1, 3, 8, 6, 2], np.int32), 6),
        Request(np.asarray([5, 9, 2], np.int32), 4),
        # Expired before admission → deadline_exceeded label.
        Request(np.asarray([4, 4, 4], np.int32), 4, deadline_s=-1.0),
    ]
    results = eng.run(reqs, results=True)
    statuses = [r.status for r in results]
    assert statuses[:3] == ["ok"] * 3
    assert statuses[3] == "deadline_exceeded"

    snap = obs_metrics.default_registry().snapshot()
    for name in ("tdt_request_ttft_seconds", "tdt_request_tpot_seconds",
                 "tdt_request_e2e_seconds"):
        series = snap[name]["series"]
        ok = [s for s in series if s["labels"] == {"status": "ok"}]
        assert ok and ok[0]["count"] == 3, f"{name}: {series}"
        for q in ("p50", "p90", "p99"):
            assert ok[0][q] is not None and ok[0][q] > 0
    qw = snap["tdt_request_queue_wait_seconds"]["series"]
    assert qw and qw[0]["count"] >= 3  # unlabeled: all admitted requests
    pd = snap["tdt_request_prefill_dispatch_seconds"]["series"]
    assert pd and pd[0]["count"] == 3  # admit → first chunk, admitted only
    sizes = snap["tdt_request_tokens_out"]["series"]
    assert sizes and sizes[0]["count"] == 3 and sizes[0]["sum"] == 8 + 6 + 4
    got = {s["labels"]["status"]: s["value"]
           for s in snap["tdt_requests_total"]["series"]}
    assert got == {"ok": 3, "deadline_exceeded": 1}
    assert snap["tdt_tokens_out_total"]["series"][0]["value"] == 8 + 6 + 4
    # Counters mirror last_stats live.
    assert (snap["tdt_engine_decode_steps_total"]["series"][0]["value"]
            == eng.last_stats["decode_steps"])
    # Lifecycle events landed in the ring.
    kinds = {e.kind for e in obs_events.default_ring().tail(0)[0]}
    assert {"admit", "evict", "deadline"} <= kinds


def _series_named_in_docs() -> set:
    """Every `tdt_*` series a table row of docs/observability.md names
    (rows open with the name; `a` / `b` pairs and `{label}` suffixes
    included, `tdt_engine_{...}` families left to their prose)."""
    import os
    import re

    path = os.path.join(os.path.dirname(__file__), "..", "docs",
                        "observability.md")
    names = set()
    with open(path) as f:
        for line in f:
            if line.startswith("| `tdt_"):
                head = line.split("|")[1]
                names |= set(re.findall(r"`(tdt_[a-z0-9_]*[a-z0-9])[`{]",
                                        head))
    return names


def _series_defined_in_package() -> set:
    import glob
    import os
    import re

    pkg = os.path.join(os.path.dirname(__file__), "..",
                       "triton_distributed_tpu")
    names = set()
    for path in glob.glob(os.path.join(pkg, "**", "*.py"), recursive=True):
        with open(path) as f:
            names |= set(re.findall(r'"(tdt_[a-z0-9_]+)"', f.read()))
    return names


@pytest.mark.parametrize("side", ["registry", "docs"])
def test_series_catalog_holds_only_what_exists(own_model, side):
    """The engine registers, and docs/observability.md's tables name,
    only series the program can move: nothing of the emulated work ring
    or the virtual-rank long-context path, and no row for a series the
    package does not define."""
    gone = ("tdt_cp_", "tdt_longctx_", "tdt_mega_ring_")
    if side == "registry":
        from triton_distributed_tpu.models.stats import (
            STAT_METRIC_ALIASES,
            STAT_METRICS,
        )

        _tiny_continuous(own_model)
        names = set(obs_metrics.default_registry().snapshot())
        catalog = {name for name, _ in STAT_METRICS.values()}
        catalog |= {name for aliases in STAT_METRIC_ALIASES.values()
                    for name, _ in aliases}
        assert catalog <= names  # pre-touched: a cold scrape shows all
    else:
        names = _series_named_in_docs()
        assert len(names) > 80
        assert names <= _series_defined_in_package(), (
            sorted(names - _series_defined_in_package()))
    assert not [n for n in names if n.startswith(gone)]


def test_core_stats_keys_unified(own_model):
    """Satellite (ISSUE 5): Engine.last_stats and
    ContinuousEngine.last_stats expose ONE shared core key set
    (models/stats.py) — the shapes must not drift again."""
    from triton_distributed_tpu.models.engine import Engine
    from triton_distributed_tpu.models.stats import (
        CORE_STATS_KEYS,
        missing_core_stats,
    )

    model, ceng = _tiny_continuous(own_model)
    ceng.run([([5, 9, 2, 4], 4)])
    assert missing_core_stats(ceng.last_stats) == []

    feng = Engine(model, temperature=0.0)
    feng.serve(np.asarray([[5, 9, 2, 4]], np.int32), gen_len=4)
    assert missing_core_stats(feng.last_stats) == []

    # The schema itself stays honest: every core key is a string and
    # the set is non-trivial.
    assert len(CORE_STATS_KEYS) >= 5


def test_outputs_bit_identical_with_telemetry_off(own_model):
    """Acceptance (ISSUE 5): telemetry never touches the token path —
    the same workload decodes to identical tokens enabled or
    disabled."""
    prompts = [([5, 9, 2, 4], 8), ([7, 1, 3, 8, 6, 2], 6)]
    _m1, e1 = _tiny_continuous(own_model, prefix_cache=True, prefill_chunk=16)
    on = [o.tolist() for o in e1.run(prompts)]
    schedule = ("tdt_engine_step_rows", "tdt_engine_token_gap_seconds")
    snap = obs_metrics.default_registry().snapshot()
    before = {name: snap[name] for name in schedule}
    assert sum(s["count"] for s in before[schedule[1]]["series"]) == 7 + 5
    obs.set_enabled(False)
    _m2, e2 = _tiny_continuous(own_model, prefix_cache=True, prefill_chunk=16)
    off = [o.tolist() for o in e2.run(prompts)]
    obs.set_enabled(True)
    assert on == off
    # Neither histogram of the schedule moved, and no clock was read
    # for them: every stamp the second engine took is None.
    snap = obs_metrics.default_registry().snapshot()
    assert {name: snap[name] for name in schedule} == before
    assert e1._admit_t is not None and None not in e1._tok_t
    assert e2._admit_t is None and e2._tok_t == [None, None]


# -- server integration ------------------------------------------------------


def test_server_metrics_verb_and_grammar(own_model):
    """Acceptance (ISSUE 5): {"cmd": "metrics"} returns Prometheus text
    that parses line-by-line, plus the JSON snapshot; {"cmd": "events"}
    tails the ring through the wire."""
    from triton_distributed_tpu.serving.server import ModelServer, request

    _model, eng = _tiny_continuous(own_model)
    server = ModelServer(eng).start()
    try:
        r = request(server.host, server.port,
                    {"requests": [[5, 9, 2, 4]], "gen_lens": [4]})
        assert r["results"][0]["status"] == "ok"
        m = request(server.host, server.port, {"cmd": "metrics"})
        samples = assert_prometheus_parses(m["prometheus"])
        assert "tdt_requests_total" in samples
        assert "tdt_request_ttft_seconds_bucket" in samples
        snap = m["metrics"]
        assert snap["tdt_server_requests_total"]["type"] == "counter"
        ttft = snap["tdt_request_ttft_seconds"]["series"][0]
        assert ttft["count"] >= 1 and ttft["p50"] is not None
        ev = request(server.host, server.port,
                     {"cmd": "events", "since": 0})
        kinds = [e["kind"] for e in ev["events"]]
        assert "admit" in kinds and ev["next_since"] >= 1
        # Incremental tail from next_since is drop-free and empty-ish.
        ev2 = request(server.host, server.port,
                      {"cmd": "events", "since": ev["next_since"]})
        assert ev2["dropped"] == 0
        # since/limit validation: wrong types and negative cursors are
        # the CLIENT's fault (bad_request, never `internal`) — and a
        # negative since must not manufacture phantom `dropped` counts.
        for bad in ({"since": []}, {"since": "abc"}, {"since": -5},
                    {"limit": -1}):
            with pytest.raises(RuntimeError, match="bad_request"):
                request(server.host, server.port,
                        {"cmd": "events", **bad})
        # JSON null still reads as "from the start" / "no cap".
        ev3 = request(server.host, server.port,
                      {"cmd": "events", "since": None, "limit": None})
        assert [e["kind"] for e in ev3["events"]] == kinds
        s = request(server.host, server.port, {"cmd": "stats"})
        assert s["stats"]["server"]["uptime_s"] >= 0.0
        assert "snapshot_at" in s["stats"]["server"]
    finally:
        request(server.host, server.port, {"cmd": "shutdown"})
        server.shutdown()


def test_server_metrics_answers_mid_generation(own_model):
    """Acceptance (ISSUE 5): the metrics verb never takes the engine
    lock — a scrape completes while a generation batch is in flight."""
    from triton_distributed_tpu.serving.server import ModelServer, request

    _model, eng = _tiny_continuous(own_model)
    server = ModelServer(eng).start()
    errors: list = []

    def generate():
        try:
            request(server.host, server.port,
                    {"requests": [[5, 9, 2, 4, 7, 1, 3, 8]],
                     "gen_lens": [40]}, timeout=300)
        except Exception as e:  # pragma: no cover - surfaced below
            errors.append(e)

    t = threading.Thread(target=generate)
    t.start()
    try:
        # Scrape repeatedly while the batch decodes; at least one
        # scrape must START while the generation is in flight and
        # complete — asserted directly, so a metrics verb that
        # regressed into taking the engine lock fails this test
        # instead of silently passing after the batch drains.
        answered_mid_flight = False
        while t.is_alive():
            m = request(server.host, server.port, {"cmd": "metrics"},
                        timeout=30)
            assert "prometheus" in m and "metrics" in m
            assert_prometheus_parses(m["prometheus"])
            if t.is_alive():
                # The response arrived while the batch was STILL
                # generating — a lock-blocked scrape would only have
                # returned after the generation drained.
                answered_mid_flight = True
                break
        assert answered_mid_flight, (
            "generation finished before any scrape started — raise "
            "gen_lens so the batch outlives the first metrics request"
        )
    finally:
        t.join(timeout=300)
        request(server.host, server.port, {"cmd": "shutdown"})
        server.shutdown()
    assert not errors
