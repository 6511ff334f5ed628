"""The one-step lookahead of the single-step decode round
(docs/serving.md "The decode loop").

The round dispatches step N+1 off step N's on-device greedy tokens
before it fetches step N's, whenever that is exactly what the serial
loop would have run next. Both sides of that choice are held here: the
lookahead against the serial round token for token, the cases in which
it must not engage, and the events the host cannot foresee while a
step is in flight. The serial round is the same code with the choice
answered "no" (``serial`` below), which is what the parent commit ran.

A CPU run gives counts and tokens, never a time: the gain is a chip
matter (PERF.md).
"""

import numpy as np
import pytest

from triton_distributed_tpu.models.continuous import (
    ContinuousEngine,
    Request,
    _StepLaunch,
)
from triton_distributed_tpu.obs import metrics as obs_metrics
from triton_distributed_tpu.runtime.faults import FaultPlan

RNG = np.random.default_rng(30)
# One prompt length and two shared prefixes: few programs to compile,
# and radix hits when the prefix cache is on.
HEADS = [RNG.integers(1, 200, size=16).astype(np.int32) for _ in range(2)]


def prompt(head: int, tail_seed: int) -> np.ndarray:
    tail = np.random.default_rng(tail_seed).integers(1, 200, size=8)
    return np.concatenate([HEADS[head], tail.astype(np.int32)])


# Staggered lengths on 2 slots: slots finish and are re-admitted mid-run,
# a first token finishes a request, a round holds 1 and 2 live slots.
# (A program call costs a third of a second here whatever its size, so
# the batches are short.)
MIXED = [(prompt(i % 2, i), g) for i, g in enumerate([5, 2, 4, 1])]
LONE = prompt(0, 99)


@pytest.fixture(scope="module")
def greedy(own_model):
    """What ``LONE`` decodes to, alone and undisturbed."""
    out = engine(own_model).run([(LONE, 8)])[0]
    assert out[3] not in out[:3]  # as a stop token it stops once, at 3
    return out


def engine(model, **kw) -> ContinuousEngine:
    kw.setdefault("max_batch", 2)
    kw.setdefault("page_size", 16)
    kw.setdefault("max_length", 64)
    return ContinuousEngine(model, **kw)


def serial(eng: ContinuousEngine) -> ContinuousEngine:
    """The parent's round: the same engine, never looking ahead."""
    eng._may_look_ahead = lambda step: False
    return eng


def run(eng, reqs):
    """``(tokens, status)`` of each request, and the engine's stats."""
    res = eng.run(reqs, results=True)
    assert eng.audit() == []
    return [(r.tokens.tolist(), r.status) for r in res], eng.last_stats


# -- (a) the lookahead is the serial round, token for token ---------------


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
@pytest.mark.parametrize("prefix_cache", [False, True])
def test_lookahead_matches_serial_round(own_model, prefix_cache, kv_dtype):
    kw = dict(prefix_cache=prefix_cache, kv_dtype=kv_dtype)
    want, s_stats = run(serial(engine(own_model, **kw)), MIXED)
    got, stats = run(engine(own_model, **kw), MIXED)
    assert got == want
    assert [len(t) for t, _ in got] == [g for _, g in MIXED]
    # The same steps, most of them dispatched a round early, none wasted.
    assert stats["decode_steps"] == s_stats["decode_steps"]
    assert s_stats["lookahead_steps"] == 0
    assert stats["lookahead_steps"] >= stats["decode_steps"] // 2
    assert stats["lookahead_discarded"] == 0
    if prefix_cache:
        assert stats["prefix_hit_tokens"] == s_stats["prefix_hit_tokens"] > 0


# -- (b) where it must not engage ------------------------------------------


def _sampled(model):
    reqs = lambda: [  # noqa: E731 — fresh Requests for each engine
        Request(LONE, 6, temperature=0.8, top_k=20),
        Request(prompt(1, 5), 10),
    ]
    return (engine(model, seed=7), engine(model, seed=7)), reqs


def _speculative(model):
    rep = np.tile(np.asarray([5, 9, 2, 4], np.int32), 6)
    reqs = lambda: [(rep, 10), (LONE, 6)]  # noqa: E731
    return (engine(model, speculative=3), engine(model, speculative=3)), reqs


def _fault_plan(model):
    reqs = lambda: [(LONE, 10), (prompt(1, 5), 6)]  # noqa: E731
    return (engine(model), engine(model)), reqs


@pytest.mark.parametrize("case", [_sampled, _speculative, _fault_plan])
def test_lookahead_stays_out(own_model, case):
    """A sampled slot, a speculative plan or an armed FaultPlan: the
    round keeps the parent's serial order and gives the parent's
    outputs."""
    (parent, eng), reqs = case(own_model)
    want, _ = run(serial(parent), reqs())
    if case is _fault_plan:
        # Armed, with no rule: the seams fire into a plan that counts.
        with FaultPlan(seed=0) as plan:
            got, stats = run(eng, reqs())
        assert plan.hits["engine.decode"] == stats["decode_steps"]
    else:
        got, stats = run(eng, reqs())
    assert got == want
    assert all(status == "ok" for _, status in got)
    if case is _sampled:
        # Nothing looks ahead while the sampled request lives (its 6
        # tokens); the greedy one then goes on alone: its step 6 comes
        # from the host's tokens, 7 to 9 are dispatched a round early.
        assert stats["lookahead_steps"] == 3
    else:
        assert stats["lookahead_steps"] == 0
    assert stats["lookahead_discarded"] == 0


# -- (c) what the host cannot foresee while a step is in flight -----------


class Watch:
    """Records, at every page release of a slot, whether the in-flight
    step had left the device (``no page is freed or retired to the radix
    tree before the drain``), and every token a sink was handed."""

    def __init__(self, eng):
        self.eng, self.unsettled, self.frames = eng, [], []
        self.in_flight = {}  # request -> the step parked when its slot ended
        evict = eng._evict

        def evict_noting(req, after=None):
            self.in_flight[id(req)] = after
            return evict(req, after)
        eng._evict = evict_noting
        for name in ("_release", "_teardown_slot"):
            setattr(eng, name, self._guarded(name, getattr(eng, name)))

    def _guarded(self, name, fn):
        def call(req):
            # A slot that ended under a step in flight (since PR 35 by
            # its length too): its pages wait for THAT step, and a step
            # dispatched after it, on a table that no longer maps the
            # slot, may be in flight when they go back.
            pend = self.in_flight.get(id(req), self.eng._pend)
            if isinstance(pend, _StepLaunch) and pend.host is None:
                self.unsettled.append(name)
            return fn(req)
        return call

    def sink(self, at: int, then):
        def on_token(i, t):
            self.frames.append((i, t))
            if i == at:
                then()
        return on_token


def _stop_token(eng, watch, greedy):
    eng.eos_id = int(greedy[3])
    return Request(LONE, 8, on_token=watch.sink(-1, None))


def _cancel(eng, watch, greedy):
    return Request(LONE, 8, ticket_id="t-30",
                   on_token=watch.sink(3, lambda: eng.cancel(["t-30"])))


def _deadline(eng, watch, greedy):
    req = Request(LONE, 8)

    def expire():
        req.deadline_at = 0.0
    req.on_token = watch.sink(3, expire)
    return req


def _nan_row(eng, watch, greedy):
    launch = eng._launch_step

    def poisoned(tok, active, n_active):
        step = launch(tok, active, n_active)
        if eng.stats["decode_steps"] == 3:  # the step that gives token 3
            finite = np.asarray(step.finite).copy()
            finite[0] = False
            step.finite = finite
        return step
    eng._launch_step = poisoned
    return Request(LONE, 8, on_token=watch.sink(-1, None))


@pytest.mark.parametrize(
    "event,status,kept,prefix_cache",
    [(_stop_token, "ok", 4, False), (_stop_token, "ok", 4, True),
     (_cancel, "cancelled", 4, True),
     (_deadline, "deadline_exceeded", 4, True),
     (_nan_row, "nan_logits", 3, True)])
def test_slot_ends_while_a_step_is_in_flight(own_model, greedy, event,
                                             status, kept, prefix_cache):
    """The slot's token of the in-flight step is never emitted and is
    counted; the parent's serial round gives the same tokens and status;
    no page goes back before the device has left the step; the engine
    serves the next batch."""
    other = (prompt(1, 5), 6)
    outcomes = {}
    for name in ("serial", "ahead"):
        eng = engine(own_model, prefix_cache=prefix_cache)
        if name == "serial":
            serial(eng)
        watch = Watch(eng)
        req = event(eng, watch, greedy)
        out, stats = run(eng, [req, other])
        assert out[0] == (greedy[:kept].tolist(), status)
        assert [t for _, t in watch.frames] == out[0][0]
        assert out[1][1] == "ok"
        assert watch.unsettled == []
        outcomes[name] = (out, stats)
        # The next batch, on the same engine.
        eng.eos_id = None
        eng._launch_step = type(eng)._launch_step.__get__(eng)
        again, _ = run(eng, [(LONE, 4)])
        assert again == [(greedy[:4].tolist(), "ok")]
    (s_out, s_stats), (a_out, a_stats) = outcomes["serial"], outcomes["ahead"]
    assert a_out == s_out
    assert s_stats["lookahead_discarded"] == 0
    assert a_stats["lookahead_discarded"] == 1
    assert a_stats["lookahead_steps"] > 0


def test_lookahead_goes_on_over_a_request_that_ends_by_its_length(own_model):
    """Two requests of 3 and 6 tokens and nobody waiting: the round in
    which the shorter ends still dispatches the next step for the other
    (its own row of that step is dropped, and no page goes back before
    the device has left it); with a third request waiting for the slot
    the round waits, as the serial loop admits it into that very step.
    (32 slots end a request every tenth round: PERF.md "PR 35".)"""
    reqs = lambda: [(prompt(0, 1), 3), (prompt(1, 2), 6)]  # noqa: E731
    want, s_stats = run(serial(engine(own_model)), reqs())
    eng = engine(own_model)
    watch = Watch(eng)
    got, stats = run(eng, reqs())
    assert got == want and watch.unsettled == []
    assert stats["decode_steps"] == s_stats["decode_steps"] == 5
    assert stats["lookahead_steps"] == 4  # all but the first
    assert stats["lookahead_discarded"] == 1
    queued = reqs() + [(prompt(0, 3), 3)]
    want, s_stats = run(serial(engine(own_model)), queued)
    got, stats = run(engine(own_model), queued)
    assert got == want
    assert stats["decode_steps"] == s_stats["decode_steps"]
    # The first end waited for the admission; the third request's own
    # end, with the queue empty by then, did not.
    assert stats["lookahead_discarded"] == 1


def test_host_and_device_kv_len_agree_after_every_drain(own_model, greedy):
    """``audit()`` compares the two wherever nothing is in flight: here
    after each admission's drain, mid-run, with live slots."""
    eng = engine(own_model, prefix_cache=True)
    admit, audits = eng._try_admit, []

    def audited(queue):
        done = admit(queue)
        if eng._pend is None and any(r is not None for r in eng._slots):
            audits.append(eng.audit())
        return done
    eng._try_admit = audited
    eng.eos_id = int(greedy[3])
    run(eng, [(LONE, 8)] + MIXED)
    assert len(audits) >= 3 and all(a == [] for a in audits)
    assert eng.last_stats["lookahead_discarded"] >= 1


# -- (d) every site that changes slot, table or pool state syncs `_pend` ----


class SiteSpy:
    """Wraps one site that mutates slot, table or pool state and the
    calls through which it does so. Counts the entries that found a
    launch parked in ``_pend``, and names every mutation made while
    that launch was still the device's: not drained where the site
    drains (``settle=False``), not even fetched where a looked-ahead
    step may stay parked (``_settle_pend``'s sites)."""

    def __init__(self, eng, patch, site, settle=False):
        self.eng, self.patch, self.settle = eng, patch, settle
        self.depth = self.parked = self.mutations = 0
        self.early = []
        fn = getattr(eng, site)

        def entered(*a, **kw):
            self.parked += eng._pend is not None
            self.depth += 1
            try:
                return fn(*a, **kw)
            finally:
                self.depth -= 1
        patch.setattr(eng, site, entered)

    def _in_flight(self) -> bool:
        pend = self.eng._pend
        if self.settle and isinstance(pend, _StepLaunch):
            return pend.host is None
        return pend is not None

    def mutator(self, owner, name):
        fn = getattr(owner, name)

        def call(*a, **kw):
            if self.depth:
                self.mutations += 1
                if self._in_flight():
                    self.early.append(name)
            return fn(*a, **kw)
        self.patch.setattr(owner, name, call)


def _site_try_admit(eng, spy, greedy):
    # A stop token ends LONE while the next step is parked: the queued
    # third request is admitted into its slot.
    eng.eos_id = int(greedy[3])
    spy.mutator(eng, "_admit")
    return [(LONE, 8), (prompt(1, 5), 6), (prompt(0, 7), 3)], ["ok"] * 3


def _site_apply_cancels(eng, spy, greedy):
    spy.mutator(eng, "_teardown_slot")
    sink = Watch(eng).sink(3, lambda: eng.cancel(["t-32"]))
    return ([Request(LONE, 12, ticket_id="t-32", on_token=sink),
             (prompt(1, 5), 6)], ["cancelled", "ok"])


def _site_expire_deadlines(eng, spy, greedy):
    spy.mutator(eng, "_teardown_slot")
    req = Request(LONE, 12)

    def expire():
        req.deadline_at = 0.0
    req.on_token = Watch(eng).sink(3, expire)
    return [req, (prompt(1, 5), 6)], ["deadline_exceeded", "ok"]


def _site_handoff_sweep(eng, spy, greedy):
    spy.mutator(eng, "_migrate_out")
    eng.request_handoff(after_rounds=3)
    return [(LONE, 12), (prompt(1, 5), 12)], ["migrated"] * 2


def _site_update_snapshot_buffer(eng, spy, greedy):
    from triton_distributed_tpu.models import slot_state

    spy.patch.setattr(eng, "snapshot_every", 1)
    spy.mutator(slot_state, "export_slot")
    return ([Request(LONE, 8, ticket_id="a"),
             Request(prompt(1, 5), 6, ticket_id="b")], ["ok"] * 2)


def _site_step_guard(eng, spy, greedy):
    # The teardown path: the second emit raises with the next launch
    # parked; the guard has to reclaim it before any slot is torn down.
    spy.mutator(eng, "_teardown_slot")
    process, calls = eng._process, []

    def raising(slot_tokens, *after):
        calls.append(1)
        if len(calls) == 2:
            raise RuntimeError("injected emit fault")
        return process(slot_tokens, *after)
    spy.patch.setattr(eng, "_process", raising)
    return [(LONE, 10), (prompt(1, 5), 10)], ["failed"] * 2


@pytest.fixture(scope="module")
def kinds(own_model):
    """One engine a launch kind for all six sites (a mega engine
    compiles its launch programs anew): ``step`` parks a looked-ahead
    ``_StepLaunch``, ``mega`` a resident ``_MegaLaunch``."""
    built = {}

    def get(kind):
        if kind not in built:
            kw = dict(mode="mega", resident=True, ns=2) \
                if kind == "mega" else {}
            built[kind] = engine(own_model, prefix_cache=True, **kw)
        return built[kind]
    return get


@pytest.mark.parametrize("kind", ["step", "mega"])
@pytest.mark.parametrize(
    "site,settle",
    [(_site_try_admit, False), (_site_apply_cancels, True),
     (_site_expire_deadlines, True), (_site_handoff_sweep, False),
     (_site_update_snapshot_buffer, False), (_site_step_guard, False)])
def test_state_mutation_syncs_pend_first(kinds, greedy, monkeypatch, kind,
                                         site, settle):
    """Whatever mutates slot, table or pool state comes through
    ``_drain_pend`` / ``_settle_pend`` / ``_abort_pend`` first, for both
    kinds of launch the one ``_pend`` slot parks; the engine serves the
    next batch."""
    eng = kinds(kind)
    spy = SiteSpy(eng, monkeypatch, site.__name__[len("_site"):], settle)
    try:
        reqs, statuses = site(eng, spy, greedy)
        got, _ = run(eng, reqs)
    finally:
        eng.eos_id = None
    assert [status for _, status in got] == statuses
    assert spy.parked > 0      # the site did meet a launch in flight
    assert spy.mutations > 0   # and did change state
    assert spy.early == []     # never before the device had left it
    assert eng._pend is None
    monkeypatch.undo()
    again, _ = run(eng, [(LONE, 4)])
    assert again[0][1] == "ok"
    if kind == "step":
        assert again[0][0] == greedy[:4].tolist()


# -- (e) the engagement rate and where the counters show -------------------


def test_engagement_rate_and_counters(own_model, fresh_telemetry):
    from triton_distributed_tpu.serving.server import ModelServer, request

    eng = engine(own_model)
    _, stats = run(eng, [(prompt(1, 40), 11)])
    # 10 steps: the first from the host's tokens, the rest a round early.
    assert stats["decode_steps"] == 10
    assert stats["lookahead_steps"] == 9  # 0.9 of them
    assert stats["lookahead_discarded"] == 0
    snap = obs_metrics.default_registry().snapshot()
    assert (snap["tdt_engine_lookahead_steps_total"]["series"][0]["value"]
            == stats["lookahead_steps"])
    server = ModelServer(eng).start()
    try:
        m = request(server.host, server.port, {"cmd": "metrics"})
        assert "tdt_engine_lookahead_steps_total" in m["prometheus"]
        assert "tdt_engine_lookahead_discarded_total" in m["prometheus"]
        s = request(server.host, server.port, {"cmd": "stats"})
        assert s["stats"]["lookahead_steps"] == stats["lookahead_steps"]
    finally:
        request(server.host, server.port, {"cmd": "shutdown"})
        server.shutdown()


# -- (f) the schedule: the rows a step carried, what stood in a token's gap --


def schedule(snap) -> tuple:
    """``(rows histogram, {after: count of gaps})`` of a registry
    snapshot (docs/observability.md "Metric catalog")."""
    (rows,) = snap["tdt_engine_step_rows"]["series"]
    gaps = {s["labels"]["after"]: s["count"]
            for s in snap["tdt_engine_token_gap_seconds"]["series"]}
    return rows, gaps


def _one_greedy(model):
    # 10 steps for one row: the first from the host's tokens, nine
    # looked ahead to. The row's own admission stands in none of its
    # gaps: the first starts at its first token.
    return engine(model), [(prompt(1, 40), 11)], {"serial": 1, "ahead": 9}


def _queue_longer_than_the_slots(model):
    # Four requests (5, 2, 4, 1 tokens) on two slots. Round 1: the
    # first row waited behind the second's admission, whose own gap
    # holds only the serial step. The second ends, nobody looked ahead
    # over an end that the third waits for, and round 2 is round 1
    # again with the third. Rounds 3 and 4 were looked ahead to and end
    # both rows; the fourth request ends at its first token.
    return engine(model), MIXED, {"admit": 2, "serial": 2, "ahead": 4}


def _sampled_row(model):
    # A sampled row keeps every round serial while it lives.
    return (engine(model, seed=7),
            [Request(LONE, 6, temperature=0.8, top_k=20)], {"serial": 5})


def _chunked_admission(model):
    # The second prompt's 24 tokens go in as chunks of 16: the running
    # row's round between the two chunks and its first round after the
    # admission both had a chunk in their gap; the admitted row's own
    # first gap has none, only the serial step of the round after it.
    # Every later round was looked ahead to (the running row's other
    # five tokens, the admitted row's last).
    eng = engine(model, prefix_cache=True, prefill_chunk=16)
    return (eng, [(LONE, 8), (prompt(1, 5), 3)],
            {"admit": 2, "serial": 1, "ahead": 6})


@pytest.mark.parametrize("case", [_one_greedy, _queue_longer_than_the_slots,
                                  _sampled_row, _chunked_admission])
def test_schedule_histograms_hold_their_identities(own_model, fresh_telemetry,
                                                   case):
    """One observation a launched step, of its rows: as many as decode
    steps, summing to the decoded tokens plus the discarded ones. One a
    decoded token, by what stood in its gap: as many as decoded tokens."""
    eng, reqs, want = case(own_model)
    got, stats = run(eng, reqs)
    decoded = sum(len(t) - 1 for t, _ in got)
    rows, gaps = schedule(obs_metrics.default_registry().snapshot())
    assert rows["count"] == stats["decode_steps"]
    assert rows["sum"] == decoded + stats["lookahead_discarded"]
    # One edge a row: a bucket holds the steps of exactly that many.
    edges, counts = rows["buckets"]["edges"], rows["buckets"]["counts"]
    assert edges == list(range(1, 129))
    assert sum(e * c for e, c in zip(edges, counts)) == rows["sum"]
    assert sum(gaps.values()) == decoded
    assert gaps == want


def _walked(lens: list, page: int, pps: int, layers: int) -> tuple:
    """``(pages, grid steps)`` the dense paged kernel walked over the
    lengths each decode step was given, from the device walks."""
    from triton_distributed_tpu.ops.attention import (
        dense_decode_walk,
        paged_decode_walk,
    )

    return (layers * sum(int(paged_decode_walk(n, page, pps)[2])
                         for n in lens),
            layers * sum(int(dense_decode_walk(n, page, pps)[2])
                         for n in lens))


@pytest.mark.parametrize("case", [_one_greedy, _queue_longer_than_the_slots,
                                  _sampled_row, _chunked_admission])
def test_the_dense_walk_is_counted_from_the_lengths_each_step_was_given(
        own_model, fresh_telemetry, monkeypatch, case):
    """``tdt_paged_decode_{pages,grid_steps}_total`` are the host's count
    of what ``tdt_flash_decode_paged`` walked in every launched step, an
    idle row's pages included, over the model's layers: the walks of the
    lengths the device's cache held, whether the step was looked ahead
    to, launched between two chunks of an admission or after a slot
    ended."""
    lens = []
    step = own_model.decode_step

    def seen(tokens, cache, mode="xla"):
        lens.append(np.asarray(cache.kv_len) + 1)
        return step(tokens, cache, mode)

    monkeypatch.setattr(own_model, "decode_step", seen)
    eng, reqs, _ = case(own_model)
    _, stats = run(eng, reqs)
    assert len(lens) == stats["decode_steps"] > 0
    want = _walked(lens, eng.page_size, eng.pps, own_model.cfg.num_layers)
    assert (stats["paged_decode_pages"],
            stats["paged_decode_grid_steps"]) == want
    snap = obs_metrics.default_registry().snapshot()
    assert (snap["tdt_paged_decode_pages_total"]["series"][0]["value"],
            snap["tdt_paged_decode_grid_steps_total"]["series"][0]["value"]
            ) == want


def test_the_dense_walk_counters_ride_the_stats_and_metrics_verbs(
        own_model, fresh_telemetry):
    """Over a table of 8 pages (a group of 4) a row of 40 tokens walks 3
    pages in ONE grid step a layer: pages over grid steps reads above 1
    in the ``stats`` and ``metrics`` verbs; a megakernel step, which
    runs no such kernel, counts none."""
    from triton_distributed_tpu.serving.server import ModelServer, request

    eng = engine(own_model, max_length=128)
    _, stats = run(eng, [(prompt(1, 41), 20)])
    assert stats["paged_decode_pages"] > stats["paged_decode_grid_steps"]
    server = ModelServer(eng).start()
    try:
        m = request(server.host, server.port, {"cmd": "metrics"})
        for name in ("tdt_paged_decode_pages_total",
                     "tdt_paged_decode_grid_steps_total"):
            assert name in m["prometheus"]
        s = request(server.host, server.port, {"cmd": "stats"})
        for key in ("paged_decode_pages", "paged_decode_grid_steps"):
            assert s["stats"][key] == stats[key]
    finally:
        request(server.host, server.port, {"cmd": "shutdown"})
        server.shutdown()
    assert engine(own_model, mode="mega")._walk_layers == 0


def test_a_rows_gap_is_its_own(own_model, fresh_telemetry):
    """A row admitted a moment ago is not charged the running row's
    whole period: its first gap starts at its own first token."""
    eng = engine(own_model)
    stamps = []
    admit = eng._admit

    def slow_admit(req, slot, m=None):
        first = admit(req, slot, m)
        stamps.append(list(eng._tok_t))
        return first
    eng._admit = slow_admit
    run(eng, [(LONE, 4), (prompt(1, 5), 4)])
    # At the second admission the first row's stamp is its first
    # token's, earlier than the clock the second row's is set from.
    assert stamps[0] == [None, None]
    assert stamps[1][0] is not None and stamps[1][1] is None
    assert eng._tok_t[1] > stamps[1][0]
