"""``benchmark/spans.py`` and the five readers that share it: on a
hand-made trace whose every number can be worked out by hand, on a
trace of a program without spans (an older commit: nothing is read,
nothing raises), and on a recorded piece of a chip trace with the
program's spans in it (``data/trace_sample_spans.json``, cut with
``xplane.sample`` from a traced ``qwen3-4b.chat`` window on the v5e)."""

import json
import os

import pytest

from benchmark import cells, spans, xplane

MS = 1e6
DATA = os.path.join(os.path.dirname(__file__), "data")
READERS = ["scheduler.batch_wait_p50_ms", "engine.step_host_p50_ms",
           "engine.admit_p50_ms", "device.idle_with_work_share",
           "device.idle_unnamed_share"]


def handmade(with_spans=True):
    """One second on one device. The worker waits 300 ms in three
    spans; a batch then admits a request (80 ms, of which one decode
    round of the running batch takes 30) and runs two more rounds of 30
    ms, each 27 ms of fetch. The device runs the chunk, three steps of
    26 ms and nothing else."""
    mods = [["jit_tdt_prefill_chunk(1)", 310 * MS, 40 * MS],
            ["jit_tdt_decode_step(2)", 352 * MS, 26 * MS],
            ["jit_tdt_decode_step(2)", 392 * MS, 26 * MS],
            ["jit_tdt_decode_step(2)", 422 * MS, 26 * MS]]
    host = [["", "PjitFunction(tdt_decode_step)", 391 * MS, 0.5 * MS],
            ["", "some::Internal", 0.0, 1000 * MS]]
    if with_spans:
        w = "scheduler:wait_for_work"
        host += [
            ["", w, 0.0, 100 * MS], ["", w, 100.01 * MS, 100 * MS],
            ["", w, 200.02 * MS, 99.9 * MS],
            ["", "scheduler:batch", 300 * MS, 160 * MS],
            ["", "engine:admit", 301 * MS, 80 * MS],
            ["", "prefix_cache:admit", 302 * MS, 49 * MS],
            ["", "prefix_cache:chunk", 303 * MS, 2 * MS],
            ["", "engine:decode_round", 350 * MS, 30 * MS],
            ["", "engine:fetch", 351 * MS, 27 * MS],
            ["", "engine:decode_round", 390 * MS, 30 * MS],
            ["", "engine:dispatch", 390.5 * MS, 1 * MS],
            ["", "engine:fetch", 392 * MS, 26 * MS],
            ["", "engine:sample_emit", 418.5 * MS, 1 * MS],
            ["", "engine:decode_round", 420 * MS, 30 * MS],
            ["", "engine:fetch", 421 * MS, 28 * MS],
            ["", "engine:audit", 455 * MS, 2 * MS],
            # A connection's thread: its span overlaps the worker's.
            ["", "entry:payload", 299 * MS, 170 * MS],
        ]
    form = {"devices": {"0": {"ops": [[n.split("(")[0], s, d]
                                      for n, s, d in mods],
                              "modules": mods}}, "host": host}
    return xplane.Trace.of(form)


def hist(counts):
    return {"count": sum(counts), "sum": 0.0, "edges": [0.5, 1.0, 2.0],
            "counts": counts}


def ctx_of(tr, batch_wait=True):
    c0 = {"tdt_request_queue_wait_seconds": hist([0, 0, 0, 0])}
    c1 = {"tdt_request_queue_wait_seconds": hist([0, 1, 3, 0])}
    if batch_wait:
        c0["tdt_request_batch_wait_seconds"] = hist([0, 0, 0, 0])
        c1["tdt_request_batch_wait_seconds"] = hist([1, 3, 0, 0])
    return {"trace": tr, "counters_window_0": c0, "counters_window_1": c1}


def read(name, ctx):
    return cells.load_reader(name).read(ctx)


def test_intervals_merge_cover_and_self_time():
    assert spans.merged([(5, 5), (0, 2), (1, 3), (20, 1)]) == [
        [0, 4], [5, 10], [20, 21]]
    cover = spans.merged([(0, 4), (5, 5), (20, 1)])
    assert spans.covered_ns(cover, 2, 7) == 2 + 2
    assert spans.covered_ns(cover, 10, 20) == 0
    assert spans.covered_ns(cover, -5, 100) == 4 + 5 + 1
    assert spans.covered_ns([], 0, 10) == 0
    assert [spans.covers(cover, t) for t in (-1, 0, 4, 4.5, 7, 21, 22)] == [
        False, True, True, False, True, True, False]
    tr = handmade()
    assert spans.named(tr, "engine:fetch") == [
        (351 * MS, 27 * MS), (392 * MS, 26 * MS), (421 * MS, 28 * MS)]
    # Children by containment in time; self time is what they leave.
    assert spans.self_ms(tr, "engine:decode_round", "engine:fetch") == [
        pytest.approx(3.0), pytest.approx(4.0), pytest.approx(2.0)]
    assert spans.self_ms(tr, "engine:admit", "engine:decode_round") == [
        pytest.approx(50.0)]
    assert spans.self_ms(tr, "engine:audit", "engine:fetch") == [
        pytest.approx(2.0)]
    assert spans.self_ms(tr, "no:such", "engine:fetch") == []
    # prefix_cache:* and a PJRT internal are no spans of the program's
    # hot path; entry:, scheduler: and engine: are.
    assert len(spans.program_spans(tr)) == 3 + 1 + 1 + 3 + 3 + 1 + 1 + 1 + 1


@pytest.mark.parametrize("name, expected", [
    # Four requests: one waited under 0.5 s, three between 0.5 and 1 s.
    ("scheduler.batch_wait_p50_ms", 500.0 + 500.0 * (2 - 1) / 3),
    ("engine.step_host_p50_ms", 3.0),
    ("engine.admit_p50_ms", 50.0),
    # Idle 1000 - 118 = 882 ms, of which 310 before the chunk lie in
    # the waits but for 0.02 ms between them and 10.08 ms after them.
    ("device.idle_with_work_share", 100.0 * (882 - 299.9) / 1000),
    # Unnamed: only the gap after the batch, 448 to 1000 ms, whose
    # midpoint no span of the program covers.
    ("device.idle_unnamed_share", 100.0 * 552 / 882),
])
def test_reader_on_the_handmade_trace(name, expected):
    assert read(name, ctx_of(handmade())) == pytest.approx(expected)


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_nothing_from_a_program_without_spans(name):
    ctx = ctx_of(handmade(with_spans=False), batch_wait=False)
    assert read(name, ctx) is None


def test_gap_named_by_midpoint_as_the_breakdown_names_it():
    tr = handmade()
    rows = dict(tr.breakdown()["idle_gaps"])
    # The long wait is one gap, named by the 0.1 s wait over its middle.
    assert rows["scheduler:wait_for_work"] == pytest.approx(0.310)
    assert "(no host span)" not in rows
    assert rows["some::Internal"] == pytest.approx(0.552)
    own = spans.program_spans(tr)
    assert spans.idle_unnamed_ns(tr, own) == pytest.approx(552 * MS)
    assert spans.idle_ns(tr) == pytest.approx(882 * MS)


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "trace_sample_spans.json")) as f:
        return xplane.Trace.of(json.load(f))


def test_recorded_chip_trace_holds_the_spans_on_the_device_clock(recorded):
    """The piece: the last rounds of a batch, its audit, and the worker
    waiting for work while the device idles."""
    tr = recorded
    rounds = spans.named(tr, "engine:decode_round")
    steps = tr.modules(r"decode")
    assert len(rounds) >= 2 and len(steps) >= len(rounds)
    assert {n.split("(")[0] for n, _, _ in steps} == {"jit_tdt_decode_step"}
    assert not tr.modules(r"lambda")
    assert len(spans.named(tr, "engine:audit")) == 1
    assert spans.named(tr, spans.WAIT_FOR_WORK)
    # One clock: the host launched every step inside an engine:dispatch
    # span, and the device ran it inside that round's engine:fetch.
    dispatch = spans.merged(spans.named(tr, "engine:dispatch"))
    launches = [s for _, n, s, _ in tr.form["host"]
                if n == "PjitFunction(tdt_decode_step)"]
    assert len(launches) >= len(rounds)
    assert all(spans.covers(dispatch, s) for s in launches)
    fetch = spans.merged(spans.named(tr, "engine:fetch"))
    inside = [spans.covered_ns(fetch, s, s + d) / d for _, s, d in steps]
    # The piece starts at a launch, so its first step's round is cut.
    assert sorted(inside)[1] > 0.95
    # The three phases lie inside their round.
    cover = spans.merged(rounds)
    for child in ("engine:dispatch", "engine:fetch", "engine:sample_emit"):
        kids = spans.named(tr, child)
        orphans = [k for k in kids
                   if spans.covered_ns(cover, k[0], k[0] + k[1]) < k[1]]
        assert len(kids) >= len(rounds) and len(orphans) <= 1, child


def test_readers_on_the_recorded_chip_trace(recorded):
    ctx = {"trace": recorded}
    # A round is about 30 ms, nearly all of it the wait for the step;
    # the host's own part was 0.9-1.4 ms in the traced runs of PR 26.
    rounds = [d / MS for _, d in spans.named(recorded, "engine:decode_round")]
    assert all(25.0 < d < 40.0 for d in rounds)
    assert 0.3 < read("engine.step_host_p50_ms", ctx) < 3.0
    # No admission in the piece (a prefill chunk's 23,000 operations
    # would make it 2.5 MB): the reader reports nothing.
    assert read("engine.admit_p50_ms", ctx) is None
    idle = read("device.idle_share", ctx)
    with_work = read("device.idle_with_work_share", ctx)
    assert 0.0 < with_work < idle
    # What the waits do not cover is the host's part of each round.
    waits = sum(d for _, d in spans.named(recorded, spans.WAIT_FOR_WORK))
    assert idle - with_work == pytest.approx(
        100.0 * waits / (recorded.window_s * 1e9), rel=0.05)
    assert read("device.idle_unnamed_share", ctx) < 5.0
    rows = dict(recorded.breakdown()["idle_gaps"])
    # (The piece's first gap lies in a round whose span the cut took.)
    assert rows[spans.WAIT_FOR_WORK] > 10 * rows.get("(no host span)", 0.0)
