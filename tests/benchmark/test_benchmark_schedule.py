"""``benchmark/rounds.py`` and the six readers of the schedule: on
hand-made counter snapshots whose every number can be worked out by
hand, on a hand-made trace that holds one serial and two looked-ahead
decode rounds (``data/trace_schedule_rounds.json``), and on a program
without the histograms or the span (an older commit: nothing is read,
nothing raises)."""

import json
import os

import pytest

from benchmark import cells, rounds, spans, xplane

MS = 1e6
DATA = os.path.join(os.path.dirname(__file__), "data")
EDGES = [float(r) for r in range(1, 129)]
COUNTED = ["scheduler.rows_in_flight_mean", "scheduler.rows_at_token_p95",
           "engine.lookahead_share", "engine.serial_token_share",
           "engine.admit_stalled_token_share"]
TRACED = "device.idle_in_serial_round_share"
# The 32-slot cells: the dense ones have `scheduler.batch_occupancy`.
WIDE = ["dots-vlm1-ep16.docs-closed", "granite-4.0-h-micro.chat-closed32"]


def rows_hist(steps_by_rows: dict) -> dict:
    counts = [0] * (len(EDGES) + 1)
    for rows, steps in steps_by_rows.items():
        counts[min(rows, 129) - 1] += steps
    return {"count": sum(counts), "edges": list(EDGES), "counts": counts,
            "sum": float(sum(r * s for r, s in steps_by_rows.items()))}


def gap_hist(n: int, seconds: float = 0.0125) -> dict:
    # Two edges stand for LATENCY_BUCKETS: the readers take the count.
    return {"count": n, "sum": n * seconds, "edges": [0.01, 0.1],
            "counts": [0, n, 0]}


def snapshot(steps_by_rows, lookahead, gaps) -> dict:
    out = {"tdt_engine_decode_steps_total": sum(steps_by_rows.values()),
           "tdt_engine_lookahead_steps_total": lookahead,
           rounds.STEP_ROWS: rows_hist(steps_by_rows)}
    for after, n in gaps.items():
        out[f"{rounds.TOKEN_GAP}[after={after}]"] = gap_hist(n)
    return out


@pytest.fixture
def recorded():
    with open(os.path.join(DATA, "trace_schedule_rounds.json")) as f:
        return xplane.Trace.of(json.load(f))


def window(recorded=None, **after) -> dict:
    """Before the window: 10 steps of 32 rows, 9 looked ahead to. In it:
    100 steps of one row, 10 of ten and 4 of 27 (308 tokens), 110 of the
    114 looked ahead to; 6 tokens waited behind an admission, 20 for a
    serial step, 282 for nothing but a step."""
    c0 = snapshot({32: 10}, 9, {"admit": 31, "serial": 1, "ahead": 288})
    gaps = {"admit": 31 + 6, "serial": 1 + 20, "ahead": 288 + 282}
    gaps.update(after)
    c1 = snapshot({32: 10, 1: 100, 10: 10, 27: 4}, 9 + 110, gaps)
    ctx = {"counters_window_0": c0, "counters_window_1": c1}
    if recorded is not None:
        ctx["trace"] = recorded
    return ctx


def read(name, ctx):
    return cells.load_reader(name).read(ctx)


@pytest.mark.parametrize("name, expected", [
    ("scheduler.rows_in_flight_mean", 308 / 114),
    # 95% of 308 tokens is 292.6: the one-row steps hold 100, those of
    # ten rows 200 in all, and only the four steps of 27 rows reach it.
    ("scheduler.rows_at_token_p95", 27.0),
    ("engine.lookahead_share", 100 * 110 / 114),
    ("engine.serial_token_share", 100 * 20 / 308),
    ("engine.admit_stalled_token_share", 100 * 6 / 308),
])
def test_counter_reader_on_the_handmade_window(name, expected):
    assert read(name, window()) == pytest.approx(expected)


def test_token_weighted_quantile_on_a_known_histogram():
    # Ten steps of one row and ten of ten: 110 tokens, 100 of them made
    # ten at a time; by STEPS the median is one row, by TOKENS ten.
    pairs = [(1, 10), (10, 10)]
    assert rounds.rows_at_token_quantile(pairs, 0.05) == 1
    assert rounds.rows_at_token_quantile(pairs, 10 / 110) == 1
    assert rounds.rows_at_token_quantile(pairs, 0.5) == 10
    assert rounds.rows_at_token_quantile(pairs, 1.0) == 10
    assert rounds.rows_at_token_quantile([(32, 7)], 0.95) == 32
    # What the program files past its last edge counts at that edge.
    ctx = {"counters_window_0": {},
           "counters_window_1": snapshot({3: 2, 200: 1}, 0, {})}
    assert rounds.step_rows(ctx) == [(3, 2), (128, 1)]


@pytest.mark.parametrize("name, after", [
    ("engine.serial_token_share", "serial"),
    ("engine.admit_stalled_token_share", "admit")])
def test_an_empty_share_of_counted_tokens_reads_zero_not_nothing(name, after):
    # No token of the kind in the window: its count did not move...
    ctx = window(**{after: {"admit": 31, "serial": 1}[after]})
    assert read(name, ctx) == 0.0
    # ... or the program never made its series (nothing is pre-touched).
    for c in ("counters_window_0", "counters_window_1"):
        del ctx[c][f"{rounds.TOKEN_GAP}[after={after}]"]
    del ctx["_window"]
    assert read(name, ctx) == 0.0
    assert rounds.gap_counts(ctx)[after] == 0
    # No step looked ahead to: 0.0 as well.
    ctx["counters_window_1"]["tdt_engine_lookahead_steps_total"] = 9
    del ctx["_window"]
    assert read("engine.lookahead_share", ctx) == 0.0


def test_serial_rounds_are_found_by_the_span_they_hold(recorded):
    assert len(spans.named(recorded, rounds.ROUND)) == 3
    assert rounds.serial_rounds(recorded) == [(10 * MS, 15 * MS)]
    # Idle: 0-2, 8-12 and 48-60 ms; round A (10-25 ms) holds 10-12.
    assert spans.idle_ns(recorded) == pytest.approx(18 * MS)
    assert read(TRACED, window(recorded)) == pytest.approx(100 * 2 / 18)
    # What PERF.md reports beside it, with the same two calls: the
    # admission (1-10 ms) holds 1-2 and 8-10.
    admits = spans.named(recorded, "engine:admit")
    assert spans.idle_ns(recorded) - spans.idle_outside_ns(
        recorded, admits) == pytest.approx(3 * MS)


def test_a_trace_without_a_serial_round_reads_zero_not_nothing(recorded):
    form = dict(recorded.form, host=[
        e for e in recorded.form["host"] if e[1] != rounds.SERIAL_LAUNCH])
    tr = xplane.Trace.of(form)
    assert rounds.serial_rounds(tr) == []
    assert read(TRACED, window(tr)) == 0.0
    # No decode round at all: nothing to divide, nothing reported.
    form = dict(form, host=[e for e in form["host"] if e[1] != rounds.ROUND])
    assert read(TRACED, window(xplane.Trace.of(form))) is None


@pytest.mark.parametrize("name", COUNTED + [TRACED])
def test_reader_reads_nothing_from_a_program_without_the_schedule(
        name, recorded):
    """The parent commit: no histogram, no `engine:serial_launch`; its
    look-ahead counters exist, so that one reader does report."""
    old = {"tdt_engine_decode_steps_total": 10,
           "tdt_engine_lookahead_steps_total": 9}
    form = dict(recorded.form, host=[
        e for e in recorded.form["host"] if e[1] != rounds.SERIAL_LAUNCH])
    ctx = {"counters_window_0": dict(old),
           "counters_window_1": {k: v + 100 for k, v in old.items()},
           "trace": xplane.Trace.of(form)}
    got = read(name, ctx)
    assert got == (100.0 if name == "engine.lookahead_share" else None)
    # A window in which nothing was decoded: nothing, never a division.
    ctx = {"counters_window_0": window()["counters_window_1"],
           "counters_window_1": window()["counters_window_1"],
           "trace": ctx["trace"]}
    if name != TRACED:
        assert read(name, ctx) is None


def test_the_manifest_lists_the_six_with_the_issues_cells():
    manifest = cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
    every = [w["name"] for w in manifest["workloads"]]
    listed = {m["name"]: m for m in manifest["per_layer"]}
    for name in COUNTED + [TRACED]:
        m = listed[name]
        assert m["moves"] == "token_gap_p95_ms"
        assert m["unit"] == ("rows" if "rows" in name else "%")
        assert m["source"] == ("program_span" if name == TRACED
                               else "program_counter")
        assert m["workloads"] == (
            WIDE if name == "scheduler.rows_in_flight_mean" else every)
