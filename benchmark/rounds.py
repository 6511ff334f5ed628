"""The schedule, for the per-layer readers that share it: the rows each
decode step carried and what stood in each decoded token's gap, from
the program's two histograms over the window, and the decode rounds of
a trace that launched their own step.

The program (``models/continuous.py``) observes ``tdt_engine_step_rows``
once a launched decode step, with one bucket edge a row, and
``tdt_engine_token_gap_seconds{after=admit|serial|ahead}`` once a
decoded token; a round that finds no step parked launches its own
inside a span ``engine:serial_launch``. A program without them (an
older commit) yields ``None`` here, and the readers then report
nothing.
"""

from __future__ import annotations

from benchmark import server, spans

STEP_ROWS = "tdt_engine_step_rows"
TOKEN_GAP = "tdt_engine_token_gap_seconds"
AFTER = ("admit", "serial", "ahead")
ROUND, SERIAL_LAUNCH = "engine:decode_round", "engine:serial_launch"


def window_delta(ctx: dict) -> dict:
    """The counters' change over the window, histograms bucket by
    bucket; worked out once a run."""
    if "_window" not in ctx:
        ctx["_window"] = server.delta(ctx["counters_window_1"],
                                      ctx["counters_window_0"])
    return ctx["_window"]


def rows_histogram(ctx: dict) -> dict | None:
    """``tdt_engine_step_rows`` over the window; None where the program
    has no such histogram or launched no step."""
    h = window_delta(ctx).get(STEP_ROWS)
    return h if h and h["count"] > 0 else None


def step_rows(ctx: dict) -> list | None:
    """``(rows, steps)`` for every number of rows some decode step of
    the window carried, in rising order (None as ``rows_histogram``). A
    step past the last edge counts at the last edge."""
    h = rows_histogram(ctx)
    if h is None:
        return None
    rows = [int(e) for e in h["edges"]] + [int(h["edges"][-1])]
    return [(r, c) for r, c in zip(rows, h["counts"]) if c > 0]


def rows_at_token_quantile(pairs: list, q: float) -> int:
    """The rows of the step at or under which the share ``q`` of the
    tokens were made: each ``(rows, steps)`` pair weighs ``rows x
    steps`` tokens."""
    want = q * sum(r * c for r, c in pairs)
    cum = 0
    for r, c in pairs:
        cum += r * c
        if cum >= want:
            return r
    return pairs[-1][0]


def gap_counts(ctx: dict) -> dict | None:
    """Decoded tokens of the window by what stood in their gap
    (``AFTER``); None where the program counts none."""
    d = window_delta(ctx)
    counts = {a: d.get(f"{TOKEN_GAP}[after={a}]", {}).get("count", 0)
              for a in AFTER}
    return counts if sum(counts.values()) > 0 else None


def gap_share(ctx: dict, after: str) -> float | None:
    """Percent of the window's decoded tokens whose gap held ``after``:
    0.0, not nothing, where other tokens were counted and none such."""
    counts = gap_counts(ctx)
    if counts is None:
        return None
    return 100.0 * counts[after] / sum(counts.values())


def serial_rounds(tr) -> list:
    """``(start_ns, dur_ns)`` of every decode round of the trace that
    holds an ``engine:serial_launch``: a round that launched its own
    step, none having been parked for it."""
    launches = spans.merged(spans.named(tr, SERIAL_LAUNCH))
    return [(s, d) for s, d in spans.named(tr, ROUND)
            if spans.covered_ns(launches, s, s + d) > 0]
