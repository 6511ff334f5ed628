"""Percentile, pooled-gap and window-rate arithmetic on hand-made
timelines, and a stalled window that has to move every metric."""

import pytest

from benchmark import stats
from benchmark.stats import Record


def rec(i, due, first, n, gap, **kw):
    ts = [first + j * gap for j in range(n)]
    return Record(i=i, due=due, sent=due + 0.001, token_ts=ts,
                  tokens=list(range(n)), status="ok", attempts=1,
                  done=ts[-1] + 0.01, prompt_len=10, gen_len=n, **kw)


def steady(stalls=(), stall=0.0):
    """20 requests, one due every second, first token 0.5 s after due,
    11 tokens 0.05 s apart. Each time in ``stalls`` freezes everything
    after it for ``stall`` seconds."""
    out = []
    for i in range(20):
        r = rec(i, float(i), i + 0.5, 11, 0.05)
        r.token_ts = [t + stall * sum(t >= s for s in stalls)
                      for t in r.token_ts]
        out.append(r)
    return out


def test_percentile_matches_linear_interpolation():
    v = [1.0, 2.0, 3.0, 4.0, 5.0]
    assert stats.percentile(v, 50) == 3.0
    assert stats.percentile(v, 90) == pytest.approx(4.6)
    assert stats.percentile(v, 0) == 1.0 and stats.percentile(v, 100) == 5.0
    assert stats.percentile([7.0], 99) == 7.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_latencies_are_taken_from_the_due_time():
    r = rec(0, 10.0, 12.5, 3, 0.1)
    r.sent = 11.0  # sent a second late: the lateness is the request's
    assert stats.ttfts([r]) == [2.5]
    assert stats.token_gaps([r]) == pytest.approx([0.1, 0.1])


def test_window_rate_counts_tokens_received_inside_the_window():
    recs = steady()
    e = stats.end_to_end(recs, 0.0, 20.0)
    # Request 19's tokens arrive from 19.5 to 20.0: 11 inside.
    assert e["tokens_per_s"] == pytest.approx(220 / 20.0)
    e = stats.end_to_end(recs, 0.0, 10.0)
    assert e["tokens_per_s"] == pytest.approx((9 * 11 + 11) / 10.0)
    assert e["ttft_p50_ms"] == pytest.approx(500.0)
    assert e["token_gap_p99_ms"] == pytest.approx(50.0)


def test_the_four_ttft_statistics():
    recs = [rec(i, 0.0, w, 2, 0.05) for i, w in
            enumerate((0.1, 0.2, 0.3, 0.4, 2.0))]
    e = stats.end_to_end(recs, 0.0, 10.0)
    assert e["ttft_p50_ms"] == pytest.approx(300.0)
    assert e["ttft_p75_ms"] == pytest.approx(400.0)
    assert e["ttft_p90_ms"] == pytest.approx(400.0 + 0.6 * 1600.0)
    assert e["ttft_mean_ms"] == pytest.approx(600.0)
    recs[4].status = "failed:no_summary_before_drain_limit"
    e = stats.end_to_end(recs, 0.0, 10.0)  # a failed request gives none
    assert e["ttft_mean_ms"] == pytest.approx(250.0)
    assert e["ttft_p75_ms"] == pytest.approx(325.0)


def test_one_arrival_across_a_batch_end_moves_the_mean_by_its_share():
    """Why a cell may bound the mean where a percentile is a coin: 40
    requests, 30 served at once (60 ms) and 10 that waited for a batch
    (0.5-1.4 s). The 30th by rank arrives just before or just after a
    batch's end: 60 ms or 1.2 s."""
    waits = [0.06] * 29 + [0.5 + 0.1 * k for k in range(10)]
    before = stats.end_to_end(
        [rec(i, 0.0, w, 2, 0.05) for i, w in enumerate(waits + [0.06])],
        0.0, 10.0)
    after = stats.end_to_end(
        [rec(i, 0.0, w, 2, 0.05) for i, w in enumerate(waits + [1.2])],
        0.0, 10.0)
    assert after["ttft_p75_ms"] > 3 * before["ttft_p75_ms"]
    assert after["ttft_mean_ms"] / before["ttft_mean_ms"] == pytest.approx(
        1 + 1.14 / sum(waits + [0.06]), rel=1e-6)
    assert after["ttft_mean_ms"] < 1.11 * before["ttft_mean_ms"]


def test_failed_requests_count_and_give_no_latency():
    recs = steady()
    recs[3].status = "failed:retries_exhausted"
    s = stats.summary(recs, 0.0, 20.0)
    assert (s["ok"], s["failed"], s["ttft_samples"]) == (19, 1, 19)
    assert stats.end_to_end(recs, 0.0, 20.0)["tokens_per_s"] == pytest.approx(
        209 / 20.0)


def test_a_stalled_window_moves_every_end_to_end_metric():
    base = stats.end_to_end(steady(), 0.0, 20.0)
    # One 2 s freeze late in the window: the requests after it get
    # their first tokens late, and tokens fall out of the window's end.
    # It breaks a single gap of 200, which a p99 rightly does not see.
    hurt = stats.end_to_end(steady([15.7], 2.0), 0.0, 20.0)
    assert hurt["tokens_per_s"] < base["tokens_per_s"]
    assert hurt["ttft_p90_ms"] > base["ttft_p90_ms"] * 2
    assert hurt["token_gap_p99_ms"] == pytest.approx(base["token_gap_p99_ms"])
    # A freeze of 0.4 s inside every request from the sixth on (another
    # request's prefill between two steps) moves every metric.
    worse = stats.end_to_end(steady([i + 0.7 for i in range(5, 20)], 0.4),
                             0.0, 20.0)
    assert worse["tokens_per_s"] < base["tokens_per_s"]
    assert worse["ttft_p50_ms"] > base["ttft_p50_ms"] * 2
    assert worse["ttft_p90_ms"] > base["ttft_p90_ms"] * 2
    assert worse["token_gap_p99_ms"] > base["token_gap_p99_ms"] * 2


def test_summary_reports_lateness_and_sheds():
    recs = steady()
    recs[0].shed, recs[0].attempts = 2, 3
    s = stats.summary(recs, 0.0, 20.0)
    assert s["shed_replies"] == 2 and s["attempts"] == 22
    assert s["generator_late_max_ms"] == pytest.approx(1.0)
    assert s["gap_samples"] == 200
