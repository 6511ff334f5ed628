"""Arithmetic from request records to the end-to-end metrics.

A record is what the client's clock saw of one request: when it was
due, when each token frame arrived, how it ended. Times are seconds on
``time.monotonic``; every latency is taken from the DUE time, so a
stall charges the requests it delayed.
"""

from __future__ import annotations

import dataclasses
import math


@dataclasses.dataclass
class Record:
    i: int
    due: float                 # when the request was due to be sent
    sent: float | None = None  # when the first attempt was written
    token_ts: list = dataclasses.field(default_factory=list)
    tokens: list = dataclasses.field(default_factory=list)
    status: str = "pending"    # ok | failed:<why>
    attempts: int = 0          # connections opened (1 + sheds honoured)
    shed: int = 0              # `overloaded` replies received
    done: float | None = None
    prompt_len: int = 0
    gen_len: int = 0
    server: dict | None = None  # the summary frame's `wire` entry

    @property
    def ok(self) -> bool:
        return self.status == "ok"


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile (numpy's default), q in [0, 100]."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of no samples")
    pos = (len(v) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def ttfts(records) -> list:
    return [r.token_ts[0] - r.due for r in records if r.ok and r.token_ts]


def token_gaps(records) -> list:
    """Every gap between consecutive token frames, pooled."""
    out = []
    for r in records:
        if r.ok:
            out.extend(b - a for a, b in zip(r.token_ts, r.token_ts[1:]))
    return out


def tokens_in_window(records, t0: float, t1: float) -> int:
    return sum(1 for r in records if r.ok for t in r.token_ts if t0 <= t <= t1)


def end_to_end(records, t0: float, seconds: float) -> dict:
    """Every end-to-end number the records can give, by metric name."""
    tt = ttfts(records)
    gaps = token_gaps(records)
    out = {"tokens_per_s":
           tokens_in_window(records, t0, t0 + seconds) / seconds}
    if tt:
        out["ttft_p50_ms"] = percentile(tt, 50) * 1e3
        out["ttft_p75_ms"] = percentile(tt, 75) * 1e3
        out["ttft_p90_ms"] = percentile(tt, 90) * 1e3
        out["ttft_mean_ms"] = sum(tt) / len(tt) * 1e3
    if gaps:
        out["token_gap_p99_ms"] = percentile(gaps, 99) * 1e3
        out["token_gap_p95_ms"] = percentile(gaps, 95) * 1e3
        out["token_gap_p50_ms"] = percentile(gaps, 50) * 1e3
    return out


def summary(records, t0: float, seconds: float) -> dict:
    """Counts that go on an earlier line: samples, lateness, sheds."""
    late = [r.sent - r.due for r in records if r.sent is not None]
    return {
        "requests": len(records),
        "ok": sum(r.ok for r in records),
        "failed": sum(not r.ok for r in records),
        "shed_replies": sum(r.shed for r in records),
        "attempts": sum(r.attempts for r in records),
        "ttft_samples": len(ttfts(records)),
        "gap_samples": len(token_gaps(records)),
        "tokens_total": sum(len(r.token_ts) for r in records if r.ok),
        "tokens_in_window": tokens_in_window(records, t0, t0 + seconds),
        "generator_late_p50_ms": percentile(late, 50) * 1e3 if late else None,
        "generator_late_max_ms": max(late) * 1e3 if late else None,
        "drain_s": (max((r.done for r in records if r.done), default=t0)
                    - (t0 + seconds)),
    }
