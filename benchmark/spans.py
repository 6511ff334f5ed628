"""The program's own spans in a profiler trace, for the per-layer
readers that share them.

The program marks the served request's hot path with
``runtime/profiling.trace_span`` (``layer:phase`` names: ``entry:``,
``scheduler:``, ``engine:``). A span is a ``TraceAnnotation``, so it
lies in the host plane of the same file as the device lines, on the
same clock. ``xplane.from_xplane`` keeps an event's name, start and
duration, and of its thread only a name that threads may share, so a
child is found by time: a span that lies inside its parent's interval.
That is sound for ``scheduler:*`` and ``engine:*``, which all come from
the one worker thread of the one replica. A span that was open when
the profiler started or stopped is not in the trace at all.

A trace of a program without such spans (an older commit) yields empty
lists here, and the readers then report nothing.
"""

from __future__ import annotations

import bisect
import re
import statistics

PROGRAM_SPAN = re.compile(r"^(entry|scheduler|engine):")
WAIT_FOR_WORK = "scheduler:wait_for_work"


def named(tr, name: str) -> list:
    """``(start_ns, dur_ns)`` of every host span called ``name``, in
    time order."""
    return sorted((s, d) for _, n, s, d in tr.form["host"] if n == name)


def program_spans(tr) -> list:
    """``(start_ns, dur_ns)`` of every span the program itself made."""
    return sorted((s, d) for _, n, s, d in tr.form["host"]
                  if PROGRAM_SPAN.match(n))


def merged(spans) -> list:
    """The union of ``(start, dur)`` spans as disjoint ``(start, end)``
    intervals in time order."""
    out: list = []
    for s, d in sorted(spans):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], s + d)
        else:
            out.append([s, s + d])
    return out


def covered_ns(intervals: list, a: float, b: float) -> float:
    """How much of ``[a, b]`` the disjoint sorted ``intervals`` cover."""
    i = max(bisect.bisect_right(intervals, [a, float("inf")]) - 1, 0)
    total = 0.0
    while i < len(intervals) and intervals[i][0] < b:
        total += max(min(intervals[i][1], b) - max(intervals[i][0], a), 0.0)
        i += 1
    return total


def covers(intervals: list, t: float) -> bool:
    """Whether one of the disjoint sorted ``intervals`` holds ``t``."""
    i = bisect.bisect_right(intervals, [t, float("inf")]) - 1
    return i >= 0 and intervals[i][0] <= t <= intervals[i][1]


def self_ms(tr, name: str, child: str) -> list:
    """Self time of each span called ``name`` with respect to one kind
    of child: its duration less the part of it that the ``child`` spans
    inside it cover, in milliseconds."""
    kids = merged(named(tr, child))
    return [(d - covered_ns(kids, s, s + d)) / 1e6 for s, d in named(tr, name)]


def median_ms(values: list) -> float | None:
    return statistics.median(values) if values else None


def idle_ns(tr) -> float:
    return sum(d for _, d in tr.idle_gaps())


def idle_outside_ns(tr, spans) -> float:
    """Idle time of the first device that lies outside every one of
    ``spans``."""
    cover = merged(spans)
    return sum(d - covered_ns(cover, s, s + d) for s, d in tr.idle_gaps())


def idle_unnamed_ns(tr, spans) -> float:
    """Idle time of the first device in gaps whose midpoint none of
    ``spans`` covers (the rule ``Trace.host_span_at`` names a gap by)."""
    cover = merged(spans)
    return sum(d for s, d in tr.idle_gaps()
               if not covers(cover, s + d / 2))
