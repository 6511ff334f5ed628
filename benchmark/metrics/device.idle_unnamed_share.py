"""Share of the first device's idle time that lies in gaps whose
midpoint no span of the program's own (`entry:`, `scheduler:`,
`engine:`) covers: idle time the program does not account for. Nothing
where the trace holds no such span at all. Layer: device."""

from benchmark import spans


def read(ctx):
    tr = ctx["trace"]
    own, idle = spans.program_spans(tr), spans.idle_ns(tr)
    if not own or idle <= 0:
        return None
    return 100.0 * spans.idle_unnamed_ns(tr, own) / idle
