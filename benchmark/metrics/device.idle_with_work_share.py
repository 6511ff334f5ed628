"""Share of the traced span in which the first device was idle while
the program had work: its idle time outside every
`scheduler:wait_for_work` span (the replica's worker waiting on an empty
queue). Nothing where the trace holds no span of the program's own.
Layer: device."""

from benchmark import spans


def read(ctx):
    tr = ctx["trace"]
    if tr.window_s <= 0 or not spans.program_spans(tr):
        return None
    waiting = spans.named(tr, spans.WAIT_FOR_WORK)
    return 100.0 * spans.idle_outside_ns(tr, waiting) / (tr.window_s * 1e9)
