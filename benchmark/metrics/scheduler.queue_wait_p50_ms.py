"""Median enqueue-to-admit wait of the requests the engine finished in
the window, from the program's own timeline histogram (bucketed: linear
inside the bucket). Layer: scheduler."""

from benchmark import server

HISTOGRAM = "tdt_request_queue_wait_seconds"


def read(ctx):
    after, before = ctx["counters_window_1"], ctx["counters_window_0"]
    if HISTOGRAM not in after:
        return None
    q = server.histogram_quantile(
        server.delta(after, before)[HISTOGRAM], 0.5)
    return None if q is None else q * 1e3
