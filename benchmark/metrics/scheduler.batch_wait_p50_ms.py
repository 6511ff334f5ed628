"""Median enqueue-to-batch-start wait of the requests the engine finished
in the window: how long a request lay in the replica's queue until the
worker took it into the batch it runs next, from the program's own
timeline histogram (read as `scheduler.queue_wait_p50_ms` reads its
own). Queue wait less this is the wait for a slot inside the engine.
Layer: scheduler."""

from benchmark import server

HISTOGRAM = "tdt_request_batch_wait_seconds"


def read(ctx):
    after, before = ctx["counters_window_1"], ctx["counters_window_0"]
    if HISTOGRAM not in after:
        return None
    q = server.histogram_quantile(
        server.delta(after, before)[HISTOGRAM], 0.5)
    return None if q is None else q * 1e3
