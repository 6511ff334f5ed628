"""Mean rows in flight of the window's decode steps: the sum of the
program's histogram `tdt_engine_step_rows` over its count. What
`scheduler.batch_occupancy` is over the slots, as a number of rows, for
the cells that report no TTFT or throughput. Layer: scheduler."""

from benchmark import rounds


def read(ctx):
    h = rounds.rows_histogram(ctx)
    return None if h is None else h["sum"] / h["count"]
