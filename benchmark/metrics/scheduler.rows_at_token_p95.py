"""The rows of the decode step at or under which 95% of the window's
decoded tokens were made: the program's histogram `tdt_engine_step_rows`
(one edge a row) with each bucket weighted by its rows. Where a step's
time grows with its rows, this is the step the p95 gap falls on: a p95
gap that rose WITH it is a fuller step, not a slower one. Layer:
scheduler."""

from benchmark import rounds


def read(ctx):
    pairs = rounds.step_rows(ctx)
    if pairs is None:
        return None
    return float(rounds.rows_at_token_quantile(pairs, 0.95))
