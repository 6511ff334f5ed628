"""Share of the window's decode steps that were dispatched a round
early, before the step before's tokens were fetched: the program's
counter `tdt_engine_lookahead_steps_total` over
`tdt_engine_decode_steps_total`. What is left launched serially, with
the device waiting for the host. Layer: engine."""

from benchmark import layerwork, rounds

LOOKAHEAD = "tdt_engine_lookahead_steps_total"


def read(ctx):
    d = rounds.window_delta(ctx)
    steps = d.get(layerwork.DECODE_STEPS, 0)
    if steps <= 0 or LOOKAHEAD not in d:
        return None
    return 100.0 * d[LOOKAHEAD] / steps
