"""Share of the first device's idle time that lies inside decode rounds
which launched their own step (`engine:decode_round` spans that hold an
`engine:serial_launch`): the device waiting for the host's round where
the look-ahead did not engage. 0.0 where the traced span holds no such
round; nothing where the trace holds no decode round at all, or the
program does not mark its serial launches (it then has no
`tdt_engine_step_rows` either: both came together). Layer: device."""

from benchmark import rounds, spans


def read(ctx):
    tr = ctx["trace"]
    idle = spans.idle_ns(tr)
    if (idle <= 0 or not spans.named(tr, rounds.ROUND)
            or rounds.STEP_ROWS not in ctx.get("counters_window_1", ())):
        return None
    inside = idle - spans.idle_outside_ns(tr, rounds.serial_rounds(tr))
    return 100.0 * inside / idle
