"""Least time the chip could take for the recurrent-state kernel over
the traced span's decode steps (the advanced rows' states read and
written once a recurrent layer, over the memory bandwidth; its FLOPs
over the peak; the larger, by the configuration's work module) over the
device time of the operations named ``tdt_ssm_decode``. Layer: kernels."""

from benchmark import layerwork, server

KERNEL = "tdt_ssm_decode"
ROWS = "tdt_ssm_decode_rows_total"
DECODE_PROGRAM = r"decode"


def read(ctx):
    work = getattr(ctx["cell"].work, "ssm_decode_least_seconds", None)
    if work is None or "counters_trace_1" not in ctx:
        return None
    d = server.delta(ctx["counters_trace_1"], ctx["counters_trace_0"])
    counted = d.get(layerwork.DECODE_STEPS, 0)
    tr = ctx["trace"]
    ops = tr.form["devices"][tr.first_device()]["ops"]
    seconds = sum(dur for name, _, dur in ops if KERNEL in name) / 1e9
    steps = layerwork.step_launches(ctx, DECODE_PROGRAM, "decode_steps")
    if seconds <= 0 or not steps or counted <= 0 or ROWS not in d:
        return None
    # The rows of as many steps as the trace holds (the counter's span
    # may hold a few more or fewer at its edges).
    rows = int(d[ROWS] / counted * len(steps))
    least, _bound = work(ctx["cell"].config, rows, ctx["peak"], ctx["chips"])
    return 100.0 * least / seconds
