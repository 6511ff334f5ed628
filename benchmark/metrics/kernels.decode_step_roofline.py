"""Least time the chip(s) could take for the traced span's decode steps
(weights once a step plus the live cache of the decoded tokens, over the
memory bandwidth; FLOPs over peak; the larger) over the device time of
the decode-step programs. Layer: kernels."""

from benchmark import layerwork

DECODE_PROGRAM = r"decode"


def read(ctx):
    dec = layerwork.decode_work(ctx)
    steps = layerwork.step_launches(ctx, DECODE_PROGRAM, "decode_steps")
    seconds = sum(d for _, d in steps) / 1e9
    if dec is None or seconds <= 0:
        return None
    # The counter's steps, of which the trace may hold a few more or
    # fewer at its edges: the work of as many steps as were timed.
    per_step = 1.0 / dec[0]
    n = len(steps)
    least, _bound = ctx["cell"].work.decode_least_seconds(
        ctx["cell"].config, n, int(dec[1] * per_step * n),
        int(dec[2] * per_step * n), ctx["peak"], ctx["chips"])
    return 100.0 * least / seconds
