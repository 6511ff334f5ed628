"""Least time the chip could take for the absorbed latent-attention
decode kernel over the traced span's decode steps (the decoded tokens'
latent rows over the memory bandwidth; its FLOPs over the peak; the
larger, by the configuration's work module) over the device time of the
operations named ``tdt_mla_decode_paged``. Layer: kernels."""

from benchmark import layerwork

KERNEL = "tdt_mla_decode_paged"
DECODE_PROGRAM = r"decode"


def read(ctx):
    work = getattr(ctx["cell"].work, "mla_decode_least_seconds", None)
    dec = layerwork.decode_work(ctx)
    if work is None or dec is None:
        return None
    tr = ctx["trace"]
    ops = tr.form["devices"][tr.first_device()]["ops"]
    seconds = sum(d for name, _, d in ops if KERNEL in name) / 1e9
    steps = layerwork.step_launches(ctx, DECODE_PROGRAM, "decode_steps")
    if seconds <= 0 or not steps:
        return None
    # The contexts of as many steps as the trace holds (the counter's
    # span may hold a few more or fewer at its edges).
    context_sum = int(dec[2] / dec[0] * len(steps))
    least, _bound = work(ctx["cell"].config, context_sum, ctx["peak"],
                         ctx["chips"])
    return 100.0 * least / seconds
