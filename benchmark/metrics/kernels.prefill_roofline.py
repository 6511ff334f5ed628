"""The same for the prefill-chunk programs: FLOPs of the prompts
prefilled in the traced span over peak (or their bytes over bandwidth,
if larger) over those programs' device time. Layer: kernels."""

from benchmark import layerwork

PREFILL_PROGRAM = r"prefill"


def read(ctx):
    pre = layerwork.prefill_work(ctx)
    chunks = layerwork.step_launches(ctx, PREFILL_PROGRAM, "prefill_chunks",
                                     exclude="decode_steps")
    seconds = sum(d for _, d in chunks) / 1e9
    if pre is None or seconds <= 0:
        return None
    least, _bound = ctx["cell"].work.prefill_least_seconds(
        ctx["cell"].config, pre[0], pre[1], ctx["peak"], ctx["chips"])
    return 100.0 * least / seconds
