"""FLOPs the traced span's prefill and decoded tokens require over
span x chips x the chip's peak. Layer: model."""

from benchmark import layerwork


def read(ctx):
    f = layerwork.flops(ctx)
    tr = ctx["trace"]
    if f is None or tr.window_s <= 0:
        return None
    return 100.0 * f / (tr.window_s * ctx["chips"] * ctx["peak"].flops_bf16)
