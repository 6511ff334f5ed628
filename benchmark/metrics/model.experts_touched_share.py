"""Held experts that got at least one row in a decode step, over the
held experts of every expert layer of every decode step of the window:
how near this rank's expert load is to a deployment's, where the other
ranks' rows touch every held expert (100%). From the program's live
counters. Layer: model."""

from benchmark import layerwork, server

TOUCHED = "tdt_moe_decode_experts_touched_total"
HELD = "tdt_moe_experts_held"


def read(ctx):
    after = ctx["counters_window_1"]
    d = server.delta(after, ctx["counters_window_0"])
    steps = d.get(layerwork.DECODE_STEPS, 0)
    held = after.get(HELD, 0)
    config = ctx["cell"].config
    if steps <= 0 or held <= 0 or TOUCHED not in d:
        return None
    layers = config["num_hidden_layers"] - config.get(
        "first_k_dense_replace", 0)
    return 100.0 * d[TOUCHED] / (held * layers * steps)
