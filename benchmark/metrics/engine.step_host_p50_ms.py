"""Median over the traced `engine:decode_round` spans of the round's
duration less its `engine:fetch` child (the blocking wait for the
step's tokens): what the host itself spends on a decode round, in
dispatch, NaN guard, sampling, token frames. Layer: engine."""

from benchmark import spans

ROUND, FETCH = "engine:decode_round", "engine:fetch"


def read(ctx):
    return spans.median_ms(spans.self_ms(ctx["trace"], ROUND, FETCH))
