"""Median over the traced `engine:admit` spans of one request's
admission (pages, prefill chunks, first token) less the
`engine:decode_round` spans nested in it: the running batch's steps
between its chunks are not its own. Layer: engine."""

from benchmark import spans

ADMIT, ROUND = "engine:admit", "engine:decode_round"


def read(ctx):
    return spans.median_ms(spans.self_ms(ctx["trace"], ADMIT, ROUND))
