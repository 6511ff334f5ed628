"""Median device-idle gap before a decode-step program, from the device
trace: what the host spends between two steps. Layer: engine."""

import statistics

from benchmark import layerwork

DECODE_PROGRAM = r"decode"


def read(ctx):
    steps = layerwork.step_launches(ctx, DECODE_PROGRAM, "decode_steps")
    gaps = ctx["trace"].gaps_before(s for s, _ in steps)
    return statistics.median(gaps) / 1e6 if gaps else None
