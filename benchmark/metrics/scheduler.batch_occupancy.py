"""Tokens a decode step yields over the slots it has: generated tokens
(less each request's first, which its prefill makes) over decode steps
times --max-batch, from the program's live counters over the window.
Layer: scheduler."""

from benchmark import layerwork, server


def read(ctx):
    d = server.delta(ctx["counters_window_1"], ctx["counters_window_0"])
    steps = d.get(layerwork.DECODE_STEPS, 0)
    argv = ctx["cell"].config["serve_argv"]
    if steps <= 0 or "--max-batch" not in argv:
        return None
    slots = int(argv[argv.index("--max-batch") + 1])
    first = sum(1 for r in ctx["records"] if r.ok)
    tokens = max(d.get(layerwork.GENERATED, 0) - first, 0)
    return 100.0 * tokens / (steps * slots)
