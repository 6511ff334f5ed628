"""Share of the window's decode steps' unavoidable bytes that is
recurrent state: rows advanced x the state a row (read and written),
over that plus the weights once a step and the K/V rows of the decoded
tokens' contexts. Whether the traffic keeps the mechanism in front: 43%
at 32 rows in flight, under 3% at one. From the program's live counters
and gauge, the contexts from the client's records. Layer: model."""

from benchmark import layerwork, server

ROWS = "tdt_ssm_decode_rows_total"
STATE = "tdt_ssm_state_bytes_per_slot"


def read(ctx):
    after = ctx["counters_window_1"]
    d = server.delta(after, ctx["counters_window_0"])
    steps = d.get(layerwork.DECODE_STEPS, 0)
    state = after.get(STATE, 0)
    work, config = ctx["cell"].work, ctx["cell"].config
    if steps <= 0 or state <= 0 or ROWS not in d or not hasattr(
            work, "kv_bytes_per_token"):
        return None
    t0, t1 = ctx["t0"], ctx["t0"] + ctx["seconds"]
    context_sum = sum(
        rec.prompt_len + j for rec in ctx["records"] if rec.ok
        for j, t in enumerate(rec.token_ts) if j and t0 <= t <= t1)
    moved = d[ROWS] * 2 * state
    rest = (steps * work.matmul_params(config) * work._bytes(config)
            + context_sum * work.kv_bytes_per_token(config))
    return 100.0 * moved / (moved + rest)
