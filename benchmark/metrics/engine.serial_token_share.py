"""Share of the window's decoded tokens whose step was launched in the
very round that emitted them, none having been parked: the program's
histogram `tdt_engine_token_gap_seconds[after=serial]`, its count over
the three counts. Such a token waited for the host's round as well as
for the step. Layer: engine."""

from benchmark import rounds


def read(ctx):
    return rounds.gap_share(ctx, "serial")
