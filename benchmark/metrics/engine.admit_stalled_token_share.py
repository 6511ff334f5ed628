"""Share of the window's decoded tokens that waited behind an admission
(its prefill, or a chunk of it, ran since the row's last token): the
program's histogram `tdt_engine_token_gap_seconds[after=admit]`, its
count over the three counts. At 5% the p95 gap becomes an admission's
prefill. Layer: engine."""

from benchmark import rounds


def read(ctx):
    return rounds.gap_share(ctx, "admit")
