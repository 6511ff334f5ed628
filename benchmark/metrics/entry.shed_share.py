"""Share of generation payloads the front door answered `overloaded`,
from its own counters over the window (`shed` over `shed` + `requests`).
Layer: entry (serving/server.py)."""

from benchmark import server


def read(ctx):
    d = server.delta(ctx["counters_window_1"], ctx["counters_window_0"])
    shed, served = d.get("server.shed", 0), d.get("server.requests", 0)
    if shed + served <= 0:
        return None
    return 100.0 * shed / (shed + served)
