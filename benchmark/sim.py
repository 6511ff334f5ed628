"""A count, not a measurement: what a cell's TTFT statistics would read
at a given decode round, by a simulation of the scheduler over the
cell's fixed arrivals.

    python3 -m benchmark.sim --workload qwen3-4b.chat
    python3 -m benchmark.sim --workload qwen3-4b.chat-closed8 --round 12.5

The traffic generator gives every seed the same sizes and the same
arrivals, so which request waits for which batch is a function of the
round time alone, and a statistic that turns on a single arrival racing
a single batch end shows here as a jump between neighbouring rounds. A
`benchmark` PR bounds a TTFT statistic in a cell only where the sweep of
rounds shows no such jump (PERF.md section 2); a `perf_opt` issue asks
here where a new round time lands before it claims.

The model, which a per-request dump of a chip run has to bear out
(PERF.md gives the comparison): behind ``--replicas 1`` the replica
worker takes whatever is queued and runs it to completion as ONE batch
on ``slots`` decode slots; inside a run the engine admits the batch's
requests in order while a slot is free, a prefill of ``PREFILL_MS[0] +
PREFILL_MS[1] * prompt`` ms stalling the others; every live slot gets a
token a round; a request's first token is its prefill's; arrivals during
a run wait for its end; a closed-loop caller sends its next request when
the run that held its last one ends, too late for the run that starts at
that instant unless nothing else is queued. Requests past the front
door's cap of ``PENDING_CAP`` waiting payloads are shed and retried,
which this does not model: it raises instead (a cell runs below that).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

from benchmark import cells, stats, traffic

PREFILL_MS = (12.0, 0.074)  # due to first token of an idle arrival: fixed, per prompt token
BATCH_START_MS = 0.0        # a run's start to its first prefill
PENDING_CAP = 8
STATISTICS = ("ttft_p50_ms", "ttft_p75_ms", "ttft_p90_ms", "ttft_mean_ms")


def simulate(spec: dict, round_ms: float, seconds: float, slots: int,
             first: int | None = None) -> list:
    """TTFT in ms of every request sent in a window of ``seconds``, in
    sending order. ``first``: how many of a closed loop's callers the
    first run finds (they race where they start together; default: those
    due at its start)."""
    reqs = traffic.generate(spec, 1, seconds, 1000)
    sizes = [(len(r.prompt), r.gen_len) for r in reqs]
    closed = spec["loop"] == "closed"
    end = seconds * 1e3
    if closed:
        step = float(spec.get("stagger_ms", 0))
        arrivals = [(k * step, k) for k in range(int(spec["clients"]))]
        deck = iter(range(len(arrivals), len(sizes)))
    else:
        arrivals = [(r.t * 1e3, r.i) for r in reqs]
        deck = iter(())
    due = {i: t for t, i in arrivals}
    arrivals.sort()
    ttft: dict = {}
    t = 0.0
    while arrivals:
        t = max(t, arrivals[0][0])
        batch = [i for a, i in arrivals if a <= t]
        if first and t == 0.0:
            batch = batch[:first]
        if len(batch) > PENDING_CAP:
            raise ValueError(
                f"{len(batch)} requests wait at {t:.0f} ms: over the front "
                f"door's cap of {PENDING_CAP}, the cell would shed")
        arrivals = [(a, i) for a, i in arrivals if i not in batch]
        t = _run(batch, sizes, due, ttft, t + BATCH_START_MS, round_ms, slots)
        if closed and t < end:
            # The callers of this run send their next requests at its
            # end. The worker has by then taken what was queued; they
            # are in time only for a worker that found nothing.
            late = 1e-6 if arrivals and arrivals[0][0] <= t else 0.0
            for _ in batch:
                i = next(deck, None)
                if i is not None:
                    due[i] = t
                    arrivals.append((t + late, i))
            arrivals.sort()
    return [ttft[i] for i in sorted(ttft)]


def _run(batch, sizes, due, ttft, t, round_ms, slots) -> float:
    """One ``engine.run(batch)`` from time ``t``; returns its end."""
    live: dict = {}  # request -> tokens still to decode
    waiting = list(batch)
    while live or waiting:
        while waiting and len(live) < slots:
            i = waiting.pop(0)
            t += PREFILL_MS[0] + PREFILL_MS[1] * sizes[i][0]
            ttft[i] = t - due[i]
            if sizes[i][1] > 1:
                live[i] = sizes[i][1] - 1
        if live:
            t += round_ms
            for i in list(live):
                live[i] -= 1
                if not live[i]:
                    del live[i]
    return t


def read(ttft_ms: list) -> dict:
    """The candidate statistics of one simulated window."""
    recs = [stats.Record(i=i, due=0.0, token_ts=[v / 1e3], status="ok")
            for i, v in enumerate(ttft_ms)]
    e2e = stats.end_to_end(recs, 0.0, 1.0)
    return {k: e2e[k] for k in STATISTICS} | {"requests": len(ttft_ms)}


def sweep(spec: dict, seconds: float, slots: int, lo: float, hi: float,
          step: float) -> dict:
    """Every statistic over rounds ``lo`` to ``hi`` ms: the widest
    relative difference between two neighbouring rounds (over the
    smaller reading), and where."""
    n = int(round((hi - lo) / step))
    rounds = [round(lo + k * step, 6) for k in range(n + 1)]
    rows = [read(simulate(spec, r, seconds, slots)) for r in rounds]
    out = {}
    for name in STATISTICS:
        jumps = [(abs(b[name] - a[name]) / min(a[name], b[name]), r)
                 for a, b, r in zip(rows, rows[1:], rounds[1:])]
        widest, at = max(jumps)
        out[name] = {"widest_neighbour_jump": widest, "at_round_ms": at,
                     "min": min(r[name] for r in rows),
                     "max": max(r[name] for r in rows)}
    out["requests"] = sorted({r["requests"] for r in rows})
    return out


def slots_of(config: dict) -> int:
    argv = config["serve_argv"]
    return int(argv[argv.index("--max-batch") + 1])


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, default=None,
                   help="default: BENCHMARK.json's run_seconds")
    p.add_argument("--round", type=float, default=None, metavar="MS",
                   help="one round's readings instead of the sweep")
    p.add_argument("--rounds", default="9.0:14.0:0.05", metavar="LO:HI:STEP")
    p.add_argument("--traffic-file", default=None)
    args = p.parse_args(argv)
    cell = cells.load_cell(args.workload, traffic_file=args.traffic_file)
    spec = cell.traffic
    seconds = args.seconds or float(cells.load_json(
        os.path.join(cells.ROOT, "BENCHMARK.json"))["run_seconds"])
    slots = slots_of(cell.config)
    if args.round is not None:
        out = read(simulate(spec, args.round, seconds, slots))
    else:
        lo, hi, step = (float(x) for x in args.rounds.split(":"))
        out = sweep(spec, seconds, slots, lo, hi, step)
    print(json.dumps({"workload": cell.name, "a_count_not_a_time": True,
                      **out}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
