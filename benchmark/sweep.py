"""A benchmark PR's tool, not part of a run: several windows against ONE
server, to find a cell's knee, to read its output check on many seeds,
and to run its controls through the harness's own comparison.

    python3 -m benchmark.sweep --workload qwen3-4b.chat --seconds 30 \
        --rates 0.5,0.75,1.0,1.25 --seeds 11
    python3 -m benchmark.sweep --workload qwen3-4b.chat --seconds 50 \
        --seeds 1,2,3 --check --control int8
    python3 -m benchmark.sweep --workload qwen3-4b.chat --seconds 50 \
        --seeds 1,2,3 --check --serve-extra "--kv-dtype int8"

Each (rate, seed) pair is one window at the cell's own sizes, offered
and judged by ``benchmark.run``'s own functions; the weights are the
first seed's for the whole process. With ``--check`` the reference runs
over every finished request of each window once the server is down and
prints the cell's numbers beside their limits and ``correct``. The
controls have to come out ``correct: false``: ``--serve-extra`` switches
the program's own lower-precision path on, and ``--control int8`` puts
the picks of the reference computed in int8 in the served tokens' place
(a ``control`` line beside each ``reference`` line). ``--dump DIR``
keeps every judged token's reading, ``--trace-sample PATH`` traces the
last window and writes a short piece of the trace in the reduction's
neutral form (the tests' recorded trace). One JSON line per window;
nothing here is a result line.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shlex
import shutil
import sys

from benchmark import cells, run, server, stats, traffic


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seeds", default="1")
    p.add_argument("--rates", default="",
                   help="open loop: rates to try instead of the cell's")
    p.add_argument("--check", action="store_true")
    p.add_argument("--control", default=None, choices=["int8"])
    p.add_argument("--serve-extra", default="")
    p.add_argument("--dump", default=None, metavar="DIR")
    p.add_argument("--trace-sample", default=None, metavar="PATH")
    p.add_argument("--config-file", default=None)
    p.add_argument("--traffic-file", default=None)
    args = p.parse_args(argv)
    cell = cells.load_cell(args.workload, config_file=args.config_file,
                           traffic_file=args.traffic_file)
    run.require_chip(cell.chips)
    run.cache_every_program()
    compiles = run.CompileCounter()
    seeds = [int(s) for s in args.seeds.split(",")]
    rates = [float(r) for r in args.rates.split(",") if r] or [None]
    weight_seed = seeds[0] % run.WEIGHT_SEED_MOD
    run.patch_weight_seed(weight_seed)
    vocab = cell.config["vocab_size"]
    argv_s = [*cell.config["serve_argv"], *shlex.split(args.serve_extra)]
    trace_dir = os.path.join(run.WORK_DIR, f"trace.{os.getpid()}")
    samples = []
    with server.running_server(argv_s, run.WORK_DIR) as (host, port, _):
        run.say(phase="setup", **run.warm_up(
            host, port, cell, seeds[0], args.seconds, vocab, compiles))
        for rate in rates:
            at = cell if rate is None else dataclasses.replace(
                cell, traffic=dict(cell.traffic, rate_per_s=rate))
            for seed in seeds:
                reqs = traffic.generate(at.traffic, seed, args.seconds, vocab)
                last = (rate, seed) == (rates[-1], seeds[-1])
                w = run.window(host, port, at, reqs, args.seconds, compiles,
                               trace_dir if args.trace_sample and last
                               else None)
                recs, t0 = w["records"], w["t0"]
                d = server.delta(w["counters_window_1"],
                                 w["counters_window_0"])
                half = len(recs) // 2
                run.say(
                    phase="window", rate=rate, seed=seed,
                    compiles_in_window=w["compiled_in_window"],
                    server_shed=d.get("server.shed", 0),
                    decode_steps=d.get("tdt_engine_decode_steps_total"),
                    # A backlog that grows shows as later requests
                    # waiting longer than earlier ones.
                    ttft_p50_first_half_ms=stats.percentile(
                        stats.ttfts(recs[:half]), 50) * 1e3 if half else None,
                    ttft_p50_second_half_ms=stats.percentile(
                        stats.ttfts(recs[half:]), 50) * 1e3 if half else None,
                    **stats.summary(recs, t0, args.seconds),
                    **stats.end_to_end(recs, t0, args.seconds))
                samples.append((rate, seed, run.finished(recs, reqs)))
        peak = run.memory_peak_bytes(cell.chips)
    run.say(phase="memory", memory_peak_bytes=peak)
    if args.trace_sample:
        from benchmark import xplane

        tr = xplane.reduce_dir(trace_dir, chips=cell.chips)
        with open(args.trace_sample, "w") as f:
            json.dump(xplane.sample(tr.form, 0.4), f)
        shutil.rmtree(trace_dir, ignore_errors=True)
    if not args.check:
        return 0
    run.say(phase="released",
            live_bytes=server.release_program_state())
    weights = run.reference_weights(cell, weight_seed)
    limits = cell.traffic["correct"]
    if args.dump:
        os.makedirs(args.dump, exist_ok=True)
    for rate, seed, sample in samples:
        if not sample:
            continue
        read = run.check_outputs(cell, sample, weights, args.control,
                                 per_token=bool(args.dump))
        tokens = read.pop("tokens", None)
        if tokens:
            with open(os.path.join(args.dump, f"{cell.name}.{rate}.{seed}"
                                   ".tokens.json"), "w") as f:
                json.dump(tokens, f)
        low = read.pop("control", None)
        checks, correct = run.decide(read, limits)
        run.say(phase="reference", rate=rate, seed=seed, correct=correct,
                checks=checks, **read)
        if low:
            checks, correct = run.decide(low, limits)
            run.say(phase="control", control=args.control, rate=rate,
                    seed=seed, correct=correct, checks=checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
