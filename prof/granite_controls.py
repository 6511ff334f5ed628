"""The new cell's output check on several seeds against ONE server, with
the reference's controls: what ``benchmark.sweep --check --control int8``
does (the same functions of ``benchmark.run``), for a LIST of controls,
so that ``bf16_state`` (the hybrid's reference with ``S`` rounded to
bfloat16 after every position; ``benchmark.sweep`` knows ``int8`` alone)
is read on the same windows.

    python prof/granite_controls.py --seeds 900000131,700000237 \
        --controls int8,bf16_state [--seconds 50]
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.getcwd())

from benchmark import cells, run, server, stats, traffic  # noqa: E402

CELL = "granite-4.0-h-micro.chat-closed32"


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--seeds", required=True)
    p.add_argument("--controls", default="int8,bf16_state")
    p.add_argument("--seconds", type=float, default=50.0)
    p.add_argument("--workload", default=CELL)
    p.add_argument("--config-file", default=None)
    p.add_argument("--traffic-file", default=None)
    args = p.parse_args()
    cell = cells.load_cell(args.workload, config_file=args.config_file,
                           traffic_file=args.traffic_file)
    if not os.environ.get("REHEARSE"):
        run.require_chip(cell.chips)
    run.cache_every_program()
    compiles = run.CompileCounter()
    seeds = [int(s) for s in args.seeds.split(",")]
    weight_seed = seeds[0] % run.WEIGHT_SEED_MOD
    run.patch_weight_seed(weight_seed)
    vocab = cell.config["vocab_size"]
    samples = []
    with server.running_server(cell.config["serve_argv"],
                               run.WORK_DIR) as (host, port, _):
        run.say(phase="setup", **run.warm_up(
            host, port, cell, seeds[0], args.seconds, vocab, compiles))
        for seed in seeds:
            reqs = traffic.generate(cell.traffic, seed, args.seconds, vocab)
            w = run.window(host, port, cell, reqs, args.seconds, compiles)
            recs, t0 = w["records"], w["t0"]
            run.say(phase="window", seed=seed,
                    compiles_in_window=w["compiled_in_window"],
                    **stats.summary(recs, t0, args.seconds),
                    **stats.end_to_end(recs, t0, args.seconds))
            samples.append((seed, run.finished(recs, reqs)))
        peak = run.memory_peak_bytes(cell.chips)
    run.say(phase="memory", memory_peak_bytes=peak,
            live_bytes=server.release_program_state())
    weights = run.reference_weights(cell, weight_seed)
    limits = cell.traffic["correct"]
    for seed, sample in samples:
        for k, control in enumerate(args.controls.split(",")):
            read = run.check_outputs(cell, sample, weights, control)
            low = read.pop("control")
            if k == 0:
                checks, correct = run.decide(read, limits)
                run.say(phase="reference", seed=seed, correct=correct,
                        checks=checks, **read)
            checks, correct = run.decide(low, limits)
            run.say(phase="control", control=control, seed=seed,
                    correct=correct, checks=checks, **low)


if __name__ == "__main__":
    main()
