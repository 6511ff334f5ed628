"""One run of one cell: ``python3 -m benchmark.run --workload <name>
--seed <n> --seconds <s> --trace <0|1>``.

One process. It fails, with no result line, when JAX finds no TPU or
fewer chips than the cell asks for. It starts the program's own entry,
``serving.run_server.main``, on a thread with the configuration's
``serve_argv``; warms up every program the seed's traffic will use;
offers the cell's load for ``--seconds`` from the main process over the
socket, one streaming request per connection; shuts the server down;
and only then runs the plain reference over a sample of what was
served. The last line of standard output is the result object.
"""

from __future__ import annotations

import time

_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

from benchmark import cells, client, server, stats, traffic  # noqa: E402

WORK_DIR = os.path.join(cells.ROOT, ".bench_work")
# The jax.monitoring event of a program handed to the compiler (or
# looked up in the persistent cache): a new program signature.
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",)
WEIGHT_SEED_MOD = 2**31 - 1  # jax.random.key takes what int32 holds


def say(**fields) -> None:
    """An earlier line of standard output (never the last)."""
    print(json.dumps(fields), flush=True)


def require_chip(chips: int) -> dict:
    """The device as JAX reports it; exits non-zero off the TPU or with
    fewer chips than the cell asks for."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"the benchmark needs a TPU; JAX found {devs[0].platform} "
            f"({devs[0].device_kind} x{len(devs)})")
    if len(devs) < chips:
        raise SystemExit(
            f"the cell asks for {chips} chips; JAX reports {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def cache_every_program() -> None:
    """Keep also the sub-second programs in the persistent compile
    cache, so that only the first run of a cell in a checkout compiles."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


class CompileCounter:
    """Counts programs traced or compiled, by a jax.monitoring listener."""

    def __init__(self):
        import jax.monitoring

        self.n = 0
        self.names: dict = {}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event in COMPILE_EVENTS:
            self.n += 1
            key = f"{event.rsplit('/', 1)[-1]}:{kw.get('fun_name')}"
            self.names[key] = self.names.get(key, 0) + 1


def patch_weight_seed(seed: int) -> None:
    """``run_server`` has no ``--seed``: hand ``AutoLLM.from_pretrained``
    the run's seed, so the served weights are the seed's."""
    import functools

    from triton_distributed_tpu import models

    orig = models.AutoLLM.__dict__["from_pretrained"].__func__
    models.AutoLLM.from_pretrained = staticmethod(
        functools.partial(orig, seed=seed))


def memory_peak_bytes(chips: int) -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


def warm_up(host, port, cell, seed, seconds, vocab, compiles) -> dict:
    """Drive every program the window will use, the way the window
    will: the window's own prompt lengths (one request per distinct
    length; where that is more than 64 requests, per distinct length
    rounded up to 16, the finest grain a TPU tile gives a program to
    specialise on), other tokens, a few output tokens each, one streaming
    request per connection from as many callers as the server takes at
    once. Passes repeat until one compiles nothing (at most 4)."""
    import dataclasses

    argv = cell.config["serve_argv"]
    callers = int(cell.traffic.get("clients") or (
        argv[argv.index("--max-batch") + 1] if "--max-batch" in argv else 8))
    passes = []
    for k in range(4):
        reqs = traffic.generate(cell.traffic, seed + 7919 * (k + 1), seconds,
                                vocab)
        longest = max(len(r.prompt) for r in reqs)
        grain = 16 if len({len(r.prompt) for r in reqs}) > 64 else 1
        lens = {}
        for r in reqs:
            n = min(-(-len(r.prompt) // grain) * grain, longest)
            # Repeating the prompt's own tokens keeps a shared prefix.
            body = (r.prompt * (n // len(r.prompt) + 1))[:n]
            lens.setdefault(n, dataclasses.replace(
                r, prompt=body, gen_len=min(r.gen_len, 4 + k)))
        deck = list(lens.values())
        before, t = compiles.n, time.monotonic()
        recs = client.run_deck(host, port, deck, callers)
        bad = [r.status for r in recs if not r.ok]
        if bad or len(recs) != len(deck):
            raise RuntimeError(f"warm-up requests failed: {bad[:3]}")
        passes.append({"requests": len(deck), "compiled": compiles.n - before,
                       "seconds": time.monotonic() - t})
        if passes[-1]["compiled"] == 0:
            break
    return {"passes": passes}


def finished(records, reqs) -> list:
    """Every request of the window that ended ``ok``, in sending order:
    ``(prompt, served tokens)`` pairs."""
    by_i = {r.i: r for r in reqs}
    done = sorted((rec for rec in records if rec.ok and rec.tokens),
                  key=lambda rec: rec.i)
    return [(list(by_i[rec.i].prompt), list(rec.tokens)) for rec in done]


def pad_sizes(cell: cells.Cell) -> tuple:
    """Fixed reference shapes for the cell, so that it compiles once:
    requests to a forward pass, the padded sequence length, and a
    pass's room for served tokens."""
    t = cell.traffic
    block = int(t["correct"]["requests_to_a_pass"])
    pmax = max(c["prompt"]["max"] for c in t["classes"])
    omax = max(c["output"]["max"] for c in t["classes"])
    share = t.get("sharing") or {}
    if share.get("prefix_pool"):
        pmax += share["prefix"]["max"]
    return block, -(-(pmax + omax) // 128) * 128, block * omax


LIMITED = ("logit_gap_max", "logit_gap_mean")


def decide(read: dict, limits: dict) -> tuple:
    """Each number the cell's file gives a limit, beside that limit, and
    whether all keep to theirs."""
    checks = {name: {"value": read[name], "limit": limits[name]}
              for name in LIMITED if name in limits}
    return checks, bool(checks) and all(
        c["value"] <= c["limit"] for c in checks.values())


def check_outputs(cell, sample, weights, control=None,
                  per_token=False) -> dict:
    """The configuration's reference over every finished request of the
    window."""
    block, pad_to, rows_pad = pad_sizes(cell)
    t = time.monotonic()
    read = cell.reference.judge(cell.config, weights, sample, pad_to,
                                rows_pad, block=block, control=control,
                                per_token=per_token)
    read["reference_s"] = time.monotonic() - t
    return read


def reference_weights(cell, weight_seed: int):
    import jax

    return cell.reference.make_weights(cell.config, weight_seed,
                                       jax.devices()[:cell.chips])


def window(host, port, cell, reqs, seconds, compiles, trace_dir=None) -> dict:
    """Offer ``reqs`` for ``seconds`` and wait for every request that
    was started; with ``trace_dir`` the profiler runs over a part of the
    window. Returns the records, the window's start and the counters
    read around it."""
    import jax

    t0 = time.monotonic() + 0.25
    out: dict = {"t0": t0, "compiled_before": compiles.n}
    names_before = dict(compiles.names)
    result: dict = {}
    driver = threading.Thread(
        target=lambda: result.update(records=client.drive(
            host, port, reqs, cell.traffic, seconds, t0)),
        name="load")
    out["counters_window_0"] = server.counters(host, port)
    driver.start()
    if trace_dir:
        # Trace a part of the window, not all of it: traces are
        # large, and reading one must fit the run's time.
        span = min(float(cell.traffic.get("trace_seconds", 10.0)),
                   seconds * 0.5)
        lead = (seconds - span) * 0.5
        time.sleep(max(t0 + lead - time.monotonic(), 0))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # TraceAnnotations stay
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        # Starting and stopping the profiler take seconds in which
        # the server goes on: count and stamp inside them, so that
        # the counted work is the traced work.
        out["counters_trace_0"] = server.counters(host, port)
        out["trace_t0"] = time.monotonic()
        time.sleep(span)
        out["trace_t1"] = time.monotonic()
        out["counters_trace_1"] = server.counters(host, port)
        jax.profiler.stop_trace()
    time.sleep(max(t0 + seconds - time.monotonic(), 0))
    out["counters_window_1"] = server.counters(host, port)
    out["compiled_in_window"] = compiles.n - out.pop("compiled_before")
    out["compiled_names"] = {k: v - names_before.get(k, 0)
                             for k, v in compiles.names.items()
                             if v > names_before.get(k, 0)}
    driver.join()
    out["records"] = result["records"]
    return out


def run(args) -> int:
    cell = cells.load_cell(args.workload, config_file=args.config_file,
                           traffic_file=args.traffic_file)
    device = require_chip(cell.chips)
    import jax

    from benchmark import peaks

    peak = peaks.lookup(device["kind"])
    cache_every_program()
    compiles = CompileCounter()
    weight_seed = args.seed % WEIGHT_SEED_MOD
    patch_weight_seed(weight_seed)
    vocab = cell.config["vocab_size"]
    reqs = traffic.generate(cell.traffic, args.seed, args.seconds, vocab)
    say(phase="traffic", workload=cell.name, seed=args.seed,
        **traffic.histogram(reqs))
    trace_dir = os.path.join(WORK_DIR, f"trace.{os.getpid()}")
    shutil.rmtree(trace_dir, ignore_errors=True)
    ctx: dict = {"cell": cell, "peak": peak, "chips": cell.chips,
                 "seconds": args.seconds}
    with server.running_server(cell.config["serve_argv"],
                               WORK_DIR) as (host, port, listen_s):
        warm = warm_up(host, port, cell, args.seed, args.seconds, vocab,
                       compiles)
        say(phase="setup", listening_after_s=listen_s, **warm)
        ctx.update(window(host, port, cell, reqs, args.seconds, compiles,
                          trace_dir if args.trace else None))
        peak_bytes = memory_peak_bytes(cell.chips)
    # The server is down: free its state before the reference runs.
    live_bytes = server.release_program_state()
    records, t0 = ctx["records"], ctx["t0"]
    compiled_in_window = ctx["compiled_in_window"]
    ctx["reqs"] = {r.i: r for r in reqs}
    e2e = stats.end_to_end(records, t0, args.seconds)
    e2e["setup_s"] = t0 - _PROCESS_START
    say(phase="window", compiles_in_window=compiled_in_window,
        compiled=ctx["compiled_names"],
        **stats.summary(records, t0, args.seconds),
        **{k: v for k, v in e2e.items()})
    # What the scheduler's simulation (benchmark/sim.py) is held against:
    # when each request was due, got its first token and ended, in ms
    # from the window's start.
    say(phase="requests", columns=["i", "due", "ttft", "done", "prompt",
                                   "output"],
        rows=[[r.i, round((r.due - t0) * 1e3, 1),
               round((r.token_ts[0] - r.due) * 1e3, 1) if r.token_ts else None,
               round((r.done - t0) * 1e3, 1) if r.done else None,
               r.prompt_len, r.gen_len] for r in records])
    sample = finished(records, reqs)
    attempted = len(records)
    failed = sum(not r.ok for r in records)
    if sample:
        read = check_outputs(cell, sample,
                             reference_weights(cell, weight_seed))
        checks, correct = decide(read, cell.traffic["correct"])
    else:
        read = {}
        checks, correct = {"requests_finished": {"value": 0, "limit": 1}}, False
    say(phase="reference", live_bytes_before=live_bytes, **read)
    device["memory_peak_bytes"] = peak_bytes
    metrics = {}
    breakdown = None
    if args.trace:
        from benchmark import xplane

        t = time.monotonic()
        tr = xplane.reduce_dir(trace_dir, chips=cell.chips)
        ctx["trace"] = tr
        device["busy_s"] = tr.busy_s
        device["window_s"] = tr.window_s
        breakdown = tr.breakdown()
        for m in cell.per_layer:
            value = cells.load_reader(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        say(phase="trace", read_s=time.monotonic() - t, **tr.describe())
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        for m in cell.end_to_end:
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    line = {"correct": correct, "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["compiles_in_window"] = compiled_in_window
    line["checks"] = checks
    print(json.dumps(line), flush=True)
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=[0, 1], default=0)
    # For the tests only (a tiny configuration that BENCHMARK.json does
    # not list); the driver passes neither.
    p.add_argument("--config-file", default=None)
    p.add_argument("--traffic-file", default=None)
    return run(p.parse_args(argv))


if __name__ == "__main__":
    sys.exit(main())
