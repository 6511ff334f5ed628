"""Production-shaped load generator for the serving wire.

Every perf/ artifact before this one measured a single scenario; the
SLO goodput yardstick (ROADMAP item 5, docs/observability.md "SLO
goodput") needs traffic shaped like production:

- **Arrival processes** — seeded Poisson (exponential gaps at ``rate``
  req/s) or bursty (exponential gaps between bursts of
  ``burst_size`` near-simultaneous arrivals at the same mean rate) —
  the two shapes that bracket real front-end traffic.
- **Zipf-weighted shared-prefix population** — prompts draw one of
  ``prefix_pool`` system-prompt-like prefixes with probability
  ∝ 1/rank^``zipf_a`` plus a unique suffix, so the radix tree (and
  the KV tier behind it) sees the hot-head/long-tail reuse pattern
  production sees.
- **Long-tail output lengths** — lognormal ``gen_len`` clipped to
  [``gen_min``, ``gen_max``]: most answers short, a heavy tail of
  long ones (what makes per-token SLOs interesting).
- **Mid-stream cancellations** — a ``cancel_frac`` of requests carry
  ``cancel_after`` (tokens): the driver cancels them once that many
  frames arrived, exercising the teardown path under load.

A trace is a PURE function of its :class:`LoadSpec` (same seed → same
trace, tested), serializable to JSONL (``save_trace``/``load_trace``)
so runs are comparable ACROSS PRs: record once, replay against every
scheduler change. :func:`replay` walks the arrival stamps against the
wall clock and drives one streaming request per arrival through
``serving.server.request_stream``, collecting each request's summary
(wire-side TTFT/TPOT/outcome — stamped by the server at the frame
writes, docs/serving.md "Streaming & cancellation").
"""

from __future__ import annotations

import dataclasses
import json
import threading
import time

import numpy as np


@dataclasses.dataclass(frozen=True)
class LoadSpec:
    """One workload's shape. ``rate`` is mean arrivals/second;
    ``process`` is ``"poisson"`` or ``"bursty"``."""

    rate: float = 4.0
    n_requests: int = 32
    process: str = "poisson"
    burst_size: int = 4
    # Shared-prefix population.
    prefix_pool: int = 8
    zipf_a: float = 1.2
    prefix_len: int = 24
    suffix_min: int = 2
    suffix_max: int = 8
    vocab: int = 211
    # Long-tail output lengths (lognormal, clipped).
    gen_mean_ln: float = 2.2
    gen_sigma_ln: float = 0.6
    gen_min: int = 4
    gen_max: int = 48
    # Mid-stream cancellations.
    cancel_frac: float = 0.0
    cancel_after: int = 2
    slo_class: str = "default"
    # Per-request class mix (docs/observability.md "SLO goodput"):
    # ``((name, weight), ...)`` pairs — each request draws its
    # ``slo_class`` with probability ∝ weight (seeded, replay-
    # identical). Empty keeps the scalar ``slo_class`` for every
    # request — traces from pre-mix specs are bit-identical.
    #
    # The class name ``"agentic"`` is special: those requests are
    # reshaped into repetitive re-ask continuations — the prompt
    # becomes its shared prefix plus one per-prefix motif repeated
    # ``agentic_repeats`` times, the multi-turn agent-loop shape
    # (same tool-call scaffolding re-sent each turn) that speculative
    # decoding — n-gram AND radix-tree drafting — feeds on
    # (docs/serving.md "Speculative decoding"). Motifs are drawn from
    # the seeded rng AFTER every pre-existing draw, so specs without
    # an agentic class (and all pre-mix specs) keep bit-identical
    # traces.
    class_mix: tuple = ()
    agentic_motif: int = 6
    agentic_repeats: int = 3
    # The class name ``"document"`` is special too: those requests
    # become long document jobs — the shared prefix plus a unique
    # body of ``doc_min``..``doc_max`` tokens (10k+ by default; tests
    # scale the knobs down). Body draws land AFTER the
    # agentic motif draws, so mixes without "document" (and all
    # pre-mix specs) keep bit-identical traces.
    doc_min: int = 10240
    doc_max: int = 16384
    seed: int = 0


def _prefixes(spec: LoadSpec, rng) -> list[list[int]]:
    """The shared-prefix population: ``prefix_pool`` deterministic
    token chains (drawn once from the seeded rng, so the POPULATION is
    part of the trace's identity too)."""
    return [
        rng.integers(1, spec.vocab, size=spec.prefix_len).tolist()
        for _ in range(spec.prefix_pool)
    ]


def _zipf_weights(n: int, a: float) -> np.ndarray:
    w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** a
    return w / w.sum()


def generate_trace(spec: LoadSpec) -> list[dict]:
    """The trace: one dict per request, sorted by arrival time ``t``
    (seconds from trace start). Pure in ``spec`` — the determinism the
    cross-PR comparability contract rests on (tested)."""
    rng = np.random.default_rng(spec.seed)
    prefixes = _prefixes(spec, rng)
    weights = _zipf_weights(spec.prefix_pool, spec.zipf_a)
    # Arrival stamps.
    ts: list[float] = []
    t = 0.0
    if spec.process == "poisson":
        for _ in range(spec.n_requests):
            t += float(rng.exponential(1.0 / spec.rate))
            ts.append(t)
    elif spec.process == "bursty":
        # Bursts of `burst_size` back-to-back arrivals; gaps sized so
        # the MEAN rate still equals `rate`.
        while len(ts) < spec.n_requests:
            t += float(rng.exponential(spec.burst_size / spec.rate))
            for _ in range(min(spec.burst_size,
                               spec.n_requests - len(ts))):
                ts.append(t)
    else:
        raise ValueError(
            f"process must be 'poisson' or 'bursty', got {spec.process!r}"
        )
    trace: list[dict] = []
    for i, t_arr in enumerate(ts):
        pi = int(rng.choice(spec.prefix_pool, p=weights))
        suffix_len = int(rng.integers(spec.suffix_min,
                                      spec.suffix_max + 1))
        suffix = rng.integers(1, spec.vocab, size=suffix_len).tolist()
        gen_len = int(np.clip(
            round(float(rng.lognormal(spec.gen_mean_ln,
                                      spec.gen_sigma_ln))),
            spec.gen_min, spec.gen_max,
        ))
        cancel_after = None
        if spec.cancel_frac > 0 and rng.random() < spec.cancel_frac:
            cancel_after = min(spec.cancel_after, max(gen_len - 1, 1))
        trace.append({
            "i": i,
            "t": round(t_arr, 6),
            "prompt": prefixes[pi] + suffix,
            "prefix_id": pi,
            "gen_len": gen_len,
            "cancel_after": cancel_after,
            "slo_class": spec.slo_class,
        })
    if spec.class_mix:
        # Class draws come AFTER every pre-existing draw so a spec
        # without a mix consumes the rng stream exactly as before —
        # the cross-PR trace-identity contract stays intact.
        names = [str(n) for n, _w in spec.class_mix]
        w = np.asarray([float(wt) for _n, wt in spec.class_mix],
                       np.float64)
        if len(names) == 0 or (w <= 0).all():
            raise ValueError(f"bad class_mix: {spec.class_mix!r}")
        w = w / w.sum()
        for row in trace:
            row["slo_class"] = names[int(rng.choice(len(names), p=w))]
        if "agentic" in names:
            # Repetitive re-ask continuation class: one motif PER
            # PREFIX (drawn lazily, in row order — deterministic), so
            # agentic requests sharing a prefix repeat the SAME
            # continuation and the radix tree sees cross-request
            # reuse, not just within-prompt n-gram repetition. Draws
            # land after every other rng use: mixes without "agentic"
            # consume the stream exactly as before.
            motifs: dict[int, list[int]] = {}
            for row in trace:
                if row["slo_class"] != "agentic":
                    continue
                pi = row["prefix_id"]
                if pi not in motifs:
                    motifs[pi] = rng.integers(
                        1, spec.vocab, size=spec.agentic_motif
                    ).tolist()
                row["prompt"] = (
                    prefixes[pi] + motifs[pi] * spec.agentic_repeats
                )
        if "document" in names:
            # Document class: shared prefix + a unique
            # 10k+-token body (row order, after the agentic draws —
            # the same stream-compatibility contract as above).
            for row in trace:
                if row["slo_class"] != "document":
                    continue
                body_len = int(rng.integers(spec.doc_min,
                                            spec.doc_max + 1))
                body = rng.integers(1, spec.vocab,
                                    size=body_len).tolist()
                row["prompt"] = prefixes[row["prefix_id"]] + body
    return trace


def save_trace(path: str, trace: list[dict],
               spec: LoadSpec | None = None) -> None:
    """JSONL: an optional spec header line, then one request per line
    (the replayable-artifact half of cross-PR comparability)."""
    with open(path, "w") as f:
        if spec is not None:
            f.write(json.dumps(
                {"_spec": dataclasses.asdict(spec)}
            ) + "\n")
        for row in trace:
            f.write(json.dumps(row) + "\n")


def load_trace(path: str) -> tuple[list[dict], dict | None]:
    """Inverse of :func:`save_trace`: ``(trace, spec_dict_or_None)``."""
    trace: list[dict] = []
    spec = None
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            row = json.loads(line)
            if "_spec" in row:
                spec = row["_spec"]
                continue
            trace.append(row)
    return trace, spec


def replay(trace: list[dict], host: str, port: int, *,
           speed: float = 1.0, timeout: float = 300.0) -> list[dict]:
    """Replay a trace against a live server through the STREAMING
    wire: one thread per arrival (launched at its trace stamp /
    ``speed``), each driving ``request_stream`` and — when the row
    carries ``cancel_after`` — sending ``{"cmd": "cancel"}`` on a
    second connection once that many frames arrived (the mid-stream
    cancellation arm). Returns one record per request, trace order::

        {"i", "t", "gen_len", "cancel_after", "tokens": [...],
         "wire": {ttft_s, tpot_s, e2e_s, outcome, status, ...},
         "error": str | None}

    Every latency number in ``wire`` is the SERVER's wire-side stamp
    (docs/serving.md "Streaming & cancellation") — the replay adds no
    client-side clock of its own.
    """
    from triton_distributed_tpu.serving.server import (
        request,
        request_stream,
    )

    records: list[dict | None] = [None] * len(trace)
    t0 = time.monotonic()

    def drive(idx: int, row: dict) -> None:
        tid = f"lg{idx}"
        rec = {
            "i": row.get("i", idx),
            "t": row["t"],
            "gen_len": row["gen_len"],
            "cancel_after": row.get("cancel_after"),
            "tokens": [],
            "wire": None,
            "error": None,
        }
        payload = {
            "requests": [row["prompt"]],
            "gen_lens": [row["gen_len"]],
            "ticket_ids": [tid],
        }
        if row.get("slo_class"):
            payload["slo_class"] = row["slo_class"]
        cancel_after = row.get("cancel_after")
        cancelled = False
        try:
            for fr in request_stream(host, port, payload,
                                     timeout=timeout):
                if fr.get("frame") == "token":
                    rec["tokens"].append(fr["token"])
                    if (cancel_after is not None and not cancelled
                            and len(rec["tokens"]) >= cancel_after):
                        cancelled = True
                        request(host, port, {
                            "cmd": "cancel", "ticket_ids": [tid],
                        }, timeout=timeout)
                else:
                    rec["wire"] = (fr.get("wire") or [None])[0]
        except Exception as e:  # noqa: BLE001 — per-request record
            rec["error"] = f"{type(e).__name__}: {e}"
        records[idx] = rec

    threads: list[threading.Thread] = []
    for idx, row in enumerate(trace):
        due = t0 + row["t"] / max(speed, 1e-9)
        delay = due - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        th = threading.Thread(target=drive, args=(idx, row), daemon=True)
        th.start()
        threads.append(th)
    for th in threads:
        th.join(timeout)
    return [r if r is not None else {"error": "driver timed out"}
            for r in records]


def parse_classes(text: str) -> tuple:
    """``"interactive:4,document:1"`` → ``((name, weight), ...)`` —
    the ``class_mix`` wire format of the CLI (a bare name means
    weight 1)."""
    mix: list[tuple[str, float]] = []
    for part in text.split(","):
        part = part.strip()
        if not part:
            continue
        if ":" in part:
            name, w = part.rsplit(":", 1)
            mix.append((name.strip(), float(w)))
        else:
            mix.append((part, 1.0))
    return tuple(mix)


def main(argv=None) -> int:
    """Generate a trace to JSONL (round-trip-verified) and print a
    per-class summary — the record-once half of cross-PR replay."""
    import argparse

    p = argparse.ArgumentParser(
        description="Seeded production-shaped trace generator "
        "(perf/loadgen.py). Writes JSONL replayable with replay().",
    )
    p.add_argument("--out", required=True, help="JSONL trace path")
    p.add_argument("--rate", type=float, default=4.0)
    p.add_argument("--n", type=int, default=32,
                   help="number of requests")
    p.add_argument("--process", choices=("poisson", "bursty"),
                   default="poisson")
    p.add_argument("--burst-size", type=int, default=4)
    p.add_argument(
        "--classes", default="",
        help="SLO class mix, e.g. 'interactive:4,document:1' "
        "(weights optional). 'agentic' requests become repetitive "
        "re-ask continuations; 'document' requests become "
        "long-context jobs (--doc-min/--doc-max body tokens).",
    )
    p.add_argument("--doc-min", type=int, default=10240)
    p.add_argument("--doc-max", type=int, default=16384)
    p.add_argument("--cancel-frac", type=float, default=0.0)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)
    spec = LoadSpec(
        rate=args.rate, n_requests=args.n, process=args.process,
        burst_size=args.burst_size, cancel_frac=args.cancel_frac,
        class_mix=parse_classes(args.classes),
        doc_min=args.doc_min, doc_max=args.doc_max, seed=args.seed,
    )
    trace = generate_trace(spec)
    save_trace(args.out, trace, spec)
    back, _spec_d = load_trace(args.out)
    if back != trace:
        raise SystemExit(
            f"JSONL round-trip mismatch for {args.out} — trace is not "
            "replay-safe"
        )
    by_class: dict[str, list[int]] = {}
    for row in trace:
        by_class.setdefault(row["slo_class"], []).append(
            len(row["prompt"])
        )
    print(f"{len(trace)} requests -> {args.out}")
    for name in sorted(by_class):
        lens = by_class[name]
        print(f"  {name}: {len(lens)} reqs, prompt tokens "
              f"{min(lens)}..{max(lens)}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
