"""Megakernel weight-stream sweep: (tile_n/tile_k, nbuf) on the chip.

The decode ladder's floor is the per-step weight stream (~1.2 GB at
0.6B); the r3 ladder ran it at ~280 GB/s effective vs the 667 GB/s
probe-measured HBM rate. Two levers target the gap (see
``MegaConfig``): wider tiles (fewer per-tile control gaps) and a
deeper staging pipeline (``nbuf`` > 2 keeps DMAs in flight through
those gaps). This sweep times the SAME 32-step greedy chain (NS=8
launches, the ladder's mega_multi configuration) across configs and
cross-checks token equality against the baseline config.

Usage: python perf/mega_tile_sweep.py [--configs 1024:1024:2,1024:1024:4,...]
"""

import argparse
import json
import os
import sys
import time


sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# tn:tk:nbuf[:fuse_norms[:cross_prefetch]] — baseline (the library
# defaults) first; then each lever added cumulatively so the deltas
# attribute: staging depth, tile width, norm fusion, cross-task
# prefetch. Tail candidates probe the edges of the space (deeper
# staging, wider K tiles, max-width N tiles) on top of the full lever
# stack — VMEM stays under the derived limit at 0.6B dims.
DEFAULT = ("1024:1024:2,1024:1024:4,2048:1024:4,"
           "1024:1024:4:1,1024:1024:4:1:1,2048:1024:4:1:1,"
           "1024:1024:6:1:1,2048:2048:4:1:1,3072:1024:4:1:1")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--configs", default=DEFAULT,
                   help="comma list of tile_n:tile_k:nbuf[:fuse_norms]")
    p.add_argument("--steps", type=int, default=32)
    p.add_argument("--ns", type=int, default=8)
    p.add_argument("--model", default="Qwen/Qwen3-0.6B")
    p.add_argument("--q8", action="store_true",
                   help="sweep with weight-only int8 streams "
                        "(wq8=True on every config; results are NOT "
                        "written to MEGA_TUNED.json, which tunes the "
                        "bf16 headline rungs)")
    p.add_argument("--deadline-s", type=float, default=1800,
                   help="stop starting new configs past this wall "
                        "budget and finalize with what's measured — a "
                        "chip call must never end with ZERO tuning "
                        "because the sweep was killed mid-flight "
                        "(0 disables)")
    p.add_argument("--cpu", action="store_true")
    args = p.parse_args(argv)
    t_start = time.time()

    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")

    from triton_distributed_tpu.megakernel import MegaQwen3
    from triton_distributed_tpu.megakernel.code_generator import MegaConfig
    from triton_distributed_tpu.models import AutoLLM
    from triton_distributed_tpu.runtime.mesh import initialize_distributed
    from triton_distributed_tpu.runtime.utils import median_time

    ctx = initialize_distributed(tp=1, devices=jax.devices()[:1])
    model = AutoLLM.from_pretrained(args.model, ctx=ctx, max_length=1024)
    jax.block_until_ready(model.params)

    steps, ns = args.steps, args.ns
    if steps % ns:
        raise SystemExit(f"--ns {ns} must divide --steps {steps}")

    from perf._chain import multi_step_chain, prepare_decode_state

    tok0, cache0, s_max = prepare_decode_state(model)

    ref_chain = None
    all_match = True
    any_ok = False
    rows = []
    truncated = False
    for i, spec in enumerate(args.configs.split(",")):
        if (args.deadline_s and i > 0
                and time.time() - t_start > args.deadline_s):
            # Stop starting configs; rc stays 0 so the window queue
            # moves on to the ladder rather than re-paying the sweep.
            print(json.dumps({
                "deadline_s": args.deadline_s,
                "skipped_configs": args.configs.split(",")[i:],
            }), flush=True)
            truncated = True
            break
        label = spec
        try:
            cfg = MegaConfig.from_spec(spec)
            if args.q8:
                import dataclasses as _dc

                cfg = _dc.replace(cfg, wq8=True)
        except ValueError as e:
            # A malformed spec is an OPERATOR error, not a chip
            # failure: record it AND fail the run (bench.py's explicit-
            # override philosophy — a silently thinner A/B is invalid).
            print(json.dumps({"config": spec, "error": str(e)}), flush=True)
            all_match = False
            continue
        label = (f"tn{cfg.tile_n}_tk{cfg.tile_k}_nb{cfg.nbuf}"
                 + ("_fn" if cfg.fuse_norms else "")
                 + ("_xp" if cfg.cross_prefetch else "")
                 + ("_q8" if cfg.wq8 else ""))
        try:
            mega = MegaQwen3(model, cfg=cfg)
            once = multi_step_chain(
                mega.decode_multi_fn(1, s_max, ns), ns,
                mega._step_params(), tok0, cache0, steps,
            )
            chain = once()  # compile + warm
            if ref_chain is None:
                ref_chain = chain
            match = bool((chain == ref_chain).all())
            all_match = all_match and match
            any_ok = True
            sec = median_time(lambda: once())
            rows.append((cfg.spec(), sec / steps * 1e3, match, i == 0))
            print(json.dumps({
                "config": label,
                "ms_per_step": round(sec / steps * 1e3, 3),
                "tokens_match_baseline": match,
            }), flush=True)
        except Exception as e:  # keep sweeping past a failed compile
            print(json.dumps({
                "config": label,
                "error": f"{type(e).__name__}: {e}"[:220],
            }), flush=True)
            if i == 0:
                # The FIRST config is the trusted baseline every other
                # chain is checked against; without it, "matches" would
                # mean "matches an unverified candidate". Keep timing
                # the rest (data is still useful) but fail the run.
                all_match = False
    # Persist the winner for bench.py's mega rungs (TPU timings only —
    # a CPU smoke must never touch chip tuning). The file is
    # write-OR-REMOVE on every run with a valid baseline: a stale
    # winner that stopped qualifying (mismatch after a kernel change,
    # or no longer faster) must not keep steering the ladder.
    if (jax.devices()[0].platform != "cpu" and rows and rows[0][3]
            and not args.q8):  # q8 timings must not tune the bf16 rungs
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "MEGA_TUNED.json")
        base_ms = rows[0][1]
        best = min((r for r in rows if r[2]), key=lambda r: r[1])
        # A deadline-TRUNCATED sweep saw only a subset of the space: it
        # may improve an existing record but must never delete or
        # downgrade one a FULL sweep wrote (the remove below exists to
        # drop stale winners, and "stale" can only be judged by a full
        # re-measure).
        prior_ms = None
        if truncated:
            try:
                with open(path) as f:
                    prior = json.load(f)
                # Only an APPLICABLE prior can block the write — a
                # record for another chip/model is ignored by bench's
                # reader anyway (same gate as _tuned_mega_config).
                if (prior.get("device") == jax.devices()[0].device_kind
                        and prior.get("model") == args.model):
                    prior_ms = float(prior["ms_per_step"])
            except (OSError, ValueError, KeyError, TypeError):
                prior_ms = None
        if best[1] < base_ms * 0.98 and (  # >2% win, not noise
                prior_ms is None or best[1] < prior_ms):
            with open(path, "w") as f:
                json.dump({
                    "config": best[0],
                    "ms_per_step": round(best[1], 3),
                    "baseline_ms_per_step": round(base_ms, 3),
                    "written_by": "perf/mega_tile_sweep.py",
                    "truncated": truncated,
                    "device": jax.devices()[0].device_kind,
                    "model": args.model,
                }, f)
            print(json.dumps({"tuned": best[0], "written": path}), flush=True)
        elif os.path.exists(path) and not truncated:
            os.remove(path)
            print(json.dumps({"tuned": None, "removed": path}), flush=True)

    # A mismatching config computed wrong logits — its timing must not
    # be promotable from a green-looking run (mega_ns_sweep contract).
    return 0 if (any_ok and all_match) else 1


if __name__ == "__main__":
    raise SystemExit(main())
