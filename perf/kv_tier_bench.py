"""Durable KV tier bench: hit-rate/capacity curves, fault-back vs
re-prefill, and supervisor-restart recovery — every number gated on an
asserted bit-exact continuation.

Three arms (docs/serving.md "Tiered KV", docs/scale-out.md "Durable
snapshots"):

1. **Hit rate vs capacity** under a long-tail shared-prefix population
   (a handful of hot system prompts + a tail of colder templates) on a
   pool far smaller than the population. Without the tier, every
   eviction is re-prefilled (the PREFIX_CACHE.json regime under
   pressure); with it, evicted chains fault back on digest match. The
   sweep reports prefill work done / avoided at several tier
   capacities, with every arrival's output asserted equal to its
   tier-less golden BEFORE the number is recorded.
2. **Fault-back latency vs re-prefill**: wall time to admit a prompt
   whose prefix pages are tier-resident vs the same prompt cold
   (gen_len=1 arrivals, the prefix_cache_bench TTFT method). CPU
   wall-clock is interpret-taxed and advisory; the platform-
   independent lever is prefill tokens computed (fault-back writes
   pages, a device-side copy; re-prefill runs the model).
3. **Supervisor-restart recovery** (the PR 10 chaos suite's missing
   case): a stub process fleet with snapshot pulls persisted under
   ``resume_dir`` is killed mid-batch — children SIGKILLed, supervisor
   abandoned un-drained. A fresh supervisor boots over the same dir,
   the requests are re-submitted, and the arm records tokens restored
   from the durable snapshots vs regenerated — gated on every output
   matching the stub's pure-function golden bit-exactly.

Output follows the perf/ convention: one JSON object with a
``provenance`` block, printed to stdout and written to
``perf/KV_TIER.json``.

Usage:  JAX_PLATFORMS=cpu python perf/kv_tier_bench.py
"""

from __future__ import annotations

import json
import os
import shutil
import signal
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("TDT_AUTOTUNE_CACHE", "0")
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=4"
)

import jax  # noqa: E402

if jax.default_backend() != "tpu":
    jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

from triton_distributed_tpu.runtime import mesh as mesh_mod  # noqa: E402

# Long-tail shared-prefix population: a rotating HOT set whose chains
# (3 × 4 pages) exceed the 8-page pool — every hot revisit finds its
# chain ALREADY LRU-evicted, which is exactly the regime where
# "evicted = gone" re-prefills everything and a tier faults it back —
# plus a cold TAIL (seen once each; classic long-tail).
PAGE_SIZE = 16
MAX_LENGTH = 128
NUM_PAGES = 8           # pool: 2 chains' worth; the hot set needs 3
HOT_PREFIXES = 3
TAIL_PREFIXES = 3
PREFIX_TOKENS = 48      # 3 full pages per prefix
SUFFIX_TOKENS = 4
ARRIVALS = 18
TIER_CAPS = [0, 64 << 10, 32 << 20]  # off / starved / ample


def _population(rng):
    hots = [rng.integers(1, 200, size=PREFIX_TOKENS).astype(np.int32)
            for _ in range(HOT_PREFIXES)]
    tails = [rng.integers(1, 200, size=PREFIX_TOKENS).astype(np.int32)
             for _ in range(TAIL_PREFIXES)]
    arrivals = []
    t = 0
    for i in range(ARRIVALS):
        if i % 6 == 5 and t < len(tails):
            pre = tails[t]  # a cold tail request, seen exactly once
            t += 1
        else:
            pre = hots[i % HOT_PREFIXES]  # deterministic hot rotation
        suf = rng.integers(1, 200, size=SUFFIX_TOKENS).astype(np.int32)
        arrivals.append(np.concatenate([pre, suf]))
    return arrivals


def arm_hit_rate(model):
    from triton_distributed_tpu.models.continuous import ContinuousEngine

    rng = np.random.default_rng(0)
    arrivals = _population(rng)
    golden_eng = ContinuousEngine(
        model, max_batch=1, page_size=PAGE_SIZE, max_length=MAX_LENGTH,
        prefix_cache=True,
    )
    golds = [golden_eng.run([(p, 1)])[0] for p in arrivals]

    sweep = []
    for cap in TIER_CAPS:
        eng = ContinuousEngine(
            model, max_batch=1, page_size=PAGE_SIZE,
            max_length=MAX_LENGTH, prefix_cache=True,
            num_pages=NUM_PAGES, tier_bytes=cap,
        )
        prefilled = hits = faults = spilled = tier_hits = 0
        for i, p in enumerate(arrivals):
            out = eng.run([(p, 1)])[0]
            np.testing.assert_array_equal(out, golds[i])  # the gate
            st = eng.last_stats
            prefilled += st["prefill_tokens"]
            hits += st["prefix_hit_tokens"]
            faults += st["tier_faults"]
            tier_hits += st["tier_hits"]
            spilled += st["tier_spilled_pages"]
        total_prompt = sum(len(p) for p in arrivals)
        entry = {
            "tier_bytes": cap,
            "prefill_tokens": int(prefilled),
            "prompt_tokens": int(total_prompt),
            "prefill_work_avoided_frac": round(
                1.0 - prefilled / total_prompt, 4
            ),
            "prefix_hit_tokens": int(hits),
            "tier_hits": int(tier_hits),
            "tier_faulted_pages": int(faults),
            "tier_spilled_pages": int(spilled),
        }
        if cap:
            entry["tier_hit_rate"] = round(
                eng.tier.stats["hits"]
                / max(eng.tier.stats["hits"] + eng.tier.stats["misses"],
                      1),
                4,
            )
            entry["store"] = {
                k: eng.tier.snapshot()[k]
                for k in ("puts", "hits", "misses", "evictions", "drops")
            }
        assert eng.audit() == []
        sweep.append(entry)
    return sweep, arrivals, golds


def arm_fault_back_latency(model):
    """Admission wall: tier fault-back vs cold re-prefill of the SAME
    prompt (both bit-exact-gated)."""
    from triton_distributed_tpu.models.continuous import ContinuousEngine

    def build(tier_bytes):
        return ContinuousEngine(
            model, max_batch=1, page_size=PAGE_SIZE,
            max_length=MAX_LENGTH, prefix_cache=True,
            num_pages=NUM_PAGES, tier_bytes=tier_bytes,
        )

    # Dedicated prompts: one probe + two distinct evictors whose
    # chains (3 × ~4 pages on a 10-page pool) force the probe's chain
    # out of the tree between its two admissions.
    rng = np.random.default_rng(42)
    prompts = [
        np.concatenate([
            rng.integers(1, 200, size=PREFIX_TOKENS),
            rng.integers(1, 200, size=SUFFIX_TOKENS),
        ]).astype(np.int32)
        for _ in range(3)
    ]
    golden_eng = ContinuousEngine(
        model, max_batch=1, page_size=PAGE_SIZE, max_length=MAX_LENGTH,
        prefix_cache=True,
    )
    p_golds = [golden_eng.run([(p, 1)])[0] for p in prompts]
    probe, gold_probe = prompts[0], p_golds[0]

    def cycle(eng):
        """probe → 2 evictors (push probe's chain out) → probe again;
        times the FINAL admission (warm tier / cold cache)."""
        np.testing.assert_array_equal(eng.run([(probe, 1)])[0], gold_probe)
        for p, g in zip(prompts[1:], p_golds[1:]):
            np.testing.assert_array_equal(eng.run([(p, 1)])[0], g)
        t0 = time.perf_counter()
        out = eng.run([(probe, 1)])[0]
        wall = time.perf_counter() - t0
        np.testing.assert_array_equal(out, gold_probe)  # the gate
        return wall, eng.last_stats

    # Warm both program shapes outside the timings.
    cycle(build(0))
    cycle(build(32 << 20))
    reps = 3
    cold_walls, warm_walls = [], []
    warm_faults = warm_prefill = cold_prefill = 0
    for _ in range(reps):
        w, st = cycle(build(0))
        cold_walls.append(w)
        cold_prefill += st["prefill_tokens"]
        w, st = cycle(build(32 << 20))
        warm_walls.append(w)
        warm_faults += st["tier_faults"]
        warm_prefill += st["prefill_tokens"]
    assert warm_faults > 0, "tier never faulted — the arm measured nothing"
    cold_w, warm_w = float(np.mean(cold_walls)), float(np.mean(warm_walls))
    return {
        "reprefill_wall_s_mean": round(cold_w, 4),
        "faultback_wall_s_mean": round(warm_w, 4),
        # >1 means fault-back was slower in WALL time on this host —
        # expected on CPU, where interpret-mode prefill of a tiny
        # model is cheap while the fault path pays per-page host→
        # device writes; on hardware the prefill side scales with
        # model FLOPs and the fault side stays a memcpy. The
        # platform-independent lever is the prefill-token delta.
        "wall_ratio_faultback_over_reprefill_cpu_advisory": round(
            warm_w / max(cold_w, 1e-9), 3
        ),
        "reprefill_tokens_per_cycle": int(cold_prefill / reps),
        "faultback_prefill_tokens_per_cycle": int(warm_prefill / reps),
        "prefill_tokens_avoided_per_cycle": int(
            (cold_prefill - warm_prefill) / reps
        ),
        "faultback_pages_per_cycle": int(warm_faults / reps),
    }


def arm_supervisor_restart():
    """Kill a fleet mid-batch; reboot over the same resume_dir; gate
    on bit-exact outputs; record restored vs regenerated tokens."""
    from triton_distributed_tpu.models.kv_tier import SNAP_KIND, PageStore
    from triton_distributed_tpu.models.stub import stub_generate
    from triton_distributed_tpu.serving.supervisor import (
        FleetSupervisor,
        stub_spec,
    )

    resume = tempfile.mkdtemp(prefix="tdt-tier-resume-")
    prompts = [np.arange(1, 9, dtype=np.int32),
               np.arange(20, 30, dtype=np.int32)]
    gens = [8, 8]
    golds = [stub_generate(p, g) for p, g in zip(prompts, gens)]

    def mk_sup():
        return FleetSupervisor(
            [stub_spec("r0", delay_s=2.5, page_size=4, num_pages=64)],
            heartbeat_s=0.05, snapshot_s=0.05, resume_dir=resume,
            spawn_timeout_s=120.0,
        )

    sup = mk_sup()
    router = sup.start()
    results: dict = {}
    th = threading.Thread(
        target=lambda: results.update(
            res=router.run(list(zip(prompts, gens)), results=True)
        ),
        daemon=True,
    )
    th.start()
    store = PageStore(dir=resume)

    def progressed():
        # Kill only once real work is at stake: a persisted snapshot
        # with ≥4 of its tokens generated but unfinished.
        for k in store.keys(SNAP_KIND):
            snap = store.peek(SNAP_KIND, k) or {}
            out = snap.get("out") or []
            if 4 <= len(out) < int(snap.get("gen_len", 0)):
                return True
        return False

    assert sup.wait_for(progressed, timeout_s=60)
    t_kill = time.monotonic()
    sup._stop.set()
    if sup._thread is not None:
        sup._thread.join(timeout=10)
    proc = router.replicas[0].proc
    os.kill(router.replicas[0].pid, signal.SIGKILL)
    proc.wait(timeout=10)
    th.join(timeout=60)

    sup2 = mk_sup()
    try:
        router2 = sup2.start()
        t_up = time.monotonic()
        res2 = router2.run(list(zip(prompts, gens)), results=True)
        t_done = time.monotonic()
        for r, gold in zip(res2, golds):
            assert r.status == "ok", (r.status, r.reason)
            assert r.tokens.tolist() == gold  # the gate
        st = router2.last_stats
        restored = int(st["migrated_in_tokens"])
        assert restored >= 1, "nothing resumed — the arm measured nothing"
        out = {
            "requests": len(prompts),
            "tokens_total": int(sum(gens)),
            "tokens_restored_from_snapshots": restored,
            "work_preserved_frac": round(restored / sum(gens), 3),
            "reboot_to_serving_s": round(t_up - t_kill, 2),
            "resubmit_wall_s": round(t_done - t_up, 2),
            "bit_exact": True,  # asserted above, per request
        }
    finally:
        sup2.shutdown()
        shutil.rmtree(resume, ignore_errors=True)
    return out


def main() -> int:
    from triton_distributed_tpu.models import AutoLLM

    ctx = mesh_mod.initialize_distributed(
        tp=min(4, len(jax.devices())), devices=jax.devices()[:4]
    )
    model = AutoLLM.from_pretrained("tiny", ctx=ctx, max_length=MAX_LENGTH)
    sweep, _arrivals, _golds = arm_hit_rate(model)
    latency = arm_fault_back_latency(model)
    restart = arm_supervisor_restart()

    off = next(e for e in sweep if e["tier_bytes"] == 0)
    ample = sweep[-1]
    result = {
        "metric": "kv_tier_hit_rate_faultback_and_restart_recovery",
        "workload": {
            "page_size": PAGE_SIZE,
            "num_pages": NUM_PAGES,
            "hot_prefixes": HOT_PREFIXES,
            "tail_prefixes": TAIL_PREFIXES,
            "prefix_tokens": PREFIX_TOKENS,
            "arrivals": ARRIVALS,
        },
        "platform": jax.default_backend(),
        "capacity_sweep": sweep,
        "tier_prefill_tokens_saved_vs_off": int(
            off["prefill_tokens"] - ample["prefill_tokens"]
        ),
        "faultback_vs_reprefill": latency,
        "supervisor_restart": restart,
        "provenance": {
            "harness": "perf/kv_tier_bench.py — per-arrival "
            "ContinuousEngine.run(gen_len=1) over a long-tail "
            "shared-prefix population on a 10-page pool (tiny model); "
            "restart arm kills a stub fleet mid-batch and reboots a "
            "FleetSupervisor over the same resume_dir",
            "gates": "EVERY recorded arrival asserted bit-exact "
            "against a tier-less golden before counting; the restart "
            "arm asserts per-request bit-exactness vs the stub's pure "
            "generator and restored tokens >= 1",
            "caveat": "CPU wall-clock is interpret-mode-taxed and "
            "advisory; prefill tokens computed / avoided and the "
            "restored-token fractions are the platform-independent "
            "levers",
        },
    }
    print(json.dumps(result), flush=True)
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "KV_TIER.json")
    with open(out, "w") as f:
        f.write(json.dumps(result, indent=2) + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
