"""Decode ladder on one TPU chip, in one process.

Qwen3-0.6B bf16, batch 1, 512-token context: the chip-local analog of
the reference's decode ladder (``docs/mega_triton_kernel.md:27-37`` —
torch / cudagraph / triton_dist_AR / megakernel ms per step). Rungs:
``jit`` (XLA decode step), ``pallas`` (framework Pallas kernels in the
decode path), ``mega`` (whole step as one Pallas kernel), ``mega_multi``
(NS steps per kernel launch) and ``mega_q8`` (weight-only int8).

It needs a TPU and exits non-zero without one, before printing any
number; a rung that fails ends the run with its traceback. Each rung
prints one JSON line; the last line is the summary, and names the device
it ran on. ROADMAP S0 replaces this script with the benchmark's cells.

Timing: decode steps are chained inside ONE jit via ``lax.fori_loop``
(greedy feedback keeps the steps data-dependent, one dispatch for all of
them) and fenced by fetching the final token to the host, so the clock
stops only when the device has finished.
"""

import json
import os
import sys

# Published HBM bandwidth per chip, GB/s, keyed by a substring of
# ``device_kind`` (Google Cloud TPU documentation, per generation).
_PEAK_GBS = {
    "v5 lite": 819.0,
    "v5e": 819.0,
    "v5p": 2765.0,
    "v4": 1228.0,
    "v6 lite": 1640.0,
    "v6e": 1640.0,
}

MODEL = "Qwen/Qwen3-0.6B"
PROMPT = 512
STEPS = 32
NS = 8  # decode steps per megakernel launch in the multi-step rungs


def chip_peak_gbs(device_kind: str) -> float:
    """Peak HBM GB/s of ``device_kind``. A kind the table does not list
    is an error, not a default."""
    kind = device_kind.lower()
    for key, val in _PEAK_GBS.items():
        if key in kind:
            return val
    raise ValueError(
        f"no published HBM bandwidth for device kind {device_kind!r}; "
        f"add it to bench._PEAK_GBS with its source"
    )


def _tuned_mega_config(device_kind: str, model_name: str):
    """Megakernel config for the mega rungs: ``TDT_BENCH_MEGA_CFG``
    ("tile_n:tile_k:nbuf") wins, else ``perf/MEGA_TUNED.json`` (written
    by ``perf/mega_tile_sweep.py`` for the best token-exact config on
    this chip+model, validated against both before use), else None
    (library defaults).

    Returns ``(config_or_None, note_str)`` — the note is printed so a
    dropped override/tuning is visible. A malformed EXPLICIT env
    override raises: silently timing defaults would invalidate the
    operator's A/B without a trace.
    """
    from triton_distributed_tpu.megakernel.code_generator import MegaConfig

    parse = MegaConfig.from_spec

    env = os.environ.get("TDT_BENCH_MEGA_CFG")
    if env:
        try:
            return parse(env), f"env TDT_BENCH_MEGA_CFG={env}"
        except Exception as e:
            raise ValueError(
                f"malformed TDT_BENCH_MEGA_CFG={env!r} "
                "(want tn:tk:nbuf[:fuse_norms[:cross_prefetch]])"
            ) from e
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "perf", "MEGA_TUNED.json")
    try:
        with open(path) as f:
            rec = json.load(f)
    except (OSError, ValueError):
        return None, "defaults (no tuning file)"
    # Tuning is per chip and per model — ignore a file from another.
    if rec.get("device") != device_kind or rec.get("model") != model_name:
        return None, (
            f"defaults (tuning file is for {rec.get('device')}/"
            f"{rec.get('model')}, this run is {device_kind}/{model_name})"
        )
    try:
        return parse(rec["config"]), f"perf/MEGA_TUNED.json {rec['config']}"
    except Exception:
        return None, "defaults (malformed tuning file ignored)"


def _emit(obj) -> None:
    print(json.dumps(obj), flush=True)


def run_ladder(device: dict) -> dict:
    """Run every rung on the attached chip; returns ``{rung: ms}``."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from triton_distributed_tpu.megakernel import MegaQwen3
    from triton_distributed_tpu.megakernel.code_generator import MegaConfig
    from triton_distributed_tpu.models import AutoLLM
    from triton_distributed_tpu.runtime.mesh import initialize_distributed
    from triton_distributed_tpu.runtime.utils import median_time

    ctx = initialize_distributed(tp=1, devices=jax.devices()[:1])
    model = AutoLLM.from_pretrained(MODEL, ctx=ctx, max_length=1024)
    cfg = model.cfg
    cache0 = model.new_cache(1)
    tokens = jnp.asarray(np.arange(PROMPT) % cfg.vocab_size, jnp.int32)
    logits, cache0 = model.prefill(tokens, cache0, "xla")
    tok0 = jnp.argmax(logits)[None].astype(jnp.int32)
    s_max = int(cache0.k.shape[3])

    def streamed_bytes(params) -> int:
        """Weight bytes one decode step must read: every leaf but the
        embedding table, of which a step gathers one row. (Counting the
        table put the int8 rung above 100% of peak.)"""
        total = sum(x.size * x.dtype.itemsize
                    for x in jax.tree.leaves(params))
        return total - params.embed.size * params.embed.dtype.itemsize

    kv_bytes = (
        2 * cfg.num_layers * cfg.num_kv_heads * PROMPT * cfg.head_dim
        * jnp.dtype(cfg.dtype).itemsize
    )
    peak_gbs = chip_peak_gbs(device["kind"])
    ladder: dict[str, float] = {}

    def rung(name, run_once, step_bytes, **extra):
        """Time one rung; ``step_bytes`` is what the algorithm must
        read from HBM per step (weights once + the KV context)."""
        ms = median_time(run_once) / STEPS * 1e3
        ladder[name] = ms
        _emit({"rung": name, "ms_per_step": round(ms, 3),
               "hbm_share_of_peak": round(
                   step_bytes / (ms * 1e-3) / 1e9 / peak_gbs, 4),
               "device": device, **extra})

    def chain(step_fn):
        """``n`` greedy steps of a single-step fn inside one jit;
        returns the token sequence."""
        def seq(params, tok, cache, n):
            def body(i, carry):
                tok, cache, out = carry
                logits, cache = step_fn(params, tok, cache)
                tok = jnp.argmax(logits, -1).astype(jnp.int32)
                return tok, cache, out.at[i].set(tok[0])

            out0 = jnp.zeros((n,), jnp.int32)
            return jax.lax.fori_loop(0, n, body, (tok, cache, out0))[2]

        return jax.jit(seq, static_argnums=3)

    def multi_chain(multi_fn):
        """``nl`` launches of an NS-step fn inside one jit; returns the
        token sequence."""
        def seq(params, tok, cache, nl):
            def body(i, carry):
                tok, cache, out = carry
                toks, _lg, cache = multi_fn(params, tok, cache)
                out = jax.lax.dynamic_update_slice(
                    out, toks[:, 0], (i * NS,))
                return toks[NS - 1], cache, out

            out0 = jnp.zeros((nl * NS,), jnp.int32)
            return jax.lax.fori_loop(0, nl, body, (tok, cache, out0))[2]

        return jax.jit(seq, static_argnums=3)

    def timed(run, params, n):
        # np.asarray fetches the tokens: the fence the clock needs.
        return lambda: np.asarray(run(params, tok0, cache0, n))

    for name, mode in (("jit", "xla"), ("pallas", "pallas")):
        rung(name, timed(chain(model.decode_fn(mode)), model.params, STEPS),
             streamed_bytes(model.params) + kv_bytes)

    mega_cfg, cfg_note = _tuned_mega_config(device["kind"], MODEL)
    _emit({"mega_config": cfg_note})
    for name, mcfg in (
            ("mega", mega_cfg),
            # Weight-only int8 halves the weight bytes of the bf16
            # step; a separate regime, so it never becomes the
            # headline.
            ("mega_q8", dataclasses.replace(mega_cfg or MegaConfig(),
                                            wq8=True))):
        mega = MegaQwen3(model, cfg=mcfg)
        params = mega._step_params()
        step_bytes = streamed_bytes(params) + kv_bytes
        single = chain(mega.decode_fn(1, s_max))
        multi = multi_chain(mega.decode_multi_fn(1, s_max, NS))
        # Single- and multi-step chains run the same kernel math and
        # must agree token for token before either timing counts.
        s_seq = np.asarray(single(params, tok0, cache0, STEPS))
        m_seq = np.asarray(multi(params, tok0, cache0, STEPS // NS))
        if (s_seq != m_seq).any():
            raise RuntimeError(
                f"{name}: multi-step tokens diverge from single-step: "
                f"{s_seq.tolist()} vs {m_seq.tolist()}"
            )
        if name == "mega":
            rung("mega", timed(single, params, STEPS), step_bytes)
            rung("mega_multi", timed(multi, params, STEPS // NS),
                 step_bytes, steps_per_launch=NS)
        else:
            rung("mega_q8", timed(multi, params, STEPS // NS),
                 step_bytes, steps_per_launch=NS)
    return ladder


def main() -> int:
    import jax

    d = jax.devices()
    device = {"platform": d[0].platform, "kind": d[0].device_kind,
              "count": len(d)}
    if device["platform"] != "tpu":
        sys.stderr.write(
            f"bench.py needs a TPU; JAX found {device['platform']} "
            f"({device['kind']} x{device['count']}). It prints no "
            "number from any other backend.\n"
        )
        return 2
    from triton_distributed_tpu.runtime.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    ladder = run_ladder(device)
    # Headline = best BF16 rung: the reference ladder it stands beside
    # is bf16, so the int8 rung rides along in the ladder only.
    bf16 = {k: v for k, v in ladder.items() if k != "mega_q8"}
    best = min(bf16, key=bf16.get)
    _emit({
        "metric": "qwen3_0.6b_decode_ms_per_step",
        "value": round(ladder[best], 3),
        "unit": "ms",
        "best_rung": best,
        "ladder": {k: round(v, 3) for k, v in ladder.items()},
        "device": device,
    })
    return 0


if __name__ == "__main__":
    sys.exit(main())
