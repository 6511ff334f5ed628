#!/usr/bin/env python3
"""The quickest proof that the serving path still starts on the chip.

Run with no arguments on a machine with one TPU chip. For each serving
mode it calls ``run_server.main`` (the entry point a user calls) on a
thread of this process, drives the socket with the wire client of
``serving/server.py`` from the main thread, and shuts the server down.
Then it builds the model once and checks the attention kernels against
their references and the paged path's logits against a dense prefill.
``--chips 4`` runs the path across chips instead, and what it is
compared with, and nothing else: the tp=4 model under Pallas collectives
against XLA collectives, each overlap kernel and collective against its
XLA reference, and four one-chip replicas behind the router.

One process holds the chip throughout. A phase that fails raises, so
the exit code is non-zero and no result line is printed. The last line
of a passing run is ``{"ok": true, "device": {...}}`` with the device as
JAX reports it; the lines before it are one JSON object per phase.

The seconds it prints are readings of this run (compilation included
where a line says so), for the bring-up record; they are not benchmark
results.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import faulthandler
import functools
import gc
import json
import os
import sys
import tempfile
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

SEED = 0
RUN_LIMIT_S = 1150  # under the 1200 s a run is given
# Serving phases, in the order a default run keeps them, with the
# run_server flags that select each.
MODES = {
    "xla": ["--mode", "xla"],
    "pallas": ["--mode", "pallas"],
    "int8": ["--mode", "xla", "--kv-dtype", "int8"],
    "mega": ["--mode", "mega"],
    "mega-resident": ["--mode", "mega", "--resident"],
}
# One chip's share of dots.vlm1.inst's language model (latent attention,
# 16 of 256 experts held), as the benchmark's cell serves it: the
# `latent` phase (``--modes latent``; not in a default run).
LATENT = {
    "model": "rednote-hilab/dots.vlm1.inst", "slots": 32,
    "cut": ["--num-layers", "5", "--first-k-dense", "1",
            "--experts-held", "16", "--vocab-rows", "16160"],
}
# granite-4.0-h-micro whole (36 Mamba-2 layers over a per-slot recurrent
# state beside 4 attention layers over the pool), as the benchmark's
# cell serves it: the `hybrid` phase (``--modes hybrid``; not in a
# default run).
HYBRID = {"model": "ibm-granite/granite-4.0-h-micro", "slots": 32}
ONE_CHIP_MODEL = "Qwen/Qwen3-4B"   # largest dense preset one 16 GB chip holds
FOUR_CHIP_MODEL = "Qwen/Qwen3-8B"  # 16.4 GB bf16: the preset that needs four

# Tolerances, as the largest absolute difference from the reference.
# Kernel outputs are softmax-weighted means of unit-normal values, so
# they are O(1); logits are compared relative to the largest reference
# logit.
#
# bf16 kernels: the reference is computed in f32 from the same bf16
# inputs and rounded to bf16 once; the kernel also rounds the softmax
# weights to bf16 before the PV product. Outputs reach magnitudes in
# [2, 4), where one bf16 ulp is 2^-6 = 0.0156, and the bound is two of
# them: a bf16 accumulator, the nearest lower-precision mistake, errs by
# several times that over a 512-long sum. f32 (the CPU rehearsal)
# differs only by summation order: 1e-3.
KERNEL_TOL = {"bfloat16": 3.2e-2, "float32": 1e-3}
# Logits, paged chunked prefill + paged decode against a dense prefill
# of the same tokens. Both paths round to bf16 at the same points and
# differ in summation order (flash blocks, chunk boundaries, the
# split-KV decode kernel), which flips single bf16 ulps that then travel
# through every later layer: 0.018 on the v5e at Qwen3-4B (PERF.md,
# PR 22), bound 0.025. An int8 KV pool, the nearest lower-precision
# path, adds amax/254 to every cached value; it read 0.033 there, is
# held to its own looser bound, and is also printed against the
# full-width bound, which it must exceed for that bound to mean
# anything.
LOGIT_TOL = {"bfloat16": 2.5e-2, "float32": 1e-3}
LOGIT_TOL_INT8 = 1.5e-1
# Across chips, relative to the largest reference value. The tp=4 model
# under Pallas collectives against XLA collectives: every rank rounds
# its partial product to bf16 in both modes, but the modes add the
# ranks' partials differently (XLA's all-reduce; an f32 sum inside the
# kernel), twice per layer — more than the one-chip paged-vs-dense
# noise and still under an int8 pool's 0.033. Read 0.022 at Qwen3-8B
# (PERF.md, PR 22); bound 0.03. A single overlap kernel or collective
# differs from its XLA reference by the rounding of one sum — a bf16 ulp
# is at most 2^-7 of its value, and a ring adds a rounding per hop —
# while a lost or misplaced shard, the fault these checks exist for, is
# an O(1) error.
TP_LOGIT_TOL = {"bfloat16": 3e-2, "float32": 1e-3}
COLLECTIVE_TOL = {"bfloat16": 2e-2, "float32": 1e-5}


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


def device_facts() -> dict:
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def check_on_chip(ctx=None) -> None:
    """Raise unless this process is on the TPU — and, given a context
    the program built, unless that context says so too and compiles its
    Pallas kernels instead of interpreting them."""
    facts = device_facts()
    if facts["platform"] != "tpu":
        raise SystemExit(
            f"chip_smoke needs a TPU; JAX found {facts['platform']} "
            f"({facts['kind']} x{facts['count']})"
        )
    if ctx is not None and not (
            ctx.on_tpu and ctx.pallas_interpret() is False):
        raise RuntimeError(
            "the context the program built is not on the TPU "
            f"(platform {ctx.topology.platform!r}, pallas_interpret "
            f"{ctx.pallas_interpret()!r})"
        )


def peak_bytes() -> int | None:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def max_abs(a, b) -> float:
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    if not (np.isfinite(a).all() and np.isfinite(b).all()):
        raise RuntimeError("non-finite values in a compared result")
    return float(np.max(np.abs(a - b)))


def rel_err(a, b) -> float:
    """Largest absolute difference over the largest reference value."""
    return max_abs(a, b) / float(np.max(np.abs(np.asarray(b, np.float32))))


def require(cond: bool, what: str) -> None:
    if not cond:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def page_of(cfg) -> int:
    """The engine's page size, 128 — or 16 for the CPU rehearsal's
    ``tiny`` model, whose whole context is 128 tokens."""
    return 128 if cfg.max_length >= 1024 else 16


# -- the served phase -------------------------------------------------------


@contextlib.contextmanager
def running_server(flags: list[str]):
    """``run_server.main`` on a thread of this process, listening on a
    port of its own choosing. Yields ``ask(payload) -> (response,
    seconds)`` and the seconds the server took to listen (model build
    included); on exit it sends ``shutdown``, joins the thread and
    raises unless ``main`` returned 0."""
    from triton_distributed_tpu.serving import run_server
    from triton_distributed_tpu.serving.server import request

    with tempfile.TemporaryDirectory(prefix="chip_smoke-") as tmp:
        port_file = os.path.join(tmp, "port")
        argv = [*flags, "--port", "0", "--port-file", port_file]
        outcome: dict = {}

        def serve():
            try:
                outcome["rc"] = run_server.main(argv)
            except BaseException as e:  # noqa: BLE001 — re-raised by the main thread
                outcome["error"] = e

        t0 = time.monotonic()
        server = threading.Thread(target=serve, daemon=True)
        server.start()
        while not os.path.exists(port_file):
            if not server.is_alive():
                raise RuntimeError(
                    f"run_server {' '.join(argv)} ended before it listened"
                ) from outcome.get("error")
            time.sleep(0.05)
        start_s = time.monotonic() - t0
        with open(port_file) as f:
            host, port = f.read().strip().rsplit(":", 1)

        def ask(payload):
            t = time.monotonic()
            resp = request(host, int(port), payload, timeout=900.0)
            return resp, time.monotonic() - t

        try:
            yield ask, start_s
        finally:
            if server.is_alive():
                ask({"cmd": "shutdown"})
            server.join(timeout=120)
    require(not server.is_alive(), "server thread did not stop")
    if "error" in outcome:
        raise RuntimeError("run_server raised") from outcome["error"]
    require(outcome.get("rc") == 0, f"run_server returned {outcome}")


def make_payloads(cfg) -> tuple[dict, dict]:
    """Two ``requests`` payloads from the seed: A has mixed prompt
    lengths, three of them on one shared prefix; B asks two new tails
    of that prefix, so its admissions hit the radix tree A left."""
    rng = np.random.default_rng(SEED)
    unit = cfg.max_length // 16

    def toks(n):
        return rng.integers(0, cfg.vocab_size, size=n).tolist()

    prefix = toks(unit)
    a_prompts = [prefix + toks(unit // 6 + 1), prefix + toks(unit + 5),
                 prefix + toks(3), toks(unit // 6 + 2)]
    b_prompts = [prefix + toks(unit // 3 + 2), prefix + toks(7)]
    # 24 tokens are three 8-step megakernel launches: enough for the
    # resident mode to chain one launch off another.
    a = {"requests": a_prompts, "gen_lens": [24, 21, 12, 4]}
    b = {"requests": b_prompts, "gen_lens": [4, 12]}
    return a, b


def check_response(resp: dict, payload: dict, vocab: int) -> int:
    """Every request ok, the asked-for lengths, tokens in the
    vocabulary. Returns the tokens generated."""
    statuses = [r["status"] for r in resp["results"]]
    require(all(s == "ok" for s in statuses), f"statuses {statuses}")
    lens = [len(o) for o in resp["outputs"]]
    require(lens == payload["gen_lens"],
            f"lengths {lens} != asked {payload['gen_lens']}")
    flat = [t for o in resp["outputs"] for t in o]
    require(all(0 <= t < vocab for t in flat), "token outside vocabulary")
    return len(flat)


def serve_phase(model: str, mode: str) -> None:
    """One server lifetime: the cold payload, a payload on the repeated
    prefix, two repeats of the cold payload, then stats and audit."""
    from triton_distributed_tpu.models.config import get_config
    from triton_distributed_tpu.runtime import mesh

    cfg = get_config(model)
    pay_a, pay_b = make_payloads(cfg)
    with running_server(["--model", model, "--continuous",
                         *MODES[mode]]) as (ask, start_s):
        check_on_chip(mesh.current_context())
        cold, first_s = ask(pay_a)
        n_tokens = check_response(cold, pay_a, cfg.vocab_size)
        hit, _ = ask(pay_b)
        n_tokens += check_response(hit, pay_b, cfg.vocab_size)
        require(hit["stats"]["prefix_hit_tokens"] > 0,
                "no radix hit on the repeated prefix")
        # The first repeat hits the radix tree on whole prompts and so
        # compiles the short-suffix programs; the second finds every
        # program compiled.
        again, repeat_s = ask(pay_a)
        n_tokens += check_response(again, pay_a, cfg.vocab_size)
        warm, warm_s = ask(pay_a)
        n_tokens += check_response(warm, pay_a, cfg.vocab_size)
        stats = ask({"cmd": "stats"})[0]["stats"]
        require(stats["prefill_tokens"] > 0 and stats["generated_tokens"] > 0,
                f"stats carry no work: {stats}")
        require(stats["prefix_hit_tokens"] > 0,
                "no radix hit on the warm repeat")
        if mode.startswith("mega"):
            require(stats["mega_launches"] > 0, "no megakernel launch")
        if mode == "mega-resident":
            require(stats["mega_resident_rounds"] > 0,
                    "no resident (pipelined) round")
        if mode == "int8":
            require(stats.get("kv_dtype") == "int8",
                    f"stats report kv_dtype {stats.get('kv_dtype')!r}")
        problems = ask({"cmd": "audit"})[0]["problems"]
        require(problems == [], f"audit: {problems}")
    emit(phase="serve", model=model, mode=mode,
         listening_after_s=round(start_s, 2),
         first_response_s_with_compile=round(first_s, 2),
         first_repeat_s=round(repeat_s, 2), warm_repeat_s=round(warm_s, 2),
         tokens_generated=n_tokens,
         prefix_hit_tokens_warm=stats["prefix_hit_tokens"],
         warm_repeat_reproduced_cold_tokens=(
             warm["outputs"] == cold["outputs"]),
         peak_bytes_in_use=peak_bytes())


# -- kernel and logit checks ------------------------------------------------

def kernel_checks(cfg) -> None:
    """``flash_attention`` against ``mha_reference`` and
    ``paged_flash_decode`` against ``gqa_decode_reference`` at the
    model's head geometry, full-width and int8."""
    from triton_distributed_tpu.models.paged_kv_cache import (
        dequantize_page,
        quantize_pages,
    )
    from triton_distributed_tpu.ops.attention.flash_attention import (
        flash_attention,
        mha_reference,
    )
    from triton_distributed_tpu.ops.attention.flash_decode import (
        gqa_decode_reference,
        paged_flash_decode,
        pages_to_dense,
    )

    hq, hkv, d = cfg.num_q_heads, cfg.num_kv_heads, cfg.head_dim
    dt = cfg.dtype
    tol = KERNEL_TOL[jnp.dtype(dt).name]
    page = page_of(cfg)
    sk, c, off = 4 * page, page, 2 * page
    ks = iter(jax.random.split(jax.random.key(SEED), 8))

    def rnd(*shape):
        return jax.random.normal(next(ks), shape, jnp.float32).astype(dt)

    q, k, v = rnd(1, hq, sk, d), rnd(1, hkv, sk, d), rnd(1, hkv, sk, d)
    errs = {}
    errs["flash_prefill"] = max_abs(
        flash_attention(q, k, v, causal=True),
        mha_reference(q, k, v, causal=True))
    qc = q[:, :, :c]
    # The offset is an array, so it rides as a scalar-prefetch operand:
    # the chunked-prefill form of the kernel.
    errs["flash_chunk_traced_offset"] = max_abs(
        flash_attention(qc, k, v, causal=True, kv_offset=jnp.int32(off)),
        mha_reference(qc, k, v, causal=True, kv_offset=off))

    def blocks(x):  # [1, H, S, d] -> [1, H, S/page, page, d]
        return x.reshape(1, hkv, sk // page, page, d)

    k8, k_sc = quantize_pages(blocks(k))
    v8, v_sc = quantize_pages(blocks(v))
    # The reference reads the SAME dequantized values, so only the
    # kernel's arithmetic is compared, not the quantization.
    k_dq = dequantize_page(k8, k_sc).reshape(1, hkv, sk, d)
    v_dq = dequantize_page(v8, v_sc).reshape(1, hkv, sk, d)
    errs["flash_chunk_int8"] = max_abs(
        flash_attention(qc, k8.reshape(1, hkv, sk, d),
                        v8.reshape(1, hkv, sk, d), causal=True,
                        kv_offset=jnp.int32(off), block_k=page,
                        k_scale=k_sc, v_scale=v_sc),
        mha_reference(qc, k_dq, v_dq, causal=True, kv_offset=off))

    b, pps = 4, 4
    n_pages = b * pps + 1
    kp, vp = rnd(n_pages, hkv, page, d), rnd(n_pages, hkv, page, d)
    table = jnp.asarray(
        np.random.default_rng(SEED).permutation(np.arange(1, n_pages))
        .reshape(b, pps).astype(np.int32))
    kv_len = jnp.asarray([1, page, page + 3, pps * page], jnp.int32)
    qd = rnd(b, hq, d)
    errs["paged_decode"] = max_abs(
        paged_flash_decode(qd, kp, vp, table, kv_len),
        gqa_decode_reference(qd, pages_to_dense(kp, table),
                             pages_to_dense(vp, table), kv_len))
    kp8, kp_sc = quantize_pages(kp)
    vp8, vp_sc = quantize_pages(vp)
    errs["paged_decode_int8"] = max_abs(
        paged_flash_decode(qd, kp8, vp8, table, kv_len,
                           k_scale=kp_sc, v_scale=vp_sc),
        gqa_decode_reference(
            qd, pages_to_dense(dequantize_page(kp8, kp_sc), table),
            pages_to_dense(dequantize_page(vp8, vp_sc), table), kv_len))
    emit(phase="kernels", heads=[hq, hkv, d], dtype=jnp.dtype(dt).name,
         tolerance=tol, max_abs_err=errs)
    for name, err in errs.items():
        require(err <= tol, f"{name}: max abs err {err} > {tol}")


def latent_phase() -> None:
    """The cut latent-attention preset: ``tdt_mla_decode_paged`` against
    the plain absorbed formula at the preset's widths (all slots, uneven
    contexts over a shuffled table), then one server lifetime at
    ``LATENT["slots"]`` decode slots: as many requests as slots, a
    repeat that hits the radix tree, stats and audit."""
    from triton_distributed_tpu.models.config import get_config
    from triton_distributed_tpu.ops.attention.mla_decode import (
        mla_decode_reference,
        mla_paged_decode,
    )
    from triton_distributed_tpu.runtime import mesh
    from triton_distributed_tpu.serving.run_server import resolve_model_args

    name, overrides = resolve_model_args(
        LATENT["model"], **{
            k[2:].replace("-", "_"): int(v) for k, v in zip(
                LATENT["cut"][::2], LATENT["cut"][1::2])})
    cfg = get_config(name, **overrides)
    dt, b = cfg.dtype, LATENT["slots"]
    page, pps = page_of(cfg), 4
    n_pages = b * pps + 1
    ks = iter(jax.random.split(jax.random.key(SEED), 4))

    def rnd(*shape):
        return jax.random.normal(next(ks), shape, jnp.float32).astype(dt)

    h, rank, rope = cfg.num_q_heads, cfg.kv_lora_rank, cfg.qk_rope_head_dim
    q_lat, q_rope = rnd(b, h, rank), rnd(b, h, rope)
    c_pages, r_pages = rnd(n_pages, 1, page, rank), rnd(n_pages, 1, rope, page)
    rng = np.random.default_rng(SEED)
    table = jnp.asarray(rng.permutation(np.arange(1, n_pages))
                        .reshape(b, pps).astype(np.int32))
    kv_len = jnp.asarray(
        [1, page, page + 3, pps * page]
        + rng.integers(1, pps * page + 1, size=b - 4).tolist(), jnp.int32)
    scale = (cfg.qk_nope_head_dim + rope) ** -0.5
    err = max_abs(
        mla_paged_decode(q_lat, q_rope, c_pages, r_pages, table, kv_len,
                         sm_scale=scale),
        mla_decode_reference(q_lat, q_rope, c_pages, r_pages, table, kv_len,
                             sm_scale=scale))
    tol = KERNEL_TOL[jnp.dtype(dt).name]
    emit(phase="latent_kernel", heads=[h, rank, rope], slots=b,
         dtype=jnp.dtype(dt).name, tolerance=tol,
         max_abs_err={"mla_decode_paged": err})
    require(err <= tol, f"mla_decode_paged: max abs err {err} > {tol}")

    unit = cfg.max_length // 16
    prompts = [rng.integers(0, cfg.vocab_size, size=unit + 7 * i).tolist()
               for i in range(b)]
    payload = {"requests": prompts, "gen_lens": [4 + i % 5 for i in range(b)]}
    with running_server(["--model", LATENT["model"], "--continuous",
                         "--max-batch", str(b), *LATENT["cut"]]) as (
                             ask, start_s):
        check_on_chip(mesh.current_context())
        cold, first_s = ask(payload)
        n_tokens = check_response(cold, payload, cfg.vocab_size)
        warm, warm_s = ask(payload)
        n_tokens += check_response(warm, payload, cfg.vocab_size)
        stats = ask({"cmd": "stats"})[0]["stats"]
        require(stats["prefix_hit_tokens"] > 0, "no radix hit on the repeat")
        require(stats["moe_decode_local_rows"] > 0
                and stats["moe_decode_experts_touched"] > 0,
                f"the expert share counted nothing: {stats}")
        problems = ask({"cmd": "audit"})[0]["problems"]
        require(problems == [], f"audit: {problems}")
    emit(phase="latent_serve", model=LATENT["model"], cut=LATENT["cut"],
         slots=b, listening_after_s=round(start_s, 2),
         first_response_s_with_compile=round(first_s, 2),
         warm_repeat_s=round(warm_s, 2), tokens_generated=n_tokens,
         decode_steps=stats["decode_steps"],
         kv_bytes_per_token=stats["kv_bytes_per_token"],
         local_rows=stats["moe_decode_local_rows"],
         experts_touched=stats["moe_decode_experts_touched"],
         warm_repeat_reproduced_cold_tokens=(
             warm["outputs"] == cold["outputs"]),
         peak_bytes_in_use=peak_bytes())


def hybrid_phase() -> None:
    """The Mamba-2 / attention hybrid: ``tdt_ssm_decode`` against the
    plain sums at the preset's widths (some rows in flight, the
    others' state bit for bit as it was), then one server lifetime at
    ``HYBRID["slots"]`` decode slots: as many requests as slots, a
    repeat (no radix hit: a page holds no recurrent state), stats and
    audit."""
    from triton_distributed_tpu.models.config import get_config
    from triton_distributed_tpu.ops.ssm.decode import (
        live_rows,
        ssm_decode,
        ssm_decode_reference,
    )
    from triton_distributed_tpu.runtime import mesh

    cfg = get_config(HYBRID["model"])
    b, h, p, n = (HYBRID["slots"], cfg.mamba_n_heads, cfg.mamba_d_head,
                  cfg.mamba_d_state)
    ks = jax.random.split(jax.random.key(SEED), 5)
    state = jax.random.normal(ks[0], (2, b, n, h * p), jnp.float32)
    a = jax.random.uniform(ks[1], (b, h), jnp.float32, 0.5, 1.0)
    dt = jax.random.uniform(ks[2], (b, h), jnp.float32, 0.0, 1.0)
    d = jax.random.normal(ks[3], (h,), jnp.float32)
    xbc = jax.random.normal(ks[4], (b, h * p + 2 * n), jnp.float32)
    live = jnp.arange(b) % 3 != 1
    want_y, want_s = ssm_decode_reference(state[1], a, dt, d, xbc, live)
    got_y, got_s = ssm_decode(state, a, dt, d, xbc, *live_rows(live),
                              layer=1)
    errs = {"y": max_abs(got_y, want_y), "state": max_abs(got_s[1], want_s)}
    untouched = bool(jnp.array_equal(got_s[0], state[0]))
    emit(phase="hybrid_kernel", state=[n, h * p], slots=b, tolerance=1e-3,
         max_abs_err=errs, other_layer_untouched=untouched)
    require(max(errs.values()) <= 1e-3 and untouched,
            f"ssm_decode: max abs err {errs}, untouched {untouched}")

    rng = np.random.default_rng(SEED)
    unit = cfg.max_length // 16
    prompts = [rng.integers(0, cfg.vocab_size, size=unit + 7 * i).tolist()
               for i in range(b)]
    payload = {"requests": prompts, "gen_lens": [4 + i % 5 for i in range(b)]}
    with running_server(["--model", HYBRID["model"], "--continuous",
                         "--max-batch", str(b)]) as (ask, start_s):
        check_on_chip(mesh.current_context())
        cold, first_s = ask(payload)
        n_tokens = check_response(cold, payload, cfg.vocab_size)
        warm, warm_s = ask(payload)
        n_tokens += check_response(warm, payload, cfg.vocab_size)
        stats = ask({"cmd": "stats"})[0]["stats"]
        require(stats["prefix_hit_tokens"] == 0,
                "a radix hit for a model with a recurrent state")
        decoded = stats["generated_tokens"] - stats["admitted"]
        require(stats["ssm_decode_rows"]
                == decoded + stats["lookahead_discarded"],
                f"rows advanced are not the decoded tokens: {stats}")
        problems = ask({"cmd": "audit"})[0]["problems"]
        require(problems == [], f"audit: {problems}")
    emit(phase="hybrid_serve", model=HYBRID["model"], slots=b,
         listening_after_s=round(start_s, 2),
         first_response_s_with_compile=round(first_s, 2),
         warm_repeat_s=round(warm_s, 2), tokens_generated=n_tokens,
         decode_steps=stats["decode_steps"],
         kv_bytes_per_token=stats["kv_bytes_per_token"],
         state_bytes_per_slot=stats["state_bytes_per_slot"],
         rows_advanced=stats["ssm_decode_rows"],
         warm_repeat_reproduced_cold_tokens=(
             warm["outputs"] == cold["outputs"]),
         peak_bytes_in_use=peak_bytes())


def paged_logits(model, prompt, forced, mode: str, kv_dtype=None):
    """Logits ``[1 + len(forced), V]``: the prompt's last position
    through paged chunked prefill, then each teacher-forced token
    through a paged decode step — the programs the engine runs."""
    from triton_distributed_tpu.models.engine import prefill_suffix_chunks
    from triton_distributed_tpu.models.paged_kv_cache import init_paged_cache

    cfg = model.cfg
    page = page_of(cfg)
    pps = 8
    # Page 0 stays out of the sequence's table, as in the engine: the
    # quantized scatter routes a ragged chunk's pad rows to it.
    cache, _pool = init_paged_cache(
        cfg, 1, model.ctx, model.axis, max_length=pps * page,
        page_size=page, num_pages=pps + 1, assign_pages=False,
        kv_dtype=kv_dtype,
    )
    cache = dataclasses.replace(
        cache, page_table=jnp.arange(1, pps + 1, dtype=jnp.int32)[None])
    # chunk_width = one page: a prompt of several pages is prefilled in
    # several chunks, each after the first at a non-zero traced offset.
    logits, cache, chunks = prefill_suffix_chunks(
        model, cache, 0, np.asarray(prompt, np.int32), 0, page, mode)
    require(chunks > 1, "the prompt did not span several chunks")
    rows = [logits]
    for t in forced:
        step, cache = model.decode_step(
            jnp.asarray([t], jnp.int32), cache, mode)
        rows.append(step[0])
    return np.asarray(jnp.stack(rows), np.float32)


def logit_prompt(cfg):
    page = page_of(cfg)
    rng = np.random.default_rng(SEED + 1)
    n = 2 * page + page // 3   # three chunks, the last one ragged
    toks = rng.integers(0, cfg.vocab_size, size=n + 3)
    return toks[:n], toks[n:], 3 * page


def logit_checks(model_name: str) -> None:
    """Build the model once; compare the paged path's logits (full
    width and int8) with a dense prefill of the same tokens."""
    from triton_distributed_tpu.models import AutoLLM
    from triton_distributed_tpu.runtime.mesh import initialize_distributed

    ctx = initialize_distributed(tp=1, devices=jax.devices()[:1])
    check_on_chip(ctx)
    t0 = time.monotonic()
    model = AutoLLM.from_pretrained(model_name, ctx=ctx, seed=SEED)
    cfg = model.cfg
    kernel_checks(cfg)
    prompt, forced, s_pad = logit_prompt(cfg)
    padded = np.zeros(s_pad, np.int32)
    padded[: len(prompt) + len(forced)] = np.concatenate([prompt, forced])
    dense_cache = model.new_cache(1, s_pad)
    dense = np.stack([
        np.asarray(model.prefill(jnp.asarray(padded), dense_cache, "xla",
                                 true_len=len(prompt) + i)[0], np.float32)
        for i in range(len(forced) + 1)
    ])
    require(dense.shape == (len(forced) + 1, cfg.vocab_size),
            f"dense logits shape {dense.shape}")
    name = jnp.dtype(cfg.dtype).name
    tol = LOGIT_TOL[name]
    errs = {
        kv or "full": rel_err(
            paged_logits(model, prompt, forced, "xla", kv), dense)
        for kv in (None, "int8")
    }
    emit(phase="logits", model=model_name, dtype=name,
         compared="paged chunked prefill + teacher-forced paged decode "
                  "vs dense prefill, max |dlogit| / max |logit|",
         positions=len(forced) + 1,
         max_logit=round(float(np.max(np.abs(dense))), 3),
         tolerance=tol, tolerance_int8=LOGIT_TOL_INT8, rel_err=errs,
         int8_exceeds_full_width_tolerance=errs["int8"] > tol,
         seconds=round(time.monotonic() - t0, 2),
         peak_bytes_in_use=peak_bytes())
    require(errs["full"] <= tol, f"paged vs dense logits {errs['full']}")
    require(errs["int8"] <= LOGIT_TOL_INT8,
            f"int8 paged vs dense logits {errs['int8']}")


# -- four chips -------------------------------------------------------------

def tp_model_check(model_name: str, ctx) -> None:
    """The tp-sharded model served by ``ContinuousEngine`` under Pallas
    collectives and under XLA collectives, then the two modes' logits
    on the same tokens."""
    from triton_distributed_tpu.models import AutoLLM
    from triton_distributed_tpu.models.continuous import ContinuousEngine

    tp = ctx.axis_size("tp")
    t0 = time.monotonic()
    model = AutoLLM.from_pretrained(model_name, ctx=ctx, seed=SEED)
    cfg = model.cfg
    pay, _ = make_payloads(cfg)
    reqs = list(zip([np.asarray(p, np.int32) for p in pay["requests"]],
                    pay["gen_lens"]))
    outs = {}
    for mode in ("xla", "pallas"):
        eng = ContinuousEngine(model, mode=mode, prefix_cache=True)
        t = time.monotonic()
        first = eng.run(reqs, results=True)
        first_s = time.monotonic() - t
        t = time.monotonic()
        warm = eng.run(reqs, results=True)
        warm_s = time.monotonic() - t
        for r, (_, g) in zip(first + warm, reqs + reqs):
            require(r.ok and len(r.tokens) == g, f"{mode}: {r.status}")
        require(eng.last_stats["prefix_hit_tokens"] > 0,
                f"{mode}: no radix hit on the repeat")
        problems = eng.audit()
        require(problems == [], f"{mode}: audit {problems}")
        outs[mode] = [r.tokens.tolist() for r in first]
        emit(phase="tp_serve", model=model_name, tp=tp, mode=mode,
             first_run_s_with_compile=round(first_s, 2),
             warm_repeat_s=round(warm_s, 2),
             tokens_generated=2 * sum(pay["gen_lens"]),
             peak_bytes_in_use=peak_bytes())
        del eng
        gc.collect()
    prompt, forced, _ = logit_prompt(cfg)
    lx = paged_logits(model, prompt, forced, "xla")
    lp = paged_logits(model, prompt, forced, "pallas")
    name = jnp.dtype(cfg.dtype).name
    tol = TP_LOGIT_TOL[name]
    err = rel_err(lp, lx)
    emit(phase="tp_logits", model=model_name, tp=tp,
         compared="mode=pallas vs mode=xla, max |dlogit| / max |logit|",
         rel_err=err, tolerance=tol,
         greedy_tokens_agree=outs["pallas"] == outs["xla"],
         seconds=round(time.monotonic() - t0, 2))
    require(err <= tol, f"pallas vs xla logits {err} > {tol}")


def collective_checks(model_name: str, ctx) -> None:
    """Each overlap kernel and collective against its XLA reference at
    the tp-sharded model's shard shapes."""
    from jax.sharding import PartitionSpec as P

    from triton_distributed_tpu.models.config import get_config
    from triton_distributed_tpu.ops.collectives.all_gather import (
        AllGatherMethod,
        all_gather,
    )
    from triton_distributed_tpu.ops.collectives.all_reduce import (
        AllReduceMethod,
        all_reduce,
    )
    from triton_distributed_tpu.ops.collectives.reduce_scatter import (
        ReduceScatterMethod,
        reduce_scatter,
    )
    from triton_distributed_tpu.ops.overlap.ag_gemm import ag_gemm
    from triton_distributed_tpu.ops.overlap.gemm_ar import (
        GemmARMethod,
        gemm_ar,
    )
    from triton_distributed_tpu.ops.overlap.gemm_rs import gemm_rs

    tp = ctx.axis_size("tp")
    cfg = get_config(model_name)
    dt = cfg.dtype
    d = cfg.hidden_size
    qkv = (cfg.num_q_heads + 2 * cfg.num_kv_heads) * cfg.head_dim
    o_k = cfg.num_q_heads * cfg.head_dim
    s, dec = (1024, 16) if page_of(cfg) == 128 else (64, 8)
    ks = iter(jax.random.split(jax.random.key(SEED + 2), 16))

    def rnd(shape, *spec, scale=1.0):
        x = jax.random.normal(next(ks), shape, jnp.float32) * scale
        return ctx.shard(x.astype(dt), *spec)

    def sm(fn, in_specs, out_specs, **kw):
        return jax.jit(ctx.shard_map(
            functools.partial(fn, axis="tp", ctx=ctx, **kw),
            in_specs=in_specs, out_specs=out_specs))

    def dot(a, b):
        return jnp.dot(a, b, preferred_element_type=jnp.float32).astype(dt)

    errs = {}
    a_rows, w_cols = rnd((s, d), "tp", None), rnd((d, qkv), None, "tp",
                                                  scale=d ** -0.5)
    errs["ag_gemm"] = rel_err(
        sm(ag_gemm, (P("tp", None), P(None, "tp")), P(None, "tp"))(
            a_rows, w_cols),
        dot(a_rows, w_cols))
    a_cols, w_rows = rnd((s, o_k), None, "tp"), rnd((o_k, d), "tp", None,
                                                    scale=o_k ** -0.5)
    errs["gemm_rs"] = rel_err(
        sm(gemm_rs, (P(None, "tp"), P("tp", None)), P("tp", None))(
            a_cols, w_rows),
        dot(a_cols, w_rows))
    for label, rows, method in (
            ("gemm_ar_one_shot", dec, GemmARMethod.ONE_SHOT),
            ("gemm_ar_two_shot", s // 4, GemmARMethod.TWO_SHOT)):
        a_small = a_cols[:rows]
        errs[label] = rel_err(
            sm(gemm_ar, (P(None, "tp"), P("tp", None)), P(None, None),
               method=method)(a_small, w_rows),
            dot(a_small, w_rows))
    # Collectives: the ring forms on a prefill-sized payload, the
    # one-shot forms on a decode-sized one (they keep every peer's copy
    # in VMEM).
    x_ring, x_one = rnd((s, d), "tp", None), rnd((tp * dec, d), "tp", None)
    for label, x, method in (
            ("all_gather_ring", x_ring, AllGatherMethod.PALLAS_RING),
            ("all_gather_full_mesh", x_one,
             AllGatherMethod.PALLAS_FULL_MESH)):
        errs[label] = rel_err(
            sm(all_gather, P("tp", None), P(None, None), method=method)(x),
            x)
    # Reductions take a different partial on every rank: a [tp, rows, d]
    # array sharded on its leading axis, summed over it.
    p_ring, p_one = rnd((tp, s, d), "tp"), rnd((tp, tp * dec, d), "tp")

    def per_rank(fn):
        return lambda x, **kw: fn(x[0], **kw)

    for label, x, method in (
            ("reduce_scatter_ring", p_ring,
             ReduceScatterMethod.PALLAS_RING_HBM),
            ("reduce_scatter_one_shot", p_one,
             ReduceScatterMethod.ONE_SHOT)):
        errs[label] = rel_err(
            sm(per_rank(reduce_scatter), P("tp"), P("tp", None),
               method=method)(x),
            jnp.sum(x.astype(jnp.float32), 0).astype(dt))
    for label, x, method in (
            ("all_reduce_ring", p_ring, AllReduceMethod.TWO_SHOT),
            ("all_reduce_one_shot", p_one[:, :dec],
             AllReduceMethod.ONE_SHOT)):
        errs[label] = rel_err(
            sm(per_rank(all_reduce), P("tp"), P(None, None),
               method=method)(x),
            jnp.sum(x.astype(jnp.float32), 0).astype(dt))
    tol = COLLECTIVE_TOL[jnp.dtype(dt).name]
    emit(phase="collectives", shard_shapes_of=model_name, tp=tp,
         tolerance=tol, rel_err=errs)
    for name, err in errs.items():
        require(err <= tol, f"{name}: rel err {err} > {tol}")


def replica_check(model_name: str, n: int) -> None:
    """``run_server --replicas n``: one model per chip behind the
    router, its outputs against a single engine's on the same
    requests."""
    from triton_distributed_tpu.models.config import get_config

    cfg = get_config(model_name)
    # Eight greedy requests of mixed lengths with NO shared prefix:
    # round-robin gives every replica two, and with no radix hit in
    # either arm a request's arithmetic does not depend on what was
    # served before it, so every replica must reproduce the golden.
    rng = np.random.default_rng(SEED + 3)
    unit = cfg.max_length // 16
    lengths = [unit // 6, unit + 44, unit // 6, unit + 44,
               unit // 2 + 9, unit // 2 + 9, 2 * unit + 70, 2 * unit + 70]
    payload = {
        "requests": [rng.integers(0, cfg.vocab_size, size=n).tolist()
                     for n in lengths],
        "gen_lens": [12, 8, 12, 8, 16, 16, 6, 6],
    }
    outputs = {}
    for arm, flags in (("single", ["--continuous"]),
                       ("replicas", ["--replicas", str(n),
                                     "--policy", "round_robin"])):
        t0 = time.monotonic()
        with running_server(["--model", model_name, *flags]) as (ask, _):
            resp, _ = ask(payload)
            check_response(resp, payload, cfg.vocab_size)
            problems = ask({"cmd": "audit"})[0]["problems"]
            require(problems == [], f"{arm} audit: {problems}")
            stats = ask({"cmd": "stats"})[0]["stats"]
        outputs[arm] = resp["outputs"]
        served = None
        if arm == "replicas":
            served = {r["name"]: r["served"]
                      for r in stats["router"]["replicas"]}
            require(len(served) == n and all(served.values()),
                    f"not every replica served: {served}")
        emit(phase="replicas", model=model_name, arm=arm,
             seconds_with_start_and_compile=round(time.monotonic() - t0, 2),
             served_per_replica=served)
        gc.collect()
    require(outputs["replicas"] == outputs["single"],
            "replica outputs differ from the single engine's")


# -- entry ------------------------------------------------------------------

# Phases that serve a preset of their own, named by their mode.
OTHER_MODELS = {"latent": latent_phase, "hybrid": hybrid_phase}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--model", default=None,
                   help=f"model preset (default {ONE_CHIP_MODEL}, or "
                   f"{FOUR_CHIP_MODEL} with --chips 4)")
    p.add_argument("--modes", default=",".join(MODES),
                   help="comma-separated serving phases to run, of "
                   f"{list(MODES)} (one chip only), or 'latent': the cut "
                   "latent-attention preset at 32 slots and its kernel, "
                   "or 'hybrid': the Mamba-2 / attention hybrid whole at "
                   "32 slots and its state kernel")
    p.add_argument("--chips", type=int, default=1, choices=[1, 4])
    args = p.parse_args(argv)
    modes = [m for m in args.modes.split(",") if m]
    for m in modes:
        if m not in MODES and m not in OTHER_MODELS:
            p.error(f"unknown mode {m!r}; choose from "
                    f"{[*MODES, *OTHER_MODELS]}")

    # Fail, never hang: past the limit every thread's stack is dumped
    # and the process exits non-zero.
    faulthandler.dump_traceback_later(RUN_LIMIT_S, exit=True,
                                      file=sys.__stderr__)
    try:
        facts = run(args.chips, args.model, modes)
    finally:
        faulthandler.cancel_dump_traceback_later()
    print(json.dumps({"ok": True, "device": facts}), flush=True)
    return 0


def run(chips: int, model: str | None, modes: list[str]) -> dict:
    """Every phase of one run; returns the device facts. Raises at the
    first phase that fails."""
    check_on_chip()
    facts = device_facts()
    require(facts["count"] >= chips,
            f"--chips {chips} but JAX reports {facts['count']} devices")
    from triton_distributed_tpu.runtime.compile_cache import (
        enable_compile_cache,
    )

    emit(phase="start", jax=jax.__version__, device=facts,
         compile_cache=enable_compile_cache())
    if chips == 4:
        from triton_distributed_tpu.runtime.mesh import (
            initialize_distributed,
        )

        ctx = initialize_distributed(tp=4)
        check_on_chip(ctx)
        emit(phase="mesh", shape=dict(ctx.mesh.shape),
             rung=ctx.topology.mesh_rung,
             devices=[str(d) for d in ctx.mesh.devices.flat])
        # Cheapest first: a kernel that misbehaves across chips shows
        # before the model is built.
        collective_checks(model or FOUR_CHIP_MODEL, ctx)
        tp_model_check(model or FOUR_CHIP_MODEL, ctx)
        gc.collect()
        replica_check(model or ONE_CHIP_MODEL, 4)
    else:
        for mode in modes:
            if mode in OTHER_MODELS:
                OTHER_MODELS[mode]()
            else:
                serve_phase(model or ONE_CHIP_MODEL, mode)
            # The phase's model must be gone before the next is built:
            # the chip does not hold two.
            gc.collect()
        if set(modes) - set(OTHER_MODELS):
            logit_checks(model or ONE_CHIP_MODEL)
    return facts


if __name__ == "__main__":
    sys.exit(main())
