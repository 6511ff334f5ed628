"""End-to-end serve of a REAL HF-format Qwen3 checkpoint on the chip.

VERDICT r2 missing #4: nothing had ever run the HF-checkpoint path end
to end. Zero-egress means no true pretrained weights exist on this
machine, so this script builds the most faithful substitute: an actual
``transformers.Qwen3ForCausalLM`` (random init, REAL architecture code)
saved with ``save_pretrained`` — byte-identical format to a downloaded
checkpoint — then drives the full serving path against it:

    AutoLLM.from_pretrained(<hf dir>) → Engine.serve(mode=...)

and emits a generation transcript + tok/s. The loader/math parity with
upstream transformers is separately pinned by
``tests/test_model.py::test_hf_transformers_parity`` (greedy tokens
bit-identical at fp32), so a coherent run here certifies the
checkpoint path, not the weights' knowledge.

Defaults to a reduced depth for a quick run; pass --full for true
Qwen3-0.6B dims.

Usage: python perf/real_weights_e2e.py [--full] [--mode mega_multi]
"""

import argparse
import json
import os
import sys
import tempfile
import time

_T0 = time.perf_counter()  # process start — anchors the first phase marker

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


# Checkpoint geometries. ``1.7b`` is the REAL Qwen3-1.7B architecture
# (headline-class per VERDICT r3 task 4: a 16 GB v5e holds 1.7B/4B
# bf16); its checkpoint is saved bf16 — the dtype real Qwen3 releases
# ship in — which also halves the host->device load.
_GEOMS = {
    "small": dict(vocab_size=32768, hidden_size=1024,
                  intermediate_size=3072, num_hidden_layers=8,
                  num_attention_heads=16, num_key_value_heads=8),
    "0.6b": dict(vocab_size=151936, hidden_size=1024,
                 intermediate_size=3072, num_hidden_layers=28,
                 num_attention_heads=16, num_key_value_heads=8),
    "1.7b": dict(vocab_size=151936, hidden_size=2048,
                 intermediate_size=6144, num_hidden_layers=28,
                 num_attention_heads=16, num_key_value_heads=8),
}


def build_checkpoint(geom: str) -> str:
    # Reuse an already-built checkpoint: save_pretrained costs minutes
    # on this 1-core host, and every watcher retry pays it again. The
    # build is deterministic (manual_seed(0)), so an existing dir with
    # weights is byte-equivalent to a rebuild.
    path = os.path.join(
        tempfile.gettempdir(),
        {"small": "qwen3_hf_small", "0.6b": "qwen3_hf_full"}.get(
            geom, f"qwen3_hf_{geom}"
        ),
    )
    if os.path.exists(os.path.join(path, "config.json")) and any(
        f.endswith(".safetensors") for f in os.listdir(path)
    ):
        return path

    import torch
    import transformers

    cfg = transformers.Qwen3Config(
        head_dim=128,
        rope_theta=1e6,
        rms_norm_eps=1e-6,
        tie_word_embeddings=True,
        max_position_embeddings=2048,
        **_GEOMS[geom],
    )
    torch.manual_seed(0)
    model = transformers.Qwen3ForCausalLM(cfg).eval()
    if geom == "1.7b":
        model = model.to(torch.bfloat16)
    # Build into a scratch dir and rename into place: save_pretrained
    # is non-atomic and takes minutes here — a watcher kill mid-save
    # would otherwise leave a partial dir that passes the reuse check
    # forever.
    tmp = path + ".building"
    if os.path.exists(tmp):
        import shutil

        shutil.rmtree(tmp)
    model.save_pretrained(tmp, safe_serialization=True)
    os.rename(tmp, path)
    return path


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--full", action="store_true",
                   help="alias for --geom 0.6b")
    p.add_argument("--geom", default=None, choices=sorted(_GEOMS),
                   help="checkpoint geometry: small (depth-8 smoke), "
                        "0.6b, 1.7b (headline-class, bf16 checkpoint)")
    p.add_argument("--mode", default="mega_multi",
                   choices=["xla", "pallas", "mega", "mega_multi"])
    p.add_argument("--q8", action="store_true",
                   help="weight-only int8 megakernel decode "
                        "(MegaConfig(wq8=True); mega modes only)")
    p.add_argument("--gen-len", type=int, default=64)
    p.add_argument("--cpu", action="store_true")
    args = p.parse_args(argv)

    if args.cpu:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=1"
        )
        import jax

        jax.config.update("jax_platforms", "cpu")
    import jax
    import jax.numpy as jnp
    import numpy as np

    from triton_distributed_tpu.models import AutoLLM, Engine
    from triton_distributed_tpu.runtime.mesh import initialize_distributed

    # Phase progress on stderr (flushed): a step-timeout kill then still
    # shows WHERE the time went — the 03:19 on-chip session burned its
    # whole 1500 s budget with zero output.
    def phase(name, t0=[_T0]):
        now = time.perf_counter()
        print(f"[e2e +{now - t0[0]:.0f}s] {name}", file=sys.stderr, flush=True)
        t0[0] = now

    geom = args.geom or ("0.6b" if args.full else "small")
    phase(f"imports done; building HF checkpoint (torch, 1 core, {geom})")
    ckpt = build_checkpoint(geom)
    phase("checkpoint saved; initializing device context")
    ctx = initialize_distributed(tp=1, devices=jax.devices()[:1])
    phase("ctx up; AutoLLM.from_pretrained (safetensors -> device)")
    t0 = time.perf_counter()
    model = AutoLLM.from_pretrained(ckpt, ctx=ctx, max_length=1024)
    load_s = time.perf_counter() - t0
    phase("params loaded; building Engine")

    mode = args.mode
    if mode == "mega_multi":
        mode = "mega"  # Engine auto-selects multi-step in mega mode
    mega_cfg = None
    if args.q8:
        from triton_distributed_tpu.megakernel.code_generator import (
            MegaConfig,
        )

        mega_cfg = MegaConfig(wq8=True)
    eng = Engine(model, temperature=0.0, mode=mode, mega_cfg=mega_cfg)
    prompt = np.arange(1, 33, dtype=np.int32)[None]

    # First serve is the WARM-UP (prefill + decode compiles, tens of
    # seconds); the timed number comes from the
    # second, already-compiled call. The pair doubles as the greedy
    # determinism check.
    t0 = time.perf_counter()
    out = eng.serve(prompt, gen_len=args.gen_len)
    cold_wall = time.perf_counter() - t0
    phase(f"cold serve done ({cold_wall:.0f}s incl. compiles); timed serve")
    t0 = time.perf_counter()
    out2 = eng.serve(prompt, gen_len=args.gen_len)
    wall = time.perf_counter() - t0
    gen = out[0, prompt.shape[1]:]
    deterministic = bool((out == out2).all())

    print(json.dumps({
        "checkpoint": ckpt,
        "config": {"small": "qwen3-0.6B-depth8", "0.6b": "qwen3-0.6B",
                   "1.7b": "qwen3-1.7B"}[geom],
        "platform": jax.devices()[0].platform,
        "mode": args.mode + ("+q8" if args.q8 else ""),
        "load_s": round(load_s, 1),
        "gen_len": int(args.gen_len),
        "cold_wall_s": round(cold_wall, 2),
        "wall_s": round(wall, 2),
        "tok_s": round(args.gen_len / wall, 2),
        "deterministic": deterministic,
        "transcript_tokens": gen.tolist(),
    }))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
