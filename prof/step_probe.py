"""Chip probe: a decode step's device time by operation (PERF.md
section 5's table): 20 chained ``model.decode_step`` calls under
``jax.profiler`` at given context lengths, reduced by
``benchmark/xplane.py``. Runs the package of the directory it is run
FROM (so the parent, unpacked in ``.parent_src``, is probed by the same
file), one process a tree.

    python prof/step_probe.py <tag>

The result lines go to standard output and to
``chiprun_out/step_probe_<tag>.json`` under the working directory; the
profiler's trace goes to a temporary directory of this process's own
(under ``TMPDIR``) and is removed with it.
"""
import dataclasses
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.getcwd())

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import xplane
from triton_distributed_tpu.models.config import get_config
from triton_distributed_tpu.models.paged_kv_cache import init_paged_cache
from triton_distributed_tpu.models.qwen import Qwen3
from triton_distributed_tpu.runtime.mesh import initialize_distributed

LENS = {
    "two_live": [411, 300, 0, 0],
    "four_live": [411, 300, 520, 180],
    "four_live_b": [650, 130, 390, 260],
    "one_long": [4000, 300, 300, 300],
}
STEPS = 20


def main():
    tag = sys.argv[1]
    dev = jax.devices()[0]
    assert dev.platform == "tpu", dev
    result = {"tag": tag, "device": dev.device_kind}
    ctx = initialize_distributed(tp=1, devices=jax.devices()[:1])
    cfg = get_config("Qwen/Qwen3-4B")
    model = Qwen3(cfg, ctx=ctx)
    model.init_params(jax.random.key(0))
    cache, _ = init_paged_cache(
        cfg, 4, ctx, num_pages=4 * 32 + 1, max_length=32 * 128, page_size=128)
    # The pool stays zeros: the kernel's time follows the pages it
    # walks, not their contents.
    tokens = jnp.asarray([1, 2, 3, 4], jnp.int32)
    logits, cache = model.decode_step(tokens, cache, "xla")  # compile
    np.asarray(logits)
    for name, lens in LENS.items():
        cache = dataclasses.replace(cache, kv_len=jnp.asarray(lens, jnp.int32))
        for _ in range(3):
            logits, cache = model.decode_step(tokens, cache, "xla")
        np.asarray(logits)
        cache = dataclasses.replace(cache, kv_len=jnp.asarray(lens, jnp.int32))
        with tempfile.TemporaryDirectory(prefix="step_probe_") as tdir:
            jax.profiler.start_trace(tdir)
            t0 = time.perf_counter()
            for _ in range(STEPS):
                logits, cache = model.decode_step(tokens, cache, "xla")
            np.asarray(logits)
            wall = time.perf_counter() - t0
            jax.profiler.stop_trace()
            tr = xplane.reduce_dir(tdir, 1)
        own = tr.self_seconds()
        mods = tr.modules("decode")
        durs = sorted(e[2] for e in mods)
        ops = {k: round(v / STEPS * 1e3, 4) for k, v in
               sorted(own.items(), key=lambda kv: -kv[1])[:14]}
        kern = sum(v for k, v in own.items() if "tdt_flash_decode_paged" in k)
        result[name] = {
            "lens": lens,
            "step_ms_median": durs[len(durs) // 2] / 1e6,
            "steps_traced": len(mods),
            "wall_ms_a_step": wall / STEPS * 1e3,
            "kernel_ms_a_step": kern / STEPS * 1e3,
            "ops_ms_a_step": ops,
        }
        print(tag, name, json.dumps(result[name]), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", f"step_probe_{tag}.json"), "w") as f:
        json.dump(result, f, indent=1)


if __name__ == "__main__":
    main()
