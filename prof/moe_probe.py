"""Chip probe, kernel only (scratch): ``tdt_moe_decode_experts`` called
once a layer over the four expert layers' stacked weights at the served
widths (a scan, x carried so nothing hoists), by how many experts are
touched and by the column tile, beside the gate-weighted einsum over all
16 (the parent's branch). Device time from the profiler trace.

    python prof/moe_probe.py <tag> [tiles, e.g. 128,256,512]
"""
import json
import os
import sys
import tempfile

sys.path.insert(0, os.getcwd())

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import xplane
from triton_distributed_tpu.ops.moe import decode_experts
from triton_distributed_tpu.ops.moe.decode_experts import (
    moe_decode_experts,
    moe_decode_experts_reference,
    touched_experts,
)

LAYERS, HELD = 4, 16
D, F = (int(v) for v in os.environ.get("DF", "7168,2048").split(","))
ROWS = int(os.environ.get("ROWS", 32))
REPS = 10
EXPERT_BYTES = 3 * D * F * 2


def draw(key, shape, scale):
    """bf16 uniform weights, a layer a program (no float32 temporaries
    of the whole stack)."""
    def one(k):
        return (jax.random.randint(k, shape[1:], -127, 128, jnp.int8)
                .astype(jnp.bfloat16) * (scale / 127.0)).astype(jnp.bfloat16)

    return jnp.stack([jax.jit(one)(k) for k in jax.random.split(key, shape[0])])


def scan_of(ffn):
    """Four layers in a scan, each feeding the next (as the model)."""
    def run(x, gate, touched, n, w1, w2):
        def body(x, layer):
            y = ffn(x, gate, touched, n, w1, w2, layer)
            return (x + 0.01 * y.astype(x.dtype)).astype(x.dtype), y

        return jax.lax.scan(body, x, jnp.arange(LAYERS, dtype=jnp.int32))

    return jax.jit(run)


def traced(fn, args, select):
    out = fn(*args)
    jax.block_until_ready(out)
    with tempfile.TemporaryDirectory(prefix="moe_probe_") as tdir:
        jax.profiler.start_trace(tdir)
        for _ in range(REPS):
            out = fn(*args)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        tr = xplane.reduce_dir(tdir, 1)
    if os.environ.get("REHEARSE"):
        return 1.0, {}
    own = tr.self_seconds()
    picked = {k: v for k, v in own.items() if select(k)}
    top = sorted(own.items(), key=lambda kv: -kv[1])[:6]
    return (sum(picked.values()) / (REPS * LAYERS) * 1e3,
            {k: round(v / (REPS * LAYERS) * 1e3, 4) for k, v in top})


def main():
    tag = sys.argv[1]
    tiles = [int(t) for t in (sys.argv[2] if len(sys.argv) > 2
                              else "128,256,512").split(",")]
    dev = jax.devices()[0]
    assert dev.platform == "tpu" or os.environ.get("REHEARSE"), dev
    k = jax.random.split(jax.random.key(0), 4)
    w1 = draw(k[0], (LAYERS, HELD, D, 2 * F), 1.7 * D ** -0.5)
    w2 = draw(k[1], (LAYERS, HELD, F, D), 1.7 * F ** -0.5)
    x = jax.random.normal(k[2], (ROWS, D), jnp.float32).astype(jnp.bfloat16)
    result = {"tag": tag, "device": dev.device_kind, "rows": ROWS}

    def gate_of(chosen):
        """Every touched expert gets two or three rows."""
        g = np.zeros((ROWS, HELD), np.float32)
        for j, e in enumerate(chosen):
            for r in (3 * j, 3 * j + 1, (7 * j + 5)):
                g[r % ROWS, e] = 0.2 + 0.01 * e
        return jnp.asarray(g)

    sets = {
        "n0": [], "n1": [5], "n3": [1, 6, 11], "n8": list(range(0, 16, 2)),
        "n16": list(range(16)),
    }
    # The mark: the parent's branch, every held expert on every row.
    ref = scan_of(lambda x, g, e, n, w1, w2, layer:
                  moe_decode_experts_reference(
                      x, g, w1[layer], w2[layer]))
    for name in ("n3", "n16"):
        g = gate_of(sets[name])
        t, n = touched_experts(jnp.any(g != 0, axis=0))
        ms, top = traced(ref, (x, g, t, n, w1, w2),
                         lambda k: "fusion" in k or "convolution" in k)
        result[f"einsum_{name}"] = {"ms_a_layer": round(ms, 4), "top": top}
        print(tag, "einsum", name, json.dumps(result[f"einsum_{name}"]),
              flush=True)
    want = {name: np.asarray(ref(x, gate_of(ch), *touched_experts(
        jnp.any(gate_of(ch) != 0, axis=0)), w1, w2)[1], np.float32)
        for name, ch in sets.items()}
    for tile in tiles:
        decode_experts.F_TILE = tile  # read while the scan is traced
        fn = scan_of(lambda x, g, e, n, w1, w2, layer:
                     moe_decode_experts(x, g, e, n, w1, w2, layer=layer))
        for name, chosen in sets.items():
            g = gate_of(chosen)
            t, n = touched_experts(jnp.any(g != 0, axis=0))
            try:
                ms, top = traced(fn, (x, g, t, n, w1, w2),
                                 lambda k: "tdt_moe_decode_experts" in k)
            except Exception as e:  # noqa: BLE001
                print(tag, "tile", tile, name, "FAILED",
                      str(e).splitlines()[0][:300], flush=True)
                break
            got = np.asarray(fn(x, g, t, n, w1, w2)[1], np.float32)
            err = float(np.max(np.abs(got - want[name])))
            nn = max(len(chosen), 0)
            row = {
                "ms_a_layer": round(ms, 4),
                "ms_an_expert": round(ms / nn, 4) if nn else None,
                "GBps": round(nn * EXPERT_BYTES / ms / 1e6, 1) if nn else None,
                "max_abs_err_vs_einsum": err,
                "scale": float(np.max(np.abs(want[name]))),
                "top": top,
            }
            result[f"tile{tile}_{name}"] = row
            print(tag, "tile", tile, name, json.dumps(row), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", f"moe_probe_{tag}.json"), "w") as f:
        json.dump(result, f, indent=1)


if __name__ == "__main__":
    main()
