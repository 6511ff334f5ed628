"""Autotuner + perf-model coverage.

Parity: the reference exercises its autotuner through the kernel tests
(``contextual_autotune`` wrapping ag_gemm runs) and uses the perf models
for pruning; here both get direct unit tests.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from triton_distributed_tpu.tools import (
    ChipSpec,
    Config,
    autotune,
    chip_spec,
    estimate_all_gather_time_ms,
    estimate_all_reduce_time_ms,
    estimate_gemm_time_ms,
    estimate_reduce_scatter_time_ms,
    prune_configs_by_model,
)
from triton_distributed_tpu.tools.autotuner import Autotuner, KernelError


def test_autotune_picks_best_and_caches(tmp_path, monkeypatch):
    monkeypatch.setenv("TDT_AUTOTUNE_LOG_DIR", str(tmp_path))
    calls = []

    def op(x, tile=128):
        calls.append(tile)
        if tile == 512:
            raise ValueError("config does not fit")  # pruned-at-runtime path
        import time

        time.sleep(0.02 if tile == 64 else 0.001)
        return x * tile

    tuner = Autotuner(
        op,
        [Config({"tile": 64}), Config({"tile": 128}), Config({"tile": 512})],
        n_warmup=1,
        n_repeat=2,
    )
    x = jnp.ones((4, 4))
    out = tuner(x)
    best = tuner.cache[next(iter(tuner.cache))]
    assert best.kwargs["tile"] == 128
    np.testing.assert_allclose(np.asarray(out), 128.0)

    n_before = len(calls)
    tuner(x)  # cached: exactly one call, no re-bench
    assert len(calls) == n_before + 1
    # a different shape re-tunes
    tuner(jnp.ones((8, 4)))
    assert len(tuner.cache) == 2
    log = (tmp_path / "rank-0.log").read_text()
    assert "best-config" in log and "error" in log


def test_autotune_key_includes_kwargs():
    tuned_with = []

    def op(x=None, flag=False, tile=64):
        tuned_with.append((flag, tile))
        return flag

    tuner = Autotuner(
        op, [Config({"tile": 64}), Config({"tile": 128})],
        n_warmup=0, n_repeat=1,
    )
    tuner(x=jnp.ones((4, 4)), flag=False)
    tuner(x=jnp.ones((4096, 4)), flag=False)  # kw array: distinct key
    tuner(x=jnp.ones((4, 4)), flag=True)      # kw scalar: distinct key
    assert len(tuner.cache) == 3


def test_contextual_autotune_overrides_inner_tuners():
    from triton_distributed_tpu.tools.autotuner import contextual_autotune

    bench_calls = []

    def op(x, tile=64):
        bench_calls.append(tile)
        return x

    tuner = Autotuner(
        op, [Config({"tile": 64}), Config({"tile": 128})],
        n_warmup=0, n_repeat=5,
    )

    @contextual_autotune(n_repeat=1, n_warmup=0)
    def outer(x):
        return tuner(x)

    outer(jnp.ones((2, 2)))
    # 2 configs x (1 repeat + 0 warmup) + 1 replay = 3 calls, not 11.
    assert len(bench_calls) == 3
    assert outer.__name__ == "outer"  # functools.wraps applied


def test_autotune_decorator_and_all_fail():
    @autotune(configs=[{"t": 1}, {"t": 2}], n_warmup=0, n_repeat=1)
    def op(x, t=1):
        raise RuntimeError("boom")

    with pytest.raises(KernelError):
        op(jnp.ones((2, 2)))


def test_perf_model_rooflines():
    spec = ChipSpec("v5e", 197.0, 394.0, 819.0, 45.0, 4, 25.0)
    # Large square bf16 GEMM is compute-bound: time ≈ flops/peak.
    ms = estimate_gemm_time_ms(4096, 4096, 4096, jnp.bfloat16, spec)
    ideal = 2 * 4096**3 / (197e12) * 1e3
    assert ms == pytest.approx(ideal, rel=1e-6)
    # Skinny decode GEMM is memory-bound: time ≥ weight-stream time.
    ms = estimate_gemm_time_ms(1, 4096, 4096, jnp.bfloat16, spec)
    assert ms >= 2 * 4096 * 4096 / (819e9) * 1e3

    rs = estimate_reduce_scatter_time_ms(2**20, 8, spec=spec)
    ag = estimate_all_gather_time_ms(2**20, 8, spec=spec)
    ar = estimate_all_reduce_time_ms(2**20, 8, spec=spec)
    assert rs == ag and ar == pytest.approx(2 * rs)
    # Crossing a slice boundary (DCN) must cost more than staying on ICI.
    multi = estimate_reduce_scatter_time_ms(2**20, 16, 8, spec=spec)
    assert multi > rs


def test_prune_and_chip_spec_unknown_kind_raises():
    cfgs = [Config({"tile": t}) for t in (64, 128, 256, 512)]
    kept = prune_configs_by_model(cfgs, lambda c: abs(c.kwargs["tile"] - 256), 2)
    assert [c.kwargs["tile"] for c in kept] == [256, 128]
    assert chip_spec("TPU v5 lite").name == "v5e"
    assert chip_spec("TPU v5p").name == "v5p"
    # A kind that matches no generation is an error, never a v5e — and
    # so is the attached device's own kind when it is no TPU.
    with pytest.raises(ValueError, match="no chip spec"):
        chip_spec("weird device")
    with pytest.raises(ValueError, match="no chip spec"):
        chip_spec()


def test_autotune_persistent_cache(tmp_path, monkeypatch):
    """A fresh Autotuner (new process stand-in) replays the argmin from
    disk without re-sweeping; a changed config space re-tunes."""
    monkeypatch.setenv("TDT_AUTOTUNE_CACHE", "1")
    monkeypatch.setenv("TDT_AUTOTUNE_CACHE_DIR", str(tmp_path))
    calls = []

    def op(x, tile=128):
        calls.append(tile)
        import time

        time.sleep(0.02 if tile == 64 else 0.001)
        return x * tile

    configs = [Config({"tile": 64}), Config({"tile": 128})]
    x = jnp.ones((4, 4))
    Autotuner(op, configs, n_warmup=1, n_repeat=2)(x)
    import os
    cached = os.listdir(tmp_path)
    assert len(cached) == 1 and cached[0].endswith(".json")
    swept = len(calls)
    assert swept > 2  # both configs benched

    # Fresh instance: disk hit — exactly one replay call, no sweep.
    out = Autotuner(op, configs, n_warmup=1, n_repeat=2)(x)
    assert len(calls) == swept + 1
    np.testing.assert_allclose(np.asarray(out), 128.0)

    # Config space changed: stored argmin no longer resolves → re-tune.
    calls.clear()
    Autotuner(op, [Config({"tile": 32}), Config({"tile": 256})],
              n_warmup=1, n_repeat=2)(x)
    assert len(calls) > 2


@pytest.mark.slow
def test_ag_gemm_tuned_end_to_end(ctx4, rng, tmp_path, monkeypatch):
    """The tuned overlap entry points sweep the tile grid once per shape
    and replay the argmin (in-memory + disk cache)."""
    monkeypatch.setenv("TDT_AUTOTUNE_CACHE_DIR", str(tmp_path))
    from triton_distributed_tpu.ops.overlap import ag_gemm_tuned
    import triton_distributed_tpu.ops.overlap.tuned as tuned
    from triton_distributed_tpu.ops.overlap.tuned import _ag_tuner

    # Tiny grid: interpret-mode sweeps are slow; 2 configs prove the
    # sweep/replay machinery.
    monkeypatch.setattr(tuned, "_TILE_MS", (32,))
    monkeypatch.setattr(tuned, "_TILE_NS", (128, 256))
    _ag_tuner.cache_clear()
    M, K, N = 4 * 32, 128, 1024
    a = jnp.asarray(rng.standard_normal((M, K), dtype=np.float32))
    b = jnp.asarray(rng.standard_normal((K, N), dtype=np.float32))
    out = ag_gemm_tuned(a, b, "tp", ctx4)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(a) @ np.asarray(b), rtol=1e-4, atol=1e-4
    )
    tuner = _ag_tuner(M // 4, N // 4, K, "tp", 4, "float32", False)
    assert len(tuner.cache) == 1  # swept once, argmin cached
    out2 = ag_gemm_tuned(a, b, "tp", ctx4)  # replay path
    np.testing.assert_allclose(np.asarray(out2), np.asarray(out), rtol=1e-6)


@pytest.mark.slow
def test_gemm_rs_tuned_end_to_end(ctx4, rng, tmp_path, monkeypatch):
    monkeypatch.setenv("TDT_AUTOTUNE_CACHE_DIR", str(tmp_path))
    from triton_distributed_tpu.ops.overlap import gemm_rs_tuned
    import triton_distributed_tpu.ops.overlap.tuned as tuned
    from triton_distributed_tpu.ops.overlap.tuned import _rs_tuner

    # Two configs so the sweep/replay path actually runs (a single
    # config short-circuits the tuner).
    monkeypatch.setattr(tuned, "_TILE_MS", (32,))
    monkeypatch.setattr(tuned, "_TILE_NS", (128, 256))
    _rs_tuner.cache_clear()
    M, K, N = 4 * 32, 256, 512
    a = jnp.asarray(rng.standard_normal((M, K), dtype=np.float32))
    b = jnp.asarray(rng.standard_normal((K, N), dtype=np.float32))
    out = gemm_rs_tuned(a, b, "tp", ctx4)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(a) @ np.asarray(b), rtol=1e-4, atol=1e-4
    )
    tuner = _rs_tuner(M, N, K // 4, "tp", 4, "float32", False)
    assert len(tuner.cache) == 1  # swept once, argmin cached


def test_anchored_spec_and_straggler_model():
    """anchored_spec derives effective rates from recorded measurements
    (hbm verbatim, MXU solved from the gemm anchor, ICI derated by the
    HBM fraction); the straggler-stall model shows the adaptive
    schedule's tolerance."""
    from triton_distributed_tpu.tools.perf_model import (
        anchored_spec,
        chip_spec,
        estimate_straggler_stall_ms,
    )

    base = chip_spec("v5e")
    anchors = {
        "chip": "v5e",
        "hbm_gbs": 667.0,
        "gemm_anchor": {"m": 8192, "n": 12288, "k": 4096, "ms": 12.65},
        "error_bars_frac": 0.3,
    }
    spec, meta = anchored_spec(anchors)
    assert meta["anchored"] is True
    assert spec.hbm_gbs == 667.0
    ideal = 2.0 * 8192 * 12288 * 4096 / (12.65e-3) / 1e12
    assert abs(spec.bf16_tflops - ideal) < 0.1
    assert abs(spec.ici_gbs_per_link - base.ici_gbs_per_link * 667 / 819) < 0.1
    # No anchors: datasheet fallback, flagged.
    spec2, meta2 = anchored_spec({})
    assert meta2 == {"anchored": False}
    assert spec2.bf16_tflops == base.bf16_tflops

    # Straggler model: lag of 3 steps at tp=8 — static exposes some,
    # adaptive exposes none (laggard met last, 7 steps of cover).
    static = estimate_straggler_stall_ms(3.0, 1.0, 8, adaptive=False)
    adapt = estimate_straggler_stall_ms(3.0, 1.0, 8, adaptive=True)
    assert adapt == 0.0
    assert static == pytest.approx(3 / 7)  # [2,1,0,...]/7
    # Lag beyond full cover exposes the remainder either way.
    assert estimate_straggler_stall_ms(10.0, 1.0, 8, True) == 3.0


def test_runtime_faults_compiles():
    """The fault-injection harness (runtime/faults.py) must
    byte-compile: its seams are imported by the pool allocator and the
    server, so a syntax error there takes down the whole serving
    stack at import time."""
    import os
    import subprocess
    import sys

    target = os.path.join(
        os.path.dirname(__file__), "..", "triton_distributed_tpu",
        "runtime", "faults.py",
    )
    proc = subprocess.run(
        [sys.executable, "-m", "compileall", "-q", "-f", target],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, (
        f"runtime/faults.py failed to compile:\n{proc.stdout}\n{proc.stderr}"
    )


def test_perf_scripts_compile():
    """Every perf/ script must at least byte-compile (tier-1 guard: the
    bench harnesses are run ad hoc on the chip, so a syntax error
    would otherwise surface only when chip time is burning)."""
    import os
    import subprocess
    import sys

    perf_dir = os.path.join(os.path.dirname(__file__), "..", "perf")
    proc = subprocess.run(
        [sys.executable, "-m", "compileall", "-q", "-f", perf_dir],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, (
        f"perf/ scripts failed to compile:\n{proc.stdout}\n{proc.stderr}"
    )


def test_obs_modules_compile():
    """The telemetry stack must byte-compile: obs/ is imported by the
    engines, the server, the fault harness, and the profiler span
    wrapper — a syntax error there takes the whole serving stack down
    at import time. The CPU-runnable overhead bench rides along (repo
    convention: perf harnesses fail tier-1, not a chip run)."""
    import os
    import subprocess
    import sys

    root = os.path.join(os.path.dirname(__file__), "..")
    targets = [
        os.path.join(root, "triton_distributed_tpu", "obs"),
        os.path.join(root, "triton_distributed_tpu", "models", "stats.py"),
        os.path.join(root, "perf", "obs_overhead_bench.py"),
    ]
    proc = subprocess.run(
        [sys.executable, "-m", "compileall", "-q", "-f", *targets],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, (
        f"obs modules failed to compile:\n{proc.stdout}\n{proc.stderr}"
    )


def test_kernel_trace_modules_compile():
    """ISSUE 8: the device task tracer's host half must byte-compile —
    obs/kernel_trace.py is imported lazily from the decode hot path
    (a traced launch decodes its ring inline), and the CPU-runnable
    bench that writes perf/MEGA_TRACE.json rides along (repo
    convention: perf harnesses fail tier-1, not a chip run)."""
    import os
    import subprocess
    import sys

    root = os.path.join(os.path.dirname(__file__), "..")
    targets = [
        os.path.join(root, "triton_distributed_tpu", "obs",
                     "kernel_trace.py"),
        os.path.join(root, "triton_distributed_tpu", "megakernel",
                     "task.py"),
        os.path.join(root, "perf", "mega_trace_bench.py"),
    ]
    proc = subprocess.run(
        [sys.executable, "-m", "compileall", "-q", "-f", *targets],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, (
        f"kernel-trace modules failed to compile:\n"
        f"{proc.stdout}\n{proc.stderr}"
    )


def test_resident_modules_compile():
    """ISSUE-19: the resident-decode pieces must byte-compile — the
    engine's mega round loop, and the bench that writes the resident
    section of perf/MEGA_SERVE.json rides along (repo convention: perf
    harnesses fail tier-1, not a chip run)."""
    import os
    import subprocess
    import sys

    root = os.path.join(os.path.dirname(__file__), "..")
    targets = [
        os.path.join(root, "triton_distributed_tpu", "models",
                     "continuous.py"),
        os.path.join(root, "perf", "mega_serve_bench.py"),
    ]
    proc = subprocess.run(
        [sys.executable, "-m", "compileall", "-q", "-f", *targets],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, (
        f"resident-decode modules failed to compile:\n"
        f"{proc.stdout}\n{proc.stderr}"
    )


def test_goodput_modules_compile():
    """ISSUE-13: the SLO-goodput yardstick's modules must byte-compile
    — obs/slo.py is imported by the server (a syntax error takes the
    wire down at import time), and the CPU-runnable load generator +
    goodput bench that write perf/GOODPUT.json ride along (repo
    convention: perf harnesses fail tier-1, not a chip run)."""
    import os
    import subprocess
    import sys

    root = os.path.join(os.path.dirname(__file__), "..")
    targets = [
        os.path.join(root, "triton_distributed_tpu", "obs", "slo.py"),
        os.path.join(root, "perf", "loadgen.py"),
        os.path.join(root, "perf", "goodput_bench.py"),
    ]
    proc = subprocess.run(
        [sys.executable, "-m", "compileall", "-q", "-f", *targets],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, (
        f"goodput modules failed to compile:\n{proc.stdout}\n{proc.stderr}"
    )


def test_pools_modules_compile():
    """ISSUE-15: the elastic pool control plane must byte-compile —
    pools.py/autoscaler.py are imported by the serving package (a
    syntax error takes every fleet down at import time), and the
    pools bench that writes perf/POOLS.json rides along (repo
    convention: perf harnesses fail tier-1, not a chip run)."""
    import os
    import subprocess
    import sys

    root = os.path.join(os.path.dirname(__file__), "..")
    serving = os.path.join(root, "triton_distributed_tpu", "serving")
    targets = [
        os.path.join(serving, "pools.py"),
        os.path.join(serving, "autoscaler.py"),
        os.path.join(serving, "router.py"),
        os.path.join(serving, "supervisor.py"),
        os.path.join(root, "perf", "pools_bench.py"),
    ]
    proc = subprocess.run(
        [sys.executable, "-m", "compileall", "-q", "-f", *targets],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, (
        f"pool control-plane modules failed to compile:\n"
        f"{proc.stdout}\n{proc.stderr}"
    )


def test_multihost_modules_compile():
    """ISSUE-18: the multi-host launcher seam must byte-compile —
    launcher.py is imported by the supervisor (a syntax error takes
    every fleet down at import time), and the host-loss bench that
    writes perf/HOST_LOSS.json rides along (repo convention: perf
    harnesses fail tier-1, not a chip run)."""
    import os
    import subprocess
    import sys

    root = os.path.join(os.path.dirname(__file__), "..")
    serving = os.path.join(root, "triton_distributed_tpu", "serving")
    targets = [
        os.path.join(serving, "launcher.py"),
        os.path.join(serving, "supervisor.py"),
        os.path.join(serving, "remote.py"),
        os.path.join(serving, "run_server.py"),
        os.path.join(root, "perf", "host_loss_bench.py"),
    ]
    proc = subprocess.run(
        [sys.executable, "-m", "compileall", "-q", "-f", *targets],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, (
        f"multi-host modules failed to compile:\n"
        f"{proc.stdout}\n{proc.stderr}"
    )


def test_tier1_marker_audit():
    """ISSUE 8 satellite: a ``slow`` mark takes a test out of tier-1,
    so a suite marked slow wholesale, or nearly so, guards nothing
    there. Every ``tests/test_*.py`` keeps a test that tier-1 runs, and
    a file of ten tests or more keeps five."""
    import ast
    import glob
    import os

    def is_slow(node):
        return any("slow" in ast.dump(d) for d in node.decorator_list)

    thin = {}
    here = os.path.dirname(__file__)
    for path in sorted(glob.glob(os.path.join(here, "test_*.py"))):
        marks = []  # one bool a test: is it marked slow?
        for node in ast.parse(open(path).read()).body:
            members = node.body if isinstance(node, ast.ClassDef) else [node]
            marks += [
                is_slow(m) or (m is not node and is_slow(node))
                for m in members
                if isinstance(m, ast.FunctionDef)
                and m.name.startswith("test_")
            ]
        fast = marks.count(False)
        if fast < (5 if len(marks) >= 10 else 1):
            thin[os.path.basename(path)] = f"{fast} of {len(marks)}"
    assert not thin, f"too few tier-1-runnable tests: {thin}"


def test_a_wait_past_the_limit_fails_that_test_with_every_stack():
    """``conftest.time_limit`` (armed with ``LIMIT`` round every phase of
    every test): a wait past it fails the test BY NAME with every
    thread's stack, the process lives on, and the timer and handler
    found on entry (this test's own ``LIMIT``) are back afterwards."""
    import signal
    import threading
    import time

    import conftest

    handler = signal.getsignal(signal.SIGALRM)
    left, _ = signal.getitimer(signal.ITIMER_REAL)
    assert 0 < left <= conftest.LIMIT  # this test runs under its own limit
    stop = threading.Event()

    def a_thread_that_waits():
        stop.wait(30)

    waiter = threading.Thread(target=a_thread_that_waits)
    waiter.start()
    t0 = time.monotonic()
    try:
        with pytest.raises(pytest.fail.Exception) as e:
            with conftest.time_limit(0.2, "tests/x.py::test_that_waits"):
                stop.wait(30)
    finally:
        stop.set()
        waiter.join(5)
    assert time.monotonic() - t0 < 5
    msg = str(e.value)
    assert "tests/x.py::test_that_waits ran past its limit of 0.2 s" in msg
    # The main thread's stack, down to the wait, and the other thread's.
    assert "Current thread 0x" in msg and "test_a_wait_past_the_limit" in msg
    assert "Thread 0x" in msg and "a_thread_that_waits" in msg
    assert signal.getsignal(signal.SIGALRM) is handler
    after, _ = signal.getitimer(signal.ITIMER_REAL)
    assert 0 < after <= left
    # And a body that ends in time leaves the same behind it.
    with conftest.time_limit(0.2, "x"):
        pass
    time.sleep(0.3)
    assert signal.getsignal(signal.SIGALRM) is handler


def test_serving_tier_modules_compile():
    """The multi-engine serving tier must byte-compile: the router,
    replica, and process-fleet modules are imported by the serving
    package (so a syntax error takes the whole server down at import
    time), and the CPU-runnable benches that write perf/ROUTER.json
    and perf/FLEET.json ride along (repo convention: perf harnesses
    fail tier-1, not a chip run)."""
    import os
    import subprocess
    import sys

    root = os.path.join(os.path.dirname(__file__), "..")
    targets = [
        os.path.join(root, "triton_distributed_tpu", "serving",
                     "router.py"),
        os.path.join(root, "triton_distributed_tpu", "serving",
                     "replica.py"),
        os.path.join(root, "triton_distributed_tpu", "serving",
                     "remote.py"),
        os.path.join(root, "triton_distributed_tpu", "serving",
                     "supervisor.py"),
        os.path.join(root, "triton_distributed_tpu", "serving",
                     "run_server.py"),
        os.path.join(root, "triton_distributed_tpu", "models",
                     "stub.py"),
        os.path.join(root, "perf", "router_bench.py"),
        os.path.join(root, "perf", "fleet_bench.py"),
    ]
    proc = subprocess.run(
        [sys.executable, "-m", "compileall", "-q", "-f", *targets],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, (
        f"serving-tier modules failed to compile:\n"
        f"{proc.stdout}\n{proc.stderr}"
    )


def test_migration_modules_compile():
    """ISSUE-10: the slot-migration stack must byte-compile — the
    portable-slot-state module is imported by the continuous engine's
    admission path (a syntax error takes serving down at import time),
    and the CPU-runnable bench that writes perf/MIGRATION.json rides
    along (repo convention: perf harnesses fail tier-1, not a chip
    run)."""
    import os
    import subprocess
    import sys

    root = os.path.join(os.path.dirname(__file__), "..")
    targets = [
        os.path.join(root, "triton_distributed_tpu", "models",
                     "slot_state.py"),
        os.path.join(root, "triton_distributed_tpu", "models",
                     "continuous.py"),
        os.path.join(root, "triton_distributed_tpu", "models",
                     "stub.py"),
        os.path.join(root, "perf", "migration_bench.py"),
    ]
    proc = subprocess.run(
        [sys.executable, "-m", "compileall", "-q", "-f", *targets],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, (
        f"slot-migration modules failed to compile:\n"
        f"{proc.stdout}\n{proc.stderr}"
    )


def test_kv_quant_modules_compile():
    """The quantized-KV stack must byte-compile: the scale-aware pool,
    the dequantizing attention kernels, and the CPU-runnable bench that
    writes perf/KV_QUANT.json (run ad-hoc like the other perf
    harnesses — a syntax error must fail tier-1, not a chip run)."""
    import os
    import subprocess
    import sys

    root = os.path.join(os.path.dirname(__file__), "..")
    targets = [
        os.path.join(root, "triton_distributed_tpu", "models",
                     "paged_kv_cache.py"),
        os.path.join(root, "triton_distributed_tpu", "ops", "attention",
                     "flash_decode.py"),
        os.path.join(root, "triton_distributed_tpu", "ops", "attention",
                     "flash_attention.py"),
        os.path.join(root, "perf", "kv_quant_bench.py"),
    ]
    proc = subprocess.run(
        [sys.executable, "-m", "compileall", "-q", "-f", *targets],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, (
        f"kv-quant modules failed to compile:\n{proc.stdout}\n{proc.stderr}"
    )


def test_mega_serve_modules_compile():
    """The megakernel serving fast path must byte-compile: the fused
    int8/sampling/overlap decode modules are imported by both engines
    (a syntax error takes serving down at import time), and the
    CPU-runnable bench that writes perf/MEGA_SERVE.json rides along
    (repo convention: perf harnesses fail tier-1, not a chip
    run)."""
    import os
    import subprocess
    import sys

    root = os.path.join(os.path.dirname(__file__), "..")
    targets = [
        os.path.join(root, "triton_distributed_tpu", "megakernel"),
        os.path.join(root, "triton_distributed_tpu", "models",
                     "continuous.py"),
        os.path.join(root, "perf", "mega_serve_bench.py"),
    ]
    proc = subprocess.run(
        [sys.executable, "-m", "compileall", "-q", "-f", *targets],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, (
        f"mega-serve modules failed to compile:\n"
        f"{proc.stdout}\n{proc.stderr}"
    )


def test_moe_serving_modules_compile():
    """ISSUE-11: the MoE serving fast path must byte-compile — the
    routed-expert model/layer/ops stack, the megakernel's MoE task
    modules, and the CPU-runnable bench that writes
    perf/MOE_SERVE.json (repo convention: perf harnesses fail tier-1,
    not a chip run)."""
    import os
    import subprocess
    import sys

    root = os.path.join(os.path.dirname(__file__), "..")
    targets = [
        os.path.join(root, "triton_distributed_tpu", "models",
                     "qwen_moe.py"),
        os.path.join(root, "triton_distributed_tpu", "layers",
                     "tp_moe.py"),
        os.path.join(root, "triton_distributed_tpu", "ops", "moe"),
        os.path.join(root, "triton_distributed_tpu", "megakernel"),
        os.path.join(root, "perf", "moe_serve_bench.py"),
    ]
    proc = subprocess.run(
        [sys.executable, "-m", "compileall", "-q", "-f", *targets],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, (
        f"MoE serving modules failed to compile:\n"
        f"{proc.stdout}\n{proc.stderr}"
    )


def test_kv_tier_modules_compile():
    """ISSUE-12: the durable KV tier must byte-compile — the PageStore
    subsystem, the tier-aware prefix cache / continuous engine /
    supervisor wiring, and the CPU-runnable bench that writes
    perf/KV_TIER.json (repo convention: perf harnesses fail tier-1,
    not a chip run)."""
    import os
    import subprocess
    import sys

    root = os.path.join(os.path.dirname(__file__), "..")
    targets = [
        os.path.join(root, "triton_distributed_tpu", "models",
                     "kv_tier.py"),
        os.path.join(root, "triton_distributed_tpu", "models",
                     "prefix_cache.py"),
        os.path.join(root, "triton_distributed_tpu", "models",
                     "continuous.py"),
        os.path.join(root, "triton_distributed_tpu", "serving",
                     "supervisor.py"),
        os.path.join(root, "perf", "kv_tier_bench.py"),
    ]
    proc = subprocess.run(
        [sys.executable, "-m", "compileall", "-q", "-f", *targets],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, (
        f"KV tier modules failed to compile:\n"
        f"{proc.stdout}\n{proc.stderr}"
    )


def test_kv_fabric_modules_compile():
    """ISSUE-17: the KV fabric must byte-compile — the fabric client /
    wire peers (kv_tier.py), the suite itself, and the CPU-runnable
    bench that writes perf/KV_FABRIC.json (repo convention: perf
    harnesses fail tier-1, not a chip run)."""
    import os
    import subprocess
    import sys

    root = os.path.join(os.path.dirname(__file__), "..")
    targets = [
        os.path.join(root, "triton_distributed_tpu", "models",
                     "kv_tier.py"),
        os.path.join(root, "tests", "test_kv_fabric.py"),
        os.path.join(root, "perf", "kv_fabric_bench.py"),
    ]
    proc = subprocess.run(
        [sys.executable, "-m", "compileall", "-q", "-f", *targets],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, (
        f"KV fabric modules failed to compile:\n"
        f"{proc.stdout}\n{proc.stderr}"
    )


def test_tree_speculation_modules_compile():
    """ISSUE-16: every layer the tree-speculation path threads through
    must byte-compile — the drafter/verifier, the radix proposer, the
    row-move commit, the biased flash kernel and its model plumbing,
    both engines, and the CPU-runnable bench that writes
    perf/SPEC_DECODE.json (repo convention: perf harnesses fail
    tier-1, not a chip run)."""
    import os
    import subprocess
    import sys

    root = os.path.join(os.path.dirname(__file__), "..")
    targets = [
        os.path.join(root, "triton_distributed_tpu", "models",
                     "speculative.py"),
        os.path.join(root, "triton_distributed_tpu", "models",
                     "prefix_cache.py"),
        os.path.join(root, "triton_distributed_tpu", "models",
                     "paged_kv_cache.py"),
        os.path.join(root, "triton_distributed_tpu", "models",
                     "qwen.py"),
        os.path.join(root, "triton_distributed_tpu", "models",
                     "engine.py"),
        os.path.join(root, "triton_distributed_tpu", "models",
                     "continuous.py"),
        os.path.join(root, "triton_distributed_tpu", "layers",
                     "tp_attn.py"),
        os.path.join(root, "triton_distributed_tpu", "ops", "attention",
                     "flash_attention.py"),
        os.path.join(root, "perf", "spec_decode_bench.py"),
        os.path.join(root, "perf", "loadgen.py"),
    ]
    proc = subprocess.run(
        [sys.executable, "-m", "compileall", "-q", "-f", *targets],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, (
        f"tree-speculation modules failed to compile:\n"
        f"{proc.stdout}\n{proc.stderr}"
    )


def test_serving_cli_speculative_mega_conflict(capsys):
    """Both serving CLIs refuse --speculative with --mode mega by flag
    name, BEFORE loading a model (argparse error → SystemExit 2) — for
    EVERY --model spelling, including the ones whose name resolution
    used to run first and die on a missing checkpoint instead of the
    named-flag message (ISSUE-16 satellite). The refusal text names
    the actual conflicting pair. The spec-string parser round-trips
    the new overlap_ar field."""
    import os
    import sys

    import pytest

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from perf import serve_demo
    from triton_distributed_tpu.serving import run_server

    for main in (serve_demo.main, run_server.main):
        for extra in ([], ["--model", "moe"], ["--model", "stub"]):
            with pytest.raises(SystemExit) as ei:
                main([*extra, "--speculative", "2", "--mode", "mega"])
            assert ei.value.code == 2  # argparse p.error exit code
            err = capsys.readouterr().err
            assert "--speculative and --mode mega" in err, err

    from triton_distributed_tpu.megakernel.code_generator import MegaConfig

    cfg = MegaConfig(tile_n=512, nbuf=3, fuse_norms=True,
                     cross_prefetch=True, overlap_ar=True)
    assert MegaConfig.from_spec(cfg.spec()) == cfg
    # Old 5-field strings (pre-overlap_ar MEGA_TUNED.json) still parse.
    old = MegaConfig.from_spec("1024:1024:2:1:0")
    assert old.overlap_ar is False and old.fuse_norms is True


def test_serving_cli_tier_flags_require_continuous_stack():
    """Both serving CLIs refuse --tier-bytes/--tier-dir on paths that
    would silently ignore them (the plain fixed-batch Engine, the
    single stub server) by flag name, BEFORE loading a model — the
    speculative×mega fail-fast convention (docs/serving.md 'Tiered
    KV')."""
    import os
    import sys

    import pytest

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from perf import serve_demo
    from triton_distributed_tpu.serving import run_server

    for main in (serve_demo.main, run_server.main):
        for flags in (["--tier-bytes", "1048576"],
                      ["--tier-dir", "/tmp/nope.tier"]):
            with pytest.raises(SystemExit) as ei:
                main(flags)
            assert ei.value.code == 2  # argparse p.error exit code
    # The single-stub server has no tier either (fleet stub children
    # ride the supervisor's resume_dir instead).
    with pytest.raises(SystemExit) as ei:
        run_server.main(["--model", "stub", "--tier-bytes", "1048576"])
    assert ei.value.code == 2

def test_serving_cli_tier_shared_guardrails(capsys):
    """Both serving CLIs refuse every --tier-shared combination that
    would silently do nothing (single engine, stub fleet, process
    fleet without a common dir, threaded replicas without a tier) by
    flag name, BEFORE loading a model — the PR 12 tier-flag
    convention (docs/scale-out.md 'KV fabric')."""
    import os
    import sys

    import pytest

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from perf import serve_demo
    from triton_distributed_tpu.serving import run_server

    cases = (
        # One engine: nothing to share.
        ["--tier-shared", "--tier-bytes", "1048576"],
        # Stub fleet children have no KV tier at all.
        ["--model", "stub", "--fleet", "2", "--tier-shared"],
        # Separate processes share through DISK: --tier-dir required.
        ["--fleet", "2", "--tier-shared", "--tier-bytes", "1048576"],
        # Threaded replicas still need a tier to share.
        ["--replicas", "2", "--tier-shared"],
    )
    for main in (serve_demo.main, run_server.main):
        for flags in cases:
            with pytest.raises(SystemExit) as ei:
                main(flags)
            assert ei.value.code == 2, flags  # argparse p.error
            err = capsys.readouterr().err
            assert "--tier-shared" in err, (flags, err)
