"""Host-side utilities: rank-filtered printing, timing, seeding, tolerances.

Reference parity: ``python/triton_dist/utils.py`` —
``perf_func``:274, ``dist_print``:289, ``init_seed``:77,
``assert_allclose``:870-899, ``sleep_async``:1018.
"""

from __future__ import annotations

import contextlib
import os
import random
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np


def init_seed(seed: int = 42) -> jax.Array:
    """Deterministic seeding across python/numpy + a jax PRNG key.

    Parity: reference ``init_seed`` (utils.py:77-96) which seeds torch /
    cuda / numpy / random for reproducible multi-rank tests. JAX is
    functional: we seed the host RNGs and hand back a key.
    """
    random.seed(seed)
    np.random.seed(seed)
    return jax.random.key(seed)


def dist_print(*args, prefix: bool = True, allowed_ranks="0", **kwargs) -> None:
    """Print only on the allowed process ranks (parity: utils.py:289-318).

    ``allowed_ranks`` is "all" or an int-list/comma string of process
    indices. On single-process meshes rank is always 0.
    """
    rank = jax.process_index()
    if allowed_ranks != "all":
        if isinstance(allowed_ranks, str):
            allowed = {int(r) for r in allowed_ranks.split(",") if r != ""}
        else:
            allowed = {int(r) for r in allowed_ranks}
        if rank not in allowed:
            return
    if prefix:
        print(f"[rank {rank}]", *args, **kwargs)
    else:
        print(*args, **kwargs)


def perf_func(
    func: Callable[[], object],
    iters: int = 10,
    warmup_iters: int = 5,
) -> tuple[object, float]:
    """Time a thunk, returning (last_output, mean_ms).

    Parity: reference ``perf_func`` (utils.py:274-287) which uses CUDA
    events around a stream; on TPU we block on the returned arrays
    (``jax.block_until_ready``) which is the dispatch-queue analog.
    """
    def _sync(out):
        # Fetching bytes to the host is a fence no backend can answer
        # early: the value has to exist. Pull one element per output leaf.
        leaves = [x for x in jax.tree_util.tree_leaves(out) if hasattr(x, "ravel")]
        if leaves:
            jax.device_get([x.ravel()[:1] for x in leaves])

    output = None
    for _ in range(warmup_iters):
        output = func()
    _sync(output)
    start = time.perf_counter()
    for _ in range(iters):
        output = func()
    _sync(output)
    elapsed_ms = (time.perf_counter() - start) * 1e3 / max(iters, 1)
    return output, elapsed_ms


def median_time(run: Callable[[], object], reps: int = 5) -> float:
    """Median wall-time (seconds) of ``run()`` over ``reps`` calls after
    one warmup. ``run`` must fence its own device work (host fetch).

    Median, not min: one call's device work can leak into the next
    measurement window (asynchronous dispatch, a host that shares its
    cores), and min() latches onto the impossibly-fast rep that results.
    Shared by bench.py and runtime/probe.py.
    """
    run()  # warm (compile on first use)
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        run()
        ts.append(time.perf_counter() - t0)
    return sorted(ts)[len(ts) // 2]


def assert_allclose(x, y, atol=1e-3, rtol=1e-3, verbose: bool = True) -> None:
    """Tolerant comparison with a mismatch report (parity: utils.py:870-899)."""
    x = np.asarray(jax.device_get(x), dtype=np.float64)
    y = np.asarray(jax.device_get(y), dtype=np.float64)
    if x.shape != y.shape:
        raise AssertionError(f"shape mismatch {x.shape} vs {y.shape}")
    close = np.isclose(x, y, atol=atol, rtol=rtol)
    if close.all():
        return
    mismatch = (~close).sum()
    frac = mismatch / close.size
    idx = np.unravel_index(np.argmax(np.abs(x - y)), x.shape)
    raise AssertionError(
        f"{mismatch}/{close.size} ({frac:.2%}) mismatched "
        f"(atol={atol}, rtol={rtol}); worst at {idx}: {x[idx]} vs {y[idx]}"
        + (f"\n x={x}\n y={y}" if verbose and x.size <= 64 else "")
    )


def sleep_async(ms: float):
    """Straggler injection: return a delay thunk to run before a collective.

    Parity: reference ``sleep_async`` (utils.py:1018-1031) which launches a
    spin-kernel on the stream. On TPU we cannot spin a device core from
    Python cheaply, so straggler injection is host-side sleep before
    dispatch — it skews this rank's arrival the same way. Kernels with a
    ``straggler_option`` use ``pl.delay`` on-device instead.
    """

    def _delay():
        time.sleep(ms / 1e3)

    return _delay


@contextlib.contextmanager
def with_env(**env: str):
    """Temporarily set environment variables (test helper)."""
    old = {k: os.environ.get(k) for k in env}
    os.environ.update({k: str(v) for k, v in env.items()})
    try:
        yield
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def bytes_of(tree) -> int:
    """Total bytes of a pytree of arrays (for bandwidth reporting)."""
    leaves = jax.tree_util.tree_leaves(tree)
    return sum(x.size * x.dtype.itemsize for x in leaves if hasattr(x, "dtype"))


def to_bf16(tree):
    return jax.tree.map(
        lambda x: x.astype(jnp.bfloat16)
        if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating)
        else x,
        tree,
    )
