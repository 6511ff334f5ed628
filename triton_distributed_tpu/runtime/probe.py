"""Hardware probes: measured HBM / ICI bandwidth + topology summary.

Parity: reference ``utils.py:592-867`` — NVLink full-mesh detection,
link-speed and PCIe-bandwidth probes, NUMA maps — which feed its perf
models and method dispatch. The TPU analog measures what the hardware
actually delivers (driver and DVFS shave the datasheet number) and
reports it alongside the static :class:`ChipSpec` and the detected
:class:`MeshTopology`.

Timing: every iteration is data-dependent on the previous one inside a
single jit (so the optimizer cannot hoist or fold the work), the fence
is a host fetch (the clock stops only when the device has finished),
and the statistic is a median over reps.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from triton_distributed_tpu.runtime.mesh import DistContext, current_context
from triton_distributed_tpu.runtime.utils import median_time as _median_time


def measure_hbm_bandwidth_gbs(
    nbytes: int = 256 * 1024 * 1024, iters: int = 32, device=None
) -> float:
    """Measured HBM copy bandwidth (read + write counted) in GB/s.

    A call has a fixed cost (dispatch, the host fetch) that a single
    timed call folds into the bandwidth; timing ``iters`` and
    ``2 * iters`` and differencing cancels every per-call constant.
    """
    n = nbytes // 4
    x = jnp.arange(n, dtype=jnp.float32)
    if device is not None:
        x = jax.device_put(x, device)

    @functools.partial(jax.jit, static_argnums=1)
    def chained(x, m):
        def body(_, acc):
            # A full read + write of nbytes, chained iteration to
            # iteration by the sub-ulp add.
            return acc + 1e-30

        return jnp.sum(jax.lax.fori_loop(0, m, body, x)[::4096])

    t1 = _median_time(lambda: np.asarray(chained(x, iters)))
    t2 = _median_time(lambda: np.asarray(chained(x, 2 * iters)))
    dt = max(t2 - t1, 1e-9)
    return 2 * nbytes * iters / dt / 1e9


def measure_ici_bandwidth_gbs(
    axis: str = "tp",
    nbytes: int = 64 * 1024 * 1024,
    iters: int = 8,
    ctx: DistContext | None = None,
) -> float:
    """Measured per-link ICI bandwidth via a ring ``ppermute`` chain.

    Each iteration shifts ``nbytes`` to the ring neighbor; with every
    device sending concurrently the timed rate is one link's one-way
    bandwidth. On a CPU simulator mesh this measures memcpy, not ICI —
    meaningful only on real multi-chip hardware; single-chip meshes
    return 0.0 (nothing to permute).
    """
    ctx = ctx or current_context()
    n_dev = ctx.axis_size(axis)
    if n_dev < 2:
        return 0.0
    perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]
    x = jnp.arange(nbytes // 4, dtype=jnp.float32)

    from jax.sharding import PartitionSpec as P

    def make(m):
        def body_fn(x):
            def body(_, acc):
                y = jax.lax.ppermute(acc, axis, perm)
                return y + 1e-30  # chain iterations

            return jnp.sum(jax.lax.fori_loop(0, m, body, x)[::4096])

        return jax.jit(ctx.shard_map(body_fn, in_specs=(P(),), out_specs=P()))

    xs = ctx.replicate(x)
    f1, f2 = make(iters), make(2 * iters)
    # Difference two iteration counts: cancels fixed per-call cost.
    t1 = _median_time(lambda: np.asarray(f1(xs)))
    t2 = _median_time(lambda: np.asarray(f2(xs)))
    dt = max(t2 - t1, 1e-9)
    return nbytes * iters / dt / 1e9


def probe_topology(ctx: DistContext | None = None) -> dict[str, Any]:
    """Topology + spec summary (reference's probe-suite report analog).

    Static facts come from :class:`MeshTopology` (device coords) and
    :func:`chip_spec` (datasheet); ``measured`` adds the live HBM probe
    on TPU. Off TPU there is no chip to know: the spec is then the v5e's
    by name, and ``chip_attached`` says so. Keys are stable for
    logging/JSON.
    """
    from triton_distributed_tpu.tools.perf_model import chip_spec

    ctx = ctx or current_context()
    topo = ctx.topology
    spec = chip_spec(
        ctx.mesh.devices.flat[0].device_kind if topo.on_tpu else "v5e"
    )
    out = {
        "mesh": {k: int(v) for k, v in ctx.mesh.shape.items()},
        "platform": topo.platform,
        "chip": spec.name,
        "chip_attached": topo.on_tpu,
        "torus_shape": topo.torus_shape,
        "has_wraparound": topo.has_wraparound,
        "num_processes": topo.num_processes,
        "multi_slice": topo.multi_slice,
        "spec": {
            "bf16_tflops": spec.bf16_tflops,
            "hbm_gbs": spec.hbm_gbs,
            "ici_gbs_per_link": spec.ici_gbs_per_link,
            "ici_links": spec.ici_links,
            "dcn_gbs": spec.dcn_gbs,
        },
    }
    if topo.on_tpu:
        out["measured"] = {
            "hbm_gbs": round(measure_hbm_bandwidth_gbs(), 1),
        }
    return out
