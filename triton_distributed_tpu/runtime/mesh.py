"""Device mesh, topology, and distributed initialization.

Reference parity: ``python/triton_dist/utils.py:182-205``
(``initialize_distributed``: torchrun env → process group → NVSHMEM uid init)
and the NVLink/PCIe/NUMA topology probes (``utils.py:592-867``).

TPU-native design: there is no NVSHMEM symmetric heap to map — the data plane
is the ICI mesh that XLA already knows about. "Initialization" therefore means:

1. (multi-host only) ``jax.distributed.initialize`` — the control-plane
   rendezvous, analog of ``torch.distributed.init_process_group``.
2. Building a named ``jax.sharding.Mesh`` over the device grid with the
   parallelism axes the caller asks for (dp/pp/tp/sp/ep), in an order that
   keeps the fastest-varying (most-communicating) axes on contiguous ICI
   neighbors.
3. Recording topology facts kernels need (axis sizes, ring neighbors,
   whether we are on real TPU or the CPU simulator) — the analog of the
   reference's NVLink fullmesh/NUMA probes, except on TPU the answer comes
   from the platform, not from sysfs crawling.

Symmetric memory: the reference allocates NVSHMEM symmetric tensors
(``utils.py:114-136``). In JAX the same thing is an identically-shaped
per-device shard inside ``shard_map`` — every device holds the same local
shape at the same logical name, and Pallas remote DMAs address peers by mesh
index. No allocator is needed; ``DistContext.shard_map`` is the entry point.
"""

from __future__ import annotations

import dataclasses
import functools
import os
from typing import Any, Callable, Mapping, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

shard_map = jax.shard_map

# Canonical axis names, outermost (least communication) to innermost
# (most communication → contiguous ICI). Mirrors the scaling-book recipe:
# data axes outside, model axes inside.
# dcn (cross-slice) outermost; tp innermost (contiguous ICI neighbors).
AXIS_ORDER = ("dcn", "dp", "pp", "ep", "sp", "tp")


@dataclasses.dataclass(frozen=True)
class MeshTopology:
    """Static facts about the device grid a kernel may want.

    Analog of the reference's topology probe results (nvlink fullmesh,
    NUMA grouping — ``utils.py:592-867``): on TPU the useful facts are the
    ICI axis structure and whether multiple slices (DCN hops) are involved.
    """

    num_devices: int
    num_processes: int
    process_index: int
    platform: str  # "tpu" | "cpu" | ...
    devices_per_process: int
    torus_shape: tuple[int, ...] | None = None  # physical ICI grid dims
    has_wraparound: bool | None = None  # any torus dim with wrap links
    # Which rung of ``_tpu_device_grid`` arranged the mesh (TPU only).
    mesh_rung: str | None = None

    @property
    def on_tpu(self) -> bool:
        return self.platform == "tpu"

    @property
    def multi_slice(self) -> bool:
        """True when the mesh spans a DCN boundary (multi-process TPU)."""
        return self.num_processes > 1


class DistContext:
    """Global distributed context: mesh + axis layout + topology.

    The analog of the reference's ``initialize_distributed()`` return state
    (process groups + NVSHMEM heap). Everything downstream (collectives,
    overlap kernels, model layers) takes a ``DistContext`` the way the
    reference ops take their per-op ``*Context`` dataclasses.
    """

    def __init__(self, mesh: Mesh, topology: MeshTopology):
        self.mesh = mesh
        self.topology = topology

    # -- identity ---------------------------------------------------------
    @property
    def axis_names(self) -> tuple[str, ...]:
        return tuple(self.mesh.axis_names)

    def axis_size(self, axis: str) -> int:
        return self.mesh.shape[axis]

    @property
    def world_size(self) -> int:
        return int(np.prod(list(self.mesh.shape.values())))

    @property
    def on_tpu(self) -> bool:
        return self.topology.on_tpu

    def axis_is_ici(self, axis: str) -> bool:
        """True when neighbors along ``axis`` share a SLICE — i.e. the
        axis is reachable by device-initiated remote DMA (ICI). A
        DCN-spanning axis must use XLA collectives: DCN transfers are
        host-driven (SURVEY.md §7 "inter-slice paths can't be
        device-initiated"). AUTO method dispatchers consult this so a
        device-push kernel is never selected across a slice boundary.

        Slice identity comes from ``device.slice_index`` — ICI spans
        HOSTS inside one slice (a v4-32 has 4 processes and one
        all-ICI slice), so process boundaries must NOT be the signal.
        Devices without a ``slice_index`` attribute (CPU sim, older
        stacks) are treated as one slice."""
        devs = np.asarray(self.mesh.devices)
        ids = np.vectorize(
            lambda d: getattr(d, "slice_index", None) or 0
        )(devs)
        if (ids == ids.flat[0]).all():
            return True  # one slice: every axis is ICI
        return self._axis_within_group(ids, self.axis_names.index(axis))

    @staticmethod
    def _axis_within_group(ids: "np.ndarray", ax_i: int) -> bool:
        """Pure check: every move along mesh dim ``ax_i`` stays inside
        one slice-id group (split out so the DCN/ICI classification is
        unit-testable without a real multi-slice mesh)."""
        moved = np.moveaxis(ids, ax_i, 0)
        return bool((moved == moved[0]).all())

    # -- pallas helpers ---------------------------------------------------
    def pallas_interpret(self):
        """Interpret-mode params for Pallas on non-TPU backends.

        On the CPU simulator mesh, Pallas TPU kernels (including remote
        DMAs and semaphores) run under ``pltpu.InterpretParams`` with full
        TPU memory semantics; on real TPU this returns False so kernels
        compile through Mosaic.
        """
        if self.on_tpu:
            return False
        from jax.experimental.pallas import tpu as pltpu

        return pltpu.InterpretParams()

    # -- shard_map entry point -------------------------------------------
    def shard_map(
        self,
        f: Callable,
        in_specs: Any,
        out_specs: Any,
        check_vma: bool = False,
    ) -> Callable:
        """Wrap ``f`` in a ``shard_map`` over this mesh.

        This is the "symmetric memory" entry point: inside ``f`` every
        device sees its local shard and may address peers via Pallas remote
        DMA or ``jax.lax`` collectives by axis name.
        """
        return shard_map(
            f,
            mesh=self.mesh,
            in_specs=in_specs,
            out_specs=out_specs,
            check_vma=check_vma,
        )

    # -- teams / sub-groups ----------------------------------------------
    def split_axis(
        self,
        axis: str,
        names: tuple[str, str],
        sizes: tuple[int, int],
        *,
        set_as_current: bool = False,
    ) -> "DistContext":
        """Split a mesh axis into two (parity: NVSHMEM team split —
        ``nvshmem_team_split_strided`` / ``team_my_pe``,
        ``libnvshmem_device.py:130,1343``, ``test_team_split.py``).

        A rank's ids along the new axes are ``(old // sizes[1],
        old % sizes[1])`` — the strided/round-robin split of the
        reference's 2D protocols (NUMA-aware ring, 2D allgather).
        Collectives and remote DMAs then target either sub-axis by name.
        """
        if sizes[0] * sizes[1] != self.axis_size(axis):
            raise ValueError(
                f"split {sizes} does not cover axis {axis!r} of size "
                f"{self.axis_size(axis)}"
            )
        idx = self.mesh.axis_names.index(axis)
        new_names = (
            self.mesh.axis_names[:idx] + names
            + self.mesh.axis_names[idx + 1:]
        )
        shape = self.mesh.devices.shape
        new_shape = shape[:idx] + sizes + shape[idx + 1:]
        ctx = DistContext(
            Mesh(self.mesh.devices.reshape(new_shape), new_names),
            self.topology,
        )
        if set_as_current:
            set_context(ctx)
        return ctx

    def sharding(self, *spec) -> NamedSharding:
        return NamedSharding(self.mesh, P(*spec))

    def replicate(self, x):
        return jax.device_put(x, self.sharding())

    def shard(self, x, *spec):
        return jax.device_put(x, self.sharding(*spec))


_CURRENT: DistContext | None = None


def set_context(ctx: DistContext | None) -> None:
    global _CURRENT
    _CURRENT = ctx


def current_context() -> DistContext:
    if _CURRENT is None:
        raise RuntimeError(
            "Distributed context not initialized; call "
            "triton_distributed_tpu.initialize_distributed() first."
        )
    return _CURRENT


def snake_ring_order(coords: np.ndarray) -> np.ndarray:
    """Permutation of device indices whose consecutive entries are physical
    ICI neighbors (boustrophedon walk of the torus).

    Parity role: the reference's topology probes (``utils.py:592-867``)
    answer "which ranks are one NVLink hop apart"; on TPU the analog is
    "which chips are one ICI hop apart", answered from device coords.
    Works for any full n-D grid; the closing hop (last → first) is also
    distance 1 whenever every inner dim is even (the usual torus case).
    """
    coords = np.asarray(coords)
    lo = coords.min(axis=0)
    sizes = coords.max(axis=0) - lo + 1
    norm = coords - lo

    def snake_key(c) -> int:
        key = 0
        for v, s in zip(c, sizes):
            vv = int(s) - 1 - int(v) if key % 2 else int(v)
            key = key * int(s) + vv
        return key

    return np.argsort([snake_key(c) for c in norm], kind="stable")


def _tpu_device_grid(
    devices: list[jax.Device], shape: tuple[int, ...]
) -> tuple[np.ndarray, str]:
    """Arrange TPU devices so the innermost mesh axis rides contiguous ICI.

    Returns the grid and the name of the rung that built it.
    ``jax.experimental.mesh_utils.create_device_mesh`` does the real
    assignment from physical coords; it requires the full device set of
    the slice. For subsets (or when it declines) the snake ring over
    coords keeps consecutive innermost-axis entries one-hop neighbours.
    There is no enumeration-order rung: on a 2x2 without wraparound it
    can put "ring neighbours" two hops apart, so when neither
    topology-aware rung can build the grid this raises with both
    reasons.
    """
    declined = []
    if len(devices) == len(jax.devices()):
        from jax.experimental import mesh_utils

        try:
            grid = mesh_utils.create_device_mesh(shape, devices=devices)
            return grid, "create_device_mesh"
        except (ValueError, NotImplementedError, AssertionError) as e:
            declined.append(f"create_device_mesh: {e}")
    try:
        coords = np.asarray([d.coords for d in devices])
        order = snake_ring_order(coords)
        return np.asarray(devices)[order].reshape(shape), "snake_ring"
    except (AttributeError, ValueError) as e:
        declined.append(f"snake_ring: {e}")
    raise RuntimeError(
        f"no topology-aware arrangement of {len(devices)} TPU devices "
        f"into {shape}: " + "; ".join(declined)
    )


def _detect_topology(
    devices: Sequence[jax.Device], mesh_rung: str | None = None
) -> MeshTopology:
    platform = devices[0].platform
    num_processes = jax.process_count()
    torus_shape = None
    has_wrap = None
    if platform == "tpu":
        try:
            coords = np.asarray([d.coords for d in devices])
            dims = tuple(int(x) for x in coords.max(0) - coords.min(0) + 1)
            torus_shape = dims
            kind = devices[0].device_kind.lower()
            if "v4" in kind or "v5p" in kind:
                # 3D-torus generations: wraparound links on dims >= 4.
                has_wrap = any(d >= 4 for d in dims)
            elif "lite" in kind or "v5e" in kind or "v6e" in kind:
                has_wrap = False  # 2D-mesh generations: no wrap links
            # else: unknown generation — leave None
        except AttributeError:
            pass  # a TPU device object without coords: facts stay None
    return MeshTopology(
        num_devices=len(devices),
        num_processes=num_processes,
        process_index=jax.process_index(),
        platform=platform,
        devices_per_process=max(1, len(devices) // num_processes),
        torus_shape=torus_shape,
        has_wraparound=has_wrap,
        mesh_rung=mesh_rung,
    )


def initialize_distributed(
    axes: Mapping[str, int] | None = None,
    *,
    tp: int | None = None,
    dp: int | None = None,
    pp: int | None = None,
    sp: int | None = None,
    ep: int | None = None,
    devices: Sequence[jax.Device] | None = None,
    multihost: bool | None = None,
    set_as_current: bool = True,
) -> DistContext:
    """Create the global mesh + context.

    Analog of reference ``initialize_distributed`` (``utils.py:182``):
    where the reference wires torchrun env vars → NCCL/gloo groups → NVSHMEM
    heap, we wire (optionally) ``jax.distributed.initialize`` → a named
    ``Mesh`` whose axes map onto ICI.

    Axis sizes may be given either as an ``axes`` mapping or via the
    keyword shorthands; unspecified parallelism consumes no axis. If the
    product is smaller than the device count, a ``dp`` axis absorbs the
    remainder (data parallelism is free on TPU — it is just a sharded
    leading axis).
    """
    if multihost is None:
        multihost = bool(int(os.environ.get("TDT_MULTIHOST", "0")))
    if multihost:
        # Control-plane rendezvous across hosts (DCN). Must run before any
        # JAX call that initializes an XLA backend, so we don't probe
        # jax.process_count() first; re-initialization raises and is ignored.
        try:
            jax.distributed.initialize()
        except RuntimeError:
            pass  # already initialized (or single-process run)

    if devices is None:
        devices = jax.devices()
    devices = list(devices)

    sizes: dict[str, int] = dict(axes or {})
    for name, val in (("tp", tp), ("dp", dp), ("pp", pp), ("sp", sp), ("ep", ep)):
        if val is not None:
            sizes[name] = val

    used = int(np.prod(list(sizes.values()))) if sizes else 1
    n = len(devices)
    if n % used != 0:
        raise ValueError(
            f"device count {n} not divisible by requested axes {sizes}"
        )
    if used < n and "dp" not in sizes:
        sizes = {"dp": n // used, **sizes}
    elif used < n:
        sizes["dp"] = sizes["dp"] * (n // used)

    # Order axes canonically: dp/pp outermost, tp innermost (contiguous ICI).
    ordered = [a for a in AXIS_ORDER if a in sizes]
    ordered += [a for a in sizes if a not in ordered]
    shape = tuple(sizes[a] for a in ordered)
    if not ordered:
        ordered, shape = ["dp"], (n,)

    mesh_rung = None
    if devices[0].platform == "tpu":
        dev_array, mesh_rung = _tpu_device_grid(devices, shape)
    else:
        dev_array = np.asarray(devices).reshape(shape)
    mesh = Mesh(dev_array, tuple(ordered))
    ctx = DistContext(mesh, _detect_topology(devices, mesh_rung))
    if set_as_current:
        set_context(ctx)
    return ctx


def finalize_distributed() -> None:
    """Tear down the global context (and multihost runtime if we own it)."""
    set_context(None)


@functools.lru_cache(maxsize=None)
def cpu_sim_devices(n: int) -> tuple[jax.Device, ...]:
    """Return ``n`` CPU devices for simulator meshes (tests, dry runs)."""
    cpus = jax.devices("cpu")
    if len(cpus) < n:
        raise RuntimeError(
            f"need {n} CPU devices; launch with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n}"
        )
    return tuple(cpus[:n])
