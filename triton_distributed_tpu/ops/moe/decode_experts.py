"""A decode step's routed experts, read in place from the stacked weights.

A decode step sends an expert-parallel rank a few rows an expert: of the
``held`` experts it holds, the step's live rows chose some, and only
those experts' weights are worth reading. The caller hands in the gate
``[T, held]`` (a row's routing weight for each held expert, nought where
the row did not choose it) and the list of the experts some live row
chose; the kernel walks ONE dynamic grid axis of ``n x tiles`` steps
(PR 33's technique, :func:`paged_decode_walk`), and step ``(i, j)``
fetches column tile ``j`` of expert ``touched[i]``'s gate, up and down
projections of THIS layer straight from the stacked ``w1 [layers, held,
d, 2 f]`` / ``w2 [layers, held, f, d]`` seen as ``[layers * held, ...]``
(the layer rides the index map, as the page table carries it in
:func:`mla_paged_decode`): nothing is sliced or copied out first, and an
expert no live row chose costs no byte. ``x [T, d]`` and the float32
``y [T, d]`` stay in VMEM over the whole grid.

The arithmetic is the gate-weighted einsum's: ``h = x @ w1_e`` in
float32 rounded to the served dtype, SwiGLU, ``@ w2_e`` in float32,
times the gate column, summed over the touched experts in ascending
order in float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_distributed_tpu.ops.common import exporting_portable, interpret_mode

# Columns of ``f`` a grid step takes through both projections: three
# tiles of ``d x tile`` (gate, up, down), double-buffered. On the v5e at
# the served widths 128, 256 and 512 stream within 1% of each other
# (744 / 755 / 753 GB/s: PERF.md "PR 36").
F_TILE = 256
_ROWS = 16  # a bf16 tile's sublanes: rows are padded to a multiple


def touched_experts(chosen: jax.Array):
    """``chosen [held]`` bool to ``(touched [held] int32, n)``: the
    chosen experts in ascending order, then the others (in range, never
    read), and how many are chosen."""
    order = jnp.argsort(~chosen, stable=True).astype(jnp.int32)
    return order, jnp.sum(chosen).astype(jnp.int32)


def _decode_experts_kernel(
    touched_ref,  # [held] int32 SMEM (scalar prefetch): the index maps' too
    n_ref,        # [1] int32 SMEM: how many of them are touched
    layer_ref,    # [1] int32 SMEM: consumed by the index maps
    x_ref,        # [T, d]
    g_ref,        # [1, T, 1] f32: the expert's gate column
    w1g_ref,      # [1, d, tile]: gate columns of the expert's w1
    w1u_ref,      # [1, d, tile]: up columns
    w2_ref,       # [1, tile, d]
    y_ref,        # [T, d] f32: resident over the whole grid
):
    @pl.when(pl.program_id(0) == 0)
    def _first_step():
        y_ref[...] = jnp.zeros_like(y_ref)

    # With no expert touched the grid still has its one step, which
    # writes the zeros (and whose fetched tile is not looked at).
    @pl.when(n_ref[0] > 0)
    def _accumulate():
        x = x_ref[...]
        gate = jnp.dot(x, w1g_ref[0], preferred_element_type=jnp.float32)
        up = jnp.dot(x, w1u_ref[0], preferred_element_type=jnp.float32)
        # Rounded to the served dtype before the SwiGLU, as the layer's
        # other feed-forwards round ``x @ w1`` (``_silu_mul``).
        act = (
            jax.nn.silu(gate.astype(x.dtype).astype(jnp.float32))
            * up.astype(x.dtype).astype(jnp.float32)
        ).astype(x.dtype)
        y_ref[...] += g_ref[0] * jnp.dot(
            act, w2_ref[0], preferred_element_type=jnp.float32)


def moe_decode_experts_reference(x, gate, w1, w2):
    """Every expert of ONE layer (``w1 [held, d, 2 f]``) on every row,
    weighted by the gate: the golden of :func:`moe_decode_experts` and
    its portable-export path (it reads all the weights)."""
    h = jnp.einsum("td,edf->etf", x, w1,
                   preferred_element_type=jnp.float32).astype(x.dtype)
    g, u = jnp.split(h, 2, axis=-1)
    act = (jax.nn.silu(g.astype(jnp.float32))
           * u.astype(jnp.float32)).astype(x.dtype)
    h = jnp.einsum("etf,efd->etd", act, w2,
                   preferred_element_type=jnp.float32)
    return jnp.einsum("etd,te->td", h, gate)


def moe_decode_experts(
    x: jax.Array,      # [T, d]
    gate: jax.Array,   # [T, held] f32, nought where a row did not choose
    touched: jax.Array,  # [held] int32: touched_experts(...)
    n: jax.Array,        # int32: how many of ``touched`` count
    w1: jax.Array,     # [layers, held, d, 2 f] gate|up, or one layer's 3-D
    w2: jax.Array,     # [layers, held, f, d]
    *,
    layer: jax.Array | int | None = None,  # which layer of 4-D weights
    interpret=None,
) -> jax.Array:
    """``y [T, d]`` float32: ``sum_e gate[:, e] * FFN_e(x)`` over the
    experts ``touched[:n]`` of ``layer``, whose weights are the only
    ones read (every ``gate`` column outside them must be nought)."""
    t, d = x.shape
    if (w1.ndim == 4) != (layer is not None):
        raise ValueError(
            "stacked 4-D weights need layer=, one layer's 3-D take none "
            f"(w1 rank {w1.ndim}, layer {layer!r})"
        )
    if layer is None:
        w1, w2, layer = w1[None], w2[None], 0
    layers, held, f = w2.shape[:3]
    layer = jnp.asarray(layer, jnp.int32)
    resolved = interpret_mode() if interpret is None else interpret
    if resolved and exporting_portable():
        return moe_decode_experts_reference(x, gate, w1[layer], w2[layer])
    w1, w2 = (w.reshape(layers * held, *w.shape[2:]) for w in (w1, w2))
    tile = F_TILE if f % F_TILE == 0 else f
    tiles = f // tile
    rows = -(-t // _ROWS) * _ROWS
    if rows != t:
        x = jnp.pad(x, ((0, rows - t), (0, 0)))
        gate = jnp.pad(gate, ((0, rows - t), (0, 0)))

    def expert(s, touched, n, layer):
        return layer[0] * held + touched[s // tiles]

    itemsize = jnp.dtype(w1.dtype).itemsize
    y = pl.pallas_call(
        _decode_experts_kernel,
        name="tdt_moe_decode_experts",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(jnp.maximum(n * tiles, 1),),
            in_specs=[
                pl.BlockSpec((rows, d), lambda s, *_: (0, 0)),
                pl.BlockSpec(
                    (1, rows, 1),
                    lambda s, touched, *_: (touched[s // tiles], 0, 0)),
                pl.BlockSpec(
                    (1, d, tile), lambda s, *p: (expert(s, *p), 0, s % tiles)),
                pl.BlockSpec(
                    (1, d, tile),
                    lambda s, *p: (expert(s, *p), 0, tiles + s % tiles)),
                pl.BlockSpec(
                    (1, tile, d), lambda s, *p: (expert(s, *p), s % tiles, 0)),
            ],
            out_specs=pl.BlockSpec((rows, d), lambda s, *_: (0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # Three weight tiles, double-buffered, beside x, y and the
            # product's float32 rows (22 MB of tiles at the served
            # widths: over the compiler's default of 16).
            vmem_limit_bytes=int(
                6 * d * tile * itemsize + 8 * rows * d * 4 + (8 << 20)),
        ),
        interpret=resolved,
    )(touched, jnp.reshape(n, (1,)).astype(jnp.int32), jnp.reshape(layer, (1,)),
      x, gate.T[:, :, None], w1, w1, w2)
    return y[:t]
