"""A decode step's recurrent state update, in place at (layer, slot).

A Mamba-2 layer keeps one state ``S [H, P, N]`` a slot (heads x head
dim x state dim, float32) whatever the context, and a decode step moves
it by one position:

    S <- exp(delta A) S + (delta x) (x) B        y = S C

(``delta``, ``A`` a number a head; ``x [H, P]``; ``B``, ``C`` ``[N]``,
shared by the heads; the skip ``D x`` is the caller's). The state is
the step's bytes: at 64 x 64 x 128 float32 a row reads and writes 2.1 MB
a layer, and nothing else of the update is worth counting.

The kernel walks ONE dynamic grid axis (PR 33's technique,
:func:`paged_decode_walk`) of ``n x H / HEADS`` steps over the rows IN
FLIGHT, a list the engine uploads with its tables (``rows[:n]``; the
page table cannot say which rows are in flight: a slot whose admission
is between two chunks is mapped and must not move). Step ``(i, j)``
takes heads ``[j HEADS, (j + 1) HEADS)`` of slot ``rows[i]`` of THIS
layer from the stacked state ``[layers, slots, H, P, N]`` seen as
``[layers * slots, H, P, N]`` (the layer rides the index map, as in
:func:`moe_decode_experts`), updates them and writes them back to the
same block: the state is aliased to the output, a row not in the list
costs no byte and keeps its state, and no row in flight is one step
that rewrites the block it read.

Inside a block a head's state is ``[P, N]``, ``N`` on the lanes. What
multiplies it a row at a time (``exp(delta A)`` and ``delta x``) comes
in TRANSPOSED, ``[slots, H / HEADS, P, HEADS]``: a head's column is
picked by a lane mask and summed to ``[P, 1]``, the shape a lane
broadcast takes; ``y`` leaves the same way.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_distributed_tpu.ops.common import exporting_portable, interpret_mode

# Heads a grid step takes: HEADS x P x N float32 in and out, each
# double-buffered (16 heads of 64 x 128: 0.5 MB a block). On the v5e 8,
# 16, 32 and 64 heads stream alike (0.253 ms a layer at 32 rows: 530
# GB/s of state read and written), and so do cheaper ways to pick a
# head's column or to read ``y`` out: the block's two streams are the
# time (PERF.md "PR 37").
HEADS = 16


def _ssm_decode_kernel(
    rows_ref,   # [slots] int32 SMEM (scalar prefetch): the rows in flight
    n_ref,      # [1] int32 SMEM: how many of them count
    layer_ref,  # [1] int32 SMEM: consumed by the index maps
    da_ref,     # [1, 1, P, hb] f32: exp(delta A), a head a lane
    dx_ref,     # [1, 1, P, hb] f32: delta x
    b_ref,      # [1, 1, N] f32
    c_ref,      # [1, 1, N] f32
    s_ref,      # [1, hb, P, N] f32: the block of the state
    y_ref,      # [1, 1, P, hb] f32
    o_ref,      # [1, hb, P, N] f32: the same block, aliased
):
    hb = s_ref.shape[1]

    @pl.when(n_ref[0] == 0)
    def _nobody():
        # The one step of an empty list hands its block back as it was.
        o_ref[...] = s_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(n_ref[0] > 0)
    def _advance():
        da, dx = da_ref[0, 0], dx_ref[0, 0]          # [P, hb]
        b, c = b_ref[0], c_ref[0]                    # [1, N]
        lane = jax.lax.broadcasted_iota(jnp.int32, da.shape, 1)
        y = jnp.zeros(da.shape, jnp.float32)
        for h in range(hb):
            mine = lane == h
            a_col = jnp.sum(jnp.where(mine, da, 0.0), axis=1, keepdims=True)
            x_col = jnp.sum(jnp.where(mine, dx, 0.0), axis=1, keepdims=True)
            s = a_col * s_ref[0, h] + x_col * b      # [P, N]
            o_ref[0, h] = s
            y = jnp.where(mine, jnp.sum(s * c, axis=1, keepdims=True), y)
        y_ref[0, 0] = y


def ssm_decode_reference(state, da, dx, b, c, live):
    """The same update of ONE layer's state ``[slots, H, P, N]`` by
    plain einsums, on every row ``live`` marks: the kernel's golden and
    its portable-export path. Returns ``(y [slots, H, P], state)``."""
    new = (da[:, :, None, None] * state
           + dx[:, :, :, None] * b[:, None, None, :])
    new = jnp.where(live[:, None, None, None], new, state)
    y = jnp.einsum("bhpn,bn->bhp", new, c)
    return jnp.where(live[:, None, None], y, 0.0), new


def live_rows(live: jax.Array):
    """``live [slots]`` bool to ``(rows [slots] int32, n)``: the rows in
    flight in ascending order, then the others (in range, never read),
    and how many are in flight."""
    order = jnp.argsort(~live, stable=True).astype(jnp.int32)
    return order, jnp.sum(live).astype(jnp.int32)


def ssm_decode(
    state: jax.Array,  # [layers, slots, H, P, N] f32, or one layer's 4-D
    da: jax.Array,     # [slots, H] f32: exp(delta A)
    dx: jax.Array,     # [slots, H, P] f32: delta x
    b: jax.Array,      # [slots, N] f32
    c: jax.Array,      # [slots, N] f32
    rows: jax.Array,   # [slots] int32: live_rows(...)
    n: jax.Array,      # int32: how many of ``rows`` count
    *,
    layer: jax.Array | int | None = None,  # which layer of a 5-D state
    interpret=None,
):
    """``(y [slots, H, P] f32, state)``: rows ``rows[:n]`` of ``layer``
    advanced by one position, in place; every other row's ``y`` is
    nought and its state untouched (and unread)."""
    if (state.ndim == 5) != (layer is not None):
        raise ValueError(
            "a stacked 5-D state needs layer=, one layer's 4-D takes none "
            f"(state rank {state.ndim}, layer {layer!r})")
    stacked = state.ndim == 5
    if not stacked:
        state, layer = state[None], 0
    layers, slots, H, P, N = state.shape
    layer = jnp.asarray(layer, jnp.int32)
    resolved = interpret_mode() if interpret is None else interpret
    if resolved and exporting_portable():
        live = jnp.zeros((slots,), bool).at[rows].set(
            jnp.arange(slots) < n)
        y, new = ssm_decode_reference(state[layer], da, dx, b, c, live)
        state = jax.lax.dynamic_update_slice(
            state, new[None], (layer, 0, 0, 0, 0))
        return y, state if stacked else state[0]
    hb = HEADS if H % HEADS == 0 else H
    blocks = H // hb

    def lanes(v):  # [slots, H, P] -> [slots, H / hb, P, hb]
        return v.reshape(slots, blocks, hb, P).swapaxes(2, 3)

    def row(s, rows, n, layer):
        return rows[s // blocks]

    col = pl.BlockSpec((1, 1, P, hb), lambda s, *p: (row(s, *p), s % blocks,
                                                     0, 0))
    vec = pl.BlockSpec((1, 1, N), lambda s, *p: (row(s, *p), 0, 0))
    blk = pl.BlockSpec(
        (1, hb, P, N),
        lambda s, rows, n, layer: (layer[0] * slots + rows[s // blocks],
                                   s % blocks, 0, 0))
    y, new = pl.pallas_call(
        _ssm_decode_kernel,
        name="tdt_ssm_decode",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(jnp.maximum(n * blocks, 1),),
            in_specs=[col, col, vec, vec, blk],
            out_specs=[col, blk],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((slots, blocks, P, hb), jnp.float32),
            jax.ShapeDtypeStruct((layers * slots, H, P, N), state.dtype),
        ],
        # Operand 7 (after the three prefetched scalars) is the state.
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=resolved,
    )(rows, jnp.reshape(n, (1,)).astype(jnp.int32), jnp.reshape(layer, (1,)),
      lanes(jnp.broadcast_to(da[:, :, None], dx.shape)), lanes(dx),
      b[:, None, :], c[:, None, :],
      state.reshape(layers * slots, H, P, N))
    # A row the grid never visited holds whatever the buffer held.
    live = jnp.zeros((slots,), bool).at[rows].set(jnp.arange(slots) < n)
    y = jnp.where(live[:, None, None],
                  y.swapaxes(2, 3).reshape(slots, H, P), 0.0)
    new = new.reshape(layers, slots, H, P, N)
    return y, new if stacked else new[0]
