"""A decode step's recurrent state update, in place at (layer, slot).

A Mamba-2 layer keeps one state a slot (float32) whatever the context,
and a decode step moves it by one position:

    S <- exp(delta A) S + (delta x) (x) B        y = S C + D x

(``delta``, ``A``, ``D`` a number a head; ``x`` ``P`` channels a head;
``B``, ``C`` ``[N]``, shared by the heads). The state is the step's
bytes: at 64 heads of 64 and a state of 128, float32, a row reads and
writes 2.1 MB a layer, and nothing else of the update is worth
counting.

The state is held TRANSPOSED, ``S^T [N, di]``: the state dimension on
sublanes and the ``di = H x P`` inner channels (``h * P + p``) on the
lanes. A block of channels then moves as ``S'[:, blk] = a_row * S[:,
blk] + b_col * dx_row`` and ``y_row = sum over sublanes of (S' *
c_col)``: ``x`` and ``y`` are lane-dense rows of ``di``, ``B`` and ``C``
columns of ``N``, and a head's numbers are spread over its ``P`` lanes
(from SMEM, one select a head a vreg). No head's column is picked by a
lane mask, and nothing is relaid out around the kernel: with ``N`` on
the lanes, what multiplies a head a row at a time would have to come in
as columns of 16 heads, 16 lanes padded to 128.

The kernel walks ONE dynamic grid axis (PR 33's technique,
:func:`paged_decode_walk`) of ``n x di / W`` steps over the rows IN
FLIGHT, a list the engine uploads with its tables (``rows[:n]``; the
page table cannot say which rows are in flight: a slot whose admission
is between two chunks is mapped and must not move). Step ``(i, j)``
takes lanes ``[j W, (j + 1) W)`` (``W = BLOCK``) of slot ``rows[i]``
of THIS layer from the stacked state ``[layers, slots, N, di]`` seen as
``[layers * slots, N, di]`` (the layer rides the index map, as in
:func:`moe_decode_experts`), updates them and writes them back to the
same block: the state is aliased to the output, a row not in the list
costs no byte and keeps its state, and no row in flight is one step
that rewrites the block it read.

The row operands, the convolution's output ``x | B | C`` ``[slots, di +
2 N]`` as the mixer makes it and ``y [slots, di]``, stay WHOLE in VMEM
across the grid (a constant index map: fetched once, 1.1 MB a layer at
32 slots, ``y`` written back once), and the numbers a head (``exp(delta
A)``, ``delta`` ``[slots, H]``, ``D [H]``) whole in SMEM. A block of one
row of a ``[slots, di]`` array would have a second-minor dimension of
1, which Mosaic takes only as ``[slots, 1, di]``, tiled to 8 sublanes:
eight times the bytes. A step reads its row out of the aligned group of
8 that holds it (``B`` and ``C`` stood up by an ``[8, N]`` transpose)
and writes ``y`` back into that group; ``y`` is zeroed on the first
step, so a row not in flight reads nought.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from triton_distributed_tpu.ops.common import exporting_portable, interpret_mode

# Channels a grid step takes: an ``[N, BLOCK]`` float32 block in and
# out, each double-buffered (16 heads of 64 at a state of 128: 0.5 MB).
BLOCK = 1024
# Lanes the block's arithmetic takes at a time (one vreg wide).
LANES = 128
# Rows of the aligned group a step reads its row out of (a sublane tile).
GROUP = 8


def _ssm_decode_kernel(
    rows_ref,   # [slots] int32 SMEM (scalar prefetch): the rows in flight
    n_ref,      # [1] int32 SMEM: how many of them count
    layer_ref,  # [1] int32 SMEM: consumed by the index maps
    a_ref,      # [slots, H] f32 SMEM: exp(delta A), a number a head
    dt_ref,     # [slots, H] f32 SMEM: delta
    d_ref,      # [1, H] f32 SMEM: the skip
    xbc_ref,    # [rows8, di + 2 N] f32, whole: x | B | C
    s_ref,      # [1, N, W] f32: the block of the state
    y_ref,      # [rows8, di] f32, whole
    o_ref,      # [1, N, W] f32: the same block, aliased
):
    step = pl.program_id(0)
    n_state, w = s_ref.shape[1:]
    di = y_ref.shape[1]
    blocks = di // w
    p = di // a_ref.shape[1]   # channels a head

    @pl.when(step == 0)
    def _zero():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(n_ref[0] == 0)
    def _nobody():
        # The one step of an empty list hands its block back as it was.
        o_ref[...] = s_ref[...]

    @pl.when(n_ref[0] > 0)
    def _advance():
        r = rows_ref[step // blocks]
        j = step % blocks
        group = pl.ds(pl.multiple_of(r // GROUP * GROUP, GROUP), GROUP)
        lanes = (pl.ds(pl.multiple_of(j * w, LANES), w) if blocks > 1
                 else slice(0, w))
        mine = jax.lax.broadcasted_iota(jnp.int32, (GROUP, w), 0) == r % GROUP
        x = jnp.sum(jnp.where(mine, xbc_ref[group, lanes], 0.0), axis=0,
                    keepdims=True)                        # [1, W]

        def col(at):  # [N, 1]: row r of the N lanes from ``at``, stood up
            t = xbc_ref[group, pl.ds(at, n_state)].T      # [N, 8]
            pick = jax.lax.broadcasted_iota(jnp.int32, t.shape, 1) == r % GROUP
            return jnp.sum(jnp.where(pick, t, 0.0), axis=1, keepdims=True)

        b, c = col(di), col(di + n_state)
        ys = []
        for k in range(0, w, LANES):
            piece = slice(k, min(k + LANES, w))
            head = (k + jax.lax.broadcasted_iota(
                jnp.int32, (1, piece.stop - k), 1)) // p
            first, *rest = range(k // p, (piece.stop - 1) // p + 1)

            def spread(ref, row):  # [1, piece]: each head's number on its lanes
                v = jnp.full(head.shape, ref[row, j * (w // p) + first])
                for hh in rest:
                    v = jnp.where(head == hh, ref[row, j * (w // p) + hh], v)
                return v

            a, dt, d = spread(a_ref, r), spread(dt_ref, r), spread(d_ref, 0)
            s = a * s_ref[0, :, piece] + b * (dt * x[:, piece])
            o_ref[0, :, piece] = s
            ys.append(jnp.sum(s * c, axis=0, keepdims=True) + d * x[:, piece])
        y = jnp.concatenate(ys, axis=1)
        y_ref[group, lanes] = jnp.where(mine, y, y_ref[group, lanes])


def ssm_decode_reference(state, a, dt, d, xbc, live):
    """The same update of ONE layer's state ``[slots, N, di]`` by plain
    elementwise sums, on every row ``live`` marks: the kernel's golden
    and its portable-export path. Returns ``(y [slots, di]`` with the
    skip, ``state)``."""
    n_state, di = state.shape[1:]
    x, b, c = jnp.split(xbc, [di, di + n_state], axis=1)

    def channels(v):
        return jnp.repeat(v, di // v.shape[-1], axis=-1)

    new = (channels(a)[:, None, :] * state
           + b[:, :, None] * (channels(dt) * x)[:, None, :])
    new = jnp.where(live[:, None, None], new, state)
    y = jnp.sum(new * c[:, :, None], axis=1) + channels(d) * x
    return jnp.where(live[:, None], y, 0.0), new


def live_rows(live: jax.Array):
    """``live [slots]`` bool to ``(rows [slots] int32, n)``: the rows in
    flight in ascending order, then the others (in range, never read),
    and how many are in flight."""
    order = jnp.argsort(~live, stable=True).astype(jnp.int32)
    return order, jnp.sum(live).astype(jnp.int32)


def ssm_decode(
    state: jax.Array,  # [layers, slots, N, di] f32, or one layer's 3-D
    a: jax.Array,      # [slots, H] f32: exp(delta A)
    dt: jax.Array,     # [slots, H] f32: delta
    d: jax.Array,      # [H] f32: the skip
    xbc: jax.Array,    # [slots, di + 2 N] f32: x (H heads of P) | B | C
    rows: jax.Array,   # [slots] int32: live_rows(...)
    n: jax.Array,      # int32: how many of ``rows`` count
    *,
    layer: jax.Array | int | None = None,  # which layer of a 4-D state
    interpret=None,
):
    """``(y [slots, di] f32, state)``: rows ``rows[:n]`` of ``layer``
    advanced by one position, in place, and their ``y = S C + D x``;
    every other row's ``y`` is nought and its state untouched (and
    unread)."""
    if (state.ndim == 4) != (layer is not None):
        raise ValueError(
            "a stacked 4-D state needs layer=, one layer's 3-D takes none "
            f"(state rank {state.ndim}, layer {layer!r})")
    stacked = state.ndim == 4
    if not stacked:
        state, layer = state[None], 0
    layers, slots, N, di = state.shape
    layer = jnp.asarray(layer, jnp.int32)
    resolved = interpret_mode() if interpret is None else interpret
    if resolved and exporting_portable():
        live = jnp.zeros((slots,), bool).at[rows].set(
            jnp.arange(slots) < n)
        y, new = ssm_decode_reference(state[layer], a, dt, d, xbc, live)
        state = jax.lax.dynamic_update_slice(
            state, new[None], (layer, 0, 0, 0))
        return y, state if stacked else state[0]
    heads = a.shape[1]
    w = BLOCK if di % BLOCK == 0 and BLOCK % (di // heads) == 0 else di
    blocks = di // w
    # Whole groups of 8 rows, so that a row's group is always in range.
    rows8 = -(-slots // GROUP) * GROUP
    if rows8 != slots:
        xbc = jnp.pad(xbc, ((0, rows8 - slots), (0, 0)))

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    blk = pl.BlockSpec(
        (1, N, w),
        lambda s, rows, n, layer: (layer[0] * slots + rows[s // blocks],
                                   0, s % blocks))
    y, new = pl.pallas_call(
        _ssm_decode_kernel,
        name="tdt_ssm_decode",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(jnp.maximum(n * blocks, 1),),
            in_specs=[smem, smem, smem,
                      pl.BlockSpec(xbc.shape, lambda s, *_: (0, 0)), blk],
            out_specs=[pl.BlockSpec((rows8, di), lambda s, *_: (0, 0)), blk],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((rows8, di), jnp.float32),
            jax.ShapeDtypeStruct((layers * slots, N, di), state.dtype),
        ],
        # Operand 7 (after the three prefetched scalars) is the state.
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=resolved,
    )(rows, jnp.reshape(n, (1,)).astype(jnp.int32), jnp.reshape(layer, (1,)),
      a, dt, jnp.reshape(d, (1, -1)), xbc,
      state.reshape(layers * slots, N, di))
    new = new.reshape(layers, slots, N, di)
    return y[:slots], new if stacked else new[0]
