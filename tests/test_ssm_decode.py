"""``tdt_ssm_decode`` (ops/ssm/decode.py) under the interpreter: the
recurrent state of the rows in flight moved by one position, in place at
(layer, slot), against the plain einsums; and the chunked form of the
same recurrence (layers/mamba2.py) against a position at a time."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from triton_distributed_tpu.layers.mamba2 import ssd_chunked
from triton_distributed_tpu.ops.ssm.decode import (
    live_rows,
    ssm_decode,
    ssm_decode_reference,
)

LAYERS, SLOTS, H, P, N = 3, 6, 8, 16, 16


def _operands(seed=0, heads=H):
    k = jax.random.split(jax.random.key(seed), 5)
    return (
        jax.random.normal(k[0], (LAYERS, SLOTS, heads, P, N), jnp.float32),
        jax.random.uniform(k[1], (SLOTS, heads), jnp.float32, 0.3, 1.0),
        jax.random.normal(k[2], (SLOTS, heads, P), jnp.float32),
        jax.random.normal(k[3], (SLOTS, N), jnp.float32),
        jax.random.normal(k[4], (SLOTS, N), jnp.float32),
    )


def test_live_rows_lists_the_rows_in_flight_in_order():
    rows, n = live_rows(jnp.asarray([False, True, False, True, True, False]))
    assert int(n) == 3 and rows[:3].tolist() == [1, 3, 4]
    assert sorted(rows.tolist()) == list(range(6))
    rows, n = live_rows(jnp.zeros((4,), bool))
    assert int(n) == 0 and sorted(rows.tolist()) == [0, 1, 2, 3]


@pytest.mark.parametrize("case", ["all", "some", "one", "nobody", "poisoned",
                                  "head_blocks"])
def test_a_step_advances_the_rows_in_flight_in_place(case):
    """By how many rows are in the list: their state moves as the einsum
    says and their ``y`` is the einsum's; every other row of the layer,
    and every other layer, is bit for bit what it was (NaN in them
    proves they are not read); an empty list moves nothing."""
    heads = 32 if case == "head_blocks" else H  # two blocks of 16 heads
    state, da, dx, b, c = _operands(heads=heads)
    live = jnp.asarray({
        "all": [1] * 6, "some": [0, 1, 1, 0, 1, 0], "one": [0, 0, 0, 0, 0, 1],
        "nobody": [0] * 6, "poisoned": [1, 0, 0, 1, 0, 0],
        "head_blocks": [0, 1, 0, 1, 1, 0]}[case], bool)
    layer = 1
    want_y, want_s = ssm_decode_reference(state[layer], da, dx, b, c, live)
    if case == "poisoned":
        dead = jnp.ones((LAYERS, SLOTS), bool).at[layer].set(~live)
        state = jnp.where(dead[:, :, None, None, None], jnp.nan, state)
    rows, n = live_rows(live)
    got_y, got_s = jax.jit(ssm_decode, donate_argnums=(0,))(
        jnp.array(state), da, dx, b, c, rows, n, layer=jnp.int32(layer))
    assert got_y.shape == (SLOTS, heads, P) and got_y.dtype == jnp.float32
    assert got_s.shape == state.shape
    np.testing.assert_allclose(got_y, want_y, atol=2e-5, rtol=2e-5)
    assert np.isfinite(np.asarray(got_y)).all()
    on = np.asarray(live)
    np.testing.assert_allclose(got_s[layer][on], want_s[on], atol=1e-6,
                               rtol=1e-6)
    # Untouched: the layer's other rows and the other layers whole.
    np.testing.assert_array_equal(got_s[layer][~on], state[layer][~on])
    np.testing.assert_array_equal(got_s[0], state[0])
    np.testing.assert_array_equal(got_s[2], state[2])
    np.testing.assert_array_equal(np.asarray(got_y)[~on], 0.0)


def test_one_layers_state_takes_no_layer():
    state, da, dx, b, c = _operands(1)
    live = jnp.asarray([1, 1, 0, 0, 1, 1], bool)
    rows, n = live_rows(live)
    y, s = ssm_decode(state[0], da, dx, b, c, rows, n)
    want_y, want_s = ssm_decode_reference(state[0], da, dx, b, c, live)
    np.testing.assert_allclose(y, want_y, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(s, want_s, atol=1e-6, rtol=1e-6)
    with pytest.raises(ValueError, match="layer="):
        ssm_decode(state, da, dx, b, c, rows, n)


@pytest.mark.parametrize("block", [4, 8, 24])
def test_the_chunked_form_is_the_recurrence(block):
    """``ssd_chunked`` over 24 positions in blocks of 4 / 8 / 24 against
    the recurrence a position at a time, from a state that is not nought;
    positions whose ``delta`` is nought leave the state as it was."""
    t = 24
    k = jax.random.split(jax.random.key(2), 6)
    x = jax.random.normal(k[0], (t, H, P), jnp.float32)
    delta = jax.nn.softplus(jax.random.normal(k[1], (t, H), jnp.float32))
    delta = delta.at[19:].set(0.0)  # right-padding
    a = -jnp.exp(jax.random.normal(k[2], (H,), jnp.float32))
    b = jax.random.normal(k[3], (t, N), jnp.float32)
    c = jax.random.normal(k[4], (t, N), jnp.float32)
    s0 = jax.random.normal(k[5], (H, P, N), jnp.float32)

    def position(s, inp):
        x_t, d_t, b_t, c_t = inp
        s = (jnp.exp(d_t * a)[:, None, None] * s
             + (d_t[:, None] * x_t)[..., None] * b_t[None, None, :])
        return s, jnp.einsum("hpn,n->hp", s, c_t)

    want_s, want_y = jax.lax.scan(position, s0, (x, delta, b, c))
    got_y, got_s = jax.jit(ssd_chunked, static_argnums=(6,))(
        x, delta, a, b, c, s0, block)
    np.testing.assert_allclose(got_y[:19], want_y[:19], atol=5e-4, rtol=5e-4)
    np.testing.assert_allclose(got_s, want_s, atol=5e-4, rtol=5e-4)
    # The padding moved nothing: the state after 19 positions.
    s19, _ = jax.lax.scan(position, s0, (x[:19], delta[:19], b[:19], c[:19]))
    np.testing.assert_allclose(got_s, s19, atol=5e-4, rtol=5e-4)
