"""The group-limited sigmoid router and one rank's share of an expert
layer (ops/moe/routing.py, layers/moe_share.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from triton_distributed_tpu.layers.moe_share import (
    MoEShareDims,
    MoEShareParams,
    moe_share_fwd,
    swiglu,
)
from triton_distributed_tpu.layers.tp_mlp import TPMLPParams
from triton_distributed_tpu.ops.moe.decode_experts import (
    moe_decode_experts,
    touched_experts,
)
from triton_distributed_tpu.ops.moe.routing import (
    held_sort,
    router_group_limited,
    router_topk,
)


def _route(logits, bias, k=2, n_group=4, topk_group=2, scale=2.5):
    """Route rows whose router logits are ``logits`` (x = I picks them
    out of the weight matrix)."""
    logits = jnp.asarray(logits, jnp.float32)
    return router_group_limited(
        jnp.eye(logits.shape[0], dtype=jnp.float32), logits,
        jnp.asarray(bias, jnp.float32), k, n_group=n_group,
        topk_group=topk_group, route_scale=scale)


def test_router_by_hand_bias_moves_the_choice_not_the_weight():
    # 8 experts in 4 groups of 2. Row 0: sigmoid scores by group
    # (.9 .5) (.8 .7) (.6 .6) (.2 .1): group sums 1.4, 1.5, 1.2, 0.3, so
    # groups 1 and 0 are kept and the 2 best inside them are experts 0
    # (.9) and 2 (.8).
    s = np.array([[.9, .5, .8, .7, .6, .6, .2, .1]])
    logits = np.log(s / (1 - s))
    ids, w = _route(logits, np.zeros(8))
    assert sorted(ids[0].tolist()) == [0, 2]
    by_id = dict(zip(ids[0].tolist(), w[0].tolist()))
    assert by_id[0] == pytest.approx(2.5 * .9 / 1.7, rel=1e-5)
    assert by_id[2] == pytest.approx(2.5 * .8 / 1.7, rel=1e-5)
    # A bias of +0.25 on expert 3 makes it the second choice (.95 over
    # .8) in place of expert 2; its WEIGHT is still made of its score .7.
    bias = np.zeros(8)
    bias[3] = 0.25
    ids, w = _route(logits, bias)
    assert sorted(ids[0].tolist()) == [0, 3]
    by_id = dict(zip(ids[0].tolist(), w[0].tolist()))
    assert by_id[3] == pytest.approx(2.5 * .7 / 1.6, rel=1e-5)
    assert by_id[0] == pytest.approx(2.5 * .9 / 1.6, rel=1e-5)


def test_a_group_outside_the_best_contributes_nothing():
    # Group 3 holds the single best expert (.95) beside a .01: its sum
    # .96 loses to groups 1 (1.5) and 2 (1.2), so neither of its experts
    # is chosen although .95 beats every chosen score.
    s = np.array([[.5, .4, .8, .7, .6, .6, .95, .01]])
    ids, w = _route(np.log(s / (1 - s)), np.zeros(8))
    assert sorted(ids[0].tolist()) == [2, 3]
    assert float(w[0].sum()) == pytest.approx(2.5, rel=1e-5)


def test_router_topk_is_what_it_was():
    """The softmax top-k gate keeps its output bit for bit beside the
    new function (the formula written out again here)."""
    kx, kw = jax.random.split(jax.random.key(0))
    x = jax.random.normal(kx, (12, 16), jnp.float32)
    w = jax.random.normal(kw, (16, 8), jnp.float32)
    got = router_topk(x, w, 3)
    probs = jax.nn.softmax(
        jnp.dot(x, w, preferred_element_type=jnp.float32), axis=-1)
    top, ids = jax.lax.top_k(probs, 3)
    np.testing.assert_array_equal(got.expert_ids, ids.astype(jnp.int32))
    np.testing.assert_array_equal(
        got.weights, top / jnp.sum(top, axis=-1, keepdims=True))


def test_held_sort_drops_rows_of_experts_held_elsewhere():
    route = _route(np.random.default_rng(0).normal(size=(6, 8)), np.zeros(8))
    st = held_sort(route, offset=2, held=2)
    flat = np.asarray(route.expert_ids).reshape(-1)
    assert st.group_sizes.tolist() == [(flat == 2).sum(), (flat == 3).sum()]
    n = int(st.group_sizes.sum())
    assert (np.asarray(st.expert_ids)[:n] < 2).all()
    assert (np.asarray(st.expert_ids)[n:] == 2).all()


def _layer(key, d=16, f=8, experts=16, fs=8):
    ks = jax.random.split(key, 6)

    def rnd(k, *shape):
        return jax.random.normal(k, shape, jnp.float32) * shape[-2] ** -0.5

    return dict(
        w_router=rnd(ks[0], d, experts),
        bias=jax.random.normal(ks[1], (experts,), jnp.float32) * 0.02,
        w1=rnd(ks[2], experts, d, 2 * f), w2=rnd(ks[3], experts, f, d),
        shared=TPMLPParams(w1=rnd(ks[4], d, 2 * fs), w2=rnd(ks[5], fs, d)))


@pytest.mark.parametrize("rows", [24, 200])  # the decode kernel, the sorted path
def test_the_shares_add_up_to_the_uncut_layer(rows):
    """Four ranks each hold 4 of 16 experts: the sum of their routed
    parts plus the shared expert ONCE is the layer with all 16 held."""
    full = _layer(jax.random.key(1))
    x = jax.random.normal(jax.random.key(2), (rows, 16), jnp.float32)

    def share(offset, held):
        p = MoEShareParams(
            w_router=full["w_router"], bias=full["bias"],
            w1=full["w1"][offset: offset + held],
            w2=full["w2"][offset: offset + held], shared=full["shared"])
        dims = MoEShareDims(top_k=4, n_group=4, topk_group=2,
                            route_scale=2.5, offset=offset, held=held)
        return moe_share_fwd(p, x, dims)

    whole, whole_counts = share(0, 16)
    shared = swiglu(full["shared"], x)
    parts, counts = zip(*(share(o, 4) for o in (0, 4, 8, 12)))
    routed = sum(p - shared for p in parts)
    np.testing.assert_allclose(routed + shared, whole, atol=2e-5, rtol=2e-5)
    # Every row's 4 assignments are held by exactly one rank.
    assert sum(int(c[0]) for c in counts) == rows * 4 == int(whole_counts[0])
    assert sum(int(c[1]) for c in counts) == int(whole_counts[1]) <= 16


def test_counts_follow_the_live_rows():
    full = _layer(jax.random.key(3))
    x = jax.random.normal(jax.random.key(4), (8, 16), jnp.float32)
    p = MoEShareParams(w_router=full["w_router"], bias=full["bias"],
                       w1=full["w1"][:4], w2=full["w2"][:4],
                       shared=full["shared"])
    dims = MoEShareDims(top_k=4, n_group=4, topk_group=2, route_scale=2.5,
                        offset=0, held=4)
    live = jnp.arange(8) < 3
    y_all, c_all = moe_share_fwd(p, x, dims)
    y_live, c_live = moe_share_fwd(p, x, dims, live)
    # A live row is what it was; a dead one gets the shared expert only.
    np.testing.assert_array_equal(y_all[:3], y_live[:3])
    np.testing.assert_array_equal(y_live[3:], swiglu(p.shared, x)[3:])
    route = router_group_limited(x, p.w_router, p.bias, 4, n_group=4,
                                 topk_group=2, route_scale=2.5)
    ids = np.asarray(route.expert_ids)
    assert int(c_all[0]) == (ids < 4).sum()
    assert int(c_live[0]) == (ids[:3] < 4).sum()
    assert int(c_live[1]) == len({e for e in ids[:3].ravel() if e < 4})


def _gate_weighted_loop(x, gate, w1, w2):
    """``sum_e gate[:, e] * FFN_e(x)``, one expert after the other."""
    y = jnp.zeros(x.shape, jnp.float32)
    for e in range(w1.shape[0]):
        g, u = jnp.split(x @ w1[e], 2, axis=-1)
        y += gate[:, e: e + 1] * ((jax.nn.silu(g) * u) @ w2[e])
    return y


def _dead_row_case():
    """8 one-hot rows whose router logits are set by hand (16 experts in
    4 groups, 4 a row from 2 groups, experts 0-3 held): the 7 live rows
    choose 0, 1, 4, 5 and the dead one 0, 3, 8, 9."""
    full = _layer(jax.random.key(7))
    logits = np.full((16, 16), -4.0, np.float32)
    logits[:7, [0, 1, 4, 5]] = 4.0
    logits[7, [0, 3, 8, 9]] = 4.0
    p = MoEShareParams(
        w_router=jnp.asarray(logits), bias=jnp.zeros((16,), jnp.float32),
        w1=full["w1"][:4], w2=full["w2"][:4], shared=full["shared"])
    dims = MoEShareDims(top_k=4, n_group=4, topk_group=2, route_scale=2.5,
                        offset=0, held=4)
    return p, jnp.eye(8, 16, dtype=jnp.float32), dims, jnp.arange(8) < 7


@pytest.mark.parametrize(
    "case", ["none", "one", "all", "layer_of_stacked", "poisoned",
             "dead_row"])
def test_a_decode_step_reads_the_experts_its_live_rows_chose(case):
    """``tdt_moe_decode_experts`` (under the interpreter) against the
    plain loop, by how many of the held experts the gate touches; NaN in
    every weight outside the list proves those are not read."""
    if case == "dead_row":
        p, x, dims, live = _dead_row_case()
        y_all, c_all = moe_share_fwd(p, x, dims)
        assert c_all.tolist() == [2 * 7 + 2, 3]  # experts 0, 1 and 3
        # Expert 3 is the dead row's alone: out of the list, so are 2's
        # and 3's weights out of the step.
        poisoned = MoEShareParams(
            w_router=p.w_router, bias=p.bias, shared=p.shared,
            w1=p.w1.at[2:].set(jnp.nan), w2=p.w2.at[2:].set(jnp.nan))
        y_live, c_live = moe_share_fwd(poisoned, x, dims, live)
        assert c_live.tolist() == [2 * 7, 2]
        assert np.isfinite(np.asarray(y_live)).all()
        np.testing.assert_array_equal(y_live[:7], y_all[:7])
        np.testing.assert_array_equal(y_live[7:], swiglu(p.shared, x)[7:])
        return
    layers, held, t, d, f = 3, 4, 8, 16, 8
    k = jax.random.split(jax.random.key(5), 4)
    x = jax.random.normal(k[0], (t, d), jnp.float32)
    w1 = jax.random.normal(k[1], (layers, held, d, 2 * f), jnp.float32) / 4
    w2 = jax.random.normal(k[2], (layers, held, f, d), jnp.float32) / 3
    chosen = {"none": [], "one": [2], "all": [0, 1, 2, 3],
              "layer_of_stacked": [0, 3], "poisoned": [1, 3]}[case]
    layer = {"layer_of_stacked": 2, "poisoned": 1}.get(case, 0)
    mask = jnp.zeros((held,), bool).at[jnp.asarray(chosen, jnp.int32)].set(
        True)
    # Some rows choose none of the touched experts, none chooses another.
    gate = jnp.where(
        mask[None, :] & (jax.random.uniform(k[3], (t, held)) < 0.6),
        jax.random.uniform(k[3], (t, held), minval=0.1), 0.0)
    if chosen:
        gate = gate.at[0, jnp.asarray(chosen)].set(0.5)
    touched, n = touched_experts(jnp.any(gate != 0, axis=0))
    assert int(n) == len(chosen) and touched[: int(n)].tolist() == chosen
    want = _gate_weighted_loop(x, gate, w1[layer], w2[layer])
    if case == "poisoned":
        dead = jnp.ones((layers, held), bool).at[
            layer, jnp.asarray(chosen)].set(False)[:, :, None, None]
        w1, w2 = jnp.where(dead, jnp.nan, w1), jnp.where(dead, jnp.nan, w2)
    got = jax.jit(moe_decode_experts)(
        x, gate, touched, n, w1, w2, layer=jnp.int32(layer))
    assert got.dtype == jnp.float32 and got.shape == (t, d)
    assert np.isfinite(np.asarray(got)).all()
    if not chosen:
        np.testing.assert_array_equal(got, np.zeros((t, d), np.float32))
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
