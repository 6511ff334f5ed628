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


@pytest.mark.parametrize("rows", [24, 200])  # the dense path, the sorted one
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
    np.testing.assert_array_equal(y_all, y_live)  # live moves counts only
    route = router_group_limited(x, p.w_router, p.bias, 4, n_group=4,
                                 topk_group=2, route_scale=2.5)
    ids = np.asarray(route.expert_ids)
    assert int(c_all[0]) == (ids < 4).sum()
    assert int(c_live[0]) == (ids[:3] < 4).sum()
    assert int(c_live[1]) == len({e for e in ids[:3].ravel() if e < 4})
