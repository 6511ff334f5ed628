"""MoE tests (parity: reference test_ag_moe.py / test_moe_reduce_rs.py /
test_ep_a2a.py — golden = dense per-token expert loop)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from triton_distributed_tpu.layers.tp_moe import TPMoE
from triton_distributed_tpu.ops.moe import (
    ep_moe_ffn,
    grouped_ffn,
    moe_combine,
    moe_sort,
    router_topk,
)


def _golden_moe(x, w_router, gate, up, down, k, norm=True):
    """Dense reference: route each token, run its experts, weighted sum."""
    logits = np.asarray(x, np.float64) @ np.asarray(w_router, np.float64)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    t, e = probs.shape
    out = np.zeros((t, x.shape[1]))
    for i in range(t):
        ids = np.argsort(-probs[i])[:k]
        w = probs[i][ids]
        if norm:
            w = w / w.sum()
        for j, eid in zip(w, ids):
            h = np.asarray(x[i], np.float64)
            g = h @ np.asarray(gate[eid], np.float64)
            u = h @ np.asarray(up[eid], np.float64)
            act = g / (1 + np.exp(-g)) * u
            out[i] += j * (act @ np.asarray(down[eid], np.float64))
    return out


@pytest.fixture
def moe_weights(rng):
    e, d, f, k = 8, 32, 64, 2
    mk = lambda *s: jnp.asarray(rng.standard_normal(s) * 0.1, jnp.float32)
    return dict(
        e=e, d=d, f=f, k=k,
        w_router=mk(d, e), gate=mk(e, d, f), up=mk(e, d, f), down=mk(e, f, d),
    )


def test_routing_and_grouped_ffn(rng, moe_weights):
    """Single-device sort + grouped FFN matches the dense loop."""
    mw = moe_weights
    t = 16
    x = jnp.asarray(rng.standard_normal((t, mw["d"])) * 0.1, jnp.float32)
    route = router_topk(x, mw["w_router"], mw["k"])
    st = moe_sort(route, mw["e"])
    w1 = jnp.concatenate([mw["gate"], mw["up"]], axis=2)
    h = grouped_ffn(x[st.token_ids], w1, mw["down"], st.group_sizes)
    out = moe_combine(h, st, t)
    gold = _golden_moe(x, mw["w_router"], mw["gate"], mw["up"], mw["down"], mw["k"])
    np.testing.assert_allclose(np.asarray(out), gold, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("mode", ["xla", "pallas", "ring", "xla_ar", "pallas_ar"])
def test_tp_moe(ctx4, rng, moe_weights, mode):
    mw = moe_weights
    t = 32
    x = jnp.asarray(rng.standard_normal((t, mw["d"])) * 0.1, jnp.float32)
    layer = TPMoE(mw["d"], mw["f"], mw["e"], mw["k"], dtype=jnp.float32, ctx=ctx4)
    layer.load(mw["w_router"], mw["gate"], mw["up"], mw["down"])
    out = layer.forward(x, mode=mode)
    gold = _golden_moe(x, mw["w_router"], mw["gate"], mw["up"], mw["down"], mw["k"])
    np.testing.assert_allclose(np.asarray(out), gold, atol=5e-4, rtol=5e-4)


@pytest.mark.parametrize("method", ["xla", "pallas"])
def test_ep_moe(ctx4, rng, moe_weights, method):
    """Experts sharded over 4 ranks; each rank owns 8 local tokens.
    Default (lossless) path must match the dense loop."""
    mw = moe_weights
    t_loc, n = 8, 4
    x = jnp.asarray(rng.standard_normal((n * t_loc, mw["d"])) * 0.1, jnp.float32)
    w1 = jnp.concatenate([mw["gate"], mw["up"]], axis=2)

    f = jax.jit(ctx4.shard_map(
        functools.partial(
            ep_moe_ffn, k=mw["k"], axis="tp", method=method, ctx=ctx4,
        ),
        in_specs=(P("tp", None), P(), P("tp", None, None), P("tp", None, None)),
        out_specs=P("tp", None),
    ))
    out = f(x, mw["w_router"], w1, mw["down"])
    gold = _golden_moe(x, mw["w_router"], mw["gate"], mw["up"], mw["down"], mw["k"])
    np.testing.assert_allclose(np.asarray(out), gold, atol=5e-4, rtol=5e-4)


@pytest.mark.parametrize("method", ["xla", "pallas"])
def test_ep_moe_lossless_adversarial(ctx4, rng, moe_weights, method):
    """VERDICT r1 #5: worst-case routing skew — a router biased so EVERY
    token's top-k lands on rank 0's experts — must still be bit-exact vs
    the dense golden, with zero drops (reference never drops;
    ``kernel_get_ag_splits_and_recv_offset`` exchanges real splits)."""
    mw = moe_weights
    t_loc, n = 8, 4
    # Positive tokens + ±100 column bias → every top-k lands on rank 0's
    # experts with certainty (x@(w±100) = x@w ± 100·sum(x), sum(x) > 0).
    x = jnp.asarray(
        np.abs(rng.standard_normal((n * t_loc, mw["d"]))) * 0.1, jnp.float32
    )
    w_router = mw["w_router"].at[:, 2:].add(-100.0).at[:, :2].add(100.0)
    w1 = jnp.concatenate([mw["gate"], mw["up"]], axis=2)

    f = jax.jit(ctx4.shard_map(
        functools.partial(
            ep_moe_ffn, k=mw["k"], axis="tp", method=method, ctx=ctx4,
        ),
        in_specs=(P("tp", None), P(), P("tp", None, None), P("tp", None, None)),
        out_specs=P("tp", None),
    ))
    out = f(x, w_router, w1, mw["down"])
    gold = _golden_moe(x, w_router, mw["gate"], mw["up"], mw["down"], mw["k"])
    np.testing.assert_allclose(np.asarray(out), gold, atol=5e-4, rtol=5e-4)


def test_ep_dispatch_overflow_detected(ctx4, rng, moe_weights):
    """Capacity mode must COUNT overflow, not hide it (detected-error
    semantics): adversarial skew at capacity_factor=1.0 reports drops."""
    from triton_distributed_tpu.ops.moe.ep_a2a import ep_dispatch
    from triton_distributed_tpu.ops.moe.routing import router_topk

    mw = moe_weights
    t_loc = 8
    x = jnp.asarray(
        np.abs(rng.standard_normal((4 * t_loc, mw["d"]))) * 0.1, jnp.float32
    )
    w_router = (
        mw["w_router"].at[:, 2:].add(-100.0).at[:, :2].add(100.0)
    )  # all → rank 0

    def body(x_loc):
        route = router_topk(x_loc, w_router, mw["k"])
        # capacity 8 < t_loc*k=16 all targeting rank 0 → drops detected
        _, _, _, state = ep_dispatch(x_loc, route, mw["e"], capacity=8, axis="tp")
        return state.num_dropped[None]

    f = jax.jit(ctx4.shard_map(body, in_specs=P("tp", None),
                               out_specs=P("tp")))
    dropped = f(x)
    assert int(np.asarray(dropped).max()) > 0


@pytest.mark.parametrize("method", ["xla", "pallas"])
def test_ep_moe_fp8_payload(ctx4, rng, moe_weights, method):
    """LL fp8+scales codec (reference low_latency_all_to_all.py:36-125):
    quantized dispatch stays close to the dense golden — over both
    transports, and bit-identically between them (same codec, different
    wire)."""
    mw = moe_weights
    t_loc, n = 8, 4
    x = jnp.asarray(rng.standard_normal((n * t_loc, mw["d"])) * 0.1, jnp.float32)
    w1 = jnp.concatenate([mw["gate"], mw["up"]], axis=2)

    f = jax.jit(ctx4.shard_map(
        functools.partial(
            ep_moe_ffn, k=mw["k"], axis="tp", payload_dtype="fp8",
            method=method, ctx=ctx4,
        ),
        in_specs=(P("tp", None), P(), P("tp", None, None), P("tp", None, None)),
        out_specs=P("tp", None),
    ))
    out = f(x, mw["w_router"], w1, mw["down"])
    gold = _golden_moe(x, mw["w_router"], mw["gate"], mw["up"], mw["down"], mw["k"])
    # fp8 payload: ~2^-3 relative mantissa error through one FFN
    np.testing.assert_allclose(np.asarray(out), gold, atol=5e-2, rtol=5e-2)


@pytest.mark.slow
@pytest.mark.parametrize("payload", [None, "fp8"])
def test_ep_transport_parity(ctx4, rng, moe_weights, payload):
    """The device-push transport must be BIT-IDENTICAL to the XLA
    transport (same tokens, same slots, only the wire differs) — at
    skewed splits so partial blocks and empty segments both occur."""
    mw = moe_weights
    t_loc, n = 8, 4
    x = jnp.asarray(
        np.abs(rng.standard_normal((n * t_loc, mw["d"]))) * 0.1, jnp.float32
    )
    # Skew most tokens to rank 0's experts (non-uniform splits).
    w_router = mw["w_router"].at[:, :2].add(50.0)
    w1 = jnp.concatenate([mw["gate"], mw["up"]], axis=2)

    outs = {}
    for method in ("xla", "pallas"):
        f = jax.jit(ctx4.shard_map(
            functools.partial(
                ep_moe_ffn, k=mw["k"], axis="tp", method=method,
                payload_dtype=payload, ctx=ctx4,
            ),
            in_specs=(P("tp", None), P(), P("tp", None, None),
                      P("tp", None, None)),
            out_specs=P("tp", None),
        ))
        outs[method] = np.asarray(f(x, w_router, w1, mw["down"]))
    np.testing.assert_array_equal(outs["xla"], outs["pallas"])


def test_ep_moe_capacity_pallas(ctx4, rng, moe_weights):
    """Capacity (bounded-memory) mode over the device-push transport:
    uniform routing under capacity must match the dense golden, and the
    unwritten tail of each segment must not poison the combine."""
    mw = moe_weights
    t_loc, n = 8, 4
    x = jnp.asarray(rng.standard_normal((n * t_loc, mw["d"])) * 0.1, jnp.float32)
    w1 = jnp.concatenate([mw["gate"], mw["up"]], axis=2)

    f = jax.jit(ctx4.shard_map(
        functools.partial(
            ep_moe_ffn, k=mw["k"], axis="tp", method="pallas",
            capacity_factor=4.0, ctx=ctx4,
        ),
        in_specs=(P("tp", None), P(), P("tp", None, None), P("tp", None, None)),
        out_specs=P("tp", None),
    ))
    out = f(x, mw["w_router"], w1, mw["down"])
    gold = _golden_moe(x, mw["w_router"], mw["gate"], mw["up"], mw["down"], mw["k"])
    np.testing.assert_allclose(np.asarray(out), gold, atol=5e-4, rtol=5e-4)


def test_qwen3_moe_model(ctx4):
    """Tiny Qwen3-MoE end-to-end: prefill + greedy decode determinism
    (parity: reference test_ep_moe_inference.py)."""
    from triton_distributed_tpu.models import AutoLLM, Engine

    model = AutoLLM.from_pretrained("tiny-moe", ctx=ctx4)
    eng = Engine(model, temperature=0.0, mode="xla")
    prompt = np.arange(8, dtype=np.int32)[None].repeat(2, 0)
    out = eng.serve(prompt, gen_len=3)
    assert out.shape == (2, 11)
    np.testing.assert_array_equal(out[0], out[1])

    # pallas prefill mode must agree with xla mode on the same weights.
    cache_x = model.new_cache(1)
    cache_p = model.new_cache(1)
    toks = jnp.arange(16, dtype=jnp.int32)
    lx, _ = model.prefill(toks, cache_x, "xla")
    lp, _ = model.prefill(toks, cache_p, "pallas")
    np.testing.assert_allclose(np.asarray(lx), np.asarray(lp), atol=2e-4,
                               rtol=2e-4)
