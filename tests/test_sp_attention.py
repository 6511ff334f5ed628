"""Sequence-parallel attention tests (parity: reference
test_sp_ag_attention_intra_node.py — golden = dense causal attention over
the full gathered sequence)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from triton_distributed_tpu.ops.attention import (
    mha_reference,
    ring_attention,
    sp_ag_attention,
)


def _make(rng, hq, hkv, s, hd):
    q = jnp.asarray(rng.standard_normal((hq, s, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((hkv, s, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((hkv, s, hd)), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2)])
def test_sp_ag_attention(ctx4, rng, hq, hkv):
    s, hd = 256, 64  # 64 rows per device
    q, k, v = _make(rng, hq, hkv, s, hd)

    f = jax.jit(ctx4.shard_map(
        functools.partial(sp_ag_attention, axis="tp", block_q=32, ctx=ctx4),
        in_specs=(P(None, "tp", None),) * 3,
        out_specs=P(None, "tp", None),
    ))
    out = f(q, k, v)
    ref = mha_reference(q[None], k[None], v[None], causal=True)[0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention(ctx4, rng, causal):
    s, hq, hkv, hd = 256, 4, 2, 64
    q, k, v = _make(rng, hq, hkv, s, hd)

    f = jax.jit(ctx4.shard_map(
        functools.partial(ring_attention, axis="tp", causal=causal, block_q=64,
                          block_k=64),
        in_specs=(P(None, "tp", None),) * 3,
        out_specs=P(None, "tp", None),
    ))
    out = f(q, k, v)
    ref = mha_reference(q[None], k[None], v[None], causal=causal)[0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)


@pytest.mark.parametrize("method", ["xla", "pallas"])
def test_sp_decode_attention(ctx4, rng, method):
    """Append a token into the sequence-sharded cache, then attend.
    Parity: reference test_sp_decode_attn.py."""
    from triton_distributed_tpu.layers.sp_flash_decode import sp_decode_attention
    from triton_distributed_tpu.ops.attention import gqa_decode_reference

    b, hq, hkv, s, hd = 2, 4, 2, 256, 64
    q = jnp.asarray(rng.standard_normal((b, hq, hd)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((b, hkv, s, hd)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((b, hkv, s, hd)), jnp.float32)
    kn = jnp.asarray(rng.standard_normal((b, hkv, hd)), jnp.float32)
    vn = jnp.asarray(rng.standard_normal((b, hkv, hd)), jnp.float32)
    lens = jnp.asarray([100, 37], jnp.int32)

    f = jax.jit(ctx4.shard_map(
        functools.partial(
            sp_decode_attention, axis="tp", chunk_k=64, method=method, ctx=ctx4
        ),
        in_specs=(P(), P(), P(), P(None, None, "tp", None),
                  P(None, None, "tp", None), P()),
        out_specs=(P(), P(None, None, "tp", None), P(None, None, "tp", None)),
    ))
    out, kc2, vc2 = f(q, kn, vn, kc, vc, lens)

    # Golden: cache with the new token written at kv_len[b].
    kg, vg = np.array(kc), np.array(vc)
    for i in range(b):
        kg[i, :, int(lens[i])] = np.asarray(kn[i])
        vg[i, :, int(lens[i])] = np.asarray(vn[i])
    np.testing.assert_allclose(np.asarray(kc2), kg, atol=0, rtol=0)
    ref = gqa_decode_reference(q, jnp.asarray(kg), jnp.asarray(vg), lens + 1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)


@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2)])
def test_sp_ag_attention_2level(ctx2x4, rng, hq, hkv):
    """DCN×ICI two-level SP attention vs dense causal golden (parity:
    reference test_sp_ag_attention_inter_node.py)."""
    from triton_distributed_tpu.ops.attention import sp_ag_attention_2level

    # Small: 8 interpret devices share one CPU core and big per-device
    # buffers starve the XLA client (see conftest).
    s, hd = 128, 32  # 2 slices × 4 ranks → 16 rows per device
    q, k, v = _make(rng, hq, hkv, s, hd)

    f = jax.jit(ctx2x4.shard_map(
        functools.partial(
            sp_ag_attention_2level, inner_axis="tp", outer_axis="dp",
            block_q=16, ctx=ctx2x4,
        ),
        in_specs=(P(None, ("dp", "tp"), None),) * 3,
        out_specs=P(None, ("dp", "tp"), None),
    ))
    out = f(q, k, v)
    ref = mha_reference(q[None], k[None], v[None], causal=True)[0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)


@pytest.mark.parametrize("method", ["xla", "pallas"])
def test_distributed_flash_decode_2level(ctx2x4, rng, method):
    """Two-level (DCN×ICI) decode merge vs dense golden (parity:
    reference flash-decode multi-node scaling, README.md:202-209)."""
    from triton_distributed_tpu.ops.attention import (
        distributed_flash_decode_2level,
        gqa_decode_reference,
    )

    b, hq, hkv, s, hd = 2, 4, 2, 256, 64  # 8 shards × 32 positions
    q = jnp.asarray(rng.standard_normal((b, hq, hd)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((b, hkv, s, hd)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((b, hkv, s, hd)), jnp.float32)
    lens = jnp.asarray([200, 37], jnp.int32)

    f = jax.jit(ctx2x4.shard_map(
        functools.partial(
            distributed_flash_decode_2level, inner_axis="tp",
            outer_axis="dp", chunk_k=32, method=method, ctx=ctx2x4,
        ),
        in_specs=(P(), P(None, None, ("dp", "tp"), None),
                  P(None, None, ("dp", "tp"), None), P()),
        out_specs=P(),
    ))
    out = f(q, kc, vc, lens)
    ref = gqa_decode_reference(q, kc, vc, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)


def test_ring_attention_bf16(ctx4, rng):
    """Causal ring attention in bf16 vs the dense causal reference —
    at serving's own dtype."""
    from triton_distributed_tpu.ops.attention import (
        mha_reference,
        ring_attention,
    )

    s, hq, hkv, hd = 256, 4, 2, 64
    q = jnp.asarray(rng.standard_normal((hq, s, hd)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((hkv, s, hd)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((hkv, s, hd)), jnp.bfloat16)
    f = jax.jit(ctx4.shard_map(
        functools.partial(
            ring_attention, axis="tp", causal=True, block_q=64,
            block_k=64,
        ),
        in_specs=(P(None, "tp", None),) * 3,
        out_specs=P(None, "tp", None),
    ))
    out = f(q, k, v)
    assert out.dtype == jnp.bfloat16
    ref = mha_reference(
        q[None].astype(jnp.float32), k[None].astype(jnp.float32),
        v[None].astype(jnp.float32), causal=True,
    )[0]
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), atol=5e-2,
        rtol=5e-2,
    )


def test_distributed_flash_decode_2level_bf16(ctx2x4, rng):
    """Two-level (DCN×ICI) decode merge in bf16 vs the dense golden —
    at serving's own dtype."""
    from triton_distributed_tpu.ops.attention import (
        distributed_flash_decode_2level,
        gqa_decode_reference,
    )

    b, hq, hkv, s, hd = 2, 4, 2, 256, 64
    q = jnp.asarray(rng.standard_normal((b, hq, hd)), jnp.bfloat16)
    kc = jnp.asarray(rng.standard_normal((b, hkv, s, hd)), jnp.bfloat16)
    vc = jnp.asarray(rng.standard_normal((b, hkv, s, hd)), jnp.bfloat16)
    lens = jnp.asarray([200, 37], jnp.int32)
    f = jax.jit(ctx2x4.shard_map(
        functools.partial(
            distributed_flash_decode_2level, inner_axis="tp",
            outer_axis="dp", chunk_k=32, method="xla", ctx=ctx2x4,
        ),
        in_specs=(P(), P(None, None, ("dp", "tp"), None),
                  P(None, None, ("dp", "tp"), None), P()),
        out_specs=P(),
    ))
    out = f(q, kc, vc, lens)
    assert out.dtype == jnp.bfloat16
    ref = gqa_decode_reference(
        q.astype(jnp.float32), kc.astype(jnp.float32),
        vc.astype(jnp.float32), lens,
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), atol=5e-2,
        rtol=5e-2,
    )


def test_distributed_flash_decode_2level_int8(ctx2x4, rng):
    """Two-level decode over int8 shards with per-chunk scales: each
    rank dequantizes in-kernel, the (O, LSE) combine is unchanged."""
    from triton_distributed_tpu.models.paged_kv_cache import quantize_pages
    from triton_distributed_tpu.ops.attention import (
        distributed_flash_decode_2level,
        gqa_decode_reference,
    )

    b, hq, hkv, s, hd, chunk = 2, 4, 2, 256, 64, 32
    q = jnp.asarray(rng.standard_normal((b, hq, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, hkv, s, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, hkv, s, hd)), jnp.float32)
    lens = jnp.asarray([180, 47], jnp.int32)
    k_q, k_sc = quantize_pages(k.reshape(b, hkv, s // chunk, chunk, hd))
    v_q, v_sc = quantize_pages(v.reshape(b, hkv, s // chunk, chunk, hd))
    def shard_fn(q, k, v, lens, ks, vs):
        return distributed_flash_decode_2level(
            q, k, v, lens, inner_axis="tp", outer_axis="dp",
            chunk_k=chunk, method="xla", k_scale=ks, v_scale=vs,
            ctx=ctx2x4,
        )

    f = jax.jit(ctx2x4.shard_map(
        shard_fn,
        in_specs=(P(), P(None, None, ("dp", "tp"), None),
                  P(None, None, ("dp", "tp"), None), P(),
                  P(None, None, ("dp", "tp")),
                  P(None, None, ("dp", "tp"))),
        out_specs=P(),
    ))
    out = f(
        q, k_q.reshape(b, hkv, s, hd), v_q.reshape(b, hkv, s, hd),
        lens, k_sc, v_sc,
    )
    ref = gqa_decode_reference(q, k, v, lens)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=0.1, rtol=0.1
    )
