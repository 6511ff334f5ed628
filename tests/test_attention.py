"""Attention kernel tests (parity: test_decode_attn.py, test_sp_decode_attn.py
— golden = dense softmax attention)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from triton_distributed_tpu.ops.attention import (
    distributed_flash_decode,
    flash_attention,
    flash_decode,
    gqa_decode_reference,
    mha_reference,
)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("hq,hkv", [(4, 4), (8, 2)])
def test_flash_attention(rng, causal, hq, hkv):
    b, s, d = 2, 256, 64
    q = jnp.asarray(rng.standard_normal((b, hq, s, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, hkv, s, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, hkv, s, d)), jnp.float32)
    out = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_flash_attention_lse(rng):
    b, h, s, d = 1, 2, 128, 64
    q = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    out, lse = flash_attention(q, k, v, causal=True, return_lse=True, block_q=64)
    ref, ref_lse = mha_reference(q, k, v, causal=True, return_lse=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse), atol=2e-5,
                               rtol=2e-5)


def test_flash_attention_kv_offset(rng):
    """Chunked prefill: q is the tail chunk of a longer sequence."""
    b, h, d = 1, 2, 64
    s_kv, s_q = 256, 64
    q = jnp.asarray(rng.standard_normal((b, h, s_q, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, h, s_kv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, h, s_kv, d)), jnp.float32)
    off = s_kv - s_q
    out = flash_attention(q, k, v, causal=True, kv_offset=off, block_q=64)
    ref = mha_reference(q, k, v, causal=True, kv_offset=off)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("kv_len", [1, 100, 512])
def test_flash_decode(rng, kv_len):
    b, hq, hkv, s, d = 2, 8, 2, 512, 64
    q = jnp.asarray(rng.standard_normal((b, hq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, hkv, s, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, hkv, s, d)), jnp.float32)
    lens = jnp.full((b,), kv_len, jnp.int32)
    out = flash_decode(q, k, v, lens, chunk_k=128)
    ref = gqa_decode_reference(q, k, v, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("method", ["xla", "pallas"])
def test_distributed_flash_decode(ctx4, rng, method):
    """KV cache sequence-sharded over 4 devices; cross-rank LSE combine."""
    b, hq, hkv, s, d = 2, 4, 2, 512, 64
    q = jnp.asarray(rng.standard_normal((b, hq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, hkv, s, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, hkv, s, d)), jnp.float32)
    lens = jnp.asarray([300, 47], jnp.int32)

    f = ctx4.shard_map(
        functools.partial(
            distributed_flash_decode, axis="tp", chunk_k=64, method=method,
            ctx=ctx4,
        ),
        in_specs=(P(), P(None, None, "tp", None), P(None, None, "tp", None), P()),
        out_specs=P(),
    )
    out = f(q, k, v, lens)
    ref = gqa_decode_reference(q, k, v, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("layer", [1, 2], ids=["middle", "last"])
def test_paged_flash_decode_layer_addressed(rng, layer):
    """The served form — the whole ``[L, P, Hkv, page, d]`` pool plus a
    (traced) layer index, folded into the page table — reads exactly the
    pages the 4-D call reads from ``pool[layer]``: bit for bit."""
    from triton_distributed_tpu.ops.attention import paged_flash_decode

    n_layers, b, hq, hkv, d, page, pps = 3, 2, 4, 2, 64, 16, 4
    p = 2 * b * pps
    table = jnp.asarray(
        rng.permutation(p)[: b * pps].reshape(b, pps), jnp.int32)
    k_pool = jnp.asarray(
        rng.standard_normal((n_layers, p, hkv, page, d)), jnp.bfloat16)
    v_pool = jnp.asarray(
        rng.standard_normal((n_layers, p, hkv, page, d)), jnp.bfloat16)
    q = jnp.asarray(rng.standard_normal((b, hq, d)), jnp.bfloat16)
    lens = jnp.asarray([37, 18], jnp.int32)

    # Both sides jitted, so the LSE merge around the kernel is the same
    # XLA fusion and the comparison is of the pages read.
    want, want_lse = jax.jit(
        lambda: paged_flash_decode(
            q, k_pool[layer], v_pool[layer], table, lens, return_lse=True)
    )()
    got, got_lse = jax.jit(
        lambda lyr: paged_flash_decode(
            q, k_pool, v_pool, table, lens, layer=lyr, return_lse=True)
    )(jnp.asarray(layer, jnp.int32))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got_lse), np.asarray(want_lse))
    # Another layer's pages give another answer: the index is honoured.
    other = paged_flash_decode(q, k_pool, v_pool, table, lens, layer=0)
    assert not np.array_equal(np.asarray(other), np.asarray(want))
    with pytest.raises(ValueError, match="layer"):
        paged_flash_decode(q, k_pool, v_pool, table, lens)
    with pytest.raises(ValueError, match="layer"):
        paged_flash_decode(q, k_pool[0], v_pool[0], table, lens, layer=0)


def test_paged_flash_decode_walk_ends_at_longest_sequence(rng):
    """The grid's page axis stops at the longest live sequence's last
    page (a dynamic bound): short contexts in a long table give the
    dense answer, and the chunks never walked (NaN-filled by interpret
    mode) leave no trace in it."""
    from triton_distributed_tpu.ops.attention import (
        gqa_decode_reference,
        paged_flash_decode,
    )
    from triton_distributed_tpu.ops.attention.flash_decode import (
        pages_to_dense,
    )

    b, hq, hkv, d, page, pps = 3, 4, 2, 64, 16, 8
    p = b * pps + 1
    table = jnp.asarray(
        1 + rng.permutation(p - 1).reshape(b, pps), jnp.int32)
    k_pool = jnp.asarray(rng.standard_normal((p, hkv, page, d)), jnp.float32)
    v_pool = jnp.asarray(rng.standard_normal((p, hkv, page, d)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((b, hq, d)), jnp.float32)
    for lens in ([5, 20, 1], [1, 1, 1], [16 * 8, 3, 40]):
        lens = jnp.asarray(lens, jnp.int32)
        out, lse = paged_flash_decode(
            q, k_pool, v_pool, table, lens, return_lse=True)
        gold, gold_lse = gqa_decode_reference(
            q, pages_to_dense(k_pool, table), pages_to_dense(v_pool, table),
            lens, return_lse=True)
        assert np.isfinite(np.asarray(out)).all()
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(gold), atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(
            np.asarray(lse), np.asarray(gold_lse), atol=2e-5, rtol=2e-5)
