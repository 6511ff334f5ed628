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

    f = jax.jit(ctx4.shard_map(
        functools.partial(
            distributed_flash_decode, axis="tp", chunk_k=64, method=method,
            ctx=ctx4,
        ),
        in_specs=(P(), P(None, None, "tp", None), P(None, None, "tp", None), P()),
        out_specs=P(),
    ))
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


# Context lengths of three sequences over a table of 8 pages of 16: what
# the one-axis walk over live (slot, page) pairs has to get right.
_WALK_LENS = {
    "one_full_among_short": [16 * 8, 3, 40],   # a context of pps * page
    "full_context_last": [5, 20, 16 * 8],
    "dead_slot": [5, 20, 1],                   # the engine's idle row
    "all_dead": [1, 1, 1],
    "exact_page_multiples": [16, 48, 32],
    "all_equal": [40, 40, 40],
}


_ONE_LAYER: dict = {}


@pytest.mark.parametrize("return_lse", [True, False], ids=["lse", "no_lse"])
@pytest.mark.parametrize("pool_form", ["one_layer", "layer_of_pool"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(_WALK_LENS))
def test_paged_flash_decode_walk(rng, case, dtype, pool_form, return_lse):
    """One grid step per live (slot, page) pair, every KV head in the
    block, the softmax online across a sequence's pages: ragged contexts
    in a long table give the dense answer, a short or dead row costs
    (and reads) its own pages only, and the table entries never walked
    (their pages NaN here) leave no trace. ``layer_of_pool``: the served
    form reads what the one-layer call reads from ``pool[layer]``, bit
    for bit."""
    from triton_distributed_tpu.ops.attention import paged_flash_decode
    from triton_distributed_tpu.ops.attention.flash_decode import (
        pages_to_dense,
    )

    n_layers, layer = 3, 1
    b, hq, hkv, d, page, pps = 3, 8, 2, 64, 16, 8
    lens = _WALK_LENS[case]
    p = b * pps + 1
    table = 1 + rng.permutation(p - 1).reshape(b, pps)
    k_pool = rng.standard_normal((n_layers, p, hkv, page, d))
    v_pool = rng.standard_normal((n_layers, p, hkv, page, d))
    # Poison every page no live (slot, page) pair names: a walk that
    # fetched one for use would carry the NaN into the answer.
    live = {0} | {int(table[i, c]) for i, n in enumerate(lens)
                  for c in range(-(-n // page))}
    dead = [i for i in range(p) if i not in live]
    k_pool[:, dead] = v_pool[:, dead] = np.nan
    k_pool, v_pool = (jnp.asarray(a, dtype) for a in (k_pool, v_pool))
    q = jnp.asarray(rng.standard_normal((b, hq, d)), dtype)
    table, lens = jnp.asarray(table, jnp.int32), jnp.asarray(lens, jnp.int32)

    # The one-layer answer is the same for both pool forms (``rng`` is
    # seeded alike for every case): compiled and run once for the two.
    key = (case, dtype, return_lse)
    if key not in _ONE_LAYER:
        _ONE_LAYER[key] = jax.jit(
            lambda: paged_flash_decode(
                q, k_pool[layer], v_pool[layer], table, lens,
                return_lse=return_lse)
        )()
    got = one_layer = _ONE_LAYER[key]
    if pool_form == "layer_of_pool":
        got = jax.jit(
            lambda lyr: paged_flash_decode(
                q, k_pool, v_pool, table, lens, layer=lyr,
                return_lse=return_lse)
        )(jnp.asarray(layer, jnp.int32))
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(one_layer)):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    # Rows past a length are masked in the reference; NaN * 0 is not 0.
    k_d, v_d = (
        jnp.nan_to_num(pages_to_dense(a[layer], table))
        for a in (k_pool, v_pool)
    )
    gold, gold_lse = gqa_decode_reference(q, k_d, v_d, lens, return_lse=True)
    out, lse = got if return_lse else (got, None)
    assert out.dtype == q.dtype and out.shape == q.shape
    assert np.isfinite(np.asarray(out, np.float32)).all()
    tol = 2e-5 if dtype == "float32" else 2e-2  # bf16: P rounds for P.V
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(gold, np.float32),
        atol=tol, rtol=tol)
    if return_lse:
        assert lse.shape == (b, hq) and lse.dtype == jnp.float32
        np.testing.assert_allclose(
            np.asarray(lse), np.asarray(gold_lse), atol=2e-5, rtol=2e-5)


def test_paged_flash_decode_hoisted_walk(rng):
    """``walk=`` (the served step derives it once, outside its layer
    scan) is the walk the call would derive itself, bit for bit; one
    built for another batch or table is refused by its shape."""
    from triton_distributed_tpu.ops.attention import (
        paged_decode_walk,
        paged_flash_decode,
    )

    b, hq, hkv, d, page, pps = 3, 8, 2, 64, 16, 8
    p = b * pps + 1
    table = jnp.asarray(1 + rng.permutation(p - 1).reshape(b, pps), jnp.int32)
    k_pool = jnp.asarray(rng.standard_normal((p, hkv, page, d)), jnp.float32)
    v_pool = jnp.asarray(rng.standard_normal((p, hkv, page, d)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((b, hq, d)), jnp.float32)
    lens = jnp.asarray([16 * 8, 3, 40], jnp.int32)
    want = paged_flash_decode(q, k_pool, v_pool, table, lens)
    got = paged_flash_decode(
        q, k_pool, v_pool, table, lens,
        walk=paged_decode_walk(lens, page, pps))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    with pytest.raises(ValueError, match="paged_decode_walk"):
        paged_flash_decode(
            q, k_pool, v_pool, table, lens,
            walk=paged_decode_walk(lens[:2], page, pps))


@pytest.mark.parametrize("lens,page,pps,steps", [
    # The parent's grid took slots x heads x the LONGEST walk: 4 x 8 x 32
    # = 1,024 steps for one context of 4,096 among three of 300.
    ([4096, 300, 300, 300], 128, 32, 41),
    ([411, 300, 520, 180], 128, 32, 4 + 3 + 5 + 2),  # chat-closed8's shape
    ([128, 256, 129, 1], 128, 32, 1 + 2 + 2 + 1),    # exact multiples
    ([1, 1, 1, 1, 1, 1, 1, 1], 128, 32, 8),          # eight idle rows
    ([0, 5000], 128, 32, 1 + 32),      # an empty row still writes; the
                                       # table's end bounds a walk
    ([40, 40, 40], 16, 8, 9),
], ids=["one_long_among_short", "closed8", "page_multiples", "idle",
        "clipped", "equal"])
def test_paged_decode_walk_counts_live_pairs(lens, page, pps, steps):
    """The kernel's grid as a COUNT: ``sum(ceil(len / page))`` steps, in
    slot order, each naming a table entry inside its sequence's length
    (what tells a long walk from a short one; the wrapper sizes its grid
    with this same function)."""
    from triton_distributed_tpu.ops.attention import paged_decode_walk

    slot, page_of, n = paged_decode_walk(jnp.asarray(lens), page, pps)
    n = int(n)
    per_slot = [min(max(-(-x // page), 1), pps) for x in lens]
    assert n == steps == sum(per_slot)
    assert slot.shape == page_of.shape == (len(lens) * pps,)
    want = [(i, c) for i, k in enumerate(per_slot) for c in range(k)]
    assert list(zip(np.asarray(slot)[:n].tolist(),
                    np.asarray(page_of)[:n].tolist())) == want
    # Entries past the grid's end are never read, and stay in range.
    assert (np.asarray(slot) < len(lens)).all()
    assert (np.asarray(page_of) < pps).all() and (np.asarray(page_of) >= 0).all()
