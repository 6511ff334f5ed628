"""Quantized paged KV cache (int8 per-page scales) coverage.

The contract under test (docs/serving.md "Quantized KV cache"):

- quant/dequant round-trips within the symmetric half-step bound,
- the int8 decode/prefill kernels dequantize in-register and match the
  full-width reference within the quantization tolerance (and match a
  reference over the DEQUANTIZED values to float tolerance — the kernel
  math is exactly ``(q @ codes) * scale``),
- prefix-shared pages carry their scales through refcounted sharing,
  COW clones, and eviction/recycling (a recycled page's stale scale is
  reset, never grown),
- ``rollback_kv`` stays consistent on a quantized pool (per-page scales
  are monotone within a page's lifetime, so truncation needs no scale
  write),
- the pool/radix auditor passes with quantization enabled,
- ``kv_dtype`` unset keeps the full-width pytree (and therefore every
  compiled program) bit-identical to the unquantized build.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from triton_distributed_tpu.models.paged_kv_cache import (
    PagedKVCache,
    append_n,
    as_dense,
    copy_page,
    dequantize_page,
    init_paged_cache,
    kv_bytes_per_token,
    paged_cache_specs,
    quantize_pages,
    quantized_row_scatter,
    rollback_kv,
)
from triton_distributed_tpu.ops.attention import (
    flash_attention,
    flash_decode,
    gqa_decode_reference,
    mha_reference,
    paged_flash_decode,
)
from triton_distributed_tpu.ops.attention.flash_decode import (
    distributed_flash_decode,
    scales_to_dense,
)


def test_quant_roundtrip_error_bound(rng):
    """Symmetric int8 round-trip: |x - deq(quant(x))| ≤ scale/2."""
    x = jnp.asarray(
        rng.standard_normal((3, 4, 16, 32)) * 5.0, jnp.float32
    )
    q, sc = quantize_pages(x)
    assert q.dtype == jnp.int8 and sc.shape == (3, 4)
    back = dequantize_page(q, sc)
    bound = np.asarray(sc)[..., None, None] * 0.5 + 1e-6
    assert np.all(np.abs(np.asarray(back) - np.asarray(x)) <= bound)
    # All-zero input: scale 0, codes 0, round-trip exact (no NaN).
    qz, sz = quantize_pages(jnp.zeros((1, 2, 8, 8)))
    assert np.all(np.asarray(sz) == 0) and np.all(np.asarray(qz) == 0)
    assert np.isfinite(np.asarray(dequantize_page(qz, sz))).all()


def _random_pool(rng, p, hkv, page, d):
    k = jnp.asarray(rng.standard_normal((p, hkv, page, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((p, hkv, page, d)), jnp.float32)
    return k, v


@pytest.mark.parametrize("lens", [
    [16 * 4, 21],   # a full table beside a short row
    [37, 1],        # ragged, and the engine's idle row
    [16, 48],       # exact page multiples
    [5, 5],
], ids=["full_and_short", "ragged_dead_slot", "page_multiples", "equal"])
def test_paged_flash_decode_int8_parity(rng, lens):
    """In-kernel dequant == reference over the dequantized view (float
    tolerance) == full-width reference (quant tolerance), over ragged
    lengths: every (slot, page) step reads ITS page's scales."""
    b, hq, hkv, page, pps, p, d = 2, 8, 2, 16, 4, 9, 32
    q = jnp.asarray(rng.standard_normal((b, hq, d)), jnp.float32)
    k_pool, v_pool = _random_pool(rng, p, hkv, page, d)
    table = jnp.asarray(
        rng.permutation(p - 1)[: b * pps].reshape(b, pps) + 0, jnp.int32
    )
    lens = jnp.asarray(lens, jnp.int32)
    k_q, k_sc = quantize_pages(k_pool)
    v_q, v_sc = quantize_pages(v_pool)
    out = paged_flash_decode(
        q, k_q, v_q, table, lens, k_scale=k_sc, v_scale=v_sc
    )
    # Exact contract: the kernel computes attention over codes*scale
    # (pure-XLA reference over the dequantized dense view — no second
    # kernel compile needed).
    from triton_distributed_tpu.ops.attention.flash_decode import (
        pages_to_dense,
    )

    k_deq = pages_to_dense(dequantize_page(k_q, k_sc), table)
    v_deq = pages_to_dense(dequantize_page(v_q, v_sc), table)
    ref_deq = gqa_decode_reference(q, k_deq, v_deq, lens)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref_deq), atol=2e-4, rtol=2e-4
    )
    # Accuracy contract vs the never-quantized values.
    ref_full = gqa_decode_reference(
        q, pages_to_dense(k_pool, table), pages_to_dense(v_pool, table),
        lens,
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref_full), atol=0.1, rtol=0.1
    )


@pytest.mark.parametrize("lens", [[16 * 4, 21], [37, 1]],
                         ids=["full_and_short", "ragged_dead_slot"])
def test_paged_flash_decode_int8_bf16_q_keeps_p_in_f32(rng, lens):
    """The served ``--kv-dtype int8`` form: bf16 q over int8 codes. The
    codes and q are exact in bf16 and the MXU accumulates in f32, so
    QK^T is the f32-q call's; P stays f32 for P.V. What is left between
    the two calls is the rounding of the OUTPUT to bf16 — a P rounded to
    bf16 moves a tenth of the elements off that rounding."""
    b, hq, hkv, page, pps, p, d = 2, 8, 2, 16, 4, 9, 32
    q = jnp.asarray(rng.standard_normal((b, hq, d)), jnp.bfloat16)
    k_pool, v_pool = _random_pool(rng, p, hkv, page, d)
    table = jnp.asarray(
        rng.permutation(p - 1)[: b * pps].reshape(b, pps), jnp.int32)
    lens = jnp.asarray(lens, jnp.int32)
    k_q, k_sc = quantize_pages(k_pool)
    v_q, v_sc = quantize_pages(v_pool)
    out = paged_flash_decode(
        q, k_q, v_q, table, lens, k_scale=k_sc, v_scale=v_sc)
    wide = paged_flash_decode(
        q.astype(jnp.float32), k_q, v_q, table, lens,
        k_scale=k_sc, v_scale=v_sc)
    assert out.dtype == jnp.bfloat16 and wide.dtype == jnp.float32
    got = np.asarray(out, np.float32)
    want = np.asarray(wide.astype(jnp.bfloat16), np.float32)
    # One bf16 step at most anywhere, and off the rounding almost never
    # (an f32 sum in another order can sit on a rounding boundary).
    np.testing.assert_allclose(got, want, atol=0, rtol=2 ** -7)
    assert np.mean(got != want) < 0.02


@pytest.mark.parametrize("layer", [1, 2], ids=["middle", "last"])
def test_paged_flash_decode_int8_layer_addressed(rng, layer):
    """Layer-addressed int8 decode over the whole pool (codes AND
    ``[L, P, Hkv]`` scales) == the 4-D call on that layer's slices, bit
    for bit."""
    n_layers, b, hq, hkv, page, pps, p, d = 3, 2, 8, 2, 16, 4, 9, 32
    q = jnp.asarray(rng.standard_normal((b, hq, d)), jnp.float32)
    pools = [_random_pool(rng, p, hkv, page, d) for _ in range(n_layers)]
    k_q, k_sc = quantize_pages(jnp.stack([k for k, _ in pools]))
    v_q, v_sc = quantize_pages(jnp.stack([v for _, v in pools]))
    table = jnp.asarray(
        rng.permutation(p - 1)[: b * pps].reshape(b, pps), jnp.int32)
    lens = jnp.asarray([page * pps, 21], jnp.int32)
    # Both sides jitted: the kernel's partials are the same bits, and
    # the LSE merge around them is then the same XLA fusion too.
    want = jax.jit(
        lambda: paged_flash_decode(
            q, k_q[layer], v_q[layer], table, lens,
            k_scale=k_sc[layer], v_scale=v_sc[layer])
    )()
    got = jax.jit(
        lambda lyr: paged_flash_decode(
            q, k_q, v_q, table, lens, layer=lyr, k_scale=k_sc, v_scale=v_sc)
    )(jnp.asarray(layer, jnp.int32))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    with pytest.raises(ValueError, match="per-page layout"):
        paged_flash_decode(
            q, k_q, v_q, table, lens, layer=layer,
            k_scale=k_sc[layer], v_scale=v_sc[layer],
        )


# (pages a table, lengths) at 16-token pages and a group of 4 pages a
# grid step: every page of a group reads ITS scales over its columns.
_INT8_GROUP_CASES = {
    "pps_not_a_multiple": (6, [6 * 16, 70]),
    "pps_below_the_group": (3, [3 * 16, 17]),
    "ends_mid_group": (8, [40, 100]),
    "ends_on_a_group_boundary": (8, [64, 8 * 16]),
    "empty_row_among_live": (8, [0, 90]),
}


@pytest.mark.parametrize("q_dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("pool_form", ["one_layer", "layer_of_pool"])
@pytest.mark.parametrize("case", list(_INT8_GROUP_CASES))
def test_paged_flash_decode_int8_groups(rng, monkeypatch, case, pool_form,
                                        q_dtype):
    """The grouped kernel over int8 codes: each page's per-head scales
    multiply its own columns of the scores and of P, the LSE included,
    against the plain formula over the dequantized view; the table
    entries past a group's live pages are never fetched (their scales
    are not even read: the pages are NaN-free codes, the scales NaN),
    and an empty row reads zeros."""
    import sys

    from triton_distributed_tpu.ops.attention.flash_decode import (
        pages_to_dense,
    )

    # The cases are named for a group of 4 pages a grid step.
    monkeypatch.setattr(
        sys.modules["triton_distributed_tpu.ops.attention.flash_decode"],
        "GROUP_PAGES", 4)
    n_layers, layer = 2, 1
    b, hq, hkv, page, d = 2, 8, 2, 16, 32
    pps, lens = _INT8_GROUP_CASES[case]
    p = b * pps + 1
    table = 1 + rng.permutation(p - 1).reshape(b, pps)
    pools = [_random_pool(rng, p, hkv, page, d) for _ in range(n_layers)]
    k_q, k_sc = quantize_pages(jnp.stack([k for k, _ in pools]))
    v_q, v_sc = quantize_pages(jnp.stack([v for _, v in pools]))
    live = {int(table[i, c]) for i, n in enumerate(lens)
            for c in range(-(-n // page))}
    dead = np.asarray([i not in live for i in range(p)])
    k_sc, v_sc = (jnp.where(dead[None, :, None], jnp.nan, sc)
                  for sc in (k_sc, v_sc))
    q = jnp.asarray(rng.standard_normal((b, hq, d)), q_dtype)
    table, lens = jnp.asarray(table, jnp.int32), jnp.asarray(lens, jnp.int32)
    if pool_form == "layer_of_pool":
        out, lse = jax.jit(lambda lyr: paged_flash_decode(
            q, k_q, v_q, table, lens, layer=lyr, k_scale=k_sc,
            v_scale=v_sc, return_lse=True))(jnp.asarray(layer, jnp.int32))
    else:
        out, lse = jax.jit(lambda: paged_flash_decode(
            q, k_q[layer], v_q[layer], table, lens, k_scale=k_sc[layer],
            v_scale=v_sc[layer], return_lse=True))()
    k_deq, v_deq = (
        jnp.nan_to_num(pages_to_dense(dequantize_page(c[layer], sc[layer]),
                                      table))
        for c, sc in ((k_q, k_sc), (v_q, v_sc)))
    gold, gold_lse = gqa_decode_reference(
        q.astype(jnp.float32), k_deq, v_deq, lens, return_lse=True)
    out = np.asarray(out, np.float32)
    assert np.isfinite(out).all()
    rows = np.asarray(lens) > 0
    tol = 2e-4 if q_dtype == "float32" else 2 ** -7  # the output's rounding
    np.testing.assert_allclose(
        out[rows], np.asarray(gold)[rows], atol=tol, rtol=tol)
    np.testing.assert_allclose(
        np.asarray(lse)[rows], np.asarray(gold_lse)[rows],
        atol=2e-4, rtol=2e-4)
    assert not out[~rows].any()


@pytest.mark.parametrize("writer", ["append", "chunk"])
def test_in_place_writers_int8_match_one_layer_scatter(rng, writer):
    """The serving programs' in-place writers on the WHOLE int8 pool
    (``layers/tp_attn.py``: the decode append's single-row updates, the
    chunk's page read-merge-write) leave the addressed layer's codes and
    scales bit-identical to ``quantized_row_scatter`` on that layer's
    slice — the one-layer form every earlier test pins — and every other
    layer untouched."""
    from triton_distributed_tpu.layers.tp_attn import (
        _append_rows,
        _write_chunk,
    )

    n_layers, p, h, page, d, layer = 3, 7, 2, 8, 16, 1
    pages = jnp.asarray(
        rng.integers(-127, 128, (n_layers, p, h, page, d)), jnp.int8)
    scales = jnp.asarray(
        rng.uniform(0.01, 0.05, (n_layers, p, h)), jnp.float32)
    lyr = jnp.asarray(layer, jnp.int32)
    if writer == "append":
        # Three sequences mid-page, one at offset 0 (scale reset), one
        # inactive slot on the trash page.
        rows = jnp.asarray(rng.standard_normal((5, h, d)) * 3, jnp.float32)
        pids = jnp.asarray([2, 5, 3, 6, 0], jnp.int32)
        offs = jnp.asarray([3, 7, 1, 0, 0], jnp.int32)
        got_p, got_s = jax.jit(_append_rows)(
            pages, scales, rows, lyr, pids, offs)
    else:
        # A 13-row chunk starting mid-page, 10 real rows: it crosses
        # into a second and third page; the pad rows touch nothing but
        # the trash page.
        table_row = jnp.asarray([4, 2, 6, 1], jnp.int32)
        start, n_real = 5, 10
        rows = jnp.asarray(rng.standard_normal((13, h, d)) * 3, jnp.float32)
        pos = start + np.arange(13)
        real = np.arange(13) < n_real
        pids = jnp.asarray(
            np.where(real, np.asarray(table_row)[pos // page], 0), jnp.int32)
        offs = jnp.asarray(np.where(real, pos % page, 0), jnp.int32)
        got_p, got_s = jax.jit(_write_chunk)(
            pages, scales, rows, lyr, table_row,
            jnp.asarray(start, jnp.int32), jnp.asarray(n_real, jnp.int32))
    want_p, want_s = quantized_row_scatter(
        pages[layer], scales[layer], rows, pids, offs)
    # Page 0 is the trash page: several rows land on one offset there
    # and which of them survives is nobody's contract.
    np.testing.assert_array_equal(
        np.asarray(got_p)[layer, 1:], np.asarray(want_p)[1:])
    np.testing.assert_array_equal(
        np.asarray(got_s)[layer, 1:], np.asarray(want_s)[1:])
    for other in (0, 2):
        np.testing.assert_array_equal(
            np.asarray(got_p)[other], np.asarray(pages)[other])
        np.testing.assert_array_equal(
            np.asarray(got_s)[other], np.asarray(scales)[other])


def test_flash_decode_dense_int8_parity(rng):
    """Dense split-KV kernel with per-chunk scales (the layout the
    distributed 1/2-level variants pass through)."""
    b, hq, hkv, s, d, chunk = 2, 8, 2, 256, 64, 64
    q = jnp.asarray(rng.standard_normal((b, hq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, hkv, s, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, hkv, s, d)), jnp.float32)
    lens = jnp.asarray([200, 47], jnp.int32)
    # Per-chunk quantization: [B, Hkv, C, chunk, d] blocks.
    kc = k.reshape(b, hkv, s // chunk, chunk, d)
    vc = v.reshape(b, hkv, s // chunk, chunk, d)
    k_q, k_sc = quantize_pages(kc)
    v_q, v_sc = quantize_pages(vc)
    out = flash_decode(
        q, k_q.reshape(b, hkv, s, d), v_q.reshape(b, hkv, s, d), lens,
        chunk_k=chunk, k_scale=k_sc, v_scale=v_sc,
    )
    ref = gqa_decode_reference(q, k, v, lens)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=0.1, rtol=0.1
    )
    with pytest.raises(ValueError, match="together"):
        flash_decode(q, k_q.reshape(b, hkv, s, d),
                     v_q.reshape(b, hkv, s, d), lens,
                     chunk_k=chunk, k_scale=k_sc)


def test_distributed_flash_decode_int8(ctx4, rng):
    """Sequence-sharded int8 decode: per-rank in-kernel dequant, then
    the unchanged (O, LSE) cross-rank combine."""
    b, hq, hkv, s, d, chunk = 2, 4, 2, 256, 64, 64
    q = jnp.asarray(rng.standard_normal((b, hq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, hkv, s, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, hkv, s, d)), jnp.float32)
    lens = jnp.asarray([180, 47], jnp.int32)
    kc = k.reshape(b, hkv, s // chunk, chunk, d)
    vc = v.reshape(b, hkv, s // chunk, chunk, d)
    k_q, k_sc = quantize_pages(kc)
    v_q, v_sc = quantize_pages(vc)

    def shard_fn(q, k, v, lens, ks, vs):
        return distributed_flash_decode(
            q, k, v, lens, axis="tp", chunk_k=chunk, method="xla",
            k_scale=ks, v_scale=vs, ctx=ctx4,
        )

    f = jax.jit(ctx4.shard_map(
        shard_fn,
        in_specs=(
            P(), P(None, None, "tp", None), P(None, None, "tp", None),
            P(), P(None, None, "tp"), P(None, None, "tp"),
        ),
        out_specs=P(),
    ))
    out = f(
        q, k_q.reshape(b, hkv, s, d), v_q.reshape(b, hkv, s, d), lens,
        k_sc, v_sc,
    )
    ref = gqa_decode_reference(q, k, v, lens)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=0.1, rtol=0.1
    )


def test_flash_attention_int8_parity(rng):
    """Prefill chunk kernel: int8 KV + per-block scales + kv_offset."""
    b, h, d, s_kv, s_q, blk = 1, 2, 32, 128, 32, 16
    q = jnp.asarray(rng.standard_normal((b, h, s_q, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, h, s_kv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, h, s_kv, d)), jnp.float32)
    kb = k.reshape(b, h, s_kv // blk, blk, d)
    vb = v.reshape(b, h, s_kv // blk, blk, d)
    k_q, k_sc = quantize_pages(kb)
    v_q, v_sc = quantize_pages(vb)
    off = s_kv - s_q
    out = flash_attention(
        q, k_q.reshape(b, h, s_kv, d), v_q.reshape(b, h, s_kv, d),
        causal=True, kv_offset=off, block_q=16, block_k=blk,
        k_scale=k_sc, v_scale=v_sc,
    )
    ref = mha_reference(q, k, v, causal=True, kv_offset=off)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=0.1, rtol=0.1
    )
    # Dynamic (traced) offset rides scalar prefetch on the same path.
    out_dyn = flash_attention(
        q, k_q.reshape(b, h, s_kv, d), v_q.reshape(b, h, s_kv, d),
        causal=True, kv_offset=jnp.asarray(off, jnp.int32),
        block_q=16, block_k=blk, k_scale=k_sc, v_scale=v_sc,
    )
    np.testing.assert_allclose(
        np.asarray(out_dyn), np.asarray(out), atol=2e-5, rtol=2e-5
    )


def test_quantized_row_scatter_reset_and_grow(rng):
    """A write at page offset 0 RESETS a recycled page's stale scale; a
    mid-page append grows the scale and requantizes earlier rows within
    the new half-step bound."""
    p, h, page, d = 4, 2, 8, 16
    pages = jnp.zeros((p, h, page, d), jnp.int8)
    # Stale tenant: huge scale left on page 2.
    scales = jnp.zeros((p, h), jnp.float32).at[2].set(1e6)
    rows1 = jnp.asarray(rng.standard_normal((4, h, d)), jnp.float32)
    pids = jnp.asarray([2, 2, 2, 2], jnp.int32)
    offs = jnp.asarray([0, 1, 2, 3], jnp.int32)
    pages, scales = quantized_row_scatter(pages, scales, rows1, pids, offs)
    sc_after = np.asarray(scales)[2]
    amax1 = np.max(np.abs(np.asarray(rows1)), axis=(0, 2)) / 127.0
    np.testing.assert_allclose(sc_after, amax1, rtol=1e-6)
    # Grow: append bigger rows mid-page; earlier rows stay within the
    # grown half-step bound.
    rows2 = jnp.asarray(rng.standard_normal((2, h, d)) * 10.0, jnp.float32)
    pages, scales = quantized_row_scatter(
        pages, scales, rows2, jnp.asarray([2, 2], jnp.int32),
        jnp.asarray([4, 5], jnp.int32),
    )
    sc2 = np.asarray(scales)[2]
    assert np.all(sc2 >= sc_after - 1e-9)
    deq = np.asarray(
        dequantize_page(pages, scales)
    )[2][:, :4]  # [h, first 4 rows, d]
    want = np.asarray(rows1).transpose(1, 0, 2)
    # One quantization + one requantization: ≤ 2 half-steps.
    assert np.all(np.abs(deq - want) <= sc2[:, None, None] * 1.0 + 1e-6)


def test_append_n_sequential_scale_protocol(rng):
    """``append_n`` on an int8 pool must leave the pool BIT-IDENTICAL
    to NS single-row ``append`` calls over the same rows: the megakernel
    NS-launch retires pages into the radix tree that unfused serving
    also produces, so the scale grow/requant EVENT ORDER — not just the
    values — must match (append_n sequences its per-step scatters for
    exactly this)."""
    from triton_distributed_tpu.models.paged_kv_cache import append

    L, B, H, NS, page, hd, P_ = 2, 2, 2, 5, 4, 8, 6
    cache = PagedKVCache(
        k_pages=jnp.zeros((L, P_, H, page, hd), jnp.int8),
        v_pages=jnp.zeros((L, P_, H, page, hd), jnp.int8),
        page_table=jnp.asarray([[1, 2, 0], [3, 4, 0]], jnp.int32),
        kv_len=jnp.asarray([2, 3], jnp.int32),
        k_scale=jnp.zeros((L, P_, H), jnp.float32),
        v_scale=jnp.zeros((L, P_, H), jnp.float32),
    )
    # Row magnitudes GROW per step so every append forces a scale grow
    # + requant of the earlier rows — the order-sensitive case.
    k_new = jnp.asarray(
        rng.standard_normal((L, B, H, NS, hd))
        * (2.0 ** np.arange(NS))[None, None, None, :, None],
        jnp.float32,
    )
    v_new = jnp.asarray(rng.standard_normal((L, B, H, NS, hd)),
                        jnp.float32)
    batch = append_n(cache, k_new, v_new)
    seq = cache
    for s in range(NS):
        seq = append(seq, k_new[:, :, :, s, :], v_new[:, :, :, s, :])
    np.testing.assert_array_equal(
        np.asarray(batch.k_pages), np.asarray(seq.k_pages)
    )
    np.testing.assert_array_equal(
        np.asarray(batch.k_scale), np.asarray(seq.k_scale)
    )
    np.testing.assert_array_equal(
        np.asarray(batch.v_pages), np.asarray(seq.v_pages)
    )
    np.testing.assert_array_equal(
        np.asarray(batch.kv_len), np.asarray(seq.kv_len)
    )


def test_append_n_trash_routes_overshoot(rng):
    """``n_valid`` routes a finishing row's guaranteed-overshoot rows
    to the trash page: the sequence's own pages (the ones that retire
    into the radix tree) keep codes AND scales free of garbage-row
    contamination."""
    L, B, H, NS, page, hd, P_ = 1, 2, 1, 4, 4, 8, 4
    cache = PagedKVCache(
        k_pages=jnp.zeros((L, P_, H, page, hd), jnp.int8),
        v_pages=jnp.zeros((L, P_, H, page, hd), jnp.int8),
        page_table=jnp.asarray([[1, 2], [3, 0]], jnp.int32),
        kv_len=jnp.asarray([1, 0], jnp.int32),
        k_scale=jnp.zeros((L, P_, H), jnp.float32),
        v_scale=jnp.zeros((L, P_, H), jnp.float32),
    )
    rows = jnp.asarray(rng.standard_normal((L, B, H, NS, hd)),
                       jnp.float32)
    # Row 0 keeps 2 of 4 rows; row 1 keeps all 4. Make row 0's
    # overshoot HUGE: without routing it would inflate page 1's scale.
    rows = rows.at[:, 0, :, 2:, :].multiply(100.0)
    full = append_n(cache, rows, rows)
    routed = append_n(
        cache, rows, rows, n_valid=jnp.asarray([2, 4], jnp.int32)
    )
    # Routed: page 1 (slot 0's page) scale covers only the 2 kept rows.
    assert float(routed.k_scale[0, 1, 0]) < float(full.k_scale[0, 1, 0])
    # Slot 1 untouched by routing.
    np.testing.assert_array_equal(
        np.asarray(routed.k_pages[:, 3]), np.asarray(full.k_pages[:, 3])
    )
    # Overshoot landed on the trash page (page 0), nowhere else; the
    # kept rows dequantize the same values as an un-routed append of
    # just those rows would.
    clean = append_n(
        cache, rows[:, :, :, :2, :], rows[:, :, :, :2, :],
        n_valid=jnp.asarray([2, 2], jnp.int32),
    )
    np.testing.assert_array_equal(
        np.asarray(routed.k_pages[:, 1]), np.asarray(clean.k_pages[:, 1])
    )


def test_engine_int8_teacher_forced_close(own_model, rng):
    """Documented accuracy tolerance on the tier-1 smoke model: with the
    SAME token stream fed to a full-width and an int8 engine cache, the
    per-step logits stay within atol 0.25 and the greedy argmax agrees
    on ≥ 80% of steps (the rare flips happen where the full-width
    model's own top1-top2 gap is below the quantization noise)."""
    from triton_distributed_tpu.models.paged_kv_cache import write_prefill

    prompt = rng.integers(1, 200, size=(2, 24)).astype(np.int32)

    def build(kv_dtype):
        cache, _pool = init_paged_cache(
            own_model.cfg, 2, own_model.ctx, "tp", max_length=128,
            page_size=16, kv_dtype=kv_dtype,
        )
        dense1 = own_model.new_cache(1, 128)
        logits = []
        for i in range(2):
            lg, dense1 = own_model.prefill_batched(
                jnp.asarray(prompt[i : i + 1]), dense1, "xla",
                jnp.asarray([24], np.int32),
            )
            cache = write_prefill(cache, i, dense1.k, dense1.v, 24)
            logits.append(lg[0])
        return jnp.stack(logits), cache

    lf, cf = build(None)
    lq, cq = build("int8")
    # Prefill logits come from the dense forward BEFORE the quantized
    # scatter — identical by construction.
    np.testing.assert_array_equal(np.asarray(lf), np.asarray(lq))
    assert cq.quantized and cq.k_pages.dtype == jnp.int8
    assert kv_bytes_per_token(cq) < kv_bytes_per_token(cf) / 1.9

    tok = jnp.argmax(lf, -1).astype(jnp.int32)
    steps, agree, max_diff = 6, 0, 0.0
    for _ in range(steps):
        lgf, cf = own_model.decode_step(tok, cf, "xla")
        lgq, cq = own_model.decode_step(tok, cq, "xla")
        max_diff = max(max_diff, float(jnp.max(jnp.abs(lgf - lgq))))
        agree += int((jnp.argmax(lgf, -1) == jnp.argmax(lgq, -1)).sum())
        tok = jnp.argmax(lgf, -1).astype(jnp.int32)
    assert max_diff < 0.25, f"int8 KV perturbed logits by {max_diff}"
    assert agree >= int(0.8 * 2 * steps), f"argmax agreement {agree}/{2*steps}"


def test_rollback_scales_lockstep(rng):
    """Speculative rollback on a quantized pool: truncate, re-append
    different rows, and the dequantized live prefix still matches the
    full-width history within the quant bound (scales never shrink, so
    the retained rows' codes stay exact)."""
    p, h, page, d, L = 5, 2, 8, 16, 1
    cache = PagedKVCache(
        k_pages=jnp.zeros((L, p, h, page, d), jnp.int8),
        v_pages=jnp.zeros((L, p, h, page, d), jnp.int8),
        page_table=jnp.asarray([[1, 2]], jnp.int32),
        kv_len=jnp.zeros((1,), jnp.int32),
        k_scale=jnp.zeros((L, p, h), jnp.float32),
        v_scale=jnp.zeros((L, p, h), jnp.float32),
    )
    hist_k = []

    def rows():
        r = jnp.asarray(rng.standard_normal((L, 1, h, 1, d)), jnp.float32)
        return r

    for _ in range(6):  # fill 6 rows
        rk, rv = rows(), rows()
        hist_k.append(np.asarray(rk)[:, 0, :, 0])
        cache = append_n(cache, rk, rv)
    # Speculative overshoot: 2 more rows, then reject them.
    cache = append_n(cache, rows(), rows())
    cache = append_n(cache, rows(), rows())
    assert int(cache.kv_len[0]) == 8
    cache = rollback_kv(cache, 0, 6)
    assert int(cache.kv_len[0]) == 6
    # Scales were untouched by the rollback (monotone upper bound).
    sc_before = np.asarray(cache.k_scale)
    # Re-append two fresh rows past the rollback point.
    for _ in range(2):
        rk, rv = rows(), rows()
        hist_k.append(np.asarray(rk)[:, 0, :, 0])
        cache = append_n(cache, rk, rv)
    assert np.all(np.asarray(cache.k_scale) >= sc_before - 1e-9)
    k_dense, _ = as_dense(cache)  # [L, 1, h, S, d] dequantized
    got = np.asarray(k_dense)[:, 0, :, :8]
    want = np.stack(hist_k, axis=2)  # [L, h, 8, d]
    sc = np.asarray(cache.k_scale)  # upper bound on any page's half-step
    # Each of the up-to-7 scale-growing appends requantizes earlier
    # rows by ≤ half a step; bound the accumulated error generously.
    tol = sc.max() * 4.0 + 1e-6
    assert np.all(np.abs(got - want) <= tol)


def test_write_prefill_ignores_stale_scratch_rows(rng):
    """The dense prefill scratch is reused across admissions, so rows
    beyond ``true_len`` hold a PREVIOUS request's KV — the quantized
    scatter must zero them out: same prompt after different
    predecessors must produce byte-identical codes and scales."""
    from triton_distributed_tpu.models.paged_kv_cache import write_prefill

    L, H, S, hd, page = 1, 2, 32, 16, 16
    base = rng.standard_normal((L, 1, H, S, hd)).astype(np.float32)
    g1, g2 = base.copy(), base.copy()
    g1[..., 24:, :] = 77.7     # stale garbage variant A (inflates amax)
    g2[..., 24:, :] = -0.001   # stale garbage variant B

    def fresh():
        return PagedKVCache(
            k_pages=jnp.zeros((L, 4, H, page, hd), jnp.int8),
            v_pages=jnp.zeros((L, 4, H, page, hd), jnp.int8),
            page_table=jnp.asarray([[1, 2]], jnp.int32),
            kv_len=jnp.zeros((1,), jnp.int32),
            k_scale=jnp.zeros((L, 4, H), jnp.float32),
            v_scale=jnp.zeros((L, 4, H), jnp.float32),
        )

    c1 = write_prefill(fresh(), 0, jnp.asarray(g1), jnp.asarray(g1), 24)
    c2 = write_prefill(fresh(), 0, jnp.asarray(g2), jnp.asarray(g2), 24)
    np.testing.assert_array_equal(np.asarray(c1.k_pages),
                                  np.asarray(c2.k_pages))
    np.testing.assert_array_equal(np.asarray(c1.k_scale),
                                  np.asarray(c2.k_scale))
    # And the codes beyond true_len are zero, not quantized garbage.
    assert not np.asarray(c1.k_pages)[:, 2, :, 8:].any()


def test_copy_page_carries_scales(rng):
    L, p, h, page, d = 2, 4, 2, 8, 16
    k = jnp.asarray(rng.standard_normal((L, p, h, page, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((L, p, h, page, d)), jnp.float32)
    k_q, k_sc = quantize_pages(k)
    v_q, v_sc = quantize_pages(v)
    # Snapshot before the copy: copy_page DONATES the cache arrays.
    k_q_np, k_sc_np = np.asarray(k_q), np.asarray(k_sc)
    v_sc_np = np.asarray(v_sc)
    cache = PagedKVCache(
        k_pages=k_q, v_pages=v_q,
        page_table=jnp.zeros((1, 2), jnp.int32),
        kv_len=jnp.zeros((1,), jnp.int32),
        k_scale=k_sc, v_scale=v_sc,
    )
    out = copy_page(cache, 1, 3)
    np.testing.assert_array_equal(np.asarray(out.k_pages)[:, 3], k_q_np[:, 1])
    np.testing.assert_array_equal(np.asarray(out.k_scale)[:, 3], k_sc_np[:, 1])
    np.testing.assert_array_equal(np.asarray(out.v_scale)[:, 3], v_sc_np[:, 1])


def test_prefix_cow_audit_and_speculative_with_quant(own_model, rng):
    """One serving pass over an int8 pool covering three contracts:

    - a PAGE-ALIGNED shared prefix reuses the cold run's quantized
      pages verbatim → warm output == cold output bit-for-bit,
    - a COW (mid-page) match clones codes+scale and serves cleanly,
    - the pool/radix invariant auditor stays empty throughout,
      including under speculative decoding's verify/rollback churn."""
    from triton_distributed_tpu.models.continuous import ContinuousEngine

    system = rng.integers(1, 200, size=32).astype(np.int32)  # 2 full pages

    # First suffix token differs per arrival → the radix walk stops
    # at the page boundary (no shared child), i.e. no COW.
    reqs = [
        (np.concatenate(
            [system, np.asarray([200 + i], np.int32),
             rng.integers(1, 200, size=7).astype(np.int32)]
        ), 4)
        for i in range(2)
    ]
    warm = ContinuousEngine(
        own_model, max_batch=1, page_size=16, max_length=128,
        prefix_cache=True, kv_dtype="int8",
    )
    cold_outs = [warm.run([r])[0] for r in reqs]   # seeds the tree
    warm_outs = [warm.run([r])[0] for r in reqs]   # reuses shared pages
    assert warm.last_stats["prefix_hit_tokens"] > 0
    for c, w in zip(cold_outs, warm_outs):
        np.testing.assert_array_equal(c, w)
    assert warm.audit() == []
    st = warm.last_stats
    assert st["kv_dtype"] == "int8"
    cfg = own_model.cfg
    assert st["kv_bytes_per_token"] < 2 * cfg.num_layers * \
        cfg.num_kv_heads * cfg.head_dim * 2  # < bf16 layout

    # COW path: an arrival sharing a PARTIAL page (prompt diverges
    # mid-page) clones codes+scale and must serve cleanly.
    base = np.concatenate(
        [system, rng.integers(1, 200, size=8).astype(np.int32)]
    )
    alt = base.copy()
    alt[-2:] = (base[-2:] + 1) % 200 + 1  # diverge inside the tail page
    warm.run([(base, 4)])
    warm.run([(alt, 4)])
    assert warm.last_stats["pages_cow_copied"] >= 1
    assert warm.audit() == []

    # Speculative verify/rollback over the same quantized pool (the
    # repetitive prompt guarantees n-gram drafts, hence rollbacks).
    spec = ContinuousEngine(
        own_model, max_batch=2, page_size=16, max_length=128,
        prefix_cache=True, speculative=3, kv_dtype="int8",
    )
    prompt = np.tile(rng.integers(1, 200, size=8).astype(np.int32), 4)
    outs = spec.run([(prompt, 5), (prompt[:20], 4)])
    assert [len(o) for o in outs] == [5, 4]
    assert spec.audit() == []


def test_bf16_bit_identical_when_unset_and_validation(own_model):
    """kv_dtype unset: the cache pytree (dtypes, structure, specs) is
    EXACTLY the pre-quantization layout — no scale leaves, pool in
    cfg.dtype — so every compiled program and its donation/sharding
    behavior is unchanged. Plus the knob's validation surface."""
    from triton_distributed_tpu.models.continuous import ContinuousEngine
    from triton_distributed_tpu.models.engine import Engine

    cache, _pool = init_paged_cache(
        own_model.cfg, 2, own_model.ctx, "tp", max_length=128, page_size=16
    )
    assert cache.k_scale is None and cache.v_scale is None
    assert not cache.quantized
    assert cache.k_pages.dtype == own_model.cfg.dtype
    # EXACTLY four array leaves — scale fields are empty subtrees, so
    # every jitted program sees the pre-quantization pytree (same
    # donation indices, same shardings, same compiled cache keys).
    assert len(jax.tree.leaves(cache)) == 4
    specs = paged_cache_specs("tp")
    assert specs.k_scale is None and specs.v_scale is None
    # kv_len-only ops keep the scale-less layout.
    assert rollback_kv(cache, 0, 0).k_scale is None

    with pytest.raises(ValueError, match="unsupported"):
        init_paged_cache(own_model.cfg, 1, own_model.ctx, "tp", kv_dtype="fp8")
    with pytest.raises(ValueError, match="paged"):
        Engine(own_model, kv_dtype="int8")
    # PR 7: kv_dtype COMPOSES with mode="mega" (the fused decode
    # dequantizes the int8 pool in-kernel) — construction must succeed;
    # the one remaining mega exclusion is speculative.
    Engine(own_model, paged=True, mode="mega", kv_dtype="int8")
    ContinuousEngine(own_model, mode="mega", kv_dtype="int8")
    with pytest.raises(ValueError, match="speculative"):
        ContinuousEngine(own_model, mode="mega", kv_dtype="int8",
                         speculative=4)
    # cfg-level default plumbs through without the explicit knob.
    cfg = dataclasses.replace(own_model.cfg, kv_dtype="int8")
    qcache, _ = init_paged_cache(cfg, 1, own_model.ctx, "tp", max_length=128,
                                 page_size=16)
    assert qcache.quantized and qcache.k_pages.dtype == jnp.int8


def test_scales_to_dense_layout():
    scales = jnp.arange(3 * 2, dtype=jnp.float32).reshape(3, 2)  # [P, H]
    table = jnp.asarray([[2, 0]], jnp.int32)
    out = scales_to_dense(scales, table, page=4)  # [1, H, 8]
    assert out.shape == (1, 2, 8)
    np.testing.assert_array_equal(
        np.asarray(out)[0, 1], np.asarray([5, 5, 5, 5, 1, 1, 1, 1], np.float32)
    )
