"""The plain reference against the program at `tiny`: the same weights
from the same seed, and the same logits for prefill-then-decode through
the paged cache; and its lower-precision control, which has to read far
above what a sound program reads."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference

DATA = os.path.join(os.path.dirname(__file__), "data")
SEED = 2**31 + 17


def tiny_dims():
    with open(os.path.join(DATA, "tiny.config.json")) as f:
        return reference.Dims.of(json.load(f))


@pytest.fixture(scope="module")
def tiny_model():
    from triton_distributed_tpu.models import AutoLLM
    from triton_distributed_tpu.runtime import mesh

    ctx = mesh.initialize_distributed(tp=1, devices=jax.devices()[:1])
    yield AutoLLM.from_pretrained("tiny", ctx=ctx, seed=SEED % (2**31 - 1))
    mesh.finalize_distributed()


def test_weights_are_the_programs_bit_for_bit(tiny_model):
    m = tiny_dims()
    w = reference.make_weights(m, SEED % (2**31 - 1), jax.devices()[:1])
    p = tiny_model.params
    qkv = np.concatenate([w["wq"], w["wk"], w["wv"]], axis=2)
    assert np.array_equal(qkv, p.layers.attn.wqkv)
    assert np.array_equal(np.concatenate([w["gate"], w["up"]], axis=2),
                          p.layers.mlp.w1)
    for mine, theirs in ((w["wo"], p.layers.attn.wo),
                         (w["w2"], p.layers.mlp.w2), (w["embed"], p.embed),
                         (w["lm_head"], p.lm_head[:, : m.vocab])):
        assert np.array_equal(mine, theirs)
    for ones in (p.layers.ln1, p.layers.ln2, p.layers.attn.q_norm,
                 p.layers.attn.k_norm, p.norm):
        assert np.all(np.asarray(ones) == 1.0)  # the reference skips them


def test_sparse_weights_are_the_programs_bit_for_bit(tiny_model):
    """The second architecture's reference (`data/tiny_moe_reference.py`,
    loaded as the harness loads it) draws the program's `tiny-moe`
    weights from the seed: router and experts too."""
    from benchmark import cells
    from triton_distributed_tpu.models import AutoLLM

    config = cells.load_json(os.path.join(DATA, "tiny-moe.config.json"))
    moe = cells.load_module(config["reference"], ["tests/benchmark"])
    p = AutoLLM.from_pretrained("tiny-moe", ctx=tiny_model.ctx,
                                seed=SEED % (2**31 - 1)).params
    w = moe.make_weights(config, SEED % (2**31 - 1), jax.devices()[:1])
    qkv = np.concatenate([w["wq"], w["wk"], w["wv"]], axis=2)
    assert np.array_equal(qkv, p.layers.attn.wqkv)
    assert np.array_equal(np.concatenate([w["gate"], w["up"]], axis=3),
                          p.layers.mlp.w1)
    for mine, theirs in ((w["wo"], p.layers.attn.wo),
                         (w["router"], p.layers.mlp.w_router),
                         (w["w2"], p.layers.mlp.w2), (w["embed"], p.embed),
                         (w["lm_head"], p.lm_head[:, :256])):
        assert np.array_equal(mine, theirs)


def test_prefill_then_decode_through_the_paged_cache_agrees(tiny_model):
    """Paged chunked prefill of a 40-token prompt (three chunks, the last
    ragged), then five teacher-forced paged decode steps, against ONE
    full forward pass of the reference over the 45 tokens."""
    import dataclasses

    from triton_distributed_tpu.models.engine import prefill_suffix_chunks
    from triton_distributed_tpu.models.paged_kv_cache import init_paged_cache

    model, m = tiny_model, tiny_dims()
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, m.vocab, size=40)
    forced = rng.integers(0, m.vocab, size=5)
    page, pps = 16, 8
    cache, _ = init_paged_cache(model.cfg, 1, model.ctx, model.axis,
                                max_length=pps * page, page_size=page,
                                num_pages=pps + 1, assign_pages=False)
    cache = dataclasses.replace(
        cache, page_table=jnp.arange(1, pps + 1, dtype=jnp.int32)[None])
    logits, cache, chunks = prefill_suffix_chunks(
        model, cache, 0, np.asarray(prompt, np.int32), 0, page, "xla")
    assert chunks == 3
    rows = [logits]
    for t in forced:
        step, cache = model.decode_step(jnp.asarray([t], jnp.int32), cache,
                                        "xla")
        rows.append(step[0])
    served = np.asarray(jnp.stack(rows), np.float32)
    w = reference.make_weights(m, SEED % (2**31 - 1), jax.devices()[:1])
    tokens = np.zeros((1, 64), np.int32)
    tokens[0, :45] = np.concatenate([prompt, forced])
    n = len(forced) + 1
    ref = np.asarray(reference.forward_logits(
        m, w, tokens, np.zeros(n, np.int32), np.arange(39, 39 + n)))
    # Both are float32 at precision `highest`; they differ by summation
    # order only (flash blocks, chunk boundaries, the split-KV decode):
    # a few float32 ulps of logits of magnitude 1-4, far under 1e-3. A
    # wrong rope pairing, norm or mask is an O(1) error.
    assert np.max(np.abs(served - ref)) < 1e-3
    assert np.array_equal(served.argmax(-1), ref.argmax(-1))


MID = reference.Dims(vocab=4096, d=256, ffn=768, layers=4, hq=8, hkv=2,
                     hd=32, theta=1e6, eps=1e-6, dtype="bfloat16")


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_the_int8_control_reads_far_above_a_sound_bf16_program(seed):
    """At a size a test can hold (hidden 256, 4 layers, bf16 weights):
    tokens picked greedily by a bf16 forward pass (what a sound program
    serves) lie close under the reference's best; the int8 control's
    picks lie at least three times as far under it on average."""
    w = reference.make_weights(MID, seed, jax.devices()[:1])
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, MID.vocab, size=(2, 128)).astype(np.int32)
    rows = np.repeat(np.arange(2), 64).astype(np.int32)
    cols = np.tile(np.arange(63, 127), 2).astype(np.int32)
    ref = reference.forward_logits(MID, w, tokens, rows, cols)
    gaps = {}
    for mode in ("bf16", "int8"):
        low = reference.forward_logits(MID, w, tokens, rows, cols, mode=mode)
        picks = jnp.argmax(low, axis=-1)
        gaps[mode] = np.asarray(reference._gaps(ref, picks)[0])
    assert gaps["bf16"].max() > 0  # bf16 does flip some near-ties
    # Read on seeds 11-15: the mean gap 6-14 times the bf16 one, the
    # widest, which swings by its nature, 2.8-7 times.
    assert gaps["int8"].mean() >= 3 * gaps["bf16"].mean()
    assert gaps["int8"].max() >= 2 * gaps["bf16"].max()


def test_judge_reads_zero_for_the_references_own_tokens():
    m = tiny_dims()
    w = reference.make_weights(m, 5, jax.devices()[:1])
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, m.vocab, size=12).tolist()
    seq = list(prompt)
    for _ in range(6):  # greedy decode with the reference itself
        t = np.zeros((1, 32), np.int32)
        t[0, : len(seq)] = seq
        lg = reference.forward_logits(m, w, t, np.zeros(1, np.int32),
                                      np.asarray([len(seq) - 1], np.int32))
        seq.append(int(jnp.argmax(lg[0])))
    out = reference.judge(m, w, [(prompt, seq[12:])], 32, 8)
    assert out["logit_gap_max"] == 0.0 and out["tokens_compared"] == 6
    assert out["not_best_share"] == 0.0
    wrong = list(seq[12:])
    wrong[2] = (wrong[2] + 1) % m.vocab
    bad = reference.judge(m, w, [(prompt, wrong)], 32, 8)
    assert bad["logit_gap_max"] > 1e-2
