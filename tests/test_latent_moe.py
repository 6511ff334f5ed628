"""The latent-attention expert model (models/latent_moe.py) at the
`tiny-mla-moe` preset (float32; 1 dense + 2 expert layers, 4 groups of 4
experts, 2 groups kept, 4 a token, 1 shared, latent rank 32, rope dims
16, YaRN on), with a share of 4 experts from offset 4, against the
benchmark's plain reference."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_latent_moe as ref
from triton_distributed_tpu.layers import mla_attn
from triton_distributed_tpu.models import AutoLLM, ContinuousEngine, Request
from triton_distributed_tpu.models.latent_moe import LatentMoE, weight_layout
from triton_distributed_tpu.models.qwen import Qwen3
from triton_distributed_tpu.models.paged_kv_cache import (
    PagedKVCache,
    init_paged_cache,
    kv_bytes_per_token,
)
from triton_distributed_tpu.ops.attention.mla_decode import (
    mla_decode_reference,
    mla_paged_decode,
)
from triton_distributed_tpu.runtime import mesh as mesh_mod

CONFIG = os.path.join(os.path.dirname(__file__), "benchmark", "data",
                      "tiny-mla-moe.config.json")
SEED, PAGE = 11, 16


@pytest.fixture(scope="module")
def config():
    with open(CONFIG) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def served(config):
    ctx = mesh_mod.initialize_distributed(tp=1, devices=jax.devices()[:1])
    model = AutoLLM.from_pretrained(
        "tiny-mla-moe", ctx=ctx, seed=SEED, experts_held=4, expert_offset=4)
    weights = ref.make_weights(config, SEED, jax.devices()[:1])
    yield model, weights
    mesh_mod.finalize_distributed()


def test_auto_llm_dispatches_by_architecture(served):
    from triton_distributed_tpu.models.config import get_config
    from triton_distributed_tpu.models.qwen_moe import Qwen3MoE

    model, _ = served
    assert type(model) is LatentMoE
    assert get_config("tiny-moe").kv_lora_rank == 0  # Qwen3MoE's branch
    assert get_config("tiny").num_experts == 0
    # Both stand on the dense decoder's serving skeleton.
    assert issubclass(Qwen3MoE, Qwen3) and issubclass(LatentMoE, Qwen3)
    cfg = get_config("rednote-hilab/dots.vlm1.inst")
    assert (cfg.num_layers, cfg.first_k_dense, cfg.num_experts) == (61, 3, 256)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim) == (1536, 512, 128, 64, 128)
    assert model.mla.sm_scale == pytest.approx(
        48 ** -0.5 * (0.1 * np.log(40) + 1) ** 2)
    assert mla_attn.MLADims.of(cfg).sm_scale == pytest.approx(0.1352, abs=5e-5)


def test_weights_are_the_references_bit_for_bit(served):
    model, weights = served
    p = model.params
    mine = {"embed": p.embed, "lm_head": p.lm_head}
    for g, layers in (("d", p.dense), ("s", p.sparse)):
        for f in ("wq_a", "wq_b", "wkv_a", "wk_b", "wv_b", "wo"):
            mine[f"{g}.{f}"] = getattr(layers.attn, f)
    mine.update({"d.w1": p.dense.mlp.w1, "d.w2": p.dense.mlp.w2,
                 "s.router": p.sparse.mlp.w_router, "s.bias": p.sparse.mlp.bias,
                 "s.w1": p.sparse.mlp.w1, "s.w2": p.sparse.mlp.w2,
                 "s.shared_w1": p.sparse.mlp.shared.w1,
                 "s.shared_w2": p.sparse.mlp.shared.w2})
    assert set(mine) == set(weights) == {n for n, *_ in
                                         weight_layout(model.cfg)}
    for name, w in weights.items():
        got = np.asarray(mine[name])
        if name == "lm_head":  # the program pads the head to 128 columns
            got = got[:, : w.shape[1]]
        np.testing.assert_array_equal(got, np.asarray(w), err_msg=name)
    assert weights["s.bias"].dtype == jnp.float32
    assert float(jnp.abs(weights["s.bias"]).max()) > 0


def _reference_logits(config, weights, seq, cols):
    tokens = np.zeros((1, 128), np.int32)
    tokens[0, : len(seq)] = seq
    return np.asarray(ref.forward_logits(
        ref.Dims.of(config), weights, tokens,
        np.zeros(len(cols), np.int32), np.asarray(cols, np.int32)))


def test_served_logits_match_the_references_full_forward(served, config,
                                                         monkeypatch):
    """Prefill in two chunks (the second starts past 0), a second slot on
    a radix hit (its table maps the first slot's two prefix pages and its
    chunk starts at 32), then decode steps of both through the paged
    latent cache: every logit row against the plain float32 reference's
    full forward pass over the same tokens."""
    monkeypatch.setattr(ref, "QUERY_BLOCK", 32)  # several query blocks
    model, weights = served
    cfg = model.cfg
    rng = np.random.default_rng(5)
    a = rng.integers(0, cfg.vocab_size, 45).tolist()
    b = a[:32] + rng.integers(0, cfg.vocab_size, 9).tolist()
    cache, _ = init_paged_cache(
        cfg, 2, model.ctx, page_size=PAGE, max_length=128, num_pages=17,
        assign_pages=False)
    table = np.zeros((2, 8), np.int32)
    table[0] = np.arange(1, 9)
    table[1] = [1, 2, 9, 10, 11, 12, 13, 14]  # the shared prefix's pages
    cache = dataclasses.replace(cache, page_table=jnp.asarray(table))

    def chunk(tokens, slot, off, width):
        nonlocal cache
        buf = np.zeros(width, np.int32)
        buf[: len(tokens)] = tokens
        logits, cache = model.prefill_paged_chunk(
            buf, slot, off, off + len(tokens), len(tokens) - 1, cache, "xla",
            kv_pages=8)
        return np.asarray(logits)

    chunk(a[:32], 0, 0, 32)
    got_a = chunk(a[32:], 0, 32, 16)
    got_b = chunk(b[32:], 1, 32, 16)
    np.testing.assert_allclose(
        got_a, _reference_logits(config, weights, a, [44])[0], atol=2e-4)
    np.testing.assert_allclose(
        got_b, _reference_logits(config, weights, b, [40])[0], atol=2e-4)
    seqs = [a + [int(got_a.argmax())], b + [int(got_b.argmax())]]
    for _ in range(4):
        logits, cache, counts = model.decode_step_counted(
            jnp.asarray([s[-1] for s in seqs], jnp.int32), cache, "xla")
        logits = np.asarray(logits)
        for slot, seq in enumerate(seqs):
            want = _reference_logits(config, weights, seq, [len(seq) - 1])[0]
            np.testing.assert_allclose(logits[slot], want, atol=2e-4)
            seq.append(int(logits[slot].argmax()))
        # Two live rows, 2 expert layers, 4 choices each of 16 experts.
        assert 0 <= int(counts[1]) <= int(counts[0]) <= 2 * 2 * 4
    assert np.asarray(cache.kv_len).tolist() == [49, 45]


def test_absorbed_equals_expanded(served):
    model, _ = served
    m = model.mla
    ap = jax.tree.map(lambda w: w[0], model.params.dense.attn)
    x = jax.random.normal(jax.random.key(2), (24, model.cfg.hidden_size))
    q_nope, q_rope, c_kv, k_rope = mla_attn.mla_project(
        ap, x, jnp.arange(24), m)
    causal = jnp.arange(24)[:, None] >= jnp.arange(24)[None, :]
    absorbed = mla_attn.mla_absorbed(ap, q_nope, q_rope, c_kv, k_rope,
                                     causal, m)
    k_nope = (c_kv @ ap.wk_b).reshape(24, m.heads, m.nope)
    v = (c_kv @ ap.wv_b).reshape(24, m.heads, m.v)
    s = (jnp.einsum("thn,shn->ths", q_nope, k_nope)
         + jnp.einsum("thr,sr->ths", q_rope, k_rope)) * m.sm_scale
    p = jax.nn.softmax(jnp.where(causal[:, None, :], s, -1e30), axis=-1)
    expanded = jnp.einsum("ths,shv->thv", p, v)
    np.testing.assert_allclose(absorbed, expanded, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("pool", ["one_layer", "layer_of_pool"])
def test_mla_paged_decode_kernel_against_the_plain_formula(pool):
    b, h, rank, rope, pps = 3, 4, 32, 16, 4
    n_pages = b * pps + 1
    ks = jax.random.split(jax.random.key(0), 4)
    q_lat = jax.random.normal(ks[0], (b, h, rank))
    q_rope = jax.random.normal(ks[1], (b, h, rope))
    lead = (3,) if pool == "layer_of_pool" else ()
    c_pages = jax.random.normal(ks[2], (*lead, n_pages, 1, PAGE, rank))
    r_pages = jax.random.normal(ks[3], (*lead, n_pages, 1, rope, PAGE))
    table = jnp.asarray(np.random.default_rng(0).permutation(
        np.arange(1, n_pages)).reshape(b, pps).astype(np.int32))
    kv_len = jnp.asarray([1, PAGE + 3, pps * PAGE], jnp.int32)
    layer = {"layer": jnp.int32(2)} if lead else {}
    got = mla_paged_decode(q_lat, q_rope, c_pages, r_pages, table, kv_len,
                           sm_scale=0.2, **layer)
    want = mla_decode_reference(
        q_lat, q_rope, c_pages[2] if lead else c_pages,
        r_pages[2] if lead else r_pages, table, kv_len, sm_scale=0.2)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_kv_bytes_per_token_is_the_latent_row():
    def cache_of(layers, rank, rope, dtype):
        sds = jax.ShapeDtypeStruct
        return PagedKVCache(
            k_pages=sds((layers, 9, 1, 128, rank), dtype),
            v_pages=sds((layers, 9, 1, rope, 128), dtype),
            page_table=None, kv_len=None)

    assert kv_bytes_per_token(cache_of(3, 32, 16, jnp.float32)) == 48 * 4 * 3
    # The served cut: 1,152 bytes a token a layer, five layers.
    assert kv_bytes_per_token(cache_of(5, 512, 64, jnp.bfloat16)) == 1152 * 5
    # Per-head K and V keep their count.
    qwen = PagedKVCache(
        k_pages=jax.ShapeDtypeStruct((36, 9, 8, 128, 128), jnp.bfloat16),
        v_pages=jax.ShapeDtypeStruct((36, 9, 8, 128, 128), jnp.bfloat16),
        page_table=None, kv_len=None)
    assert kv_bytes_per_token(qwen) == 2 * 36 * 8 * 128 * 2


@pytest.mark.parametrize("kw,flag", [
    (dict(mode="mega"), "--mode mega"),
    (dict(kv_dtype="int8"), "--kv-dtype int8"),
    (dict(speculative=2), "--speculative"),
])
def test_the_engine_refuses_by_flag_name(served, kw, flag):
    model, _ = served
    with pytest.raises(ValueError, match=flag):
        ContinuousEngine(model, max_batch=2, page_size=PAGE,
                         prefix_cache=True, **kw)


def test_tensor_parallel_and_dense_cache_are_refused(served):
    from triton_distributed_tpu.models.config import get_config

    model, _ = served
    ctx = mesh_mod.initialize_distributed(tp=2, devices=jax.devices()[:2])
    try:
        with pytest.raises(ValueError, match="--tp 2"):
            LatentMoE(get_config("tiny-mla-moe"), ctx=ctx)
    finally:
        mesh_mod.finalize_distributed()
    with pytest.raises(ValueError, match="no dense-cache path"):
        model.new_cache(1)


def test_engine_counts_the_share_and_sets_the_gauges(served):
    from triton_distributed_tpu.obs import metrics as obs_metrics

    model, _ = served
    eng = ContinuousEngine(model, max_batch=3, page_size=PAGE,
                           prefix_cache=True)
    rng = np.random.default_rng(1)
    reqs = [Request(rng.integers(0, 256, n).astype(np.int32), g)
            for n, g in ((20, 5), (37, 3), (50, 6))]
    outs = eng.run(reqs)
    assert [len(o) for o in outs] == [5, 3, 6]
    st = eng.last_stats
    rows = st["prefill_tokens"] + st["generated_tokens"] - len(reqs)
    # Rows x top-k, as before: a request that ends under a step in
    # flight leaves its row of that step unused, and it is routed too.
    assert st["moe_routed_tokens"] == (rows + st["lookahead_discarded"]) * 4
    assert st["kv_bytes_per_token"] == 48 * 4 * 3
    steps = st["decode_steps"]
    assert 0 < st["moe_decode_experts_touched"] <= min(
        st["moe_decode_local_rows"], 4 * 2 * steps)
    assert st["moe_decode_local_rows"] <= 3 * 4 * 2 * steps
    snap = obs_metrics.default_registry().snapshot()
    assert snap["tdt_moe_experts_held"]["series"][0]["value"] == 4
    assert snap["tdt_kv_row_bytes"]["series"][0]["value"] == 48 * 4
    assert eng.audit() == []


def test_a_decode_step_slices_no_layer_of_experts_out_of_the_stack(served):
    """The expert group's ``w1 [2, 4, 64, 64]`` / ``w2 [2, 4, 32, 64]``
    reach ``tdt_moe_decode_experts`` whole, closed over by the layer
    scan: as its ``xs`` each layer's four experts were sliced out (on
    the chip a copy of 1.4 GB a layer a step, for a kernel). No
    instruction of the lowered step yields one layer's experts."""
    import re

    model, _ = served
    cache, _ = init_paged_cache(model.cfg, 2, model.ctx, page_size=PAGE)
    text = jax.jit(model.decode_fn_paged("xla")).lower(
        model.params, jnp.zeros((2,), jnp.int32), cache).as_text()
    one_layer = re.compile(r"-> tensor<(1x)?4x(64x64|32x64)xf32>")
    assert "tensor<2x4x64x64xf32>" in text  # the stack itself is there
    assert [ln for ln in text.splitlines() if one_layer.search(ln)] == []


def test_the_cut_reaches_the_program_through_resolve_model_args():
    from triton_distributed_tpu.models.config import get_config
    from triton_distributed_tpu.serving.run_server import resolve_model_args

    name, over = resolve_model_args(
        "rednote-hilab/dots.vlm1.inst", num_layers=5, first_k_dense=1,
        experts_held=16, expert_offset=0, vocab_rows=16160)
    assert over == {"num_layers": 5, "first_k_dense": 1, "experts_held": 16,
                    "vocab_size": 16160}
    cfg = get_config(name, **over)
    assert (cfg.hidden_size, cfg.num_experts, cfg.max_length) == (
        7168, 256, 4096)
    assert resolve_model_args("moe", 16, 4, 32) == (
        "tiny-moe", {"num_experts": 16, "num_experts_per_tok": 4,
                     "moe_intermediate_size": 32})


@pytest.mark.parametrize("slots,pending", [(12, 12), (4, 8)])
def test_run_server_takes_as_many_payloads_as_decode_slots(
        monkeypatch, slots, pending):
    from triton_distributed_tpu.serving import run_server, server

    seen = []
    monkeypatch.setattr(server.ModelServer, "serve_forever",
                        lambda self: seen.append(self))
    assert run_server.main(["--model", "tiny-mla-moe", "--continuous",
                            "--replicas", "1", "--max-batch", str(slots),
                            "--experts-held", "4"]) == 0
    front = seen[0]
    try:
        assert front.max_pending == pending
        assert [r.max_pending for r in front.engine.replicas] == [pending]
        assert front.engine.replicas[0].engine.max_batch == slots
    finally:
        front.engine.shutdown()
        front._sock.close()
        mesh_mod.finalize_distributed()
