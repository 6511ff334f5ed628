"""The hybrid of Mamba-2 and attention layers (models/hybrid_ssm.py) at
the `tiny-hybrid` preset (float32; 7 layers ``m m a m m a m``, 8 mamba
heads of 16, state 16, blocks of 8, 8 query / 4 kv heads of 16, no
positions, the four scalar multipliers) against the benchmark's plain
reference, and the engine's four rules for a recurrent state
(docs/serving.md "Recurrent state beside pages")."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_hybrid_ssm as ref
from triton_distributed_tpu.models import AutoLLM, ContinuousEngine, Request
from triton_distributed_tpu.models.config import get_config
from triton_distributed_tpu.models.hybrid_ssm import (
    HybridSSM,
    layer_runs,
    weight_layout,
)
from triton_distributed_tpu.models.paged_kv_cache import (
    init_paged_cache,
    kv_bytes_per_token,
    state_bytes_per_slot,
)
from triton_distributed_tpu.models.qwen import Qwen3
from triton_distributed_tpu.runtime import mesh as mesh_mod

CONFIG = os.path.join(os.path.dirname(__file__), "benchmark", "data",
                      "tiny-hybrid.config.json")
SEED, PAGE = 7, 16
# float32 on both sides at matmul precision "highest" (conftest): what is
# left is the order of float32 sums, the chunked form's exp of
# differences of cumulative sums against a product of exps, and the
# kernel's lane sums: 1e-5 at logits of 0.5, read here as 3e-6. 1e-4
# leaves an order of magnitude and is a tenth of what a state advanced
# once too often reads (2e-3).
ATOL = 1e-4


@pytest.fixture(scope="module")
def config():
    with open(CONFIG) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def served(config):
    ctx = mesh_mod.initialize_distributed(tp=1, devices=jax.devices()[:1])
    model = AutoLLM.from_pretrained("tiny-hybrid", ctx=ctx, seed=SEED)
    weights = ref.make_weights(config, SEED, jax.devices()[:1])
    yield model, weights
    mesh_mod.finalize_distributed()


def _reference_logits(config, weights, seq, cols):
    return np.asarray(ref.forward_logits(
        ref.Dims.of(config), weights, np.asarray([seq], np.int32),
        np.zeros(len(cols), np.int32), np.asarray(cols, np.int32)))


def test_the_layer_list_is_declared_once_and_the_groups_follow(served):
    model, _ = served
    assert type(model) is HybridSSM and issubclass(HybridSSM, Qwen3)
    assert model.runs == [("mamba", 0, 2), ("attention", 2, 1),
                          ("mamba", 3, 2), ("attention", 5, 1),
                          ("mamba", 6, 1)]
    cfg = get_config("ibm-granite/granite-4.0-h-micro")
    assert [(k[0], n) for k, _, n in layer_runs(cfg.layer_types)] == [
        ("m", 5), ("a", 1), ("m", 9), ("a", 1), ("m", 9), ("a", 1), ("m", 9),
        ("a", 1), ("m", 4)]
    assert (cfg.mamba_layers, cfg.attention_layers) == (36, 4)
    assert cfg.slot_keeps == ("kv_pages", "recurrent_state")
    assert get_config("Qwen/Qwen3-4B").slot_keeps == ("kv_pages",)
    assert get_config("tiny-mla-moe").slot_keeps == ("latent_rows",)
    assert (cfg.pool_row_dim, get_config("tiny-hybrid").pool_row_dim,
            get_config("Qwen/Qwen3-4B").pool_row_dim) == (128, 16, 128)
    # What the configuration states of an attention layer.
    assert model.dims.rope_theta is None and model.dims.sm_scale == 0.0625
    assert model.params.runs[1].attn.q_norm is None


def test_weights_are_the_references_bit_for_bit(served):
    model, weights = served
    layout = {name: (n, shape) for name, n, shape, _ in
              weight_layout(model.cfg)}
    assert {k: (v.shape[0] if k not in ("embed", "lm_head") else 1,
                tuple(v.shape[1:] if k not in ("embed", "lm_head")
                      else v.shape)) for k, v in weights.items()} == layout
    seen = {"mamba": 0, "attention": 0}
    for (kind, start, n), run in zip(model.runs, model.params.runs):
        k0 = seen[kind]
        seen[kind] += n
        pairs = {"f.w1": run.mlp.w1, "f.w2": run.mlp.w2}
        at = {"f.w1": start, "f.w2": start}
        if kind == "mamba":
            pairs.update({"m.w_in": run.attn.w_in, "m.conv_w": run.attn.conv_w,
                          "m.a_log": run.attn.a_log,
                          "m.dt_bias": run.attn.dt_bias,
                          "m.w_out": run.attn.w_out})
        else:
            pairs.update({"a.wqkv": run.attn.wqkv, "a.wo": run.attn.wo})
        for name, got in pairs.items():
            lo = at.get(name, k0)
            np.testing.assert_array_equal(got, weights[name][lo: lo + n])
    np.testing.assert_array_equal(model.params.embed, weights["embed"])
    np.testing.assert_array_equal(model.params.lm_head, weights["lm_head"])
    # The published initial ranges of what is no matrix.
    a = np.exp(np.asarray(weights["m.a_log"]))
    assert 1.0 <= a.min() and a.max() < 16.0
    dt = np.log1p(np.exp(np.asarray(weights["m.dt_bias"])))
    assert 1e-3 * 0.999 <= dt.min() and dt.max() <= 1e-1 * 1.001


def _cache(model, slots=2, pages=8):
    cache, _ = init_paged_cache(
        model.cfg, slots, model.ctx, page_size=PAGE, max_length=pages * PAGE,
        num_pages=slots * pages + 1, assign_pages=False)
    table = 1 + np.arange(slots * pages, dtype=np.int32).reshape(slots, pages)
    return dataclasses.replace(cache, page_table=jnp.asarray(table))


def _prefill(model, cache, tokens, slot, widths):
    """``tokens`` into ``slot`` in chunks of ``widths`` (the last one
    right-padded to its width); the last real token's logits."""
    off = 0
    for i, width in enumerate(widths):
        take = min(width, len(tokens) - off)
        buf = np.zeros(width, np.int32)
        buf[:take] = tokens[off: off + take]
        logits, cache = model.prefill_paged_chunk(
            buf, slot, off, off + take, take - 1, cache, "xla", kv_pages=8)
        off += take
    assert off == len(tokens)
    return np.asarray(logits), cache


@pytest.mark.parametrize("widths", [(48,), (8, 24, 16), (32, 16)])
def test_prefill_then_decode_against_the_references_full_forward(
        served, config, widths):
    """A prompt of 37 tokens prefilled in one chunk of 48 (11 positions
    of padding), in uneven chunks and in chunks whose last is mostly
    padding, then two decode steps through the cache beside a
    second slot with its own prompt: every logit row against the plain
    float32 reference's full forward over the same tokens (which runs
    the recurrence a position at a time: the chunked form, the padding
    and the kernel are all on this side only)."""
    model, weights = served
    rng = np.random.default_rng(3)
    a = rng.integers(0, 256, 37).tolist()
    b = rng.integers(0, 256, 21).tolist()
    cache = _cache(model)
    got_a, cache = _prefill(model, cache, a, 0, widths)
    got_b, cache = _prefill(model, cache, b, 1, (32,))
    np.testing.assert_allclose(
        got_a, _reference_logits(config, weights, a, [36])[0], atol=ATOL)
    np.testing.assert_allclose(
        got_b, _reference_logits(config, weights, b, [20])[0], atol=ATOL)
    cache = dataclasses.replace(cache, live=jnp.asarray([True, True]))
    seqs = [a + [int(got_a.argmax())], b + [int(got_b.argmax())]]
    for _ in range(2):
        logits, cache, counts = model.decode_step_counted(
            jnp.asarray([s[-1] for s in seqs], jnp.int32), cache, "xla")
        logits = np.asarray(logits)
        assert counts.tolist() == [2]
        for slot, seq in enumerate(seqs):
            want = _reference_logits(config, weights, seq, [len(seq) - 1])[0]
            np.testing.assert_allclose(logits[slot], want, atol=ATOL)
            seq.append(int(logits[slot].argmax()))
    assert np.asarray(cache.kv_len).tolist() == [39, 23]


def test_a_step_moves_only_rows_in_flight_and_a_chunk_only_real_positions(
        served):
    """The state arrays themselves: a decode step leaves a row that is
    not in flight bit for bit (mapped or not), a chunk's padding leaves
    the state where its last real position put it, and a chunk at offset
    0 starts from zeros whatever the slot held."""
    model, _ = served
    rng = np.random.default_rng(4)
    a = rng.integers(0, 256, 19).tolist()
    cache = _cache(model)
    _, cache = _prefill(model, cache, a, 0, (32,))
    _, exact = _prefill(model, _cache(model), a[:16] + a[16:], 0, (16, 8))
    np.testing.assert_allclose(cache.ssm_state[:, 0], exact.ssm_state[:, 0],
                               atol=1e-5)
    np.testing.assert_allclose(cache.conv_state[:, :, 0],
                               exact.conv_state[:, :, 0], atol=1e-5)
    before = (np.asarray(cache.ssm_state), np.asarray(cache.conv_state))
    cache = dataclasses.replace(cache, live=jnp.asarray([False, True]))
    _, cache, counts = model.decode_step_counted(
        jnp.asarray([5, 6], jnp.int32), cache, "xla")
    assert counts.tolist() == [1]
    np.testing.assert_array_equal(cache.ssm_state[:, 0], before[0][:, 0])
    np.testing.assert_array_equal(cache.conv_state[:, :, 0],
                                  before[1][:, :, 0])
    assert np.abs(np.asarray(cache.ssm_state[:, 1])).max() > 0
    # Slot 1 now holds a state; a fresh admission into it starts from
    # zeros (the same prompt into a clean slot gives the same state).
    _, cache = _prefill(model, cache, a, 1, (32,))
    np.testing.assert_array_equal(cache.ssm_state[:, 1], before[0][:, 0])
    np.testing.assert_array_equal(cache.conv_state[:, :, 1],
                                  before[1][:, :, 0])


SLOTS = 3  # every engine here: one set of compiled programs


def _serve(model, reqs, **kw):
    eng = ContinuousEngine(model, max_batch=SLOTS, page_size=PAGE,
                           prefix_cache=True, **kw)
    outs = eng.run([Request(np.asarray(p, np.int32), g) for p, g in reqs])
    assert eng.audit() == []
    return [list(o) for o in outs], eng


_ALONE: dict = {}


def _alone(model, req):
    """What ``req`` serves with nobody beside it, in a clean engine."""
    key = (tuple(req[0]), req[1])
    if key not in _ALONE:
        _ALONE[key] = _serve(model, [req])[0][0]
    return _ALONE[key]


def test_a_prompt_admitted_in_chunks_between_steps_is_the_prompt_alone(
        served):
    """Rule (c): a prompt prefilled in three chunks while the other
    slots decode between them serves what it serves alone. The slot is
    mapped before its first chunk and is not in flight until its last:
    a step that advanced it there would be wrong for good."""
    model, _ = served
    rng = np.random.default_rng(6)
    long = rng.integers(0, 256, 44).tolist()
    others = [(rng.integers(0, 256, n).tolist(), g)
              for n, g in ((9, 6), (13, 5))]
    alone, _ = _serve(model, [(long, 3)], prefill_chunk=16)
    eng = ContinuousEngine(model, max_batch=SLOTS, page_size=PAGE,
                           prefix_cache=True, prefill_chunk=16)
    between = []
    launch = eng._launch_step

    def counted(tok, active, n_active):
        # Mapped, not yet held: the long prompt's admission is under way.
        if eng._table[2].any() and eng._slots[2] is None:
            between.append(np.asarray(eng.cache.live).tolist())
        return launch(tok, active, n_active)

    eng._launch_step = counted
    mixed = eng.run([Request(np.asarray(p, np.int32), g)
                     for p, g in others + [(long, 3)]])
    assert list(mixed[2]) == alone[0]
    assert eng.last_stats["prefill_chunks"] == 1 + 1 + 3
    # Two steps ran between its three chunks, on the two rows in flight.
    assert between == [[True, True, False]] * 2
    assert eng.audit() == []


def test_a_slot_reused_after_another_request_serves_as_alone(served):
    """Rule (a): the state starts from zero at admission. Three requests
    in turn through ONE engine's first slot: each is what it is alone in
    a clean engine."""
    model, _ = served
    rng = np.random.default_rng(8)
    reqs = [(rng.integers(0, 256, n).tolist(), g)
            for n, g in ((23, 4), (40, 3), (7, 5))]
    eng = ContinuousEngine(model, max_batch=SLOTS, page_size=PAGE,
                           prefix_cache=True)
    for req in reqs:
        out = eng.run([Request(np.asarray(req[0], np.int32), req[1])])
        assert list(out[0]) == _alone(model, req)
        assert eng.last_stats["admitted"] == 1 and eng.audit() == []


def test_rows_total_is_the_decoded_tokens_and_ends_cost_only_ended_rows(
        served, fresh_telemetry):
    """Rule (d) and the counter: over a whole run
    ``tdt_ssm_decode_rows_total`` is the decoded tokens exactly, plus
    the rows of looked-ahead steps whose slot had ENDED under them
    (``lookahead_discarded``), the only rows a step ever wastes."""
    from triton_distributed_tpu.obs import metrics as obs_metrics

    model, _ = served
    rng = np.random.default_rng(9)
    reqs = [(rng.integers(0, 256, n).tolist(), g)
            for n, g in ((12, 3), (30, 7), (8, 5))]
    alone = [_alone(model, r) for r in reqs]

    def rows_total():
        snap = obs_metrics.default_registry().snapshot()
        return snap["tdt_ssm_decode_rows_total"]["series"][0]["value"]

    before = rows_total()
    # As many slots as requests: nobody waits, the round looks ahead
    # over every end.
    outs, eng = _serve(model, reqs)
    assert outs == alone
    st = eng.last_stats
    decoded = st["generated_tokens"] - st["admitted"]
    assert st["lookahead_discarded"] == 2  # the two ends before the last
    assert st["ssm_decode_rows"] == decoded + st["lookahead_discarded"]
    assert rows_total() - before == st["ssm_decode_rows"]
    snap = obs_metrics.default_registry().snapshot()
    assert snap["tdt_ssm_state_slots"]["series"][0]["value"] == SLOTS
    per_slot = 5 * (8 * 16 * 16 * 4 + 3 * (128 + 32) * 4)
    assert snap["tdt_ssm_state_bytes_per_slot"]["series"][0][
        "value"] == per_slot == st["state_bytes_per_slot"]
    # The pool holds the two attention layers only: K and V rows of 4
    # heads x 16 in float32.
    assert st["kv_bytes_per_token"] == 2 * 2 * 4 * 16 * 4


def test_a_slot_readmitted_the_moment_it_ends_serves_as_alone(served):
    """Six requests through three slots: a request ends (under a step in
    flight or not), its slot is readmitted at once, and every request
    serves what it serves alone; the rows advanced are the decoded
    tokens and the discarded ones, and without the look-ahead the
    decoded tokens exactly."""
    model, _ = served
    rng = np.random.default_rng(9)
    reqs = [(rng.integers(0, 256, n).tolist(), g)
            for n, g in ((12, 3), (30, 7), (8, 5), (17, 5), (25, 2),
                         (11, 6))]
    alone = [_alone(model, r) for r in reqs]
    outs, eng = _serve(model, reqs)
    assert outs == alone
    st = eng.last_stats
    assert st["ssm_decode_rows"] == (st["generated_tokens"] - st["admitted"]
                                     + st["lookahead_discarded"])
    eng2 = ContinuousEngine(model, max_batch=SLOTS, page_size=PAGE,
                            prefix_cache=True)
    eng2._may_look_ahead = lambda step: False
    outs2 = eng2.run([Request(np.asarray(p, np.int32), g) for p, g in reqs])
    assert [list(o) for o in outs2] == alone
    st2 = eng2.last_stats
    assert st2["lookahead_discarded"] == 0
    assert st2["ssm_decode_rows"] == (st2["generated_tokens"]
                                      - st2["admitted"])


def test_the_look_ahead_wastes_only_rows_of_slots_that_ended(served):
    """Rule (d), on the dispatches themselves: every row the device's
    ``live`` marks when a step is dispatched is a slot that holds a
    request (never one whose admission is under way, never an empty
    one); a request is advanced once a decoded token, and once more
    only by the step that was in flight when it ENDED."""
    model, _ = served
    eng = ContinuousEngine(model, max_batch=SLOTS, page_size=PAGE,
                           prefix_cache=True, prefill_chunk=16)
    advanced = {}
    launch = eng._launch_step

    def checked(tok, active, n_active):
        for slot, on in enumerate(np.asarray(eng.cache.live)):
            held = eng._slots[slot]
            assert bool(on) == (held is not None)
            if on:
                advanced[id(held)] = advanced.get(id(held), 0) + 1
        return launch(tok, active, n_active)

    eng._launch_step = checked
    rng = np.random.default_rng(10)
    reqs = [Request(rng.integers(0, 256, n).astype(np.int32), g)
            for n, g in ((40, 5), (9, 8), (21, 3))]
    eng.run(reqs)
    extra = [advanced[id(r)] - (r.gen_len - 1) for r in reqs]
    assert set(extra) <= {0, 1}
    assert sum(extra) == eng.last_stats["lookahead_discarded"] > 0
    assert sum(advanced.values()) == eng.last_stats["ssm_decode_rows"]


def test_the_radix_cache_answers_no_match_and_keeps_no_page(served):
    model, _ = served
    rng = np.random.default_rng(11)
    p = rng.integers(0, 256, 40).tolist()
    eng = ContinuousEngine(model, max_batch=SLOTS, page_size=PAGE,
                           prefix_cache=True)
    free = len(eng.pool.free)
    first = eng.run([Request(np.asarray(p, np.int32), 5)])
    again = eng.run([Request(np.asarray(p, np.int32), 5)])
    assert list(first[0]) == list(again[0])
    st = eng.last_stats  # the second run's
    assert st["prefix_hit_tokens"] == 0 and st["tree_pages"] == 0
    assert st["prefill_tokens"] == 40
    assert len(eng.pool.free) == free and eng.audit() == []


@pytest.mark.parametrize("kw,flag", [
    (dict(mode="mega"), "--mode mega"),
    (dict(kv_dtype="int8"), "--kv-dtype int8"),
    (dict(speculative=2), "--speculative"),
    (dict(snapshot_every=4), "--snapshot-every"),
    (dict(tier_bytes=1 << 20), "--tier-bytes"),
])
def test_the_engine_refuses_by_flag_name(served, kw, flag):
    model, _ = served
    with pytest.raises(ValueError, match=flag) as e:
        ContinuousEngine(model, max_batch=SLOTS, page_size=PAGE,
                         prefix_cache=True, **kw)
    assert "tiny-hybrid" in str(e.value)


def test_the_paths_with_no_recurrent_state_program_are_refused(served):
    model, _ = served
    ctx = mesh_mod.initialize_distributed(tp=2, devices=jax.devices()[:2])
    try:
        with pytest.raises(ValueError, match="--tp 2"):
            HybridSSM(get_config("tiny-hybrid"), ctx=ctx)
    finally:
        mesh_mod.finalize_distributed()
    with pytest.raises(ValueError, match="no dense-cache path"):
        model.new_cache(1)
    with pytest.raises(ValueError, match="no dense-cache path"):
        ContinuousEngine(model, max_batch=2, page_size=PAGE)
    with pytest.raises(ValueError, match="--kv-dtype int8"):
        init_paged_cache(model.cfg, 2, model.ctx, page_size=PAGE,
                         kv_dtype="int8")
    eng = ContinuousEngine(model, max_batch=SLOTS, page_size=PAGE,
                           prefix_cache=True)
    for call in (lambda: eng.export_slot(0), eng.request_handoff):
        with pytest.raises(ValueError, match="recurrent state"):
            call()
    res = eng.run([Request(np.arange(9, dtype=np.int32), 3,
                           prefill_only=True)], results=True)
    assert res[0].status == "failed" and "recurrent" in res[0].reason
    with pytest.raises(ValueError, match="--speculative"):
        model.prefill_paged_chunk(np.zeros(16, np.int32), 0, 0, 9, 8,
                                  eng.cache, "xla", all_logits=True)


@pytest.mark.parametrize("kernel", ["paged_decode", "prefill_chunk"])
def test_head_dim_64_through_both_attention_kernels(kernel):
    """A 64-wide head rides the pool padded to 128 columns with zeros
    (``ModelConfig.pool_row_dim``), through ``tdt_flash_decode_paged``
    and ``tdt_flash_attention`` under the interpreter, under its OWN
    scale: against plain softmax attention at 64."""
    from triton_distributed_tpu.layers.tp_attn import (
        TPAttnDims,
        TPAttnParams,
        tp_attn_decode_paged,
        tp_attn_prefill_paged_chunk,
    )
    from triton_distributed_tpu.ops.attention import paged_decode_walk

    hq, hkv, hd, d, page = 4, 2, 64, 32, 16
    dims = TPAttnDims(hq_loc=hq, hkv_loc=hkv, head_dim=hd, rope_theta=None,
                      sm_scale=1 / 64)
    k = jax.random.split(jax.random.key(12), 3)
    p = TPAttnParams(
        wqkv=jax.random.normal(k[0], (d, (hq + 2 * hkv) * hd)) * d ** -0.5,
        wo=jax.random.normal(k[1], (hq * hd, d)) * (hq * hd) ** -0.5,
        q_norm=None, k_norm=None)
    n = 21
    x = jax.random.normal(k[2], (n + 1, d), jnp.float32)
    pool = jnp.zeros((1, 5, hkv, page, 128), jnp.float32)
    table = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    ctx = mesh_mod.initialize_distributed(tp=1, devices=jax.devices()[:1])
    try:
        def chunk(x, kp, vp):
            return tp_attn_prefill_paged_chunk(
                p, x, kp, vp, jnp.int32(0), table[0], jnp.int32(0), dims,
                mode="xla_ar", ctx=ctx, q_end=jnp.int32(n))

        def step(x, kp, vp):
            kv_len = jnp.asarray([n], jnp.int32)
            return tp_attn_decode_paged(
                p, x, kp, vp, jnp.int32(0), table, kv_len, dims,
                walk=paged_decode_walk(kv_len + 1, page, 4), mode="xla_ar",
                ctx=ctx)

        pad = jnp.zeros((32 - n, d), jnp.float32)
        # Under jit as the served programs are: called eagerly, a
        # shard_map compiles its hundreds of operations one at a time.
        out, kp, vp, _, _ = jax.jit(ctx.shard_map(
            chunk, in_specs=(jax.P(),) * 3, out_specs=(jax.P(),) * 5))(
            jnp.concatenate([x[:n], pad]), pool, pool)
        if kernel == "paged_decode":
            out, kp, vp, _, _ = jax.jit(ctx.shard_map(
                step, in_specs=(jax.P(),) * 3, out_specs=(jax.P(),) * 5))(
                x[n:], kp, vp)
            rows = slice(n, n + 1)
        else:
            out, rows = out[:n], slice(0, n)
    finally:
        mesh_mod.finalize_distributed()
    # The zero columns stayed zero in the pool.
    assert not np.asarray(kp[..., hd:]).any()
    q, kk, v = dims.split_qkv(x @ p.wqkv)
    kk, v = (jnp.repeat(a, hq // hkv, axis=1) for a in (kk, v))
    s = jnp.einsum("qhd,khd->hqk", q, kk) / 64
    s = jnp.where(jnp.tril(jnp.ones((n + 1, n + 1), bool)), s, -jnp.inf)
    want = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    want = want.reshape(n + 1, hq * hd) @ p.wo
    np.testing.assert_allclose(out, want[rows], atol=2e-5, rtol=2e-5)
