"""MoE serving tests (ISSUE-11 acceptance core): Qwen3MoE through the
paged/continuous stack + the megakernel's split-phase EP combine.

Layers of evidence:

- **engine level**: Qwen3MoE through ``ContinuousEngine`` — bf16(f32)
  + int8 pools × greedy + seeded sampling, bit-exact vs single-request
  goldens, prefix-cache reuse/COW/eviction with clean pool/radix audits
  (the conftest autouse fixture re-audits every live engine after every
  test), speculation riding the inherited chunk-verify path;
- **megakernel level**: ``mode="mega"`` serves the MoE model via the
  EP-resharded expert streams + MOE_GATE/MOE_FFN/A2A tasks — greedy
  parity vs the unfused engine at tp=1 (tp=4 rides the slow marker,
  like the other interpret-heavy multi-rank suites), the device trace
  ring validating every A2A_SEND/A2A_WAIT scoreboard edge
  (``obs.kernel_trace.validate_ring`` over the scheduled order), and
  the measured A2A overlap report;
- **satellites**: ``SlotSnapshot`` round-trips an MoE slot (the
  geometry is model-agnostic — guarded here), ``server_stats.engine``
  reports the expert knobs, and ``last_stats`` carries the
  ``moe_routed_tokens``/``a2a_dropped`` ledger.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from triton_distributed_tpu.models import AutoLLM
from triton_distributed_tpu.models.continuous import (
    ContinuousEngine,
    Request,
)
from triton_distributed_tpu.runtime import mesh as mesh_mod


@pytest.fixture(scope="module")
def moe_model():
    """ONE tiny-moe model on a single device for the whole module (the
    test_router/test_migration rationale: model init and the first
    compiled programs dominate; every test shares them)."""
    ctx = mesh_mod.initialize_distributed(tp=1, devices=jax.devices()[:1])
    model = AutoLLM.from_pretrained("tiny-moe", ctx=ctx)
    yield model
    mesh_mod.finalize_distributed()


PROMPTS = [
    np.arange(1, 13, dtype=np.int32),
    np.arange(30, 40, dtype=np.int32),
    np.arange(1, 13, dtype=np.int32),  # exact repeat → radix hit
]
GENS = [8, 6, 8]


def make_engine(model, **kw):
    kw.setdefault("max_batch", 2)
    kw.setdefault("page_size", 16)
    kw.setdefault("max_length", 64)
    kw.setdefault("prefix_cache", True)
    return ContinuousEngine(model, **kw)


def goldens(model, reqs, **kw):
    """Single-request, single-slot runs — the bit-exactness reference
    (each request decodes alone, so batching effects can't hide)."""
    outs = []
    for r in reqs:
        eng = make_engine(model, max_batch=1, **kw)
        outs.append(eng.run([r], results=True)[0].tokens.tolist())
        assert eng.audit() == []
    return outs


# -- engine level ---------------------------------------------------------


@pytest.mark.parametrize("kv_dtype", [None, "int8"])
def test_moe_continuous_greedy_bit_exact(moe_model, kv_dtype):
    """Batched continuous serving of the MoE model is bit-exact vs the
    single-request goldens on both pool dtypes, audits clean."""
    reqs = list(zip(PROMPTS, GENS))
    gold = goldens(moe_model, reqs, kv_dtype=kv_dtype)
    eng = make_engine(moe_model, kv_dtype=kv_dtype)
    res = eng.run(reqs, results=True)
    assert all(r.ok for r in res)
    assert [r.tokens.tolist() for r in res] == gold
    assert eng.audit() == []
    st = eng.last_stats
    # The MoE ledger: routed assignments = processed positions × top_k,
    # and the lossless path's drop counter is 0 by construction.
    assert st["num_experts"] == moe_model.cfg.num_experts
    assert st["experts_per_tok"] == moe_model.cfg.num_experts_per_tok
    assert st["moe_routed_tokens"] > 0
    assert st["a2a_dropped"] == 0
    # Work accounting ties out: every prefilled position routed top_k
    # assignments, plus top_k per active slot per decode step.
    assert st["moe_routed_tokens"] % moe_model.cfg.num_experts_per_tok == 0


def test_moe_continuous_seeded_sampling_bit_exact(moe_model):
    """Seeded per-request sampling through the MoE model: with
    explicit per-request keys, a batched run is bit-identical to the
    single-request goldens (every draw is fold_in(request key, draw
    counter) — the per-request PRNG protocol, guarded on MoE here)."""

    def reqs():
        return [
            Request(p, g, temperature=0.8, top_p=0.9,
                    key=jax.random.key(100 + i))
            for i, (p, g) in enumerate(zip(PROMPTS, GENS))
        ]

    gold = goldens(moe_model, reqs(), kv_dtype="int8", seed=11)
    eng = make_engine(moe_model, kv_dtype="int8", seed=11)
    res = eng.run(reqs(), results=True)
    assert [r.tokens.tolist() for r in res] == gold
    assert eng.audit() == []


def test_moe_prefix_cache_reuse_cow_eviction(moe_model):
    """Radix reuse on the MoE model: the repeated prompt admits with
    prefix hits, a diverging tail COW-clones, and eviction pressure
    leaves the audits clean."""
    eng = make_engine(moe_model, kv_dtype="int8", num_pages=12)
    base = np.arange(1, 17, dtype=np.int32)
    eng.run([(base, 6)])
    st1 = dict(eng.last_stats)
    # Same prompt again: the tree serves the prefix.
    eng.run([(base, 6)])
    st2 = eng.last_stats
    assert st2["prefix_hit_tokens"] > 0
    assert st2["prefill_tokens"] < st1["prefill_tokens"]
    # Diverging tail on a shared page boundary → COW clone.
    fork = base.copy()
    fork[-1] += 1
    eng.run([(fork, 6)])
    assert eng.last_stats["pages_cow_copied"] >= 1
    # Eviction pressure: a stream of disjoint prompts through a small
    # pool forces LRU eviction; audits stay clean throughout (the
    # autouse fixture re-checks after the test too).
    for lo in range(50, 110, 12):
        eng.run([(np.arange(lo, lo + 12, dtype=np.int32), 4)])
        assert eng.audit() == []


def test_moe_speculative_greedy_parity(moe_model):
    """Self-drafting speculation rides the inherited chunk-verify path
    for MoE: greedy output matches the non-speculative run and the
    accept ledger moves."""
    # Period-3 repetition gives the n-gram drafter material.
    p = np.asarray([5, 6, 7] * 5, np.int32)
    base = make_engine(moe_model)
    gold = base.run([(p, 8)], results=True)[0].tokens.tolist()
    eng = make_engine(moe_model, speculative=2)
    res = eng.run([(p, 8)], results=True)
    assert res[0].tokens.tolist() == gold
    assert eng.last_stats["spec_verify_steps"] > 0
    assert eng.audit() == []


# -- megakernel level -----------------------------------------------------


def test_moe_mega_greedy_parity_tp1(moe_model, fresh_telemetry):
    """mode='mega' (EP expert streams + split-phase A2A combine under
    the serving default config) matches the unfused engine
    token-for-token, with the device tracer live: launches carry A2A
    windows and the measured overlap report is populated."""
    reqs = list(zip(PROMPTS[:2], GENS[:2]))  # the radix repeat is above
    gold_eng = make_engine(moe_model)
    gold = [r.tokens.tolist()
            for r in gold_eng.run(reqs, results=True)]
    eng = make_engine(moe_model, mode="mega", kernel_trace=True)
    res = eng.run(reqs, results=True)
    assert [r.tokens.tolist() for r in res] == gold
    assert eng.audit() == []
    st = eng.last_stats
    assert st["mega_launches"] > 0
    assert st["moe_routed_tokens"] > 0
    summ = eng.kernel_trace_summary()
    assert summ["launches"] == st["mega_trace_launches"]
    rep = summ["recent"][-1]["overlap"]
    assert rep["a2a_windows"] > 0
    assert rep["a2a_hidden_fraction"] is not None


def test_moe_mega_a2a_ring_validation_tp1(moe_model):
    """Every A2A_SEND/A2A_WAIT scoreboard edge of a traced multi-step
    MoE launch holds on the device clock (``validate_ring`` over the
    scheduled order), and the graph carries the expected MoE tasks."""
    from triton_distributed_tpu.megakernel import MegaQwen3
    from triton_distributed_tpu.megakernel.code_generator import MegaConfig
    from triton_distributed_tpu.megakernel.task import TaskType
    from triton_distributed_tpu.obs import kernel_trace as kt

    model = moe_model
    cache = model.new_cache(2, 64)
    toks = jnp.asarray(np.arange(16, dtype=np.int32).reshape(2, 8))
    lg, cache = model.prefill_batched(toks, cache, "xla")
    tok = jnp.argmax(lg, -1).astype(jnp.int32)
    mega = MegaQwen3(model, cfg=MegaConfig(
        fuse_norms=True, cross_prefetch=True, overlap_ar=True,
    ))
    NS = 3
    fn = mega.decode_multi_fn(2, 64, NS, trace=True)
    order = mega.multi_task_order(2, 64, NS, trace=True)
    ops = {t.task_type for t in order}
    assert {TaskType.MOE_GATE, TaskType.MOE_FFN,
            TaskType.A2A_SEND, TaskType.A2A_WAIT} <= ops
    assert TaskType.FC1 not in ops and TaskType.FC2 not in ops
    # Per layer: one gate, E/n expert tasks, two phase sends, one wait.
    epr = model.cfg.num_experts  # tp=1 → all experts local
    sends = [t for t in order if t.task_type == TaskType.A2A_SEND]
    assert len(sends) == 2 * model.cfg.num_layers
    assert sorted({t.arg0 for t in sends}) == [0, 1]
    assert sum(
        1 for t in order if t.task_type == TaskType.MOE_FFN
    ) == epr * model.cfg.num_layers
    _toks, _logits, _cache, ring = fn(mega._step_params(), tok, cache)
    records = kt.decode_trace(np.asarray(ring))
    assert kt.validate_ring(records, order) == []
    rep = kt.overlap_report(records)
    assert rep["a2a_windows"] == model.cfg.num_layers * NS
    assert rep["a2a_comm_ticks"] > 0
    assert rep["a2a_hidden_ticks"] > 0


@pytest.mark.slow
def test_moe_mega_ring_validated_tp4():
    """tp=4: EP-sharded experts (2 local experts/rank), greedy parity
    vs the unfused chain, and ring validation of every scoreboard edge
    — including the A2A pair's — on all four ranks."""
    from triton_distributed_tpu.megakernel import MegaQwen3
    from triton_distributed_tpu.megakernel.code_generator import MegaConfig
    from triton_distributed_tpu.obs import kernel_trace as kt

    ctx = mesh_mod.initialize_distributed(tp=4, devices=jax.devices()[:4])
    try:
        model = AutoLLM.from_pretrained("tiny-moe", ctx=ctx)
        cache = model.new_cache(2, 64)
        toks = jnp.asarray(np.arange(16, dtype=np.int32).reshape(2, 8))
        lg, cache = model.prefill_batched(toks, cache, "xla")
        tok = jnp.argmax(lg, -1).astype(jnp.int32)
        mega = MegaQwen3(model, cfg=MegaConfig(
            fuse_norms=True, cross_prefetch=True, overlap_ar=True,
        ))
        NS = 3
        fn = mega.decode_multi_fn(2, 64, NS, trace=True)
        order = mega.multi_task_order(2, 64, NS, trace=True)
        mtoks, _lg, _c, ring = fn(
            mega._step_params(), tok, jax.tree.map(jnp.copy, cache)
        )
        # Unfused greedy chain over the same cache.
        t = tok
        chain = []
        for _ in range(NS):
            lx, cache = model.decode_step(t, cache, "xla")
            t = jnp.argmax(lx, -1).astype(jnp.int32)
            chain.append(np.asarray(t))
        assert np.array_equal(np.asarray(mtoks), np.stack(chain))
        records = kt.decode_trace(np.asarray(ring))
        assert kt.validate_ring(records, order) == []
        rep = kt.overlap_report(records)
        assert rep["a2a_windows"] == model.cfg.num_layers * NS * 4
        assert rep["a2a_hidden_fraction"] > 0
    finally:
        mesh_mod.finalize_distributed()


@pytest.mark.slow
def test_moe_mega_int8_single_step_parity(moe_model):
    """Single-step mega decode over an int8 MoE pool: greedy tokens
    match the unfused int8 path step-for-step (the NS-launch band
    carries the PR 7 band-precision tolerance instead — its rows are
    full precision while the unfused path re-reads them quantized)."""
    from triton_distributed_tpu.megakernel import MegaQwen3
    from triton_distributed_tpu.megakernel.code_generator import MegaConfig
    from triton_distributed_tpu.models.paged_kv_cache import (
        init_paged_cache,
        write_prefill,
    )

    model = moe_model
    paged, _pool = init_paged_cache(
        model.cfg, 2, model.ctx, max_length=64, page_size=16,
        kv_dtype="int8",
    )
    dense1 = model.new_cache(1, 64)
    toks = np.arange(16, dtype=np.int32).reshape(2, 8)
    last = []
    for i in range(2):
        li, dense1 = model.prefill_batched(
            jnp.asarray(toks[i:i + 1]), dense1, "xla"
        )
        paged = write_prefill(paged, i, dense1.k, dense1.v, 8)
        last.append(li[0])
    tok = jnp.argmax(jnp.stack(last), -1).astype(jnp.int32)
    mega = MegaQwen3(model, cfg=MegaConfig(
        fuse_norms=True, cross_prefetch=True, overlap_ar=True,
    ))
    cu = jax.tree.map(jnp.copy, paged)
    cm = jax.tree.map(jnp.copy, paged)
    tu = tm = tok
    for _ in range(5):
        lu, cu = model.decode_step(tu, cu, "xla")
        lm, cm = mega.decode_step(tm, cm)
        tu = jnp.argmax(lu, -1).astype(jnp.int32)
        tm = jnp.argmax(lm, -1).astype(jnp.int32)
        assert tu.tolist() == tm.tolist()


# -- satellites -----------------------------------------------------------


def test_moe_slot_snapshot_roundtrip(moe_model):
    """``migrate.export`` smoke (ISSUE-11 satellite): a mid-generation
    MoE slot exports, round-trips the wire codec, and imports into a
    SECOND engine whose remaining tokens are bit-identical — the
    snapshot geometry is model-agnostic and stays that way."""
    from triton_distributed_tpu.models import slot_state

    reqs = list(zip(PROMPTS[:2], GENS[:2]))
    gold = [
        r.tokens.tolist()
        for r in make_engine(moe_model, kv_dtype="int8").run(
            reqs, results=True
        )
    ]
    A = make_engine(moe_model, kv_dtype="int8")
    A.request_handoff(after_rounds=2)
    res1 = A.run(reqs, results=True)
    assert all(r.status == "migrated" for r in res1)
    assert A.audit() == []
    B = make_engine(moe_model, kv_dtype="int8")
    resume = []
    for (p, g), r in zip(reqs, res1):
        # Wire round trip before resuming (base64 codec, MoE KV pages).
        snap = slot_state.SlotSnapshot.from_wire(r.snapshot).to_wire()
        resume.append(Request(p, g, snapshot=snap))
    res2 = B.run(resume, results=True)
    assert [r.tokens.tolist() for r in res2] == gold
    assert B.last_stats["migration_fallbacks"] == 0
    assert B.audit() == []


def test_moe_server_stats_and_wire(moe_model):
    """``server_stats.engine`` reports the expert knobs and a requests
    payload serves the MoE model over the wire."""
    from triton_distributed_tpu.serving.server import ModelServer, request

    eng = make_engine(moe_model)
    server = ModelServer(eng).start()
    try:
        stats = request(server.host, server.port, {"cmd": "stats"})
        e = stats["stats"]["server"]["engine"]
        assert e["num_experts"] == moe_model.cfg.num_experts
        assert e["experts_per_tok"] == moe_model.cfg.num_experts_per_tok
        out = request(server.host, server.port, {
            "requests": [PROMPTS[0].tolist()], "gen_lens": [4],
        })
        assert len(out["outputs"][0]) == 4
        assert out["stats"]["moe_routed_tokens"] > 0
        assert out["stats"]["a2a_dropped"] == 0
    finally:
        server.shutdown()


def test_moe_a2a_dropped_surface(moe_model):
    """The ``a2a_dropped`` ledger is a live surface, not a constant:
    the lossless serving path reports 0 by construction, and a
    capacity-mode EP run's detected overflow comes back through
    ``ep_moe_ffn(return_state=True)`` → ``DispatchState.num_dropped``
    (what perf/moe_serve_bench.py records)."""
    import functools

    from jax.sharding import PartitionSpec as P

    from triton_distributed_tpu.ops.moe.ep_a2a import ep_moe_ffn

    # Lossless serving arm: 0 by construction.
    eng = make_engine(moe_model)
    eng.run([(PROMPTS[0], 4)])
    assert eng.last_stats["a2a_dropped"] == 0

    # Capacity-mode arm (tp=1 shard_map): adversarial skew onto the
    # first experts at capacity_factor=1 must DROP and COUNT.
    rng = np.random.default_rng(3)
    e, d, f, k, t = 8, 32, 64, 2, 16
    x = jnp.asarray(np.abs(rng.standard_normal((t, d))) * 0.1,
                    jnp.float32)
    w_router = jnp.asarray(
        rng.standard_normal((d, e)) * 0.1, jnp.float32
    ).at[:, 2:].add(-100.0).at[:, :2].add(100.0)
    w1 = jnp.asarray(rng.standard_normal((e, d, 2 * f)) * 0.1,
                     jnp.float32)
    w2 = jnp.asarray(rng.standard_normal((e, f, d)) * 0.1, jnp.float32)

    def body(x_loc):
        out, state = ep_moe_ffn(
            x_loc, w_router, w1, w2, k, capacity_factor=0.5,
            axis="tp", method="xla", return_state=True,
        )
        return out, state.num_dropped[None]

    fn = moe_model.ctx.shard_map(
        functools.partial(body),
        in_specs=P(None, None), out_specs=(P(None, None), P(None)),
    )
    _out, dropped = fn(x)
    assert int(np.asarray(dropped).sum()) > 0


def test_moe_cli_model_alias():
    """``--model moe`` resolves to the tiny-moe preset with the
    --num-experts/--top-k/--moe-intermediate overrides threaded through
    (the ONE resolution helper run_server's main uses)."""
    from triton_distributed_tpu.models.config import get_config
    from triton_distributed_tpu.serving.run_server import (
        resolve_model_args,
    )

    name, ov = resolve_model_args("moe", num_experts=4, top_k=2,
                                  moe_intermediate=32)
    assert name == "tiny-moe"
    cfg = get_config(name, **ov)
    assert cfg.num_experts == 4
    assert cfg.num_experts_per_tok == 2
    assert cfg.moe_intermediate_size == 32
    # Non-moe names pass through untouched.
    assert resolve_model_args("tiny") == ("tiny", {})
