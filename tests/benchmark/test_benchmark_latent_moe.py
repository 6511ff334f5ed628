"""The latent-attention expert share through the harness: its plain
reference (`benchmark/reference_latent_moe.py`), its work counts
(`benchmark/work_latent_moe.py`), the cell's two readers, and the
`tiny-mla-moe` preset served and judged through `benchmark.run` on the
CPU by files alone."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells, peaks, run, xplane
from benchmark import reference_latent_moe as ref
from benchmark import work_latent_moe as work

DATA = os.path.join(os.path.dirname(__file__), "data")
CELL = "dots-vlm1-ep16.docs-closed"
V5E = peaks.PEAKS["TPU v5 lite"]


@pytest.fixture(scope="module")
def cut():
    return cells.load_cell(CELL).config


# -- the cell and its files ---------------------------------------------------

def test_the_cell_carries_its_own_modules():
    c = cells.load_cell(CELL)
    # A file of the package is that package's module: the harness calls
    # the objects these tests import.
    assert c.reference is ref and c.work is work
    assert callable(c.reference.make_weights) and callable(c.reference.judge)
    for fn in ("decode_flops", "prefill_flops", "decode_least_seconds",
               "prefill_least_seconds", "mla_decode_least_seconds"):
        assert callable(getattr(c.work, fn))
    # It reports the two end-to-end metrics no `workloads` list keeps
    # from it, and its own two per-layer metrics beside the shared ones.
    assert [m["name"] for m in c.end_to_end] == ["token_gap_p95_ms", "setup_s"]
    names = {m["name"] for m in c.per_layer}
    assert {"kernels.mla_decode_roofline", "model.experts_touched_share",
            "kernels.decode_step_roofline", "model.step_mfu",
            "device.idle_share", "engine.host_gap_p50_ms"} <= names
    assert not any("ttft" in n or n.endswith(".open") for n in names)


def test_the_file_keeps_every_published_width_and_says_what_it_cut(cut):
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        published = next(json.loads(ln) for ln in f
                         if '"dots.vlm1.inst"' in ln)["config"]
    manifest = cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "dots-vlm1-ep16")
    assert entry["reduced"] == cut["reduced"]
    for key, value in published.items():
        if key in cut["reduced"]:
            assert cut[key] != value and cut["published"][key] == value
        else:
            assert cut[key] == value, key
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"
                   for k in cut["reduced"])
    assert "9 pipeline stages of 16" in cut["deployment"]
    argv = cut["serve_argv"]
    assert argv[argv.index("--max-batch") + 1] == "32"
    assert {"weights", "e_score_correction_bias", "max_length",
            "max_batch"} <= set(cut["assumed"])
    traffic = cells.load_cell(CELL).traffic
    assert (traffic["clients"], traffic["stagger_ms"], traffic["deck"]) == (
        32, 5, 512)
    assert traffic["classes"][0]["prompt"] == {
        "dist": "lognormal", "median": 2048, "sigma": 0.4, "min": 1024,
        "max": 3584}


# -- the work counts ----------------------------------------------------------

def test_work_counts_match_the_parameter_arithmetic(cut):
    """ISSUE 35's arithmetic, bf16: attention 187.105 M a layer, one
    expert 44.04 M, the router 1.835 M, the dense ffn 396.36 M; one
    expert layer here 937.6 M; all held 4.566 B = 9.13 GB."""
    assert work.attn_params(cut) == 187_105_280
    assert work.expert_params(cut) == 44_040_192
    assert work.router_params(cut) == 1_835_008
    assert work.dense_ffn_params(cut) == 396_361_728
    layer = (work.attn_params(cut) + work.router_params(cut)
             + 17 * work.expert_params(cut))
    assert round(layer / 1e6, 1) == 937.6
    assert round(work.held_params(cut) / 1e9, 3) == 4.566
    assert round(2 * work.held_params(cut) / 1e9, 2) == 9.13
    assert work.kv_bytes_per_token(cut) == 1152 * 5
    # A token's routed experts held here, at their expectation: half of one.
    assert (work.token_matmul_params(cut) - work.unavoidable_params(cut)
            == 4 * 0.5 * work.expert_params(cut))
    # What no routing avoids: 1.167 + 4 x (0.374 + 0.004 + 0.088) + 0.232 GB.
    assert round(2 * work.unavoidable_params(cut) / 1e9, 2) == 3.26


def test_work_flops_and_least_times(cut):
    tokens, context = 32, 32 * 2300
    per_tok = 2 * work.token_matmul_params(cut)
    attn = 2 * 5 * 128 * context * (2 * 512 + 64)
    assert work.decode_flops(cut, tokens, context) == per_tok * tokens + attn
    # Prefill counts the expanded form: 2 x 128 x (192 + 128) a pair.
    n = 2048
    head = 7168 * 16160
    want = ((per_tok - 2 * head) * n
            + 2 * 5 * 128 * (n * (n + 1) // 2) * 320 + 2 * head)
    assert work.prefill_flops(cut, [n]) == want
    # A step's least time holds NO routed expert: 3.26 GB and the rows.
    secs, bound = work.decode_least_seconds(cut, 1, tokens, context, V5E)
    assert bound == "memory"
    assert secs == pytest.approx(
        (2 * work.unavoidable_params(cut) + context * 5760) / 819e9)
    assert 4.4e-3 < secs < 4.6e-3
    # The kernel alone sits at the v5e's ridge: bytes and FLOPs within 2%.
    secs, bound = work.mla_decode_least_seconds(cut, context, V5E)
    t_mem, t_cmp = context * 5760 / 819e9, attn / 197e12
    assert secs == max(t_mem, t_cmp) and abs(t_mem / t_cmp - 1) < 0.02
    assert work.prefill_least_seconds(cut, [n], 1, V5E)[1] == "compute"


# -- the readers --------------------------------------------------------------

def _ctx(cut, ops, modules, counters0, counters1, records=()):
    from benchmark.client import Record  # noqa: F401 (the records' type)

    cell = cells.load_cell(CELL)
    form = {"devices": {"0": {"ops": ops, "modules": modules}}, "host": []}
    return {"cell": cell, "peak": V5E, "chips": 1, "seconds": 50,
            "trace": xplane.Trace.of(form), "records": list(records),
            "counters_window_0": counters0, "counters_window_1": counters1,
            "counters_trace_0": counters0, "counters_trace_1": counters1,
            "trace_t0": 0.0, "trace_t1": 10.0}


class _Rec:
    ok = True

    def __init__(self, prompt_len, token_ts):
        self.prompt_len, self.token_ts = prompt_len, token_ts


def test_readers_on_a_recorded_piece_of_trace(cut):
    """Two decode steps as a chip run's trace holds them (operation and
    program names from the `--trace 1` run of PR 35; times rounded): the
    kernel runs once in the dense layer's loop and once in the expert
    layers', and only operations named `tdt_mla_decode_paged` count."""
    step = "jit_tdt_decode_step(1234)"
    kern = ('%tdt_mla_decode_paged.{} = bf16[32,128,512]{{2,1,0}} custom-call'
            '(...), custom_call_target="tpu_custom_call"')
    ops, modules = [], []
    for k in range(2):
        t = k * 16e6
        modules.append([step, t, 15.5e6])
        ops += [[kern.format(11), t + 1e6, 0.45e6],
                [kern.format(12), t + 3e6, 1.80e6],
                ["%fusion.523 = bf16[16,32,4096]{2,1,0} fusion(...)",
                 t + 6e6, 4.87e6]]
    before = {"tdt_engine_decode_steps_total": 100,
              "tdt_engine_generated_tokens_total": 3000,
              "tdt_moe_decode_experts_touched_total": 4000,
              "tdt_moe_experts_held": 16}
    after = {"tdt_engine_decode_steps_total": 102,
             "tdt_engine_generated_tokens_total": 3064,
             "tdt_moe_decode_experts_touched_total": 4080,
             "tdt_moe_experts_held": 16}
    # 64 decoded tokens whose contexts average 2,300.
    recs = [_Rec(2299, [-1.0, 1.0, 2.0]) for _ in range(32)]
    ctx = _ctx(cut, ops, modules, before, after, recs)
    got = cells.load_reader("kernels.mla_decode_roofline").read(ctx)
    context = 2 * 32 * 2300.5
    least = max(context * 5760 / 819e9,
                2 * 5 * 128 * context * 1088 / 197e12)
    assert got == pytest.approx(100 * least / (2 * 2.25e-3), rel=1e-3)
    assert 20 < got < 25
    share = cells.load_reader("model.experts_touched_share").read(ctx)
    assert share == pytest.approx(100 * 80 / (16 * 4 * 2))
    # The accepted readers read the cell through its own work module.
    roof = cells.load_reader("kernels.decode_step_roofline").read(ctx)
    least_step = work.decode_least_seconds(cut, 2, 64, int(context), V5E)[0]
    assert roof == pytest.approx(100 * least_step / 31e-3, rel=1e-3)


def test_readers_return_nothing_where_the_program_has_no_such_source(cut):
    """The parent's program: no kernel of that name in the trace, no
    such counter. Both readers say nothing and raise nothing."""
    before = {"tdt_engine_decode_steps_total": 1,
              "tdt_engine_generated_tokens_total": 10}
    after = {"tdt_engine_decode_steps_total": 3,
             "tdt_engine_generated_tokens_total": 18}
    modules = [["jit_tdt_decode_step(1)", 0.0, 12e6]]
    ops = [["%tdt_flash_decode_paged.1 = bf16[4,32,128] custom-call(...)",
            1e6, 0.4e6]]
    ctx = _ctx(cut, ops, modules, before, after,
               [_Rec(300, [-1.0, 1.0, 2.0])])
    assert cells.load_reader("kernels.mla_decode_roofline").read(ctx) is None
    assert cells.load_reader("model.experts_touched_share").read(ctx) is None
    dense = dict(ctx, cell=cells.load_cell("qwen3-4b.chat-closed8"))
    assert cells.load_reader("kernels.mla_decode_roofline").read(dense) is None


# -- the reference's own pieces ----------------------------------------------

def test_reference_rotary_is_the_programs_by_numbers(cut):
    from triton_distributed_tpu.ops.attention.rope import yarn_freqs

    m = ref.Dims.of(cut)
    np.testing.assert_allclose(
        ref.inv_freq(m), np.asarray(yarn_freqs(64, 1e4, 40.0, 32.0, 1.0, 4096)),
        rtol=1e-6)
    # Fast dims keep theta^(-2i/64), slow ones are divided by 40.
    base = 1e4 ** -(np.arange(0, 64, 2) / 64)
    assert ref.inv_freq(m)[0] == pytest.approx(base[0])
    assert ref.inv_freq(m)[-1] == pytest.approx(base[-1] / 40)
    assert ref.softmax_scale(m) == pytest.approx(0.1352, abs=5e-5)


def test_reference_router_by_hand():
    m = ref.Dims.of(cells.load_json(
        os.path.join(DATA, "tiny-mla-moe.config.json")))
    m = ref.dataclasses.replace(m, experts=8, n_group=4, topk_group=2,
                                top_k=2)
    s = np.array([[.9, .5, .8, .7, .6, .6, .95, .01]])
    logits = jnp.asarray(np.log(s / (1 - s)), jnp.float32)
    ids, w = ref.route(m, jnp.ones((1, 1)), logits, jnp.zeros(8))
    assert sorted(ids[0].tolist()) == [0, 2]  # group 3's .95 is outside
    assert float(w.sum()) == pytest.approx(2.5, rel=1e-5)
    bias = jnp.zeros(8).at[3].set(0.25)
    ids, w = ref.route(m, jnp.ones((1, 1)), logits, bias)
    assert sorted(ids[0].tolist()) == [0, 3]
    assert sorted(w[0].tolist()) == pytest.approx(
        [2.5 * .7 / 1.6, 2.5 * .9 / 1.6], rel=1e-5)


def test_reference_experts_drop_nothing_when_an_expert_overflows():
    """64 experts, 4 a token: the gather's capacity is a quarter of the
    rows. A bias that sends EVERY row to held expert 1 overflows it, and
    the layer then runs that expert on every row: the same numbers as
    every held expert on every row, weighted by the gate."""
    base = ref.Dims.of(cells.load_json(
        os.path.join(DATA, "tiny-mla-moe.config.json")))
    m = ref.dataclasses.replace(base, experts=64, n_group=4, topk_group=2,
                                top_k=4, held=4, offset=0)
    ks = jax.random.split(jax.random.key(0), 6)
    d, f, t = m.d, m.expert_ffn, 96
    lw = {"router": jax.random.normal(ks[0], (d, 64)) * d ** -0.5,
          "bias": jnp.zeros(64).at[1].set(10.0),
          "w1": jax.random.normal(ks[1], (4, d, 2 * f)) * d ** -0.5,
          "w2": jax.random.normal(ks[2], (4, f, d)) * f ** -0.5,
          "shared_w1": jax.random.normal(ks[3], (d, 2 * f)) * d ** -0.5,
          "shared_w2": jax.random.normal(ks[4], (f, d)) * f ** -0.5}
    x = jax.random.normal(ks[5], (1, t, d))
    live = jnp.arange(t)[None, :] < 90
    got = ref.experts(m, "f32", x, lw, live)
    h = ref.dense._rms(x, m.eps)[0]
    ids, w = ref.route(m, h, lw["router"], lw["bias"])
    assert int((ids == 1).sum()) == t > t // 4  # over the capacity
    y = ref._swiglu(h, lw["shared_w1"], lw["shared_w2"], "f32")
    for e in range(4):
        gate = jnp.sum(jnp.where(ids == e, w, 0.0), axis=-1)
        y += ref._swiglu(h, lw["w1"][e], lw["w2"][e], "f32") * gate[:, None]
    np.testing.assert_allclose(got[0, :90], (x[0] + y)[:90], atol=2e-5,
                               rtol=2e-5)


# -- served and judged by files alone ----------------------------------------

def drive(monkeypatch, capfd, seed):
    dev = jax.devices()[0]
    monkeypatch.setattr(run, "require_chip", lambda chips: {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())})
    monkeypatch.setitem(peaks.PEAKS, dev.device_kind,
                        peaks.Peak(1e12, 1e11, 1e10, "CPU rehearsal"))
    rc = run.main(["--workload", "tiny.rehearsal", "--seed", str(seed),
                   "--seconds", "3", "--trace", "0",
                   "--config-file",
                   os.path.join(DATA, "tiny-mla-moe.config.json"),
                   "--traffic-file",
                   os.path.join(DATA, "tiny-mla-moe.closed.json")])
    out, _ = capfd.readouterr()
    lines = [ln for ln in out.splitlines() if ln.strip()]
    return rc, json.loads(lines[-1])


def test_the_tiny_share_is_served_and_judged_correct(monkeypatch, capfd):
    rc, last = drive(monkeypatch, capfd, 2**31 + 135)
    assert rc == 0 and last["failed"] == 0 and last["attempted"] >= 3
    assert last["correct"] is True, last["checks"]
    assert last["compiles_in_window"] == 0
    assert set(last["metrics"]) == {"token_gap_p95_ms", "setup_s"}


@pytest.mark.parametrize("left_out", ["shared_expert", "bias"])
def test_a_program_that_leaves_a_piece_out_is_not_correct(
        monkeypatch, capfd, left_out):
    """Patched here, no switch in the program: without the shared expert,
    or with the router's bias left out of the choice, the served tokens
    miss the reference's by more than the rehearsal's limits."""
    from triton_distributed_tpu.layers import moe_share

    if left_out == "shared_expert":
        monkeypatch.setattr(moe_share, "swiglu",
                            lambda params, x: jnp.zeros_like(x))
    else:
        route = moe_share.router_group_limited
        monkeypatch.setattr(
            moe_share, "router_group_limited",
            lambda x, w, bias, k, **kw: route(x, w, bias * 0, k, **kw))
    rc, last = drive(monkeypatch, capfd, 2**31 + 136)
    assert rc == 0 and last["failed"] == 0
    assert last["correct"] is False, last["checks"]
    assert last["checks"]["logit_gap_max"]["value"] > 1e-2
