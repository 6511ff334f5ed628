"""The Mamba-2 / attention hybrid through the harness: its plain
reference (`benchmark/reference_hybrid_ssm.py`), its work counts
(`benchmark/work_hybrid_ssm.py`), the cell's two readers, and the
`tiny-hybrid` preset served and judged through `benchmark.run` on the
CPU by files alone."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells, peaks, run, xplane
from benchmark import reference_hybrid_ssm as ref
from benchmark import work_hybrid_ssm as work

DATA = os.path.join(os.path.dirname(__file__), "data")
CELL = "granite-4.0-h-micro.chat-closed32"
V5E = peaks.PEAKS["TPU v5 lite"]


@pytest.fixture(scope="module")
def published():
    return cells.load_cell(CELL).config


@pytest.fixture(scope="module")
def tiny():
    return cells.load_json(os.path.join(DATA, "tiny-hybrid.config.json"))


# -- the cell and its files ---------------------------------------------------

def test_the_cell_carries_its_own_modules():
    c = cells.load_cell(CELL)
    assert c.reference is ref and c.work is work
    assert callable(c.reference.make_weights) and callable(c.reference.judge)
    for fn in ("decode_flops", "prefill_flops", "decode_least_seconds",
               "prefill_least_seconds", "ssm_decode_least_seconds"):
        assert callable(getattr(c.work, fn))
    assert [m["name"] for m in c.end_to_end] == ["token_gap_p95_ms", "setup_s"]
    names = {m["name"] for m in c.per_layer}
    assert {"kernels.ssm_decode_roofline", "model.state_bytes_share",
            "kernels.decode_step_roofline", "model.step_mfu",
            "device.idle_share", "engine.host_gap_p50_ms"} <= names
    assert not any("ttft" in n or n.endswith(".open") or "mla" in n
                   or "experts" in n for n in names)


def test_the_file_keeps_every_published_key_and_cuts_nothing(published):
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        entry = next(json.loads(ln) for ln in f
                     if '"granite-4.0-h-micro"' in ln)
    manifest = cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
    listed = next(c for c in manifest["configs"]
                  if c["name"] == "granite-4.0-h-micro")
    assert listed["reduced"] == published["reduced"] == []
    assert listed["source"] == published["source"] == entry["source_url"]
    for key, value in entry["config"].items():
        assert published[key] == value, key
    argv = published["serve_argv"]
    assert argv == ["--model", "ibm-granite/granite-4.0-h-micro",
                    "--continuous", "--replicas", "1", "--max-batch", "32"]
    assert {"weights", "lm_head", "head_dim", "max_length",
            "max_batch"} <= set(published["assumed"])
    assert "float32" in published["precision"]
    traffic = cells.load_cell(CELL).traffic
    assert (traffic["loop"], traffic["clients"], traffic["stagger_ms"],
            traffic["deck"], traffic["block"], traffic["max_retries"],
            traffic["trace_seconds"]) == ("closed", 32, 5, 512, 32, 200, 10)
    # qwen3-4b.chat-closed8's lengths to the letter.
    closed8 = cells.load_cell("qwen3-4b.chat-closed8").traffic
    assert traffic["classes"] == closed8["classes"]
    # The program's preset holds the same widths.
    from triton_distributed_tpu.models.config import get_config

    cfg = get_config("ibm-granite/granite-4.0-h-micro")
    assert list(cfg.layer_types) == published["layer_types"]
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size,
            cfg.num_q_heads, cfg.num_kv_heads, cfg.head_dim) == (
        published["hidden_size"], published["shared_intermediate_size"],
        published["vocab_size"], published["num_attention_heads"],
        published["num_key_value_heads"], 64)
    assert (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state,
            cfg.mamba_d_conv, cfg.mamba_n_groups, cfg.mamba_chunk_size) == tuple(
        published[k] for k in ("mamba_n_heads", "mamba_d_head",
                               "mamba_d_state", "mamba_d_conv",
                               "mamba_n_groups", "mamba_chunk_size"))
    assert cfg.mamba_n_heads * cfg.mamba_d_head == (
        published["mamba_expand"] * published["hidden_size"])
    assert (cfg.attention_multiplier, cfg.embedding_multiplier,
            cfg.residual_multiplier, cfg.logits_scaling, cfg.rms_eps) == (
        published["attention_multiplier"], published["embedding_multiplier"],
        published["residual_multiplier"], published["logits_scaling"],
        published["rms_norm_eps"])
    assert not cfg.rope


# -- the work counts ----------------------------------------------------------

def test_work_counts_against_a_hand_count_at_the_published_widths(published):
    """ISSUE 37's arithmetic: a Mamba-2 layer 25.8 M beside 50.3 M of
    SwiGLU, an attention layer 10.5 M; 36 x 76.2 + 4 x 60.8 + the head
    205.5 = 3.19 B = 6.38 GB; a slot's state 76.4 MB, 153 MB moved a row
    a step; 8 KB of K/V a token."""
    c = published
    assert work.mamba_params(c) == 2048 * 8512 + 4096 * 2048 == 25_821_184
    assert work.attn_params(c) == 2048 * 48 * 64 + 2048 * 2048 == 10_485_760
    assert work.mlp_params(c) == 3 * 2048 * 8192 == 50_331_648
    total = (36 * (25_821_184 + 50_331_648) + 4 * (10_485_760 + 50_331_648)
             + 2048 * 100_352)
    assert work.matmul_params(c) == total
    assert round(total / 1e9, 2) == 3.19 and round(2 * total / 1e9, 2) == 6.38
    assert work.ssm_state_bytes(c) == 36 * 64 * 64 * 128 * 4
    assert work.state_bytes_per_row(c) == 36 * (64 * 64 * 128 * 4
                                                + 3 * 4352 * 2)
    assert round(work.state_bytes_per_row(c) / 1e6, 1) == 76.4
    assert round(2 * work.state_bytes_per_row(c) / 1e6) == 153
    assert work.kv_bytes_per_token(c) == 4 * 2 * 8 * 64 * 2 == 8192


def test_work_flops_and_least_times(published):
    c = published
    tokens, context = 32, 32 * 500
    per_tok = 2 * work.matmul_params(c) + 36 * 4 * 4096 * 128
    attn = 4 * 4 * 32 * 64 * context
    assert work.decode_flops(c, tokens, context) == per_tok * tokens + attn
    # A full step: weights 6.38 GB + 32 rows x 153 MB + 0.13 GB of K/V.
    secs, bound = work.decode_least_seconds(c, 1, tokens, context, V5E)
    assert bound == "memory"
    assert secs == pytest.approx(
        (2 * work.matmul_params(c) + 32 * 2 * work.state_bytes_per_row(c)
         + context * 8192) / 819e9)
    assert 13.8e-3 < secs < 14.1e-3
    # One row in flight: the weights and little else.
    assert 7.9e-3 < work.decode_least_seconds(c, 1, 1, 500, V5E)[0] < 8.1e-3
    # The kernel alone: the states of 32 rows, read and written.
    secs, bound = work.ssm_decode_least_seconds(c, 32, V5E)
    assert bound == "memory"
    assert secs == pytest.approx(32 * 2 * 36 * 64 * 64 * 128 * 4 / 819e9)
    # Prefill: W_in, W_out, the SwiGLU, the chunked form at Q 256 and 4
    # layers of causal attention, the head once.
    n, head = 1024, 2048 * 100_352
    chunked = 36 * (2 * 256 * 128 + 2 * 256 * 4096 + 4 * 4096 * 128)
    want = ((2 * (work.matmul_params(c) - head) + chunked) * n
            + 4 * 4 * 32 * 64 * (n * (n + 1) // 2) + 2 * head)
    assert work.prefill_flops(c, [n]) == want
    assert work.prefill_least_seconds(c, [n], 1, V5E)[1] == "compute"
    assert work.prefill_least_seconds(c, [32], 1, V5E)[1] == "memory"


# -- the readers --------------------------------------------------------------

def _ctx(ops, modules, counters0, counters1, records=(), cell=CELL):
    form = {"devices": {"0": {"ops": ops, "modules": modules}}, "host": []}
    return {"cell": cells.load_cell(cell), "peak": V5E, "chips": 1,
            "seconds": 50, "t0": 0.0,
            "trace": xplane.Trace.of(form), "records": list(records),
            "counters_window_0": counters0, "counters_window_1": counters1,
            "counters_trace_0": counters0, "counters_trace_1": counters1,
            "trace_t0": 0.0, "trace_t1": 10.0}


class _Rec:
    ok = True

    def __init__(self, prompt_len, token_ts):
        self.prompt_len, self.token_ts = prompt_len, token_ts


def test_readers_on_a_recorded_piece_of_trace(published):
    """Two decode steps as a chip run's trace holds them (operation and
    program names from this PR's traced run; times rounded): the kernel
    runs once in each of the five recurrent scans, and only operations
    named `tdt_ssm_decode` count."""
    step = "jit_tdt_decode_step(1234)"
    kern = ('%tdt_ssm_decode.{} = (f32[32,4,64,16]{{3,2,1,0}}, '
            'f32[1152,64,64,128]{{3,2,1,0}}) custom-call(...), '
            'custom_call_target="tpu_custom_call"')
    ops, modules = [], []
    for k in range(2):
        t = k * 20e6
        modules.append([step, t, 19.0e6])
        ops += [[kern.format(46 + i), t + (1 + 3 * i) * 1e6, dur * 1e6]
                for i, dur in enumerate((0.6, 1.1, 1.1, 1.1, 0.5))]
        ops.append(["%fusion.1041 = bf16[32,16384]{1,0} fusion(...)",
                    t + 17e6, 0.81e6])
    before = {"tdt_engine_decode_steps_total": 100,
              "tdt_engine_generated_tokens_total": 3000,
              "tdt_ssm_decode_rows_total": 1500,
              "tdt_ssm_state_bytes_per_slot": 76_437_504}
    after = {"tdt_engine_decode_steps_total": 102,
             "tdt_engine_generated_tokens_total": 3032,
             "tdt_ssm_decode_rows_total": 1532,
             "tdt_ssm_state_bytes_per_slot": 76_437_504}
    # 32 decoded tokens (16 rows a step) whose contexts average 400.
    recs = [_Rec(399, [-1.0, 1.0, 2.0]) for _ in range(16)]
    ctx = _ctx(ops, modules, before, after, recs)
    got = cells.load_reader("kernels.ssm_decode_roofline").read(ctx)
    least = 32 * 2 * 36 * 64 * 64 * 128 * 4 / 819e9
    assert got == pytest.approx(100 * least / (2 * 4.4e-3), rel=1e-6)
    assert 65 < got < 70
    share = cells.load_reader("model.state_bytes_share").read(ctx)
    moved = 32 * 2 * 76_437_504
    rest = 2 * 2 * work.matmul_params(published) + 32 * 400.5 * 8192
    assert share == pytest.approx(100 * moved / (moved + rest), rel=1e-6)
    assert 27 < share < 28
    # A full step (32 rows) reads 43%, one row 2%.
    full = dict(after, tdt_ssm_decode_rows_total=1564)
    assert 43 < cells.load_reader("model.state_bytes_share").read(
        _ctx(ops, modules, before, full, recs)) < 44
    # The accepted readers read the cell through its own work module.
    roof = cells.load_reader("kernels.decode_step_roofline").read(ctx)
    least_step = work.decode_least_seconds(
        published, 2, 32, int(32 * 400.5), V5E)[0]
    assert roof == pytest.approx(100 * least_step / 38e-3, rel=1e-3)
    assert roof < 100


def test_readers_return_nothing_where_the_program_has_no_such_source():
    """The parent's program, and another configuration's cell: no kernel
    of that name in the trace, no such counter or gauge. Both readers say
    nothing and raise nothing."""
    before = {"tdt_engine_decode_steps_total": 1,
              "tdt_engine_generated_tokens_total": 10}
    after = {"tdt_engine_decode_steps_total": 3,
             "tdt_engine_generated_tokens_total": 18}
    modules = [["jit_tdt_decode_step(1)", 0.0, 12e6]]
    ops = [["%tdt_flash_decode_paged.1 = bf16[4,32,128] custom-call(...)",
            1e6, 0.4e6]]
    recs = [_Rec(300, [-1.0, 1.0, 2.0])]
    for cell in (CELL, "qwen3-4b.chat-closed8"):
        ctx = _ctx(ops, modules, before, after, recs, cell=cell)
        assert cells.load_reader("kernels.ssm_decode_roofline").read(
            ctx) is None
        assert cells.load_reader("model.state_bytes_share").read(ctx) is None
    # An untraced run's context holds no trace counters at all.
    ctx = _ctx(ops, modules, before, after, recs)
    del ctx["counters_trace_0"], ctx["counters_trace_1"]
    assert cells.load_reader("kernels.ssm_decode_roofline").read(ctx) is None


# -- the reference's own pieces ----------------------------------------------

def test_reference_imports_nothing_from_the_program():
    with open(ref.__file__) as f:
        src = f.read()
    assert "triton_distributed_tpu" not in src.split('"""', 2)[2]
    assert "lax.scan" in src and 'precision="highest"' in src


def test_reference_mixer_against_a_loop_by_hand(tiny):
    """The mamba mixer over 9 positions against plain numpy, a position
    and a head at a time: the convolution's zeros before the start, the
    recurrence from a zero state, the gated norm over all channels."""
    m = ref.Dims.of(tiny)
    w = ref.make_weights(tiny, 5, jax.devices()[:1])
    lw = {k[2:]: np.asarray(v[1], np.float64) for k, v in w.items()
          if k.startswith("m.")}
    x = np.asarray(jax.random.normal(jax.random.key(1), (1, 9, m.d)),
                   np.float64)
    got = np.asarray(ref.mamba(m, "f32", jnp.asarray(x, jnp.float32),
                               {k: jnp.asarray(v, jnp.float32)
                                for k, v in lw.items()}))[0]
    u = x[0] / np.sqrt((x[0] ** 2).mean(-1, keepdims=True) + m.eps)
    proj = u @ lw["w_in"]
    z, xbc, dt = np.split(proj, [m.inner, m.inner + m.conv_dim], axis=-1)
    conv = np.zeros_like(xbc)
    for t in range(9):
        for k in range(m.taps):
            if t - (m.taps - 1) + k >= 0:
                conv[t] += lw["conv_w"][k] * xbc[t - (m.taps - 1) + k]
    conv = conv / (1 + np.exp(-conv))
    xs, b, c = np.split(conv, [m.inner, m.inner + m.state], axis=-1)
    xs = xs.reshape(9, m.heads, m.head_dim)
    delta = np.log1p(np.exp(dt + lw["dt_bias"]))
    a = -np.exp(lw["a_log"])
    s = np.zeros((m.heads, m.head_dim, m.state))
    y = np.zeros((9, m.heads, m.head_dim))
    for t in range(9):
        for h in range(m.heads):
            s[h] = (np.exp(delta[t, h] * a[h]) * s[h]
                    + delta[t, h] * np.outer(xs[t, h], b[t]))
            y[t, h] = s[h] @ c[t] + xs[t, h]
    g = y.reshape(9, m.inner) * (z / (1 + np.exp(-z)))
    g = g / np.sqrt((g ** 2).mean(-1, keepdims=True) + m.eps)
    want = x[0] + m.res_mult * (g @ lw["w_out"])
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_reference_controls_read_worse_than_the_reference(tiny):
    """The int8 control and the reference's own (``S`` rounded to bf16
    after every position) both move logits; float32 against itself reads
    nought."""
    m = ref.Dims.of(tiny)
    w = ref.make_weights(tiny, 3, jax.devices()[:1])
    toks = np.random.default_rng(0).integers(0, 256, (2, 40))
    rows, cols = np.asarray([0, 0, 1, 1]), np.asarray([20, 39, 7, 39])
    f32 = ref.forward_logits(m, w, toks, rows, cols)
    assert f32.shape == (4, 256) and float(jnp.abs(f32).max()) < 2.0
    for mode, least in (("int8", 1e-3), ("bf16_state", 1e-5), ("bf16", 1e-3)):
        low = ref.forward_logits(m, w, toks, rows, cols, mode=mode)
        assert float(jnp.abs(low - f32).max()) > least, mode
    np.testing.assert_array_equal(
        f32, ref.forward_logits(m, w, toks, rows, cols))
    # Right-padding changes no real position: the recurrence is causal.
    cut = ref.forward_logits(m, w, toks[:, :24], rows[:1], cols[:1])
    np.testing.assert_allclose(cut[0], f32[0], atol=1e-6)


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
                   os.path.join(DATA, "tiny-hybrid.config.json"),
                   "--traffic-file",
                   os.path.join(DATA, "tiny-hybrid.closed.json")])
    out, _ = capfd.readouterr()
    lines = [ln for ln in out.splitlines() if ln.strip()]
    return rc, json.loads(lines[-1])


def test_the_tiny_hybrid_is_served_and_judged_correct(monkeypatch, capfd):
    rc, last = drive(monkeypatch, capfd, 2**31 + 137)
    assert rc == 0 and last["failed"] == 0 and last["attempted"] >= 4
    assert last["correct"] is True, last["checks"]
    assert last["compiles_in_window"] == 0
    assert set(last["metrics"]) == {"token_gap_p95_ms", "setup_s"}


def test_a_program_that_starts_from_a_stale_state_is_not_correct(
        monkeypatch, capfd):
    """Patched here, no switch in the program: an admission that starts
    from whatever state the slot's last request left (rule (a) of
    docs/serving.md "Recurrent state beside pages") serves tokens that
    miss the reference's by more than the limits."""
    from triton_distributed_tpu.models import hybrid_ssm

    chunk = hybrid_ssm.mamba2_chunk
    monkeypatch.setattr(
        hybrid_ssm, "mamba2_chunk",
        lambda *a, q_offset, **kw: chunk(*a, q_offset=q_offset + 1, **kw))
    rc, last = drive(monkeypatch, capfd, 2**31 + 138)
    assert rc == 0 and last["failed"] == 0
    assert last["correct"] is False, last["checks"]
