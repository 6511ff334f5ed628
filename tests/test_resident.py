"""Resident megakernel decode (ISSUE 19): pipelined NS-step launches,
in-kernel top-k/top-p, batch-bucket launches, device-side stop-token
retire.

Coverage contract:
- ``validate_ring`` keeps every record's ``mid`` inside its clock
  interval;
- the ``tdt_mega_*`` resident series pre-touch to 0 at engine
  construction (the PR 15 convention: a cold counter must READ 0 on
  the dashboard, not be missing), and
  ``tdt_mega_single_step_fallbacks_total`` scrapes 0 after a PURE
  SAMPLED mega run — the in-kernel filter replaced the fallback;
- both serving CLIs refuse --speculative × --mode mega with the
  slot-splice reason (the flag-name substring is pinned by
  test_tools.py; THIS file pins the wording);
- device-side stop-token retire: a slot hitting eos mid-multi-step
  retires with no host round trip, its pages flow back through the
  normal teardown path (radix tree receives the chain, pool audit
  clean), and the co-batched survivor's tokens are bit-exact;
- batch-bucket launches emit bit-identical tokens to the full-width
  program; the resident pipeline serves the non-resident engine's
  tokens at every batch bucket, greedy and sampled, and its traced
  launches validate against the scheduled order;
- no-op filter knobs (top_k >= V, top_p == 1) never force the
  filtered program or the tp>1 fallback, and a drain that faults
  reaches the step guard with the just-issued launch parked in
  ``_pend`` (no orphaned in-flight launch).
"""

import jax
import numpy as np
import pytest

from triton_distributed_tpu.models import AutoLLM
from triton_distributed_tpu.models.engine import Engine
from triton_distributed_tpu.obs import kernel_trace as kt


@pytest.fixture
def ctx1():
    from triton_distributed_tpu.runtime import mesh as mesh_mod

    ctx = mesh_mod.initialize_distributed(tp=1, devices=jax.devices()[:1])
    yield ctx
    mesh_mod.finalize_distributed()


# -- host-side units (no model) -----------------------------------------


def _rec(index, opcode, begin, end, mid=0, task_id=None):
    return kt.TaskRecord(0, 0, index, task_id or index, opcode, 0, 0,
                         begin, end, mid)


def test_validate_ring_mid_clock_check():
    """A record's stamped ``mid`` is a clock tick of its own task: one
    outside ``[begin, end]`` is flagged, one inside (or unstamped) is
    not."""
    from triton_distributed_tpu.megakernel.task import TaskType

    op = int(TaskType.LM_HEAD)
    assert kt.validate_ring(
        [_rec(0, op, 10, 20), _rec(1, op, 20, 40, mid=30)]) == []
    bad = [_rec(0, op, 10, 20, mid=99)]
    assert any("outside" in p for p in kt.validate_ring(bad))


def test_cli_refusals_carry_splice_reason(capsys):
    """Both CLIs refuse --speculative × --mode mega as an argparse
    error (exit 2, before any model load), and the message explains
    the RESIDENT reason: the pipeline splices whole slots between
    rounds, never a mid-launch verify/rollback."""
    from perf import serve_demo
    from triton_distributed_tpu.serving import run_server

    for main in (run_server.main, serve_demo.main):
        with pytest.raises(SystemExit) as exc:
            main(["--speculative", "2", "--mode", "mega"])
        assert exc.value.code == 2
        err = capsys.readouterr().err
        assert "--speculative and --mode mega" in err
        assert "resident pipeline splices whole slots" in err


def test_resident_knob_validation(capsys, own_model):
    """--resident without --mode mega refuses by flag name at the CLI
    (exit 2, nothing loaded); the engine ctor enforces the same pair."""
    from triton_distributed_tpu.models.continuous import ContinuousEngine
    from triton_distributed_tpu.serving import run_server

    with pytest.raises(SystemExit) as exc:
        run_server.main(["--resident", "--mode", "xla"])
    assert exc.value.code == 2
    assert "--resident requires --mode mega" in capsys.readouterr().err
    with pytest.raises(SystemExit):
        run_server.main(["--ns", "0"])

    with pytest.raises(ValueError, match="resident"):
        ContinuousEngine(own_model, max_batch=1, max_length=64,
                         mode="xla", resident=True)
    with pytest.raises(ValueError, match="ns"):
        ContinuousEngine(own_model, max_batch=1, max_length=64,
                         mode="mega", ns=0)


def test_resident_metrics_pretouch(fresh_telemetry, own_model):
    """Engine construction alone pre-touches the resident-decode
    catalog: every new series reads 0 from the first scrape (PR 15
    convention), including the fallback counter the acceptance gate
    watches."""
    from triton_distributed_tpu.models.continuous import ContinuousEngine
    from triton_distributed_tpu.obs import metrics as obs_metrics

    ContinuousEngine(own_model, max_batch=1, page_size=16, max_length=64,
                     mode="mega")
    text = obs_metrics.prometheus_text()
    for name in (
        "tdt_mega_single_step_fallbacks_total",
        "tdt_mega_device_retires_total",
        "tdt_mega_resident_rounds_total",
        "tdt_mega_bucket_launches_total",
        "tdt_mega_filtered_rounds_total",
    ):
        assert f"{name} 0" in text, name


# -- engine paths (tiny model, CPU interpret) ---------------------------


@pytest.mark.slow
def test_device_stop_retire_no_host_round_trip(ctx1):
    """A slot hitting eos mid-multi-step retires off the DEVICE stop
    test (mega_device_retires, not a host-side trim of a full launch),
    its pages flow back through the normal teardown (pool audit clean,
    radix tree receives the finished chain for reuse), and the
    co-batched survivor's tokens are bit-exact."""
    from triton_distributed_tpu.models.continuous import ContinuousEngine

    model = AutoLLM.from_pretrained("tiny", ctx=ctx1)
    p0 = np.asarray([5, 9, 2, 4], np.int32)
    p1 = np.asarray([7, 1, 3, 8, 6, 2, 4, 9], np.int32)
    probe = Engine(model, temperature=0.0).serve(p0[None], gen_len=6)[0, 4:]
    gold1 = Engine(model, temperature=0.0).serve(p1[None], gen_len=6)[0, 8:]
    eos = int(probe[1])  # p0 retires at its 2nd generated token

    eng = ContinuousEngine(
        model, max_batch=2, page_size=16, max_length=64, eos_id=eos,
        mode="mega", prefix_cache=True,
    )
    free0 = len(eng.pool.free)
    outs = eng.run([(p0, 6), (p1, 6)])
    st = eng.stats
    assert st["mega_device_retires"] >= 1, st
    np.testing.assert_array_equal(outs[0], probe[:2])
    gold1_trim = gold1[: np.argmax(gold1 == eos) + 1] \
        if eos in gold1.tolist() else gold1
    np.testing.assert_array_equal(outs[1], np.asarray(gold1_trim))
    # Pages audit clean and back in the free list ∪ radix tree.
    assert eng.audit() == []
    # The retired chain landed in the radix tree: a re-run of the same
    # prompt + generated chain matches cached pages.
    chain = np.concatenate([p0, outs[0]])
    m = eng.prefix.match(chain)
    assert m.matched_len > 0
    eng.prefix.release_match(m)
    assert free0 == len(eng.pool.free) + eng.prefix.reclaimable_pages()


@pytest.mark.slow
def test_bucket_launch_bit_exact(ctx1):
    """2 live slots in a max_batch=4 engine ride a 2-wide bucket
    program (mega_bucket_launches) and emit exactly the tokens the
    full-width program emits — which themselves match the unfused
    goldens."""
    from triton_distributed_tpu.models.continuous import ContinuousEngine

    model = AutoLLM.from_pretrained("tiny", ctx=ctx1)
    prompts = [np.asarray([5, 9, 2, 4], np.int32),
               np.asarray([7, 1, 3, 8, 6, 2, 4, 9], np.int32)]
    gens = [5, 3]
    golds = [
        Engine(model, temperature=0.0).serve(p[None], gen_len=g)[0, len(p):]
        for p, g in zip(prompts, gens)
    ]

    def run(buckets):
        eng = ContinuousEngine(
            model, max_batch=4, page_size=16, max_length=64,
            mode="mega", mega_buckets=buckets,
        )
        outs = eng.run(list(zip(prompts, gens)))
        return outs, eng.stats

    outs_full, st_full = run(False)
    outs_b, st_b = run(True)
    assert st_full["mega_bucket_launches"] == 0
    assert st_b["mega_bucket_launches"] > 0, st_b
    for a, b, gold in zip(outs_full, outs_b, golds):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(b, np.asarray(gold))


_PROMPTS = [np.asarray(p, np.int32) for p in (
    [5, 9, 2, 4], [7, 1, 3, 8, 6, 2, 4, 9], [3, 3, 8, 1, 6], [2, 7, 4],
)]


@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "sampled"])
@pytest.mark.parametrize("bucket", [1, 2, 4])
def test_resident_matches_non_resident(own_model, bucket, sampled):
    """The resident pipeline (launch i+1 issued off launch i's device
    outputs, before launch i drains) serves the non-resident engine's
    tokens, token for token, at every batch bucket of a 4-slot engine,
    greedy and seeded-sampled (the slow tests below hold the greedy
    tokens to the unfused goldens)."""
    from triton_distributed_tpu.models.continuous import ContinuousEngine

    prompts = _PROMPTS[:bucket]
    knobs = dict(temperature=0.8, seed=3) if sampled else {}

    def run(resident):
        eng = ContinuousEngine(
            own_model, max_batch=4, page_size=16, max_length=64,
            mode="mega", ns=2, resident=resident, **knobs,
        )
        outs = eng.run([(p, 6) for p in prompts])
        assert eng.audit() == []
        return outs, eng.stats

    outs_res, st = run(True)
    outs_plain, st_plain = run(False)
    assert st["mega_resident_rounds"] > 0, st
    assert st_plain["mega_resident_rounds"] == 0, st_plain
    for a, b in zip(outs_res, outs_plain):
        np.testing.assert_array_equal(a, b)


@pytest.mark.slow
def test_resident_pipeline_trace_validates(ctx1):
    """Resident decode under the device task tracer: round i+1 issues
    off round i's device outputs (mega_resident_rounds), every traced
    launch's records validate, and tokens stay bit-exact."""
    from triton_distributed_tpu.models.continuous import ContinuousEngine

    model = AutoLLM.from_pretrained("tiny", ctx=ctx1)
    prompts = _PROMPTS[:2]
    golds = [
        Engine(model, temperature=0.0).serve(p[None], gen_len=6)[0, len(p):]
        for p in prompts
    ]
    eng = ContinuousEngine(
        model, max_batch=2, page_size=16, max_length=64, mode="mega",
        resident=True, kernel_trace=True, ns=2,
    )
    outs = eng.run([(p, 6) for p in prompts])
    for got, gold in zip(outs, golds):
        np.testing.assert_array_equal(got, np.asarray(gold))
    assert eng.stats["mega_resident_rounds"] > 0, eng.stats
    launches = eng.kernel_trace_launches()
    assert launches
    for ln in launches:
        assert kt.validate_ring(ln.get_records()) == []


@pytest.mark.slow
def test_sampled_run_scrapes_zero_fallbacks(fresh_telemetry, ctx1):
    """The acceptance gate: a PURE SAMPLED workload (every slot top-k +
    top-p) serves entirely through the in-kernel bisection filter —
    ``tdt_mega_single_step_fallbacks_total`` scrapes 0 and the filtered
    counter shows the rounds that previously fell back."""
    from triton_distributed_tpu.models.continuous import ContinuousEngine
    from triton_distributed_tpu.obs import metrics as obs_metrics

    model = AutoLLM.from_pretrained("tiny", ctx=ctx1)
    prompts = [np.asarray([5, 9, 2, 4], np.int32),
               np.asarray([7, 1, 3, 8, 6, 2, 4, 9], np.int32)]
    eng = ContinuousEngine(
        model, max_batch=2, page_size=16, max_length=64, mode="mega",
        temperature=0.8, top_k=5, top_p=0.9, seed=3,
    )
    outs = eng.run([(p, 6) for p in prompts])
    assert all(len(o) == 6 for o in outs)
    st = eng.stats
    assert st["mega_filtered_rounds"] > 0, st
    assert st["mega_fallback_steps"] == 0, st
    reg = obs_metrics.default_registry()
    assert reg.get("tdt_mega_single_step_fallbacks_total").value() == 0
    assert reg.get("tdt_mega_filtered_rounds_total").value() > 0
    assert "tdt_mega_single_step_fallbacks_total 0" in \
        obs_metrics.prometheus_text()


@pytest.mark.slow
def test_noop_filter_knobs_stay_fused(ctx1):
    """top_k >= vocab_size with top_p == 1 is a NO-OP filter: the plan
    gate must agree with the per-row enable (0 < k < V or p < 1) and
    compose the plain sampled launch — no filtered program at tp == 1
    (and no permanent single-step fallback at tp > 1). Tokens are
    bit-identical to the unfiltered sampled engine at the same seed."""
    from triton_distributed_tpu.models.continuous import ContinuousEngine

    model = AutoLLM.from_pretrained("tiny", ctx=ctx1)
    V = model.cfg.vocab_size
    prompts = [np.asarray([5, 9, 2, 4], np.int32),
               np.asarray([7, 1, 3, 8, 6, 2, 4, 9], np.int32)]

    def run(top_k, top_p):
        eng = ContinuousEngine(
            model, max_batch=2, page_size=16, max_length=64, mode="mega",
            temperature=0.8, top_k=top_k, top_p=top_p, seed=3,
        )
        return eng.run([(p, 6) for p in prompts]), eng.stats

    outs_noop, st = run(top_k=V, top_p=1.0)
    assert st["mega_filtered_rounds"] == 0, st
    assert st["mega_fallback_steps"] == 0, st
    outs_plain, _ = run(top_k=0, top_p=1.0)
    for a, b in zip(outs_noop, outs_plain):
        np.testing.assert_array_equal(a, b)


@pytest.mark.slow
def test_resident_drain_fault_parks_inflight_launch(ctx1):
    """A drain that raises mid-resident-round must reach the step guard
    with the just-issued NEXT launch already parked in ``_pend`` — so
    ``_abort_pend`` blocks on it before teardown frees pages it still
    reads (the pre-fix ordering drained first and orphaned the launch).
    The engine stays reusable and bit-exact afterwards."""
    from triton_distributed_tpu.models.continuous import ContinuousEngine
    from triton_distributed_tpu.runtime.faults import FaultPlan

    model = AutoLLM.from_pretrained("tiny", ctx=ctx1)
    prompts = [np.asarray([5, 9, 2, 4], np.int32),
               np.asarray([7, 1, 3, 8, 6, 2, 4, 9], np.int32)]
    golds = [
        Engine(model, temperature=0.0).serve(p[None], gen_len=6)[0, len(p):]
        for p in prompts
    ]
    eng = ContinuousEngine(
        model, max_batch=2, page_size=16, max_length=64, mode="mega",
        resident=True, ns=2,
    )
    # Spy on the drain entry: on pipelined rounds the next launch must
    # already be owned by ``_pend`` when the (possibly raising) drain
    # begins.
    parked, orig = [], eng._drain_launch
    eng._drain_launch = lambda pend: (
        parked.append(eng._pend is not None), orig(pend)
    )[1]
    with FaultPlan().on("engine.mega_drain", at=1):
        results = eng.run([(p, 6) for p in prompts], results=True)
    assert parked and parked[0], parked
    assert all(r.status == "failed" for r in results)
    assert all("injected" in r.reason for r in results)
    assert eng._pend is None  # the guard's _abort_pend reclaimed it
    assert eng.last_stats["decode_faults"] == 1
    assert eng.audit() == []
    eng._drain_launch = orig
    outs = eng.run([(p, 6) for p in prompts])
    for got, gold in zip(outs, golds):
        np.testing.assert_array_equal(got, np.asarray(gold))
