"""The rest of a run with the timed path broken underneath: `correct`
has to come out false, once for each fault a served cell can have. (A
step that returns its state unchanged, and half of a batch left out of a
mean, are faults of training cells; the benchmark has none.)"""

import jax

from test_benchmark_rehearsal import drive


def test_an_altered_token_makes_correct_false(monkeypatch, capfd):
    """The fault a served cell can have: a token altered where it is
    produced. Every greedy pick becomes the second best; the stream stays
    well-formed, so only the comparison with the reference can see it."""
    import jax.numpy as jnp

    from triton_distributed_tpu.models import sampling

    monkeypatch.setattr(
        sampling, "greedy",
        lambda logits: jnp.argsort(logits, axis=-1)[..., -2].astype(jnp.int32))
    jax.clear_caches()  # programs that traced the sound pick
    try:
        rc, last, _, err = drive(monkeypatch, capfd, "tiny.chat.json",
                                 2**31 + 103)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert rc == 0 and last["failed"] == 0
    assert last["correct"] is False
    assert last["checks"]["logit_gap_max"]["value"] > 1e-2
    assert "check logit_gap_max" in err


def test_the_exchange_between_chips_left_out_makes_correct_false(
        monkeypatch, capfd):
    """The fault a cell across chips can have: at tp=2 every all-reduce
    returns the rank's own partial sum. Requests still end `ok`."""
    monkeypatch.setattr(jax.lax, "psum", lambda x, axis_name, **kw: x)
    jax.clear_caches()
    try:
        rc, last, _, _ = drive(monkeypatch, capfd, "tiny.closed.json",
                               2**31 + 104, seconds="3",
                               config_file="tiny-tp2.config.json")
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert rc == 0 and last["failed"] == 0
    assert last["correct"] is False
    assert last["checks"]["logit_gap_max"]["value"] > 0.1


def test_the_int8_control_comes_out_not_correct_through_the_harness(
        monkeypatch, capfd):
    """The builder's tool offers windows and judges them by the run's own
    functions: with ``--control int8`` the picks of the reference in int8
    stand in the served tokens' place, and `correct` reads false beside a
    sound window that reads true. (A CPU window at `tiny` serves some 20
    tokens of a 256-word vocabulary, among which int8 flips none: the
    control rounds to 2 bits here. The cells' own limits against the
    chip's readings: `test_benchmark_limits.py`.)"""
    import json
    import os

    import jax.numpy as jnp

    from benchmark import peaks, reference, run, sweep
    from test_benchmark_rehearsal import DATA

    def q2(x, axis):
        s = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
        s = jnp.where(s == 0, 1.0, s)
        return jnp.clip(jnp.rint(x / s), -1, 1).astype(jnp.int8), s

    monkeypatch.setattr(reference, "_q8", q2)
    jax.clear_caches()
    dev = jax.devices()[0]
    monkeypatch.setattr(run, "require_chip", lambda chips: {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())})
    monkeypatch.setitem(peaks.PEAKS, dev.device_kind,
                        peaks.Peak(1e12, 1e11, 1e10, "CPU rehearsal"))
    rc = sweep.main(["--workload", "tiny.rehearsal", "--seeds",
                     str(2**31 + 105), "--seconds", "3", "--check",
                     "--control", "int8",
                     "--config-file", os.path.join(DATA, "tiny.config.json"),
                     "--traffic-file", os.path.join(DATA, "tiny.closed.json")])
    out, _ = capfd.readouterr()
    lines = [json.loads(ln) for ln in out.splitlines() if ln.startswith("{")]
    by = {ln["phase"]: ln for ln in lines}
    assert rc == 0 and by["window"]["failed"] == 0
    assert by["reference"]["correct"] is True, by["reference"]["checks"]
    assert by["control"]["correct"] is False
    assert set(by["control"]["checks"]) == set(by["reference"]["checks"])
    assert (by["control"]["checks"]["logit_gap_mean"]["value"]
            > 10 * by["control"]["checks"]["logit_gap_mean"]["limit"])
    monkeypatch.undo()
    jax.clear_caches()
