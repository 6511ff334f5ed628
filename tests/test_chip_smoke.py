"""CPU rehearsal of ``chip_smoke.py`` and the compile-cache rule.

The smoke exists to fail when the chip is not there, so the first thing
shown is that it does: unsteered, on this CPU, it exits non-zero before
serving anything. The platform check is then steered FROM THE TEST (the
script has no option for it) to show that the phases themselves pass at
``tiny`` size through the same entry points, and that a phase that
raises ends the run with no result line.
"""

import importlib.util
import json
import os

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def steered(smoke, monkeypatch):
    """The smoke with its platform check replaced, and no compile cache
    left behind in the checkout (the cache rule has its own tests)."""
    from triton_distributed_tpu.runtime import compile_cache

    monkeypatch.setattr(smoke, "check_on_chip", lambda ctx=None: None)
    monkeypatch.setattr(compile_cache, "enable_compile_cache",
                        lambda: "off for the rehearsal")
    return smoke


@pytest.fixture
def cache_config():
    """Put JAX's compile-cache configuration back after a test that
    moved it, so later tests in this worker compile as before."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    compilation_cache.reset_cache()


def test_fails_without_a_tpu_before_serving(smoke, monkeypatch, capsys):
    def never(*a, **kw):
        raise AssertionError("a phase ran without a TPU")

    monkeypatch.setattr(smoke, "serve_phase", never)
    monkeypatch.setattr(smoke, "logit_checks", never)
    with pytest.raises(SystemExit) as exit_:
        smoke.main(["--model", "tiny"])
    assert exit_.value.code not in (0, None)
    assert "needs a TPU" in str(exit_.value.code)
    assert capsys.readouterr().out == ""  # no result, no phase line


def test_context_check_refuses_interpreted_kernels(smoke, monkeypatch):
    """The second half of the device check: a context that interprets
    its Pallas kernels is refused even when JAX reports a TPU."""
    from triton_distributed_tpu.runtime import mesh

    monkeypatch.setattr(
        smoke, "device_facts",
        lambda: {"platform": "tpu", "kind": "steered", "count": 1},
    )
    ctx = mesh.initialize_distributed(tp=1, devices=jax.devices()[:1])
    try:
        with pytest.raises(RuntimeError, match="not on the TPU"):
            smoke.check_on_chip(ctx)
    finally:
        mesh.finalize_distributed()


def _result_lines(capsys):
    return [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("{")]


def test_phases_pass_at_tiny_size_when_steered(steered, monkeypatch, capsys):
    # Two served phases (full-width and int8 pools) through
    # run_server.main and the socket, then on to the kernel and logit
    # checks, which the next test runs. The payloads' prompts are the
    # chip's; its 24 tokens a request are there for the megakernel modes
    # to chain launches, and every step is interpreted here.
    payloads = steered.make_payloads

    def short(cfg):
        a, b = payloads(cfg)
        a["gen_lens"], b["gen_lens"] = [6, 5, 3, 2], [2, 3]
        return a, b

    monkeypatch.setattr(steered, "make_payloads", short)
    monkeypatch.setattr(steered, "logit_checks",
                        lambda model: steered.emit(checked=model))
    assert steered.main(["--model", "tiny", "--modes", "xla,int8"]) == 0
    lines = _result_lines(capsys)
    d = jax.devices()
    assert lines[-1] == {"ok": True, "device": {
        "platform": d[0].platform, "kind": d[0].device_kind,
        "count": len(d)}}
    assert lines[-2] == {"checked": "tiny"}  # after both served phases
    served = [ln for ln in lines if ln.get("phase") == "serve"]
    assert [ln["mode"] for ln in served] == ["xla", "int8"]
    assert all(ln["prefix_hit_tokens_warm"] > 0 for ln in served)
    assert all(ln["warm_repeat_reproduced_cold_tokens"] for ln in served)


def test_kernel_and_logit_checks_pass_at_tiny_size_when_steered(
        steered, capsys):
    steered.logit_checks("tiny")
    lines = _result_lines(capsys)
    assert [ln["phase"] for ln in lines] == ["kernels", "logits"]
    kernels, logits = lines
    assert max(kernels["max_abs_err"].values()) <= kernels["tolerance"]
    assert logits["rel_err"]["full"] <= logits["tolerance"]
    # The int8 pool is the lower-precision path the full-width bound
    # has to be able to tell apart.
    assert logits["int8_exceeds_full_width_tolerance"]


def test_latent_phase_at_tiny_size_when_steered(steered, monkeypatch, capsys):
    """``--modes latent`` at the `tiny-mla-moe` preset with a share of 4
    of its 16 experts: the absorbed decode kernel against the plain
    formula, then the cut preset served on every slot."""
    monkeypatch.setattr(steered, "LATENT", {
        "model": "tiny-mla-moe", "slots": 4,
        "cut": ["--experts-held", "4", "--expert-offset", "8"]})
    assert steered.main(["--modes", "latent"]) == 0
    lines = _result_lines(capsys)
    assert lines[-1]["ok"] is True
    kernel = next(ln for ln in lines if ln.get("phase") == "latent_kernel")
    assert kernel["max_abs_err"]["mla_decode_paged"] <= kernel["tolerance"]
    served = next(ln for ln in lines if ln.get("phase") == "latent_serve")
    assert served["slots"] == 4 and served["tokens_generated"] > 0
    assert served["kv_bytes_per_token"] == (32 + 16) * 4 * 3
    assert 0 < served["experts_touched"] <= served["local_rows"]
    assert served["warm_repeat_reproduced_cold_tokens"]
    assert not any(ln.get("phase") == "logits" for ln in lines)


def test_hybrid_phase_at_tiny_size_when_steered(steered, monkeypatch, capsys):
    """``--modes hybrid`` at the `tiny-hybrid` preset: the state kernel
    against the plain einsums, then the preset served on every slot;
    the rows advanced are the decoded tokens (and the rows of steps in
    flight under a slot that had ended)."""
    monkeypatch.setattr(steered, "HYBRID", {"model": "tiny-hybrid",
                                            "slots": 4})
    assert steered.main(["--modes", "hybrid"]) == 0
    lines = _result_lines(capsys)
    assert lines[-1]["ok"] is True
    kernel = next(ln for ln in lines if ln.get("phase") == "hybrid_kernel")
    assert max(kernel["max_abs_err"].values()) <= kernel["tolerance"]
    assert kernel["other_layer_untouched"]
    served = next(ln for ln in lines if ln.get("phase") == "hybrid_serve")
    assert served["slots"] == 4 and served["tokens_generated"] > 0
    assert served["kv_bytes_per_token"] == 2 * 2 * 4 * 16 * 4
    assert served["state_bytes_per_slot"] == 5 * (8 * 16 * 16 * 4
                                                  + 3 * 160 * 4)
    assert served["rows_advanced"] > 0
    assert served["warm_repeat_reproduced_cold_tokens"]
    assert not any(ln.get("phase") == "logits" for ln in lines)


def test_a_phase_that_raises_fails_the_run(steered, monkeypatch, capsys):
    def broken(model, mode):
        raise RuntimeError(f"phase {mode} broke")

    monkeypatch.setattr(steered, "serve_phase", broken)
    with pytest.raises(RuntimeError, match="phase xla broke"):
        steered.main(["--model", "tiny"])
    out = capsys.readouterr().out
    assert '"ok"' not in out


def test_unknown_mode_is_refused(smoke):
    with pytest.raises(SystemExit) as exit_:
        smoke.main(["--modes", "xla,turbo"])
    assert exit_.value.code == 2


def test_cache_rule_env_set_sets_no_directory_in_code(
        monkeypatch, tmp_path, cache_config):
    from triton_distributed_tpu.runtime import compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a, **kw: updates.append(a))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert updates == []


def test_cache_rule_unset_uses_the_checkout(monkeypatch, cache_config):
    from triton_distributed_tpu.runtime import compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(ROOT, ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
