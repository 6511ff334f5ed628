"""The FLOP and byte functions against the parameter counts of the
program's own presets, and the peaks table."""

import json
import os

import jax
import pytest

from benchmark import cells, peaks, work

CONFIGS = {
    "qwen3-4b": ("Qwen/Qwen3-4B", 4.02e9),
    "qwen3-8b-tp4": ("Qwen/Qwen3-8B", 8.19e9),
}


def config(name):
    """The cell's configuration; the 8B one, whose cell is not built yet
    (PERF.md, Open questions), from the tests' own data."""
    path = os.path.join(cells.HERE, "configs", f"{name}.json")
    if not os.path.exists(path):
        path = os.path.join(os.path.dirname(__file__), "data",
                            f"{name}.config.json")
    with open(path) as f:
        return json.load(f)


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_params_match_the_published_count_and_the_programs_preset(name):
    from triton_distributed_tpu.models.config import get_config

    cfg = config(name)
    preset, published = CONFIGS[name]
    assert work.total_params(cfg) == pytest.approx(published, rel=5e-3)
    p = get_config(preset)
    # The file's sizes are the ones the program serves.
    assert (p.hidden_size, p.intermediate_size, p.num_layers, p.num_q_heads,
            p.num_kv_heads, p.head_dim, p.vocab_size) == (
        cfg["hidden_size"], cfg["intermediate_size"],
        cfg["num_hidden_layers"], cfg["num_attention_heads"],
        cfg["num_key_value_heads"], cfg["head_dim"], cfg["vocab_size"])
    assert cfg["serve_argv"][cfg["serve_argv"].index("--model") + 1] == preset
    # Matmul weights of one layer, counted from the program's shapes.
    d, hd = p.hidden_size, p.head_dim
    per_layer = (d * (p.num_q_heads + 2 * p.num_kv_heads) * hd
                 + p.num_q_heads * hd * d + 3 * d * p.intermediate_size)
    assert work.layer_matmul_params(cfg) == per_layer
    assert work.weight_bytes(cfg) == 2 * (p.num_layers * per_layer
                                          + d * p.vocab_size)
    assert work.kv_bytes_per_token(cfg) == (
        2 * p.num_layers * p.num_kv_heads * hd * 2)


def test_weight_count_matches_the_tiny_models_own_leaves():
    from triton_distributed_tpu.models import Qwen3
    from triton_distributed_tpu.models.config import get_config
    from triton_distributed_tpu.runtime import mesh

    ctx = mesh.initialize_distributed(tp=1, devices=jax.devices()[:1])
    try:
        model = Qwen3(get_config("tiny"), ctx=ctx)
        shapes = jax.eval_shape(model.init_params, jax.random.key(0))
        n = sum(x.size for x in jax.tree.leaves(shapes))
    finally:
        mesh.finalize_distributed()
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "tiny.config.json")) as f:
        tiny = json.load(f)
    assert work.total_params(tiny) == n


def test_flops_and_least_times():
    cfg = config("qwen3-4b")
    m = work.matmul_params(cfg)
    assert work.decode_flops(cfg, 8, 0) == 16 * m
    assert work.decode_flops(cfg, 1, 1000) - 2 * m == 4 * 36 * 32 * 128 * 1000
    one = work.prefill_flops(cfg, [512])
    assert one == pytest.approx(2 * (m - 2560 * 151936) * 512
                                + 4 * 36 * 32 * 128 * (512 * 513 // 2)
                                + 2 * 2560 * 151936)
    peak = peaks.lookup("TPU v5 lite")
    t, bound = work.decode_least_seconds(cfg, 1, 8, 8 * 500, peak)
    assert bound == "memory"
    assert t == pytest.approx((work.weight_bytes(cfg) + 4000 * 147456) / 819e9)
    t4, _ = work.decode_least_seconds(cfg, 1, 8, 8 * 500, peak, chips=4)
    assert t4 == pytest.approx(t / 4)
    t, bound = work.prefill_least_seconds(cfg, [1024], 1, peak)
    assert bound == "compute" and t == pytest.approx(
        work.prefill_flops(cfg, [1024]) / 197e12)


def test_an_unknown_device_is_an_error():
    with pytest.raises(SystemExit):
        peaks.lookup("cpu")
    assert peaks.lookup("TPU v5 lite").flops_bf16 == 197e12
