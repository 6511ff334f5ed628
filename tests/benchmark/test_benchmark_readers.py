"""The per-layer readers on a hand-made run: each takes its number from
counters, records and the trace, and returns nothing where there is
nothing to read (never 0 for a share of a roofline or a peak)."""

import os

import pytest

from benchmark import cells, layerwork, peaks, server, xplane
from benchmark.stats import Record

MS = 1e6
CONFIG = cells.load_json(os.path.join(cells.HERE, "configs", "qwen3-4b.json"))


def ctx_of(trace=True, config=CONFIG):
    cell = cells.Cell(name="x", chips=1, config_name="qwen3-4b",
                      config=config, traffic={}, end_to_end=[], per_layer=[],
                      work=cells.load_module(config["work"],
                                             ["benchmark", "tests/benchmark"]))
    # Two requests decode together for 100 steps inside the traced span;
    # one of them was prefilled (512 tokens) inside it too.
    recs = []
    for i, plen in enumerate((512, 256)):
        first = 1.0 if i == 0 else -5.0
        ts = [first] + [1.1 + 0.03 * j for j in range(100)]
        recs.append(Record(i=i, due=0.0, sent=0.0, token_ts=ts,
                           tokens=[1] * 101, status="ok", attempts=1,
                           prompt_len=plen, gen_len=101))
    recs[1].shed, recs[1].attempts = 1, 2
    hist = {"count": 0, "sum": 0.0, "edges": [0.1, 1.0, 10.0],
            "counts": [0, 0, 0, 0]}
    hist1 = dict(hist, count=4, sum=6.0, counts=[0, 1, 3, 0])
    c0 = {"tdt_engine_generated_tokens_total": 10,
          "tdt_engine_decode_steps_total": 5,
          "tdt_engine_prefill_tokens_total": 100,
          "tdt_engine_prefill_chunks_total": 1,
          "tdt_request_queue_wait_seconds": hist,
          "server.shed": 0, "server.requests": 2}
    c1 = {"tdt_engine_generated_tokens_total": 211,
          "tdt_engine_decode_steps_total": 105,
          "tdt_engine_prefill_tokens_total": 612,
          "tdt_engine_prefill_chunks_total": 2,
          "tdt_request_queue_wait_seconds": hist1,
          "server.shed": 1, "server.requests": 5}
    ctx = {"cell": cell, "peak": peaks.lookup("TPU v5 lite"), "chips": 1,
           "seconds": 10.0, "records": recs, "t0": 0.0,
           "counters_window_0": c0, "counters_window_1": c1}
    if trace:
        mods = [["jit_prefill_chunk(1)", 0.0, 60 * MS]]
        mods += [[f"jit__decode_step({j})", (100 + 30 * j) * MS, 20 * MS]
                 for j in range(100)]
        form = {"devices": {"0": {"ops": [[n.split("(")[0], s, d]
                                          for n, s, d in mods],
                                  "modules": mods}},
                "host": [["py", "engine", 0.0, 3100 * MS]]}
        ctx.update(trace=xplane.Trace.of(form), trace_t0=0.9, trace_t1=4.2,
                   counters_trace_0=c0, counters_trace_1=c1)
    return ctx


def read(name, ctx):
    return cells.load_reader(name).read(ctx)


def test_counters_delta_and_histogram_quantile():
    ctx = ctx_of(trace=False)
    d = server.delta(ctx["counters_window_1"], ctx["counters_window_0"])
    assert d["tdt_engine_decode_steps_total"] == 100
    h = d["tdt_request_queue_wait_seconds"]
    assert h["count"] == 4 and h["counts"] == [0, 1, 3, 0]
    # The median is the 2nd of 4: one third into the (1, 10] bucket.
    assert server.histogram_quantile(h, 0.5) == pytest.approx(1 + 9 / 3)
    assert server.histogram_quantile(dict(h, count=0), 0.5) is None


def test_readers_on_the_handmade_run():
    ctx = ctx_of()
    assert read("entry.shed_share", ctx) == pytest.approx(100 * 1 / 4)
    assert read("scheduler.queue_wait_p50_ms", ctx) == pytest.approx(4000.0)
    # 201 tokens less 2 first tokens over 100 steps of 4 slots.
    assert read("scheduler.batch_occupancy", ctx) == pytest.approx(
        100 * 199 / 400)
    assert read("engine.host_gap_p50_ms", ctx) == pytest.approx(10.0)
    assert read("device.idle_share", ctx) == pytest.approx(
        100 * (1 - 2.06 / 3.1))
    # Decode: 100 steps took 2.0 s of device time; each must read the
    # 8.04 GB of weights, 9.8 ms: about 49% plus the live cache.
    roof = read("kernels.decode_step_roofline", ctx)
    assert 49.0 < roof < 52.0
    pre = read("kernels.prefill_roofline", ctx)
    assert 0 < pre < 100
    mfu = read("model.step_mfu", ctx)
    assert 0 < mfu < 5


def test_the_work_readers_count_by_the_configurations_own_module():
    """The same run read through another architecture's work counts (the
    tests' sparse-expert ones): the readers keep their arithmetic and
    divide by that module's numbers."""
    here = os.path.dirname(__file__)
    moe = cells.load_json(os.path.join(here, "data", "tiny-moe.config.json"))
    ctx, dense = ctx_of(config=moe), ctx_of()
    work = ctx["cell"].work
    assert work.__file__.endswith("tiny_moe_work.py")
    steps, tokens, context_sum = layerwork.decode_work(ctx)
    assert (steps, tokens) == (100, 200)  # 201 tokens less one first token
    least, bound = work.decode_least_seconds(moe, steps, tokens, context_sum,
                                             ctx["peak"], 1)
    assert bound == "memory"
    got = read("kernels.decode_step_roofline", ctx)
    assert got == pytest.approx(100 * least / 2.0)  # 100 steps of 20 ms
    assert got < read("kernels.decode_step_roofline", dense) / 1e3
    flops = (work.decode_flops(moe, tokens, context_sum)
             + work.prefill_flops(moe, [512]))
    assert read("model.step_mfu", ctx) == pytest.approx(
        100 * flops / (3.1 * 197e12))


@pytest.mark.parametrize("name", [
    "entry.shed_share", "scheduler.queue_wait_p50_ms",
    "scheduler.batch_occupancy", "scheduler.batch_wait_p50_ms"])
def test_a_split_reader_reads_what_its_twin_reads(name):
    """`<name>.open` is the same reading under the name that an open-loop
    cell reports (it moves another end-to-end metric there)."""
    ctx = ctx_of()
    ctx["counters_window_1"] = dict(
        ctx["counters_window_1"], tdt_request_batch_wait_seconds=ctx[
            "counters_window_1"]["tdt_request_queue_wait_seconds"])
    ctx["counters_window_0"] = dict(
        ctx["counters_window_0"], tdt_request_batch_wait_seconds=ctx[
            "counters_window_0"]["tdt_request_queue_wait_seconds"])
    got = read(name + ".open", ctx)
    assert got is not None and got == read(name, ctx)
    ctx["counters_window_1"] = dict(ctx["counters_window_0"])
    assert read(name + ".open", ctx) is None


def test_readers_return_nothing_without_their_source():
    ctx = ctx_of()
    # No decode or prefill program in the trace: silent, never zero.
    empty = {"devices": {"0": {"ops": [["copy.1", 0.0, MS / 2]],
                               "modules": [["jit_other(1)", 0.0, MS / 2]]}},
             "host": []}
    ctx["trace"] = xplane.Trace.of(empty)
    assert read("kernels.decode_step_roofline", ctx) is None
    assert read("kernels.prefill_roofline", ctx) is None
    assert read("engine.host_gap_p50_ms", ctx) is None
    ctx["counters_window_1"] = dict(ctx["counters_window_0"])
    assert read("scheduler.batch_occupancy", ctx) is None
    assert read("entry.shed_share", ctx) is None
    assert read("scheduler.queue_wait_p50_ms", ctx) is None
