"""The scheduler's simulation over the cells' fixed arrivals: counts on
the CPU, pinned here so that a later issue can ask where a new round
time lands, and a change to the simulation or to a cell's traffic shows."""

import json
import os

import pytest

from benchmark import cells, sim

MANIFEST = cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
SECONDS = float(MANIFEST["run_seconds"])


def cell_read(name, round_ms, **changed):
    cell = cells.load_cell(name)
    spec = dict(cell.traffic, **changed)
    return sim.read(sim.simulate(spec, round_ms, SECONDS,
                                 sim.slots_of(cell.config)))


def test_closed8_at_a_round_of_12_5_ms():
    got = cell_read("qwen3-4b.chat-closed8", 12.5)
    assert got["requests"] == 73
    assert got["ttft_p50_ms"] == pytest.approx(1596.554)
    assert got["ttft_mean_ms"] == pytest.approx(1863.920)
    assert got["ttft_p90_ms"] == pytest.approx(3715.033)


def test_chat_at_a_round_of_12_5_ms():
    got = cell_read("qwen3-4b.chat", 12.5)
    assert got["requests"] == round(
        cells.load_cell("qwen3-4b.chat").traffic["rate_per_s"] * SECONDS)
    assert got["ttft_p50_ms"] == pytest.approx(CHAT_AT_12_5["ttft_p50_ms"])
    assert got["ttft_mean_ms"] == pytest.approx(CHAT_AT_12_5["ttft_mean_ms"])


CHAT_AT_12_5 = {"ttft_p50_ms": 348.6385, "ttft_mean_ms": 589.7153}


@pytest.mark.parametrize("round_ms, p50", [
    (13.0, 1632.35), (13.75, 1759.55), (15.0, 1915.8), (16.0, 2023.175),
    (16.25, 1779.7)])
def test_callers_that_start_together_read_what_perf_md_recorded(
        monkeypatch, round_ms, p50):
    """PERF.md's table of PR 30 (`prof/closed8_sim.py`, the first run
    finding one caller, with that file's guess at a prefill's time): the
    sawtooth of closed8's median, to the digit."""
    monkeypatch.setattr(sim, "PREFILL_MS", (10.0, 0.05))
    cell = cells.load_cell("qwen3-4b.chat-closed8")
    ttft = sim.simulate(dict(cell.traffic, stagger_ms=0), round_ms, SECONDS,
                        4, first=1)
    assert sim.read(ttft)["ttft_p50_ms"] == pytest.approx(p50)


def test_the_stagger_keeps_every_caller_inside_the_first_run():
    """Request 0 (32 output tokens) runs alone; callers 25 ms apart have
    all sent before it ends at every round of the sweep, so the second
    run holds the other seven whatever the round. 50 ms apart the eighth
    caller misses it under 10.25 ms and every statistic jumps."""
    cell = cells.load_cell("qwen3-4b.chat-closed8")
    assert cell.traffic["stagger_ms"] == 25
    at = sim.sweep(cell.traffic, SECONDS, 4, 10.0, 11.0, 0.05)
    assert at["ttft_mean_ms"]["widest_neighbour_jump"] < 0.06
    apart = sim.sweep(dict(cell.traffic, stagger_ms=50), SECONDS, 4, 10.0,
                      11.0, 0.05)
    assert apart["ttft_mean_ms"]["widest_neighbour_jump"] > 0.5
    assert apart["ttft_mean_ms"]["at_round_ms"] == 10.25


def test_a_threshold_shows_as_a_neighbour_jump():
    """Two requests 638 ms apart, the first decoding 39 rounds after a
    prefill of 15 ms: under a round of 15.97 ms the second finds the
    worker idle, over it the second waits for the first's run."""
    fixed = {"dist": "uniform", "min": 40, "max": 40}
    spec = {"loop": "open", "rate_per_s": 2.0, "burst_size": 1,
            "classes": [{"share": 1.0, "prompt": fixed, "output": fixed}]}
    free = sim.simulate(spec, 8.0, 1.0, 4)
    assert free == pytest.approx([14.96, 14.96])
    assert sim.sweep(spec, 1.0, 4, 9.0, 14.0, 0.05)["ttft_mean_ms"][
        "widest_neighbour_jump"] == 0.0
    at = sim.sweep(spec, 1.0, 4, 14.0, 18.0, 0.05)
    assert at["ttft_mean_ms"]["widest_neighbour_jump"] > 0.05
    assert 15.95 < at["ttft_mean_ms"]["at_round_ms"] <= 16.1
    assert at["requests"] == [2]


def test_more_waiting_than_the_front_door_takes_is_an_error():
    cell = cells.load_cell("qwen3-4b.chat")
    with pytest.raises(ValueError, match="cap of 8"):
        sim.simulate(dict(cell.traffic, rate_per_s=4.0), 12.5, SECONDS, 4)


def test_the_command_prints_one_object(capsys):
    assert sim.main(["--workload", "qwen3-4b.chat-closed8", "--round",
                     "12.5"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["workload"] == "qwen3-4b.chat-closed8"
    assert out["requests"] == 73 and out["a_count_not_a_time"] is True
