"""The reduction from a trace to busy and idle time, time by program
and by operation, and gaps with what the host was doing — on a hand-made
trace with known answers and on a short piece of a trace recorded on
the chip (`benchmark/testdata/trace_sample.json`)."""

import json
import os

import pytest

from benchmark import cells, xplane

MS = 1e6


def handmade():
    ops = [  # two decode steps, a prefill between them, nested fusion
        ["fusion.1", 0 * MS, 4 * MS], ["dot.2", 1 * MS, 2 * MS],
        ["all-reduce.3", 4 * MS, 1 * MS],
        ["fusion.7", 10 * MS, 30 * MS],
        ["fusion.1", 50 * MS, 4 * MS], ["all-reduce.3", 54 * MS, 1 * MS],
    ]
    modules = [
        ["jit_decode_step(123)", 0 * MS, 5 * MS],
        ["jit_prefill_chunk(9)", 10 * MS, 30 * MS],
        ["jit_decode_step(123)", 50 * MS, 5 * MS],
    ]
    host = [
        ["python", "engine.step", 0 * MS, 60 * MS],
        ["python", "sample_tokens", 5 * MS, 4 * MS],
        ["python", "prefix_cache.insert", 41 * MS, 8 * MS],
    ]
    return {"devices": {"0": {"ops": ops, "modules": modules}}, "host": host}


def test_union_merges_nested_and_overlapping_intervals():
    assert xplane.union_ns([(0, 4), (1, 2), (4, 1), (10, 5), (12, 10)]) == 17
    assert xplane.union_ns([]) == 0


def test_busy_idle_and_window():
    tr = xplane.Trace.of(handmade())
    assert tr.window_s == pytest.approx(0.060)
    assert tr.busy_s == pytest.approx(0.040)  # 5 + 30 + 5 ms
    assert [round(d / MS) for _, d in tr.idle_gaps()] == [5, 10, 5]


def test_name_pattern_sums_and_gaps():
    tr = xplane.Trace.of(handmade())
    decode = tr.modules(r"decode")
    assert [d / MS for _, _, d in decode] == [5, 5]
    assert sum(d for _, _, d in tr.modules(r"prefill")) == 30 * MS
    assert tr.modules(r"nothing") == []
    # The gap before the second decode step (after the prefill): 10 ms;
    # the first launch of a trace has no gap before it.
    assert tr.gaps_before(s for _, s, _ in decode) == [10 * MS]
    assert tr.gaps_before(s for _, s, _ in tr.modules(r"prefill")) == [5 * MS]
    assert tr.gaps_before([]) == []
    # Programs by full name, and the one launched a counted number of
    # times (only those of a millisecond or more a launch).
    assert set(tr.programs()) == {"jit_decode_step(123)",
                                  "jit_prefill_chunk(9)"}
    assert tr.program_launched(2) == "jit_decode_step(123)"
    assert tr.program_launched(1) == "jit_prefill_chunk(9)"
    assert tr.program_launched(10) is None


def test_gaps_are_attributed_to_the_innermost_host_span():
    tr = xplane.Trace.of(handmade())
    assert tr.host_span_at(7 * MS) == "sample_tokens"
    assert tr.host_span_at(45 * MS) == "prefix_cache.insert"
    assert tr.host_span_at(57 * MS) == "engine.step"
    assert tr.host_span_at(99 * MS) == "(no host span)"
    b = tr.breakdown()
    # By self time: fusion.1 holds dot.2, whose 2 ms are not its own.
    ops = dict(b["device_ops"])
    assert b["device_ops"][0] == ["fusion.7", pytest.approx(0.030)]
    assert ops["fusion.1"] == pytest.approx(0.006)
    assert ops["dot.2"] == pytest.approx(0.002)
    assert ops["all-reduce.3"] == pytest.approx(0.002)
    gaps = dict(b["idle_gaps"])
    assert gaps["prefix_cache.insert"] == pytest.approx(0.010)
    assert gaps["sample_tokens"] == pytest.approx(0.005)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_a_trace_without_events_is_an_error():
    with pytest.raises(ValueError):
        xplane.Trace.of({"devices": {"0": {"ops": [], "modules": []}},
                         "host": []})


@pytest.fixture(scope="module")
def recorded():
    path = os.path.join(cells.HERE, "testdata", "trace_sample.json")
    with open(path) as f:
        return json.load(f)


def test_recorded_trace_reduces(recorded):
    tr = xplane.Trace.of(recorded)
    assert 0 < tr.busy_s <= tr.window_s
    progs = tr.describe()["programs"]
    assert progs and all(n > 0 and t > 0 for _, n, t in progs)
    # The program under test names every jitted step `jit__lambda`; the
    # fingerprint XLA adds tells them apart, and the decode step is the
    # one launched as often as the steps were counted (two here).
    assert all(k.startswith("jit__lambda(") for k in tr.programs())
    step = tr.program_launched(2)
    assert step is not None and tr.program_launched(40) is None
    launches = tr.programs()[step]
    assert all(25e6 < d < 35e6 for _, d in launches)  # 28.5 ms a step
    gaps = tr.gaps_before(s for s, _ in launches)
    # The host spends about 2.5 ms between two steps.
    assert len(gaps) == 2 and all(2e6 < g < 4e6 for g in gaps)
    b = tr.breakdown()
    assert b["device_ops"] and b["device_ops"][0][1] > 0
    # Self times add up to the busy time: nothing is counted twice.
    assert sum(tr.self_seconds().values()) == pytest.approx(tr.busy_s,
                                                            rel=1e-6)
    # The whole pool is copied in every step: the costliest single ops.
    assert any(k.startswith("copy.") and "bf16[36,129,8,128,128]" in k
               for k, _ in b["device_ops"][:4])
