"""The traffic generator is a pure function of (cell file, seed,
seconds), and every seed asks for the same work in the same order."""

import collections
import glob
import json
import os

import pytest

from benchmark import cells, traffic

CHAT = {
    "loop": "open", "rate_per_s": 2.0, "burst_size": 1,
    "classes": [{"share": 1.0,
                 "prompt": {"dist": "lognormal", "median": 256, "sigma": 0.8,
                            "min": 32, "max": 1024},
                 "output": {"dist": "lognormal", "median": 96, "sigma": 0.7,
                            "min": 16, "max": 384}}],
}


def sizes(reqs):
    return collections.Counter((len(r.prompt), r.gen_len) for r in reqs)


def test_same_seed_same_trace():
    a = traffic.generate(CHAT, 2**31 + 11, 40, 151936)
    b = traffic.generate(CHAT, 2**31 + 11, 40, 151936)
    assert a == b
    assert len(a) == 80


def test_seeds_send_the_same_sizes_and_gaps_and_other_tokens():
    a = traffic.generate(CHAT, 1, 40, 151936)
    b = traffic.generate(CHAT, 2, 40, 151936)
    assert [r.prompt for r in a] != [r.prompt for r in b]
    assert [(len(r.prompt), r.gen_len, r.t) for r in a] == [
        (len(r.prompt), r.gen_len, r.t) for r in b]
    assert all(0 < r.t < 40 for r in a)
    assert [r.t for r in a] == sorted(r.t for r in a)
    # The gaps are the evenly spaced quantiles of an exponential, scaled
    # to fill the window: their mean is the cell's, whatever their order.
    ts = [0.0] + [r.t for r in a]
    gaps = [y - x for x, y in zip(ts, ts[1:])]
    assert sum(gaps) / len(gaps) == pytest.approx(0.5, rel=0.02)
    assert max(gaps) > 4 * min(gaps)


def test_lengths_follow_the_cell_file():
    reqs = traffic.generate(CHAT, 5, 50, 1000)
    lens = sorted(len(r.prompt) for r in reqs)
    assert lens[0] >= 32 and lens[-1] <= 1024
    assert 200 <= lens[len(lens) // 2] <= 320           # median 256
    outs = sorted(r.gen_len for r in reqs)
    assert outs[0] >= 16 and outs[-1] <= 384
    assert all(0 <= t < 1000 for r in reqs for t in r.prompt)


def test_bursts_keep_the_mean_rate():
    spec = dict(CHAT, burst_size=6)
    reqs = traffic.generate(spec, 3, 30, 1000)
    assert len(reqs) == 60
    by_t = collections.Counter(r.t for r in reqs)
    assert set(by_t.values()) == {6}


def test_blocks_hold_like_mixes_in_every_seed():
    """With `block`, every stretch of five requests holds one of each
    fifth of the lengths, whatever the seed: the end of a window sees
    the same mix of short and long requests in every run."""
    spec = dict(CHAT, rate_per_s=0.4, block=5)
    for seed in (1, 2, 3):
        reqs = traffic.generate(spec, seed, 50, 151936)
        assert len(reqs) == 20
        assert sorted(len(r.prompt) for r in reqs) == sorted(
            len(r.prompt) for r in traffic.generate(spec, 99, 50, 151936))
        outs = sorted(r.gen_len for r in reqs)
        cut = [outs[4 * k + 3] for k in range(4)] + [outs[-1]]
        for b in range(4):
            got = sorted(r.gen_len for r in reqs[5 * b: 5 * b + 5])
            # One output length from each fifth of the distribution.
            assert all(g <= c for g, c in zip(got, cut[:1] + cut[1:]))
            assert got[-1] > outs[15 - 1]


def test_one_fixed_order_leaves_only_the_tokens_to_the_seed(monkeypatch):
    spec = dict(CHAT, loop="closed", clients=8, deck=32, block=8)
    a = traffic.generate(spec, 1, 40, 151936)
    b = traffic.generate(spec, 2, 40, 151936)
    assert [(len(r.prompt), r.gen_len) for r in a] == [
        (len(r.prompt), r.gen_len) for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in b]
    # The order is the yardstick's one constant, not a cell's knob.
    monkeypatch.setattr(traffic, "ORDER_SEED", traffic.ORDER_SEED + 1)
    c = traffic.generate(spec, 1, 40, 151936)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in c]
    assert sorted(len(r.prompt) for r in a) == sorted(
        len(r.prompt) for r in c)


def test_closed_loop_has_a_deck_and_no_schedule():
    spec = dict(CHAT, loop="closed", clients=8, deck=64)
    reqs = traffic.generate(spec, 9, 40, 1000)
    assert len(reqs) == 64 and all(r.t is None for r in reqs)
    assert sizes(reqs) != sizes(traffic.generate(spec, 10, 40, 1000)) or True
    assert sorted(len(r.prompt) for r in reqs) == sorted(
        len(r.prompt) for r in traffic.generate(spec, 10, 40, 1000))


def test_class_mix_and_shared_prefixes():
    spec = {
        "loop": "open", "rate_per_s": 2.0,
        "classes": [
            {"share": 0.9, "prompt": {"dist": "uniform", "min": 16, "max": 64},
             "output": {"dist": "uniform", "min": 4, "max": 16}},
            {"share": 0.1,
             "prompt": {"dist": "uniform", "min": 2560, "max": 3584},
             "output": {"dist": "uniform", "min": 4, "max": 16}}],
        "sharing": {"prefix_pool": 4, "zipf_a": 1.2,
                    "prefix": {"dist": "uniform", "min": 100, "max": 200}},
    }
    reqs = traffic.generate(spec, 4, 50, 500)
    docs = [r for r in reqs if r.cls == 1]
    assert len(docs) == 10 and all(len(r.prompt) >= 2560 for r in docs)
    heads = collections.Counter(r.prompt[:100] for r in reqs)
    assert len(heads) <= 4 and max(heads.values()) > len(reqs) / 4


def test_every_committed_cell_file_generates():
    for path in glob.glob(os.path.join(cells.HERE, "workloads", "*.json")):
        with open(path) as f:
            spec = json.load(f)
        reqs = traffic.generate(spec, 2**31 + 5, 40, 151936)
        assert reqs, path
        assert traffic.histogram(reqs)["n"] == len(reqs)


@pytest.mark.parametrize("stagger_ms", [None, 50])
def test_closed_loop_callers_start_stagger_ms_apart(monkeypatch, stagger_ms):
    """`stagger_ms` is a traffic parameter of the closed loop: caller k
    sends its first request k * stagger_ms after the window's start, and
    is timed from then; without it all start together."""
    import time

    from benchmark import client

    def served(host, port, prompt, gen_len, rec, **kw):
        time.sleep(0.4)
        rec.status, rec.done = "ok", time.monotonic()
        return rec

    monkeypatch.setattr(client, "stream_one", served)
    spec = {"loop": "closed", "clients": 4, "deck": 8,
            "classes": [{"share": 1.0,
                         "prompt": {"dist": "uniform", "min": 4, "max": 8},
                         "output": {"dist": "uniform", "min": 2, "max": 4}}]}
    if stagger_ms is not None:
        spec["stagger_ms"] = stagger_ms
    reqs = traffic.generate(spec, 5, 1.0, 100)
    t0 = time.monotonic() + 0.05
    recs = client.drive("h", 0, reqs, spec, 0.3, t0)
    assert len(recs) == 4 and all(r.ok for r in recs)  # the window closed
    starts = sorted(r.due - t0 for r in recs)
    want = [k * (stagger_ms or 0) / 1e3 for k in range(4)]
    assert starts == pytest.approx(want, abs=0.02)
    assert [r.i for r in sorted(recs, key=lambda r: r.due)] == (
        [0, 1, 2, 3] if stagger_ms else sorted(r.i for r in recs))
