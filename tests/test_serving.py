"""Model-server tests: protocol round trip vs direct Engine output.

Parity model: the reference's server is exercised by its chat/bench
clients (``mega_triton_kernel/test/models/``); here the client is
in-process and the golden is ``Engine.serve`` on the same weights.
"""

import numpy as np
import pytest

from triton_distributed_tpu.models import AutoLLM
from triton_distributed_tpu.models.engine import Engine
from triton_distributed_tpu.serving import ModelServer, request


def test_server_round_trip(own_model):
    engine = Engine(own_model, temperature=0.0, mode="xla")

    prompts = np.asarray([[1, 2, 3, 4, 5, 6, 7, 8]], np.int32)
    gold = engine.serve(prompts, gen_len=4)

    server = ModelServer(engine).start()
    try:
        assert request(server.host, server.port, {"cmd": "ping"})["ok"]
        resp = request(
            server.host, server.port,
            {"input_ids": prompts.tolist(), "gen_len": 4},
        )
        np.testing.assert_array_equal(
            np.asarray(resp["output_ids"], np.int32), gold
        )
        assert "decode_ms_per_step" in resp["stats"]
    finally:
        server.shutdown()


def test_server_reports_errors(own_model):
    engine = Engine(own_model, mode="xla")
    server = ModelServer(engine).start()
    try:
        import pytest

        # An odd prompt length serves (what pads it to the tp width is
        # held under tp=4 in tests/test_model.py).
        resp = request(
            server.host, server.port,
            {"input_ids": [[1, 2, 3]], "gen_len": 2},
        )
        assert np.asarray(resp["output_ids"]).shape == (1, 5)

        # A malformed request still surfaces as a server error.
        with pytest.raises(RuntimeError, match="server error"):
            request(
                server.host, server.port,
                {"input_ids": [[1, 2, 3]], "gen_len": 2,
                 "prompt_start": [7]},  # out of range for s=3
            )
    finally:
        server.shutdown()


def test_continuous_batching(own_model):
    """Admission/eviction over the paged pool: mixed-length requests,
    fewer slots than requests, outputs match per-request dense goldens
    and every pool page is released at the end."""
    from triton_distributed_tpu.models.continuous import ContinuousEngine

    prompts = [
        np.asarray([5, 9, 2, 4], np.int32),
        np.asarray([7, 1, 3, 8, 6, 2, 4, 9], np.int32),
        np.asarray([11, 12, 13, 14], np.int32),
    ]
    gens = [5, 3, 4]

    # Goldens: the plain dense engine, one request at a time.
    golds = []
    for p, g in zip(prompts, gens):
        out = Engine(own_model, temperature=0.0).serve(p[None], gen_len=g)
        golds.append(out[0, len(p):])

    eng = ContinuousEngine(
        own_model, max_batch=2, page_size=16, max_length=64
    )
    free0 = len(eng.pool.free)
    outs = eng.run(list(zip(prompts, gens)))
    for got, gold in zip(outs, golds):
        np.testing.assert_array_equal(got, np.asarray(gold))
    assert len(eng.pool.free) == free0  # all pages released


def test_continuous_batching_eos(own_model):
    """A request stopping at eos releases its slot early; the freed
    pages admit the waiting request."""
    from triton_distributed_tpu.models.continuous import ContinuousEngine

    p = np.asarray([5, 9, 2, 4], np.int32)
    # Find what the model actually emits so we can use it as "eos".
    probe = Engine(own_model, temperature=0.0).serve(p[None], gen_len=3)[0, 4:]
    eos = int(probe[1])  # second generated token

    eng = ContinuousEngine(
        own_model, max_batch=1, page_size=16, max_length=64, eos_id=eos
    )
    outs = eng.run([(p, 6), (p, 2)])
    # Request 0 stops right after emitting eos (2 tokens, not 6).
    np.testing.assert_array_equal(outs[0], probe[:2])
    assert len(outs[1]) == 2


def test_continuous_batching_oversubscribed_pool(own_model):
    """num_pages below max_batch*pages_per_seq (the point of paging):
    requests wait for pages, outputs stay correct, capacity errors are
    loud."""
    from triton_distributed_tpu.models.continuous import ContinuousEngine

    p = np.asarray([5, 9, 2, 4], np.int32)
    gold = Engine(own_model, temperature=0.0).serve(p[None], gen_len=4)[0, 4:]

    # 2 slots but only one sequence's worth of pages: strictly serial.
    eng = ContinuousEngine(
        own_model, max_batch=2, page_size=16, max_length=64, num_pages=4
    )
    outs = eng.run([(p, 4), (p, 4)])
    for got in outs:
        np.testing.assert_array_equal(got, np.asarray(gold))

    import pytest

    small = ContinuousEngine(
        own_model, max_batch=1, page_size=16, max_length=64, num_pages=3
    )
    with pytest.raises(ValueError, match="unservable"):
        # Needs 4 pages; capacity is 3.
        small.run([(np.zeros(48, np.int32), 16)])


def test_max_length_page_size_validation(own_model):
    """A misaligned (max_length, page_size) pair must refuse at
    construction NAMING BOTH VALUES — before it, ``pps`` silently
    truncated and the tail tokens had no page."""
    from triton_distributed_tpu.models.continuous import ContinuousEngine

    with pytest.raises(ValueError, match=r"100.*not a multiple.*16"):
        ContinuousEngine(
            own_model, max_batch=1, page_size=16, max_length=100
        )
    # Engine validates against the model's cfg.max_length (128 for
    # tiny) — 48 does not divide it.
    with pytest.raises(ValueError, match=r"max_length=128.*page_size=48"):
        Engine(own_model, paged=True, page_size=48)
    with pytest.raises(ValueError, match=r"max_length.*page_size"):
        Engine(own_model, paged=True, page_size=16).serve(
            [np.arange(1, 9, dtype=np.int32)], gen_len=1, max_length=100
        )


@pytest.mark.parametrize(
    "argv", [["--cp", "2"], ["--rank-page-budget", "512"]],
    ids=["cp", "rank_page_budget"])
def test_run_server_has_no_long_context_flags(argv, capsys):
    """What does not fit a slot is refused (`unservable`), never
    sharded: the flags that chose the virtual-rank path are gone, not
    parked behind a refusal of their own."""
    from triton_distributed_tpu.serving import run_server

    with pytest.raises(SystemExit) as exc:
        run_server.main(["--model", "stub", *argv])
    assert exc.value.code == 2
    assert "unrecognized arguments: " + argv[0] in capsys.readouterr().err


def test_engine_has_no_long_context_options():
    import inspect

    from triton_distributed_tpu.models.continuous import ContinuousEngine

    options = list(inspect.signature(ContinuousEngine.__init__).parameters)
    assert "cp" not in options and "rank_page_budget" not in options
    assert len(options) - 2 <= 27  # self and the model aside


def test_snapshot_longer_than_a_slot_is_unservable(own_model):
    """A snapshot holding more KV than a slot's table row has pages
    for (what the sharded slot's stitched export could ship) is refused
    with a structured `unservable` error while the rest of the batch is
    served; nothing of it reaches the pool."""
    from triton_distributed_tpu.models.continuous import (
        ContinuousEngine,
        Request,
    )
    from triton_distributed_tpu.models.slot_state import SlotSnapshot

    eng = ContinuousEngine(own_model, max_batch=2, page_size=16, max_length=64)
    L, _, hkv, page, hd = eng.cache.k_pages.shape
    pages = np.zeros((L, 5, hkv, page, hd), eng.cache.k_pages.dtype)
    long_prompt = np.arange(1, 71, dtype=np.int32)  # 70 tokens: 5 pages
    snap = SlotSnapshot(
        prompt=long_prompt, out=[3], gen_len=8, kv_len=70, page_size=16,
        kv_dtype=None, k_pages=pages, v_pages=pages,
    )
    p = np.asarray([5, 9, 2, 4], np.int32)
    gold = Engine(own_model, temperature=0.0).serve(p[None], gen_len=4)[0, 4:]
    free = len(eng.pool.free)
    results = eng.run(
        [Request(long_prompt, 8, snapshot=snap.to_wire()), (p, 4)],
        results=True,
    )
    assert results[0].status == "unservable"
    assert results[0].error.status == "unservable"
    assert "exceeds max_length" in results[0].reason
    np.testing.assert_array_equal(results[1].tokens, np.asarray(gold))
    assert eng.stats["migrated_in"] == 0
    assert len(eng.pool.free) == free and eng.audit() == []


@pytest.mark.slow
def test_continuous_batching_mega_multi(ctx4):
    """mode="mega" continuous serving decodes in NS-token chunks
    (paged multi-step launches) with host admission at chunk
    boundaries; outputs must match the dense per-request goldens."""
    from triton_distributed_tpu.models.continuous import ContinuousEngine

    model = AutoLLM.from_pretrained("tiny", ctx=ctx4)
    prompts = [
        np.asarray([5, 9, 2, 4], np.int32),
        np.asarray([7, 1, 3, 8, 6, 2, 4, 9], np.int32),
        np.asarray([11, 12, 13, 14], np.int32),
    ]
    gens = [5, 3, 4]
    golds = []
    for p, g in zip(prompts, gens):
        out = Engine(model, temperature=0.0).serve(p[None], gen_len=g)
        golds.append(out[0, len(p):])

    eng = ContinuousEngine(
        model, max_batch=2, page_size=16, max_length=64, mode="mega"
    )
    free0 = len(eng.pool.free)
    outs = eng.run(list(zip(prompts, gens)))
    for got, gold in zip(outs, golds):
        np.testing.assert_array_equal(got, np.asarray(gold))
    assert len(eng.pool.free) == free0  # all pages released


@pytest.mark.slow
def test_continuous_batching_mega_eos(ctx4):
    """eos mid-chunk: overshoot tokens are discarded, the slot frees at
    the chunk boundary, and the queued request still serves right."""
    from triton_distributed_tpu.models.continuous import ContinuousEngine

    model = AutoLLM.from_pretrained("tiny", ctx=ctx4)
    p = np.asarray([5, 9, 2, 4], np.int32)
    probe = Engine(model, temperature=0.0).serve(p[None], gen_len=3)[0, 4:]
    eos = int(probe[1])

    eng = ContinuousEngine(
        model, max_batch=1, page_size=16, max_length=64, eos_id=eos,
        mode="mega",
    )
    outs = eng.run([(p, 6), (p, 2)])
    np.testing.assert_array_equal(outs[0], probe[:2])
    assert len(outs[1]) == 2


def _mega_compose_engine(model, mode, **kw):
    """The full serving composition the PR 7 fast path must carry:
    int8 pool + radix prefix cache + chunked prefill admission."""
    from triton_distributed_tpu.models.continuous import ContinuousEngine

    return ContinuousEngine(
        model, max_batch=2, page_size=16, max_length=64, mode=mode,
        kv_dtype="int8", prefix_cache=True, prefill_chunk=16, **kw
    )


_COMPOSE_PROMPTS = [
    np.asarray([5, 9, 2, 4], np.int32),
    np.asarray([7, 1, 3, 8, 6, 2, 4, 9], np.int32),
    np.asarray([5, 9, 2, 4, 11, 12], np.int32),  # shares a prefix
]
_COMPOSE_GENS = [5, 3, 4]


@pytest.mark.slow
def test_continuous_mega_int8_compose_greedy(ctx4):
    """The tentpole gate: mode='mega' with the REAL serving
    configuration (int8 pool + prefix cache + chunked prefill, prefix
    reuse across retirements included) emits exactly the unfused int8
    engine's greedy tokens — in-kernel dequant, full-precision launch
    band, sequential append scatter, and overshoot trash-routing all
    compose without changing a single token on this workload."""
    model = AutoLLM.from_pretrained("tiny", ctx=ctx4)
    golds = _mega_compose_engine(model, "xla").run(
        list(zip(_COMPOSE_PROMPTS, _COMPOSE_GENS))
    )
    eng = _mega_compose_engine(model, "mega")
    free0 = len(eng.pool.free)
    outs = eng.run(list(zip(_COMPOSE_PROMPTS, _COMPOSE_GENS)))
    for got, gold in zip(outs, golds):
        np.testing.assert_array_equal(got, np.asarray(gold))
    st = eng.last_stats
    assert st["mega_launches"] > 0
    assert st["kv_dtype"] == "int8"
    # Pages back in the pool or retained by the radix tree — audited by
    # the autouse fixture; here just prove nothing leaked outright.
    assert len(eng.pool.free) + eng.prefix.node_count == free0


@pytest.mark.slow
def test_continuous_mega_sampled_seeded(ctx4):
    """Per-slot temperature sampling INSIDE the fused launch: seeded
    runs are reproducible, launches actually happen (no silent
    fallback), outputs differ from greedy, and a mixed greedy/sampled
    batch (per-request temperature=0 override) still launches fused
    with the greedy slot emitting the greedy chain."""
    from triton_distributed_tpu.models.continuous import Request

    model = AutoLLM.from_pretrained("tiny", ctx=ctx4)

    def sampled_run(seed):
        eng = _mega_compose_engine(model, "mega", temperature=0.9,
                                   seed=seed)
        outs = eng.run(list(zip(_COMPOSE_PROMPTS, _COMPOSE_GENS)))
        return outs, eng.last_stats

    o1, st1 = sampled_run(3)
    o2, _ = sampled_run(3)
    for a, b in zip(o1, o2):
        np.testing.assert_array_equal(a, b)
    assert st1["mega_launches"] > 0
    assert st1["mega_fallback_steps"] == 0
    greedy = _mega_compose_engine(model, "mega").run(
        list(zip(_COMPOSE_PROMPTS, _COMPOSE_GENS))
    )
    assert any(
        not np.array_equal(a, g) for a, g in zip(o1, greedy)
    )
    # Mixed batch: slot-level greedy override rides the sampled launch.
    mixed_eng = _mega_compose_engine(model, "mega", temperature=0.9,
                                     seed=3)
    reqs = [
        Request(_COMPOSE_PROMPTS[0], _COMPOSE_GENS[0], temperature=0.0),
        Request(_COMPOSE_PROMPTS[1], _COMPOSE_GENS[1]),
    ]
    mixed = mixed_eng.run(reqs, results=True)
    assert mixed_eng.last_stats["mega_launches"] > 0
    greedy_solo = _mega_compose_engine(model, "mega").run(
        [(_COMPOSE_PROMPTS[0], _COMPOSE_GENS[0])]
    )
    np.testing.assert_array_equal(mixed[0].tokens, greedy_solo[0])


@pytest.mark.slow
def test_continuous_mega_filtered_sampling_falls_back(ctx4):
    """top-k/top-p slots can't ride the in-kernel Gumbel argmax (it
    samples the unfiltered temperature distribution): those rounds fall
    back to single-step decode with host-side filtered sampling, and
    the fallback counter says so."""
    model = AutoLLM.from_pretrained("tiny", ctx=ctx4)
    eng = _mega_compose_engine(model, "mega", temperature=0.9,
                               top_p=0.8, seed=3)
    outs = eng.run(list(zip(_COMPOSE_PROMPTS[:2], _COMPOSE_GENS[:2])))
    st = eng.last_stats
    assert st["mega_launches"] == 0
    assert st["mega_fallback_steps"] > 0
    assert all(len(o) == g for o, g in zip(outs, _COMPOSE_GENS))


@pytest.mark.slow
def test_continuous_mega_tail_and_overshoot(ctx4):
    """Mega tail paths: a row within NS of max_length single-steps its
    tail (fallback counter), and a row finishing mid-launch discards
    its overshoot tokens with the overshoot KV trash-routed — pool and
    tree stay clean (autouse audit), tokens match the unfused engine."""
    model = AutoLLM.from_pretrained("tiny", ctx=ctx4)
    # 52-token prompt + 12 = 64 == max_length: the last rounds sit
    # within NS of capacity and must fall back.
    p_long = np.arange(1, 53, dtype=np.int32)
    from triton_distributed_tpu.models.continuous import ContinuousEngine

    def run(mode):
        eng = ContinuousEngine(
            model, max_batch=1, page_size=16, max_length=64, mode=mode,
            kv_dtype="int8",
        )
        return eng.run([(p_long, 12)]), eng.last_stats

    (gold,), _ = run("xla")
    (got,), st = run("mega")
    np.testing.assert_array_equal(got, gold)
    assert st["mega_fallback_steps"] > 0
    # Overshoot: gen_len 2 finishes on the first launch (NS=8); the 6
    # overshoot tokens are discarded and their KV trash-routed.
    eng = _mega_compose_engine(model, "mega")
    outs = eng.run([(np.asarray([5, 9, 2, 4], np.int32), 2)])
    assert len(outs[0]) == 2
    assert eng.last_stats["mega_launches"] == 1


@pytest.mark.slow
def test_continuous_mega_telemetry(ctx4):
    """tdt_mega_* telemetry: launch counter and NS-amortization gauge
    mirror ``last_stats`` through the registry, and ``mega:launch``
    events land in the ring."""
    from triton_distributed_tpu.obs import events as obs_events
    from triton_distributed_tpu.obs import metrics as obs_metrics

    model = AutoLLM.from_pretrained("tiny", ctx=ctx4)
    since = obs_events.default_ring().next_seq
    eng = _mega_compose_engine(model, "mega")
    eng.run(list(zip(_COMPOSE_PROMPTS[:2], _COMPOSE_GENS[:2])))
    st = eng.last_stats
    snap = obs_metrics.default_registry().snapshot()
    assert snap["tdt_mega_launches_total"]["series"][0]["value"] >= (
        st["mega_launches"]
    )
    gauge = snap["tdt_mega_ns_amortization"]["series"][0]["value"]
    assert gauge == pytest.approx(
        st["decode_steps"] / max(st["mega_launches"], 1)
    )
    events, _dropped = obs_events.default_ring().tail(since)
    kinds = [e.kind for e in events]
    assert kinds.count("mega:launch") == st["mega_launches"]


def test_continuous_batching_first_token_finishes(own_model):
    """gen_len=1 and first-token-eos requests complete at admission:
    exactly one token back, and the freed slot admits the next request
    immediately."""
    from triton_distributed_tpu.models.continuous import ContinuousEngine

    p = np.asarray([5, 9, 2, 4], np.int32)
    first = int(
        Engine(own_model, temperature=0.0).serve(p[None], gen_len=1)[0, 4]
    )

    eng = ContinuousEngine(own_model, max_batch=1, page_size=16, max_length=64)
    outs = eng.run([(p, 1), (p, 2)])
    assert len(outs[0]) == 1 and int(outs[0][0]) == first
    assert len(outs[1]) == 2

    # eos as the very first sampled token.
    eng2 = ContinuousEngine(
        own_model, max_batch=1, page_size=16, max_length=64, eos_id=first
    )
    outs2 = eng2.run([(p, 6), (p, 2)])
    assert len(outs2[0]) == 1 and int(outs2[0][0]) == first


def test_server_per_request_sampling(own_model):
    """The ``requests`` payload's sampling knobs: scalar broadcast and
    per-request lists reach each Request; a temperature-0 override
    inside a sampled-default engine reproduces the greedy golden."""
    from triton_distributed_tpu.models.continuous import ContinuousEngine

    p = [5, 9, 2, 4]
    gold = Engine(own_model, temperature=0.0).serve(
        np.asarray([p], np.int32), gen_len=4
    )[0, 4:]
    eng = ContinuousEngine(
        own_model, max_batch=2, page_size=16, max_length=64, temperature=0.9
    )
    server = ModelServer(eng).start()
    try:
        resp = request(
            server.host, server.port,
            {"requests": [p, p], "gen_lens": [4, 4],
             "temperatures": [0.0, None], "top_ks": 8},
        )
        np.testing.assert_array_equal(
            np.asarray(resp["outputs"][0], np.int32), gold
        )
        assert len(resp["outputs"][1]) == 4
        # Mismatched knob list lengths surface as server errors.
        import pytest

        with pytest.raises(RuntimeError, match="top_ps"):
            request(
                server.host, server.port,
                {"requests": [p], "gen_lens": [2], "top_ps": [0.9, 0.5]},
            )
    finally:
        server.shutdown()


def test_server_speculative_stats(own_model):
    """A server over a speculative ContinuousEngine serves the same
    tokens and reports the accept/rollback ledger in stats."""
    from triton_distributed_tpu.models.continuous import ContinuousEngine

    p = [5, 9, 2, 4, 5, 9, 2, 4]
    gold = Engine(own_model, temperature=0.0).serve(
        np.asarray([p], np.int32), gen_len=6
    )[0, 8:]
    eng = ContinuousEngine(
        own_model, max_batch=1, page_size=16, max_length=64, speculative=3
    )
    server = ModelServer(eng).start()
    try:
        resp = request(
            server.host, server.port,
            {"requests": [p], "gen_lens": [6]},
        )
        np.testing.assert_array_equal(
            np.asarray(resp["outputs"][0], np.int32), gold
        )
        assert resp["stats"]["spec_verify_steps"] >= 1
        assert "spec_accept_rate" in resp["stats"]
    finally:
        server.shutdown()


def test_server_unknown_payload_and_malformed_json(own_model):
    """Unknown payloads return a structured error naming the accepted
    shapes (was: a bare KeyError 'input_ids'); malformed JSON is
    reported AND the connection keeps serving; both bump the server
    error counter exposed via {"cmd": "stats"}."""
    import json
    import socket

    server = ModelServer(Engine(own_model, mode="xla")).start()
    try:
        with pytest.raises(RuntimeError, match="accepted payloads"):
            request(server.host, server.port, {"whatever": 1})
        with socket.create_connection(
            (server.host, server.port), timeout=10
        ) as s, s.makefile("rwb") as f:
            f.write(b"{not json}\n")
            f.flush()
            resp = json.loads(f.readline())
            assert resp["error"]["status"] == "bad_request"
            assert "malformed JSON" in resp["error"]["reason"]
            # The SAME connection still serves after the bad line.
            f.write(json.dumps({"cmd": "ping"}).encode() + b"\n")
            f.flush()
            assert json.loads(f.readline())["ok"]
        stats = request(server.host, server.port, {"cmd": "stats"})["stats"]
        assert stats["server"]["errors"] >= 2
    finally:
        server.shutdown()


def test_server_oversized_line_bounded(own_model):
    """A giant request line is refused at the byte bound (no OOM-sized
    buffering), the connection is dropped (framing is lost), and the
    server stays serviceable."""
    import json
    import socket

    server = ModelServer(Engine(own_model, mode="xla")).start()
    server.MAX_LINE_BYTES = 1024  # instance override for the test
    try:
        with socket.create_connection(
            (server.host, server.port), timeout=10
        ) as s, s.makefile("rwb") as f:
            f.write(b"x" * 4096 + b"\n")
            f.flush()
            resp = json.loads(f.readline())
            assert resp["error"]["status"] == "bad_request"
            assert "exceeds" in resp["error"]["reason"]
            assert f.readline() == b""  # server dropped the connection
        # A line far larger than any stream buffer: the server must
        # drain the unread tail before closing, or its close() turns
        # into an RST that destroys the error response client-side.
        with socket.create_connection(
            (server.host, server.port), timeout=10
        ) as s, s.makefile("rwb") as f:
            f.write(b"y" * (1 << 20) + b"\n")
            f.flush()
            resp = json.loads(f.readline())
            assert resp["error"]["status"] == "bad_request"
        assert request(server.host, server.port, {"cmd": "ping"})["ok"]
    finally:
        server.shutdown()


def test_server_client_disconnect_mid_request(own_model):
    """A client that sends a generation payload and hard-closes (RST)
    before reading must not kill the server: the failure is counted as
    a connection error and the engine/pool stay clean."""
    import json
    import socket
    import struct
    import time as _time

    from triton_distributed_tpu.models.continuous import ContinuousEngine

    eng = ContinuousEngine(own_model, max_batch=1, page_size=16, max_length=64)
    server = ModelServer(eng).start()
    try:
        s = socket.create_connection((server.host, server.port), timeout=10)
        s.sendall(json.dumps(
            {"requests": [[5, 9, 2, 4]], "gen_lens": [4]}
        ).encode() + b"\n")
        # SO_LINGER(0): close sends RST, so the server's response write
        # fails instead of landing in a dead buffer.
        s.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                     struct.pack("ii", 1, 0))
        s.close()
        deadline = _time.monotonic() + 60
        while _time.monotonic() < deadline:
            stats = request(
                server.host, server.port, {"cmd": "stats"}, timeout=10
            )["stats"]["server"]
            if stats["conn_errors"] >= 1:
                break
            _time.sleep(0.1)
        assert stats["conn_errors"] >= 1
        assert request(server.host, server.port, {"cmd": "ping"})["ok"]
        assert eng.audit() == []
    finally:
        server.shutdown()


def test_server_concurrent_requests_and_stats(own_model):
    """stats/ping payloads bypass the engine lock: they answer while a
    generation payload is in flight on another connection."""
    import threading

    from triton_distributed_tpu.models.continuous import ContinuousEngine

    eng = ContinuousEngine(own_model, max_batch=1, page_size=16, max_length=64)
    server = ModelServer(eng).start()
    try:
        done = {}

        def gen():
            done["resp"] = request(
                server.host, server.port,
                {"requests": [[5, 9, 2, 4]], "gen_lens": [8]},
            )

        t = threading.Thread(target=gen, daemon=True)
        t.start()
        probes = 0
        while t.is_alive():
            r = request(server.host, server.port, {"cmd": "stats"},
                        timeout=10)
            assert "server" in r["stats"]
            assert request(server.host, server.port, {"cmd": "ping"},
                           timeout=10)["ok"]
            probes += 1
        t.join(timeout=60)
        # The probes above answered while (and after) generation ran;
        # at least one stats round trip always completes.
        r = request(server.host, server.port, {"cmd": "stats"}, timeout=10)
        assert r["stats"]["server"]["requests"] >= 1
        assert done["resp"]["results"][0]["status"] == "ok"
    finally:
        server.shutdown()


def test_server_graceful_drain(own_model):
    """Shutdown while a generation is in flight: the in-flight payload
    finishes and its response arrives intact; a payload on an already-
    open connection is refused with `shutting_down`; fresh connections
    are refused once the listener closes."""
    import json
    import socket
    import threading
    import time as _time

    from triton_distributed_tpu.models.continuous import ContinuousEngine

    eng = ContinuousEngine(own_model, max_batch=1, page_size=16, max_length=64)
    server = ModelServer(eng).start()
    done = {}

    def gen():
        done["resp"] = request(
            server.host, server.port,
            {"requests": [[5, 9, 2, 4]], "gen_lens": [12]}, timeout=120,
        )

    t = threading.Thread(target=gen, daemon=True)
    t.start()
    # A second connection, accepted BEFORE the drain begins.
    held = socket.create_connection((server.host, server.port), timeout=10)
    _time.sleep(0.5)  # let the generation payload reach the engine
    assert request(server.host, server.port, {"cmd": "shutdown"})["ok"]
    # New generation work on the held connection is refused...
    with held, held.makefile("rwb") as f:
        f.write(json.dumps(
            {"requests": [[1, 2, 3, 4]], "gen_lens": [2]}
        ).encode() + b"\n")
        f.flush()
        resp = json.loads(f.readline())
        assert resp["error"]["status"] == "shutting_down"
    # ...while the in-flight generation drains to completion.
    t.join(timeout=120)
    assert done["resp"]["results"][0]["status"] == "ok"
    assert len(done["resp"]["outputs"][0]) == 12
    # The listener is (eventually) closed to fresh connections.
    deadline = _time.monotonic() + 10
    refused = False
    while _time.monotonic() < deadline and not refused:
        try:
            socket.create_connection(
                (server.host, server.port), timeout=1
            ).close()
            _time.sleep(0.1)
        except OSError:
            refused = True
    assert refused
    server.shutdown()
    assert eng.audit() == []


def test_server_scrape_while_draining(own_model):
    """metrics/events/ping verbs keep answering after shutdown has been
    requested but before the in-flight generation finishes (a drain is
    exactly when an operator wants to watch the tier). Post-shutdown a
    connection closes after one response, so each probe rides its own
    pre-opened connection."""
    import json
    import socket
    import threading
    import time as _time

    from triton_distributed_tpu.models.continuous import ContinuousEngine

    eng = ContinuousEngine(own_model, max_batch=1, page_size=16, max_length=64)
    server = ModelServer(eng).start()
    done = {}

    def gen():
        done["resp"] = request(
            server.host, server.port,
            {"requests": [[5, 9, 2, 4]], "gen_lens": [16]}, timeout=120,
        )

    def probe(conn, payload):
        with conn, conn.makefile("rwb") as f:
            f.write(json.dumps(payload).encode() + b"\n")
            f.flush()
            return json.loads(f.readline())

    t = threading.Thread(target=gen, daemon=True)
    t.start()
    # Pre-open the probe connections BEFORE the drain begins (the
    # listener closes to fresh connections shortly after shutdown).
    conns = [
        socket.create_connection((server.host, server.port), timeout=10)
        for _ in range(3)
    ]
    _time.sleep(0.3)  # let the generation payload reach the engine
    assert request(server.host, server.port, {"cmd": "shutdown"})["ok"]

    ping = probe(conns[0], {"cmd": "ping"})
    assert ping["ok"] and ping["draining"]
    m = probe(conns[1], {"cmd": "metrics"})
    assert "prometheus" in m and "tdt_" in m["prometheus"]
    ev = probe(conns[2], {"cmd": "events"})
    assert "events" in ev and "next_since" in ev

    # The drained generation still finishes intact.
    t.join(timeout=120)
    assert done["resp"]["results"][0]["status"] == "ok"
    assert len(done["resp"]["outputs"][0]) == 16
    server.shutdown()
    assert eng.audit() == []


def test_client_honors_server_backoff_hint(own_model):
    """The overloaded shed reply carries ``retry_after_s``; the client
    retry loop sleeps THAT instead of its local exponential backoff —
    a local backoff_s large enough to fail the test proves the hint
    was used."""
    import json
    import socket
    import threading
    import time as _time

    hint = 0.05
    seen = []
    lsock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    lsock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    lsock.bind(("127.0.0.1", 0))
    lsock.listen(4)
    lsock.settimeout(10)  # the fake server fails where it waits
    host, port = lsock.getsockname()

    def fake_server():
        # First payload: overloaded + hint; second: success.
        for i in range(2):
            conn, _ = lsock.accept()
            with conn, conn.makefile("rwb") as f:
                f.readline()
                seen.append(_time.monotonic())
                resp = (
                    {"error": {"status": "overloaded", "reason": "full",
                               "retry_after_s": hint}}
                    if i == 0 else {"ok": True}
                )
                f.write(json.dumps(resp).encode() + b"\n")
                f.flush()

    t = threading.Thread(target=fake_server, daemon=True)
    t.start()
    try:
        t0 = _time.monotonic()
        resp = request(host, port, {"cmd": "ping"}, timeout=10,
                       retries=2, backoff_s=30.0)
        wall = _time.monotonic() - t0
        assert resp["ok"]
        assert len(seen) == 2
        # Retried after ~hint seconds, nowhere near the 30 s local
        # backoff; >= proves it actually slept the hint.
        assert hint <= (seen[1] - seen[0]) < 5.0
        assert wall < 10.0
    finally:
        lsock.close()
        t.join(timeout=10)

    # A real server's shed reply carries the hint on the wire.
    from triton_distributed_tpu.models.continuous import ContinuousEngine

    eng = ContinuousEngine(own_model, max_batch=1, page_size=16, max_length=64)
    server = ModelServer(eng, max_pending=0).start()
    try:
        with pytest.raises(RuntimeError, match="server error") as ei:
            request(server.host, server.port,
                    {"requests": [[1, 2, 3, 4]], "gen_lens": [2]})
        assert "retry_after_s" in str(ei.value)
    finally:
        server.shutdown()


def test_engine_serve_profile_hook(own_model, tmp_path):
    """Engine.serve(profile=...) must capture a decode-loop trace
    (parity: the reference Engine's built-in profiled decode,
    ``models/engine.py:151-177``) — files on disk, output unchanged."""
    prompt = np.arange(8, dtype=np.int32)[None]
    eng = Engine(own_model, temperature=0.0, mode="xla")
    gold = eng.serve(prompt, gen_len=4)
    prof_dir = str(tmp_path / "decode_trace")
    out = eng.serve(prompt, gen_len=4, profile=prof_dir)
    np.testing.assert_array_equal(out, gold)
    import os as _os

    captured = [
        _os.path.join(r, f)
        for r, _d, fs in _os.walk(prof_dir) for f in fs
    ]
    assert captured, f"no trace files under {prof_dir}"
