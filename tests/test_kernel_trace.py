"""Device task tracer (ISSUE 8): in-kernel timeline for the megakernel.

Coverage contract (ISSUE 8 acceptance):
- tracer OFF → untraced builds keep the PR 7 return arity and produce
  bit-identical outputs to traced builds' primary outputs;
- tracer ON → decoded ring is gap-free and dependency-order consistent
  with the scheduler (begin[consumer] >= end[producer] for every
  scoreboard edge) under interpret at tp=1 and tp=4;
- engine wiring: ContinuousEngine(kernel_trace=True) outputs match the
  untraced engine bit-exactly, launches land in metrics + the
  {"cmd": "kernel_trace"} verb, and request trace ids flow through
  admit events → mega:launch events → ring launch metadata;
- the merged chrome timeline carries host spans AND device task rows
  for the same trace id.
"""

import gzip
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from triton_distributed_tpu.megakernel import MegaQwen3, TaskType
from triton_distributed_tpu.megakernel.code_generator import MegaConfig
from triton_distributed_tpu.models import AutoLLM
from triton_distributed_tpu.obs import kernel_trace as kt


@pytest.fixture
def ctx1():
    from triton_distributed_tpu.runtime import mesh as mesh_mod

    ctx = mesh_mod.initialize_distributed(tp=1, devices=jax.devices()[:1])
    yield ctx
    mesh_mod.finalize_distributed()


def _warm_cache(model, B=2, s_max=64, warm=((3, 5),)):
    cache = model.new_cache(B, max_length=s_max)
    step = model.decode_fn("xla")
    for toks in warm:
        _, cache = step(
            model.params, jnp.asarray(list(toks)[:B], jnp.int32), cache
        )
    return cache


class TestRingTp1:
    def test_multi_trace_bit_identity_and_ring(self, own_model):
        """tp=1, NS=3: traced launch's tokens/logits/cache match the
        untraced build bit-exactly; the ring decodes gap-free, clock-
        monotonic, and dependency-consistent with the scheduled order;
        the untraced build keeps the PR 7 3-tuple contract."""
        B, NS = 2, 3
        cache = _warm_cache(own_model, B)
        mega = MegaQwen3(own_model)
        s_max = int(cache.k.shape[3])
        tok0 = jnp.asarray([19, 23], jnp.int32)

        f0 = mega.decode_multi_fn(B, s_max, NS)
        out0 = f0(own_model.params, tok0, jax.tree.map(jnp.copy, cache))
        assert len(out0) == 3  # PR 7 contract untouched with trace off
        # Untraced LAUNCH PARAMS bit-identical to the pre-tracer
        # layout: the task table's id column stays zero with trace
        # off (a tracer-only operand extension).
        from triton_distributed_tpu.megakernel.task import pack_table

        order0 = mega.multi_task_order(B, s_max, NS)
        tab_off = pack_table(order0)
        assert (tab_off[:, 4:] == 0).all()
        tab_on = pack_table(order0, trace=True)
        assert tab_on[:, 4].tolist() == [t.task_id for t in order0]
        np.testing.assert_array_equal(tab_off[:, :4], tab_on[:, :4])

        f1 = mega.decode_multi_fn(B, s_max, NS, trace=True)
        t1, l1, c1, ring = f1(
            own_model.params, tok0, jax.tree.map(jnp.copy, cache)
        )
        t0_, l0, c0 = out0
        np.testing.assert_array_equal(np.asarray(t0_), np.asarray(t1))
        np.testing.assert_array_equal(np.asarray(l0), np.asarray(l1))
        np.testing.assert_array_equal(np.asarray(c0.k), np.asarray(c1.k))
        np.testing.assert_array_equal(
            np.asarray(c0.kv_len), np.asarray(c1.kv_len)
        )

        ring = np.asarray(ring)
        order = mega.multi_task_order(B, s_max, NS, trace=True)
        assert ring.shape == (1, NS, len(order), 8)
        records = kt.decode_trace(ring)  # strict: raises on any gap
        assert len(records) == NS * len(order)
        problems = kt.validate_ring(records, order)
        assert problems == []
        # task_id stamping survives the schedule: ids in the ring are
        # exactly the builder's ids, not positions.
        assert ({r.task_id for r in records}
                == {t.task_id for t in order})
        # The fused single-rank exchange stamps its comm phase.
        ar = [r for r in records
              if r.opcode == int(TaskType.ALLREDUCE)]
        assert ar and all(r.begin <= r.mid <= r.end for r in ar)

    def test_single_step_trace_build(self, own_model):
        """``build(trace=True)``: the single-step path returns
        (logits, cache, ring [tp, 1, T, 8]) and the ring decodes
        cleanly; trace=False keeps the 2-tuple step."""
        cache = _warm_cache(own_model, B=1)
        mega = MegaQwen3(own_model)
        tok = jnp.asarray([7], jnp.int32)
        compiled, step, _ = mega.build(1, 64, trace=True)
        logits, c2, ring = step(
            own_model.params, tok, jax.tree.map(jnp.copy, cache)
        )
        ring = np.asarray(ring)
        assert ring.shape == (1, 1, compiled.num_tasks, 8)
        records = kt.decode_trace(ring)
        assert kt.validate_ring(records, compiled.order) == []
        # Untraced contract unchanged.
        _, step0, _ = mega.build(1, 64)
        out = step0(own_model.params, tok, cache)
        assert len(out) == 2
        np.testing.assert_array_equal(
            np.asarray(out[0]), np.asarray(logits)
        )


class TestRingTp4:
    def test_overlap_ar_ring_and_exposure(self, ctx4):
        """tp=4 serving config (fuse_norms+cross_prefetch+overlap_ar):
        every rank's ring is gap-free and dependency-consistent, every
        AR_SEND/AR_WAIT pair stamps its phase marks, and the measured
        overlap report opens one window per exchange with nonzero
        hidden time (the tile-0 prefetch the wait fires before
        blocking)."""
        model = AutoLLM.from_pretrained("tiny", ctx=ctx4)
        B, NS = 1, 2
        cache = _warm_cache(model, B, warm=((3,), (5,)))
        mega = MegaQwen3(model, cfg=MegaConfig(
            fuse_norms=True, cross_prefetch=True, overlap_ar=True
        ))
        s_max = int(cache.k.shape[3])
        fn = mega.decode_multi_fn(B, s_max, NS, trace=True)
        _toks, _lg, _c, ring = fn(
            model.params, jnp.asarray([19], jnp.int32), cache
        )
        ring = np.asarray(ring)
        assert ring.shape[0] == 4  # one ring per rank
        order = mega.multi_task_order(B, s_max, NS, trace=True)
        records = kt.decode_trace(ring)
        assert kt.validate_ring(records, order) == []
        L = model.cfg.num_layers
        sends = [r for r in records if r.opcode == int(TaskType.AR_SEND)]
        waits = [r for r in records if r.opcode == int(TaskType.AR_WAIT)]
        # 2 exchanges per layer × NS steps × 4 ranks.
        assert len(sends) == len(waits) == 2 * L * NS * 4
        assert all(r.begin <= r.mid <= r.end for r in sends + waits)
        rep = kt.overlap_report(records)
        assert rep["windows"] == 2 * L * NS * 4
        # The wait's pre-block phase (tile-0 fire) is measured hidden
        # time inside every window.
        assert rep["hidden_ticks"] > 0
        assert rep["comm_ticks"] >= rep["exposed_ticks"]
        assert 0.0 < rep["hidden_fraction"] <= 1.0
        # The vectorized inline path (what the serving loop pays per
        # launch) must agree exactly with the record-wise reference.
        assert kt._overlap_report_array(ring) == rep


class TestDecoderPure:
    """Host-side decoder invariants on synthetic rings (no kernels)."""

    @staticmethod
    def _row(task_id, opcode, begin, end, mid=0, layer=0, slot=0, flag=1):
        return [task_id, opcode, layer, slot, begin, end, mid, flag]

    def test_gap_raises_strict_and_skips_unstrict(self):
        ring = np.asarray([[[
            self._row(0, 0, 1, 2),
            self._row(1, 1, 3, 4, flag=0),  # unwritten
        ]]], np.int32)
        with pytest.raises(kt.TraceError, match="gaps"):
            kt.decode_trace(ring)
        recs = kt.decode_trace(ring, strict=False)
        assert [r.task_id for r in recs] == [0]

    def test_validate_flags_order_violations(self):
        from triton_distributed_tpu.megakernel.task import (
            Task,
            TaskDependency,
        )

        order = [
            Task(task_id=0, task_type=TaskType.EMBED),
            Task(task_id=1, task_type=TaskType.NORM,
                 deps=(TaskDependency(0),)),
        ]
        # Consumer begins BEFORE its producer ended.
        ring = np.asarray([[[
            self._row(0, int(TaskType.EMBED), 5, 8),
            self._row(1, int(TaskType.NORM), 9, 12),
        ]]], np.int32)
        good = kt.decode_trace(ring)
        assert kt.validate_ring(good, order) == []
        bad_ring = np.asarray([[[
            self._row(0, int(TaskType.EMBED), 5, 8),
            self._row(1, int(TaskType.NORM), 7, 12),
        ]]], np.int32)
        bad = kt.decode_trace(bad_ring)
        probs = kt.validate_ring(bad, order)
        assert probs and any("before" in p for p in probs)
        # Degenerate interval.
        deg = kt.decode_trace(np.asarray(
            [[[self._row(0, 0, 5, 5)]]], np.int32
        ))
        assert any(">=" in p for p in kt.validate_ring(deg))

    def test_overlap_report_exact_on_synthetic_pair(self):
        # AR_SEND [10, 12] (puts in flight at 11), two compute tasks,
        # AR_WAIT [20, 26] (tile-0 fired at 22 → blocked [22, 26]).
        ring = np.asarray([[[
            self._row(0, int(TaskType.AR_SEND), 10, 12, mid=11),
            self._row(1, int(TaskType.QKV_PROJ), 13, 17),
            self._row(2, int(TaskType.ATTN), 17, 20),
            self._row(3, int(TaskType.AR_WAIT), 20, 26, mid=22),
        ]]], np.int32)
        rep = kt.overlap_report(kt.decode_trace(ring))
        assert rep["windows"] == 1
        assert rep["comm_ticks"] == 26 - 11
        # hidden = wait pre-block (2) + qkv (4) + attn (3) = 9
        assert rep["hidden_ticks"] == 9
        assert rep["exposed_ticks"] == 26 - 22
        assert rep["hidden_fraction"] == pytest.approx(9 / 15)

    def test_merge_with_host_profile_one_file(self, tmp_path):
        """Host spans + device task rows land in ONE merged gzip, the
        device rows inside the rank's pid namespace and tagged with the
        launch's request trace ids."""
        from triton_distributed_tpu.runtime.profiling import _PID_STRIDE

        root = tmp_path / "prof" / "run" / "rank0"
        sess = root / "plugins" / "profile" / "s1"
        sess.mkdir(parents=True)
        host = {"traceEvents": [
            {"ph": "M", "name": "process_name", "pid": 1,
             "args": {"name": "host"}},
            {"ph": "X", "name": "prefix_cache:admit", "pid": 1,
             "tid": 1, "ts": 0, "dur": 5,
             "args": {"trace_id": "req-42"}},
        ]}
        with gzip.open(str(sess / "h.trace.json.gz"), "wt") as f:
            json.dump(host, f)
        records = kt.decode_trace(np.asarray([[[
            self._row(0, int(TaskType.EMBED), 1, 2),
            self._row(1, int(TaskType.LM_HEAD), 3, 4),
        ]]], np.int32))
        launch = kt.KernelTraceLaunch(
            records=records, wall_s=0.5, t0=1.0,
            trace_ids={0: "req-42"}, nsteps=1, launch=1,
        )
        out = kt.merge_with_host_profile(
            "run", str(tmp_path / "prof"), [launch]
        )
        with gzip.open(out, "rt") as f:
            merged = json.load(f)
        evs = merged["traceEvents"]
        host_rows = [e for e in evs
                     if e.get("name") == "prefix_cache:admit"]
        dev_rows = [e for e in evs if e.get("name") in ("EMBED", "LM_HEAD")]
        assert len(host_rows) == 1 and len(dev_rows) == 2
        # Device rows live inside rank 0's namespace at the device pid.
        assert {e["pid"] for e in dev_rows} == {kt.DEVICE_TASK_PID}
        assert all(e["pid"] < _PID_STRIDE for e in dev_rows)
        # The SAME trace id on the host span and the device rows.
        assert host_rows[0]["args"]["trace_id"] == "req-42"
        assert all("req-42" in e["args"]["trace_ids"] for e in dev_rows)
        names = {e["args"]["name"] for e in evs
                 if e.get("ph") == "M"
                 and e.get("name") == "process_name"}
        assert "rank0: device tasks" in names
        # No host traces on disk → a device-only timeline still lands.
        out2 = kt.merge_with_host_profile(
            "empty", str(tmp_path / "prof"), [launch]
        )
        with gzip.open(out2, "rt") as f:
            only_dev = json.load(f)
        assert all(e.get("name") != "prefix_cache:admit"
                   for e in only_dev["traceEvents"])

    def test_summary_and_observe_launch(self, fresh_telemetry):
        from triton_distributed_tpu.obs import metrics as obs_metrics

        records = kt.decode_trace(np.asarray([[[
            self._row(0, int(TaskType.EMBED), 1, 3),
            self._row(1, int(TaskType.LM_HEAD), 3, 9),
        ]]], np.int32))
        launch = kt.KernelTraceLaunch(
            records=records, wall_s=0.8, t0=0.0,
            trace_ids={0: "a", 1: "b"}, nsteps=1, launch=7,
        )
        s = launch.summary()
        assert s["ticks_by_opcode"] == {"EMBED": 2, "LM_HEAD": 6}
        assert s["trace_ids"] == {0: "a", 1: "b"}
        kt.observe_launch(launch)
        reg = obs_metrics.default_registry()
        hist = reg.get("tdt_mega_task_seconds")
        assert hist.count(opcode="LM_HEAD") == 1
        # ticks scale to the measured wall: 6/8 of 0.8 s.
        assert hist.quantile(0.5, opcode="LM_HEAD") == pytest.approx(
            0.6, rel=0.5
        )


class TestEngineAndServer:
    def test_continuous_engine_trace_and_verbs(self, own_model,
                                               fresh_telemetry):
        """ONE engine compile covers the serving acceptance: traced
        engine output == untraced engine output bit-exactly; launches
        decoded into metrics/summary with request trace ids; the
        kernel_trace and kind-filtered events verbs answer through the
        wire; trace_ids payload key tags requests end to end."""
        from triton_distributed_tpu.models.continuous import (
            ContinuousEngine,
        )
        from triton_distributed_tpu.obs import events as obs_events
        from triton_distributed_tpu.obs import metrics as obs_metrics
        from triton_distributed_tpu.serving.server import (
            ModelServer,
            request,
        )

        reqs = [(list(range(1, 9)), 6), (list(range(3, 15)), 5)]
        e0 = ContinuousEngine(
            own_model, max_batch=2, max_length=64, page_size=16, mode="mega",
        )
        out0 = e0.run(reqs, results=True)
        e1 = ContinuousEngine(
            own_model, max_batch=2, max_length=64, page_size=16, mode="mega",
            kernel_trace=True,
        )
        out1 = e1.run(reqs, results=True)
        for a, b in zip(out0, out1):
            assert a.status == b.status == "ok"
            np.testing.assert_array_equal(a.tokens, b.tokens)

        # Launch ledger + registry.
        assert e1.stats["mega_trace_launches"] >= 1
        assert (e1.stats["mega_trace_launches"]
                == e1.stats["mega_launches"])
        summary = e1.kernel_trace_summary()
        assert summary["enabled"] and summary["launches"] >= 1
        last = summary["recent"][-1]
        assert last["records"] > 0 and last["ticks_by_opcode"]
        # Trace ids attached to the launch metadata…
        assert last["trace_ids"]
        reg = obs_metrics.default_registry()
        assert reg.get("tdt_mega_task_seconds").count(
            opcode="ATTN"
        ) > 0
        # …and on admit + mega:launch events (server→device thread).
        evts, _ = obs_events.default_ring().tail(0, kind="admit")
        admit_ids = {e.fields.get("trace_id") for e in evts}
        launch_evts, _ = obs_events.default_ring().tail(
            0, kind="mega:launch"
        )
        assert launch_evts
        launched_ids = set()
        for e in launch_evts:
            launched_ids.update(
                x for x in e.fields.get("trace_ids", "").split(",") if x
            )
        assert launched_ids and launched_ids <= admit_ids

        # Wire: kernel_trace verb, kind-filtered events, trace_ids key.
        server = ModelServer(e1).start()
        try:
            r = request(server.host, server.port, {"cmd": "kernel_trace"})
            assert r["kernel_trace"]["enabled"]
            assert r["kernel_trace"]["launches"] >= 1
            r2 = request(server.host, server.port, {
                "requests": [list(range(1, 9))], "gen_lens": [9],
                "trace_ids": ["wire-req-1"],
            })
            assert [x["status"] for x in r2["results"]] == ["ok"]
            ev = request(server.host, server.port,
                         {"cmd": "events", "kind": "admit"})
            assert ev["events"]
            assert all(e["kind"] == "admit" for e in ev["events"])
            assert any(
                e["fields"].get("trace_id") == "wire-req-1"
                for e in ev["events"]
            )
            # kind with no matches: cursor still advances (progress).
            none = request(server.host, server.port,
                           {"cmd": "events", "kind": "no_such_kind"})
            assert none["events"] == []
            assert none["next_since"] >= ev["next_since"] - 1
            with pytest.raises(RuntimeError, match="kind must be a"):
                request(server.host, server.port,
                        {"cmd": "events", "kind": 7})
            st = request(server.host, server.port, {"cmd": "stats"})
            assert st["stats"]["server"]["engine"]["kernel_trace"] is True
        finally:
            request(server.host, server.port, {"cmd": "shutdown"})
            server.shutdown()

    def test_fixed_batch_engine_trace(self, own_model, fresh_telemetry):
        """``Engine(mode="mega", kernel_trace=True)``: the serve()
        multi-step launches record rings too — deterministic across
        serves, launches decoded into the summary/metrics. (Traced-vs-
        untraced bit-identity is pinned at kernel level in TestRingTp1
        and at engine level for ContinuousEngine above — a second mega
        Engine build here would only re-prove it at tier-1 wall cost.)"""
        from triton_distributed_tpu.models.engine import Engine
        from triton_distributed_tpu.obs import metrics as obs_metrics

        ids = [list(range(1, 9))]
        e1 = Engine(own_model, mode="mega", kernel_trace=True)
        out1 = e1.serve(ids, 9, max_length=64)
        out2 = e1.serve(ids, 9, max_length=64)
        np.testing.assert_array_equal(out1, out2)
        assert e1.last_stats["mega_trace_launches"] >= 2
        s = e1.kernel_trace_summary()
        assert s["enabled"] and s["launches"] >= 1
        assert s["recent"][-1]["ticks_by_opcode"]
        assert kt.validate_ring(
            e1.kernel_trace_launches()[-1].get_records()
        ) == []
        reg = obs_metrics.default_registry()
        assert reg.get("tdt_mega_task_seconds").count(opcode="ATTN") > 0

    def test_sync_tables_never_aliases_host_arrays(self, own_model):
        """Regression (found by the tracer's wider dispatch→fetch
        window): ``jnp.asarray`` on CPU may zero-copy an aligned numpy
        array, so the engine's device page_table/kv_len could ALIAS
        the live host arrays it keeps mutating — an async launch then
        raced host bookkeeping (run-to-run token flips). _sync_tables
        must hand the device its own storage: later in-place host
        mutations may never show through."""
        from triton_distributed_tpu.models.continuous import (
            ContinuousEngine,
        )

        eng = ContinuousEngine(
            own_model, max_batch=2, max_length=64, page_size=16, mode="mega",
        )
        eng._kv_len[:] = 0
        eng._table[:] = 0
        eng._sync_tables()
        before_kv = np.asarray(eng.cache.kv_len).copy()
        before_tab = np.asarray(eng.cache.page_table).copy()
        eng._kv_len += 7            # in-place host mutations...
        eng._table[:, 0] = 3
        np.testing.assert_array_equal(          # ...never reach the
            np.asarray(eng.cache.kv_len), before_kv)     # device copy
        np.testing.assert_array_equal(
            np.asarray(eng.cache.page_table), before_tab)

    def test_kernel_trace_requires_mega(self, own_model):
        from triton_distributed_tpu.models.continuous import (
            ContinuousEngine,
        )
        from triton_distributed_tpu.models.engine import Engine

        with pytest.raises(ValueError, match="mode='mega'"):
            ContinuousEngine(own_model, mode="xla", kernel_trace=True)
        with pytest.raises(ValueError, match="mode='mega'"):
            Engine(own_model, mode="xla", kernel_trace=True)

    def test_kernel_trace_verb_refused_without_tracer(self, ctx1):
        """A server over an engine with no tracer surface answers the
        verb with a structured bad_request, not an internal error."""
        from triton_distributed_tpu.serving.server import ModelServer

        class NoTracer:
            last_stats = {}

        server = ModelServer(NoTracer())
        try:
            resp = server._dispatch_inner({"cmd": "kernel_trace"})
        finally:
            server._sock.close()
        assert resp["error"]["status"] == "bad_request"
        assert "tracer" in resp["error"]["reason"]


class TestGemmArRing:
    def test_trace_plumb_shapes_and_refusal(self, ctx4):
        """The standalone gemm_ar ONE_SHOT kernel carries the same
        ring format (abstract-eval only: the barrier-semaphore path
        cannot execute under this container's interpret — the ring is
        a hardware-path feature; its decoder is shared and tested on
        megakernel rings above)."""
        from triton_distributed_tpu.ops.overlap.gemm_ar import (
            GemmARConfig,
            GemmARMethod,
            gemm_ar_op,
        )

        a = jnp.zeros((16, 256), jnp.float32)
        b = jnp.zeros((256, 256), jnp.float32)
        sh = jax.eval_shape(
            lambda a_, b_: gemm_ar_op(
                a_, b_, "tp", GemmARMethod.ONE_SHOT,
                GemmARConfig(tile_n=128), ctx4, trace=True,
            ),
            a, b,
        )
        assert sh[0].shape == (16, 256)
        # [ranks, num_j + 1, phases, TRACE_INTS]
        assert sh[1].shape == (4, 3, 3, 8) and sh[1].dtype == jnp.int32
        with pytest.raises(ValueError, match="ONE_SHOT"):
            gemm_ar_op(a, b, "tp", GemmARMethod.AUTO, None, ctx4,
                       trace=True)

    def test_single_rank_trace_keeps_arity(self, ctx1):
        """n_ranks == 1 (nothing to overlap, no fused kernel): the
        traced call still returns (out, ring) — an all-unwritten ring
        that strict=False decodes to [] — instead of crashing the
        caller's unpack."""
        from triton_distributed_tpu.ops.overlap.gemm_ar import (
            GemmARConfig,
            GemmARMethod,
            gemm_ar_op,
        )

        a = jnp.ones((16, 128), jnp.float32)
        b = jnp.ones((128, 256), jnp.float32)
        out, ring = gemm_ar_op(
            a, b, "tp", GemmARMethod.ONE_SHOT,
            GemmARConfig(tile_n=128), ctx1, trace=True,
        )
        np.testing.assert_allclose(np.asarray(out), np.asarray(a @ b))
        assert kt.decode_trace(np.asarray(ring), strict=False) == []
