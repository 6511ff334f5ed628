"""The spans of the served request's hot path and the names of its
programs (docs/observability.md "Spans of the served request").

One served session at `tiny` size behind a ``Router`` of one replica,
over the socket, under ``jax.profiler.start_trace``: the CPU backend
has the host plane and TraceAnnotations, so presence, nesting, thread
and args are checked here; times come only from a chip run. The trace
is read the way the benchmark reads it, ``benchmark.xplane.from_xplane``
(names, starts, durations), and through ``ProfileData`` itself for what
that form drops: an event's thread and its typed stats.
"""

import ast
import glob
import os
import time

import jax
import numpy as np
import pytest

from triton_distributed_tpu.models import AutoLLM
from triton_distributed_tpu.obs import metrics as obs_metrics
from triton_distributed_tpu.obs.timeline import Timeline, observe_request
from triton_distributed_tpu.runtime import mesh as mesh_mod

PACKAGE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "triton_distributed_tpu")

# Span -> its parent by nesting (None: top of its thread).
SPANS = {
    "entry:payload": None,
    "scheduler:wait_for_work": None,
    "scheduler:batch": None,
    "engine:admit": "scheduler:batch",
    "engine:decode_round": "scheduler:batch",
    "engine:dispatch": "engine:decode_round",
    "engine:serial_launch": "engine:dispatch",
    "engine:fetch": "engine:decode_round",
    "engine:sample_emit": "engine:decode_round",
    "engine:audit": "scheduler:batch",
    "prefix_cache:admit": "engine:admit",
    "prefix_cache:chunk": "prefix_cache:admit",
}
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Session:
    """What one traced session left behind."""

    def __init__(self):
        self.compiled: list = []   # fun_name of every program compiled
        self.requests: list = []   # the engine Requests the replica ran
        self.form: dict = {}       # benchmark.xplane's neutral form
        self.events: list = []     # (line index, name, start, dur, stats)

    def named(self, name):
        return sorted((s, d) for _, n, s, d in self.form["host"] if n == name)


@pytest.fixture(scope="module")
def session(tmp_path_factory):
    import jax.monitoring

    from benchmark import xplane
    from triton_distributed_tpu.models.continuous import ContinuousEngine
    from triton_distributed_tpu.serving.router import Router
    from triton_distributed_tpu.serving.server import ModelServer, request

    out = Session()
    recording = [True]

    def on_compile(event, duration, **kw):
        if recording[0] and event == COMPILE_EVENT:
            # "jit(tdt_decode_step)"
            out.compiled.append(str(kw.get("fun_name"))
                                .removeprefix("jit(").removesuffix(")"))

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    # ``PARENT["programs"]`` below is a COUNT of compile events, and a
    # compile event fires only on a cache miss: ``tdt_finite_greedy`` is
    # a module-level jit, so where an earlier file on this xdist worker
    # (``--dist loadfile`` deals files by the whole run's test count)
    # compiled it at this shape, it is missing from the count and the
    # test fails for a reason no served program has. Start cold.
    jax.clear_caches()
    ctx = mesh_mod.initialize_distributed(tp=1, devices=jax.devices()[:1])
    model = AutoLLM.from_pretrained("tiny", ctx=ctx)
    # Chunked prefill (16 tokens a chunk), so that the second request's
    # admission steps the first one's decoding between its chunks.
    eng = ContinuousEngine(model, max_batch=2, page_size=16, max_length=128,
                           prefix_cache=True, prefill_chunk=16)
    run = eng.run

    def recording_run(reqs, **kw):
        out.requests.extend(reqs)
        return run(reqs, **kw)

    eng.run = recording_run
    router = Router([eng])
    server = ModelServer(router).start()
    rng = np.random.default_rng(3)
    payload = {"requests": [rng.integers(0, 256, size=n).tolist()
                            for n in (8, 40)], "gen_lens": [12, 6]}
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    try:
        request(server.host, server.port, payload)  # compiles everything
        recording[0] = False
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            time.sleep(0.25)  # the worker waits on an empty queue
            payload["requests"] = [rng.integers(0, 256, size=n).tolist()
                                   for n in (8, 40)]
            out.requests.clear()
            request(server.host, server.port, payload)
        finally:
            jax.profiler.stop_trace()
    finally:
        server.shutdown()
        router.shutdown()
        mesh_mod.finalize_distributed()
    path = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    out.form = xplane.from_xplane(path, chips=1)
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name == xplane.HOST_PLANE:
            for i, line in enumerate(plane.lines):
                out.events += [(i, e.name, e.start_ns, e.duration_ns,
                                dict(e.stats)) for e in line.events
                               if e.name in SPANS]
    return out


def inside(child, parent) -> bool:
    return (parent[0] <= child[0]
            and child[0] + child[1] <= parent[0] + parent[1])


@pytest.mark.parametrize("name", sorted(SPANS))
def test_span_is_in_the_trace_and_nests_under_its_parent(session, name):
    found = session.named(name)
    assert found, f"no {name} span among the trace's host events"
    parent = SPANS[name]
    if parent is None:
        return
    parents = session.named(parent)
    orphans = [s for s in found if not any(inside(s, p) for p in parents)]
    assert not orphans, f"{name} outside every {parent}: {orphans[:3]}"


def test_chunked_prefill_steps_the_running_batch_inside_the_admission(session):
    admits = session.named("engine:admit")
    rounds = session.named("engine:decode_round")
    assert len(admits) == 2
    nested = [r for r in rounds if any(inside(r, a) for a in admits)]
    # 40 prompt tokens in chunks of 16: two gaps between three chunks.
    assert len(nested) == 2
    assert len(rounds) > len(nested)  # the rest lie in the batch alone
    assert len(session.named("prefix_cache:chunk")) == 1 + 3


def test_scheduler_and_engine_spans_come_from_the_one_worker_thread(session):
    lines = {}
    for line, name, *_ in session.events:
        lines.setdefault(name.split(":")[0], set()).add(line)
    assert len(lines["scheduler"] | lines["engine"]) == 1
    # The connection's thread is another.
    assert not lines["entry"] & lines["engine"]


def test_request_scoped_spans_carry_the_trace_id_typed(session):
    admits = [st for _, name, _, _, st in session.events
              if name == "engine:admit"]
    ids = sorted(st["trace_id"] for st in admits)
    assert ids == sorted(r.trace_id for r in session.requests)
    assert all(i.startswith("req-") for i in ids)
    # Ints stay ints in the profiler's stats: no ladder, no strings.
    st = admits[0]
    assert {k: type(st[k]) for k in ("slot", "prompt", "queue_wait_ms")} == {
        "slot": int, "prompt": int, "queue_wait_ms": int}
    batch = [st for _, name, _, _, st in session.events
             if name == "scheduler:batch"]
    assert [st["n"] for st in batch] == [2]
    entry = [st for _, name, _, _, st in session.events
             if name == "entry:payload"]
    assert entry == [{"requests": 2, "shed": 0}]


def test_batch_wait_is_within_queue_wait_for_every_request(session):
    assert len(session.requests) == 2
    for r in session.requests:
        tl = r.timeline
        assert tl.enqueue_t <= tl.batch_start_t <= tl.admit_t
        assert 0.0 <= tl.batch_wait_s <= tl.queue_wait_s


def test_batch_start_latches_once_and_feeds_its_histogram(fresh_telemetry):
    tl = Timeline()
    tl.stamp_enqueue()
    assert tl.batch_wait_s is None  # a direct caller has no batch
    tl.stamp_batch_start()
    first = tl.batch_start_t
    time.sleep(0.002)
    tl.stamp_batch_start()
    assert tl.batch_start_t == first
    tl.stamp_admit()
    tl.finish("ok")
    observe_request(tl)
    snap = obs_metrics.default_registry().snapshot()
    series = snap["tdt_request_batch_wait_seconds"]["series"]
    assert [s["count"] for s in series] == [1]
    assert series[0]["sum"] <= (
        snap["tdt_request_queue_wait_seconds"]["series"][0]["sum"])


def test_every_program_of_the_served_payload_has_a_name_of_its_own(session):
    ours = [n for n in session.compiled if n.startswith("tdt_")]
    assert "<lambda>" not in session.compiled
    # The readers select the step and the chunks by these words.
    assert {n for n in session.compiled if "decode" in n} == {
        "tdt_decode_step"}
    assert {n for n in session.compiled if "prefill" in n} == {
        "tdt_prefill_chunk"}
    assert {"tdt_decode_step", "tdt_prefill_chunk"} <= set(ours)


# What the parent commit of PR 32 compiled and traced for this payload
# (read from a run of this fixture on its checkout). A PR that changes a
# program or the round's host order on purpose rewrites these lists; one
# that only deletes code no served configuration reaches leaves them.
ROUND = ["engine:decode_round", "engine:dispatch", "engine:fetch",
         "engine:sample_emit"]
# A round that found no step parked launches its own (since PR 39 under
# a span of its own; the order of everything else is the parent's).
SERIAL = ROUND[:2] + ["engine:serial_launch"] + ROUND[2:]
ADMIT = ["engine:admit", "prefix_cache:admit", "prefix_cache:chunk"]
PARENT = {
    # name -> programs compiled under it (one a signature).
    # (Since PR 35: `tdt_kv_copy_page`, because a prefix-cache engine
    # compiles its copy-on-write program when it is built, not at the
    # first partial-page hit in the middle of serving; and ONE
    # `tdt_decode_step` for three, because the host's table, lengths and
    # tokens are committed to the mesh like a step's own outputs, so the
    # step has one signature wherever its inputs come from.)
    "programs": {"tdt_set_params": 1, "tdt_prefill_chunk": 3,
                 "tdt_decode_step": 1, "tdt_finite_greedy": 1,
                 "tdt_kv_copy_page": 1},
    # The one traced batch, in start order: both admissions (the second
    # prompt's three chunks step the first request between them), then
    # the rounds the longer generation needs, then the audit. Serial:
    # the two rounds between chunks (an admission is mid-prefill) and
    # the first after it; the other eight were looked ahead to.
    "spans": (["scheduler:batch"] + ADMIT + ADMIT + SERIAL
              + ["prefix_cache:chunk"] + SERIAL + ["prefix_cache:chunk"]
              + SERIAL + ROUND * 8 + ["engine:audit"]),
}


@pytest.mark.parametrize("what", sorted(PARENT))
def test_served_payload_runs_the_parents_programs_in_the_parents_order(
        session, what):
    if what == "programs":
        got = {}
        for name in session.compiled:
            if name.startswith("tdt_"):
                got[name] = got.get(name, 0) + 1
    else:
        (batch,) = session.named("scheduler:batch")
        got = [n for _, _, n in sorted(
            (s, -d, n) for _, n, s, d in session.form["host"]
            if n in SPANS and inside((s, d), batch))]
    assert got == PARENT[what]


def calls(tree, attr):
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            f = node.func
            if (f.attr if isinstance(f, ast.Attribute)
                    else getattr(f, "id", None)) == attr:
                yield node


@pytest.mark.parametrize("rule", ["jit_wraps_a_named_function",
                                  "pallas_call_passes_a_name"])
def test_no_anonymous_program_or_kernel_in_the_package(rule):
    bad = []
    for path in glob.glob(os.path.join(PACKAGE, "**", "*.py"),
                          recursive=True):
        with open(path) as f:
            tree = ast.parse(f.read())
        rel = os.path.relpath(path, PACKAGE)
        if rule == "jit_wraps_a_named_function":
            bad += [f"{rel}:{c.lineno}" for c in calls(tree, "jit")
                    if c.args and isinstance(c.args[0], ast.Lambda)]
        else:
            bad += [f"{rel}:{c.lineno}" for c in calls(tree, "pallas_call")
                    if not any(k.arg == "name" for k in c.keywords)]
            # comm_pallas_call takes the name first, as a string.
            bad += [f"{rel}:{c.lineno}"
                    for c in calls(tree, "comm_pallas_call")
                    if not (c.args and isinstance(
                        c.args[0], (ast.Constant, ast.BinOp)))]
    assert not bad, bad
