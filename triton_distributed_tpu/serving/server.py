"""Socket model server over the Engine.

Parity: reference ``mega_triton_kernel/test/models/model_server.py`` —
a TCP server (:112-198) that owns the compiled model and answers
generation requests, with the chat/bench clients speaking a small
framed protocol. Here the protocol is newline-delimited JSON over TCP:

    → {"input_ids": [[...]], "gen_len": 32}
    ← {"output_ids": [[...]], "stats": {...}}
    → {"requests": [[...], ...], "gen_lens": [4, ...],   (continuous
       "temperatures": [0.8, ...], "top_ps": [...],       batching;
       "top_ks": [...], "deadline_s": [5.0, ...],         knobs optional)
       "ticket_ids": ["t1p9", ...], "want_digest": true}
    ← {"outputs": [[...], ...],                 (partial on failure)
       "results": [{"status": "ok"|..., "reason": ...}, ...],
       "ticket_ids": [...],  "prefix_digest": [...],   (when requested)
       "stats": {...}}
    → {"cmd": "stats"}           ← {"stats": {..., "server": {...}}}
    → {"cmd": "metrics"}         ← {"prometheus": "...", "metrics": {...}}
    → {"cmd": "metrics", "scope": "fleet"}
                                 ← {"prometheus": <replica-labeled merge
                                    of every child's exposition>,
                                    "replicas": [...], "errors": {...}}
    → {"cmd": "events", "since": 0, "limit": 100, "kind": "span"}
                                 ← {"events": [...], "dropped": 0,
                                    "next_since": 17}
    → {"cmd": "events", "scope": "fleet"}
                                 ← {"events": [replica-tagged,
                                    fleet_seq-stitched], "dropped": n}
    → {"cmd": "cancel", "ticket_ids": ["t1p9"]}
                                 ← {"ok": true, "requested": 1}
    → {"cmd": "slo"}             ← {"slo": {"classes": {...},
                                    "specs": {...}}}
    → {"cmd": "kernel_trace"}    ← {"kernel_trace": {"launches": ...,
                                    "recent": [...]}}
    → {"cmd": "ping"}            ← {"ok": true, "draining": false}
    → {"cmd": "healthz"}         ← {"ok": true, "state": "serving"}
    → {"cmd": "audit"}           ← {"problems": []}   (engine lock held)
    → {"cmd": "export_slots"}    ← {"slots": {tid: snapshot, ...}}
    → {"cmd": "handoff"}         ← {"ok": true}  (in-flight batch then
                                    returns its slots as snapshots)
    → {"cmd": "shutdown"}        ← {"ok": true}   (server then drains)

A ``requests`` payload may also carry ``snapshots`` (per-request slot
snapshots to RESUME from — docs/scale-out.md "Slot migration &
handoff") and ``prefill_only`` flags (export right after admission:
the prefill→decode handoff); a ``migrated`` result entry then carries
its ``snapshot`` back.

**Streaming** (docs/serving.md "Streaming & cancellation"): a
``requests`` payload with ``"stream": true`` pushes one line-JSON
frame per EMITTED token before the final response line::

    ← {"frame": "token", "tid": "t1p9", "i": 0, "token": 17,
       "t": <monotonic stamp taken at the wire write>}
    ← ... one per token, per request, "i" strictly increasing ...
    ← {"frame": "summary", "outputs": [...], "results": [...],
       "ticket_ids": [...], "wire": [{"ttft_s": ..., "tpot_s": ...,
       "e2e_s": ..., "tokens_out": ..., "outcome": "met"}, ...],
       "stats": {...}}

``t`` stamps are taken AT the frame write — TTFT/TPOT measured from
them are what the user saw, not an engine-side latch; the per-request
``wire`` entries in the summary carry the derived wire-side numbers
and the SLO outcome (``obs/slo.py``). Requests without client
``ticket_ids`` get server-assigned ids (echoed in frames and the
summary) so a mid-stream ``{"cmd": "cancel"}`` on a second connection
can target them; a client that simply disconnects mid-stream is
detected at the next frame write and its requests are cancelled the
same way — slots torn down, pages freed, status ``cancelled`` with
the partial tokens. Re-dispatched work (router reroutes, migrations)
may re-emit earlier tokens; the sink dedups by index so each token
crosses the wire exactly once, and tokens a resume skipped are
back-filled before the summary.

The per-request sampling/deadline keys are scalars (applied to every
request) or per-request lists; omitted/null entries fall back to the
engine's defaults. ``stats`` payloads surface the engine's serving
counters verbatim — including, on paged engines, ``kv_bytes_per_token``
and ``kv_dtype`` (the quantized-KV knob, docs/serving.md "Quantized KV
cache"), so a client can read the storage mode through the wire.

**Telemetry** (docs/observability.md): ``{"cmd": "metrics"}`` returns
the process metrics registry as a Prometheus-text-format string AND a
JSON snapshot with derived p50/p90/p99; ``{"cmd": "events"}`` tails
the bounded structured-event ring drop-aware by seq number (``kind=``
pulls one stream — ``span``/``mega:launch``/``fault``/… — server-side);
``{"cmd": "kernel_trace"}`` returns the device task tracer's recent
decoded launches (mode='mega' engines; docs/observability.md "Device
task tracer"). A ``requests`` payload may carry per-request
``trace_ids`` that follow each request through admit events, launch
events, and device task rows. All are probe verbs: they never touch
the engine lock, so scraping works mid-generation. Every payload is also counted/timed per verb
(``tdt_server_requests_total``, ``tdt_server_request_seconds``,
``tdt_server_errors_total``).

**Concurrency + fault tolerance** (docs/serving.md "Fault tolerance"):
each connection is served on its own thread; generation payloads
serialize on an engine lock (the accelerator is serial anyway), while
``ping``/``stats`` bypass it — the server answers health probes even
mid-generation. At most ``max_pending`` generation payloads may wait on
the lock; excess load is shed with a structured ``overloaded`` error
(clients retry with backoff — see :func:`request`). Errors are
structured ``{"error": {"status": ..., "reason": ...}}`` objects:
``bad_request`` (malformed JSON, oversized line, unknown payload,
validation), ``overloaded``, ``shutting_down`` (graceful drain: the
server finishes in-flight work, answers pings, refuses new generation),
``internal``. Per-request failures inside a ``requests`` payload do NOT
fail the payload — the response carries per-request statuses.

The ``overloaded`` shed reply carries a load-proportional
``retry_after_s`` hint; the :func:`request` retry loop honors it over
its local exponential backoff. ``drain_grace_s`` bounds the
oversized-line connection drain (was a hardcoded 2.0) and is surfaced
in ``server_stats``.

A ``requests`` payload routes to a
:class:`~triton_distributed_tpu.models.continuous.ContinuousEngine`'s
admission/eviction loop (mixed prompt/gen lengths, paged pool, prefix
cache when the engine enables it); ``input_ids`` routes to
``Engine.serve`` fixed-batch serving. A server constructed over a
ContinuousEngine only speaks the former, over an Engine only the
latter. A server over a ``Router`` (``serving/router.py``,
docs/scale-out.md) speaks the continuous form, dispatches generation
payloads WITHOUT the engine lock (the router's per-replica queues
serialize), and drains the replica fleet on shutdown.
"""

from __future__ import annotations

import base64
import itertools
import json
import os
import random
import socket
import threading
import time

import numpy as np

from triton_distributed_tpu.models.engine import Engine
from triton_distributed_tpu.obs import events as obs_events
from triton_distributed_tpu.obs import metrics as obs_metrics
from triton_distributed_tpu.obs import slo as obs_slo
from triton_distributed_tpu.obs.metrics import prometheus_text
from triton_distributed_tpu.obs.timeline import Timeline
from triton_distributed_tpu.runtime.faults import fault_point, mutate_point
from triton_distributed_tpu.runtime.profiling import trace_span


# The probe verbs _dispatch_inner answers. ONE tuple: the metrics
# label in _verb_of and the `accepted payloads` help both derive from
# it, so a new verb can't silently label its traffic `unknown`. All
# are engine-lock-free EXCEPT `audit` (it walks live engine state, so
# it serializes behind generation — run it quiesced).
PROBE_CMDS = ("ping", "healthz", "stats", "metrics", "events",
              "kernel_trace", "audit", "shutdown", "export_slots",
              "handoff", "cancel", "slo", "tier_probe", "tier_get",
              "tier_peers")

# Bound on one tier_probe's key list: probes are per-page walks, and a
# prompt's page count is small — an unbounded list is a client bug.
MAX_TIER_PROBE_KEYS = 256

# Server-assigned stream ticket ids (payloads that stream without
# client ticket_ids still need cancellable identities); pid-suffixed
# like replica tids so they stay unique across routers sharing a
# replica.
_STREAM_IDS = itertools.count(1)


class _BadRequest(ValueError):
    """Client-side protocol error: mapped to status ``bad_request``."""


class _StreamSink:
    """Per-payload streaming state (docs/serving.md "Streaming &
    cancellation"): ONE wire write path for every token frame of a
    streamed ``requests`` payload, with the three properties the wire
    grammar promises:

    - **exactly-once frames** — engines re-emit earlier indices on
      re-dispatch (router reroutes, migration replays; at-least-once
      by design); the sink dedups by per-request index so each token
      crosses the wire once, and :meth:`finish` back-fills tokens a
      snapshot resume skipped before the summary goes out;
    - **wire-side stamps** — each frame's departure stamps the
      request's wire :class:`Timeline` (``stamp_token``), the numbers
      TTFT/TPOT/goodput are derived from;
    - **disconnect → cancel** — a failed frame write (client gone, or
      the injected ``stream.send`` fault) marks the sink broken and
      cancels the payload's requests through the engine's ``cancel``,
      so an abandoned stream frees its slots and pages instead of
      generating tokens nobody reads.

    Callbacks arrive on the engine thread (single engine) or replica
    worker threads (router) — the internal lock serializes writes.
    Back-pressure caveat: a frame write blocks ITS emitter, which for
    a single engine is only that payload's loop, but on a router a
    replica worker streaming for client A stalls any work co-batched
    with A on that replica (bounded by the connection's socket
    timeout). A per-connection writer thread with a bounded queue
    would decouple it — not built until a workload needs it.
    """

    def __init__(self, server: "ModelServer", f, tids: list):
        self._server = server
        self._f = f
        self.tids = tids
        self._lock = threading.Lock()
        self._sent = [0] * len(tids)
        self.timelines = [Timeline() for _ in tids]
        self.broken = False
        self._closed = False

    def attach_enqueue(self, enqueue_t: float | None) -> None:
        for tl in self.timelines:
            tl.enqueue_t = enqueue_t
            tl.stamp_enqueue()

    def seed(self, ri: int, n: int) -> None:
        """Start request ``ri``'s stream at index ``n`` — the tokens a
        payload-carried snapshot already restored. The client
        resubmitting its own snapshot HOLDS that prefix; without the
        seed, the first live token (index n) would read as a gap and
        every post-resume frame would defer to the summary back-fill,
        freezing the stream for exactly the migration-resume case."""
        with self._lock:
            self._sent[ri] = max(self._sent[ri], int(n))

    def sink_for(self, ri: int):
        """The ``on_token`` callback for request index ``ri``."""

        def cb(i, token):
            self.push(ri, int(i), int(token))

        return cb

    def push(self, ri: int, i: int, token: int) -> None:
        with self._lock:
            if self._closed or self.broken:
                return
            if i != self._sent[ri]:
                # i < sent: re-dispatch replay, already delivered.
                # i > sent: a resume skipped past frames this sink
                # never carried (lost with a dying child's socket) —
                # streaming the jump would violate the in-order
                # contract, and the missing tokens aren't known HERE;
                # finish() back-fills the whole ordered tail from the
                # final result instead.
                return
            self._write(ri, i, token)

    def _write(self, ri: int, i: int, token: int) -> None:
        """One frame out (caller holds the lock). The ``t`` stamp is
        taken at the write — the wire-side clock."""
        frame = {"frame": "token", "tid": self.tids[ri], "i": i,
                 "token": token, "t": time.monotonic()}
        try:
            data = json.dumps(frame).encode() + b"\n"
            data = mutate_point("stream.send", data,
                                tid=self.tids[ri], i=i)
            self._f.write(data)
            self._f.flush()
        except Exception:  # noqa: BLE001 — the client vanished
            self.broken = True
            self._disconnect()
            return
        self._sent[ri] = i + 1
        self.timelines[ri].stamp_token()
        if obs_metrics.default_registry().enabled:
            self._server._m_frames.inc()

    def _disconnect(self) -> None:
        self._server._m_disconnects.inc()
        obs_events.emit("stream_disconnect", requests=len(self.tids))
        if self._closed:
            # The disconnect surfaced during finish()'s back-fill —
            # the engine batch already returned AND pruned this
            # batch's cancel ids, so arming them now would only go
            # stale and kill a future request that reuses the same
            # client ticket id. There is nothing left to cancel.
            return
        canceller = getattr(self._server.engine, "cancel", None)
        if canceller is not None:
            try:
                canceller(self.tids)
            except Exception:  # noqa: BLE001 — teardown is best-effort
                pass

    def finish(self, results) -> None:
        """Close the sink (late worker callbacks become no-ops) and
        back-fill any tokens the frames never carried — a snapshot
        resume on another replica starts past what ITS engine emitted,
        and those earlier tokens may predate this sink entirely. They
        reached the user NOW, so their stamps are now: wire-honest."""
        with self._lock:
            self._closed = True
            if self.broken:
                return
            for ri, r in enumerate(results):
                toks = [int(t) for t in r.tokens]
                for i in range(self._sent[ri], len(toks)):
                    self._write(ri, i, toks[i])
                    if self.broken:
                        return


class ModelServer:
    """Own a listening socket + an Engine; serve generation requests."""

    # An idle client must not wedge a connection thread forever: a
    # connection that sends nothing within this window is dropped.
    IDLE_TIMEOUT_S = 10.0
    # Bound on one accepted request line: a giant payload must not OOM
    # the server before JSON parsing even starts.
    MAX_LINE_BYTES = 1 << 20
    # Graceful-drain bound: how long serve_forever waits for in-flight
    # connections after shutdown (threads are daemonized — a wedged
    # client cannot hold process exit hostage).
    DRAIN_TIMEOUT_S = 30.0

    def __init__(
        self,
        engine: Engine,
        host: str = "127.0.0.1",
        port: int = 0,
        *,
        max_pending: int = 8,
        drain_grace_s: float = 2.0,
        trace_dir: str | None = None,
        slo=None,
        advertise_host: str | None = None,
    ):
        self.engine = engine
        self.max_pending = max_pending
        # SLO specs (docs/observability.md "SLO goodput"): a single
        # SLOSpec, a {class: spec} dict, or None — normalized so a
        # `default` class always exists. Streaming payloads judge
        # their wire-side timelines against the request's class; the
        # {"cmd": "slo"} verb reports the resulting goodput.
        self.slo_specs = obs_slo.normalize_specs(slo)
        # Informational: where a --trace run merges its host+device
        # timeline (run_server owns the actual group_profile capture;
        # the server only surfaces the knob in server_stats so a
        # scraper can see tracing is deployed).
        self.trace_dir = trace_dir
        # Connection-drain budget (was a hardcoded 2.0): bounds how
        # long an oversized-line tail is drained before the conn
        # closes, and rides into the router's replica-drain grace when
        # this server fronts a Router (docs/scale-out.md). Surfaced in
        # ``server_stats`` so a scraper can see the deployed value.
        self.drain_grace_s = float(drain_grace_s)
        # Routers serialize internally (per-replica queues): dispatch
        # their generation payloads WITHOUT the engine lock so
        # payloads from many connections fan out across replicas.
        self._concurrent = bool(getattr(engine, "concurrent_safe", False))
        self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._sock.bind((host, port))
        self._sock.listen(16)
        self.host, self.port = self._sock.getsockname()
        # The address peers should DIAL (docs/scale-out.md "Multi-host
        # fleet"): binding 0.0.0.0 (or any wildcard) makes the bound
        # host meaningless to other machines, so port files, peer
        # lists, and server_stats carry this instead. Defaults to the
        # bound host — single-host setups see no change.
        self.advertise_host = (str(advertise_host) if advertise_host
                               else self.host)
        self._shutdown = threading.Event()
        self._thread: threading.Thread | None = None
        # One generation at a time (the accelerator is serial); probes
        # (ping/stats) never take this lock, so the server answers them
        # mid-generation.
        self._engine_lock = threading.Lock()
        self._pending = 0
        self._pending_lock = threading.Lock()
        self._counters = {
            "connections": 0,
            "requests": 0,
            "errors": 0,       # per-payload failures (bad/unknown/internal)
            "conn_errors": 0,  # per-connection failures (drop/timeout)
            "shed": 0,         # generation payloads shed as overloaded
            "refused": 0,      # generation payloads refused while draining
        }
        self._counters_lock = threading.Lock()
        self._last_conn_error: str | None = None
        self._t0 = time.monotonic()
        # Metric handles resolved ONCE (engine-convention): a payload
        # must not pay registry get-or-create lookups on the same
        # global lock the decode loop's counters contend on.
        self._m_requests = obs_metrics.counter(
            "tdt_server_requests_total",
            "Payloads dispatched, by verb.", labels=("verb",),
        )
        self._m_seconds = obs_metrics.histogram(
            "tdt_server_request_seconds",
            "Wall time handling one payload, by verb.",
            labels=("verb",),
        )
        self._m_errors = obs_metrics.counter(
            "tdt_server_errors_total",
            "Structured error responses, by verb and status.",
            labels=("verb", "status"),
        )
        self._m_frames = obs_metrics.counter(
            "tdt_server_stream_frames_total",
            "Token frames pushed to streaming clients.",
        )
        self._m_disconnects = obs_metrics.counter(
            "tdt_server_stream_disconnects_total",
            "Streaming payloads whose client vanished mid-stream "
            "(their requests are cancelled).",
        )

    def _count(self, key: str) -> None:
        with self._counters_lock:
            self._counters[key] += 1

    @property
    def server_stats(self) -> dict:
        with self._counters_lock:
            stats = dict(self._counters)
            stats["last_conn_error"] = self._last_conn_error
        with self._pending_lock:
            stats["pending"] = self._pending
        stats["draining"] = self._shutdown.is_set()
        stats["drain_grace_s"] = self.drain_grace_s
        stats["advertise_host"] = self.advertise_host
        # Deployed engine knobs (docs/serving.md): scrapers see what
        # configuration is actually serving without shelling into the
        # host. Routers surface per-replica details in the stats verb's
        # ``router`` ledger instead; these getattrs then report the
        # fleet-level defaults (None/0).
        engine_cfg = getattr(
            getattr(self.engine, "model", None), "cfg", None
        )
        stats["engine"] = {
            "mode": getattr(self.engine, "mode", None),
            "kv_dtype": getattr(self.engine, "kv_dtype", None),
            "speculative": getattr(self.engine, "speculative", 0),
            "kernel_trace": getattr(self.engine, "kernel_trace", False),
            # MoE knobs (docs/serving.md "MoE serving"): 0 for dense
            # models and fleet routers (whose per-replica details ride
            # the stats verb's ``router`` ledger).
            "num_experts": getattr(engine_cfg, "num_experts", 0),
            "experts_per_tok": getattr(
                engine_cfg, "num_experts_per_tok", 0
            ),
        }
        # Durable KV tier (docs/serving.md "Tiered KV"): the deployed
        # capacity/dir, next to kv_dtype — 0/None when no tier is
        # attached (or when a Router fronts per-replica tiers, whose
        # details ride the stats verb's per-replica snapshots).
        tier = getattr(self.engine, "tier", None)
        stats["engine"]["tier_bytes"] = (
            int(getattr(tier, "capacity_bytes", 0)) if tier is not None
            else 0
        )
        stats["engine"]["tier_dir"] = (
            getattr(tier, "dir", None) if tier is not None else None
        )
        # Deployed SLO deadlines (docs/observability.md "SLO
        # goodput"): scrapers see what the goodput numbers are judged
        # against without shelling into the host.
        stats["engine"]["slo"] = {
            name: spec.as_dict()
            for name, spec in sorted(self.slo_specs.items())
        }
        # Pool shape (docs/scale-out.md "Disaggregated pools &
        # autoscaling"): per-role replica counts when a pool-aware
        # Router fronts the engine — absent for single-engine servers.
        shape = getattr(self.engine, "pool_shape", None)
        if callable(shape):
            try:
                stats["pools"] = shape()
            except Exception:  # noqa: BLE001 — stats must answer
                pass
        # --trace DIR deployments (run_server) surface where the
        # merged host+device timeline will land.
        stats["trace_dir"] = self.trace_dir
        # ``snapshot_at`` is the same monotonic clock the per-request
        # timelines use, so a scraper can order stats snapshots against
        # event-ring timestamps without wall-clock skew.
        now = time.monotonic()
        stats["uptime_s"] = now - self._t0
        stats["snapshot_at"] = now
        return stats

    # -- request handling ------------------------------------------------

    @staticmethod
    def _error(status: str, reason: str, **extra) -> dict:
        return {"error": {"status": status, "reason": reason, **extra}}

    @staticmethod
    def _verb_of(req) -> str:
        """Metrics label for a payload: its probe cmd, or which
        generation form it takes (bounded cardinality by construction —
        unknown cmds all land under ``unknown``)."""
        if not isinstance(req, dict):
            return "unknown"
        cmd = req.get("cmd")
        if cmd in PROBE_CMDS:
            return cmd
        if "requests" in req:
            return "requests"
        if "input_ids" in req:
            return "generate"
        return "unknown"

    def _dispatch(self, req, stream_f=None) -> dict:
        """Route one parsed payload with per-verb telemetry; every
        failure becomes a structured error response — nothing escapes
        to kill the connection. ``stream_f`` is the connection's
        buffered file: a ``"stream": true`` generation payload pushes
        its token frames through it before the returned summary."""
        verb = self._verb_of(req)
        t0 = time.monotonic()
        resp = self._dispatch_inner(req, stream_f)
        if obs_metrics.default_registry().enabled:
            self._m_requests.inc(verb=verb)
            self._m_seconds.observe(time.monotonic() - t0, verb=verb)
            err = resp.get("error")
            if isinstance(err, dict):
                self._m_errors.inc(verb=verb, status=str(err.get("status")))
        return resp

    def _dispatch_inner(self, req, stream_f=None) -> dict:
        try:
            if not isinstance(req, dict):
                raise _BadRequest("payload must be a JSON object")
            cmd = req.get("cmd")
            if cmd == "ping":
                return {"ok": True, "draining": self._shutdown.is_set()}
            if cmd == "cancel":
                # Client-driven cancellation (docs/serving.md
                # "Streaming & cancellation"). Engine-lock-FREE (a set
                # add / queue filter): the whole point is landing
                # MID-generation, from a second connection, against a
                # batch the engine lock is busy serving.
                tids = req.get("ticket_ids")
                if (not isinstance(tids, list) or not tids
                        or not all(isinstance(t, (str, int))
                                   for t in tids)):
                    raise _BadRequest(
                        "cancel needs a non-empty ticket_ids list of "
                        "strings/ints"
                    )
                canceller = getattr(self.engine, "cancel", None)
                if canceller is None:
                    raise _BadRequest(
                        "this engine has no cancel() "
                        "(ContinuousEngine/StubEngine/Router expose it; "
                        "see docs/serving.md 'Streaming & cancellation')"
                    )
                canceller([str(t) for t in tids])
                return {"ok": True, "requested": len(tids)}
            if cmd == "slo":
                # Goodput readout (docs/observability.md "SLO
                # goodput"): per-class met/missed/cancelled counts,
                # goodput, and wire-side latency quantiles, judged
                # against this server's deployed specs. Probe verb —
                # registry reads only.
                return {"slo": obs_slo.snapshot(self.slo_specs)}
            if cmd == "healthz":
                # The heartbeat target (docs/scale-out.md "Process
                # fleet"): liveness ONLY. No engine lock, no
                # server_stats construction — it must answer fast
                # mid-generation, because a missed deadline here is
                # what the supervisor reads as a wedged process.
                # `state` lets it tell a draining replica from a dead
                # one before classifying an exit as a crash.
                return {
                    "ok": True,
                    "state": ("shutting_down" if self._shutdown.is_set()
                              else "serving"),
                }
            if cmd == "audit":
                # Fleet-audit verb: the router's `Router.audit` reaches
                # remote replicas' pool/radix invariants through this.
                # NOT engine-lock-free — the audit walks live slot and
                # tree state, so it queues behind in-flight generation
                # instead of racing it.
                auditor = getattr(self.engine, "audit", None)
                if auditor is None:
                    raise _BadRequest("this engine has no audit()")
                with self._engine_lock:
                    return {"problems": [str(p) for p in auditor()]}
            if cmd == "export_slots":
                # Slot-migration probe (docs/scale-out.md "Slot
                # migration & handoff"): the engine's incremental
                # per-ticket snapshot buffer, refreshed at scheduling-
                # round boundaries. Engine-lock-FREE (the buffer has
                # its own lock) — the supervisor polls this MID-batch;
                # that is the whole point of snapshot-based crash
                # recovery.
                exporter = getattr(self.engine, "export_slots", None)
                if exporter is None:
                    raise _BadRequest(
                        "this engine has no slot snapshots "
                        "(ContinuousEngine/StubEngine expose them; see "
                        "docs/scale-out.md 'Slot migration & handoff')"
                    )
                return {"slots": exporter()}
            if cmd == "handoff":
                # Lossless-drain trigger: arm the engine's handoff
                # sweep so the in-flight batch returns its unfinished
                # slots as exported snapshots instead of finishing
                # them here. Engine-lock-free (an event/int write) —
                # it must land WHILE the batch runs.
                rh = getattr(self.engine, "request_handoff", None)
                if rh is None:
                    raise _BadRequest(
                        "this engine has no handoff support "
                        "(ContinuousEngine/StubEngine expose it)"
                    )
                rh()
                return {"ok": True}
            if cmd in ("tier_probe", "tier_get"):
                # KV fabric serve side (docs/scale-out.md "KV fabric").
                # Engine-lock-FREE like metrics/healthz: the PageStore
                # has its own lock, and peers probe/pull MID-batch —
                # that is the point of cross-replica fault-back.
                # ``prefix`` entries only: snapshots are per-ticket
                # crash-recovery state, not shareable cache.
                from triton_distributed_tpu.models import kv_tier

                tier = getattr(self.engine, "tier", None)
                if tier is None:
                    raise _BadRequest(
                        "this engine has no KV tier (run with "
                        "tier_bytes/tier_dir; see docs/serving.md "
                        "'Tiered KV')"
                    )
                kind = req.get("kind", kv_tier.PREFIX_KIND)
                if kind != kv_tier.PREFIX_KIND:
                    raise _BadRequest(
                        "the KV fabric serves 'prefix' entries only"
                    )
                if cmd == "tier_probe":
                    keys = req.get("keys")
                    if (not isinstance(keys, list) or not keys
                            or len(keys) > MAX_TIER_PROBE_KEYS
                            or not all(isinstance(k, str) for k in keys)):
                        raise _BadRequest(
                            "tier_probe needs a non-empty keys list of "
                            f"<= {MAX_TIER_PROBE_KEYS} strings"
                        )
                    return {
                        "have": [bool(tier.contains(kind, k))
                                 for k in keys],
                    }
                key = req.get("key")
                if not isinstance(key, str) or not key:
                    raise _BadRequest("tier_get needs a string key")
                blob = tier.get_blob(kind, key)
                if blob is None:
                    return {"found": False}
                b64 = base64.b64encode(blob).decode()
                if len(b64) > self.MAX_LINE_BYTES - 4096:
                    # The response must fit one wire line; an oversized
                    # entry reads as a miss — the puller re-prefills.
                    return {"found": False, "reason": "oversized"}
                return {"found": True, "blob": b64}
            if cmd == "tier_peers":
                # Supervisor broadcast: (re)wire this replica's fabric
                # client at the engine's peer set. Engine-lock-free (a
                # list swap under the client's own lock).
                fabric = getattr(self.engine, "fabric", None)
                if fabric is None:
                    raise _BadRequest(
                        "this engine has no KV fabric client (run with "
                        "a tier + fabric; see docs/scale-out.md "
                        "'KV fabric')"
                    )
                peers = req.get("peers")
                if not isinstance(peers, list):
                    raise _BadRequest("tier_peers needs a peers list")
                fabric.set_wire_peers(peers)
                return {"ok": True, "peers": len(fabric.peers)}
            if cmd == "shutdown":
                self._shutdown.set()
                return {"ok": True}
            if cmd == "stats":
                stats = dict(self.engine.last_stats)
                stats["server"] = self.server_stats
                return {"stats": stats}
            if cmd == "metrics":
                # Probe verb: reads the registry under its own short
                # lock, never the engine lock — scraping answers
                # mid-generation (docs/observability.md).
                scope = req.get("scope")
                if scope not in (None, "process", "fleet"):
                    raise _BadRequest(
                        "metrics scope must be 'process' or 'fleet'"
                    )
                if scope == "fleet":
                    fleet = getattr(self.engine, "fleet", None)
                    if fleet is not None and hasattr(fleet,
                                                     "fleet_metrics"):
                        # Process fleet (docs/scale-out.md "Fleet-scope
                        # telemetry"): the supervisor fans the metrics
                        # verb out to every child and merges the
                        # expositions replica-labeled — one scrape
                        # sees the whole fleet.
                        out = fleet.fleet_metrics()
                        return {
                            "prometheus": out["prometheus"],
                            "scope": "fleet",
                            "replicas": out["replicas"],
                            "errors": out["errors"],
                        }
                    # No process fleet behind this server: in-process
                    # replicas share THIS registry, so the process
                    # scrape already IS the fleet view.
                    reg = obs_metrics.default_registry()
                    return {
                        "prometheus": prometheus_text(reg),
                        "metrics": reg.snapshot(),
                        "scope": "process",
                    }
                reg = obs_metrics.default_registry()
                return {
                    "prometheus": prometheus_text(reg),
                    "metrics": reg.snapshot(),
                }
            if (cmd == "events"
                    and req.get("scope") not in (None, "process")):
                # Same validation rule as metrics: a typo'd scope must
                # not silently degrade a fleet scraper to one process.
                if req.get("scope") != "fleet":
                    raise _BadRequest(
                        "events scope must be 'process' or 'fleet'"
                    )
                fleet = getattr(self.engine, "fleet", None)
                if fleet is None or not hasattr(fleet, "fleet_events"):
                    raise _BadRequest(
                        "events scope 'fleet' needs a supervised "
                        "process fleet behind this server "
                        "(docs/scale-out.md 'Fleet-scope telemetry')"
                    )
                limit = req.get("limit")
                if limit is not None and (not isinstance(limit, int)
                                          or limit < 0):
                    raise _BadRequest(
                        "events limit must be an integer >= 0"
                    )
                if req.get("kind") is not None or "since" in req:
                    # The fleet stream's per-child cursors are SHARED
                    # server-side state: a kind-filtered pull would
                    # advance them past every other-kind event
                    # (dropped=0) and hide those events forever, and a
                    # client `since` cannot seek them — refusing both
                    # loudly beats silently returning an arbitrary
                    # window.
                    raise _BadRequest(
                        "fleet-scope events supports neither kind nor "
                        "since (server-side shared cursors page "
                        "forward); filter the merged rows client-side"
                    )
                return fleet.fleet_events(limit=limit)
            if cmd == "events":
                try:
                    # JSON null is a natural "from the start" / "no
                    # cap" spelling; anything else must be an int —
                    # and a wrong TYPE is the client's fault, not an
                    # `internal` server error.
                    since = req.get("since")
                    since = 0 if since is None else int(since)
                    limit = req.get("limit")
                    limit = None if limit is None else int(limit)
                except (TypeError, ValueError) as e:
                    raise _BadRequest(
                        f"events since/limit must be integers: {e}"
                    )
                if since < 0 or (limit is not None and limit < 0):
                    # A negative cursor would manufacture phantom
                    # `dropped` counts (tail reports events[0].seq -
                    # since - 1), corrupting drop-summing consumers.
                    raise _BadRequest(
                        "events since/limit must be >= 0"
                    )
                # kind= pulls one stream (span / mega:launch / fault /
                # admit / ...) server-side instead of every consumer
                # re-filtering the full firehose client-side.
                kind = req.get("kind")
                if kind is not None and not isinstance(kind, str):
                    raise _BadRequest("events kind must be a string")
                ring = obs_events.default_ring()
                # Snapshot the newest seq BEFORE tailing: a
                # kind-filtered empty page may safely skip everything
                # scanned (all non-matching), but not events emitted
                # after the scan.
                newest_pre = ring.next_seq - 1
                evts, dropped = ring.tail(since, limit, kind=kind)
                # Empty tail still advances the cursor past anything
                # the ring dropped (e.g. a clear()), or a drop-summing
                # consumer would re-count the same loss every poll —
                # but never past events a `limit` deferred to the next
                # page (tail keeps the oldest, so since+dropped is
                # always the seq just before the first undelivered
                # event). A kind-filtered empty page additionally
                # skips the scanned non-matching events.
                if evts:
                    next_since = evts[-1].seq
                elif kind is not None and limit != 0:
                    # Zero matches in the WHOLE scanned range (a
                    # nonzero limit can only truncate matches, and
                    # there were none): safe to skip the scanned
                    # non-matching events. limit == 0 returns an empty
                    # page regardless of matches, so it must NOT skip
                    # — matching events may sit in (since, newest].
                    next_since = max(since, newest_pre)
                else:
                    next_since = since + dropped
                return {
                    "events": [e.as_dict() for e in evts],
                    "dropped": dropped,
                    "next_since": next_since,
                }
            if cmd == "kernel_trace":
                # Probe verb (engine-lock-free): the engines keep the
                # decoded launches under their own bounded deque, so a
                # scrape mid-generation reads a recent snapshot.
                summary = getattr(
                    self.engine, "kernel_trace_summary", None
                )
                if summary is None:
                    raise _BadRequest(
                        "this engine has no device kernel tracer "
                        "(mode='mega' engines expose it; see "
                        "docs/observability.md 'Device task tracer')"
                    )
                return {"kernel_trace": summary()}
            if "requests" in req or "input_ids" in req:
                return self._generate_guarded(req, stream_f)
            accepted = [
                f"cmd ({'|'.join(PROBE_CMDS)})",
                "requests + gen_lens/temperatures/top_ps/top_ks/"
                "deadline_s/trace_ids/ticket_ids/want_digest/"
                "want_tier_digest/snapshots/prefill_only/stream/"
                "slo_class (continuous batching)",
                "input_ids + gen_len/prompt_start (fixed batch)",
            ]
            raise _BadRequest(
                f"unknown request with keys {sorted(req.keys())}; "
                f"accepted payloads: {accepted}"
            )
        except _BadRequest as e:
            self._count("errors")
            return self._error("bad_request", str(e))
        except ValueError as e:
            # Engine-side request validation (knob/gen_lens mismatch,
            # prompt_start out of range, oversized fixed-batch serve)
            # is the client's fault; anything else escaping the engine
            # (TypeError/KeyError deep in a forward pass) is OURS and
            # must read as `internal`, not as a malformed request.
            self._count("errors")
            return self._error("bad_request", f"{type(e).__name__}: {e}")
        except Exception as e:  # noqa: BLE001 — keep the server alive
            self._count("errors")
            return self._error("internal", f"{type(e).__name__}: {e}")

    def _generate_guarded(self, req: dict, stream_f=None) -> dict:
        """Admission control around the engine: refuse while draining,
        shed when too many payloads already wait on the engine lock."""
        if self._shutdown.is_set():
            self._count("refused")
            return self._error(
                "shutting_down",
                "server is draining; no new generation work accepted",
            )
        shed_depth = None
        with self._pending_lock:
            if self._pending >= self.max_pending:
                shed_depth = self._pending
            else:
                self._pending += 1
        rows = req.get("requests", req.get("input_ids"))
        with trace_span(
            "entry:payload",
            requests=len(rows) if isinstance(rows, list) else 1,
            shed=int(shed_depth is not None), _ring=False,
        ):
            if shed_depth is not None:
                return self._shed(req, shed_depth)
            # Enqueue stamp BEFORE the engine lock: a request's
            # queue-wait must include the time its payload spent
            # waiting on other generations, not just the engine's
            # admission queue.
            enqueue_t = time.monotonic()
            try:
                if self._concurrent:
                    self._count("requests")
                    return self._generate(req, enqueue_t, stream_f)
                with self._engine_lock:
                    self._count("requests")
                    return self._generate(req, enqueue_t, stream_f)
            finally:
                with self._pending_lock:
                    self._pending -= 1

    def _shed(self, req: dict, shed_depth: int) -> dict:
        """The front door's ``overloaded`` reply to a payload that
        found ``shed_depth`` others already pending."""
        self._count("shed")
        # Front-door sheds are MISSES: the user got nothing, and
        # a server that sheds its way past the engine must not
        # read as 100% goodput (the invariant
        # docs/observability.md states; engine-level sheds are
        # judged through their results the same way). Outside the
        # pending lock: the ledger fold must not serialize the
        # admission gate during exactly the storm that sheds.
        self._observe_shed(req)
        # Load-proportional backoff hint: clients that honor
        # ``retry_after_s`` (see :func:`request`) spread their
        # retries with the depth of the queue they bounced off,
        # instead of hammering a shedding server in lockstep.
        return self._error(
            "overloaded",
            f"{shed_depth} generation payloads already "
            f"pending (bound {self.max_pending}); retry with "
            "backoff",
            retry_after_s=round(
                min(max(0.1 * shed_depth, 0.05), 2.0), 3
            ),
        )

    def _observe_synthetic(self, n: int, slo_class, enqueue_t,
                           status: str, tokens_out: int = 0) -> None:
        """Fold ``n`` synthetic wire timelines (no per-token stamps)
        into the SLO ledger — THE shared implementation for front-door
        sheds and fixed-batch serves, so the class-resolution rule
        (unknown → ``default``, bounded cardinality) lives once."""
        spec = self.slo_specs.get(
            slo_class if isinstance(slo_class, str) else "default"
        ) or self.slo_specs["default"]
        for _ in range(max(int(n), 1)):
            tl = Timeline()
            if status == "ok":
                # Only a SERVED synthetic gets measurable durations; a
                # shed's ~0-second "e2e" would evaluate UNDER any e2e
                # bound, recording a miss with zero violations — the
                # unmeasurable-on-failure rule (obs/slo.py) is what
                # makes violations explain every miss.
                tl.enqueue_t = enqueue_t
                tl.stamp_enqueue()
            tl.tokens_out = tokens_out
            tl.finish(status)
            obs_slo.observe_wire(tl, spec)

    def _observe_shed(self, req) -> None:
        """Fold a front-door shed into the SLO ledger: one ``missed``
        per request the refused payload carried (best-effort — the
        payload was never validated). Internal fan-out payloads skip,
        same as :meth:`_judge_wire`."""
        if not isinstance(req, dict) or req.get("fanout"):
            return
        reqs = req.get("requests")
        if isinstance(reqs, list):
            n = len(reqs)
        else:
            rows = req.get("input_ids")
            n = len(rows) if isinstance(rows, list) else 1
        self._observe_synthetic(n, req.get("slo_class"), None,
                                "overloaded")

    def _generate(self, req: dict, enqueue_t: float | None = None,
                  stream_f=None) -> dict:
        if "requests" in req:
            if not hasattr(self.engine, "run"):
                raise _BadRequest(
                    "'requests' payloads need a ContinuousEngine; this "
                    "server wraps a fixed-batch Engine"
                )
            prompts = [np.asarray(p, np.int32) for p in req["requests"]]
            gen_lens = req.get("gen_lens")
            if gen_lens is None:  # [] is malformed, not "use defaults"
                gen_lens = [16] * len(prompts)
            if len(gen_lens) != len(prompts):
                raise ValueError(
                    f"{len(prompts)} requests but {len(gen_lens)} gen_lens"
                )

            def knob(name, cast):
                """Per-request knob: scalar → broadcast, list → per
                request, absent/null → engine default."""
                v = req.get(name)
                if v is None:
                    return [None] * len(prompts)
                if isinstance(v, (int, float)):
                    return [cast(v)] * len(prompts)
                if len(v) != len(prompts):
                    raise ValueError(
                        f"{len(prompts)} requests but {len(v)} {name}"
                    )
                return [None if x is None else cast(x) for x in v]

            temps = knob("temperatures", float)
            top_ps = knob("top_ps", float)
            top_ks = knob("top_ks", int)
            deadlines = knob("deadline_s", float)
            # Client-supplied trace ids (docs/observability.md "Device
            # task tracer"): follow each request through admit events,
            # mega:launch events, and device-task ring records. Always
            # a list (no scalar broadcast — ids must stay per-request
            # unique); omitted/null entries get engine-assigned ids.
            trace_ids = req.get("trace_ids")
            if trace_ids is None:
                trace_ids = [None] * len(prompts)
            elif (not isinstance(trace_ids, list)
                  or len(trace_ids) != len(prompts)):
                raise ValueError(
                    f"{len(prompts)} requests but trace_ids is "
                    f"{trace_ids!r} (want a {len(prompts)}-entry list)"
                )
            else:
                trace_ids = [
                    None if x is None else str(x) for x in trace_ids
                ]
            # Ticket ids (docs/scale-out.md "Process fleet",
            # docs/serving.md "Streaming & cancellation"): per-request
            # identities. A RemoteReplica latches results by them, the
            # engines match cancellations against them, stream frames
            # carry them — and they are echoed verbatim in the
            # response, so a response carrying an id the caller no
            # longer waits on is recognized and discarded (the
            # at-least-once dedup). All of that keys BY id, so
            # duplicates within one payload would silently conflate
            # two requests — refused here, next to the shape check.
            ticket_ids = req.get("ticket_ids")
            if ticket_ids is not None and (
                    not isinstance(ticket_ids, list)
                    or len(ticket_ids) != len(prompts)):
                raise ValueError(
                    f"{len(prompts)} requests but ticket_ids is "
                    f"{ticket_ids!r} (want a {len(prompts)}-entry list)"
                )
            if ticket_ids is not None:
                given = [str(t) for t in ticket_ids if t is not None]
                if len(given) != len(set(given)):
                    raise ValueError(
                        "ticket_ids must be unique within a payload "
                        "(results latch, cancellations match, and "
                        "stream frames key by id)"
                    )
            # Slot migration (docs/scale-out.md "Slot migration &
            # handoff"): per-request snapshots resume migrated work
            # (the engine imports instead of re-prefilling);
            # ``prefill_only`` asks the engine to export right after
            # admission (the prefill→decode handoff's first hop).
            snapshots = req.get("snapshots")
            if snapshots is None:
                snapshots = [None] * len(prompts)
            elif (not isinstance(snapshots, list)
                  or len(snapshots) != len(prompts)):
                raise ValueError(
                    f"{len(prompts)} requests but snapshots is a "
                    f"{type(snapshots).__name__} of wrong shape "
                    f"(want a {len(prompts)}-entry list)"
                )
            prefill_only = req.get("prefill_only")
            if prefill_only is None:
                prefill_only = [False] * len(prompts)
            elif (not isinstance(prefill_only, list)
                  or len(prefill_only) != len(prompts)):
                raise ValueError(
                    f"{len(prompts)} requests but prefill_only is "
                    f"{prefill_only!r} (want a {len(prompts)}-entry "
                    "list)"
                )
            # SLO class (docs/observability.md "SLO goodput"): scalar
            # or per-request list. Unknown classes collapse into the
            # deployed `default` spec — outcome labels come from the
            # CONFIGURED spec names, so a client can't grow the label
            # cardinality with arbitrary strings.
            slo_cls = req.get("slo_class")
            if slo_cls is None:
                slo_classes = ["default"] * len(prompts)
            elif isinstance(slo_cls, str):
                slo_classes = [slo_cls] * len(prompts)
            elif (isinstance(slo_cls, list)
                  and len(slo_cls) == len(prompts)):
                slo_classes = [
                    "default" if c is None else str(c) for c in slo_cls
                ]
            else:
                raise ValueError(
                    f"{len(prompts)} requests but slo_class is "
                    f"{slo_cls!r} (want a string or a "
                    f"{len(prompts)}-entry list)"
                )
            # Streaming (docs/serving.md "Streaming & cancellation"):
            # per-token frames need cancellable identities — client
            # ticket_ids when given, server-assigned otherwise (echoed
            # in every frame and the summary).
            stream = bool(req.get("stream"))
            # Engine-side ids are ALWAYS strings: the cancel verb
            # coerces its ids to str, so an int ticket_id here would
            # make cancellation a silent no-op. The wire echo below
            # still returns the client's ids verbatim.
            eff_tids = (
                None if ticket_ids is None
                else [None if t is None else str(t) for t in ticket_ids]
            )
            sink = None
            if stream:
                if stream_f is None:
                    raise _BadRequest(
                        "streaming is only available over the socket "
                        "transport"
                    )
                if eff_tids is None:
                    eff_tids = [None] * len(prompts)
                eff_tids = [
                    t if t is not None
                    else f"s{next(_STREAM_IDS)}p{os.getpid()}"
                    for t in eff_tids
                ]
                sink = _StreamSink(self, stream_f, eff_tids)
                sink.attach_enqueue(enqueue_t)
                for i, sn in enumerate(snapshots):
                    if isinstance(sn, dict):
                        sink.seed(i, len(sn.get("out") or []))
            from triton_distributed_tpu.models.continuous import Request

            def _timeline() -> Timeline:
                tl = Timeline()
                tl.enqueue_t = enqueue_t  # pre-engine-lock arrival
                return tl

            results = self.engine.run(
                [
                    Request(
                        p, int(g), temperature=t, top_p=tp, top_k=tk,
                        deadline_s=dl, timeline=_timeline(),
                        trace_id=tid, snapshot=sn,
                        prefill_only=bool(po),
                        slo_class=slo_classes[i],
                        ticket_id=(
                            None if eff_tids is None else eff_tids[i]
                        ),
                        on_token=(
                            None if sink is None else sink.sink_for(i)
                        ),
                    )
                    for i, (p, g, t, tp, tk, dl, tid, sn, po) in enumerate(
                        zip(
                            prompts, gen_lens, temps, top_ps, top_ks,
                            deadlines, trace_ids, snapshots, prefill_only,
                        )
                    )
                ],
                results=True,
            )
            resp = {
                "outputs": [r.tokens.tolist() for r in results],
                # A migrated result carries its portable snapshot —
                # the caller (RemoteReplica) re-dispatches it; the
                # entry shape stays {status, reason} otherwise.
                "results": [
                    (
                        {"status": r.status, "reason": r.reason,
                         "snapshot": r.snapshot}
                        if r.snapshot is not None
                        else {"status": r.status, "reason": r.reason}
                    )
                    for r in results
                ],
                "stats": self.engine.last_stats,
            }
            # Wire-side SLO accounting belongs at the USER-facing hop:
            # internal fan-out payloads (a RemoteReplica batch carries
            # "fanout") skip it, or the fleet scrape would double-count
            # every request at the child AND the front.
            judge = not req.get("fanout")
            if sink is not None:
                # Late worker callbacks stop, tokens a resume skipped
                # back-fill, THEN the summary rides _respond.
                sink.finish(results)
                resp["frame"] = "summary"
                # Client ids echo VERBATIM (the non-streaming
                # contract); entries the client left null — and fully
                # absent lists — surface the server-ASSIGNED ids the
                # frames carried, so the summary always names every
                # request's cancellable identity.
                resp["ticket_ids"] = (
                    eff_tids if ticket_ids is None
                    else [t if t is not None else eff_tids[i]
                          for i, t in enumerate(ticket_ids)]
                )
                resp["wire"] = self._judge_wire(
                    sink.timelines, results, prompts, slo_classes,
                    observe=judge,
                )
            else:
                if judge:
                    # Non-streamed payloads still fold an e2e-only
                    # wire timeline into the SLO ledger (TTFT/TPOT
                    # need frames; see docs/observability.md).
                    tls = []
                    for p in prompts:
                        tl = Timeline()
                        tl.enqueue_t = enqueue_t
                        tl.stamp_enqueue()
                        tls.append(tl)
                    self._judge_wire(
                        tls, results, prompts, slo_classes, observe=True,
                    )
                if ticket_ids is not None:
                    resp["ticket_ids"] = ticket_ids
            if req.get("want_digest"):
                # Batch-boundary digest publication over the wire: the
                # RemoteReplica mirrors the in-process replica's
                # protocol (re-publish after every batch) without a
                # second round trip or an extra lock — the engine is
                # already quiesced here, under the same dispatch that
                # ran the batch.
                digest = getattr(self.engine, "prefix_digest", None)
                resp["prefix_digest"] = (
                    digest() if digest is not None else None
                )
            if req.get("want_tier_digest"):
                # Tier-digest piggyback (docs/scale-out.md "KV
                # fabric"): same batch-boundary publication protocol as
                # want_digest, one response field over — the remote
                # replica's router scores tier affinity from this.
                td = getattr(self.engine, "tier_digest", None)
                resp["tier_digest"] = td() if td is not None else None
            return resp
        if req.get("stream"):
            raise _BadRequest(
                "streaming needs a 'requests' payload (continuous "
                "batching); the fixed-batch input_ids path has no "
                "per-token emission (docs/serving.md 'Streaming & "
                "cancellation')"
            )
        input_ids = np.asarray(req["input_ids"], np.int32)
        gen_len = int(req.get("gen_len", 16))
        out = self.engine.serve(
            input_ids, gen_len, prompt_start=req.get("prompt_start")
        )
        if not req.get("fanout"):
            # Fixed-batch serves are judged too (e2e only, one per
            # batch row): without this, a workload driving only
            # input_ids payloads would record its SHEDS as missed but
            # never a met — goodput would read 0 on a healthy server.
            self._observe_synthetic(
                int(input_ids.shape[0]), req.get("slo_class"),
                enqueue_t, "ok", tokens_out=gen_len,
            )
        return {
            "output_ids": out.tolist(),
            "stats": self.engine.last_stats,
        }

    def _judge_wire(self, timelines, results, prompts, slo_classes,
                    *, observe: bool) -> list:
        """Finish each request's WIRE-side timeline, judge it against
        its SLO class, and (when ``observe``) fold it into the
        ``tdt_slo_*`` ledger. Returns the summary's per-request
        ``wire`` entries. Unknown classes resolve to the deployed
        ``default`` spec (bounded label cardinality)."""
        entries = []
        for i, r in enumerate(results):
            tl = timelines[i]
            if r.status == "migrated":
                # NON-terminal: the serving tier re-dispatches the
                # snapshot and the request is judged exactly once, at
                # its eventual completion — folding the export leg in
                # would record a spurious miss per healthy migration.
                entries.append({
                    "slo_class": slo_classes[i],
                    "outcome": "migrated",
                    "status": r.status,
                    "tokens_out": len(r.tokens),
                    "ttft_s": None, "tpot_s": None, "e2e_s": None,
                })
                continue
            tl.tokens_in = len(prompts[i])
            tl.tokens_out = len(r.tokens)
            tl.finish(r.status)
            spec = self.slo_specs.get(slo_classes[i])
            if spec is None:
                spec = self.slo_specs["default"]
            outcome = (
                obs_slo.observe_wire(tl, spec) if observe
                else obs_slo.judge(tl, spec)
            )
            entries.append({
                "slo_class": spec.name,
                "outcome": outcome,
                "status": r.status,
                "tokens_out": tl.tokens_out,
                "ttft_s": tl.ttft_s,
                "tpot_s": tl.tpot_s,
                "e2e_s": tl.e2e_s,
            })
        return entries

    def _serve_conn(self, conn: socket.socket) -> None:
        conn.settimeout(self.IDLE_TIMEOUT_S)
        try:
            with conn:
                self._serve_lines(conn)
        except Exception as e:  # noqa: BLE001 — a conn thread never dies loud
            # Connection-level failure: client vanished mid-request,
            # injected drop/recv fault, idle timeout. Catching broadly
            # keeps the contract that per-connection failures are
            # COUNTED (an injected FaultError is a RuntimeError, not an
            # OSError) — and the last failure is kept diagnosable in
            # the stats instead of vanishing into a bare counter. The
            # `with conn` above already closed the socket — the old
            # except-path conn.close() double-close could itself raise.
            with self._counters_lock:
                self._last_conn_error = f"{type(e).__name__}: {e}"
            self._count("conn_errors")

    def _serve_lines(self, conn: socket.socket) -> None:
        with conn.makefile("rwb") as f:
            while True:
                fault_point("server.recv")
                line = f.readline(self.MAX_LINE_BYTES + 1)
                if not line:
                    return  # client closed cleanly
                if len(line) > self.MAX_LINE_BYTES:
                    # Framing is lost beyond the bound (the line's tail
                    # is still in flight): answer, then drop the conn.
                    self._count("errors")
                    self._respond(f, self._error(
                        "bad_request",
                        f"request line exceeds {self.MAX_LINE_BYTES} "
                        "bytes; connection closed",
                    ))
                    # Drain the line's remainder before closing:
                    # unread bytes in the kernel queue turn close()
                    # into an RST, which makes the client discard the
                    # error response we just sent. The socket timeout
                    # is dropped to the drain budget too — the wall
                    # deadline alone only bounds the number of
                    # readline calls, not one call's duration, and a
                    # client dripping bytes could otherwise pin the
                    # thread (each drip resetting the 10 s idle
                    # timeout). A timeout here raises and is counted
                    # as a conn error, which a hostile client is.
                    conn.settimeout(self.drain_grace_s)
                    drain_deadline = time.monotonic() + self.drain_grace_s
                    while time.monotonic() < drain_deadline:
                        rest = f.readline(self.MAX_LINE_BYTES)
                        if not rest or rest.endswith(b"\n"):
                            break
                    return
                line = line.strip()
                if not line:
                    continue
                try:
                    payload = json.loads(line)
                except Exception as e:  # report, keep serving
                    self._count("errors")
                    self._respond(f, self._error(
                        "bad_request",
                        f"malformed JSON: {type(e).__name__}: {e}",
                    ))
                    continue
                self._respond(f, self._dispatch(payload, stream_f=f))
                if self._shutdown.is_set():
                    return

    def _respond(self, f, resp: dict) -> None:
        fault_point("server.send")
        f.write(json.dumps(resp).encode() + b"\n")
        f.flush()

    def serve_forever(self) -> None:
        """Accept loop; spawns one thread per connection and returns
        after a shutdown request has drained in-flight connections."""
        self._sock.settimeout(0.2)
        threads: list[threading.Thread] = []
        while not self._shutdown.is_set():
            try:
                conn, _ = self._sock.accept()
            except socket.timeout:
                threads = [t for t in threads if t.is_alive()]
                continue
            except OSError:
                break  # listener closed under us
            # Prune on EVERY accept, not just idle timeouts — under
            # continuous traffic the timeout branch never runs and the
            # list would grow one dead Thread per connection.
            threads = [t for t in threads if t.is_alive()]
            self._count("connections")
            t = threading.Thread(
                target=self._serve_conn, args=(conn,), daemon=True
            )
            t.start()
            threads.append(t)
        self._sock.close()
        # Graceful drain: in-flight payloads (generation included)
        # finish and answer; connection threads then exit on their own
        # (new generation payloads are refused with `shutting_down`).
        deadline = time.monotonic() + self.DRAIN_TIMEOUT_S
        for t in threads:
            t.join(max(deadline - time.monotonic(), 0.0))

    # -- lifecycle -------------------------------------------------------
    def start(self) -> "ModelServer":
        """Run the accept loop on a background thread (tests/demos)."""
        self._thread = threading.Thread(target=self.serve_forever, daemon=True)
        self._thread.start()
        return self

    def shutdown(self) -> None:
        self._shutdown.set()
        if self._thread is not None:
            # Cover serve_forever's full drain window: returning while
            # a connection thread is still inside engine.run() would
            # let callers (and the test-suite audit fixture) observe
            # the engine mid-mutation.
            self._thread.join(timeout=self.DRAIN_TIMEOUT_S + 5)
        # A Router engine owns replica worker threads: drain them too
        # (bounded by its drain_grace_s per replica) so a server
        # shutdown quiesces the whole tier, not just the socket.
        engine_shutdown = getattr(self.engine, "shutdown", None)
        if callable(engine_shutdown):
            engine_shutdown()


def _retry_backoff(attempt: int, backoff_s: float,
                   max_backoff_s: float) -> float:
    """One retry delay: exponential from ``backoff_s``, CAPPED at
    ``max_backoff_s``, with ±20% jitter. The cap keeps a long retry
    loop from sleeping for minutes once ``2**attempt`` runs away; the
    jitter keeps a fleet of clients that all bounced off the same
    respawning replica from re-arriving in lockstep and re-shedding
    each other forever (docs/scale-out.md "Process fleet")."""
    base = min(backoff_s * (2 ** attempt), max_backoff_s)
    return base * random.uniform(0.8, 1.2)


def request(
    host: str,
    port: int,
    payload: dict,
    timeout: float = 120.0,
    *,
    retries: int = 0,
    backoff_s: float = 0.25,
    max_backoff_s: float = 5.0,
) -> dict:
    """One JSON request/response round trip (client side).

    With ``retries > 0`` transient failures — connection refused/reset,
    the server vanishing mid-response, and structured ``overloaded``
    shedding — are retried with exponential backoff
    (``backoff_s * 2**attempt``, capped at ``max_backoff_s``, ±20%
    jitter — see :func:`_retry_backoff`). A shed reply carrying a
    ``retry_after_s`` hint overrides the local backoff for that
    attempt: the server knows its own queue depth, so router- or
    script-driven retries spread out instead of hammering a shedding
    replica in lockstep. Non-transient server errors raise
    ``RuntimeError`` immediately.
    """
    attempt = 0
    while True:
        try:
            with socket.create_connection((host, port), timeout=timeout) \
                    as s, s.makefile("rwb") as f:
                f.write(json.dumps(payload).encode() + b"\n")
                f.flush()
                line = f.readline()
            if not line:
                raise ConnectionError(
                    "server closed connection without a response"
                )
            resp = json.loads(line)
        except (ConnectionError, socket.timeout, TimeoutError, OSError,
                json.JSONDecodeError):
            # JSONDecodeError covers the server dying mid-response: a
            # truncated line is as transient as no line at all.
            if attempt >= retries:
                raise
            time.sleep(_retry_backoff(attempt, backoff_s, max_backoff_s))
            attempt += 1
            continue
        err = resp.get("error")
        if err is not None:
            status = err.get("status") if isinstance(err, dict) else None
            if status == "overloaded" and attempt < retries:
                hint = err.get("retry_after_s")
                # hint > 0 only (zero/absent/bogus must not collapse
                # the retry loop into back-to-back hammering), and
                # clamped: the client trusts ANY peer speaking the
                # protocol, and an arbitrary server value must not be
                # able to stall it for hours.
                if isinstance(hint, (int, float)) and hint > 0:
                    time.sleep(min(float(hint), 30.0))
                else:
                    time.sleep(
                        _retry_backoff(attempt, backoff_s, max_backoff_s)
                    )
                attempt += 1
                continue
            raise RuntimeError(f"server error: {err}")
        return resp


def request_stream(host: str, port: int, payload: dict,
                   timeout: float = 120.0):
    """Streaming client (docs/serving.md "Streaming & cancellation"):
    a generator over the wire frames of one ``requests`` payload —
    token frames as they arrive, then the summary frame, then it
    stops. ``"stream": true`` is added to the payload. A structured
    server error raises ``RuntimeError``; a connection that dies
    mid-stream raises ``ConnectionError`` (whatever frames already
    arrived were already yielded). To cancel mid-stream, send
    ``{"cmd": "cancel", "ticket_ids": [...]}`` on a SECOND connection
    using the tids the frames carry — or just close this one: the
    server detects the disconnect at its next frame write and cancels
    the payload's requests itself."""
    payload = dict(payload)
    payload["stream"] = True
    with socket.create_connection((host, port), timeout=timeout) as s, \
            s.makefile("rwb") as f:
        f.write(json.dumps(payload).encode() + b"\n")
        f.flush()
        while True:
            line = f.readline()
            if not line:
                raise ConnectionError("server closed mid-stream")
            obj = json.loads(line)
            if isinstance(obj, dict) and obj.get("error") is not None:
                raise RuntimeError(f"server error: {obj['error']}")
            yield obj
            if not (isinstance(obj, dict)
                    and obj.get("frame") == "token"):
                return
