"""One engine replica behind a worker thread (the scale-out unit).

The multi-engine serving tier (docs/scale-out.md) replicates the
continuous-batching engine N times behind a prefix-affinity router
(``serving/router.py``). This module is the replica half: ONE
:class:`~triton_distributed_tpu.models.continuous.ContinuousEngine`
owned by ONE worker thread, fed through a queue of :class:`Ticket`\\ s.
The engine itself is single-threaded by design (host-side slot/pool
bookkeeping); the replica boundary is what makes N of them safely
concurrent — no engine state is ever touched from outside its worker.

Lifecycle (one-way: replicas are cattle, not pets)::

    healthy ──drain()──▶ draining ──queue empties──▶ drained
       │
       └─ engine.run raises / injected ``replica.run`` fault /
          router-observed timeout ──▶ dead

A ``dead`` or ``draining`` replica refuses new tickets; whatever was
queued (and, on death, the in-flight batch) is handed to the router's
``on_failure`` callback for re-routing — requests are NEVER silently
dropped. The in-flight batch of a *timed-out* replica cannot be
aborted in-process; its late results latch harmlessly (a ticket keeps
its first result).

**Prefix view**: after every engine batch the worker re-publishes the
radix tree's :meth:`prefix_digest`, the router-side mirror affinity
routing scores against (``models/prefix_cache.py::digest_match_len``).
Publishing happens on the worker thread at batch boundaries, so the
router never reads live tree state across threads.
"""

from __future__ import annotations

import itertools
import os
import threading
import time

import numpy as np

from triton_distributed_tpu.models.continuous import Request, RequestResult
from triton_distributed_tpu.obs import events as obs_events
from triton_distributed_tpu.obs.timeline import Timeline
from triton_distributed_tpu.runtime.faults import fault_point
from triton_distributed_tpu.runtime.profiling import trace_span

HEALTHY = "healthy"
DRAINING = "draining"
DRAINED = "drained"
DEAD = "dead"

# Core serving counters accumulated per replica across every batch it
# ever ran — ONE definition; the router's fleet aggregation iterates
# the same tuple, so a new key can't silently read 0 fleet-wide (the
# models/stats.py::CORE_STATS_KEYS lesson, applied to the tier).
FLEET_TOTAL_KEYS = (
    "decode_steps", "prefill_tokens", "generated_tokens",
    "prefix_hit_tokens", "migrated_in_tokens",
)

# Process-unique ticket ids. They ride the wire (`ticket_ids` payload
# key, echoed by the server) so a RemoteReplica matches results to
# tickets BY ID, never by position — and a re-dispatched ticket keeps
# its id across hops, which is what makes the at-least-once recovery
# path dedup-safe: whichever attempt finishes first latches, the loser
# is recognized by id and discarded (docs/scale-out.md "Process
# fleet"). The pid suffix keeps ids unique even across routers talking
# to one shared replica.
_TICKET_IDS = itertools.count(1)


class Ticket:
    """One routed request and its latched outcome.

    A ticket is the routing-independent *description* of a request
    (prompt, gen_len, sampling knobs, deadline) — NOT an engine
    ``Request``. Each dispatch builds a FRESH ``Request`` via
    :meth:`make_request`, because a dead replica's Request object
    carries a failed status and partial tokens that must not leak into
    the retry. The result latches first-write-wins: a late completion
    from a timed-out replica's still-running batch cannot overwrite
    the re-routed attempt's outcome (or vice versa — whoever finishes
    first wins, which is the at-least-once contract re-routing buys).
    """

    __slots__ = ("prompt", "gen_len", "temperature", "top_p", "top_k",
                 "deadline_s", "enqueue_t", "reroutes", "replica_history",
                 "result", "_event", "_lock", "_rerouted_from",
                 "last_dispatch_t", "_prompt_list", "tid", "snapshot",
                 "prefill_only", "on_token", "client_tid", "slo_class")

    def __init__(self, prompt, gen_len: int, *, temperature=None,
                 top_p=None, top_k=None, deadline_s=None, enqueue_t=None,
                 slo_class=None):
        self.tid = f"t{next(_TICKET_IDS)}p{os.getpid()}"
        self.prompt = np.asarray(prompt, np.int32)
        self.gen_len = int(gen_len)
        self.temperature = temperature
        self.top_p = top_p
        self.top_k = top_k
        self.deadline_s = deadline_s
        self.enqueue_t = enqueue_t
        # Priority class (PR 13's ``slo_class``): rides the ticket so
        # the pool scheduler can order and shed by class, and every
        # dispatch (local or wire) rebuilds the Request with it — a
        # migrated hop is judged under the SAME class it arrived with.
        self.slo_class = slo_class
        self.reroutes = 0
        # Replica names in dispatch order. Appended by
        # EngineReplica.submit UNDER the replica's lock, atomically
        # with enqueue — so any ticket found in a replica's queue
        # already names that replica as its last hop.
        self.replica_history: list[str] = []
        self.result: RequestResult | None = None
        self._event = threading.Event()
        self._lock = threading.Lock()
        self._rerouted_from: str | None = None
        # When the CURRENT hop was dispatched (set by submit): the
        # router's timeout watches per-hop time, not total wait — a
        # ticket rerouted mid-wait gives its new replica a full budget.
        self.last_dispatch_t: float | None = None
        self._prompt_list: list[int] | None = None
        # Slot migration (docs/scale-out.md "Slot migration &
        # handoff"): a portable snapshot of this request's in-flight
        # state, attached by a handoff drain, a prefill→decode
        # migration, or the supervisor's crash recovery. The next
        # dispatch RESUMES from it instead of re-prefilling.
        # ``prefill_only`` asks the target engine to export right
        # after admission (the migrate_after_prefill policy's first
        # hop).
        self.snapshot: dict | None = None
        self.prefill_only: bool = False
        # Streaming sink (docs/serving.md "Streaming & cancellation"):
        # ``on_token(index, token_id)`` fires per emitted token — on
        # the replica worker thread for in-process replicas, on frame
        # receipt for RemoteReplicas. Re-dispatches re-fire earlier
        # indices (at-least-once); the server's stream sink dedups by
        # index, so the wire sees each token once.
        self.on_token = None
        # The CLIENT's id for this request (None when it gave none).
        # Kept ALONGSIDE the generated ``tid``, never instead of it:
        # everything wire-side (result latching, frames, the child's
        # duplicate-id refusal) keys by the process-unique ``tid``, so
        # two payloads reusing one client id can be co-batched without
        # conflating — while ``EngineReplica.cancel`` matches either,
        # so the id a client holds still cancels end-to-end.
        self.client_tid: str | None = None

    @property
    def prompt_tokens(self) -> list[int]:
        """The prompt as a plain int list, converted ONCE — affinity
        scoring walks it against every replica's digest per routing
        decision."""
        if self._prompt_list is None:
            self._prompt_list = [int(t) for t in self.prompt]
        return self._prompt_list

    @classmethod
    def of(cls, req) -> "Ticket":
        """Build from an engine :class:`Request` (the server's form) or
        a ``(prompt, gen_len)`` tuple. A request's ``ticket_id`` rides
        as ``client_tid`` NEXT TO the generated process-unique ``tid``
        — cancellation matches either (``EngineReplica.cancel``), but
        the wire keys by ``tid`` alone, so a client id reused across
        concurrent payloads can never conflate two requests in one
        child batch (or get a healthy child's duplicate-id refusal
        read as a replica death)."""
        if isinstance(req, Request):
            tl = req.timeline
            t = cls(
                req.prompt, req.gen_len, temperature=req.temperature,
                top_p=req.top_p, top_k=req.top_k, deadline_s=req.deadline_s,
                enqueue_t=tl.enqueue_t if tl is not None else None,
                slo_class=getattr(req, "slo_class", None),
            )
            if req.ticket_id is not None:
                t.client_tid = str(req.ticket_id)
            t.on_token = req.on_token
            return t
        prompt, gen_len = req
        return cls(prompt, gen_len)

    def make_request(self) -> Request:
        """A fresh engine Request for one dispatch attempt. The
        timeline keeps the ORIGINAL enqueue stamp (queue-wait measures
        what the client experienced, re-routes included) and carries
        the reroute count for ``tdt_request_reroutes_total``."""
        tl = Timeline()
        tl.enqueue_t = self.enqueue_t
        tl.stamp_enqueue()  # no-op when enqueue_t already set (latched)
        # A worker turns tickets into requests only as it starts their
        # batch: enqueue to here is the wait for the running batch to
        # end (``tdt_request_batch_wait_seconds``).
        tl.stamp_batch_start()
        tl.reroutes = self.reroutes
        return Request(
            self.prompt, self.gen_len, temperature=self.temperature,
            top_p=self.top_p, top_k=self.top_k, deadline_s=self.deadline_s,
            timeline=tl, snapshot=self.snapshot,
            prefill_only=self.prefill_only, ticket_id=self.tid,
            on_token=self.on_token, slo_class=self.slo_class,
        )

    def complete(self, result: RequestResult) -> bool:
        """Latch ``result``; True exactly once."""
        with self._lock:
            if self.result is not None:
                return False
            self.result = result
        self._event.set()
        return True

    def claim_reroute(self, source_name: str | None) -> bool:
        """Atomically claim the right to re-dispatch this ticket off
        ``source_name`` (its observed-failing replica). Exactly one
        claimant wins per hop: a latched result, a ticket already
        re-dispatched to a DIFFERENT replica, or a concurrent claim
        for the SAME hop (the timeout path racing the death callback)
        all lose — so a ticket can never be double-dispatched or
        guard-skipped into a silent hang. Increments ``reroutes`` on
        success."""
        with self._lock:
            if self.result is not None:
                return False
            if source_name is not None:
                if (self.replica_history
                        and self.replica_history[-1] != source_name):
                    return False  # already re-dispatched elsewhere
                if self._rerouted_from == source_name:
                    return False  # another thread claimed this hop
                self._rerouted_from = source_name
            self.reroutes += 1
            return True

    def wait(self, timeout: float | None = None) -> bool:
        return self._event.wait(timeout)

    def expired_hop(self, timeout_s: float) -> str | None:
        """Atomically judge the CURRENT hop: the replica name iff that
        replica has held this ticket longer than ``timeout_s``, else
        None. Name and stamp are read under the ticket lock (and
        written under it by ``submit``/batch start), so a reroute
        racing the expiry can never get the ticket's NEW healthy
        replica killed for the old hop's stale stamp."""
        with self._lock:
            if self.result is not None or not self.replica_history:
                return None
            t0 = self.last_dispatch_t
            if t0 is None or time.monotonic() < t0 + timeout_s:
                return None
            return self.replica_history[-1]


class EngineReplica:
    """One ContinuousEngine + its worker thread, with health state.

    The router talks to a replica ONLY through :meth:`submit`,
    :meth:`snapshot`, :meth:`match_len`, :meth:`drain`, and
    :meth:`mark_unhealthy` — the engine never escapes its worker
    thread. ``max_pending`` is the shed-aware routing bound: a replica
    whose queued+in-flight tickets reach it reports ``overloaded`` and
    the router skips it before the request would bounce off the
    engine's own admission shed (docs/scale-out.md).
    """

    # One engine batch admits at most this many tickets; the engine's
    # own admission loop interleaves them onto its decode slots.
    MAX_RUN_BATCH = 64

    def __init__(self, engine, name: str | None = None, *,
                 max_pending: int = 8, role: str = "mixed"):
        if not hasattr(engine, "run"):
            raise ValueError(
                "EngineReplica wraps a ContinuousEngine (needs .run); "
                f"got {type(engine).__name__}"
            )
        if role not in ("prefill", "decode", "mixed"):
            raise ValueError(
                f"role must be 'prefill', 'decode', or 'mixed', "
                f"got {role!r}"
            )
        self.engine = engine
        self.name = name if name is not None else f"replica-{id(engine):x}"
        # Pool role (docs/scale-out.md "Disaggregated pools &
        # autoscaling"): router-side placement metadata — the engine
        # behind a prefill replica is identical to a decode one, so
        # degraded fallback (serving end-to-end on either) stays legal.
        self.role = role
        self.max_pending = int(max_pending)
        self._cond = threading.Condition()
        self._queue: list[Ticket] = []
        self._current_batch: list[Ticket] = []
        self._state = HEALTHY
        self._inflight = 0
        self.last_error: str | None = None
        self.runs = 0          # engine batches completed
        self.served = 0        # tickets completed (any status)
        # Cumulative core serving counters across every batch this
        # replica ran (the engine zeroes its own stats per run; the
        # router's fleet-wide ``last_stats`` needs monotone numbers).
        self.totals = {k: 0 for k in FLEET_TOTAL_KEYS}
        # Router-installed failure callback: (replica, orphan_tickets).
        self.on_failure = None
        # Router-installed migration callback: (replica, tickets whose
        # batch exported them). Falls back to on_failure when unset —
        # both re-dispatch through the latch-first ticket machinery.
        self.on_migrate = None
        self._handoff = False
        self._digest_lock = threading.Lock()
        self._prefix_digest = None
        self._tier_digest = None
        self._digest_version: int | None = None
        self._publish_digest()
        self._thread = threading.Thread(
            target=self._worker, daemon=True, name=f"replica:{self.name}"
        )
        self._thread.start()

    # -- router-facing surface --------------------------------------------

    @property
    def state(self) -> str:
        return self._state

    @property
    def pending(self) -> int:
        """Tickets queued or in flight — the load signal balancing
        uses (the same number the engine mirrors into the PR 5
        pending/free-pages gauges, read replica-side)."""
        with self._cond:
            return len(self._queue) + self._inflight

    def _over(self, pending: int) -> bool:
        """ONE definition of the shed threshold — the routing property
        and stats snapshots must never disagree on it."""
        return pending >= self.max_pending

    @property
    def overloaded(self) -> bool:
        return self._over(self.pending)

    @property
    def free_pages(self) -> int:
        # Host-side list length: racy-but-benign as a load signal (the
        # worker mutates the free list mid-run); exact accounting
        # lives in the engine's own audit.
        return len(self.engine.pool.free)

    def submit(self, ticket: Ticket) -> bool:
        """Queue one ticket; False when the replica is not accepting
        work (the router picks another). The history append rides the
        SAME lock as the enqueue: a death that harvests this queue an
        instant later must see the ticket already naming this replica
        as its last hop, or the re-route claim would misread it as
        dispatched elsewhere and strand it."""
        with self._cond:
            if self._state != HEALTHY:
                return False
            with ticket._lock:  # atomic vs Ticket.expired_hop
                ticket.replica_history.append(self.name)
                ticket.last_dispatch_t = time.monotonic()
            self._queue.append(ticket)
            self._cond.notify_all()
        return True

    def match_len(self, tokens) -> int:
        """Affinity score: longest cached prefix of ``tokens`` in this
        replica's last published digest, in tokens."""
        from triton_distributed_tpu.models.prefix_cache import (
            digest_match_len,
        )

        with self._digest_lock:
            digest = self._prefix_digest
        return digest_match_len(digest, tokens)

    def tier_match_len(self, tokens) -> int:
        """Tier-affinity score (docs/scale-out.md "KV fabric"):
        longest whole-page prefix of ``tokens`` resident in this
        replica's last published TIER digest — pages the engine would
        fault back from its tier instead of re-prefilling. 0 without a
        tier."""
        from triton_distributed_tpu.models.kv_tier import (
            tier_digest_match_len,
        )

        with self._digest_lock:
            digest = self._tier_digest
        return tier_digest_match_len(digest, tokens)

    def snapshot(self) -> dict:
        with self._cond:
            queued = len(self._queue)
            inflight = self._inflight
            state = self._state
        return {
            "name": self.name,
            "state": state,
            "role": self.role,
            "pending": queued + inflight,
            "inflight": inflight,
            "free_pages": self.free_pages,
            "overloaded": self._over(queued + inflight),
            "runs": self.runs,
            "served": self.served,
            "last_error": self.last_error,
        }

    def cancel(self, ticket_ids) -> int:
        """Client-driven cancellation (docs/serving.md "Streaming &
        cancellation"). Ids match a ticket's unique ``tid`` OR its
        ``client_tid``: queued matches complete immediately with
        status ``cancelled`` (removed before the worker can run
        them); IN-FLIGHT matches forward their UNIQUE tids to the
        engine's own ``cancel`` (over the wire for a RemoteReplica) —
        the engine only ever sees tids it was dispatched, so a
        client id reused across payloads cancels every carrier
        without spraying foreign ids. Returns how many QUEUED tickets
        were cancelled here — in-flight cancels surface through their
        tickets' eventual ``cancelled`` results."""
        ids = {str(t) for t in ticket_ids}
        if not ids:
            return 0

        def hit(t: Ticket) -> bool:
            return t.tid in ids or (t.client_tid is not None
                                    and t.client_tid in ids)

        with self._cond:
            queued = [t for t in self._queue if hit(t)]
            if queued:
                self._queue = [t for t in self._queue if not hit(t)]
            inflight = [t.tid for t in self._current_batch if hit(t)]
        n = 0
        for t in queued:
            if t.complete(RequestResult(
                np.zeros(0, np.int32), "cancelled",
                "cancelled by client before dispatch",
            )):
                n += 1
        canceller = getattr(self.engine, "cancel", None)
        if inflight and canceller is not None:
            try:
                canceller(sorted(inflight))
            except Exception:  # noqa: BLE001 — remote best-effort
                pass
        return n

    # -- lifecycle ---------------------------------------------------------

    def begin_drain(self, handoff: bool = False) -> None:
        """Flip to DRAINING without waiting (the router flips the whole
        fleet first, then waits everyone against one shared deadline —
        sequential full drains would cost N × grace).

        ``handoff=True`` is the LOSSLESS drain (docs/scale-out.md
        "Slot migration & handoff"): instead of finishing queued and
        in-flight work here, the engine exports every unfinished slot
        at its next round boundary and the queue hands back un-run —
        the router re-admits everything elsewhere with the existing
        latch-first ticket dedup, so a rolling restart loses zero
        tokens of generated work."""
        with self._cond:
            if self._state == HEALTHY:
                self._state = DRAINING
                self._handoff = bool(handoff)
                self._cond.notify_all()
            elif self._state == DRAINING and handoff:
                self._handoff = True
                self._cond.notify_all()
            else:
                return
        if handoff:
            rh = getattr(self.engine, "request_handoff", None)
            if rh is not None:
                rh()

    def drain(self, grace_s: float | None = None) -> bool:
        """PR 3-style graceful drain: refuse new work, let queued and
        in-flight tickets finish; the WORKER then flushes the radix
        tree back to the pool before marking itself drained (it owns
        the engine — and a grace that expires mid-batch only makes
        this call return False early, the flush still happens when the
        batch ends). Returns True when the replica is QUIESCED within
        ``grace_s`` (None waits indefinitely) — drained cleanly OR
        already dead; check ``.state`` to tell a crash from a clean
        drain before e.g. decommissioning a node on the result."""
        self.begin_drain()
        with self._cond:
            deadline = (
                None if grace_s is None else time.monotonic() + grace_s
            )
            while self._state == DRAINING:
                if deadline is None:
                    self._cond.wait(0.1)
                else:
                    left = deadline - time.monotonic()
                    if left <= 0:
                        break
                    self._cond.wait(left)
            complete = self._state in (DRAINED, DEAD)
        if complete:
            self._thread.join(timeout=5.0)
        else:
            obs_events.emit(
                "replica_drain", replica=self.name, complete=False,
                pages_released=0,
            )
        return complete

    def mark_unhealthy(self, reason: str) -> list[Ticket]:
        """Take the replica out of rotation NOW (router-observed
        timeout, operator action): refuses new work and returns every
        affected ticket — the not-yet-started queue AND the in-flight
        batch — for re-routing. The in-flight batch itself cannot be
        aborted in-process; its late results latch harmlessly against
        the re-routed attempts."""
        return self._take_dead(reason)

    def join(self, timeout: float | None = None) -> None:
        """Wait for the worker thread to exit (call after drain/death;
        a healthy replica's worker never exits)."""
        self._thread.join(timeout)

    # -- worker ------------------------------------------------------------

    def _worker(self) -> None:
        while True:
            handoff_orphans: list[Ticket] = []
            with self._cond:
                while not self._queue and self._state == HEALTHY:
                    # Names the idle device's largest gaps: the worker
                    # has nothing to run (ten spans an idle second, so
                    # none goes to the event ring).
                    with trace_span("scheduler:wait_for_work",
                                    _ring=False):
                        self._cond.wait(0.1)
                if self._state == DRAINING and self._handoff:
                    # Lossless drain: NOTHING queued runs here — the
                    # queue hands back for re-dispatch (the in-flight
                    # batch, if any, already returned with its slots
                    # exported before the worker got back here).
                    handoff_orphans = self._queue
                    self._queue = []
                    batch = None
                elif self._queue and self._state in (HEALTHY, DRAINING):
                    batch = self._queue[: self.MAX_RUN_BATCH]
                    del self._queue[: self.MAX_RUN_BATCH]
                    self._inflight = len(batch)
                    # Visible to _take_dead: a death harvests the
                    # in-flight batch too, so the router re-routes it
                    # immediately instead of each ticket serially
                    # paying its own timeout.
                    self._current_batch = batch
                    # Re-arm hop timers at BATCH START — for the batch
                    # AND for everything still queued: time spent
                    # behind earlier (healthy, long) batches is not
                    # hang evidence; a batch boundary is proof the
                    # replica is making progress, so queued tickets
                    # must not accrue it toward the router's timeout
                    # either (a deep queue would otherwise read as a
                    # hang and cascade kills under overload). Sizing
                    # rule for operators: request_timeout_s must still
                    # exceed one legal batch (docs/scale-out.md).
                    now = time.monotonic()
                    for t in batch + self._queue:
                        with t._lock:  # atomic vs Ticket.expired_hop
                            t.last_dispatch_t = now
                elif self._state == DRAINING:
                    batch = None  # drain finalization, outside the lock
                else:
                    return
            if handoff_orphans:
                # Re-dispatch OUTSIDE the lock (the router's dispatch
                # takes other replicas' locks).
                self._migrate_tickets(handoff_orphans)
            if batch is None:
                # The worker owns the engine: flush the radix tree back
                # to the pool and publish the (now empty) digest BEFORE
                # announcing drained, so a caller that saw DRAINED can
                # rely on the pages being home.
                released = (
                    self.engine.drain()
                    if hasattr(self.engine, "drain") else 0
                )
                self._publish_digest()
                with self._cond:
                    if self._state == DRAINING:  # a racing kill wins
                        self._state = DRAINED
                    final = self._state
                    self._cond.notify_all()
                # Report the state that actually stuck: a kill racing
                # the finalization must not leave BOTH a replica_dead
                # and a complete=True drain in the ring.
                obs_events.emit(
                    "replica_drain", replica=self.name,
                    complete=final == DRAINED, pages_released=released,
                )
                return
            try:
                self._run_batch(batch)
            except Exception as e:  # noqa: BLE001 — tickets must never hang
                # _run_batch already isolates engine.run failures; this
                # catches anything outside that try (request
                # construction, stats accounting) so a worker bug can
                # never strand tickets with no result.
                self._die(f"{type(e).__name__}: {e}")
            with self._cond:
                self._inflight = 0
                self._current_batch = []
                self._cond.notify_all()
                if self._state == DEAD:
                    return

    def _run_batch(self, tickets: list[Ticket]) -> None:
        reqs = [t.make_request() for t in tickets]
        try:
            # The replica-kill/hang seam (docs/scale-out.md): BEFORE
            # the engine runs, so a killed batch re-routes wholesale
            # with nothing half-admitted.
            fault_point("replica.run", replica=self.name, batch=len(reqs))
            oldest = min(r.timeline.enqueue_t for r in reqs)
            with trace_span(
                "scheduler:batch", n=len(reqs),
                oldest_wait_ms=int((time.monotonic() - oldest) * 1e3),
            ):
                results = self.engine.run(reqs, results=True)
        except Exception as e:  # noqa: BLE001 — replica isolation boundary
            self._die(f"{type(e).__name__}: {e}")
            return
        if self._state == DEAD:
            # A late batch on a replica the router already timed out:
            # still try to latch results (if the re-routed attempt
            # hasn't won, delivering beats discarding), but fold
            # NOTHING into the fleet accounting — a duplicate batch
            # must not double-count runs/served/totals or refresh a
            # digest nothing routes to. (The engine-side timeline of a
            # duplicate still observes; that is the documented
            # at-least-once telemetry cost of timeout re-routing.)
            # Migrated results stay unlatched either way — the router
            # already re-routed the ticket when it marked us dead.
            for t, r in zip(tickets, results):
                if r.status != "migrated":
                    t.complete(r)
            return
        self.runs += 1
        st = self.engine.last_stats
        for k in self.totals:
            self.totals[k] += st.get(k, 0)
        migrated: list[Ticket] = []
        done = 0
        for t, r in zip(tickets, results):
            if r.status == "migrated":
                # The slot was exported, not finished: carry the
                # snapshot (None for a request that never admitted —
                # it keeps any snapshot it already had) and hand the
                # ticket back for re-dispatch. NEVER latched here, so
                # the eventual completion elsewhere is the one and
                # only emission. ``prefill_only`` is left as-is: the
                # router reads it to classify the migration, then
                # clears it before dispatching the decode hop.
                if r.snapshot is not None:
                    t.snapshot = r.snapshot
                migrated.append(t)
                continue
            done += 1
            t.complete(r)
        self.served += done
        self._publish_digest()
        if migrated:
            self._migrate_tickets(migrated)

    def _migrate_tickets(self, tickets: list[Ticket]) -> None:
        """Hand exported tickets to the router for re-dispatch (the
        latch-first machinery dedups exactly as for failures). With no
        router attached (unit tests), fail them in place — never a
        silent drop."""
        cb = self.on_migrate or self.on_failure
        if cb is not None:
            cb(self, tickets)
            return
        for t in tickets:
            t.complete(RequestResult(
                np.zeros(0, np.int32), "failed",
                f"replica {self.name} exported a slot with no router "
                "attached to resume it",
            ))

    def _publish_digest(self) -> None:
        """Re-snapshot the radix population for the router — but only
        when the tree actually CHANGED shape: re-serializing a large
        warm cache after every decode-only batch would pay O(cached
        tokens) on the worker's hot path for an identical digest.
        Inserted+evicted page counts version every shape mutation
        (in-place tail upgrades count as insertions; dedupes/COW touch
        no chain)."""
        # Tier digest rides every publish: the store memoizes it on
        # its own mutation counter, so an unchanged tier costs a dict
        # ref — no scan — and a spill/adoption between radix versions
        # still lands (docs/scale-out.md "KV fabric").
        td = getattr(self.engine, "tier_digest", None)
        tier_digest = td() if td is not None else None
        prefix = getattr(self.engine, "prefix", None)
        if prefix is not None:
            version = (
                prefix.stats["inserted_pages"]
                + prefix.stats["evicted_pages"]
            )
            if version == self._digest_version:
                with self._digest_lock:
                    self._tier_digest = tier_digest
                return
            self._digest_version = version
        digest = (
            self.engine.prefix_digest()
            if hasattr(self.engine, "prefix_digest") else None
        )
        with self._digest_lock:
            self._prefix_digest = digest
            self._tier_digest = tier_digest

    # -- death -------------------------------------------------------------

    def _take_dead(self, reason: str) -> list[Ticket]:
        """Mark dead and harvest every affected ticket: the untouched
        queue AND the in-flight batch (late results on completed
        in-flight tickets latch-lose against the re-route; the atomic
        per-hop claim makes the overlap safe)."""
        with self._cond:
            already = self._state == DEAD
            self._state = DEAD
            if not already:
                self.last_error = str(reason)
            orphans = self._current_batch + self._queue
            self._current_batch = []
            self._queue = []
            self._cond.notify_all()
        if not already:
            obs_events.emit(
                "replica_dead", replica=self.name,
                reason=str(reason)[:200], orphaned=len(orphans),
            )
        return orphans

    def _die(self, reason: str) -> None:
        """The engine loop raised out of ``run`` (its own teardown
        already released pages/pins — the audit stays clean): mark
        dead and hand EVERY affected ticket (the failed in-flight
        batch plus the untouched queue, both harvested by
        ``_take_dead``) to the router for re-routing."""
        orphans = self._take_dead(reason)
        cb = self.on_failure
        if cb is not None:
            cb(self, orphans)
            return
        # No router attached (unit tests): fail tickets in place —
        # never silently dropped.
        for t in orphans:
            t.complete(RequestResult(
                np.zeros(0, np.int32), "failed",
                f"replica {self.name} died: {reason}",
            ))
