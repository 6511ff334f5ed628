"""Deterministic fault injection for the serving stack.

Triton-distributed ships its overlap kernels with correctness
scaffolding because async resource-sharing bugs are silent until they
corrupt outputs (arXiv:2504.19442); the same discipline applies to the
shared-page serving loop: a refcount leak after a mid-batch failure is
invisible until the pool wedges under load. This module makes those
failures *reproducible*: a seeded :class:`FaultPlan` arms named seams in
the engine/pool/server code, and the chaos suite (``tests/test_faults.py``)
proves every injected fault leaves the engine serviceable and the
pool/radix audit clean.

Seams currently instrumented (grep for ``fault_point``/``mutate_point``):

=================  =====================================================
``pool.allocate``  ``PagePool.allocate`` — pool-exhaustion faults
``engine.admit``   ``ContinuousEngine._admit`` — prefill-time failures
``engine.decode``  ``ContinuousEngine._decode_once`` — decode-step
                   exceptions (attributable via ``slot=``)
``engine.logits``  decode logits mutation hook — NaN/Inf injection
``engine.mega_drain``  ``ContinuousEngine._drain_launch`` — a mega
                   drain that raises mid-resident-round (proves the
                   just-issued next launch is parked in ``_pend`` for
                   the guard's ``_abort_pend``, never orphaned)
``spec.verify``    ``speculative.spec_verify_slot`` — verify failures
``server.recv``    ``ModelServer._serve_lines`` read side — socket
                   drops / slow clients (``delay=``)
``server.send``    ``ModelServer._serve_lines`` write side
``stream.send``    one streaming token frame's bytes (mutate-style,
                   the wire-seam pattern: drop via a raising rule —
                   the server reads it as a client disconnect and
                   CANCELS the payload's requests — garble via
                   corruption the client's JSON parse catches)
``engine.cancel``  ``ContinuousEngine._apply_cancels`` — between the
                   pending-cancel snapshot and its application, so a
                   cancel can be raced deterministically against a
                   slot's natural finish (``delay=``)
``replica.run``    ``EngineReplica._run_batch`` — replica-kill /
                   replica-hang for the multi-engine router tier
                   (``replica=`` narrows to one replica by name)
``wire.connect``   ``serving/remote.py`` client connect — refused /
                   partitioned replica processes (raise-style)
``wire.send``      remote batch payload bytes (mutate-style: drop via
                   a raising rule, garble via corruption)
``wire.recv``      remote response line bytes (mutate-style, same
                   drop/garble rules as ``wire.send``)
``proc.kill``      the replica child's pid, offered mid-batch — a
                   ``kill_proc`` rule SIGKILLs the process while its
                   batch is in flight (``serving/supervisor.py``)
``proc.hang``      same offer point — a ``hang_proc`` rule SIGSTOPs
                   the child so heartbeats wedge without the process
                   exiting (resume with ``os.kill(pid, SIGCONT)``)
``migrate.export`` ``models/slot_state.py::export_slot`` — a slot
                   export dies before any state is read (the slot
                   keeps decoding; handoff retries or finishes local)
``migrate.import`` ``models/slot_state.py::import_slot`` — a snapshot
                   import dies before pages are claimed (the engine
                   falls back to replay-from-prompt)
``tier.put``       ``models/kv_tier.py::PageStore.put`` — mutate-style
                   (one hit counter, wire-seam pattern): a spill /
                   snapshot persist refuses (raising mutate: the entry
                   is simply not stored, the page drops as pre-tier),
                   stalls, or is corrupted in flight (the checksum
                   catches it at the next ``get``)
``tier.get``       ``PageStore.get`` — mutate-style: a fault-back read
                   refuses (treated as a transient miss, the request
                   re-prefills/replays), stalls, or is corrupted (the
                   integrity check drops the entry and degrades —
                   wrong bits can never come out)
``fabric.probe``   ``kv_tier.FabricClient`` peer probe — mutate-style:
                   a dead/refusing peer (raising mutate) cools down
                   and the fetch falls through to the local-miss path;
                   a stall trips the fetch deadline (``peer=`` narrows
                   to one peer by name)
``fabric.get``     the pulled entry's wire bytes — mutate-style: a
                   garbled remote entry CRC-drops to re-prefill
                   exactly like a corrupt local one (the PR 12 codec
                   is the transport); a stall past the pull deadline
                   discards even valid late bytes
``launcher.spawn`` ``serving/launcher.py`` — offered (``replica=``,
                   ``host=``) before any spawn work; an armed rule
                   surfaces as ``SpawnError``, driving the
                   supervisor's spawn-FAILOVER path
                   (``refuse_spawn``)
``host.down``      the replica's host TAG, offered mid-batch next to
                   ``proc.kill`` — a ``kill_host``/``hang_host`` rule
                   takes the WHOLE fake host down while a batch is in
                   flight (``host=`` narrows; the mutate closure
                   holds the ``FakeHostLauncher`` that owns the
                   process groups)
=================  =====================================================

The ``wire.*``/``proc.*`` seams live on the *router-process* side of
the socket (``RemoteReplica``'s send/recv path): a ``FaultPlan`` is
process-global, so arming the parent is what makes cross-process chaos
deterministic — the child never needs a plan.

Usage::

    plan = (FaultPlan(seed=7)
            .exhaust_pool(at=2)          # 2nd allocation raises
            .nan_logits(at=3, slot=1))   # 3rd decode step: slot 1 NaN
    with plan:
        results = engine.run(reqs, results=True)
    assert plan.fired  # every firing is logged for assertions

A plan is deterministic by construction: rules fire on exact per-seam
hit counts (``at``/``every``) or on a coin drawn from the plan's own
seeded RNG (``prob``) — same seed, same call order, same faults. When
no plan is active every seam is a single ``is None`` check.
"""

from __future__ import annotations

import dataclasses
import random
import threading
import time
from collections import Counter
from typing import Any, Callable

from triton_distributed_tpu.obs import events as obs_events


class FaultError(RuntimeError):
    """An injected fault. ``seam`` names the injection point; ``slot``
    (when not None) attributes the fault to one engine slot, so the
    engine's per-request isolation evicts exactly that request instead
    of failing the whole batch."""

    def __init__(self, seam: str, note: str = "injected fault",
                 slot: int | None = None):
        where = f"{note} at seam '{seam}'"
        if slot is not None:
            where += f" (slot {slot})"
        super().__init__(where)
        self.seam = seam
        self.slot = slot


@dataclasses.dataclass
class FaultRule:
    """One arming of one seam. Fires when the seam's hit count is in
    ``at``, or divides ``every``, or the seeded coin lands under
    ``prob`` — at most ``times`` total — and then raises ``exc`` (a
    :class:`FaultError` by default), sleeps ``delay`` seconds, or runs
    ``mutate(value, ctx)`` over the seam's value (mutation seams
    only). ``match`` keys must equal the seam's context kwargs."""

    seam: str
    at: tuple[int, ...] = ()
    every: int = 0
    prob: float = 0.0
    times: int = 1
    slot: int | None = None
    exc: BaseException | None = None
    mutate: Callable[[Any, dict], Any] | None = None
    delay: float = 0.0
    match: dict = dataclasses.field(default_factory=dict)
    fired: int = 0


def _event_fields(ctx: dict, seam: str, hit: int) -> dict:
    """Fault-event fields from an arbitrary seam ctx: the event's own
    keys always win; colliding ctx keys survive under a ``ctx_``
    prefix (see :func:`obs.events.safe_fields`) instead of
    TypeError-ing out of an injection site or being dropped."""
    fields = obs_events.safe_fields(ctx, reserved=("seam", "hit"))
    fields["seam"] = seam
    fields["hit"] = hit
    return fields


class FaultPlan:
    """A seeded, self-logging set of :class:`FaultRule`\\ s.

    Activate with ``with plan:`` — activation is process-global (the
    server thread must see the same plan as the test thread), guarded
    against nesting. ``plan.fired`` records ``(seam, hit, ctx)`` for
    every firing so tests can assert the plan actually exercised its
    seams."""

    def __init__(self, seed: int = 0):
        self.rng = random.Random(seed)
        self.rules: list[FaultRule] = []
        self.hits: Counter = Counter()
        self.fired: list[tuple[str, int, dict]] = []
        # Seams fire from multiple threads (the server is
        # thread-per-connection): hit counting and rule bookkeeping
        # must be atomic or times=1 rules double-fire under races.
        self._lock = threading.Lock()

    # -- arming ----------------------------------------------------------

    def on(
        self,
        seam: str,
        *,
        at: int | tuple[int, ...] | None = None,
        every: int = 0,
        prob: float = 0.0,
        times: int = 1,
        slot: int | None = None,
        exc: BaseException | None = None,
        mutate: Callable[[Any, dict], Any] | None = None,
        delay: float = 0.0,
        **match,
    ) -> "FaultPlan":
        """Arm ``seam``; returns ``self`` for chaining."""
        ats = () if at is None else (
            (int(at),) if isinstance(at, int) else tuple(int(a) for a in at)
        )
        if not ats and not every and prob <= 0.0:
            ats = (1,)  # default: fire on the first hit
        self.rules.append(FaultRule(
            seam=seam, at=ats, every=int(every), prob=float(prob),
            times=int(times), slot=slot, exc=exc, mutate=mutate,
            delay=float(delay), match=dict(match),
        ))
        return self

    # Named-seam conveniences (the chaos suite reads as a fault menu).

    def exhaust_pool(self, at: int = 1, times: int = 1) -> "FaultPlan":
        """Nth ``PagePool.allocate`` raises as if the pool were empty."""
        return self.on("pool.allocate", at=at, times=times,
                       exc=RuntimeError("page pool exhausted (injected)"))

    def admit_exc(self, at: int = 1, times: int = 1) -> "FaultPlan":
        """Nth admission prefill raises."""
        return self.on("engine.admit", at=at, times=times)

    def decode_exc(self, at: int = 1, slot: int | None = None,
                   times: int = 1) -> "FaultPlan":
        """Nth decode step raises; ``slot`` attributes the fault so
        only that request fails (None → the whole step is poisoned)."""
        return self.on("engine.decode", at=at, slot=slot, times=times)

    def nan_logits(self, at: int = 1, slot: int = 0,
                   times: int = 1) -> "FaultPlan":
        """Nth decode step's logits for ``slot`` become NaN."""

        def _nanify(value, _ctx):
            import jax.numpy as jnp
            import numpy as np

            arr = np.array(value, np.float32)
            arr[slot] = np.nan
            return jnp.asarray(arr)

        return self.on("engine.logits", at=at, times=times, mutate=_nanify)

    def verify_exc(self, at: int = 1, times: int = 1) -> "FaultPlan":
        """Nth speculative verify raises (attributed to its slot by the
        seam's own context)."""
        return self.on("spec.verify", at=at, times=times)

    def drop_connection(self, at: int = 1, times: int = 1) -> "FaultPlan":
        """Nth server response write raises mid-stream (client vanishes
        between request and response)."""
        return self.on("server.send", at=at, times=times,
                       exc=BrokenPipeError("connection dropped (injected)"))

    def slow_client(self, delay: float, at: int = 1,
                    times: int = 1) -> "FaultPlan":
        """Nth server read stalls ``delay`` seconds before proceeding."""
        return self.on("server.recv", at=at, times=times, delay=delay)

    def drop_stream(self, at: int = 1, times: int = 1,
                    **match) -> "FaultPlan":
        """The Nth streaming token-frame write raises as if the client
        vanished mid-stream: the server's stream sink marks itself
        broken and CANCELS the payload's requests — slots torn down,
        pages freed, survivors untouched (docs/serving.md 'Streaming &
        cancellation'). Narrow with ``tid=``."""

        def _raise(_value, _ctx):
            raise BrokenPipeError("stream client vanished (injected)")

        return self.on("stream.send", at=at, times=times, mutate=_raise,
                       **match)

    def garble_stream(self, at: int = 1, times: int = 1,
                      **match) -> "FaultPlan":
        """The Nth streaming frame's bytes are reversed in flight
        (valid JSON never survives it): the CLIENT's frame parse fails
        mid-stream — exercising the consumer-side protocol-error path
        while the server keeps serving."""

        def _garble(value, _ctx):
            return bytes(reversed(bytes(value)))

        return self.on("stream.send", at=at, times=times, mutate=_garble,
                       **match)

    def slow_cancel(self, delay: float, at: int = 1,
                    times: int = 1) -> "FaultPlan":
        """The Nth cancel application stalls ``delay`` seconds between
        snapshotting the pending ids and applying them — the
        deterministic handle on the cancel-vs-natural-finish race
        (whichever side the test wants to win, it sequences here)."""
        return self.on("engine.cancel", at=at, times=times, delay=delay)

    def kill_replica(self, replica: str | None = None, at: int = 0,
                     times: int = 1) -> "FaultPlan":
        """A router-tier replica's batch run raises as if its engine
        thread crashed. ``replica`` (the replica's name) narrows the
        seam to one replica and fires on its FIRST matching run;
        ``at`` instead fires on the Nth ``replica.run`` hit across all
        replicas (hit counts are per-seam, not per-replica)."""
        match = {} if replica is None else {"replica": replica}
        if at:
            return self.on("replica.run", at=at, times=times, **match)
        return self.on("replica.run", every=1, times=times, **match)

    def hang_replica(self, delay: float, replica: str | None = None,
                     times: int = 1) -> "FaultPlan":
        """A replica's batch run stalls ``delay`` seconds before
        touching its engine — the router-observed-timeout scenario
        (the router marks it unhealthy and re-routes; the late run's
        results latch harmlessly)."""
        match = {} if replica is None else {"replica": replica}
        return self.on("replica.run", every=1, times=times, delay=delay,
                       **match)

    def fail_export(self, at: int = 1, times: int = 1) -> "FaultPlan":
        """Nth slot export raises mid-migration (the source end of a
        handoff dies): the request keeps decoding locally — a handoff
        drain stays lossless, just slower (docs/scale-out.md 'Slot
        migration & handoff'). ``at=0`` fires on EVERY export (up to
        ``times``) — the export path is retried at round boundaries,
        so killing one attempt only delays the handoff."""
        kw = {"at": at} if at else {"every": 1}
        return self.on("migrate.export", times=times, **kw)

    # Tier seams (docs/serving.md "Tiered KV"). Like the wire seams,
    # refuse/corrupt/slow all ride ONE mutate-style seam per direction
    # (``tier.put``/``tier.get``), so they share a single deterministic
    # hit counter: refuse is a raising mutate, slow a sleeping one.

    def refuse_tier(self, op: str = "put", at: int = 0,
                    times: int = 1, **match) -> "FaultPlan":
        """The Nth ``tier.put``/``tier.get`` refuses: a refused put
        drops the spill exactly like the pre-tier eviction, a refused
        get reads as a transient miss (the entry survives) — both
        degrade to re-prefill/replay, never corrupt. ``at=0`` fires on
        every matching hit up to ``times``; narrow with
        ``kind=``/``key=``."""
        if op not in ("put", "get"):
            raise ValueError(f"op must be 'put' or 'get', got {op!r}")

        def _refuse(_value, _ctx):
            raise FaultError(f"tier.{op}", "tier refused (injected)")

        kw = {"at": at} if at else {"every": 1}
        return self.on(f"tier.{op}", times=times, mutate=_refuse,
                       **kw, **match)

    def corrupt_tier(self, op: str = "get", at: int = 0,
                     times: int = 1, **match) -> "FaultPlan":
        """The Nth matching tier entry's bytes are corrupted in flight
        (a middle byte flipped — the CRC can never validate it):
        exercises the integrity-drop path, proving a bad entry yields
        a degraded re-prefill and NEVER wrong KV bits."""
        if op not in ("put", "get"):
            raise ValueError(f"op must be 'put' or 'get', got {op!r}")

        def _flip(value, _ctx):
            b = bytearray(bytes(value))
            if b:
                b[len(b) // 2] ^= 0xFF
            return bytes(b)

        kw = {"at": at} if at else {"every": 1}
        return self.on(f"tier.{op}", times=times, mutate=_flip,
                       **kw, **match)

    def slow_tier(self, delay: float, op: str = "get", at: int = 0,
                  times: int = 1, **match) -> "FaultPlan":
        """The Nth matching tier access stalls ``delay`` seconds (a
        cold disk / contended host) before proceeding normally (a
        sleeping mutate, so it shares the seam's one hit counter)."""
        if op not in ("put", "get"):
            raise ValueError(f"op must be 'put' or 'get', got {op!r}")

        def _stall(value, _ctx):
            time.sleep(delay)
            return value

        kw = {"at": at} if at else {"every": 1}
        return self.on(f"tier.{op}", times=times, mutate=_stall,
                       **kw, **match)

    # Fabric seams (docs/scale-out.md "KV fabric") — same one-seam-per-
    # direction discipline as the tier seams: refuse is a raising
    # mutate, slow a sleeping one, garble a byte flip the puller's CRC
    # catches. Narrow with ``peer=`` (peer name) / ``kind=`` / ``key=``.

    def refuse_fabric(self, op: str = "get", at: int = 0,
                      times: int = 1, **match) -> "FaultPlan":
        """The Nth matching fabric probe/pull raises as if the peer
        were dead or refusing: the peer cools down and the fetch
        degrades to the local-miss path (re-prefill) without blocking
        admission. ``at=0`` fires on every matching hit up to
        ``times``."""
        if op not in ("probe", "get"):
            raise ValueError(f"op must be 'probe' or 'get', got {op!r}")

        def _refuse(_value, _ctx):
            raise FaultError(f"fabric.{op}", "fabric peer refused (injected)")

        kw = {"at": at} if at else {"every": 1}
        return self.on(f"fabric.{op}", times=times, mutate=_refuse,
                       **kw, **match)

    def corrupt_fabric(self, at: int = 0, times: int = 1,
                       **match) -> "FaultPlan":
        """The Nth matching pulled entry's wire bytes are corrupted in
        flight (a middle byte flipped — the CRC can never validate
        it): the puller drops the entry and re-prefills BIT-EXACTLY,
        proving a garbled remote entry dies at the same containment
        boundary as a corrupt local one."""

        def _flip(value, _ctx):
            b = bytearray(bytes(value))
            if b:
                b[len(b) // 2] ^= 0xFF
            return bytes(b)

        kw = {"at": at} if at else {"every": 1}
        return self.on("fabric.get", times=times, mutate=_flip,
                       **kw, **match)

    def slow_fabric(self, delay: float, op: str = "get", at: int = 0,
                    times: int = 1, **match) -> "FaultPlan":
        """The Nth matching fabric access stalls ``delay`` seconds (a
        hung peer): a stall past the client's ``pull_timeout_s`` trips
        the fetch deadline — the pull fails, admission re-prefills and
        never waits the peer out."""
        if op not in ("probe", "get"):
            raise ValueError(f"op must be 'probe' or 'get', got {op!r}")

        def _stall(value, _ctx):
            time.sleep(delay)
            return value

        kw = {"at": at} if at else {"every": 1}
        return self.on(f"fabric.{op}", times=times, mutate=_stall,
                       **kw, **match)

    def fail_import(self, at: int = 1, times: int = 1) -> "FaultPlan":
        """Nth snapshot import raises mid-migration (the target end
        dies before claiming pages): the engine falls back to a full
        replay from the prompt — correct output, saved work lost.
        ``at=0`` fires on every import up to ``times``."""
        kw = {"at": at} if at else {"every": 1}
        return self.on("migrate.import", times=times, **kw)

    # Wire/process seams for the cross-process fleet (docs/scale-out.md
    # "Process fleet"). ``replica=`` narrows every one of these to one
    # RemoteReplica by name; ``side`` picks the wire direction. The
    # wire seams fire for BOTH generation batches and probes
    # (heartbeats, remote audits) and share one hit counter — so the
    # conveniences match ``what="batch"`` by default: with a
    # supervisor's timer-driven heartbeats in the same process, a
    # what-unnarrowed times=1 rule would nondeterministically land on
    # a probe instead of the intended mid-batch fault. Pass
    # ``what="probe"`` to target heartbeats, ``what=None`` for either.

    def refuse_connect(self, replica: str | None = None, at: int = 0,
                       times: int = 1,
                       what: str | None = "batch") -> "FaultPlan":
        """A RemoteReplica's connect raises as if the child's listener
        were gone (partition / process death between batches)."""
        match = {} if replica is None else {"replica": replica}
        if what is not None:
            match["what"] = what
        kw = {"at": at} if at else {"every": 1}
        return self.on(
            "wire.connect", times=times,
            exc=ConnectionRefusedError("connection refused (injected)"),
            **kw, **match,
        )

    def drop_wire(self, side: str = "recv", replica: str | None = None,
                  at: int = 0, times: int = 1,
                  what: str | None = "batch") -> "FaultPlan":
        """The wire dies mid-batch: the Nth matching send/recv raises
        ``ConnectionResetError`` (the RST a killed or partitioned child
        produces). Implemented as a raising mutate rule so drop and
        garble share one seam and one hit counter per direction."""
        if side not in ("send", "recv"):
            raise ValueError(f"side must be 'send' or 'recv', got {side!r}")

        def _raise(_value, _ctx):
            raise ConnectionResetError(
                f"wire.{side} reset (injected)"
            )

        match = {} if replica is None else {"replica": replica}
        if what is not None:
            match["what"] = what
        kw = {"at": at} if at else {"every": 1}
        return self.on(f"wire.{side}", times=times, mutate=_raise,
                       **kw, **match)

    def garble_wire(self, side: str = "recv",
                    replica: str | None = None, at: int = 0,
                    times: int = 1,
                    what: str | None = "batch") -> "FaultPlan":
        """The Nth matching wire payload is corrupted in flight (bytes
        reversed — valid UTF-8 JSON never survives it), exercising the
        protocol-error detection path rather than the clean-close one."""
        if side not in ("send", "recv"):
            raise ValueError(f"side must be 'send' or 'recv', got {side!r}")

        def _garble(value, _ctx):
            return bytes(reversed(bytes(value)))

        match = {} if replica is None else {"replica": replica}
        if what is not None:
            match["what"] = what
        kw = {"at": at} if at else {"every": 1}
        return self.on(f"wire.{side}", times=times, mutate=_garble,
                       **kw, **match)

    def kill_proc(self, replica: str | None = None, at: int = 0,
                  times: int = 1, after_s: float = 0.0) -> "FaultPlan":
        """SIGKILL the replica child process mid-batch: the seam offers
        the child's pid right after the batch payload went out, so the
        kill lands while the batch is in flight — the OS then closes
        the socket and the parent's recv sees the crash exactly as a
        real OOM-kill would read. ``after_s`` sleeps before the kill
        (on the waiting worker thread, so the batch stays in flight):
        the child makes real progress first — what the snapshot-based
        recovery tests need a mid-generation kill for."""
        import os
        import signal

        def _kill(pid, _ctx):
            if after_s:
                time.sleep(after_s)
            if pid:
                try:
                    os.kill(pid, signal.SIGKILL)
                except ProcessLookupError:
                    pass  # already gone — the failure is still real
            return pid

        match = {} if replica is None else {"replica": replica}
        kw = {"at": at} if at else {"every": 1}
        return self.on("proc.kill", times=times, mutate=_kill,
                       **kw, **match)

    def hang_proc(self, replica: str | None = None, at: int = 0,
                  times: int = 1) -> "FaultPlan":
        """SIGSTOP the replica child mid-batch: the process stays alive
        (no exit code, no RST) but stops answering heartbeats — the
        wedged-process scenario only a heartbeat deadline can detect.
        Tests resume the child with ``os.kill(pid, SIGCONT)`` to drive
        the late-result latch race."""
        import os
        import signal

        if not hasattr(signal, "SIGSTOP"):  # pragma: no cover
            raise RuntimeError("platform has no SIGSTOP")

        def _stop(pid, _ctx):
            if pid:
                try:
                    os.kill(pid, signal.SIGSTOP)
                except ProcessLookupError:
                    pass
            return pid

        match = {} if replica is None else {"replica": replica}
        kw = {"at": at} if at else {"every": 1}
        return self.on("proc.hang", times=times, mutate=_stop,
                       **kw, **match)

    def refuse_spawn(self, host: str | None = None,
                     replica: str | None = None, at: int = 0,
                     times: int = 1) -> "FaultPlan":
        """A launcher refuses to spawn: the ``launcher.spawn`` seam
        raises, which every launcher converts to ``SpawnError`` — the
        exact failure the supervisor's spawn-FAILOVER path re-places
        around (``host=`` / ``replica=`` narrow the target)."""
        match = {}
        if host is not None:
            match["host"] = host
        if replica is not None:
            match["replica"] = replica
        kw = {"at": at} if at else {"every": 1}
        return self.on(
            "launcher.spawn", times=times,
            exc=ConnectionRefusedError("host refused spawn (injected)"),
            **kw, **match,
        )

    def kill_host(self, launcher, host: str | None = None,
                  at: int = 0, times: int = 1,
                  after_s: float = 0.0) -> "FaultPlan":
        """SIGKILL a WHOLE fake host mid-batch: the ``host.down`` seam
        offers the host tag right after a batch payload went out to a
        replica living there, and the rule kills every process group
        the launcher tagged with that host — losing the machine while
        its work is in flight, deterministically. ``after_s`` sleeps
        first (on the waiting worker thread) so the host makes real
        progress before it dies."""

        def _down(tag, _ctx):
            if after_s:
                time.sleep(after_s)
            launcher.kill_host(tag)
            return tag

        match = {} if host is None else {"host": host}
        kw = {"at": at} if at else {"every": 1}
        return self.on("host.down", times=times, mutate=_down,
                       **kw, **match)

    def hang_host(self, launcher, host: str | None = None,
                  at: int = 0, times: int = 1) -> "FaultPlan":
        """SIGSTOP a WHOLE fake host mid-batch: every process on it
        stays alive but stops answering — the correlated wedge only
        the supervisor's host-window classification reads as ONE
        ``host_down``. Thaw later with ``launcher.thaw_host`` to drive
        the zombie-vs-epoch-fence race."""

        def _freeze(tag, _ctx):
            launcher.hang_host(tag)
            return tag

        match = {} if host is None else {"host": host}
        kw = {"at": at} if at else {"every": 1}
        return self.on("host.down", times=times, mutate=_freeze,
                       **kw, **match)

    # -- firing ----------------------------------------------------------

    def _matches(self, rule: FaultRule, hit: int, ctx: dict) -> bool:
        if rule.fired >= rule.times:
            return False
        for k, v in rule.match.items():
            if ctx.get(k) != v:
                return False
        if hit in rule.at:
            return True
        if rule.every and hit % rule.every == 0:
            return True
        if rule.prob > 0.0 and self.rng.random() < rule.prob:
            return True
        return False

    def fire(self, seam: str, **ctx) -> None:
        """Raise/sleep per the armed rules; no-op if nothing matches.
        The decision runs under the plan lock (atomic hit counting);
        the sleep/raise happens outside it so a delay rule can't
        serialize every other seam."""
        delay = 0.0
        exc: BaseException | None = None
        fired_hit: int | None = None
        with self._lock:
            self.hits[seam] += 1
            hit = self.hits[seam]
            for rule in self.rules:
                if rule.seam != seam or rule.mutate is not None:
                    continue
                if not self._matches(rule, hit, ctx):
                    continue
                rule.fired += 1
                self.fired.append((seam, hit, dict(ctx)))
                fired_hit = hit
                if rule.delay:
                    delay = rule.delay
                    continue
                exc = rule.exc if rule.exc is not None else FaultError(
                    seam, slot=rule.slot
                )
                break
        if fired_hit is not None:
            # Telemetry (docs/observability.md): every activation lands
            # in the event ring, so a chaos run's injected faults line
            # up with the shed/deadline/nan events they trigger.
            obs_events.emit("fault", **_event_fields(ctx, seam, fired_hit))
        if delay:
            time.sleep(delay)
        if exc is not None:
            raise exc

    def mutate(self, seam: str, value: Any, **ctx) -> Any:
        """Pass ``value`` through the armed mutation rules."""
        matched: list[FaultRule] = []
        with self._lock:
            self.hits[seam] += 1
            hit = self.hits[seam]
            for rule in self.rules:
                if rule.seam != seam or rule.mutate is None:
                    continue
                if not self._matches(rule, hit, ctx):
                    continue
                rule.fired += 1
                self.fired.append((seam, hit, dict(ctx)))
                matched.append(rule)
        if matched:
            obs_events.emit("fault", **_event_fields(ctx, seam, hit))
        for rule in matched:
            value = rule.mutate(value, ctx)
        return value

    # -- activation ------------------------------------------------------

    def __enter__(self) -> "FaultPlan":
        global _ACTIVE
        with _LOCK:
            if _ACTIVE is not None:
                raise RuntimeError("a FaultPlan is already active")
            _ACTIVE = self
        return self

    def __exit__(self, *exc) -> None:
        global _ACTIVE
        with _LOCK:
            _ACTIVE = None


_ACTIVE: FaultPlan | None = None
_LOCK = threading.Lock()


def active_plan() -> FaultPlan | None:
    """The plan armed right now, if any. The engine's decode round
    keeps its serial order under one: a rule counts the hits of a seam
    and expects each to see the state of the step before."""
    return _ACTIVE


def fault_point(seam: str, **ctx) -> None:
    """A raise-style seam: no-op unless a plan is active and armed."""
    plan = _ACTIVE
    if plan is not None:
        plan.fire(seam, **ctx)


def mutate_point(seam: str, value: Any, **ctx) -> Any:
    """A value-corruption seam: identity unless a plan is armed."""
    plan = _ACTIVE
    if plan is not None:
        return plan.mutate(seam, value, **ctx)
    return value
