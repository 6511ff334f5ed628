"""Deterministic stub engine: the serving control plane with no model.

The process-fleet supervisor (``serving/supervisor.py``) spawns one
child process per replica, and the chaos suite SIGKILLs them mid-batch
— which makes child startup cost part of every tier-1 fleet test. A
real ``ContinuousEngine`` child pays model load + first-compile per
spawn; this stub pays neither, while keeping everything the fleet
actually exercises REAL:

- the radix prefix cache (``models/prefix_cache.py``) and page pool
  are the production classes — admission matches, COW-counts, inserts,
  retires, and evicts through the exact protocol ``ContinuousEngine``
  uses, so prefix digests, affinity routing, hit rates, and pool/tree
  audits over the wire are the real thing;
- outputs are a pure function of the token context (an FNV-1a rolling
  hash picks each next token), so a re-routed request reproduces
  BIT-EXACTLY on any replica — the chaos suite's survivor-equality
  checks mean what they mean on the real model;
- ``last_stats`` carries the fleet-total keys
  (``serving/replica.py::FLEET_TOTAL_KEYS``) with the same semantics,
  so router aggregation and the supervisor bench read one schema.

What it does NOT model: logits, KV bytes, sampling temperature (all
requests decode greedily under the hash), or wall-clock realism —
``delay_s`` exists only to hold a batch in flight long enough for a
mid-batch SIGKILL to land deterministically.

``run_server --model stub`` serves one of these behind the production
``ModelServer``, which is how the supervisor's tests and
``perf/fleet_bench.py`` spawn whole fleets in seconds.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from triton_distributed_tpu.models.continuous import RequestResult
from triton_distributed_tpu.models.paged_kv_cache import PagePool
from triton_distributed_tpu.models.prefix_cache import PrefixCache
from triton_distributed_tpu.obs import metrics as obs_metrics

_FNV_OFFSET = 2166136261
_FNV_PRIME = 16777619
_MASK = (1 << 32) - 1


def stub_next_token(context, vocab: int) -> int:
    """The stub's whole "model": FNV-1a over the token context. Pure,
    stateless, identical in every process — the property the fleet's
    bit-exact-reroute guarantee is tested against."""
    h = _FNV_OFFSET
    for t in context:
        h = ((h ^ int(t)) * _FNV_PRIME) & _MASK
    return h % vocab


def stub_generate(prompt, gen_len: int, vocab: int = 211) -> list[int]:
    """Reference continuation for ``prompt`` — what any replica must
    produce. Tests compute goldens with this, no engine needed."""
    toks = [int(t) for t in prompt]
    out = []
    for _ in range(int(gen_len)):
        nxt = stub_next_token(toks, vocab)
        out.append(nxt)
        toks.append(nxt)
    return out


class StubEngine:
    """Continuous-engine-shaped stub over a real radix prefix cache.

    Duck-types the surface ``ModelServer`` and the replica tier speak:
    ``run(reqs, results=True)``, ``last_stats``, ``prefix_digest``,
    ``drain``, ``audit``. One instance per child process; the server's
    engine lock serializes access exactly as for a real engine.
    """

    def __init__(self, *, num_pages: int = 128, page_size: int = 16,
                 vocab: int = 211, delay_s: float = 0.0,
                 max_batch: int = 0):
        self.pool = PagePool(num_pages)
        self.page_size = int(page_size)
        self.prefix = PrefixCache(self.pool, self.page_size)
        self.vocab = int(vocab)
        # Per-batch wall-time floor: keeps a batch in flight long
        # enough for the chaos suite's mid-batch kill seams. Spread
        # over the batch's tokens (not slept up front), so the
        # incremental snapshot buffer below has real partial progress
        # for a mid-batch SIGKILL to leave behind.
        self.delay_s = float(delay_s)
        # Decode-slot capacity model: a real engine runs at most
        # `max_batch` slots per continuous-batching round, so an
        # N-request batch costs ceil(N / max_batch) rounds of wall
        # time and requests past the cap don't see a first token until
        # a slot frees. 0 = unbounded (the historical shape: one
        # delay_s per engine batch no matter its size), which keeps
        # the chaos suite fast; the capacity bench sets it so replica
        # throughput is finite and saturation is measurable.
        if int(max_batch) < 0:
            raise ValueError(f"max_batch must be >= 0, got {max_batch}")
        self.max_batch = int(max_batch)
        self.last_stats: dict = self._zero_stats()
        # Slot migration (docs/scale-out.md "Slot migration & handoff"):
        # the stub keeps a per-ticket snapshot of each in-flight
        # request's progress — the control-plane half of the protocol,
        # token-cheap (no KV payload; the hash "model" regenerates KV
        # for free, so prompt+out IS the full portable state).
        self._snap_lock = threading.Lock()
        self._snapshots: dict[str, dict] = {}
        self._handoff = threading.Event()
        # Client-driven cancellation (docs/serving.md "Streaming &
        # cancellation"): ids land from any thread via :meth:`cancel`
        # (the cancel verb is engine-lock-free) and are checked at
        # every token boundary, so a mid-stream cancel tears a stub
        # request down with its partial tokens exactly like the real
        # engine.
        self._cancel_lock = threading.Lock()
        self._cancelled: set[str] = set()
        self._m_mig_saved = obs_metrics.counter(
            "tdt_migration_tokens_saved_total",
            "Generated tokens restored from a snapshot instead of "
            "re-generated (work a replay recovery would repeat).",
        )
        self._m_migrations = obs_metrics.counter(
            "tdt_migrations_total",
            "Slots exported for migration, by reason.",
            labels=("reason",),
        )

    def _zero_stats(self) -> dict:
        return {
            "decode_steps": 0,
            "prefill_tokens": 0,
            "generated_tokens": 0,
            "prefix_hit_tokens": 0,
            "kv_bytes_per_token": 0.0,
            "kv_dtype": "stub",
            "migrated_out": 0,
            "migrated_in": 0,
            "migrated_in_tokens": 0,
            "migration_fallbacks": 0,
            "cancelled_requests": 0,
        }

    def _pages_for(self, n_tokens: int) -> int:
        return -(-n_tokens // self.page_size)

    def run(self, requests, *, results: bool = False):
        """Serve a batch; same contract as ``ContinuousEngine.run``.
        Accepts engine ``Request`` objects or ``(prompt, gen_len)``
        tuples. ``decode_steps`` counts emitted tokens (the stub has no
        batched decode, so steps == tokens). The batch delay is spread
        over its tokens, and each token updates the per-ticket snapshot
        buffer — so a mid-batch SIGKILL leaves resumable progress and a
        handoff request (:meth:`request_handoff`) exports mid-request."""
        stats = self._zero_stats()
        parsed = []
        for req in requests:
            prompt = getattr(req, "prompt", None)
            if prompt is None:
                prompt, gen_len = req
                req = None
            else:
                gen_len = req.gen_len
            parsed.append((req, prompt, int(gen_len)))
        # Capacity model: each `max_batch`-sized round costs one
        # delay_s (spread over ITS tokens), so an over-cap batch's
        # tail requests wait whole rounds for a slot — the queueing a
        # saturated replica really exhibits, visible wire-side as
        # first-token latency. max_batch=0 keeps the one-round shape.
        round_size = self.max_batch or max(len(parsed), 1)
        outs: list[RequestResult] = []
        for lo in range(0, max(len(parsed), 1), round_size):
            chunk = parsed[lo:lo + round_size]
            chunk_toks = sum(max(g, 1) for _, _, g in chunk)
            sleep = self.delay_s / max(chunk_toks, 1)
            for req, prompt, gen_len in chunk:
                if self._handoff.is_set():
                    # Not-yet-started requests hand back un-run. NOT
                    # counted as migrated_out — nothing was exported;
                    # the real engine's sweep makes the same
                    # distinction, so stub and ContinuousEngine fleets
                    # report one schema.
                    outs.append(RequestResult(
                        np.zeros(0, np.int32), "migrated",
                        "handoff drain before admission",
                        getattr(req, "snapshot", None),
                    ))
                    continue
                outs.append(
                    self._serve_one(req, prompt, gen_len, stats, sleep)
                )
        with self._snap_lock:
            self._snapshots = {}
        self._handoff.clear()  # one-shot, like the engine's _handoff_at
        # Cancels that raced past their request must not leak into
        # future batches reusing the same ticket id (the
        # ContinuousEngine's batch-scoped prune + cap, mirrored).
        batch_tids = {
            getattr(req, "ticket_id", None) for req, _, _ in parsed
        }
        batch_tids.discard(None)
        with self._cancel_lock:
            self._cancelled -= batch_tids
            if len(self._cancelled) > 4096:
                self._cancelled.clear()
        self.last_stats = stats
        stats["prefix_cache"] = dict(self.prefix.stats)
        stats["prefix_hit_rate"] = self.prefix.hit_rate
        stats["tree_pages"] = self.prefix.node_count
        stats["free_pages"] = len(self.pool.free)
        if results:
            return outs
        return [np.asarray(r.tokens, np.int32) for r in outs]

    def _serve_one(self, req, prompt, gen_len: int, stats: dict,
                   sleep: float) -> RequestResult:
        toks = [int(t) for t in prompt]
        s = len(toks)
        if s == 0 or gen_len <= 0:
            return RequestResult(
                np.zeros(0, np.int32), "unservable",
                "stub needs a non-empty prompt and gen_len >= 1",
            )
        # Snapshot resume (docs/scale-out.md "Slot migration &
        # handoff"): a valid snapshot's generated tokens are restored,
        # not re-generated; anything malformed/stale falls back to a
        # full replay from the prompt — the same contract as the real
        # engine's import path.
        tid = getattr(req, "ticket_id", None)
        if tid is not None and self._cancel_hit(tid):
            stats["cancelled_requests"] += 1
            return RequestResult(
                np.zeros(0, np.int32), "cancelled",
                "cancelled by client before admission",
            )
        out: list[int] = []
        snap = getattr(req, "snapshot", None)
        if snap is not None:
            restored = self._resume_tokens(snap, toks, gen_len)
            if restored is None:
                stats["migration_fallbacks"] += 1
            else:
                out = restored
                stats["migrated_in"] += 1
                stats["migrated_in_tokens"] += len(out)
                self._m_mig_saved.inc(len(out))
        total = self._pages_for(s + gen_len)
        # The production admission protocol: match (pins + hit
        # accounting), allocate the uncovered pages (LRU-evicting the
        # tree when the free list runs short), COW-finish, retire.
        m = self.prefix.match(toks)
        new = self.prefix.allocate(total - len(m.nodes))
        if new is None:
            self.prefix.release_match(m)
            return RequestResult(
                np.zeros(0, np.int32), "overloaded",
                f"stub pool cannot cover {total} pages",
            )
        matched = m.matched_len
        shared = list(m.nodes)
        self.prefix.finish_cow(m)
        pages = m.pages + new
        # A resumed request's KV is "shipped" (the hash model carries
        # none) — only a cold start pays the prefill.
        stats["prefill_tokens"] += 0 if out else s - matched
        stats["prefix_hit_tokens"] += matched
        ctx = toks + out
        # prefill→decode handoff: emit only the admission token, then
        # export (the engine's prefill_only contract). Never re-armed
        # on a resumed request — its prefill already happened.
        prefill_only = bool(getattr(req, "prefill_only", False)) and not out
        on_token = getattr(req, "on_token", None)
        migrated = None
        cancelled = False
        while len(out) < gen_len:
            if sleep:
                time.sleep(sleep)
            if self._handoff.is_set():
                migrated = "drain"
                break
            if tid is not None and self._cancel_hit(tid):
                cancelled = True
                break
            nxt = stub_next_token(ctx, self.vocab)
            out.append(nxt)
            ctx.append(nxt)
            if on_token is not None:
                # Streaming hook (docs/serving.md "Streaming &
                # cancellation"): the ContinuousEngine contract —
                # restored tokens never re-fire, a raising sink
                # detaches instead of failing the request.
                try:
                    on_token(len(out) - 1, int(nxt))
                except Exception:  # noqa: BLE001 — sink isolation
                    on_token = None
            stats["generated_tokens"] += 1
            stats["decode_steps"] += 1
            if tid is not None:
                with self._snap_lock:
                    self._snapshots[tid] = self._snapshot_of(
                        toks, out, gen_len, req
                    )
            if prefill_only and len(out) < gen_len:
                migrated = "prefill_handoff"
                break
        if cancelled:
            # Mid-stream cancel: partial tokens back to the caller,
            # every page back to the pool (nothing retires — a
            # cancelled chain must not poison later matches any more
            # than a failed one would).
            for node in shared:
                self.prefix.release_node(node)
            self.pool.release(pages[len(shared):])
            stats["cancelled_requests"] += 1
            return RequestResult(
                np.asarray(out, np.int32), "cancelled",
                f"cancelled by client after {len(out)} generated tokens",
            )
        if migrated:
            # Mid-request handoff: export the progress, release the
            # pages (nothing retires — the tree only caches completed
            # chains in the stub), hand the snapshot back.
            for node in shared:
                self.prefix.release_node(node)
            self.pool.release(pages[len(shared):])
            stats["migrated_out"] += 1
            self._m_migrations.inc(reason=migrated)
            return RequestResult(
                np.asarray(out, np.int32), "migrated",
                f"slot exported ({migrated})",
                self._snapshot_of(toks, out, gen_len, req),
            )
        # Cache prompt + fed-back generations, positions [0, s+gen-1)
        # — the same chain a real engine retires.
        chain = ctx[: s + gen_len - 1]
        nchain = self._pages_for(len(chain))
        self.prefix.retire_sequence(chain, pages[:nchain], shared)
        self.pool.release(pages[nchain:])
        return RequestResult(np.asarray(out, np.int32))

    @staticmethod
    def _snapshot_of(toks, out, gen_len, req) -> dict:
        return {
            "stub": True,
            "prompt": list(toks),
            "out": list(out),
            "gen_len": int(gen_len),
            "trace_id": getattr(req, "trace_id", None),
            "exported_at": time.time(),
        }

    def _resume_tokens(self, snap, toks, gen_len) -> list[int] | None:
        """Validate a snapshot against the request; None → replay."""
        try:
            if [int(t) for t in snap["prompt"]] != toks:
                return None
            out = [int(t) for t in snap["out"]]
        except (KeyError, TypeError, ValueError):
            return None
        if len(out) >= int(gen_len):
            return None
        return out

    # -- replica/server surface -------------------------------------------

    def cancel(self, ticket_ids) -> None:
        """Arm cancellation for the given ticket ids — thread-safe;
        the server's engine-lock-free cancel verb calls this mid-batch
        and the in-flight request stops at its next token boundary
        (the ContinuousEngine contract, docs/serving.md)."""
        ids = {str(t) for t in ticket_ids}
        if ids:
            with self._cancel_lock:
                self._cancelled |= ids

    def _cancel_hit(self, tid: str) -> bool:
        """Consume a pending cancellation for ``tid`` (ids are
        one-shot, like the engine's per-batch prune)."""
        with self._cancel_lock:
            if tid in self._cancelled:
                self._cancelled.discard(tid)
                return True
        return False

    def request_handoff(self, after_rounds: int = 0) -> None:
        """Arm the lossless-drain export (docs/scale-out.md "Slot
        migration & handoff"): the in-flight batch stops at the next
        token, exporting each request's progress as a snapshot.
        ``after_rounds`` is accepted for engine-surface parity (the
        stub has no scheduling rounds — it always stops at the next
        token boundary)."""
        del after_rounds
        self._handoff.set()

    def export_slots(self) -> dict:
        """Per-ticket progress snapshots of the in-flight batch — what
        the server's ``export_slots`` verb returns and the supervisor's
        crash recovery resumes from. Lock-guarded; safe mid-batch."""
        with self._snap_lock:
            return dict(self._snapshots)

    def prefix_digest(self) -> list:
        return self.prefix.prefix_digest()

    def drain(self) -> int:
        """Flush the radix tree back to the pool (replica drain)."""
        return self.prefix.flush()

    def audit(self, *, raise_on_violation: bool = False) -> list[str]:
        """Tree invariants + exact pool partition (no in-flight state
        survives a synchronous ``run``, so free ∪ tree must cover the
        pool whenever the engine lock is held)."""
        problems = list(self.prefix.audit())
        held = len(self.pool.free) + self.prefix.node_count
        if held != self.pool.num_pages:
            problems.append(
                f"pool partition broken: {len(self.pool.free)} free + "
                f"{self.prefix.node_count} tree != {self.pool.num_pages}"
            )
        if problems and raise_on_violation:
            from triton_distributed_tpu.models.paged_kv_cache import (
                PoolAuditError,
            )

            raise PoolAuditError("; ".join(problems))
        return problems
