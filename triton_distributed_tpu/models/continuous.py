"""Continuous batching over the paged KV pool.

Beyond-parity subsystem: the reference Engine (``models/engine.py:113``)
serves fixed batches; modern serving interleaves requests — admit a new
sequence the moment pool pages free up, evict on completion, and step
the union every iteration. Its paged cache
(``mega_triton_kernel/models/paged_kv_cache.py``) is the natural
substrate, and this module is the TPU build's admission/eviction loop on
top of ours.

Design: the decode step stays ONE jitted program over a fixed
``max_batch`` of slots (static shapes — XLA's requirement). Slot state
(page table rows, kv_len, free list) lives host-side; admission writes a
slot's table row + kv_len and prefises the prompt into its pages,
eviction releases the pages. Inactive slots keep table row 0 and point
at a reserved trash page, so their (masked-out) appends land harmlessly.

With ``prefix_cache=True`` two serving-path upgrades switch on
(docs/serving.md):

- **Radix prefix reuse**: finished sequences retire their pages into a
  :class:`~triton_distributed_tpu.models.prefix_cache.PrefixCache`
  instead of the free list; admission maps the longest cached prefix
  into the new slot's table row (refcounted, COW for partially matched
  tail pages) and prefills ONLY the suffix.
- **Chunked prefill**: the suffix runs through
  ``Qwen3.prefill_paged_chunk`` in fixed-width chunks with a decode
  step of the running batch between chunks, so a long cold prompt never
  stalls in-flight decodes for its whole prefill (``prefill_chunk=0``
  keeps one chunk per admission).

**Fault tolerance** (docs/serving.md "Fault tolerance"): requests fail
*individually*. An unservable, deadline-expired, shed, or crashed
request tears down only its own slot — private pages back to the pool,
prefix pins back to the tree, table row back to the trash page — and
surfaces a structured :class:`RequestResult` (status, reason, partial
tokens) via ``run(results=True)`` while every other request completes.
``run()`` ends with a pool/radix invariant audit (:meth:`audit`) so a
leak is caught at the batch that caused it, not three batches later.
"""

from __future__ import annotations

import dataclasses
import hashlib
import itertools
import threading
import time
import weakref
from collections import Counter, deque

import jax
import jax.numpy as jnp
import numpy as np

from triton_distributed_tpu.models import sampling
from triton_distributed_tpu.models.engine import (
    MegaDispatch,
    prefill_suffix_chunks,
)
from triton_distributed_tpu.models.stats import (
    STAT_METRIC_ALIASES,
    STAT_METRICS,
)
from triton_distributed_tpu.obs import events as obs_events
from triton_distributed_tpu.obs import metrics as obs_metrics
from triton_distributed_tpu.obs.timeline import Timeline, observe_request
from triton_distributed_tpu.models.paged_kv_cache import (
    PoolAuditError,
    audit_pool,
    copy_page,
    gather_pages,
    init_paged_cache,
    state_bytes_per_slot,
    truncate_pages,
    write_page,
    write_prefill,
)
from triton_distributed_tpu.models.prefix_cache import (
    PrefixCache,
    PrefixMatch,
    node_chain,
    round_chunk,
)
from triton_distributed_tpu.models.qwen import Mode, Qwen3
from triton_distributed_tpu.runtime.faults import (
    FaultError,
    active_plan,
    fault_point,
    mutate_point,
)
from triton_distributed_tpu.runtime.profiling import trace_span


@dataclasses.dataclass
class RequestError:
    """Structured failure: a machine-readable ``status`` plus a human
    ``reason``. Statuses: ``unservable`` (can never fit), ``overloaded``
    (shed by the bounded admission queue — retry with backoff),
    ``deadline_exceeded``, ``nan_logits`` (non-finite model output),
    ``failed`` (crash isolated to this request), ``aborted`` (the
    engine loop itself died)."""

    status: str
    reason: str


@dataclasses.dataclass
class RequestResult:
    """One request's outcome: generated tokens (PARTIAL when the
    request failed mid-decode — everything emitted before the failure)
    and its status. ``status == "migrated"`` means the slot was
    exported instead of finished (handoff drain, prefill→decode
    handoff): ``snapshot`` then carries the portable slot state
    (``models/slot_state.py`` wire form) a different engine resumes
    from — the serving tier re-dispatches it, so a migrated result
    never reaches a client."""

    tokens: np.ndarray
    status: str = "ok"
    reason: str = ""
    snapshot: dict | None = None

    @property
    def ok(self) -> bool:
        return self.status == "ok"

    @property
    def error(self) -> RequestError | None:
        return None if self.ok else RequestError(self.status, self.reason)


# Process-wide trace-id allocator: ids must stay unique across engines
# and replicas (the router fans one batch over several), or the
# device-task tagging would alias two requests into one thread of the
# merged timeline.
_TRACE_IDS = itertools.count(1)


def _model_fingerprint(model) -> str:
    """Identity of the weights a durable tier entry was produced
    under: class name, every param leaf's shape/dtype (architecture),
    and a value sample spread across the whole tree — leaves taken at
    an even stride (the last leaf, typically the LM head, always
    included) and, within each, elements strided across the FULL
    flattened tensor, so a scan-stacked ``[L, ...]`` leaf samples
    every layer band, not just layer 0. A fine-tune's gradients are
    dense, so a partial update (later layers only, head only) still
    moves sampled bytes. Tier entries carry this so a ``tier_dir``
    reused across a weight update faults back NOTHING instead of
    stale KV — cached attention state from old weights under new
    weights is silently wrong bits, exactly what the tier promises
    never to serve. A few tiny host fetches, computed once per engine
    when a tier is attached."""
    h = hashlib.sha1(type(model).__name__.encode())
    leaves = jax.tree_util.tree_leaves(getattr(model, "params", None))
    for leaf in leaves:
        h.update(str(getattr(leaf, "shape", ())).encode())
        h.update(str(getattr(leaf, "dtype", "")).encode())
    sampled = leaves[::max(1, len(leaves) // 8)][:8]
    if leaves and leaves[-1] is not sampled[-1]:
        sampled.append(leaves[-1])
    for leaf in sampled:
        try:
            flat = jnp.ravel(leaf)
            stride = max(1, int(flat.shape[0]) // 64)
            sample = np.asarray(jax.device_get(flat[::stride][:64]))
        except Exception:  # noqa: BLE001 — identity is best-effort;
            continue  # shapes/dtypes alone still gate architecture
        h.update(sample.tobytes())
    return h.hexdigest()


class RequestFailedError(RuntimeError):
    """Raised by ``run(results=False)`` when requests failed: the
    legacy list-of-arrays interface has no failure channel, so the
    engine completes what it can, tears the failures down cleanly, and
    raises this with every per-request failure attached."""

    def __init__(self, failures):
        self.failures = failures  # list[(index, Request)]
        msgs = "; ".join(
            f"request {i}: [{r.status}] {r.reason}" for i, r in failures
        )
        super().__init__(f"{len(failures)} request(s) failed: {msgs}")


# NaN/Inf logits raised by the admission sampler and the speculative
# verify guard; both map to a structured `nan_logits` request failure.
_NonFiniteLogits = sampling.NonFiniteLogitsError

# Finite mask + greedy argmax of a decode step's logits in ONE device
# program: the NaN guard rides the token fetch the loop already pays.
@jax.jit
def tdt_finite_greedy(logits):
    return jnp.isfinite(logits).all(axis=-1), sampling.greedy(logits)

# Serving counters mirrored live into the process metrics registry
# (docs/observability.md): same numbers as ``last_stats``, but
# scrapeable through ``{"cmd": "metrics"}`` MID-generation instead of
# only after a run() returns. Keys match ``_zero_stats``; the (name,
# help) table lives in models/stats.py so Engine.serve shares the
# exact declarations.

# Event-ring kind for each terminal failure status (PR 3 taxonomy);
# statuses not listed emit a generic ``request_failed`` event.
_FAIL_EVENT_KIND = {
    "overloaded": "shed",
    "deadline_exceeded": "deadline",
    "nan_logits": "nan_guard",
    "cancelled": "cancel",
}


@dataclasses.dataclass
class Request:
    """One generation request and its accumulated output.

    ``temperature``/``top_p``/``top_k`` override the engine's defaults
    for THIS request (None → engine default) — mixed greedy/sampled
    batches decode together, each slot sampled under its own knobs.
    ``deadline_s`` is a per-request wall-clock budget measured from
    ``run()`` entry; an expired request fails with
    ``deadline_exceeded`` and its partial tokens.
    """

    prompt: np.ndarray  # [S] int32
    gen_len: int
    temperature: float | None = None
    top_p: float | None = None
    top_k: int | None = None
    deadline_s: float | None = None
    out: list[int] = dataclasses.field(default_factory=list)
    slot: int | None = None
    pages: list[int] = dataclasses.field(default_factory=list)
    # Prefix-cache bookkeeping: tree nodes whose pages lead this
    # request's page list (refcounted for the request's lifetime).
    shared_nodes: list = dataclasses.field(default_factory=list)
    # Speculative-decoding state (``SpecState``), attached at admission
    # when the engine runs with ``speculative=K``.
    spec: object | None = None
    # Failure channel (``ok`` until something fails this request).
    status: str = "ok"
    reason: str = ""
    # Telemetry (docs/observability.md): lifecycle stamps yielding the
    # queue-wait/TTFT/TPOT/e2e histograms. The server stamps enqueue at
    # payload decode; ``run()`` backfills for direct callers.
    timeline: Timeline | None = dataclasses.field(default=None, repr=False)
    deadline_at: float | None = dataclasses.field(default=None, repr=False)
    # Request trace id (docs/observability.md "Device task tracer"):
    # follows the request server → router → replica → engine →
    # individual device tasks. Clients may supply one in the payload
    # (``trace_ids``); ``run()`` assigns ``req-<n>`` when absent.
    trace_id: str | None = None
    # Slot migration (docs/scale-out.md "Slot migration & handoff"):
    # ``snapshot`` holds portable slot state (slot_state.py wire form)
    # — set on INPUT to resume a migrated request (admission imports it
    # instead of prefilling), and set on OUTPUT by a handoff export
    # (``status`` flips to "migrated" and ``result()`` ships it).
    # ``prefill_only`` makes the engine export the slot right after
    # admission — the prefill→decode handoff's first half.
    # ``ticket_id`` keys the engine's incremental snapshot buffer so
    # the supervisor's crash recovery can match snapshots to tickets.
    snapshot: dict | None = dataclasses.field(default=None, repr=False)
    prefill_only: bool = False
    ticket_id: str | None = None
    # SLO class (obs/slo.py, serving/pools.py): admission class the
    # goodput yardstick and the pool scheduler judge this request
    # under. The engine itself never branches on it — it rides along
    # so telemetry and router-side scheduling see one name end to end.
    slo_class: str | None = None
    # Per-request PRNG state: sampled requests draw from their OWN key
    # via fold_in(key, key_step) — never the engine-global key — so a
    # migrated slot's seeded-sampled continuation replays the exact
    # draws the un-migrated run would have made. Assigned lazily from
    # the engine key on first sampled draw (greedy requests never pay).
    key: object | None = dataclasses.field(default=None, repr=False)
    key_step: int = 0
    # Streaming (docs/serving.md "Streaming & cancellation"): called
    # ``on_token(index, token_id)`` on the engine thread the moment a
    # token is EMITTED (appended to ``out``) — the server's streaming
    # path writes one wire frame per call. Tokens RESTORED from a
    # migration snapshot never fire it (they were already delivered);
    # the callback must not raise — a broken sink detaches itself
    # instead of failing the request (see ``_emit_token``).
    on_token: object | None = dataclasses.field(default=None, repr=False)

    @property
    def done(self) -> bool:
        return len(self.out) >= self.gen_len

    def result(self) -> RequestResult:
        return RequestResult(
            np.asarray(self.out, np.int32), self.status, self.reason,
            self.snapshot if self.status == "migrated" else None,
        )


@dataclasses.dataclass
class _MegaPlan:
    """One composed megakernel launch: the row mapping (launch row →
    engine slot), the batch-bucket width, and the operand set. Built
    from host truth by ``_mega_plan``; a resident chain reuses the
    pending launch's plan with only ``n_valid`` re-projected (the slot
    set cannot change between issue and drain — every slot-state
    mutation site drains first)."""

    rows: list
    B: int
    compact: bool       # B < max_batch: compacted table/kv_len/tok
    sampled: bool
    filtered: bool      # in-kernel top-k/top-p (single-rank only)
    eos: bool           # device stop-token test + halt chaining
    temps: np.ndarray   # [B] per-row temperature (0 = greedy row)
    n_valid: np.ndarray  # [B] kept-row counts fed to append_n
    sampcfg: np.ndarray | None  # [B, 4] when filtered
    stop_tok: np.ndarray | None  # [B] when eos


@dataclasses.dataclass
class _MegaLaunch:
    """An issued — possibly still in-flight — NS-step launch: what the
    resident pipeline holds between issue and drain. ``toks``/``ss``/
    ``halt``/``cache``/``ring`` are device arrays nothing has synced
    on; ``cache`` is the launch's (bucket-shaped) output cache the
    NEXT chained launch donates."""

    plan: _MegaPlan
    toks: object        # [NS, B] device
    cache: object       # PagedKVCache (bucket-shaped)
    ss: object | None   # [B] first stop-token step (NS = never)
    halt: object | None  # [B] halt bits chained into the next launch
    ring: object | None  # device trace ring (kernel_trace only)
    t0: float
    trace_ids: dict


@dataclasses.dataclass
class _StepLaunch:
    """A dispatched — possibly still in-flight — single decode step:
    what the one-step lookahead parks in ``_pend`` between a step's
    dispatch and the round that emits its tokens. ``toks`` (the greedy
    argmax, still on the device) is the NEXT step's token input.
    ``reqs`` is the request each slot held at dispatch: a slot that no
    longer holds it at the drain ended first (stop token, non-finite
    row, cancel, deadline), and its token is discarded."""

    reqs: list
    logits: object      # [max_batch, V] device
    finite: object      # [max_batch] device all-finite mask
    toks: object        # [max_batch] device greedy tokens
    counts: object = None  # device int32 sums: ``model.step_counts``
    host: tuple | None = None
    ahead: bool = False  # dispatched before the step before's fetch

    def fetch(self) -> tuple:
        """THE host sync of a step: ``(finite, tokens)`` as numpy,
        fetched once (the step's own sums with them, kept in
        ``counts``: ``model.step_counts`` names them). After it the
        device has left the step."""
        if self.host is None:
            self.host = (np.asarray(self.finite), np.array(self.toks))
            if self.counts is not None:
                self.counts = np.asarray(self.counts)
        return self.host


class ContinuousEngine(MegaDispatch):
    """Admission/eviction serving loop over the paged pool.

    ``max_batch`` decode slots share ``num_pages`` pool pages; a request
    is admitted when a slot AND enough pages for its prompt+gen_len are
    free (cached prefix pages count as free coverage — they are mapped,
    not allocated). Page 0 is reserved as the trash page for inactive
    slots.

    ``max_queue`` bounds the admission queue: requests beyond it are
    shed with a structured ``overloaded`` error instead of wedging the
    batch (None → unbounded).
    """

    NS = 8  # megakernel multi-step launch width

    # Live engines, auditable by the shared pytest fixture
    # (tests/conftest.py) after every test.
    _live: "weakref.WeakSet[ContinuousEngine]" = weakref.WeakSet()

    def __init__(
        self,
        model: Qwen3,
        *,
        max_batch: int = 4,
        page_size: int = 128,
        max_length: int | None = None,
        num_pages: int | None = None,
        mode: str = "xla",  # Mode or "mega" (megakernel decode)
        temperature: float = 0.0,
        top_p: float = 1.0,
        top_k: int = 0,
        eos_id: int | None = None,
        seed: int = 0,
        mega_cfg=None,
        prefix_cache: bool = False,
        prefill_chunk: int = 0,
        speculative: int = 0,
        spec_width: int = 4,
        max_queue: int | None = None,
        kv_dtype: str | None = None,
        kernel_trace: bool = False,
        snapshot_every: int = 0,
        tier_bytes: int = 0,
        tier_dir: str | None = None,
        tier=None,
        fabric=None,
        handoff_batch: bool = True,
        ns: int = 8,
        mega_buckets: bool = True,
        resident: bool = False,
    ):
        self.model = model
        self.mode = mode
        self.mega_cfg = mega_cfg
        # A model whose step returns sums beside its logits (an expert
        # share's two counts, a hybrid's advanced rows:
        # ``decode_step_counted``, named by ``model.step_counts``) has
        # that ONE decode program; they ride each step's ``fetch()``.
        self._counted_step = getattr(model, "decode_step_counted", None)
        # What a slot of this model keeps (``cfg.slot_keeps``, the one
        # statement) decides which paths have a program. Latent rows
        # and a recurrent state have the single-step paged programs
        # over a full-width pool only; a recurrent state, which is not
        # pages, can besides not be rolled back, exported or spilled.
        # Refused by the flag that asks.
        keeps = model.cfg.slot_keeps
        self._recurrent = "recurrent_state" in keeps
        if keeps != ("kv_pages",):
            name = model.cfg.model_name
            what = ("latent attention" if "latent_rows" in keeps
                    else "a recurrent state beside K/V pages")
            for flag, asked in (
                ("--mode mega", mode == "mega"),
                ("--kv-dtype int8",
                 (kv_dtype or model.cfg.kv_dtype) == "int8"),
                ("--speculative", bool(speculative)),
                ("--snapshot-every",
                 self._recurrent and bool(snapshot_every)),
                ("--tier-bytes / --tier-dir", self._recurrent and bool(
                    tier_bytes or tier_dir or tier is not None)),
            ):
                if asked:
                    raise ValueError(
                        f"{flag}: {name} has no such path ({what} "
                        "serves the xla/pallas single-step programs "
                        "over a full-width pool"
                        + (", and a slot's state is not pages: it "
                           "cannot be rolled back, exported or spilled)"
                           if self._recurrent else ")"))
        # Resident decode (docs/megakernel.md "Resident decode"): the
        # NS launch width becomes a knob (perf/mega_serve_bench.py
        # sweeps it), batch buckets give a 2-slot round a 2-wide launch
        # program instead of the max_batch-wide one, and
        # ``resident=True`` pipelines launches: the host issues launch
        # i+1 off launch i's device outputs and syncs only to drain
        # emitted tokens.
        if int(ns) < 1:
            raise ValueError(f"ns must be >= 1, got {ns}")
        self.NS = int(ns)
        self.mega_buckets = bool(mega_buckets)
        self.resident = bool(resident)
        if resident and mode != "mega":
            raise ValueError(
                "resident=True requires mode='mega' (the resident loop "
                "pipelines megakernel NS-step launches; the xla/pallas "
                "decode paths have no device loop to keep resident)"
            )
        # The depth-1 pipeline's one slot: an in-flight resident launch
        # (``_MegaLaunch``) or a looked-ahead single step
        # (``_StepLaunch``). Everything that mutates slot, table or pool
        # state comes through ``_drain_pend`` / ``_settle_pend`` /
        # ``_abort_pend`` first.
        self._pend = None
        # The requests of the running batch that wait for a slot (the
        # queue of ``run()``; empty between runs).
        self._waiting = ()
        # Requests whose slot ended under a looked-ahead step that was
        # still appending to their pages, each with that step: the pages
        # go back once the device has left it (``_release_ended``).
        self._ended: list = []
        # Device task tracer (docs/observability.md "Device task
        # tracer"): mega launches carry an in-kernel trace ring; every
        # launch's ring is folded into tdt_mega_task_seconds and kept
        # (bounded) for the server's {"cmd": "kernel_trace"} verb and
        # the merged chrome timeline (plumbing shared with Engine via
        # MegaDispatch). Off by default: the untraced build is
        # bit-identical to PR 7's.
        self._init_kernel_trace(kernel_trace, mode)
        self.temperature = temperature
        self.top_p = top_p
        self.top_k = top_k
        # Speculative decoding (docs/serving.md): per-slot n-gram
        # drafts verified through the chunk-prefill path; rounds with
        # no draft anywhere fall back to the batched decode step. The
        # ONE remaining mega exclusion (docs/megakernel.md "Serving
        # fast path" composition matrix): speculation's chunked
        # verify+rollback steps slots at different paces, while a mega
        # launch advances every slot NS tokens in lockstep — and the
        # NS-amortized launch already buys the dispatch saving
        # speculation would chase.
        if speculative and mode == "mega":
            raise ValueError(
                "speculative=K does not compose with mode='mega': the "
                "NS-step fused launch advances all slots in lockstep "
                "and already amortizes per-step dispatch — the resident "
                "pipeline splices whole slots between rounds, never "
                "a mid-launch verify/rollback; run speculative with "
                "mode='xla'/'pallas', or drop speculative to serve "
                "through the megakernel (docs/megakernel.md)"
            )
        self.speculative = int(speculative)
        # Quantized KV storage (docs/serving.md "Quantized KV cache"):
        # int8 pool + per-page-per-head scales — halves the bytes every
        # decode step streams AND doubles how many tokens the same pool
        # HBM holds, so the radix tree retains more prefixes and more
        # slots admit before shedding. Explicit knob wins over
        # ``cfg.kv_dtype``. Composes with mode='mega': the fused decode
        # dequantizes the int8 pool in-kernel through the per-page
        # scales (PR 7 lifted the old full-width exclusion).
        self.kv_dtype = kv_dtype if kv_dtype is not None else (
            model.cfg.kv_dtype
        )
        # Tree speculation (docs/serving.md "Speculative decoding"):
        # with ``spec_width > 1`` a slot whose radix tree / KV tier
        # remembers several continuations of its suffix drafts them as
        # a token TRIE and verifies every branch in the one chunk
        # forward (the pad rows a linear draft wastes carry the extra
        # branches). Full-width pools only: the commit is a KV
        # row-move, and ``quantized_row_scatter``'s reset-scales-at-
        # offset-0 semantics make moved int8 rows unrepresentable —
        # quantized pools keep width-1 chains (today's linear path,
        # bit-for-bit).
        self.spec_width = max(int(spec_width), 1)
        self._spec_tree = (
            bool(speculative) and self.spec_width > 1
            and self.kv_dtype is None
        )
        self.eos_id = eos_id
        self.key = jax.random.key(seed)
        self.max_batch = max_batch
        self.page_size = page_size
        self.max_length = max_length or model.cfg.max_length
        if self.max_length % page_size:
            raise ValueError(
                f"max_length {self.max_length} is not a multiple of "
                f"page_size {page_size}: pages_per_seq would silently "
                f"truncate to {self.max_length // page_size} and the "
                f"tail tokens would have no page — pick an aligned pair"
            )
        self.pps = self.max_length // page_size
        self.max_queue = max_queue
        # Handoff-burst batching (docs/scale-out.md "Disaggregated
        # pools & autoscaling"): an armed drain sweep exports every
        # active slot through ONE concatenated page gather
        # (slot_state.export_slots_batch) instead of per-slot serial
        # round trips. Bit-identical either way; the flag exists so
        # perf/pools_bench.py can measure the wall delta.
        self.handoff_batch = bool(handoff_batch)

        # +1: page 0 is reserved as the trash page every inactive slot's
        # table points at, and must not shave serviceable capacity.
        n_pages = (num_pages or max_batch * self.pps) + 1
        self.cache, self.pool = init_paged_cache(
            model.cfg, max_batch, model.ctx, model.axis,
            max_length=self.max_length, page_size=page_size,
            num_pages=n_pages, assign_pages=False,
            kv_dtype=self.kv_dtype,
        )
        self.pool.free = [p for p in self.pool.free if p != 0]
        self._capacity = len(self.pool.free)
        self._table = np.zeros((max_batch, self.pps), np.int32)
        self._kv_len = np.zeros((max_batch,), np.int32)
        self._tok = np.zeros((max_batch,), np.int32)
        self._slots: list[Request | None] = [None] * max_batch
        self.prefix = PrefixCache(
            self.pool, page_size, pages_hold_all=not self._recurrent
        ) if prefix_cache else None
        if prefix_cache and not self._recurrent:
            # Compile the copy-on-write program now (the trash page onto
            # itself), not at the first partial-page hit in the middle
            # of serving: one small program a pool shape.
            self.cache = copy_page(self.cache, 0, 0)
        # Durable KV tier (docs/serving.md "Tiered KV"): a host-RAM
        # (and optionally disk) PageStore behind the radix tree —
        # evicted prefix pages spill into it instead of dropping to
        # nothing, admission faults tier-hit pages back cheaper than
        # re-prefill, and the incremental snapshot buffer persists
        # through the same store. ``tier=`` accepts a pre-built (or
        # shared) store; otherwise ``tier_bytes``/``tier_dir`` build
        # one. Off (None) keeps every pre-tier code path untouched.
        # Owned = built from this engine's knobs, so every snap entry
        # in it is this engine's (incl. leftovers a crashed previous
        # process wrote under the same dir — see run()'s start-clear).
        # A ``tier=`` store may be shared: only our own keys are ours.
        self._tier_owned = tier is None and bool(tier_bytes or tier_dir)
        if tier is None and (tier_bytes or tier_dir):
            from triton_distributed_tpu.models.kv_tier import PageStore

            # fsync=False: spills and snapshot write-throughs run ON
            # the scheduling loop — the atomic rename alone gives
            # process-crash durability (what restart resume needs),
            # and an OS crash can only tear an entry the CRC drops.
            # The supervisor's resume store keeps fsync (its writes
            # ride the monitor thread, off any decode path).
            tier = PageStore(capacity_bytes=tier_bytes or (64 << 20),
                             dir=tier_dir, fsync=False)
        self.tier = tier
        # KV fabric (docs/scale-out.md "KV fabric"): a
        # ``kv_tier.FabricClient`` consulted by ``_tier_fill`` on a
        # LOCAL tier miss — peers' tier entries are pulled over the
        # wire (or in-process), validated through the same codec +
        # geometry/fingerprint checks as local entries, and grafted
        # identically. None (default) keeps every single-replica path
        # untouched; a fabric without a tier is ignored (nowhere to
        # graft through).
        self.fabric = fabric if tier is not None else None
        self._tier_snap_keys: set[str] = set()
        # Weight identity for durable entries (computed only when a
        # tier is attached — one small host fetch): spilled pages and
        # durable snapshots are valid under THESE weights only.
        self._tier_fp = (
            _model_fingerprint(model) if self.tier is not None else None
        )
        # One-shot leftover sweep latch — see run()'s start-clear.
        self._tier_swept = False
        if self.prefix is not None and self.tier is not None:
            self.prefix.spill_fn = self._spill_page
        self.prefill_chunk = round_chunk(prefill_chunk) if prefill_chunk else 0
        # Dense batch-1 prefill scratch — only the legacy (non-prefix)
        # admission path scatters through it; the chunked path writes
        # pages directly.
        self._dense1 = None if prefix_cache else model.new_cache(
            1, self.max_length
        )
        # Lazy megakernel multi-step programs, keyed by (sampled,
        # filtered, eos, bucket_B) — greedy rounds must not
        # consume PRNG keys, or temperature=0 runs would lose their
        # seeded determinism, and each batch bucket compiles its own
        # (narrower) program.
        self._multi_fns: dict = {}
        self.stats = self._zero_stats()
        # Metric handles resolved ONCE: the hot decode loop pays a dict
        # lookup + inc per _bump, not a registry get-or-create.
        # Registry.clear zeroes series in place, so the handles stay
        # valid across test resets.
        self._metric_handles = {
            key: [obs_metrics.counter(name, help)]
            for key, (name, help) in STAT_METRICS.items()
        }
        # Fleet-dashboard aliases: extra registry names incrementing in
        # lockstep with their primary (stats.py STAT_METRIC_ALIASES).
        for key, aliases in STAT_METRIC_ALIASES.items():
            self._metric_handles[key].extend(
                obs_metrics.counter(name, help) for name, help in aliases
            )
        # Touch every unlabeled series at 0 so the full catalog renders
        # from the first scrape — a counter that never fired (say,
        # tree branch-accepts on a cold engine) must read 0 on the
        # dashboard, not be indistinguishable from "not exported".
        for handles in self._metric_handles.values():
            for handle in handles:
                handle.inc(0)
        # Cumulative accept rate as a scrape-friendly gauge (the ratio
        # of two counters is a dashboard recording rule away, but spec
        # health is the first thing a tree-speculation rollout watches).
        # Last-write-wins and UNLABELED like the gauges below.
        self._spec_accept_gauge = obs_metrics.gauge(
            "tdt_spec_accept_rate",
            "Cumulative speculative accept rate (accepted / drafted) "
            "of this process's serving engine.",
        )
        # Last-write-wins and UNLABELED by design: a serving process
        # hosts one engine (ModelServer owns exactly one), so one
        # series is the truth there; with several engines in-process
        # (the test suite) the gauge flaps to whichever synced last —
        # a per-engine label would instead leak a series per engine
        # into the never-GC'd process registry (docs/observability.md).
        self._free_pages_gauge = obs_metrics.gauge(
            "tdt_engine_free_pages", "Pool pages on the free list."
        )
        # NS-amortization gauge: decode steps emitted per mega launch
        # over the current run — NS while every round launches fused,
        # sagging toward 1 as tail/filter fallbacks mix in.
        # Last-write-wins and UNLABELED like _free_pages_gauge above
        # (same rationale): one engine per serving process is the
        # deployment shape; an in-process replica fleet's scrape shows
        # the last replica to launch, and the per-replica truth rides
        # the stats verb's per-replica `mega_launches` counters.
        self._ns_gauge = obs_metrics.gauge(
            "tdt_mega_ns_amortization",
            "Decode steps per megakernel launch (current run).",
        )
        # Slot migration (docs/scale-out.md "Slot migration & handoff"):
        # ``snapshot_every=N`` (rounds, 0 = off) keeps an incremental
        # per-ticket snapshot buffer the server's ``export_slots`` verb
        # reads — the supervisor's crash-recovery feed. ``_handoff_at``
        # arms the lossless-drain sweep: at the first scheduling round
        # >= it, every active slot exports instead of finishing here.
        # MoE serving (docs/serving.md "MoE serving"): top_k per routed
        # token position — 0 for dense models; gates the
        # moe_routed_tokens bumps and the last_stats expert keys.
        self._moe_k = (
            model.cfg.num_experts_per_tok
            if getattr(model.cfg, "num_experts", 0) else 0
        )
        if getattr(model.cfg, "experts_held", 0) or getattr(
                model.cfg, "kv_lora_rank", 0):
            from triton_distributed_tpu.models.paged_kv_cache import (
                kv_bytes_per_token,
            )

            obs_metrics.gauge(
                "tdt_moe_experts_held",
                "Routed experts whose weights this rank holds (of the "
                "router's num_experts).",
            ).set(model.cfg.experts_held or model.cfg.num_experts)
            obs_metrics.gauge(
                "tdt_kv_row_bytes",
                "Logical bytes of one token's cache row in one layer.",
            ).set(kv_bytes_per_token(self.cache) / model.cfg.num_layers)
        if self._recurrent:
            obs_metrics.gauge(
                "tdt_ssm_state_bytes_per_slot",
                "Bytes of one decode slot's recurrent state over all "
                "its layers, whatever the slot's context.",
            ).set(state_bytes_per_slot(self.cache))
            obs_metrics.gauge(
                "tdt_ssm_state_slots",
                "Decode slots that hold a recurrent state (max_batch).",
            ).set(max_batch)
        self.snapshot_every = int(snapshot_every)
        self._handoff_at: int | None = None
        self._round = 0
        self._snap_lock = threading.Lock()
        self._snapshots: dict[str, dict] = {}
        # Client-driven cancellation (docs/serving.md "Streaming &
        # cancellation"): ticket ids whose requests should tear down
        # at the next scheduling round. Written from ANY thread via
        # :meth:`cancel` (the server's cancel verb and the streaming
        # disconnect path land here mid-batch); consumed on the engine
        # thread by ``_apply_cancels``.
        self._cancel_lock = threading.Lock()
        self._cancelled: set[str] = set()
        self._m_migrations = obs_metrics.counter(
            "tdt_migrations_total",
            "Slots exported for migration, by reason.",
            labels=("reason",),
        )
        self._m_mig_bytes = obs_metrics.histogram(
            "tdt_migration_bytes",
            "KV payload bytes shipped per exported slot.",
            buckets=obs_metrics.SIZE_BUCKETS,
        )
        self._m_mig_handoff = obs_metrics.histogram(
            "tdt_migration_handoff_seconds",
            "Wall time from slot export to its import elsewhere.",
        )
        self._m_mig_saved = obs_metrics.counter(
            "tdt_migration_tokens_saved_total",
            "Generated tokens restored from a snapshot instead of "
            "re-generated (work a replay recovery would repeat).",
        )
        self._m_mig_fallbacks = obs_metrics.counter(
            "tdt_migration_fallbacks_total",
            "Snapshot imports that fell back to replay-from-prompt.",
        )
        # The schedule (docs/observability.md "Metric catalog"): the
        # rows each decode step carried, one edge a row so that a reader
        # can weight a bucket by its rows exactly, and every decoded
        # token's gap by what stood in it.
        self._m_step_rows = obs_metrics.histogram(
            "tdt_engine_step_rows",
            "Rows in flight of each decode step launched.",
            buckets=tuple(range(1, 129)),
        )
        self._m_token_gap = obs_metrics.histogram(
            "tdt_engine_token_gap_seconds",
            "Gap before each decoded token of a row, by what stood in "
            "it: an admission, a serially launched step, or nothing "
            "but a looked-ahead step.",
            labels=("after",),
        )
        # When each slot's row got its last token, and when an
        # admission (or a chunk of one) last ended: a row whose stamp
        # is the older had it in its gap. None: telemetry was off then.
        self._tok_t: list = [None] * max_batch
        self._admit_t: float | None = None
        ContinuousEngine._live.add(self)

    @staticmethod
    def _zero_stats() -> dict:
        return {
            "admitted": 0,
            "decode_steps": 0,
            "prefill_tokens": 0,
            "generated_tokens": 0,
            "prefill_chunks": 0,
            "prefix_hit_tokens": 0,
            "pages_cow_copied": 0,
            "admission_stalls": 0,
            "spec_verify_steps": 0,
            "spec_draft_tokens": 0,
            "spec_accepted_tokens": 0,
            "spec_rollback_tokens": 0,
            # Tree-speculation ledger (docs/serving.md "Speculative
            # decoding"): multi-branch rounds, drafted trie nodes,
            # cumulative drafted depth, and rounds whose accepted path
            # left the primary branch (the row-move commits).
            "spec_tree_rounds": 0,
            "spec_tree_nodes": 0,
            "spec_tree_depth": 0,
            "spec_tree_branch_accepts": 0,
            # Fault-tolerance ledger (docs/serving.md "Fault tolerance").
            "failed_requests": 0,
            "cancelled_requests": 0,
            "shed_requests": 0,
            "deadline_expired": 0,
            "nonfinite_logits": 0,
            "decode_faults": 0,
            # One-step lookahead of the single-step round (docs/
            # serving.md "The decode loop"): steps dispatched before
            # the step before's tokens were fetched, and slot-tokens of
            # such a step thrown away because the slot ended first.
            "lookahead_steps": 0,
            "lookahead_discarded": 0,
            # Megakernel fast-path ledger (mode="mega" only): fused
            # NS-step launches vs single-step fallback rounds.
            "mega_launches": 0,
            "mega_fallback_steps": 0,
            # Device task tracer: launches whose ring was decoded.
            "mega_trace_launches": 0,
            # Resident-decode ledger (docs/megakernel.md "Resident
            # decode"): in-kernel filtered rounds, device stop-token
            # retires, pipelined resident rounds, and bucket-program
            # launches.
            "mega_device_retires": 0,
            "mega_resident_rounds": 0,
            "mega_bucket_launches": 0,
            "mega_filtered_rounds": 0,
            # Slot-migration ledger (docs/scale-out.md "Slot migration
            # & handoff"): exports, imports, generated tokens restored
            # without re-generation, and imports that fell back to a
            # full replay from the prompt.
            "migrated_out": 0,
            "migrated_in": 0,
            "migrated_in_tokens": 0,
            "migration_fallbacks": 0,
            # MoE serving ledger (docs/serving.md "MoE serving"):
            # routed expert assignments (token positions through the
            # MoE FFN × top_k) and EP a2a drops — always 0 on the
            # lossless serving paths, surfaced so a capacity-mode EP
            # experiment can never hide overflow.
            "moe_routed_tokens": 0,
            "moe_decode_local_rows": 0,
            "moe_decode_experts_touched": 0,
            # Rows whose recurrent state a decode step advanced (a
            # model with recurrent layers only; docs/serving.md
            # "Recurrent state beside pages").
            "ssm_decode_rows": 0,
            "a2a_dropped": 0,
            # Durable KV tier ledger (docs/serving.md "Tiered KV"):
            # evictions demoted to the tier, and admissions extended by
            # faulting those pages back instead of re-prefilling.
            "tier_spilled_pages": 0,
            "tier_hits": 0,
            "tier_faults": 0,
            "tier_bytes": 0,
            # KV fabric (docs/scale-out.md "KV fabric"): the subset of
            # tier_faults whose entry came from a PEER replica's tier.
            "tier_remote_pages": 0,
        }

    @property
    def last_stats(self) -> dict:
        """Serving counters (parity: ``Engine.last_stats``): admission /
        prefill work done, prefix-cache reuse, COW copies, stalls, the
        speculative accept/rollback ledger, and the fault-tolerance
        ledger (failed/shed/expired requests, non-finite logits,
        isolated decode faults)."""
        stats = dict(self.stats)
        stats["free_pages"] = len(self.pool.free)
        from triton_distributed_tpu.models.paged_kv_cache import (
            kv_bytes_per_token,
        )

        stats["kv_bytes_per_token"] = kv_bytes_per_token(self.cache)
        if self._recurrent:
            stats["state_bytes_per_slot"] = state_bytes_per_slot(self.cache)
            stats["state_slots"] = self.max_batch
        stats["kv_dtype"] = (
            self.kv_dtype or str(jnp.dtype(self.cache.k_pages.dtype))
        )
        if self.prefix is not None:
            stats["prefix_cache"] = dict(self.prefix.stats)
            stats["prefix_hit_rate"] = self.prefix.hit_rate
            stats["tree_pages"] = self.prefix.node_count
        if self.speculative:
            stats["spec_accept_rate"] = (
                stats["spec_accepted_tokens"]
                / max(stats["spec_draft_tokens"], 1)
            )
            # Target forwards actually paid for decode: batched steps
            # plus per-slot verify chunks (mega's NS-per-launch counting
            # never mixes in — speculative excludes mega at the ctor).
            stats["target_steps"] = (
                stats["decode_steps"] + stats["spec_verify_steps"]
            )
        if self._moe_k:
            stats["num_experts"] = self.model.cfg.num_experts
            stats["experts_per_tok"] = self._moe_k
        if self.tier is not None:
            stats["tier"] = self.tier.snapshot()
        if self.fabric is not None:
            stats["fabric"] = self.fabric.snapshot()
        return stats

    # -- telemetry ---------------------------------------------------------

    def _bump(self, key: str, n: int = 1) -> None:
        """Increment a serving counter in ``stats`` AND its mirrored
        registry metric, so the same number is visible per-run
        (``last_stats``) and fleet-wide (``{"cmd": "metrics"}``).
        ``inc`` no-ops when telemetry is disabled."""
        self.stats[key] += n
        for handle in self._metric_handles[key]:
            handle.inc(n)

    @staticmethod
    def _gap_clock() -> float | None:
        """Now, for a row's token stamp; None with telemetry off (no
        clock is read for a histogram that would not move)."""
        if obs_metrics.default_registry().enabled:
            return time.monotonic()
        return None

    def _finish_obs(self, req: Request) -> None:
        """Latch a request's terminal timeline stamp and fold it into
        the latency histograms — exactly once per request, whichever
        teardown path gets here first."""
        tl = req.timeline
        if tl is None:
            return
        tl.tokens_in = len(req.prompt)
        tl.tokens_out = len(req.out)
        if tl.finish(req.status):
            observe_request(tl)

    # -- slot management -------------------------------------------------

    def _sync_tables(self) -> None:
        self._free_pages_gauge.set(len(self.pool.free))
        # COPIES, not views: ``jnp.asarray`` on the CPU backend may
        # zero-copy an aligned numpy array, so the device "buffer"
        # aliases the live host array — and this engine mutates
        # ``_table``/``_kv_len`` in place while async-dispatched
        # launches still read them (the decode then races host
        # bookkeeping; observed as run-to-run token flips whose
        # probability scaled with host work between dispatch and the
        # first output fetch). Explicit copies give the device arrays
        # their own storage.
        # Committed to the mesh like a step's own outputs: a step then
        # has ONE signature whether its table comes from here or from
        # the step before (an uncommitted table met by on-device tokens,
        # the round after a slot ended under a step in flight, would
        # compile the step once more in the middle of serving).
        put = self.model.ctx.replicate
        state = {}
        if self._recurrent:
            # The rows IN FLIGHT, which alone a decode step may advance:
            # ``_slots`` holds a request from the end of its admission
            # (a slot between two of its chunks is mapped above and not
            # in flight) to its end.
            state["live"] = put(
                np.asarray([r is not None for r in self._slots]))
        self.cache = dataclasses.replace(
            self.cache,
            page_table=put(self._table.copy()),
            kv_len=put(self._kv_len.copy()),
            **state,
        )

    def _admit(
        self, req: Request, slot: int, m: PrefixMatch | None = None
    ):
        """Prefill ``req`` into ``slot``; returns the first sampled
        token — or None when ``req`` carried a migration snapshot and
        was RESUMED instead (its pending token is already in ``out``;
        the next scheduling round continues decoding it)."""
        fault_point("engine.admit", slot=slot)
        if req.timeline is not None:
            req.timeline.stamp_admit()
        if req.snapshot is not None or req.prefill_only:
            self._refuse_slot_export("a migrated or prefill-only request")
        if req.snapshot is not None:
            return self._admit_import(req, slot)
        if self.prefix is not None:
            return self._admit_prefix(req, slot, m)
        s = len(req.prompt)
        n = self.model.ctx.axis_size(self.model.axis)
        pad = (-s) % n
        row = np.concatenate([req.prompt, np.zeros(pad, np.int32)])
        need = self._needed_pages(s, req.gen_len)
        req.slot = slot  # before any allocation: teardown keys off it
        req.pages = self.pool.allocate(need)
        self._table[slot] = 0
        self._table[slot, : len(req.pages)] = req.pages
        self._kv_len[slot] = s
        self._sync_tables()

        if req.timeline is not None:
            req.timeline.stamp_first_chunk()
        logits, self._dense1 = self.model.prefill_batched(
            jnp.asarray(row[None]), self._dense1, self._prefill_mode,
            jnp.asarray([s], jnp.int32),
        )
        self.cache = write_prefill(
            self.cache, slot, self._dense1.k, self._dense1.v, s
        )
        self._bump("admitted")
        self._bump("prefill_tokens", s)
        if self._moe_k:
            self._bump("moe_routed_tokens", s * self._moe_k)
        # Emitted HERE, aligned with the `admitted` counter — a failed
        # allocation/prefill must not leave a phantom admit event for
        # consumers correlating admits against counters or evicts.
        obs_events.emit("admit", slot=slot, prompt_len=s, matched=0,
                        trace_id=req.trace_id)
        self._slots[slot] = req
        return self._sample_req(req, logits[0])

    def _admit_prefix(
        self, req: Request, slot: int, m: PrefixMatch
    ) -> jax.Array:
        """Prefix-cache admission: map the matched prefix pages into the
        slot's table row, COW-clone a partially matched tail, then
        chunk-prefill only the suffix."""
        s = len(req.prompt)
        total = self._needed_pages(s, req.gen_len)
        req.slot = slot  # before any allocation: teardown keys off it
        new_pages = self.prefix.allocate(total - len(m.nodes))
        assert new_pages is not None, "try_admit availability check failed"
        matched = m.matched_len
        req.pages = m.pages + new_pages
        req.shared_nodes = list(m.nodes)
        # Pins now ride on the request: the admission failure handler
        # releases m's REMAINING pins, the slot teardown releases the
        # request's — moving them here keeps each pin owned exactly once.
        m.nodes = []
        self._table[slot] = 0
        self._table[slot, : len(req.pages)] = req.pages
        if m.cow_len:
            # The partially matched page becomes this request's first
            # private page: clone it, count only the matched positions.
            self.cache = copy_page(self.cache, m.cow_node.page, new_pages[0])
            self._bump("pages_cow_copied")
            obs_events.emit("cow", slot=slot, matched=m.cow_len)
        self.prefix.finish_cow(m)
        self._kv_len[slot] = matched
        self._sync_tables()
        if req.timeline is not None:
            req.timeline.stamp_first_chunk()
        with trace_span(
            "prefix_cache:admit", slot=slot, prompt_len=s, matched=matched
        ):
            logits = self._prefill_suffix(slot, req.prompt, matched)
        # Counted/emitted only AFTER the suffix prefill: a chunk that
        # fails must not leave a phantom admit event or `admitted`
        # count (the same contract the non-prefix path states above).
        self._bump("admitted")
        self._bump("prefix_hit_tokens", matched)
        obs_events.emit("admit", slot=slot, prompt_len=s, matched=matched,
                        trace_id=req.trace_id)
        self._slots[slot] = req
        return self._sample_req(req, logits)

    def _admit_import(self, req: Request, slot: int):
        """Resume a migrated request from its snapshot: import the
        portable slot state (``models/slot_state.py``) into ``slot``
        instead of re-prefilling. Returns None (the pending token is
        already the tail of ``out``). Import failures — geometry or
        dtype mismatch, a stale prefix delta, an injected
        ``migrate.import`` fault — fall back to a FULL REPLAY from the
        prompt: correct (the engine is deterministic), just without the
        saved work. A failure after pages were claimed unwinds through
        the standard crash-safe teardown first."""
        from triton_distributed_tpu.models import slot_state

        snap_wire = req.snapshot
        snap = None
        try:
            if (isinstance(snap_wire, dict)
                    and self._tier_fp is not None
                    and "model_fp" in snap_wire
                    and snap_wire["model_fp"] != self._tier_fp):
                # Produced under different weights (a resume store or
                # tier_dir that outlived a checkpoint swap): old-weight
                # KV continued under new weights is wrong bits — take
                # the replay fallback below instead.
                raise slot_state.SnapshotStaleError(
                    "snapshot was produced under different model weights"
                )
            snap = (
                slot_state.SlotSnapshot.from_wire(snap_wire)
                if isinstance(snap_wire, dict) else snap_wire
            )
            slot_state.import_slot(self, req, snap, slot)
        except Exception as e:  # noqa: BLE001 — fallback boundary
            if req.slot is not None:
                # Pages/pins were claimed before the failure: release
                # them the same way any crashed slot does.
                self._teardown_slot(req)
            req.snapshot = None
            req.out = []
            # Replay under the snapshot's OWN key from draw 0: the
            # replay then makes exactly the draws the original run
            # made, keeping even the fallback bit-exact for seeded
            # sampling (a fresh engine-split key would not).
            if snap is not None and snap.key_data is not None:
                req.key = jax.random.wrap_key_data(
                    jnp.asarray(snap.key_data)
                )
            req.key_step = 0
            self.stats["migration_fallbacks"] += 1
            self._m_mig_fallbacks.inc()
            obs_events.emit(
                "migrate_fallback", slot=slot,
                reason=f"{type(e).__name__}: {str(e)[:160]}",
                trace_id=req.trace_id,
            )
            m = self.prefix.match(req.prompt) if self.prefix else None
            try:
                return self._admit(req, slot, m)
            except Exception as e2:  # noqa: BLE001 — isolation boundary
                # The replay admission failed too: release the match
                # pins and fail ONLY this request, exactly as a direct
                # admission failure would (the caller reads the status).
                self._admit_failure(req, m, e2)
                return None
        req.snapshot = None
        self._sync_tables()
        self._bump("admitted")
        self.stats["migrated_in"] += 1
        self.stats["migrated_in_tokens"] += len(req.out)
        self._m_mig_saved.inc(len(req.out))
        if snap.exported_at:
            self._m_mig_handoff.observe(
                max(time.time() - snap.exported_at, 0.0)
            )
        obs_events.emit(
            "migrate_in", slot=slot, tokens_out=len(req.out),
            kv_tokens=int(self._kv_len[slot]),
            from_prefix_pages=int(snap.from_prefix_pages),
            trace_id=req.trace_id,
        )
        return None

    def _prefill_suffix(self, slot: int, prompt: np.ndarray, start: int):
        """Chunk-prefill ``prompt[start:]`` into ``slot``'s pages,
        stepping the running batch between chunks (chunked prefill:
        admission never stalls in-flight decodes for a full prefill).
        Returns the last real token's logits ``[V]``."""

        def between_chunks(cache, new_len):
            # Device kv_len is set absolutely by the chunk program, so
            # host and device agree even after interleaved decode steps
            # bumped the in-flight slot's device counter.
            self.cache = cache
            self._kv_len[slot] = new_len
            self._admit_t = self._gap_clock()  # a chunk stood in the gap
            if self._step_guard(self._decode_once):
                # An interleaved decode finished (or failed) a request:
                # its pages retired/released, and the device table must
                # drop them BEFORE the next chunk, or the stale row's
                # append would corrupt a cached page.
                self._sync_tables()
            return self.cache

        logits, self.cache, chunks = prefill_suffix_chunks(
            self.model, self.cache, slot, prompt, start,
            self.prefill_chunk, self._prefill_mode, between_chunks,
        )
        self._kv_len[slot] = len(prompt)
        self._bump("prefill_tokens", len(prompt) - start)
        self._bump("prefill_chunks", chunks)
        if self._moe_k:
            self._bump("moe_routed_tokens",
                       (len(prompt) - start) * self._moe_k)
        return logits

    def _decode_once(self) -> bool:
        """One single-step decode of every active slot; appends sampled
        tokens and evicts finished requests. Returns whether slot state
        changed (caller decides when to re-admit/sync)."""
        active = np.asarray([r is not None for r in self._slots], np.int32)
        if not active.any():
            return False
        n_active = int(active.sum())
        with self._round_span(n_active):
            return self._decode_round(active, n_active)

    def _round_span(self, active: int):
        """The span of one scheduling round of the in-flight batch,
        whatever kind: single step, speculative verify, mega launch."""
        return trace_span("engine:decode_round", active=active,
                          step=self.stats["decode_steps"], _ring=False)

    def _decode_round(self, active: np.ndarray, n_active: int) -> bool:
        """The round itself, in the three phases its spans name: a
        step's dispatch, the blocking fetch, and the host's sampling
        and token frames.

        The step whose tokens this round emits is the one a round
        before looked ahead to and parked in ``_pend``, or, with none
        parked, one dispatched here from the host's ``_tok``. Before
        its tokens are fetched the NEXT step is dispatched off its
        on-device greedy tokens whenever that is exactly what the
        serial loop would run next (:meth:`_may_look_ahead`): the
        device then finds its next step queued, and the host's fetch,
        NaN guard, slot walk and token frames overlap it."""
        step, self._pend = self._pend, None
        with trace_span("engine:dispatch", _ring=False):
            if step is None:
                # A serial round: the device waits for this upload and
                # launch, and by this span a trace's reader finds it.
                with trace_span("engine:serial_launch", _ring=False):
                    step = self._launch_step(
                        self.model.ctx.replicate(self._tok.copy()), active,
                        n_active
                    )
            if self._may_look_ahead(step):
                # Parked BEFORE the emit below: whatever that raises
                # reaches the step guard with the in-flight step still
                # owned, so ``_abort_pend`` blocks on it before the
                # teardown frees pages it appends to.
                self._pend = self._launch_step(step.toks, active, n_active)
                self._pend.ahead = True
                self._bump("lookahead_steps")
        return self._emit_step(step)

    def _may_look_ahead(self, step: _StepLaunch) -> bool:
        """Whether the step AFTER ``step`` may be dispatched before
        ``step``'s tokens reach the host: only when it is exactly what
        the serial loop would run next for the slots that go on. Its
        input must already be on the device (every live slot decodes
        greedily and was in ``step`` with the same request), the slot
        set must be known (no admission is mid-prefill), somebody must
        go on (a step whose every live slot reaches ``gen_len`` with
        ``step``'s token would be run for nobody) and nobody may wait
        for the slot of one that ends (the serial loop would admit it
        into that very step), and nothing may sit
        between a step and its tokens (a speculative plan, mega rounds
        planned from host truth, an armed ``FaultPlan``). A slot that
        ends with ``step``'s token, by its length as by what the host
        cannot foresee (a stop token, a non-finite row, a cancel, a
        deadline), costs its row of the in-flight step, never an
        emitted token (:meth:`_emit_step`). That row also advances the
        slot's recurrent state, where the model keeps one, and that is
        sound ONLY because the slot has ENDED: nobody reads its state
        again (the next admission starts from zeros), while a slot that
        goes on was in ``step`` with the same request and is advanced
        exactly once a token. With 32 slots a request
        ends every tenth round, and a round that waits for it leaves
        the device idle for the host's 6 ms (PERF.md "PR 35")."""
        if (self.speculative or self.mode == "mega"
                or active_plan() is not None):
            return False
        goes_on = ends = False
        for slot, req in enumerate(self._slots):
            if req is None:
                if self._table[slot, 0]:
                    # Mapped before ``_slots`` holds it: an admission is
                    # prefilling into this row between our rounds.
                    return False
                continue
            if (req is not step.reqs[slot]
                    or self._request_sampling(req)[0] > 0.0):
                return False
            if len(req.out) + 1 < req.gen_len:
                goes_on = True
            else:
                ends = True
        # A slot that ends leaves its row of the next step unused. With
        # a request waiting for that slot the serial loop admits it
        # first and runs the next step WITH it: looking ahead would
        # spend a step more.
        return goes_on and not (ends and self._waiting)

    def _launch_step(self, tok, active: np.ndarray,
                     n_active: int) -> _StepLaunch:
        """Dispatch one decode step on ``tok [max_batch]`` (the host's
        ``_tok`` uploaded, or the step before's greedy tokens still on
        the device) and the program that reduces its logits to a finite
        mask and the greedy tokens. Nothing here waits for the device."""
        fault_point("engine.decode", step=self.stats["decode_steps"])
        logits, self.cache, *counts = (
            self._decode_step(tok, self.cache) if self._counted_step is None
            else self._counted_step(tok, self.cache, self.mode))
        logits = mutate_point(
            "engine.logits", logits, step=self.stats["decode_steps"]
        )
        # Rebind, never ``+=`` — the zero-copy-alias discipline of
        # ``_sync_tables`` (an in-place add between an async dispatch
        # and its first fetch raced the device's kv_len read).
        self._kv_len = self._kv_len + active
        self._bump("decode_steps")
        self._m_step_rows.observe(n_active)
        if self._moe_k:
            self._bump("moe_routed_tokens", n_active * self._moe_k)
        # One device program computes the finite mask AND the greedy
        # base tokens, so the NaN guard adds no extra host-sync round
        # trip to the hot decode loop.
        finite, toks = tdt_finite_greedy(logits)
        return _StepLaunch(list(self._slots), logits, finite, toks, *counts)

    def _emit_step(self, step: _StepLaunch) -> bool:
        """Fetch one dispatched step's tokens and emit them through the
        normal guard / sample / retire paths. Returns whether slot
        state changed. A slot that ended since the dispatch is ``None``
        by now, so every walk below skips it: its token is dropped."""
        with trace_span("engine:fetch", _ring=False):
            finite, toks = step.fetch()
        self._release_ended()  # the device has left ``step``
        if step.counts is not None:
            for key, count in zip(self.model.step_counts, step.counts):
                self._bump(key, int(count))
        ended = sum(r is not None and self._slots[s] is not r
                    for s, r in enumerate(step.reqs))
        if ended:
            self._bump("lookahead_discarded", ended)
        if self._pend is not None and any(
                r is not None and not finite[s]
                for s, r in enumerate(self._slots)):
            # The guard below tears a non-finite slot down BEFORE the
            # round's frames go out, while the next step is in flight
            # and appending to its pages: the device leaves that step
            # first. (A slot that ends ON this round's token, by its
            # length or a stop token, ends after the frames:
            # ``_process``.)
            self._pend.fetch()
        # One token per live slot, less the slots the guard fails.
        with trace_span("engine:sample_emit",
                        emitted=sum(r is not None for r in self._slots),
                        _ring=False):
            failed = self._guard_logits(finite)
            nxt = self._sample_slots(step.logits, toks)
            changed = self._process(lambda slot: [nxt[slot]],
                                    "ahead" if step.ahead else "serial")
        return changed or bool(failed)

    def _guard_logits(self, finite: np.ndarray) -> list[int]:
        """Per-slot NaN/Inf guard on a batched decode output: fail ONLY
        the slots whose logits went non-finite (structured
        ``nan_logits`` error, counted in ``last_stats``) instead of
        silently sampling garbage. ``finite`` is the per-slot
        all-finite mask. Returns the failed slot indices."""
        failed = []
        for slot, req in enumerate(self._slots):
            if req is None or bool(finite[slot]):
                continue
            self._bump("nonfinite_logits")
            self._fail(
                req, "nan_logits",
                f"non-finite logits at decode step "
                f"{self.stats['decode_steps']} after {len(req.out)} tokens",
            )
            failed.append(slot)
        return failed

    def _process(self, slot_tokens, after: str = "serial") -> bool:
        """Append per-slot tokens; evict on gen_len/eos. Returns whether
        slot state changed. ``after`` says what stood in the gap before
        these tokens (``tdt_engine_token_gap_seconds``): the round's
        step was launched ``serial``ly, or looked ``ahead`` to; an
        admission since a row's last token outranks both for that row."""
        finished = []
        emitted = 0
        now = self._gap_clock()
        since: dict = {}  # a row's last token's stamp -> rows with it
        burst = 0  # tokens beyond a row's first of this call
        for slot, req in enumerate(self._slots):
            if req is None:
                continue
            before = emitted
            for t in slot_tokens(slot):
                req.out.append(int(t))
                self._emit_token(req)
                emitted += 1
                self._tok[slot] = int(t)
                if req.spec is not None:
                    req.spec.observe((int(t),))
                if self._ends(req, int(t)):
                    finished.append(req)
                    break
            if emitted > before:
                last, self._tok_t[slot] = self._tok_t[slot], now
                if now is not None and last is not None:
                    since[last] = since.get(last, 0) + 1
                    burst += emitted - before - 1
        if emitted:
            self._bump("generated_tokens", emitted)
            # The gap is the ROW's own: rows of one stamp (all that
            # were in the round before) go in together; what a burst
            # holds beyond a row's first token follows it at once.
            admit_t = self._admit_t
            for last, rows in since.items():
                self._m_token_gap.observe_n(
                    now - last, rows,
                    after="admit" if admit_t is not None and last < admit_t
                    else after)
            if burst:
                self._m_token_gap.observe_n(0.0, burst, after=after)
        # Every slot's frames are out. A step looked ahead to is still
        # appending to the finished slots' pages: it stays parked, the
        # slots' tokens in it are dropped at its own drain, and the
        # pages go back when the device has left it (``_evict``). The
        # host waits for nothing here: waiting for that step before the
        # frames held every slot's token back by a whole step (30.6 ms
        # for 15.0 in a tenth of the rounds at 32 slots), waiting after
        # them left the device idle for the host's 2.3 ms (PERF.md
        # "PR 35").
        after = self._pend if isinstance(self._pend, _StepLaunch) else None
        for req in finished:
            self._evict(req, after)
        return bool(finished)

    def _evict(self, req: Request, after: _StepLaunch | None = None) -> None:
        """End a finished request's slot. With ``after``, a looked-ahead
        step still in flight that appends to the slot's pages, the slot
        is free at once (the next step is dispatched on a table that
        no longer maps it) and the pages go back when the device has
        left ``after``: :meth:`_release_ended`, at that step's fetch, a
        round later and behind the step after it."""
        slot = req.slot
        self._finish_obs(req)  # status "ok": _evict only runs on success
        obs_events.emit("evict", slot=slot, tokens_out=len(req.out))
        self._table[slot] = 0  # back to the trash page
        self._kv_len[slot] = 0
        self._slots[slot] = None
        req.slot = None
        if after is not None and after.host is None:
            self._ended.append((req, after))
        else:
            self._release(req)

    def _release(self, req: Request) -> None:
        """A finished request's pages go back: to the radix tree, or to
        the pool. No step in flight may still append to them."""
        if self.prefix is not None:
            self._retire_to_prefix(req)
        else:
            # Full truncation: every private page goes back to the pool
            # (the 0-token case of the speculative rollback helper).
            truncate_pages(self.pool, req.pages, 0, self.page_size)
        req.pages = []

    def _release_ended(self, every: bool = False) -> None:
        """Release the pages of the requests whose slot ended under a
        step the device has left by now (``every``: nothing is in
        flight any more)."""
        if not self._ended:
            return
        waiting = []
        for req, step in self._ended:
            if every or step.host is not None:
                self._release(req)
            else:
                waiting.append((req, step))
        self._ended = waiting

    # -- failure isolation -----------------------------------------------

    def _fail(self, req: Request, status: str, reason) -> None:
        """Fail ONE request: record the structured error and, if it
        holds a slot, tear that slot down. Everything else keeps
        serving. A client-initiated ``cancelled`` rides the same
        teardown but its own counter — a cancellation is not a server
        failure."""
        req.status, req.reason = status, str(reason)
        if status == "cancelled":
            self._bump("cancelled_requests")
        else:
            self._bump("failed_requests")
        if status == "deadline_exceeded":
            self._bump("deadline_expired")
        elif status == "overloaded":
            self._bump("shed_requests")
        if req.slot is not None:
            self._teardown_slot(req)
        obs_events.emit(
            _FAIL_EVENT_KIND.get(status, "request_failed"),
            status=status, tokens_out=len(req.out),
            reason=str(reason)[:200],
        )
        self._finish_obs(req)

    def _teardown_slot(self, req: Request) -> None:
        """Crash-safe slot release: private pages to the pool, shared
        prefix pins back to the tree (the TREE owns those pages — they
        must not be freed here), table row back to the trash page.

        Unlike ``_evict`` nothing is donated to the prefix tree: a
        failed request's KV is suspect (non-finite logits, a partial
        verify chunk) and caching it would poison later matches."""
        slot = req.slot
        truncate_pages(
            self.pool, req.pages, 0, self.page_size,
            shared=len(req.shared_nodes),
        )
        if self.prefix is not None:
            for node in req.shared_nodes:
                self.prefix.release_node(node)
        req.shared_nodes = []
        req.pages = []
        self._table[slot] = 0
        self._kv_len[slot] = 0
        self._slots[slot] = None
        req.slot = None

    def _admit_failure(self, req: Request, m: PrefixMatch | None, e) -> None:
        """Clean up a failed admission: release whatever prefix pins
        were NOT yet transferred to the request, tear down any slot
        state it acquired, mark it failed, and resync the device
        table — the engine stays serviceable."""
        if self.prefix is not None and m is not None:
            if m.cow_node is not None:
                self.prefix.release_node(m.cow_node)
                m.cow_node = None
            for node in m.nodes:
                self.prefix.release_node(node)
            m.nodes = []
        status = "failed"
        if isinstance(e, _NonFiniteLogits):
            status = "nan_logits"
            self._bump("nonfinite_logits")
        self._fail(req, status, f"{type(e).__name__}: {e}")
        self._sync_tables()

    def _step_guard(self, fn) -> bool:
        """Run one decode-phase step with per-request error isolation:
        an exception carrying a ``slot`` attribute (injected faults,
        slot-attributable guards) fails exactly that request; anything
        else poisons the whole in-flight set — every active request
        gets a structured error and a clean teardown, and the engine
        (slots, pool, tree, device table) remains reusable. Returns
        whether slot state changed."""
        try:
            return fn()
        except Exception as e:  # noqa: BLE001 — isolation boundary
            self._bump("decode_faults")
            # A fault mid-resident-round may leave a launch in flight;
            # block on it before the teardown below reuses its state.
            self._abort_pend()
            slot = getattr(e, "slot", None)
            if (isinstance(slot, int) and 0 <= slot < self.max_batch
                    and self._slots[slot] is not None):
                victims = [self._slots[slot]]
            else:
                victims = [r for r in self._slots if r is not None]
            for r in victims:
                self._fail(r, "failed", f"{type(e).__name__}: {e}")
            self._sync_tables()
            return True

    def _emit_token(self, req: Request) -> None:
        """Streaming hook: hand the just-appended token to the
        request's ``on_token`` sink. A raising sink detaches itself —
        a client that vanished mid-stream must never fail the request
        through its own callback (the server's disconnect path cancels
        it explicitly instead)."""
        cb = req.on_token
        if cb is None:
            return
        try:
            cb(len(req.out) - 1, int(req.out[-1]))
        except Exception:  # noqa: BLE001 — sink isolation boundary
            req.on_token = None

    # -- client-driven cancellation (docs/serving.md) ----------------------

    def cancel(self, ticket_ids) -> None:
        """Request cancellation of the given ticket ids — thread-safe
        (a set add under its own lock; the server's cancel verb and
        the streaming disconnect path call this MID-batch). Applied at
        the next scheduling round: queued requests fail before
        admission, in-flight slots tear down through the standard
        crash-safe path with status ``cancelled`` and their partial
        tokens. Ids matching nothing in the current batch are pruned
        when the batch ends — a cancel racing a slot's natural finish
        simply loses (the tokens were already emitted). Ids stay
        ARMED until consumed or batch-pruned, deliberately: a cancel
        may legitimately beat its request here. The flip side is the
        contract that ticket ids are request IDENTITIES — a client
        that cancels ``job1`` and then submits a NEW request reusing
        ``job1`` may see the armed cancel apply to it; never reuse
        ids across requests (docs/serving.md)."""
        ids = {str(t) for t in ticket_ids}
        if not ids:
            return
        with self._cancel_lock:
            self._cancelled |= ids
        obs_events.emit("cancel", requested=len(ids))

    def _apply_cancels(self, queue: deque) -> bool:
        """Consume pending cancellations against the queue and the
        active slots. Returns whether slot state changed. The
        ``engine.cancel`` fault seam sits between the snapshot and the
        application so chaos tests can sequence a cancel deterministically
        against a finishing slot."""
        with self._cancel_lock:
            if not self._cancelled:
                return False
            pending = set(self._cancelled)
        fault_point("engine.cancel", pending=len(pending))
        if self._pend is not None:
            # Cancellation tears slots down — the in-flight launch
            # still reads their table rows; sync first.
            self._settle_pend()
        consumed: set[str] = set()
        changed = False
        for r in list(queue):
            if r.ticket_id is not None and r.ticket_id in pending:
                queue.remove(r)
                consumed.add(r.ticket_id)
                self._fail(
                    r, "cancelled", "cancelled by client before admission"
                )
        for req in list(self._slots):
            if req is None or req.ticket_id is None:
                continue
            if req.ticket_id in pending:
                consumed.add(req.ticket_id)
                self._fail(
                    req, "cancelled",
                    f"cancelled by client after {len(req.out)} generated "
                    "tokens",
                )
                changed = True
        if consumed:
            with self._cancel_lock:
                self._cancelled -= consumed
        return changed

    def _expire_deadlines(self) -> bool:
        """Fail every active request whose wall-clock deadline passed
        (structured ``deadline_exceeded`` + partial tokens). Returns
        whether slot state changed."""
        now = time.monotonic()
        if self._pend is not None and any(
                r is not None and r.deadline_at is not None
                and now > r.deadline_at for r in self._slots):
            # The expiry is about to tear a slot down mid-pipeline;
            # sync first (a resident launch's drain may even finish it
            # naturally).
            self._settle_pend()
        changed = False
        for req in list(self._slots):
            if req is None or req.deadline_at is None:
                continue
            if now > req.deadline_at:
                self._fail(
                    req, "deadline_exceeded",
                    f"deadline_s={req.deadline_s} exceeded after "
                    f"{len(req.out)} generated tokens",
                )
                changed = True
        return changed

    # -- sampling ---------------------------------------------------------

    def _retire_to_prefix(self, req: Request) -> None:
        """Donate the finished request's KV pages to the radix tree.

        Valid KV covers positions ``[0, s + len(out) - 1)`` — the last
        sampled token was never fed back, so its KV was never appended
        (and multi-step overshoot rows beyond it hold discarded-token
        garbage the retire chunking never references)."""
        gen_cached = max(len(req.out) - 1, 0)
        toks = np.concatenate(
            [req.prompt, np.asarray(req.out[:gen_cached], np.int32)]
        )
        self.prefix.retire_sequence(toks, req.pages, req.shared_nodes)
        req.shared_nodes = []

    # -- durable KV tier (docs/serving.md "Tiered KV") --------------------

    def _spill_page(self, chain: list, page: int) -> None:
        """``PrefixCache.spill_fn``: export one evicted full page to
        the tier, keyed by its token-chain digest — byte-exact via
        ``gather_pages`` (int8 codes + per-page scales travel as a
        pair). Raising is fine: eviction treats any spill failure as
        the pre-tier drop."""
        from triton_distributed_tpu.models import kv_tier

        k, v, ks, vs = gather_pages(self.cache, [page])
        payload = kv_tier.prefix_payload(
            chain, self.page_size, self.kv_dtype,
            k[:, 0], v[:, 0],
            None if ks is None else ks[:, 0],
            None if vs is None else vs[:, 0],
        )
        payload["model_fp"] = self._tier_fp
        if self.tier.put(kv_tier.PREFIX_KIND, kv_tier.chain_digest(chain),
                         payload):
            self._bump("tier_spilled_pages")
            obs_events.emit("tier_spill", tokens=len(chain), page=int(page))

    def tier_digest(self) -> dict | None:
        """The tier's compact content summary wrapped with this
        engine's page size — what replicas publish next to
        ``prefix_digest()`` at batch boundaries so the router can score
        tier affinity and peers can gate fabric probes. None without a
        tier. Memoized inside the store (mutation-counter keyed), so a
        per-batch call is a dict copy, not a scan."""
        if self.tier is None:
            return None
        return {"ps": int(self.page_size), **self.tier.digest()}

    def _tier_fill(self, tokens) -> None:
        """Fault-back half of the tier: extend the radix tree's
        coverage of ``tokens`` from the tier BEFORE admission matches —
        each hit page is re-allocated, written verbatim via
        ``write_page`` (cheaper than re-prefilling it), and grafted
        back into the tree where ``match()`` then pins it exactly like
        any cached page. Stops at the first miss, divergence, or
        allocation failure; every failure path degrades to the
        ordinary suffix prefill — never wrong bits."""
        if self.tier is None or self.prefix is None:
            return
        from triton_distributed_tpu.models import kv_tier

        fabric = self.fabric
        if fabric is not None and not fabric.peers:
            fabric = None
        if not self.tier.may_contain(kv_tier.PREFIX_KIND) and fabric is None:
            # Nothing has ever spilled (the steady state before the
            # first eviction) and no fabric peer to ask: skip the
            # per-round tree walk + SHA-1 over the uncovered prefix —
            # guaranteed misses. The queue head re-runs this every
            # scheduling round it waits. With peers attached the walk
            # must run: a cold LOCAL tier is exactly when a neighbor's
            # entries are worth pulling (the warm-boot path).
            return
        ps = self.page_size
        toks = [int(t) for t in tokens]
        limit = len(toks) - 1  # match()'s cap: one suffix token prefills
        node = self.prefix.root
        i = 0
        faulted = bytes_in = remote = 0
        # The walked path is refcount-PINNED for the fill's duration:
        # each faulted page's allocation may itself run the LRU
        # eviction sweep, which would otherwise happily evict (and
        # re-spill) the very nodes this prompt is about to match —
        # observed as a fill that fed its own allocations. Pins release
        # before match() takes its own.
        pinned: list = []
        try:
            while i + ps <= limit:
                chunk = toks[i:i + ps]
                child = node.children.get(chunk[0])
                if child is not None:
                    if tuple(chunk) == child.chunk:
                        node = child
                        node.refcount += 1
                        pinned.append(node)
                        i += ps
                        continue
                    break  # divergent/partial sibling: the tree wins
                digest = kv_tier.chain_digest(toks[: i + ps])
                payload = self.tier.get(kv_tier.PREFIX_KIND, digest)
                from_fabric = False
                if payload is None and fabric is not None:
                    # Local miss → peer fault-back: the fabric returns
                    # a payload already CRC/header-validated through
                    # the SAME codec a local read crosses; the
                    # chain/geometry/fingerprint checks below then run
                    # UNCHANGED — a remote entry can fail them exactly
                    # like a local one, and fails to re-prefill.
                    payload = fabric.fetch(kv_tier.PREFIX_KIND, digest)
                    from_fabric = payload is not None
                if payload is None:
                    break
                try:
                    chain, page_size, kv_dtype, k, v, ks, vs = (
                        kv_tier.decode_prefix_payload(payload)
                    )
                except kv_tier.TierIntegrityError:
                    if not from_fabric:  # nothing local to delete
                        self.tier.delete(kv_tier.PREFIX_KIND, digest)
                    break
                if (chain != toks[: i + ps] or page_size != ps
                        or kv_dtype != self.kv_dtype
                        or payload.get("model_fp") != self._tier_fp):
                    # Digest collision, a foreign-geometry entry, or a
                    # page produced under DIFFERENT weights (a reused
                    # tier_dir across a checkpoint swap): never fault
                    # it back — that would map wrong KV under this
                    # chain. The admission re-prefills. Deleting is
                    # owner-only: on a SHARED store (``tier=``) the
                    # entry may be perfectly valid for the engine that
                    # spilled it, and a fabric-pulled entry lives on
                    # the PEER — nothing local to delete.
                    if self._tier_owned and not from_fabric:
                        self.tier.delete(kv_tier.PREFIX_KIND, digest)
                    obs_events.emit(
                        "tier_drop", tier_kind=kv_tier.PREFIX_KIND,
                        key=digest[:64],
                        reason="chain/geometry/weights mismatch",
                    )
                    break
                pages = self.prefix.allocate(1)
                if pages is None:
                    break
                try:
                    self.cache = write_page(
                        self.cache, pages[0], k, v, ks, vs
                    )
                except Exception:  # noqa: BLE001 — degrade to re-prefill
                    self.pool.release(pages)
                    if self._tier_owned and not from_fabric:
                        self.tier.delete(kv_tier.PREFIX_KIND, digest)
                    break
                self.prefix.insert_chain(node, chunk, pages)
                child = node.children.get(chunk[0])
                if child is None or child.page != pages[0]:
                    break  # insert declined (raced sibling) — released
                node = child
                node.refcount += 1
                pinned.append(node)
                i += ps
                faulted += 1
                bytes_in += kv_tier.payload_nbytes(payload)
                if from_fabric:
                    # Adopt the validated entry into the LOCAL tier:
                    # the next evict/re-admit cycle (and peers probing
                    # us) hit here instead of re-crossing the wire.
                    self.tier.put(kv_tier.PREFIX_KIND, digest, payload)
                    remote += 1
        finally:
            for n in pinned:
                self.prefix.release_node(n)
        if faulted:
            self._bump("tier_hits")
            self._bump("tier_faults", faulted)
            self._bump("tier_bytes", bytes_in)
            if remote:
                self._bump("tier_remote_pages", remote)
            obs_events.emit(
                "tier_fault", pages=faulted, bytes=bytes_in,
                matched_tokens=i, remote_pages=remote,
            )

    def _request_sampling(self, req: Request) -> tuple[float, float, int]:
        """Resolve a request's effective (temperature, top_p, top_k):
        per-request overrides beat the engine defaults."""
        t = self.temperature if req.temperature is None else req.temperature
        p = self.top_p if req.top_p is None else req.top_p
        k = self.top_k if req.top_k is None else req.top_k
        return float(t), float(p), int(k)

    def _req_key(self, req: Request) -> jax.Array:
        """One sampling subkey for ``req`` — the per-request PRNG
        protocol slot migration relies on: every draw is
        ``fold_in(request key, draw counter)``, so a migrated slot
        (which carries key + counter in its snapshot) replays the exact
        draw sequence the un-migrated run would have made, independent
        of what other slots share its batch. The request key itself is
        split off the engine key lazily on the first sampled draw."""
        if req.key is None:
            self.key, req.key = jax.random.split(self.key)
        sub = jax.random.fold_in(req.key, req.key_step)
        req.key_step += 1
        return sub

    def _sample_req(self, req: Request, logits: jax.Array) -> int:
        """Sample one token for ``req`` from ``logits [V]`` under its
        effective knobs."""
        if not bool(jnp.isfinite(logits).all()):
            raise _NonFiniteLogits(
                "non-finite logits from the admission prefill",
                slot=req.slot,
            )
        t, p, k = self._request_sampling(req)
        if t <= 0.0:
            return int(sampling.greedy(logits))
        return int(sampling.sample(logits, self._req_key(req), t, p, k))

    def _sample_slots(
        self, logits: jax.Array, toks: np.ndarray | None = None
    ) -> np.ndarray:
        """Per-slot sampling of a batched ``[max_batch, V]`` decode
        output. All-greedy batches stay one batched argmax (``toks``,
        when given, is that argmax already fetched by the caller);
        slots with ``temperature > 0`` each draw under their own knobs
        and their own per-request key (see :meth:`_req_key`)."""
        if toks is None:
            toks = np.array(sampling.greedy(logits))
        for slot, req in enumerate(self._slots):
            if req is None:
                continue
            t, p, k = self._request_sampling(req)
            if t <= 0.0:
                continue
            toks[slot] = int(
                sampling.sample(logits[slot], self._req_key(req), t, p, k)
            )
        return toks

    def _needed_pages(self, prompt_len: int, gen_len: int) -> int:
        return -(-(prompt_len + gen_len) // self.page_size)

    # -- speculative decoding ---------------------------------------------

    def _new_spec_state(self):
        """A fresh per-request SpecState under this engine's knobs —
        admission and snapshot import build through here so both get
        the same width ceiling (1 when tree speculation is off or the
        pool is quantized)."""
        from triton_distributed_tpu.models.speculative import SpecState

        return SpecState(
            self.speculative,
            w_max=self.spec_width if self._spec_tree else 1,
        )

    def _plan_drafts(self):
        """Propose a draft for every active slot — a ``TreeDraft`` when
        tree speculation is on and the slot has a genuinely branching
        candidate set, else today's linear token list. Returns
        ``(drafts, ok)``; ``ok=False`` when some slot is too close to
        ``max_length`` for even a zero-draft verify chunk (its pad rows
        would run past the page table) — that round must use the
        batched single-step decode instead."""
        from triton_distributed_tpu.models.speculative import cap_draft

        drafts: dict = {}
        for slot, req in enumerate(self._slots):
            if req is None:
                continue
            budget = req.gen_len - len(req.out)
            k = cap_draft(
                req.spec.k, int(self._kv_len[slot]), budget, self.max_length
            )
            if k < 0:
                return {}, False
            if k <= 0:
                drafts[slot] = []
                continue
            if self._spec_tree and req.spec.width > 1:
                tree = self._plan_tree(req, slot, k)
                if tree is not None:
                    drafts[slot] = tree
                    continue
            drafts[slot] = req.spec.propose(k)
        return drafts, True

    def _plan_tree(self, req, slot: int, k: int):
        """Build ``slot``'s draft trie for a ``k``-token budget, or
        None when the candidates don't actually branch (a single path
        keeps the linear verify — identical chunk shape, identical
        per-verify PRNG consumption, no mask program).

        Branch sources, merged by the trie (shared prefixes dedup):
        the radix tree's continuations of the slot's FULL history
        (other finished requests that shared this prefix and then
        diverged), the durable KV tier's RAM-resident chains (spilled
        continuations whose token identity survives in the headers),
        and the slot's own n-gram proposal as the fallback branch.

        Budgets: per-branch depth ≤ ``k`` (so emitted ≤ accepted+1
        stays within the generation budget ``cap_draft`` enforced) and
        total nodes ≤ ``round_chunk(k+1)`` — the branches beyond the
        linear draft ride ONLY in rows the chunk would have padded
        anyway, so a tree verify is never a bigger program than the
        linear verify it replaces."""
        from triton_distributed_tpu.models.speculative import TreeDraft

        from triton_distributed_tpu.models import kv_tier

        if self.prefix is None:
            return None
        hist = [int(t) for t in req.prompt] + [int(t) for t in req.out]
        paths = self.prefix.propose_continuations(
            hist, width=req.spec.width, depth=k,
            tier_chains=(
                self.tier.resident_chains()
                if self.tier is not None
                and self.tier.may_contain(kv_tier.PREFIX_KIND)
                else None
            ),
        )
        ngram = req.spec.propose(k)
        if ngram:
            paths.append(ngram)
        if not paths:
            return None
        tree = TreeDraft(int(self._tok[slot]))
        node_budget = round_chunk(k + 1)
        for p in paths:
            tree.add_path(p[:k], budget=node_budget)
        if tree.is_chain:
            return None
        return tree

    def _spec_round(self, drafts: dict[int, list[int]]) -> bool:
        """One speculative round: every slot in ``drafts`` verifies its
        draft in a single chunked forward, rolls rejected KV back (the
        host-authoritative kv_len resync IS the rollback), and appends
        ``accepted + 1`` tokens. Slots WITHOUT an entry are untouched —
        on a mixed round the caller advances them (and the verified
        slots, one more token) through the ordinary batched decode
        step. A verify that raises fails ONLY its own slot (structured
        error, clean teardown); the other slots' round proceeds.
        Returns whether slot state changed."""
        from triton_distributed_tpu.models.speculative import (
            TreeDraft,
            spec_verify_slot,
        )

        bursts: dict[int, list[int]] = {}
        any_failed = False
        for slot, req in enumerate(self._slots):
            if req is None or slot not in drafts:
                continue
            kv = int(self._kv_len[slot])
            draft = drafts[slot]
            t, p, k = self._request_sampling(req)
            if isinstance(draft, TreeDraft):
                if self._spec_tree_slot(req, slot, draft, kv, t, p, k,
                                        bursts):
                    any_failed = True
                continue
            # One per-request subkey per verify (the internal
            # accept/resample splits derive from it) — the draw
            # sequence stays the request's own across a migration.
            sub = self._req_key(req) if t > 0.0 else None
            try:
                emitted, self.cache, a, _ = spec_verify_slot(
                    self.model, self.cache, slot, int(self._tok[slot]),
                    draft, kv, self._prefill_mode, key=sub,
                    temperature=t, top_p=p, top_k=k,
                )
            except FaultError as e:
                # Injected faults fire at the seam BEFORE the chunk
                # program consumed (donated) the cache — per-slot
                # isolation is safe.
                self._bump("decode_faults")
                self._fail(req, "failed", f"{type(e).__name__}: {e}")
                any_failed = True
                continue
            except Exception:
                # A real mid-chunk failure may have raised AFTER the
                # chunk donated self.cache's buffers: continuing the
                # round on deleted arrays would cascade crashes across
                # the surviving slots. Re-raise to _step_guard, which
                # fails the whole in-flight set and leaves the engine
                # reusable — the honest policy when the cache can't be
                # trusted.
                raise
            if emitted is None:
                # Non-finite verify logits (the cache was still
                # threaded through — only this request is poisoned).
                self._bump("nonfinite_logits")
                self._fail(
                    req, "nan_logits",
                    f"non-finite logits in speculative verify chunk "
                    f"after {len(req.out)} tokens",
                )
                any_failed = True
                continue
            req.spec.record(len(draft), a)
            self._bump("spec_verify_steps")
            if self._moe_k:
                # The verify chunk routes draft+1 positions per slot.
                self._bump("moe_routed_tokens",
                           (len(draft) + 1) * self._moe_k)
            self._bump("spec_draft_tokens", len(draft))
            self._bump("spec_accepted_tokens", a)
            self._bump("spec_rollback_tokens", len(draft) - a)
            self._kv_len[slot] = kv + a + 1
            bursts[slot] = emitted
        changed = self._process(lambda slot: bursts.get(slot, []))
        # Every verify left the device kv_len at the chunk's end
        # (accepted + rejected rows); resyncing the host table rolls the
        # rejected tail back and drops any evicted/failed slot's pages
        # in one write.
        self._sync_tables()
        self._spec_accept_gauge.set(
            self.stats["spec_accepted_tokens"]
            / max(self.stats["spec_draft_tokens"], 1)
        )
        return changed or any_failed

    def _spec_tree_slot(
        self, req, slot: int, tree, kv: int,
        t: float, p: float, k: int, bursts: dict,
    ) -> bool:
        """One TREE verify of ``slot`` inside a speculative round:
        single multi-branch chunk forward, sample/argmax-then-match
        walk, row-move commit of the accepted branch. On success
        ``bursts[slot]`` holds the emitted tokens and the host kv_len
        is advanced (the round's ``_sync_tables`` is the rollback, as
        in the linear path); returns True when the slot FAILED (fault
        seam or non-finite logits — same isolation contract as the
        linear arm)."""
        from triton_distributed_tpu.models.speculative import (
            commit_tree_path,
            spec_verify_tree,
        )

        nk = (lambda: self._req_key(req)) if t > 0.0 else None
        try:
            emitted, self.cache, path = spec_verify_tree(
                self.model, self.cache, slot, tree, kv,
                self._prefill_mode, next_key=nk,
                temperature=t, top_p=p, top_k=k,
            )
        except FaultError as e:
            # The seam fires before the chunk donated the cache —
            # per-slot isolation is safe (see the linear arm).
            self._bump("decode_faults")
            self._fail(req, "failed", f"{type(e).__name__}: {e}")
            return True
        except Exception:
            # Post-donation failure: re-raise to _step_guard (the
            # cache can no longer be trusted — same as the linear arm).
            raise
        if emitted is None:
            self._bump("nonfinite_logits")
            self._fail(
                req, "nan_logits",
                f"non-finite logits in speculative tree-verify chunk "
                f"after {len(req.out)} tokens",
            )
            return True
        a = len(path)
        # Commit: the accepted branch's rows move from their DFS
        # storage slots to the contiguous positions linear decode
        # would have written; a primary-branch accept is a no-op.
        moved = any(int(n) != j + 1 for j, n in enumerate(path))
        self.cache = commit_tree_path(self.cache, slot, kv, path)
        req.spec.record_tree(tree.num_drafted, tree.max_depth, a)
        self._bump("spec_verify_steps")
        self._bump("spec_tree_rounds")
        self._bump("spec_tree_nodes", tree.num_drafted)
        self._bump("spec_tree_depth", tree.max_depth)
        if moved:
            self._bump("spec_tree_branch_accepts")
        if self._moe_k:
            # The verify chunk routes every trie node's position.
            self._bump("moe_routed_tokens", len(tree) * self._moe_k)
        self._bump("spec_draft_tokens", tree.num_drafted)
        self._bump("spec_accepted_tokens", a)
        self._bump("spec_rollback_tokens", tree.num_drafted - a)
        self._kv_len[slot] = kv + a + 1
        bursts[slot] = emitted
        return False

    def _ends(self, req: Request, t: int) -> bool:
        """Whether token ``t``, just appended, completed ``req``
        (gen_len or eos)."""
        return req.done or (self.eos_id is not None and t == self.eos_id)

    def _maybe_finish(self, req: Request, t: int) -> bool:
        """Evict ``req`` if token ``t`` completed it."""
        if self._ends(req, t):
            self._evict(req)  # free pages NOW
            return True
        return False

    # -- the loop --------------------------------------------------------

    def _try_admit(self, queue: deque) -> bool:
        """Admit queue heads into free slots while pages allow. Failed
        admissions (injected faults, pool exhaustion races, non-finite
        prefill logits, expired deadlines) fail ONLY their request and
        the scan continues. Returns whether anything was admitted."""
        if queue and self._pend is not None:
            # Admission mutates slot/table/pool state the in-flight
            # resident launch still reads — the pipeline syncs here
            # first. An empty queue mutates nothing and keeps the
            # pipeline unbroken (the steady resident state).
            self._drain_pend()
        admitted = False
        progress = True
        while progress:  # re-scan: a first-token eviction frees its
            progress = False          # slot for the next request
            for slot in range(self.max_batch):
                if self._slots[slot] is not None or not queue:
                    continue
                head = queue[0]
                if (head.deadline_at is not None
                        and time.monotonic() > head.deadline_at):
                    queue.popleft()
                    self._fail(
                        head, "deadline_exceeded",
                        f"deadline_s={head.deadline_s} expired before "
                        "admission",
                    )
                    progress = True
                    break
                need = self._needed_pages(len(head.prompt), head.gen_len)
                m = None
                if head.snapshot is not None:
                    # Migration import does its own (prefix-delta)
                    # matching; here only a conservative availability
                    # check — full need against free + reclaimable.
                    avail = len(self.pool.free) + (
                        self.prefix.reclaimable_pages()
                        if self.prefix is not None else 0
                    )
                    if need > avail:
                        self._bump("admission_stalls")
                        progress = False
                        break
                elif self.prefix is not None:
                    if self.tier is not None:
                        # Durable-tier fault-back (docs/serving.md
                        # "Tiered KV"): pull tier-resident pages of
                        # this prompt back into the tree BEFORE the
                        # match, so a spilled-then-revisited prefix
                        # re-maps instead of re-prefilling.
                        self._tier_fill(head.prompt)
                    m = self.prefix.match(head.prompt)
                    avail = (
                        len(self.pool.free)
                        + self.prefix.reclaimable_pages()
                    )
                    if need - len(m.nodes) > avail:
                        self.prefix.release_match(m)
                        self._bump("admission_stalls")
                        progress = False  # end the scan: a rescan would
                        break             # just re-stall the same head
                elif need > len(self.pool.free):
                    progress = False
                    break  # head-of-line waits for pages
                req = queue.popleft()
                try:
                    # One request's whole admission, on every path:
                    # pages, prefill chunks (with the running batch's
                    # rounds between them), first token.
                    with trace_span(
                        "engine:admit", trace_id=req.trace_id, slot=slot,
                        prompt=len(req.prompt),
                        queue_wait_ms=int(
                            (time.monotonic() - req.timeline.enqueue_t)
                            * 1e3
                        ),
                    ):
                        first = self._admit(req, slot, m)
                except Exception as e:  # noqa: BLE001 — isolation
                    self._admit_failure(req, m, e)
                    progress = True
                    break
                # The row's first token (a resumed one's last): its
                # next gap starts here, behind its own admission, which
                # stood in the gap of every row admitted before it.
                self._admit_t = self._tok_t[slot] = self._gap_clock()
                if first is None:
                    # Snapshot path: either resumed mid-generation (its
                    # pending token is already out[-1]) or failed inside
                    # the import fallback — the status tells which.
                    if req.status != "ok":
                        progress = True
                        break
                    if req.timeline is not None:
                        req.timeline.stamp_first_token()
                    admitted = progress = True
                    continue
                if req.timeline is not None:
                    req.timeline.stamp_first_token()
                if self.speculative and req.spec is None:
                    req.spec = self._new_spec_state()
                    req.spec.observe(req.prompt)
                    req.spec.observe((int(first),))
                req.out.append(int(first))
                self._emit_token(req)
                # The admission-sampled token is emitted output too —
                # without this, generated_tokens undercounts by one per
                # request vs tokens_out and Engine.serve's b*gen_len.
                self._bump("generated_tokens")
                self._tok[slot] = int(first)
                admitted = progress = True
                # The admission token itself can finish the request
                # (gen_len=1, or eos as first token)...
                if not self._maybe_finish(req, int(first)) \
                        and req.prefill_only:
                    # ...and an unfinished prefill_only request exports
                    # HERE — the prefill→decode handoff's first half:
                    # the slot (prefill KV + the admission token) ships
                    # to a decode replica instead of occupying this one.
                    self._migrate_out(req, "prefill_handoff")
        if admitted:
            # A trailing first-token eviction leaves the device table
            # pointing at released pages until synced — and every exit
            # path must reach this sync (an early return here once left
            # a zombie slot decoding into freed pages).
            self._sync_tables()
        return admitted

    def _step(self) -> bool:
        """One scheduling round of the in-flight batch: a speculative
        verify + batched-decode mix, a megakernel multi-step launch, or
        one batched decode step. Returns whether slot state changed."""
        active = np.asarray([r is not None for r in self._slots], np.int32)
        kv_high = int((self._kv_len * active).max())
        if self.speculative:
            # Per-slot verify chunks ONLY for slots that drafted;
            # undraftable slots (or an all-empty plan, or a slot too
            # near max_length for a padded chunk) ride the ONE batched
            # decode step — a mixed round costs 1 + |drafted| forwards,
            # never per-slot chunks for the no-match majority, so
            # speculation never makes the no-match case slower than
            # plain serving.
            drafts, ok = self._plan_drafts()
            drafted = {s: d for s, d in drafts.items() if d} if ok else {}
            n_active = int(active.sum())
            changed = False
            if drafted:
                with self._round_span(len(drafted)):
                    changed = self._spec_round(drafted)
            if not ok or len(drafted) < n_active:
                changed = self._decode_once() or changed
            return changed
        # Megakernel serving decodes in NS-step chunks: one launch
        # emits NS tokens per slot (in-kernel argmax — Gumbel-perturbed
        # per slot when sampling), then the host checks eos/gen_len. A
        # finished row's overshoot tokens are discarded; its overshoot
        # KV rows land beyond its allocated pages, where the zeroed
        # table entries route them to the trash page. Top-k/top-p
        # slots sample IN-KERNEL through the bisection filter on
        # single-rank builds; rounds that don't compose (rows near
        # max_length, filtered slots at tp > 1) fall back to single
        # steps.
        if self.mode == "mega":
            with self._round_span(int(active.sum())):
                changed = self._mega_round(active, kv_high)
            if changed is not None:
                return changed
            self._bump("mega_fallback_steps")
        return self._decode_once()

    def _mega_round(self, active: np.ndarray, kv_high: int):
        """One NS-step megakernel launch — pipelined behind the
        in-flight resident launch when one is pending — or None when
        this round must use the single-step fallback: a row within NS
        of ``max_length`` (the append would overwrite cached rows past
        capacity), or — at tp > 1 only — an active slot sampling with
        top-k/top-p (the single-rank build filters IN-KERNEL through
        the bisection filter; the sharded LM head streams vocab shards
        whose running filter state does not yet cross ranks). Mixed
        greedy/sampled batches launch fused: per-slot temperatures
        scale the noise, a zero temperature zeroes it — exactly the
        greedy argmax."""
        if self._pend is not None:
            # Resident pipeline: issue the NEXT launch off the pending
            # one's device outputs FIRST (tok = its last token row,
            # halt chained, cache threaded — no host sync anywhere on
            # that path), THEN drain the pending round's tokens. The
            # next launch is parked in ``_pend`` BEFORE the drain runs:
            # a drain that raises (injected fault, non-finite logits,
            # per-slot failure) reaches the step guard with the
            # in-flight launch still owned, so ``_abort_pend`` blocks
            # on it before teardown frees pages it still reads.
            pend, self._pend = self._pend, None
            nxt = self._issue_resident(pend)
            if nxt is not None:
                self._pend = nxt
                self._bump("mega_resident_rounds")
            return self._drain_launch(pend)
        plan = self._mega_plan(active, kv_high)
        if plan is None:
            return None
        pend = self._launch_mega(plan)
        if self.resident:
            # Tokens land at the next drain site; the round made
            # progress (the launch is in flight).
            self._pend = pend
            return True
        return self._drain_launch(pend)

    def _mega_plan(self, active: np.ndarray, kv_high: int):
        """Compose the next launch from host truth: per-slot sampling
        knobs (the filtered gate), kept-row counts, the batch bucket,
        and the eos operand set. None → this round cannot launch fused
        and falls back to single steps."""
        if kv_high + self.NS > self.max_length:
            return None
        tp1 = self.model.ctx.axis_size(self.model.axis) == 1
        act = [s for s in range(self.max_batch)
               if self._slots[s] is not None]
        V = self.model.cfg.vocab_size
        filtered = False
        for slot in act:
            t, p, k = self._request_sampling(self._slots[slot])
            # Same predicate as the per-row enable below: top-k >= V
            # with top-p 1 is a no-op filter, not a filtered round —
            # treating it as one forced a permanent single-step
            # fallback at tp > 1 and a needless filtered program at
            # tp == 1.
            if t > 0.0 and (0 < k < V or p < 1.0):
                # In-kernel top-k/top-p (kernels._filtered_winner's
                # bisection) needs the full vocab row on one rank and
                # a multi-step build; otherwise single-step fallback.
                if not tp1 or self.NS <= 1:
                    return None
                filtered = True
        # Batch bucket: the smallest power-of-two program covering the
        # active slots, so a 2-slot round stops paying the
        # max_batch-wide program. Full-width rounds keep the identity
        # layout (and the exact pre-bucket launch program).
        B = self.max_batch
        if self.mega_buckets and act:
            b = 1
            while b < len(act):
                b *= 2
            B = min(b, self.max_batch)
        compact = B < self.max_batch
        rows = act + [-1] * (B - len(act)) if compact \
            else list(range(self.max_batch))
        temps = np.zeros(B, np.float32)
        # Kept-row counts: a slot finishing mid-launch (gen_len bound,
        # known NOW) emits guaranteed-overshoot rows — routed to the
        # trash page by the append so a retiring page's int8 scale
        # never covers garbage (append_n docstring).
        n_valid = np.zeros(B, np.int32)
        # Inert rows: inv_t 1, top-k window V, top-p 1, filter off.
        sampcfg = np.tile(
            np.asarray([[1.0, float(V), 1.0, 0.0]], np.float32), (B, 1)
        )
        stop_tok = np.full(B, -1, np.int32)
        for i, slot in enumerate(rows):
            req = self._slots[slot] if slot >= 0 else None
            if req is None:
                continue
            t, p, k = self._request_sampling(req)
            temps[i] = max(t, 0.0)
            n_valid[i] = min(req.gen_len - len(req.out), self.NS)
            # Mirrors sampling.filter_logits applicability exactly:
            # top-k only when 0 < k < V, top-p only when p < 1 — rows
            # with neither keep the unfiltered Gumbel argmax (enable
            # 0), bit-identical to the pre-filter sampled launch.
            en = t > 0.0 and (0 < k < V or p < 1.0)
            sampcfg[i] = [1.0 / t if t > 0.0 else 1.0,
                          float(k) if 0 < k < V else float(V),
                          min(max(p, 1e-6), 1.0),
                          1.0 if en else 0.0]
            if self.eos_id is not None:
                stop_tok[i] = self.eos_id
        sampled = bool((temps > 0.0).any())
        # Device stop-token test needs the multi-step tail (the
        # stop_step output is per-sub-step bookkeeping).
        eos = self.eos_id is not None and self.NS > 1
        return _MegaPlan(
            rows=rows, B=B, compact=compact, sampled=sampled,
            filtered=filtered, eos=eos, temps=temps, n_valid=n_valid,
            sampcfg=sampcfg if filtered else None,
            stop_tok=stop_tok if eos else None,
        )

    def _issue_resident(self, chain: _MegaLaunch):
        """Issue the next resident launch chained off ``chain``'s
        device outputs — no host sync. None when the projected state
        cannot compose another launch (the pipeline breaks; the caller
        drains and the next round replans from host truth). The slot
        set is ``chain``'s by construction: every slot-state mutation
        site drains the pipeline first, so only retires-at-drain can
        differ — those rows ride along with ``n_valid`` 0 (every KV
        write trash-routed, every token discarded at drain)."""
        n_valid = np.zeros(chain.plan.B, np.int32)
        live = False
        for i, slot in enumerate(chain.plan.rows):
            req = self._slots[slot] if slot >= 0 else None
            if req is None:
                continue
            # Projected remaining: the pending launch will emit (at
            # most) its n_valid tokens for this row before this launch
            # drains. An eos hit inside the pending launch emits fewer
            # — but then the row retires at its drain and THIS
            # launch's tokens are discarded (halt chaining already
            # stopped its KV writes in-kernel).
            rem = req.gen_len - len(req.out) - int(chain.plan.n_valid[i])
            n_valid[i] = min(max(rem, 0), self.NS)
            if n_valid[i] > 0:
                live = True
        if not live:
            return None
        # Host _kv_len is already projected past the pending launch
        # (advanced at issue); one more launch must fit under it.
        active = np.asarray(
            [r is not None for r in self._slots], np.int32
        )
        if int((self._kv_len * active).max()) + self.NS > self.max_length:
            return None
        plan = dataclasses.replace(chain.plan, n_valid=n_valid)
        return self._launch_mega(plan, chain=chain)

    def _launch_mega(self, plan: _MegaPlan,
                     chain: _MegaLaunch | None = None) -> _MegaLaunch:
        """Dispatch one NS-step launch for ``plan`` and do the
        issue-time host bookkeeping (projected ``_kv_len``, counters,
        the launch event). Nothing here syncs on device results — the
        returned record's outputs drain later (immediately for
        non-resident rounds, at the next drain site for resident)."""
        NS = self.NS
        params = self._mega_model()._step_params()  # Q8Params under wq8
        if chain is not None:
            tok = chain.toks[NS - 1]  # device gather, async
            cache_in = chain.cache
            halt_in = chain.halt
        else:
            rows = np.asarray([max(s, 0) for s in plan.rows], np.int32)
            tok = jnp.asarray(self._tok[rows].copy())
            if plan.compact:
                # Bucket launch: compacted table/kv_len views share
                # the pool buffers (the pools are page-indexed, not
                # slot-indexed). Inert filler rows keep zeroed table
                # rows (the trash page) and kv_len 0.
                tbl = self._table[rows].copy()
                kvl = self._kv_len[rows].copy()
                for i, slot in enumerate(plan.rows):
                    if slot < 0 or self._slots[slot] is None:
                        tbl[i] = 0
                        kvl[i] = 0
                cache_in = dataclasses.replace(
                    self.cache,
                    page_table=jnp.asarray(tbl),
                    kv_len=jnp.asarray(kvl),
                )
            else:
                cache_in = self.cache
            halt_in = (jnp.zeros((plan.B,), jnp.int32)
                       if plan.eos else None)
        extra = []
        if plan.eos:
            extra.append(jnp.asarray(plan.stop_tok))
            extra.append(halt_in)
        fn = self._mega_multi_fn(
            plan.sampled, filtered=plan.filtered, eos=plan.eos, B=plan.B,
        )
        nv = jnp.asarray(plan.n_valid)
        t0 = time.monotonic()
        if plan.sampled:
            self.key, sub = jax.random.split(self.key)
            outs = fn(params, tok, cache_in, nv, tuple(extra), sub,
                      jnp.asarray(plan.temps),
                      jnp.asarray(plan.sampcfg) if plan.filtered
                      else None)
        elif extra:
            outs = fn(params, tok, cache_in, nv, tuple(extra))
        else:
            outs = fn(params, tok, cache_in, nv)
        outs = list(outs)
        toks, _logits, new_cache = outs[:3]
        idx = 3
        ss = halt = None
        if plan.eos:
            ss, halt = outs[idx], outs[idx + 1]
            idx += 2
        ring_arr = outs[idx] if self.kernel_trace else None
        # Rebind, never ``+=``: the in-place add mutated the numpy
        # array a zero-copy ``jnp.asarray`` may have aliased into the
        # STILL-RUNNING launch's cache.kv_len (see _sync_tables).
        adv = np.zeros(self.max_batch, np.int32)
        n_active = 0
        for i, slot in enumerate(plan.rows):
            if slot >= 0 and self._slots[slot] is not None:
                n_active += 1
                if plan.n_valid[i] > 0:
                    adv[slot] = 1
        self._kv_len = self._kv_len + NS * adv
        if plan.compact:
            # Restore the full-width table view over the launch's
            # output pools; the record keeps the bucket-shaped cache
            # for chaining.
            self.cache = dataclasses.replace(
                new_cache,
                page_table=jnp.asarray(self._table.copy()),
                kv_len=jnp.asarray(self._kv_len.copy()),
            )
            self._bump("mega_bucket_launches")
        else:
            self.cache = new_cache
        if plan.filtered:
            self._bump("mega_filtered_rounds")
        self._bump("decode_steps", NS)
        self._m_step_rows.observe_n(n_active, NS)
        if self._moe_k:
            self._bump("moe_routed_tokens", NS * n_active * self._moe_k)
        self._bump("mega_launches")
        self._ns_gauge.set(
            self.stats["decode_steps"] / max(self.stats["mega_launches"], 1)
        )
        # Active slots' request trace ids ride the launch event (and
        # the decoded ring's launch metadata), so one request can be
        # followed server → router → replica → engine → device tasks.
        # Keys are LAUNCH rows (what the device stamps into TR_SLOT) —
        # identical to engine slots for full-width launches.
        trace_ids = {
            i: self._slots[slot].trace_id
            for i, slot in enumerate(plan.rows)
            if slot >= 0 and self._slots[slot] is not None
            and self._slots[slot].trace_id
        }
        obs_events.emit(
            "mega:launch", ns=NS, active=n_active,
            sampled=int(plan.sampled),
            trace_ids=",".join(trace_ids[k] for k in sorted(trace_ids)),
        )
        return _MegaLaunch(
            plan=plan, toks=toks, cache=new_cache, ss=ss, halt=halt,
            ring=ring_arr, t0=t0, trace_ids=trace_ids,
        )

    def _drain_pend(self) -> bool:
        """THE sync point of the depth-1 pipeline: fetch the pending
        launch's (or looked-ahead step's) emitted tokens and run the
        normal emit/retire paths. Every slot-state mutation site
        (_try_admit, _handoff_sweep, _update_snapshot_buffer; through
        ``_settle_pend`` _apply_cancels and _expire_deadlines; through
        ``_abort_pend`` run()'s teardown and the step guard) comes
        through here before touching state an in-flight launch still
        reads."""
        pend, self._pend = self._pend, None
        if pend is None:
            return False
        if isinstance(pend, _MegaLaunch):
            return self._drain_launch(pend)
        with self._round_span(sum(r is not None for r in self._slots)):
            changed = self._emit_step(pend)
        if changed:
            # No round's caller follows this drain with a sync of its
            # own: a slot it retired must leave the device table before
            # the next step appends through the stale row.
            self._sync_tables()
        return changed

    def _settle_pend(self) -> None:
        """Sync point before the HOST ends slots on its own clock (a
        cancel, an expired deadline). A resident launch drains: its
        tokens were issued before the event and count. A looked-ahead
        step only has to have left the device before the slots' pages
        go back; it stays parked, and the ended slots' tokens in it are
        discarded at its drain, so the request ends with exactly the
        tokens the serial round would have given it."""
        if isinstance(self._pend, _StepLaunch):
            self._pend.fetch()
            self._release_ended()
        else:
            self._drain_pend()

    def _abort_pend(self) -> None:
        """Teardown-path drain: block on (then discard) the in-flight
        launch so no exit path leaves one reading slot state the
        teardown is about to reuse."""
        pend, self._pend = self._pend, None
        if pend is None:
            self._release_ended(every=True)
            return
        try:
            jax.block_until_ready(pend.toks)
        except Exception:  # noqa: BLE001 — teardown is best-effort
            pass
        self._release_ended(every=True)
        if isinstance(pend, _StepLaunch):
            self._bump("lookahead_discarded",
                       sum(r is not None for r in pend.reqs))

    def _drain_launch(self, pend: _MegaLaunch) -> bool:
        """Fetch one launch's outputs and emit/retire through the
        normal paths. A device stop-token hit (``ss < n_valid``)
        truncates the row's stream at the stop token — the host never
        re-tests tokens the kernel already tested — and the retire
        flows through ``_maybe_finish``'s standard ``_evict`` (pages →
        radix tree/pool exactly as before)."""
        # Chaos seam: a drain that raises must reach the step guard
        # with any just-issued resident launch parked in ``_pend``
        # (tests/test_resident.py pins the no-orphan invariant).
        fault_point("engine.mega_drain",
                    launches=self.stats["mega_launches"])
        plan = pend.plan
        toks_np = np.asarray(pend.toks)  # [NS, B] — THE host sync
        ss_np = np.asarray(pend.ss) if pend.ss is not None else None
        wall_s = time.monotonic() - pend.t0
        if pend.ring is not None:
            # Shared MegaDispatch plumbing records the launch; the
            # per-run stats ledger + registry mirror ride _bump.
            self._record_kernel_trace(
                pend.ring, pend.t0, wall_s, self.NS, pend.trace_ids,
            )
            self._bump("mega_trace_launches")
        col = {slot: i for i, slot in enumerate(plan.rows) if slot >= 0}
        if ss_np is not None:
            for slot, i in col.items():
                if (self._slots[slot] is not None
                        and ss_np[i] < plan.n_valid[i]):
                    self._bump("mega_device_retires")

        def slot_tokens(slot):
            i = col.get(slot)
            if i is None:
                return ()
            n = int(plan.n_valid[i])
            if ss_np is not None:
                n = min(n, int(ss_np[i]) + 1)
            return toks_np[:n, i]

        return self._process(slot_tokens)

    def _mega_multi_fn(self, sampled: bool, *, filtered: bool = False,
                       eos: bool = False, B: int | None = None):
        """The NS-step launch program (built lazily, cached per full
        option tuple — the batch bucket B is part of the key). The
        sampled wrapper draws the Gumbel noise INSIDE the jit —
        per-sub-step key splits, per-slot temperature scaling — so
        each rank materializes only its vocab shard and the kernel's
        argmax over ``logits + T_b·gumbel`` IS per-slot temperature
        sampling (the Gumbel-max trick, distribution-equal to
        ``sampling.sample`` at ``top_p=1, top_k=0``). With
        ``filtered``, the per-row ``sampcfg`` rides along and the
        in-kernel bisection filter restricts that argmax to the host
        ``filter_logits`` keep-set. ``eos`` appends the device
        stop-token operands. Unified call
        shape past the base four args: ``fn(params, tok, cache,
        n_valid[, extra_tuple][, key, temps, sampcfg])``."""
        key = (sampled, filtered, eos, B or self.max_batch)
        fn = self._multi_fns.get(key)
        if fn is not None:
            return fn
        Bk = key[-1]
        mega = self._mega_model()
        base = mega.decode_multi_fn(
            Bk, self.max_length, self.NS, sampled=sampled,
            page=self.page_size, kv_quant=self.kv_dtype is not None,
            num_pages=int(self.cache.k_pages.shape[1]),
            valid_arg=True, trace=self.kernel_trace,
            filtered=filtered, eos=eos,
        )
        if sampled:
            NS = self.NS
            n = self.model.ctx.axis_size(self.model.axis)
            v_pad = mega._dims(Bk, self.max_length).v_loc * n

            def tdt_mega_round(params, tok, cache, n_valid, extra, key,
                               temps, sampcfg):
                keys = jax.random.split(key, NS)
                noise = jax.vmap(
                    lambda k: jax.random.gumbel(
                        k, (Bk, v_pad), jnp.float32
                    )
                )(keys) * temps[None, :, None]
                tail = (noise, sampcfg) if filtered else (noise,)
                return base(params, tok, cache, n_valid, *extra, *tail)

            fn = jax.jit(tdt_mega_round, donate_argnums=(2,))
        elif eos:
            def tdt_mega_round(params, tok, cache, n_valid, extra):
                return base(params, tok, cache, n_valid, *extra)

            fn = jax.jit(tdt_mega_round, donate_argnums=(2,))
        else:
            fn = base
        self._multi_fns[key] = fn
        return fn

    def run(self, requests, *, results: bool = False):
        """Serve requests to completion with per-request error
        isolation. Each entry is a ``(prompt, gen_len)`` tuple or a
        :class:`Request` (the server builds Requests to carry
        per-request sampling knobs and deadlines).

        ``results=False`` (legacy): returns each request's generated
        tokens (prompt excluded), in order. Unservable requests raise
        ``ValueError`` up front (nothing runs); runtime failures finish
        the surviving requests, tear the failures down cleanly, and
        raise :class:`RequestFailedError`.

        ``results=True``: never raises for per-request failures —
        returns one :class:`RequestResult` per request (partial tokens
        + structured status/reason), the contract the model server
        speaks.

        Every run ends with the pool/radix invariant audit
        (:meth:`audit`); a bookkeeping leak raises
        :class:`PoolAuditError` at the batch that caused it.
        """
        reqs = [
            r if isinstance(r, Request)
            else Request(np.asarray(r[0], np.int32), int(r[1]))
            for r in requests
        ]
        self.stats = self._zero_stats()
        t0 = time.monotonic()
        self._round = 0
        # A fresh batch invalidates the previous one's crash-recovery
        # snapshots (their tickets latched when run() returned) — the
        # durable copies too: leftovers on disk are only meaningful
        # after a crash, and this engine did not crash.
        with self._snap_lock:
            self._snapshots = {}
        if self.tier is not None:
            from triton_distributed_tpu.models.kv_tier import SNAP_KIND

            if self._tier_owned and not self._tier_swept:
                # First run over an OWNED store: sweep every snap
                # entry, not just this object's keys — a RESPAWNED
                # process starts with empty _tier_snap_keys, and its
                # crashed predecessor's leftovers would otherwise
                # accumulate forever (recovery consumed them before
                # resubmitting; "entries mean crash", never history).
                # One-shot: later runs track their own keys, so the
                # per-batch cost stays a handful of deletes, not a
                # directory sweep.
                if self.tier.may_contain(SNAP_KIND):
                    self.tier.clear(SNAP_KIND)
            else:
                for tid in self._tier_snap_keys:
                    self.tier.delete(SNAP_KIND, tid)
            self._tier_swept = True
            self._tier_snap_keys = set()
        # Telemetry: every request gets a lifecycle timeline; the
        # server stamps enqueue at payload decode, direct callers get
        # it backfilled here (docs/observability.md).
        for r in reqs:
            if r.timeline is None:
                r.timeline = Timeline()
            r.timeline.stamp_enqueue()
            # Trace id: client-supplied or assigned here; tags admit
            # events, mega:launch events, and device-task ring records.
            # A migrated request keeps its snapshot's id, so one id
            # follows the request across engines.
            if r.trace_id is None:
                snap_tid = (
                    r.snapshot.get("trace_id")
                    if isinstance(r.snapshot, dict) else None
                )
                r.trace_id = snap_tid or f"req-{next(_TRACE_IDS)}"
        # Load shedding: the admission queue is bounded — excess
        # requests get a structured `overloaded` error immediately
        # instead of wedging the batch (clients retry with backoff).
        if self.max_queue is not None and len(reqs) > self.max_queue:
            for r in reqs[self.max_queue:]:
                self._fail(
                    r, "overloaded",
                    f"admission queue bounded at {self.max_queue} "
                    f"requests ({len(reqs)} submitted); retry with backoff",
                )
        for r in reqs:
            if r.status != "ok":
                continue
            total = len(r.prompt) + r.gen_len
            if total > self.max_length:
                msg = (
                    f"prompt+gen_len = {total} exceeds max_length "
                    f"{self.max_length}"
                )
                if not results:
                    raise ValueError(msg)
                self._fail(r, "unservable", msg)
                continue
            need = self._needed_pages(len(r.prompt), r.gen_len)
            if need > self._capacity:
                msg = (
                    f"request needs {need} pages; "
                    f"pool capacity is {self._capacity} (unservable)"
                )
                if not results:
                    raise ValueError(msg)
                self._fail(r, "unservable", msg)
                continue
            if r.deadline_s is not None:
                r.deadline_at = t0 + float(r.deadline_s)
        queue = deque(r for r in reqs if r.status == "ok")
        self._waiting = queue  # what `_may_look_ahead` may not starve

        try:
            # Cancellations that landed before the batch (the server's
            # cancel verb is engine-lock-free, so one can beat run()
            # here) drain their requests before any admission work.
            self._apply_cancels(queue)
            self._try_admit(queue)
            while True:
                self._round += 1
                if (self._handoff_at is not None
                        and self._round > self._handoff_at):
                    # Lossless drain: export the active slots, hand the
                    # queue back; slots whose export failed keep
                    # decoding and are retried next round.
                    self._handoff_sweep(queue)
                if self._apply_cancels(queue):
                    # A cancellation freed a slot AND its pages: same
                    # admit-now rule as deadline expiry below.
                    self._sync_tables()
                    self._try_admit(queue)
                if self._expire_deadlines():
                    # An expiry freed a slot AND its pages: admit from
                    # the queue NOW — waiting for the next slot-state
                    # change would starve queued requests on a free
                    # slot for the remainder of a long decode.
                    self._sync_tables()
                    self._try_admit(queue)
                if not any(r is not None for r in self._slots):
                    if not queue:
                        break
                    if not self._try_admit(queue) and queue:
                        # Nothing in flight and the head still can't
                        # admit: capacity was validated, so this is a
                        # bookkeeping leak — fail the head rather than
                        # spin forever (the audit below will name it).
                        # The re-check matters: _try_admit itself drains
                        # expired/failed heads, so the queue may already
                        # be empty even though nothing was admitted.
                        head = queue.popleft()
                        if head.status == "ok":
                            self._fail(
                                head, "failed",
                                "admission made no progress on an idle "
                                "engine (page accounting leak?)",
                            )
                    continue
                if self._step_guard(self._step):
                    # Slot state changed: the device cache threads k/v
                    # pages, but table + kv_len are host-authoritative.
                    self._try_admit(queue)
                    self._sync_tables()
                if (self.snapshot_every
                        and self._round % self.snapshot_every == 0):
                    # Incremental crash-recovery snapshots at round
                    # boundaries — host state is consistent here.
                    self._update_snapshot_buffer()
        finally:
            self._handoff_at = None
            self._round = 0
            self._waiting = ()
            # Block on (and discard) any in-flight launch
            # BEFORE teardown reuses the state it reads.
            self._abort_pend()
            # Crash-safe teardown: NO exit path — injected fault,
            # engine bug, KeyboardInterrupt — leaves a slot holding
            # pages, a dangling tree pin, or a stale device table; the
            # engine object stays reusable.
            leftover = [r for r in self._slots if r is not None]
            for r in leftover:
                self._fail(r, "aborted", "engine loop aborted mid-flight")
            while queue:
                r = queue.popleft()
                if r.status == "ok":
                    self._fail(
                        r, "aborted", "engine loop aborted before admission"
                    )
            if leftover:
                self._sync_tables()
            # Cancellations that raced past their request (the slot
            # finished first, or the id never matched) must not leak
            # into future batches: prune THIS batch's ids; foreign ids
            # stay armed for the batch that carries them, bounded so a
            # client spraying garbage ids can't grow the set forever.
            batch_ids = {r.ticket_id for r in reqs
                         if r.ticket_id is not None}
            with self._cancel_lock:
                self._cancelled -= batch_ids
                if len(self._cancelled) > 4096:
                    self._cancelled.clear()

        with trace_span("engine:audit", _ring=False):
            self.audit(raise_on_violation=True)
        if results:
            return [r.result() for r in reqs]
        failures = [(i, r) for i, r in enumerate(reqs) if r.status != "ok"]
        if failures:
            raise RequestFailedError(failures)
        return [np.asarray(r.out, np.int32) for r in reqs]

    # -- serving-tier hooks ------------------------------------------------

    def prefix_digest(self) -> list | None:
        """Router-side mirror export (docs/scale-out.md): the radix
        tree's cached token chains as a serializable forest
        (:meth:`PrefixCache.prefix_digest`), or None when
        ``prefix_cache`` is off. The multi-replica router scores
        prefix affinity against each replica's latest digest instead
        of touching live engine state from another thread."""
        return None if self.prefix is None else self.prefix.prefix_digest()

    def drain(self) -> int:
        """Graceful-drain hook for the serving tier: release every
        unreferenced radix page back to the pool, so a replica being
        taken out of rotation returns its cache HBM instead of
        stranding it behind a dead worker. In-use (refcounted) chains
        are never dropped — call with no requests in flight for a full
        flush. Returns the number of pages released."""
        if self.prefix is None:
            return 0
        return self.prefix.flush()

    # -- slot migration (docs/scale-out.md "Slot migration & handoff") ----

    def request_handoff(self, after_rounds: int = 0) -> None:
        """Arm the lossless-drain sweep: at the first scheduling round
        past ``after_rounds`` more rounds, every active slot is
        EXPORTED (status ``migrated`` + portable snapshot) instead of
        finishing here, and queued requests return un-run for
        re-dispatch. Thread-safe (an int write); the replica tier calls
        this from ``begin_drain(handoff=True)`` while a batch is in
        flight. Tests arm it before ``run()`` for a deterministic
        mid-generation export point."""
        self._refuse_slot_export("request_handoff")
        self._handoff_at = self._round + int(after_rounds)

    def _refuse_slot_export(self, what: str) -> None:
        """A slot's recurrent state is not pages, and ``SlotSnapshot``
        has no room for it: a model that keeps one exports nothing."""
        if self._recurrent:
            raise ValueError(
                f"{what}: {self.model.cfg.model_name} keeps a recurrent "
                "state beside its K/V pages, and slot export / migration "
                "carries pages only")

    def export_slot(self, slot: int, *, target_digest=None):
        """Snapshot one active slot (``models/slot_state.py``) — a pure
        read; the slot keeps decoding. ``target_digest`` enables the
        prefix delta: payload for pages the target's radix digest
        already covers is omitted. Call between runs or from the
        engine's own thread at a round boundary with nothing parked in
        ``_pend`` (host ``_kv_len`` runs a launch ahead of ``out``
        while one is in flight; the engine's own exports drain first)."""
        from triton_distributed_tpu.models import slot_state

        self._refuse_slot_export("export_slot")
        return slot_state.export_slot(
            self, slot, target_digest=target_digest
        )

    def export_slots(self) -> dict:
        """The incremental snapshot buffer (``snapshot_every`` rounds),
        keyed by ticket id — what the server's ``export_slots`` verb
        returns and the supervisor's crash recovery resumes from.
        Lock-guarded and engine-lock-free: safe to read mid-batch."""
        with self._snap_lock:
            return dict(self._snapshots)

    def _update_snapshot_buffer(self) -> None:
        """Refresh the per-ticket snapshot buffer from every active
        slot that carries a ticket id. Wholesale replacement IS the
        pruning: finished tickets drop out on the next refresh, and a
        resumed stale snapshot can only latch-lose."""
        from triton_distributed_tpu.models import slot_state

        if self._pend is not None:
            # Snapshots read slot KV the in-flight launch is
            # still appending to; sync the pipeline first.
            self._drain_pend()
        snaps: dict[str, dict] = {}
        for slot, req in enumerate(self._slots):
            if req is None or req.ticket_id is None:
                continue
            try:
                wire = slot_state.export_slot(self, slot).to_wire()
                if self._tier_fp is not None:
                    # Weight identity rides every buffered snapshot
                    # (and through it the supervisor's resume store):
                    # a tier-enabled engine refuses to IMPORT a
                    # snapshot carrying a different fingerprint —
                    # continuing old-weight KV under new weights is
                    # wrong bits, so it replays instead.
                    wire["model_fp"] = self._tier_fp
                snaps[req.ticket_id] = wire
            except Exception:  # noqa: BLE001 — snapshotting is best-effort
                continue
        with self._snap_lock:
            self._snapshots = snaps
        if self.tier is not None:
            # Durable snapshots (docs/scale-out.md "Durable
            # snapshots"): the buffer persists through the tier, so a
            # crashed process's LAST snapshots outlive it — a fresh
            # process over the same tier dir reads them back and
            # resumes mid-generation instead of replaying.
            from triton_distributed_tpu.models.kv_tier import SNAP_KIND

            for tid, snap in snaps.items():
                self.tier.put(SNAP_KIND, tid, snap)
            for tid in self._tier_snap_keys - set(snaps):
                self.tier.delete(SNAP_KIND, tid)
            self._tier_snap_keys = set(snaps)

    def _migrate_out(self, req: Request, reason: str,
                     snap=None) -> bool:
        """Export ``req``'s slot and tear it down with status
        ``migrated`` (the serving tier re-dispatches the snapshot
        elsewhere). Returns False — and leaves the request RUNNING —
        when the export itself fails (e.g. an injected
        ``migrate.export`` fault): the slot then simply finishes here,
        which keeps a handoff drain lossless either way. ``snap``
        short-circuits the export with a snapshot the caller already
        holds (the batched handoff sweep)."""
        from triton_distributed_tpu.models import slot_state

        slot = req.slot
        try:
            if snap is None:
                snap = slot_state.export_slot(self, slot)
            req.snapshot = snap.to_wire()
        except Exception as e:  # noqa: BLE001 — export is best-effort
            obs_events.emit(
                "migrate_failed", slot=slot,
                reason=f"{type(e).__name__}: {str(e)[:160]}",
                trace_id=req.trace_id,
            )
            return False
        req.status, req.reason = "migrated", f"slot exported ({reason})"
        self.stats["migrated_out"] += 1
        self._m_migrations.inc(reason=reason)
        self._m_mig_bytes.observe(float(snap.payload_bytes()))
        self._teardown_slot(req)
        self._finish_obs(req)
        obs_events.emit(
            "migrate_out", slot=slot, tokens_out=len(req.out),
            reason=reason, bytes=snap.payload_bytes(),
            trace_id=req.trace_id,
        )
        return True

    def _handoff_sweep(self, queue: deque) -> None:
        """The armed handoff fires: export every active slot (a slot
        whose export fails keeps decoding — retried next round) and
        mark everything still queued ``migrated`` with no snapshot
        (nothing computed yet; it re-dispatches as a plain request).

        With ``handoff_batch`` (the default) the sweep gathers every
        active slot's pages in ONE device round trip
        (``slot_state.export_slots_batch`` — bit-identical to the
        serial path); any batch failure (e.g. an injected
        ``migrate.export`` fault) degrades to the per-slot exports, so
        a single bad slot never blocks the others' handoff."""
        from triton_distributed_tpu.models import slot_state

        if self._pend is not None:
            # Exports read slot KV the in-flight launch is
            # still appending to; sync the pipeline first.
            self._drain_pend()
        active = [s for s in range(self.max_batch)
                  if self._slots[s] is not None]
        snaps: dict = {}
        if self.handoff_batch and len(active) > 1:
            try:
                snaps = slot_state.export_slots_batch(self, active)
            except Exception:  # noqa: BLE001 — degrade to serial
                snaps = {}
        changed = False
        for slot in active:
            req = self._slots[slot]
            if req is not None and self._migrate_out(
                    req, "drain", snap=snaps.get(slot)):
                changed = True
        while queue:
            r = queue.popleft()
            if r.status == "ok":
                r.status = "migrated"
                r.reason = "handoff drain before admission"
                self._finish_obs(r)
        if changed:
            self._sync_tables()

    # -- auditing ---------------------------------------------------------

    def _audit_tier(self) -> list[str]:
        """Tier-residency cross-checks (docs/serving.md "Tiered KV"),
        run by :meth:`audit` when a tier is attached: a tier entry
        holds payload COPIES never pool page ids, so the pool partition
        is tier-independent — what CAN go wrong is identity drift
        between the tree and the store. For every full tree page whose
        chain also has a tier entry, that entry's payload chain must
        equal the node's chain (a mismatch means a later fault-back of
        the evicted node would map wrong KV under this prompt); the
        store-level key↔digest check rides ``PageStore.audit``."""
        from triton_distributed_tpu.models import kv_tier

        problems: list[str] = []
        for node in self.prefix.walk() if self.prefix is not None else ():
            if len(node.chunk) != self.page_size:
                continue
            chain = [int(t) for t in node_chain(node)]
            entry = self.tier.peek(
                kv_tier.PREFIX_KIND, kv_tier.chain_digest(chain)
            )
            if entry is None:
                continue
            if [int(t) for t in entry.get("chain", [])] != chain:
                problems.append(
                    f"tier entry for tree page {node.page} carries a "
                    "different token chain than the node"
                )
        return problems

    def audit(self, *, raise_on_violation: bool = False) -> list[str]:
        """Pool/radix invariant audit (docs/serving.md): free list ∪
        slot-private pages ∪ tree pages ∪ trash page partition the pool
        exactly; shared mappings target live tree pages; tree refcounts
        equal live slot references; host table rows mirror each
        request's page list. Host-side and cheap — ``run()`` calls it
        after every batch, tests after every case. Returns violation
        strings; raises :class:`PoolAuditError` instead when asked."""
        problems: list[str] = []
        owners: dict[str, list[int]] = {}
        shared: dict[str, list[int]] = {}
        for slot, req in enumerate(self._slots):
            if req is None:
                continue
            n_sh = len(req.shared_nodes)
            owners[f"slot{slot}"] = [int(p) for p in req.pages[n_sh:]]
            shared[f"slot{slot}"] = [int(p) for p in req.pages[:n_sh]]
        holders = list(self._slots)
        for i, (req, _step) in enumerate(self._ended):
            # Ended under a step in flight: the pages are still theirs.
            n_sh = len(req.shared_nodes)
            owners[f"ended{i}"] = [int(p) for p in req.pages[n_sh:]]
            shared[f"ended{i}"] = [int(p) for p in req.pages[:n_sh]]
            holders.append(req)
        if self.prefix is not None:
            problems += self.prefix.audit()
            owners["tree"] = [n.page for n in self.prefix.walk()]
            pin_counts: Counter = Counter()
            for req in holders:
                if req is None:
                    continue
                for node in req.shared_nodes:
                    pin_counts[id(node)] += 1
            for node in self.prefix.walk():
                live = pin_counts.get(id(node), 0)
                if node.refcount != live:
                    problems.append(
                        f"tree node page {node.page}: refcount "
                        f"{node.refcount} != {live} live slot references"
                    )
        if self.tier is not None:
            problems += [f"tier: {p}" for p in self.tier.audit()]
            problems += self._audit_tier()
        problems += audit_pool(
            self.pool, self.pool.num_pages, owners, shared=shared,
            reserved=(0,),
        )
        for slot in range(self.max_batch):
            req = self._slots[slot]
            row = self._table[slot]
            if req is None:
                if row.any():
                    problems.append(
                        f"inactive slot {slot} still has a nonzero "
                        "page-table row"
                    )
            else:
                want = np.zeros(self.pps, np.int32)
                want[: len(req.pages)] = req.pages
                if not np.array_equal(row, want):
                    problems.append(
                        f"slot {slot} table row disagrees with its "
                        "request's page list"
                    )
        if self._pend is None and any(r is not None for r in self._slots):
            # With nothing in flight the device's own counters are the
            # host's: a drain that left them apart would append the
            # next row in the wrong place.
            dev = np.asarray(self.cache.kv_len)
            for slot, req in enumerate(self._slots):
                if (req is not None
                        and int(dev[slot]) != int(self._kv_len[slot])):
                    problems.append(
                        f"slot {slot}: device kv_len {int(dev[slot])} != "
                        f"host {int(self._kv_len[slot])}"
                    )
            if self._recurrent:
                # The other kind of state: the rows the next step will
                # advance are the slots that hold a request.
                live = np.asarray(self.cache.live)
                for slot, req in enumerate(self._slots):
                    if bool(live[slot]) != (req is not None):
                        problems.append(
                            f"slot {slot}: in flight on the device "
                            f"{bool(live[slot])}, on the host "
                            f"{req is not None}")
        if problems and raise_on_violation:
            raise PoolAuditError("; ".join(problems))
        return problems
