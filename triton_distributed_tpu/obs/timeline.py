"""Per-request lifecycle timelines: TTFT, TPOT, queue-wait, e2e.

Each serving :class:`~triton_distributed_tpu.models.continuous.Request`
carries one :class:`Timeline` with monotonic stamps at the lifecycle
transitions the engines drive:

=================  ====================================================
``enqueue``        the request entered the system (server payload
                   decode, or ``run()`` entry for direct callers)
``batch_start``    a replica worker took it off its queue into the
                   batch it runs next (replica-served requests only)
``admit``          a decode slot + pages were assigned
``first_chunk``    its first prefill chunk program was dispatched
``first_token``    its first token was sampled (admission prefill)
``finish``         terminal: evicted on success, or torn down with a
                   PR 3 failure status
=================  ====================================================

Derived durations: ``queue_wait_s`` (enqueue→admit),
``batch_wait_s`` (enqueue→batch start: the wait for the running batch
to end; queue wait less batch wait is the wait for a slot),
``prefill_dispatch_s`` (admit→first chunk: how long an admitted
request waited for the chunked-prefill scheduler to first touch it),
``ttft_s`` (enqueue→first token), ``e2e_s`` (enqueue→finish), and
``tpot_s`` — per-output-token time over the steady decode phase,
``(finish - first_token) / (tokens_out - 1)`` (undefined until a
second token exists).

:func:`observe_request` folds a finished timeline into the default
metrics registry: one histogram per duration (TTFT/TPOT/e2e labeled by
finish ``status`` from the PR 3 taxonomy), ``tdt_requests_total`` by
status, and tokens-in/out counters plus per-request size histograms. ``finish`` is latch-once, so a
request can never be observed twice no matter how many teardown paths
race over it.
"""

from __future__ import annotations

import time

from triton_distributed_tpu.obs import metrics as _metrics

# PR 3 failure taxonomy (models/continuous.py) + success + the
# client-initiated ``cancelled`` terminal (docs/serving.md "Streaming
# & cancellation"). Exposition labels come from Request.status, which
# is always one of these.
FINISH_STATUSES = (
    "ok",
    "unservable",
    "overloaded",
    "deadline_exceeded",
    "nan_logits",
    "failed",
    "aborted",
    "cancelled",
)


class Timeline:
    """Monotonic lifecycle stamps for one request. Stamps latch on
    first write (a retried admission keeps the FIRST admit time — the
    queue-wait the client actually experienced).

    ``token_ts`` is the per-token stamp trail the STREAMING path fills
    (docs/serving.md "Streaming & cancellation"): one monotonic stamp
    per token frame, taken at the wire write — so TTFT/TPOT derived
    from a streamed timeline measure when tokens reached the socket,
    not when the engine latched them. Engine-side timelines leave it
    empty and keep the PR 5 first-token/finish arithmetic."""

    __slots__ = ("enqueue_t", "batch_start_t", "admit_t", "first_chunk_t",
                 "first_token_t", "finish_t", "tokens_in", "tokens_out",
                 "status", "reroutes", "token_ts")

    def __init__(self):
        self.enqueue_t: float | None = None
        self.batch_start_t: float | None = None
        self.admit_t: float | None = None
        self.first_chunk_t: float | None = None
        self.first_token_t: float | None = None
        self.finish_t: float | None = None
        self.tokens_in = 0
        self.tokens_out = 0
        self.status: str | None = None
        # Multi-replica serving (docs/scale-out.md): how many times the
        # router re-routed this request off a dead/timed-out replica
        # before this attempt. Stamped by the router, folded into
        # ``tdt_request_reroutes_total`` at finish.
        self.reroutes = 0
        # Wire-side per-token stamps (streaming path only).
        self.token_ts: list[float] = []

    def _stamp(self, attr: str) -> None:
        if getattr(self, attr) is None:
            setattr(self, attr, time.monotonic())

    def stamp_enqueue(self) -> None:
        self._stamp("enqueue_t")

    def stamp_batch_start(self) -> None:
        self._stamp("batch_start_t")

    def stamp_admit(self) -> None:
        self._stamp("admit_t")

    def stamp_first_chunk(self) -> None:
        self._stamp("first_chunk_t")

    def stamp_first_token(self) -> None:
        self._stamp("first_token_t")

    def stamp_token(self) -> None:
        """One per-token stamp (streaming wire writes). The first one
        also latches ``first_token_t``, so a wire-side timeline's TTFT
        is the first FRAME's departure, not an engine-side latch."""
        t = time.monotonic()
        if self.first_token_t is None:
            self.first_token_t = t
        self.token_ts.append(t)

    def finish(self, status: str) -> bool:
        """Latch the terminal stamp + status; True exactly once (the
        caller observes metrics only on True, so racing teardown paths
        can't double-count a request)."""
        if self.status is not None:
            return False
        self.status = status
        self._stamp("finish_t")
        return True

    # -- derived durations -------------------------------------------------

    @staticmethod
    def _delta(a: float | None, b: float | None) -> float | None:
        if a is None or b is None:
            return None
        return max(b - a, 0.0)

    @property
    def queue_wait_s(self) -> float | None:
        return self._delta(self.enqueue_t, self.admit_t)

    @property
    def batch_wait_s(self) -> float | None:
        return self._delta(self.enqueue_t, self.batch_start_t)

    @property
    def prefill_dispatch_s(self) -> float | None:
        return self._delta(self.admit_t, self.first_chunk_t)

    @property
    def ttft_s(self) -> float | None:
        return self._delta(self.enqueue_t, self.first_token_t)

    @property
    def e2e_s(self) -> float | None:
        return self._delta(self.enqueue_t, self.finish_t)

    @property
    def tpot_s(self) -> float | None:
        """Steady-state per-output-token time: decode time after the
        first token, averaged over the remaining tokens. None until a
        second token exists (a 1-token request has no decode phase).
        With per-token wire stamps (streaming) the span is measured
        frame-to-frame — finish-side slack (summary construction)
        never inflates it."""
        if len(self.token_ts) >= 2:
            return ((self.token_ts[-1] - self.token_ts[0])
                    / (len(self.token_ts) - 1))
        span = self._delta(self.first_token_t, self.finish_t)
        if span is None or self.tokens_out < 2:
            return None
        return span / (self.tokens_out - 1)


def _handles(reg) -> dict:
    """Per-registry metric handles, resolved ONCE and cached on the
    registry instance — a request completion must not pay nine
    get-or-create lookups (name-regex + registry lock) the way the
    engines' cached ``_bump`` handles already avoid. ``Registry.clear``
    zeroes series in place, so cached handles survive test resets; a
    racing double-build is harmless (get-or-create is idempotent)."""
    h = getattr(reg, "_timeline_handles", None)
    if h is None:
        h = {
            "requests": reg.counter(
                "tdt_requests_total",
                "Requests finished, by terminal status (PR 3 taxonomy).",
                labels=("status",),
            ),
            "tokens_in": reg.counter(
                "tdt_tokens_in_total", "Prompt tokens accepted."
            ),
            "tokens_in_size": reg.histogram(
                "tdt_request_tokens_in", "Prompt tokens per request.",
                buckets=_metrics.SIZE_BUCKETS,
            ),
            "tokens_out": reg.counter(
                "tdt_tokens_out_total",
                "Tokens generated (partials included).",
            ),
            "tokens_out_size": reg.histogram(
                "tdt_request_tokens_out", "Output tokens per request.",
                buckets=_metrics.SIZE_BUCKETS,
            ),
            "reroutes": reg.counter(
                "tdt_request_reroutes_total",
                "Times requests were re-routed off a dead or "
                "timed-out replica (docs/scale-out.md).",
            ),
            "queue_wait": reg.histogram(
                "tdt_request_queue_wait_seconds",
                "Enqueue-to-admission wait.",
            ),
            "batch_wait": reg.histogram(
                "tdt_request_batch_wait_seconds",
                "Enqueue-to-batch-start wait behind a replica worker.",
            ),
            "prefill_dispatch": reg.histogram(
                "tdt_request_prefill_dispatch_seconds",
                "Admission-to-first-prefill-chunk wait.",
            ),
            "ttft": reg.histogram(
                "tdt_request_ttft_seconds",
                "Time to first token, by finish status.",
                labels=("status",),
            ),
            "tpot": reg.histogram(
                "tdt_request_tpot_seconds",
                "Per-output-token time after the first token, by finish "
                "status.",
                labels=("status",),
            ),
            "e2e": reg.histogram(
                "tdt_request_e2e_seconds",
                "Enqueue-to-finish latency, by finish status.",
                labels=("status",),
            ),
        }
        reg._timeline_handles = h
    return h


def observe_request(tl: Timeline, registry=None) -> None:
    """Fold one FINISHED timeline into the metrics registry. Durations
    that never happened (a shed request has no admit stamp) are simply
    skipped — the status-labeled ``tdt_requests_total`` still counts
    the request."""
    reg = registry if registry is not None else _metrics.default_registry()
    h = _handles(reg)
    status = tl.status or "ok"
    h["requests"].inc(status=status)
    if tl.reroutes:
        h["reroutes"].inc(tl.reroutes)
    if tl.tokens_in:
        h["tokens_in"].inc(tl.tokens_in)
        h["tokens_in_size"].observe(tl.tokens_in)
    if tl.tokens_out:
        h["tokens_out"].inc(tl.tokens_out)
        h["tokens_out_size"].observe(tl.tokens_out)
    qw = tl.queue_wait_s
    if qw is not None:
        h["queue_wait"].observe(qw)
    bw = tl.batch_wait_s
    if bw is not None:
        h["batch_wait"].observe(bw)
    pd = tl.prefill_dispatch_s
    if pd is not None:
        h["prefill_dispatch"].observe(pd)
    ttft = tl.ttft_s
    if ttft is not None:
        h["ttft"].observe(ttft, status=status)
    tpot = tl.tpot_s
    if tpot is not None:
        h["tpot"].observe(tpot, status=status)
    e2e = tl.e2e_s
    if e2e is not None:
        h["e2e"].observe(e2e, status=status)
