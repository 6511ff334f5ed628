"""Serving engine: prefill + jitted decode loop.

Parity: reference ``models/engine.py`` — ``Engine.serve``:113 (prefill →
switch to dist kernels → CUDA-graph capture :75-105 → decode loop
:164-169 with per-step sampling and KV offset bump).

TPU translation: the CUDA graph is ``jax.jit`` of the whole decode step
(trace once, replay per token); the cache is donated so decode is
in-place at the XLA level. The decode loop stays a host loop (the
reference replays its graph from host too), keeping sampling/stopping
logic in Python while each step is a single device program.
"""

from __future__ import annotations

import time
import weakref
from collections import deque
from typing import Literal

import jax
import jax.numpy as jnp
import numpy as np

from triton_distributed_tpu.models import sampling
from triton_distributed_tpu.models.kv_cache import KVCache
from triton_distributed_tpu.models.qwen import Mode, Qwen3
from triton_distributed_tpu.models.stats import STAT_METRICS
from triton_distributed_tpu.obs import metrics as obs_metrics

# Engine modes: the model's xla/pallas decode paths plus the megakernel
# ("mega"): whole-step single-kernel decode, with a multi-step fast
# path (several steps per launch, in-kernel argmax — cross-rank
# exchanged under TP, Gumbel-perturbed for temperature sampling) when
# the cache is dense and no top-p filter truncates the distribution.
EngineMode = Literal["xla", "pallas", "mega"]


class _PrefixState:
    """Engine's cross-serve prefix-cache state: the pool-backed cache
    arrays, their pool, and the radix tree over it. ``dirty`` is set for
    the duration of a serve — a crash mid-serve leaves it set, and the
    next serve rebuilds instead of reusing donated cache buffers /
    permanently pinned pages."""

    __slots__ = ("key", "cache", "pool", "tree", "dirty")

    def __init__(self, key, cache, pool, tree):
        self.key = key
        self.cache = cache
        self.pool = pool
        self.tree = tree
        self.dirty = False


def prefill_suffix_chunks(
    model,
    cache,
    slot: int,
    prompt,
    start: int,
    chunk_width: int,
    mode,
    between_chunks=None,
):
    """Chunk-prefill ``prompt[start:]`` of one slot/row over the paged
    cache — the prefix-cache suffix path, shared by ``Engine`` and
    ``ContinuousEngine`` so the chunk-width rounding and the
    offset/new_len/last_idx alignment live in exactly one place.

    ``chunk_width=0`` runs the whole suffix as one (rounded) chunk.
    ``between_chunks(cache, new_len)`` runs after every non-final chunk
    (the continuous engine interleaves a decode step there) and must
    return the cache to keep threading. Returns
    ``(last-token logits [V], cache, chunks_run)``.
    """
    from triton_distributed_tpu.models.paged_kv_cache import gather_bucket
    from triton_distributed_tpu.models.prefix_cache import round_chunk
    from triton_distributed_tpu.runtime.profiling import trace_span

    s = len(prompt)
    # A model may round its chunk widths coarser than the tile's grain
    # (fewer programs to compile): its own ``round_chunk``.
    round_chunk = getattr(model, "round_chunk", round_chunk)
    c = round_chunk(chunk_width) if chunk_width else round_chunk(s - start)
    page = int(cache.k_pages.shape[3])
    pps = int(cache.page_table.shape[1])
    logits, off, chunks = None, start, 0
    while off < s:
        take = min(c, s - off)
        buf = np.zeros(c, np.int32)
        buf[:take] = prompt[off : off + take]
        # Gather bucket: enough table entries to cover the chunk's last
        # (padded) position, rounded to a power of two so at most
        # log2(pages_per_seq) programs compile per chunk width — a short
        # suffix never gathers the full max_length KV view.
        kv_pages = gather_bucket(off + c, page, pps)
        with trace_span("prefix_cache:chunk", slot=slot, offset=off,
                        take=take, _ring=False):
            logits, cache = model.prefill_paged_chunk(
                buf, slot, off, off + take, take - 1, cache, mode,
                kv_pages=kv_pages,
            )
        chunks += 1
        off += take
        if off < s and between_chunks is not None:
            cache = between_chunks(cache, off)
    return logits, cache, chunks


class MegaDispatch:
    """Shared megakernel-mode dispatch (Engine + ContinuousEngine):
    lazy MegaQwen3 construction, xla prefill fallback, mega-vs-model
    decode routing, and the device task tracer's host plumbing.
    Expects ``self.model`` and ``self.mode``; ``self.mega_cfg`` (an
    optional ``MegaConfig``, e.g. a sweep-tuned one —
    ``MegaConfig.from_spec(...)`` parses the ``perf/MEGA_TUNED.json``
    config strings) customizes the kernel."""

    _mega = None
    mega_cfg = None

    # -- device task tracer (docs/observability.md) ----------------------

    def _init_kernel_trace(self, kernel_trace: bool, mode: str) -> None:
        """Ctor-time tracer state, shared by both engines: validates
        the knob (the tracer rides the megakernel's trace-ring
        operand; xla/pallas decode paths have no device ring) and sets
        up the bounded launch ledger."""
        if kernel_trace and mode != "mega":
            raise ValueError(
                "kernel_trace=True requires mode='mega' (the tracer "
                "rides the megakernel's trace-ring operand; the "
                "xla/pallas decode paths have no device ring)"
            )
        self.kernel_trace = bool(kernel_trace)
        self._kernel_traces: "deque" = deque(maxlen=8)
        self._trace_launch_n = 0

    def _record_kernel_trace(
        self, ring, t0: float, wall_s: float, nsteps: int,
        trace_ids: dict | None = None,
    ) -> None:
        """Fold one launch's device ring into telemetry: the inline
        work is vectorized over the raw ring (gap check, per-opcode
        durations, measured overlap → registry); the launch is kept
        (bounded deque) with the ring attached, records decoding
        lazily for ``kernel_trace_summary`` and the merged timeline."""
        from triton_distributed_tpu.obs import kernel_trace as _kt

        self._trace_launch_n += 1
        launch = _kt.KernelTraceLaunch(
            wall_s=wall_s, t0=t0, trace_ids=trace_ids or {},
            nsteps=nsteps, launch=self._trace_launch_n,
            ring=np.asarray(ring),
        )
        self._kernel_traces.append(launch)
        _kt.observe_launch(launch)

    def kernel_trace_launches(self) -> list:
        """Recent traced launches (``KernelTraceLaunch``), oldest
        first — what ``obs.kernel_trace.merge_with_host_profile``
        takes to add device task rows to the merged chrome timeline."""
        return list(self._kernel_traces)

    def kernel_trace_summary(self) -> dict:
        """JSON-ready device-tracer state for the server's
        ``{"cmd": "kernel_trace"}`` verb: knob, launch count (process
        lifetime), and the recent launches' per-opcode tick totals +
        measured overlap + request trace ids."""
        return {
            "enabled": self.kernel_trace,
            "mode": self.mode,
            "launches": self._trace_launch_n,
            "recent": [ln.summary() for ln in self._kernel_traces],
        }

    @property
    def _prefill_mode(self) -> Mode:
        # The mega prefill path is single-sequence; batched serving
        # prefills through the model's own path.
        return "xla" if self.mode == "mega" else self.mode

    def _mega_model(self):
        if self._mega is None:
            from triton_distributed_tpu.megakernel import MegaQwen3
            from triton_distributed_tpu.megakernel.code_generator import (
                MegaConfig,
            )

            cfg = self.mega_cfg
            if cfg is None:
                # Serving default (docs/megakernel.md "Serving fast
                # path"): fused norms + cross-task tile-0 prefetch +
                # split send-early/wait-late allreduces. All three are
                # token-exact vs the plain build (tested individually
                # and composed); overlap_ar only pays with
                # cross_prefetch on and the weight stream directly
                # after AR_WAIT, which is what fuse_norms arranges.
                cfg = MegaConfig(
                    fuse_norms=True, cross_prefetch=True, overlap_ar=True
                )
            self._mega = MegaQwen3(self.model, cfg=cfg)
        return self._mega

    def _decode_step(self, tok, cache):
        if self.mode == "mega":
            return self._mega_model().decode_step(tok, cache)
        return self.model.decode_step(tok, cache, self.mode)


class Engine(MegaDispatch):
    """Parity: reference ``Engine`` (``models/engine.py:37``)."""

    # Live engines, auditable by the shared pytest fixture
    # (tests/conftest.py) after every test.
    _live: "weakref.WeakSet[Engine]" = weakref.WeakSet()

    def __init__(
        self,
        model: Qwen3,
        *,
        temperature: float = 0.0,
        top_p: float = 1.0,
        top_k: int = 0,
        mode: EngineMode = "xla",
        verbose: bool = False,
        seed: int = 0,
        paged: bool = False,
        page_size: int = 128,
        mega_cfg=None,
        prefix_cache: bool = False,
        prefill_chunk: int = 0,
        speculative: int = 0,
        spec_width: int = 4,
        kv_dtype: str | None = None,
        kernel_trace: bool = False,
    ):
        self.model = model
        self.temperature = temperature
        self.top_p = top_p
        self.top_k = top_k
        self.mode = mode
        self.mega_cfg = mega_cfg
        self.verbose = verbose
        self.key = jax.random.key(seed)
        self.last_stats: dict = {}
        # Paged serving (parity: the reference megakernel's page-pool
        # cache): prefill runs dense per sequence, pages are scattered
        # through the table, decode attends the pool directly.
        self.paged = paged
        self.page_size = page_size
        # Paged geometry must tile exactly: a ragged final page would
        # make every ceil-divide table bound (``pps``, ``gather_bucket``
        # widths, per-slot page counts) silently disagree with the
        # device cache shape. Checked after the knob-composition
        # refusals below — a caller holding two mistakes should hear
        # about the flag conflict before the geometry.
        geometry_error = None
        if paged and model.cfg.max_length % page_size != 0:
            geometry_error = (
                f"max_length={model.cfg.max_length} is not a multiple "
                f"of page_size={page_size}; paged serving needs the "
                "context to tile into whole pages"
            )
        # Quantized KV storage (docs/serving.md "Quantized KV cache"):
        # int8 pool + per-page-per-head scales, dequantized inside the
        # attention kernels. The explicit knob wins over the model
        # config's ``kv_dtype``.
        self.kv_dtype = kv_dtype if kv_dtype is not None else (
            model.cfg.kv_dtype
        )
        # kv_dtype composes with every mode incl. 'mega' (the fused
        # decode dequantizes the int8 pool in-kernel via its per-page
        # scales — docs/megakernel.md "Serving fast path").
        if self.kv_dtype is not None and not paged:
            raise ValueError(
                "kv_dtype requires paged=True (scales live on the "
                "page pool; the dense cache has no pages)"
            )
        # Prefix-cache mode (requires paged): pool + cache + radix tree
        # persist ACROSS serve() calls, finished rows retire their pages
        # into the tree, and later calls prefill only uncached suffixes
        # (docs/serving.md). ``prefill_chunk`` bounds each chunk program.
        if prefix_cache and not paged:
            raise ValueError(
                "prefix_cache=True requires paged=True (the radix tree "
                "shares pool pages; a dense cache has none)"
            )
        self.prefix_cache = prefix_cache
        self.prefill_chunk = prefill_chunk
        # Speculative decoding (docs/serving.md): draft up to
        # ``speculative`` tokens per row from its own n-gram history and
        # verify them in ONE chunked paged-prefill forward; rejected
        # tokens roll the KV back. Needs the paged cache (verify chunks
        # run over the page pool) and the model decode paths (the
        # megakernel's multi-step launch already amortizes what
        # speculation would).
        if speculative:
            if not paged:
                raise ValueError(
                    "speculative=K requires paged=True (verify chunks "
                    "run through the paged chunk-prefill path)"
                )
            if mode == "mega":
                raise ValueError(
                    "speculative=K composes with mode='xla'/'pallas', "
                    "not the megakernel"
                )
        if geometry_error:
            raise ValueError(geometry_error)
        self.speculative = int(speculative)
        # Tree speculation (docs/serving.md "Speculative decoding"):
        # multi-branch draft tries verified in one chunk forward.
        # Full-width pools only — the row-move commit cannot preserve
        # quantized rows (scale reset at page offset 0), so int8 pools
        # keep width-1 chains.
        self.spec_width = max(int(spec_width), 1)
        self._spec_tree = (
            bool(speculative) and self.spec_width > 1
            and self.kv_dtype is None
        )
        # Device task tracer (docs/observability.md "Device task
        # tracer"): multi-step mega launches in serve() carry the
        # in-kernel trace ring; decoded launches feed
        # tdt_mega_task_seconds and are kept
        # (bounded) for kernel_trace_summary / the merged timeline.
        self._init_kernel_trace(kernel_trace, mode)
        self._prefix_state: _PrefixState | None = None
        # Page-pool free list, populated by the first paged serve();
        # continuous-batching admission/eviction draws from it.
        self._pool = None
        # Jitted sampled-noise wrappers, keyed by (b, s_max, NS): a
        # fresh closure per serve() would retrace + recompile the
        # megakernel program every call.
        self._sampled_multi: dict = {}
        # Registry handles resolved ONCE (the ContinuousEngine
        # `_metric_handles` convention): serve() then increments
        # without paying a name lookup under the global registry lock.
        self._metric_handles = {
            "decode_steps": obs_metrics.counter(
                *STAT_METRICS["decode_steps"]),
            "prefill_tokens": obs_metrics.counter(
                *STAT_METRICS["prefill_tokens"]),
            "generated_tokens": obs_metrics.counter(
                *STAT_METRICS["generated_tokens"]),
            "serve_seconds": obs_metrics.histogram(
                "tdt_engine_serve_seconds",
                "Wall time of one fixed-batch serve() call."),
        }
        Engine._live.add(self)

    def audit(self, *, raise_on_violation: bool = False) -> list[str]:
        """Pool/radix invariant audit of the cross-serve prefix state
        (parity with :meth:`ContinuousEngine.audit`): between serves
        every page is either free or tree-owned (finished rows retired
        their pages), no page has two owners, and no pins are left
        behind. A ``dirty`` state (aborted serve) is skipped — it is
        rebuilt, not reused, on the next serve. Returns violation
        strings; raises ``PoolAuditError`` instead when asked."""
        state = self._prefix_state
        if state is None or state.dirty:
            return []
        from triton_distributed_tpu.models.paged_kv_cache import (
            PoolAuditError,
            audit_pool,
        )

        problems = state.tree.audit()
        for node in state.tree.walk():
            if node.refcount:
                problems.append(
                    f"idle tree node page {node.page} still pinned "
                    f"(refcount {node.refcount}) between serves"
                )
        problems += audit_pool(
            state.pool, state.pool.num_pages,
            {"tree": [n.page for n in state.tree.walk()]}, reserved=(0,),
        )
        if problems and raise_on_violation:
            raise PoolAuditError("; ".join(problems))
        return problems

    def _sample(self, logits: jax.Array) -> jax.Array:
        if self.temperature <= 0.0:
            return sampling.greedy(logits)
        self.key, sub = jax.random.split(self.key)
        return sampling.sample(
            logits, sub, self.temperature, self.top_p, self.top_k
        )

    def serve(
        self,
        input_ids,  # [B, S] int32 (list/np/jnp)
        gen_len: int,
        max_length: int | None = None,
        profile: str | None = None,
        prompt_start: list | np.ndarray | None = None,
        ns: int = 8,
    ) -> np.ndarray:
        """Generate ``gen_len`` tokens for each sequence; returns
        ``[B, S + gen_len]`` (parity: ``Engine.serve``). ``profile``
        names a trace directory for the decode loop (parity: the
        reference Engine's 64-step decode profile, ``engine.py:151-177``).

        ``prompt_start[i]`` marks where row i's real prompt begins
        (everything before it is client left-padding, e.g. for tp
        divisibility). Rows are rolled so pads sit on the RIGHT, where
        causal masking makes them inert, and the real length rides to
        ``prefill(true_len=...)`` — pad tokens never influence output.

        ``ns`` is the megakernel multi-step launch width (mode='mega'
        only; perf/mega_serve_bench.py sweeps it — wider launches
        amortize more host dispatch per token against a longer
        host-blind stretch).
        """
        input_ids = np.asarray(input_ids, np.int32)
        b, s = input_ids.shape
        n = self.model.ctx.axis_size(self.model.axis)
        starts = np.zeros(b, np.int64) if prompt_start is None else (
            np.asarray(prompt_start, np.int64)
        )
        if starts.shape != (b,) or (starts < 0).any() or (starts >= s).any():
            raise ValueError(
                f"prompt_start must be [batch={b}] ints in [0, {s}); got "
                f"{starts.tolist()}"
            )
        max_length = max_length or self.model.cfg.max_length
        if self.paged and max_length % self.page_size != 0:
            raise ValueError(
                f"max_length={max_length} is not a multiple of "
                f"page_size={self.page_size}; paged serving needs the "
                "context to tile into whole pages"
            )

        # Batched prefill (one jitted program for all rows — the
        # reference engine loops rows from host, engine.py:113). Client
        # left-padding rolls to the right where causal masking makes it
        # inert; tp divisibility is met by further right-padding.
        t0 = time.perf_counter()
        rows = np.stack(
            [np.roll(input_ids[i], -int(starts[i])) for i in range(b)]
        )
        pad = (-s) % n
        if pad:
            rows = np.concatenate(
                [rows, np.zeros((b, pad), np.int32)], axis=1
            )
        true_lens = (s - starts).astype(np.int32)
        # Capacity guards: the prefill writes s + pad cache rows, and
        # decode appends gen_len - 1 KV rows past each row's REAL
        # prompt (kv_len starts at true_len, not the padded width s).
        # Past max_length the dynamic_update_slice append would clamp
        # and silently overwrite cached rows (corrupt tokens, no
        # error). Left-padded rows therefore only need true_len +
        # gen_len - 1 to fit, not s + gen_len - 1.
        if s + pad > max_length:
            raise ValueError(
                f"padded prompt width ({s} + {pad}) exceeds "
                f"max_length={max_length}; raise max_length or shorten"
            )
        if int(true_lens.max()) + gen_len - 1 > max_length:
            raise ValueError(
                f"longest real prompt ({int(true_lens.max())}) + gen_len "
                f"({gen_len}) exceeds max_length={max_length}; raise "
                f"max_length or shorten"
            )
        if self.speculative and gen_len > 1:
            from triton_distributed_tpu.models.prefix_cache import round_chunk

            # Every verify chunk pads to round_chunk(·) ≥ 16 and its pad
            # rows write KV too — the furthest row's final chunk must
            # still fit under max_length or the page table runs out of
            # entries mid-verify (there is no batched fallback inside
            # the per-row speculative loop).
            pad = round_chunk(1)
            if int(true_lens.max()) + gen_len - 2 + pad > max_length:
                raise ValueError(
                    f"speculative serve pads verify chunks to {pad} "
                    f"tokens; longest prompt ({int(true_lens.max())}) + "
                    f"gen_len ({gen_len}) + {pad - 1} exceeds "
                    f"max_length={max_length} — raise max_length or "
                    f"shorten"
                )
        row_meta = None
        if self.paged and self.prefix_cache:
            logits, cache, row_meta = self._prefix_prefill(
                rows, true_lens, gen_len, max_length
            )
        elif self.paged:
            from triton_distributed_tpu.models.paged_kv_cache import (
                init_paged_cache,
                write_prefill,
            )

            cache, self._pool = init_paged_cache(
                self.model.cfg, b, self.model.ctx, self.model.axis,
                max_length=max_length, page_size=self.page_size,
                kv_dtype=self.kv_dtype,
            )
            # One batch-1 dense scratch, reused per row then scattered
            # into pages — a full-batch dense cache alongside the pool
            # would double peak KV memory, defeating paging.
            dense1 = self.model.new_cache(1, max_length)
            last_logits = []
            for i in range(b):
                logits_i, dense1 = self.model.prefill_batched(
                    jnp.asarray(rows[i : i + 1]), dense1, self._prefill_mode,
                    jnp.asarray(true_lens[i : i + 1]),
                )
                cache = write_prefill(
                    cache, i, dense1.k, dense1.v, int(true_lens[i])
                )
                last_logits.append(logits_i[0])
            logits = jnp.stack(last_logits)
        else:
            cache = self.model.new_cache(b, max_length)
            logits, cache = self.model.prefill_batched(
                jnp.asarray(rows), cache, self._prefill_mode,
                jnp.asarray(true_lens),
            )
        t_prefill = time.perf_counter() - t0

        out = [input_ids]
        tok = self._sample(logits)
        out.append(np.asarray(tok)[:, None])

        from triton_distributed_tpu.runtime.profiling import group_profile

        NS = int(ns)  # multi-step launch width
        if NS < 1:
            raise ValueError(f"ns must be >= 1, got {ns}")
        if self.paged:
            s_max = int(cache.page_table.shape[1]) * self.page_size
        else:
            s_max = int(cache.k.shape[3])
        # Capacity: the furthest row holds max(true_lens) cached tokens
        # and gains one per decode step; a multi launch appends NS rows
        # at once, so it must not start within NS of s_max (a clamped
        # dynamic_update_slice would silently overwrite cached rows).
        kv_high = int(true_lens.max())
        # Sampling composes with multi-step via the Gumbel-max trick
        # (argmax over logits + T*gumbel == categorical(logits/T));
        # top-p/top-k truncation now ALSO composes on single-rank
        # builds — the in-kernel bisection filter restricts that
        # argmax to the host filter_logits keep-set
        # (docs/megakernel.md "Resident decode"). Sharded LM heads
        # fall back to single steps for filtered sampling.
        V = self.model.cfg.vocab_size
        sampled = self.temperature > 0.0
        need_filter = sampled and (
            0 < self.top_k < V or self.top_p < 1.0
        )
        filtered = need_filter and n == 1 and NS > 1
        multi_launches = 0
        if (
            self.mode == "mega"
            and not self.speculative
            and (not need_filter or filtered)
        ):
            multi_launches = min(
                (gen_len - 1) // NS, max(s_max - kv_high, 0) // NS
            )
        t0 = time.perf_counter()
        spec_counters = None
        with group_profile(profile, do_prof=profile is not None):
            if self.speculative and gen_len > 1:
                tail, cache, spec_counters = self._spec_decode(
                    cache, np.asarray(tok), rows, true_lens, gen_len,
                    max_length,
                )
                out.append(tail)
            left = 0 if self.speculative else gen_len - 1
            if multi_launches:
                # Multi-step fast path: NS steps per kernel launch
                # (in-kernel argmax — Gumbel-perturbed when sampling),
                # amortizing per-launch cost; the remainder runs
                # through the single-step kernel rather than paying a
                # full extra megakernel build per distinct tail length.
                v_pad = self.model.params.lm_head.shape[1]
                quant = self.paged and self.kv_dtype is not None
                base_fn = self._mega_model().decode_multi_fn(
                    b, s_max, NS, sampled=sampled,
                    page=self.page_size if self.paged else 0,
                    kv_quant=quant,
                    num_pages=(
                        int(cache.k_pages.shape[1]) if self.paged else 0
                    ),
                    trace=self.kernel_trace,
                    filtered=filtered,
                )
                sampcfg = None
                if filtered:
                    # Per-row [1/T, top-k window, top-p, enable] the
                    # in-kernel bisection filter consumes — identical
                    # rows here (serve()'s knobs are engine-global).
                    t, k, p = self.temperature, self.top_k, self.top_p
                    sampcfg = jnp.asarray(np.tile(np.asarray(
                        [[1.0 / t, float(k) if 0 < k < V else float(V),
                          min(max(p, 1e-6), 1.0), 1.0]], np.float32,
                    ), (b, 1)))
                if sampled:
                    # Draw the Gumbel noise INSIDE the jit so each rank
                    # materializes only its vocab shard — an eager
                    # host-side draw would commit a [NS, b, V_pad] f32
                    # array to one device and reshard it every launch.
                    # Cached per shape: a fresh closure per serve()
                    # would retrace + recompile the megakernel program.
                    wkey = (b, s_max, NS, self.paged, quant, filtered)
                    fn = self._sampled_multi.get(wkey)
                    if fn is None:
                        def tdt_mega_round(params, tok, cache, key, temp,
                                           cfg):
                            noise = temp * jax.random.gumbel(
                                key, (NS, b, v_pad), jnp.float32
                            )
                            tail = (noise, cfg) if filtered else (noise,)
                            return base_fn(params, tok, cache, *tail)

                        fn = jax.jit(tdt_mega_round, donate_argnums=(2,))
                        self._sampled_multi[wkey] = fn
                else:
                    fn = base_fn
                for _ in range(multi_launches):
                    if sampled:
                        self.key, sub = jax.random.split(self.key)
                        extra = (
                            sub, jnp.float32(self.temperature), sampcfg,
                        )
                    else:
                        extra = ()
                    t_launch = time.monotonic()
                    launch_outs = fn(
                        # _step_params: the Q8Params pytree under
                        # MegaConfig(wq8=True), model.params otherwise.
                        self._mega_model()._step_params(), tok, cache,
                        *extra,
                    )
                    if self.kernel_trace:
                        toks, logits, cache, ring = launch_outs
                        toks = np.asarray(toks)  # also fences the wall
                        self._record_kernel_trace(
                            ring, t_launch,
                            time.monotonic() - t_launch, NS,
                        )
                    else:
                        toks, logits, cache = launch_outs
                        toks = np.asarray(toks)  # [NS, b]
                    out.append(toks.T)
                    tok = jnp.asarray(toks[-1])
                    left -= NS
            for _ in range(left):
                logits, cache = self._decode_step(tok, cache)
                tok = self._sample(logits)
                out.append(np.asarray(tok)[:, None])
        t_decode = time.perf_counter() - t0

        result = np.concatenate(out, axis=1)
        # Core serving-stats keys (models/stats.py): one schema both
        # engines expose, so dashboards never fork on engine type.
        # decode_steps counts batched decode programs ONLY — under
        # speculation the verify-chunk forwards ride spec_verify_steps
        # and target_steps, matching the continuous engine's ledger
        # (target_steps == decode_steps + spec_verify_steps).
        steps = max(gen_len - 1, 0)
        if spec_counters is not None:
            steps = spec_counters["spec_decode_steps"]
        # Work DONE, not accepted: prefix-cache serves count only the
        # suffix tokens actually prefilled (hits ride prefix_hit_tokens).
        prefill_toks = int(true_lens.sum())
        if row_meta is not None:
            prefill_toks = self._prefix_counters["prefill_tokens"]
        self.last_stats = {
            "prefill_s": t_prefill,
            "decode_s": t_decode,
            "decode_ms_per_step": (
                t_decode / max(gen_len - 1, 1) * 1e3
            ),
            "tokens_per_s": b * max(gen_len - 1, 1) / max(t_decode, 1e-9),
            "decode_steps": steps,
            "prefill_tokens": prefill_toks,
            "generated_tokens": int(b * gen_len),
        }
        if self.kernel_trace:
            self.last_stats["mega_trace_launches"] = self._trace_launch_n
        if obs_metrics.default_registry().enabled:
            h = self._metric_handles
            h["decode_steps"].inc(steps)
            h["prefill_tokens"].inc(prefill_toks)
            h["generated_tokens"].inc(
                self.last_stats["generated_tokens"]
            )
            h["serve_seconds"].observe(t_prefill + t_decode)
        if self.paged:
            from triton_distributed_tpu.models.paged_kv_cache import (
                kv_bytes_per_token,
            )

            self.last_stats["kv_bytes_per_token"] = kv_bytes_per_token(cache)
            self.last_stats["kv_dtype"] = (
                self.kv_dtype or str(jnp.dtype(cache.k_pages.dtype))
            )
        else:
            L, _b, H, _s, hd = cache.k.shape
            self.last_stats["kv_bytes_per_token"] = float(
                2 * L * H * hd * cache.k.dtype.itemsize
            )
            self.last_stats["kv_dtype"] = str(jnp.dtype(cache.k.dtype))
        if spec_counters is not None:
            self.last_stats.update(spec_counters)
        if getattr(self.model.cfg, "num_experts", 0):
            # MoE serving ledger (docs/serving.md "MoE serving"): the
            # fixed-batch engine computes the routed count once —
            # prefilled positions plus every decode-phase position, ×
            # top_k assignments each. Under speculation the decode
            # positions are what the verify/decode forwards actually
            # routed (Σ(draft+1) per verify chunk = draft_tokens +
            # verify_steps, plus b per batched step), matching the
            # ContinuousEngine's per-site bumps so the shared
            # tdt_moe_routed_tokens_total counter ties out across
            # engines. a2a_dropped mirrors ``DispatchState.num_dropped``
            # (ops/moe/ep_a2a.py): this engine's forward is lossless
            # (full-expert streaming), so it is 0 by construction here;
            # capacity-mode EP paths surface their detected drops
            # through the same key (perf/moe_serve_bench.py).
            k = self.model.cfg.num_experts_per_tok
            if spec_counters is not None:
                decode_pos = (
                    spec_counters["spec_draft_tokens"]
                    + spec_counters["spec_verify_steps"]
                    + b * spec_counters["spec_decode_steps"]
                )
            else:
                decode_pos = b * max(gen_len - 1, 0)
            self.last_stats["moe_routed_tokens"] = (
                (prefill_toks + decode_pos) * k
            )
            self.last_stats["a2a_dropped"] = 0
            self.last_stats["num_experts"] = self.model.cfg.num_experts
            self.last_stats["experts_per_tok"] = k
        if row_meta is not None:
            self._prefix_retire(
                result, rows, true_lens, gen_len, cache, row_meta
            )
        if self.verbose:
            print(f"[engine] {self.last_stats}")
        return result

    # -- speculative decode ------------------------------------------------

    def _spec_decode(self, cache, first_toks, rows, true_lens, gen_len,
                     max_length):
        """Per-row speculative decode over the paged cache: each row
        drafts from its own n-gram history, verifies the draft in one
        chunked forward (``spec_verify_slot``), and rolls rejected KV
        back (``rollback_kv``). Rows advance at their own pace — a row
        with a hot draft emits K+1 tokens per target step while a
        chaotic row emits 1 — until every row holds ``gen_len`` tokens.
        Returns ``(tail [b, gen_len-1], cache, counters)`` where tail
        excludes the prefill-sampled first token (already appended by
        ``serve``)."""
        from triton_distributed_tpu.models.paged_kv_cache import rollback_kv
        from triton_distributed_tpu.models.prefix_cache import round_chunk
        from triton_distributed_tpu.models.speculative import (
            SpecState,
            TreeDraft,
            cap_draft,
            commit_tree_path,
            spec_verify_slot,
            spec_verify_tree,
        )

        b = len(first_toks)
        kv = true_lens.astype(np.int64).copy()
        outs, states = [], []
        for i in range(b):
            st = SpecState(
                self.speculative,
                w_max=self.spec_width if self._spec_tree else 1,
            )
            st.observe(rows[i][: int(true_lens[i])])
            st.observe([int(first_toks[i])])
            states.append(st)
            outs.append([int(first_toks[i])])
        # Radix continuations feed the draft tries when the cross-serve
        # prefix state exists — previous serves' finished chains are
        # exactly the re-ask population tree speculation wins on.
        radix = (
            self._prefix_state.tree
            if self._spec_tree and self._prefix_state is not None
            else None
        )
        counters = {
            "spec_verify_steps": 0,
            "spec_decode_steps": 0,
            "spec_draft_tokens": 0,
            "spec_accepted_tokens": 0,
            "spec_rollback_tokens": 0,
            "spec_tree_rounds": 0,
            "spec_tree_nodes": 0,
            "spec_tree_depth": 0,
            "spec_tree_branch_accepts": 0,
        }

        def verify_row(i, draft, cache):
            emitted, cache, a, self.key = spec_verify_slot(
                self.model, cache, i, outs[i][-1], draft, int(kv[i]),
                self._prefill_mode, key=self.key,
                temperature=self.temperature, top_p=self.top_p,
                top_k=self.top_k,
            )
            if emitted is None:
                # Non-finite verify logits: the fixed-batch engine has
                # no per-request failure channel — fail the serve loud
                # (prefix state, if any, is marked dirty and rebuilt).
                from triton_distributed_tpu.models.sampling import (
                    NonFiniteLogitsError,
                )

                raise NonFiniteLogitsError(
                    f"non-finite logits in speculative verify chunk "
                    f"(row {i})", slot=i,
                )
            counters["spec_verify_steps"] += 1
            counters["spec_draft_tokens"] += len(draft)
            counters["spec_accepted_tokens"] += a
            states[i].record(len(draft), a)
            new_kv = int(kv[i]) + a + 1
            if a < len(draft):
                counters["spec_rollback_tokens"] += len(draft) - a
                cache = rollback_kv(cache, i, new_kv)
            kv[i] = new_kv
            states[i].observe(emitted)
            outs[i].extend(emitted)
            return cache

        def verify_tree_row(i, tr, cache):
            def nk():
                self.key, sub = jax.random.split(self.key)
                return sub

            emitted, cache, path = spec_verify_tree(
                self.model, cache, i, tr, int(kv[i]),
                self._prefill_mode, next_key=nk,
                temperature=self.temperature, top_p=self.top_p,
                top_k=self.top_k,
            )
            if emitted is None:
                from triton_distributed_tpu.models.sampling import (
                    NonFiniteLogitsError,
                )

                raise NonFiniteLogitsError(
                    f"non-finite logits in speculative tree-verify "
                    f"chunk (row {i})", slot=i,
                )
            a = len(path)
            counters["spec_verify_steps"] += 1
            counters["spec_tree_rounds"] += 1
            counters["spec_tree_nodes"] += tr.num_drafted
            counters["spec_tree_depth"] += tr.max_depth
            if any(int(n) != j + 1 for j, n in enumerate(path)):
                counters["spec_tree_branch_accepts"] += 1
            counters["spec_draft_tokens"] += tr.num_drafted
            counters["spec_accepted_tokens"] += a
            states[i].record_tree(tr.num_drafted, tr.max_depth, a)
            # Commit the accepted branch's rows into linear positions
            # BEFORE the rollback truncates kv_len past them.
            cache = commit_tree_path(cache, i, int(kv[i]), path)
            new_kv = int(kv[i]) + a + 1
            if a < tr.num_drafted:
                counters["spec_rollback_tokens"] += tr.num_drafted - a
                cache = rollback_kv(cache, i, new_kv)
            kv[i] = new_kv
            states[i].observe(emitted)
            outs[i].extend(emitted)
            return cache

        def plan_row(i, k):
            """One row's draft for a ``k``-token budget: a TreeDraft
            when the candidate set genuinely branches, else a linear
            token list (possibly radix-sourced), else None."""
            if k <= 0:
                return None
            if radix is not None and states[i].width > 1:
                paths = radix.propose_continuations(
                    states[i].draft.history,
                    width=states[i].width, depth=k,
                )
                ng = states[i].propose(k)
                if ng:
                    paths.append(ng)
                if paths:
                    tr = TreeDraft(outs[i][-1])
                    for p in paths:
                        tr.add_path(p[:k], budget=round_chunk(k + 1))
                    if not tr.is_chain:
                        return tr
                    return tr.chain_tokens() or None
                return None
            return states[i].propose(k) or None

        while True:
            live = [i for i in range(b) if len(outs[i]) < gen_len]
            if not live:
                break
            drafts = {}
            for i in live:
                budget = gen_len - len(outs[i])
                k = cap_draft(
                    states[i].k, int(kv[i]), budget, max_length
                )
                assert k >= 0, "speculative capacity guard violated"
                d = plan_row(i, k)
                if d is not None:
                    drafts[i] = d
            for i, draft in drafts.items():
                if isinstance(draft, TreeDraft):
                    cache = verify_tree_row(i, draft, cache)
                else:
                    cache = verify_row(i, draft, cache)
            undrafted = [i for i in live if i not in drafts]
            if not undrafted:
                continue
            if all(len(o) < gen_len for o in outs):
                # Undraftable rows share ONE batched decode step (the
                # rollback left every row's device kv_len exact, so the
                # per-row appends land right even though rows are
                # desynced); just-verified rows simply advance one more
                # token. This keeps the no-match case as cheap as plain
                # serving instead of paying per-row padded chunks.
                pending = jnp.asarray(
                    [o[-1] for o in outs], jnp.int32
                )
                logits, cache = self._decode_step(pending, cache)
                toks = np.asarray(self._sample(logits))
                counters["spec_decode_steps"] += 1
                for i in range(b):
                    t = int(toks[i])
                    outs[i].append(t)
                    states[i].observe((t,))
                    kv[i] += 1
            else:
                # Some row already finished: a batched step would append
                # KV past its budgeted pages, so the stragglers step
                # through zero-draft verify chunks instead.
                for i in undrafted:
                    cache = verify_row(i, [], cache)
        counters["spec_accept_rate"] = (
            counters["spec_accepted_tokens"]
            / max(counters["spec_draft_tokens"], 1)
        )
        counters["target_steps"] = (
            counters["spec_verify_steps"] + counters["spec_decode_steps"]
        )
        counters["spec_tokens_per_step"] = (
            b * (gen_len - 1) / max(counters["target_steps"], 1)
        )
        tail = np.asarray([o[1:] for o in outs], np.int32)
        return tail, cache, counters

    # -- prefix-cache paged serving ---------------------------------------

    def _ensure_prefix_state(self, b: int, max_length: int) -> _PrefixState:
        """Pool + pool-backed cache + radix tree persisted across
        serve() calls (that persistence IS the prefix cache). Rebuilt —
        dropping all cached prefixes — when the batch geometry changes,
        or when the previous serve aborted mid-flight (its cache buffers
        were donated to device programs and its match pins never
        released; reuse would fail on deleted arrays / pinned pages)."""
        from triton_distributed_tpu.models.paged_kv_cache import (
            init_paged_cache,
        )
        from triton_distributed_tpu.models.prefix_cache import PrefixCache

        key = (b, max_length, self.page_size, self.kv_dtype)
        state = self._prefix_state
        if state is None or state.key != key or state.dirty:
            pps = max_length // self.page_size
            cache, pool = init_paged_cache(
                self.model.cfg, b, self.model.ctx, self.model.axis,
                max_length=max_length, page_size=self.page_size,
                # +1: page 0 reserved as the trash page unused table
                # entries point at (same convention as ContinuousEngine).
                num_pages=b * pps + 1, assign_pages=False,
                kv_dtype=self.kv_dtype,
            )
            pool.free = [p for p in pool.free if p != 0]
            self._prefix_state = _PrefixState(
                key, cache, pool, PrefixCache(pool, self.page_size)
            )
            self._pool = pool
        return self._prefix_state

    def _prefix_prefill(self, rows, true_lens, gen_len: int, max_length: int):
        """Admission for every batch row: longest-prefix match against
        the radix tree, map matched pages into the row's table, COW-clone
        a partially matched tail, chunk-prefill only the suffix. Returns
        ``(last-token logits [b, V], cache, row_meta)``."""
        import dataclasses

        from triton_distributed_tpu.models.paged_kv_cache import copy_page
        from triton_distributed_tpu.runtime.profiling import trace_span

        b = rows.shape[0]
        state = self._ensure_prefix_state(b, max_length)
        cache, tree = state.cache, state.tree
        state.dirty = True  # in-flight; cleared by _prefix_retire
        pps = max_length // self.page_size
        table = np.zeros((b, pps), np.int32)
        row_meta = []
        matches = []
        for i in range(b):
            prompt = rows[i][: int(true_lens[i])]
            m = tree.match(prompt)
            # Positions written: the prompt plus gen_len - 1 decode
            # appends (the final sampled token is never fed back) — the
            # same bound serve()'s capacity guard enforces, so ``total``
            # never exceeds pages_per_seq.
            total = -(
                -(int(true_lens[i]) + gen_len - 1) // self.page_size
            )
            new_pages = tree.allocate(total - len(m.nodes))
            if new_pages is None and m.cow_node is not None:
                # A COW pin holds a page WITHOUT covering any of this
                # row's budget (unlike full-shared nodes), so it alone
                # can starve the pool — drop it and retry before
                # degrading further. The dropped span was counted as a
                # hit at match time; un-count what won't be reused.
                tree.release_node(m.cow_node)
                tree.stats["hit_tokens"] -= m.cow_len
                m.cow_node, m.cow_len = None, 0
                new_pages = tree.allocate(total - len(m.nodes))
            if new_pages is None:
                # Degrade to a cold row: with nothing pinned by this
                # row, full eviction always covers ≤ pages_per_seq.
                tree.release_match(m)
                new_pages = tree.allocate(total)
            assert new_pages is not None, "prefix pool sizing violated"
            pages = m.pages + new_pages
            table[i, : len(pages)] = pages
            if m.cow_len:
                # Clone the partially matched page into this row's first
                # private page NOW and drop the pin: a COW pin held
                # across later rows' allocations would shrink their
                # evictable set below the capacity argument above.
                cache = copy_page(cache, m.cow_node.page, new_pages[0])
            matches.append(m)
            row_meta.append([pages, list(m.nodes), m.matched_len,
                             m.cow_len])
            tree.finish_cow(m)
        cache = dataclasses.replace(
            cache,
            page_table=jnp.asarray(table),
            kv_len=jnp.zeros((b,), jnp.int32),
        )

        hit_tokens = prefill_tokens = cow_pages = 0
        last_logits = []
        for i in range(b):
            m = matches[i]
            s = int(true_lens[i])
            start = m.matched_len
            cow_pages += 1 if row_meta[i][3] else 0
            hit_tokens += start
            prompt = rows[i][:s]
            with trace_span(
                "prefix_cache:admit", row=i, prompt_len=s, matched=start
            ):
                logits_i, cache, _ = prefill_suffix_chunks(
                    self.model, cache, i, prompt, start,
                    self.prefill_chunk, self._prefill_mode,
                )
            prefill_tokens += s - start
            last_logits.append(logits_i)
        self.last_stats = {}  # populated by serve(); stash counters now
        self._prefix_counters = {
            "prefix_hit_tokens": hit_tokens,
            "prefill_tokens": prefill_tokens,
            "pages_cow_copied": cow_pages,
            "prefix_hit_rate": tree.hit_rate,
            "tree_pages": tree.node_count,
        }
        return jnp.stack(last_logits), cache, row_meta

    def _prefix_retire(
        self, result, rows, true_lens, gen_len: int, cache, row_meta
    ) -> None:
        """Retire every finished row's pages into the radix tree (valid
        KV covers prompt + gen_len - 1 fed-back tokens) and persist the
        cache arrays for the next serve() call."""
        state = self._prefix_state
        tree = state.tree
        s = result.shape[1] - gen_len
        gen = result[:, s:]
        for i, (pages, nodes, _matched, _cow) in enumerate(row_meta):
            toks = np.concatenate(
                [rows[i][: int(true_lens[i])],
                 gen[i, : gen_len - 1].astype(np.int32)]
            )
            tree.retire_sequence(toks, pages, nodes)
        state.cache = cache
        state.dirty = False  # clean: safe to reuse next serve()
        self.last_stats.update(self._prefix_counters)
        self.last_stats["prefix_cache"] = dict(tree.stats)

