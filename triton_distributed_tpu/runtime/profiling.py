"""Profiling: per-process trace capture with a merged one-file timeline.

Reference parity: ``group_profile`` (``python/triton_dist/utils.py:505-589``)
wraps ``torch.profiler``, exports one chrome trace per rank, gathers them
to rank 0, remaps pids per rank and merges + gzips into a SINGLE
timeline. The TPU-native analog wraps ``jax.profiler`` (XPlane +
chrome-trace export): each process traces into ``<dir>/<name>/rank<i>``,
then rank 0 merges every rank's chrome trace into
``<dir>/<name>/merged.trace.json.gz`` — one file, one timeline, pids
namespaced per rank exactly like the reference's ``merge_json_files``
(``utils.py:370-502``).
"""

from __future__ import annotations

import contextlib
import glob
import gzip
import json
import os
import time

import jax

from triton_distributed_tpu.obs import events as obs_events

# Rank pid namespace stride: chrome-trace pids from one process stay
# below this, so ``rank * _PID_STRIDE + pid`` never collides across
# ranks (the reference remaps pids the same way, ``utils.py:430-470``).
_PID_STRIDE = 10_000_000

@contextlib.contextmanager
def trace_span(name: str, **args):
    """Named host-side span on the jax.profiler timeline AND the
    telemetry event ring: the one way this program makes a span.

    The span is a ``jax.profiler.TraceAnnotation``, so inside any
    profiler session it lands in the host plane of the same xplane
    file as the device lines (one clock, nothing to align); with no
    session open it costs its construction and nothing is kept. Ints,
    floats and strings ride as typed event stats; anything else is
    stringified. A profiler API mismatch must never sink serving, so
    an entry failure degrades to a plain yield (body exceptions still
    propagate).

    On exit the span also lands in the event ring (kind ``span``, with
    the span's wall duration and its args, numerics kept native), so
    host spans are visible through ``{"cmd": "events"}`` without an
    active profiler capture (docs/observability.md). ``_ring=False``
    skips that entry: for a site whose moment already has a dedicated,
    richer ring event (``spec_verify``), and for per-step and
    per-chunk spans, which at tens a second would wash the bounded
    ring clean of the rare events it is for."""
    ring_emit = args.pop("_ring", True)
    try:
        span = jax.profiler.TraceAnnotation(name, **{
            k: v if isinstance(v, (int, float, str)) else str(v)
            for k, v in args.items()
        })
        span.__enter__()
    except Exception:  # noqa: BLE001 - telemetry never sinks the body
        span = None
    # Honor the disabled-mode contract (attribute check + return):
    # skip the clock reads and the kwargs coercion entirely when the
    # ring won't record the event anyway.
    ring = obs_events.default_ring()
    t0 = time.monotonic() if (ring_emit and ring.enabled) else None
    try:
        yield
    finally:
        if span is not None:
            try:
                span.__exit__(None, None, None)
            except Exception:
                pass
        if t0 is not None:
            try:
                # Arg keys colliding with the event's own fields
                # survive under a ctx_ prefix (the shared
                # collision-escape rule, obs.events.safe_fields).
                fields = obs_events.safe_fields(
                    args, reserved=("name", "dur_s")
                )
                ring.emit("span", name=name,
                          dur_s=time.monotonic() - t0, **fields)
            except Exception:
                # Telemetry must never sink the span's body.
                pass


def _load_chrome_trace(path: str) -> dict:
    op = gzip.open if path.endswith(".gz") else open
    with op(path, "rt") as f:
        return json.load(f)


def _newest_session_trace(rank_dir: str) -> tuple[str, str] | None:
    """The newest-by-MTIME profiler session under a rank dir →
    ``(session_name, trace_path)``. jax.profiler lays out
    ``<rank_dir>/plugins/profile/<session>/<host>.trace.json.gz``; a
    lexicographic sort of session names picked whichever string
    compared last, so a stale session surviving from a prior run under
    the same profile name could silently win (ADVICE r4). Sessions
    with no exported trace (a failed export) are skipped rather than
    masking an older complete one."""
    root = os.path.join(rank_dir, "plugins", "profile")
    sessions = [s for s in glob.glob(os.path.join(root, "*"))
                if os.path.isdir(s)]
    for s in sorted(sessions, key=os.path.getmtime, reverse=True):
        traces = sorted(glob.glob(os.path.join(s, "*.trace.json.gz")))
        if traces:
            return os.path.basename(s), traces[-1]
    flat = sorted(glob.glob(os.path.join(rank_dir, "*.trace.json.gz")),
                  key=os.path.getmtime)
    if flat:
        # Sentinel session name: a rank resolved via the flat fallback
        # must still participate in the mixed-sessions check — mixing
        # one rank's session-dir trace with another's flat-layout trace
        # is exactly the capture skew the warning exists for (ADVICE r5).
        return "<flat>", flat[-1]
    return None


def merge_group_profile(name: str, out_dir: str = "prof") -> str | None:
    """Merge every rank's chrome trace under ``<out_dir>/<name>`` into
    ONE gzipped timeline, ``<out_dir>/<name>/merged.trace.json.gz``.

    Each rank's events keep their relative pid/tid structure but move
    into a per-rank pid namespace, and every process-name metadata row
    is prefixed ``rank<i>:`` so the merged view in Perfetto/chrome
    reads like the reference's merged ``group_profile`` output. Returns
    the merged path, or None when no rank traces exist (e.g. profiling
    was off).

    Each rank's newest session is picked by MTIME; when ranks resolve
    to DIFFERENT session names (one rank's export failed and an older
    session won, or stale dirs persist under a reused profile name) a
    warning is emitted — the merge still proceeds (partial evidence
    beats none) but the timeline may mix capture sessions (ADVICE r4).
    """
    root = os.path.join(out_dir, name)
    rank_dirs = sorted(
        d for d in glob.glob(os.path.join(root, "rank*"))
        if os.path.isdir(d)
    )
    merged: list = []
    meta: dict = {}
    found = False
    sessions_used: dict[int, str] = {}
    for d in rank_dirs:
        try:
            rank = int(os.path.basename(d).removeprefix("rank"))
        except ValueError:
            continue
        picked = _newest_session_trace(d)
        if picked is None:
            continue
        session, trace_path = picked
        sessions_used[rank] = session
        found = True
        data = _load_chrome_trace(trace_path)
        base = rank * _PID_STRIDE
        for ev in data.get("traceEvents", []):
            ev = dict(ev)
            if isinstance(ev.get("pid"), int):
                ev["pid"] = base + ev["pid"]
            if (ev.get("ph") == "M" and ev.get("name") == "process_name"
                    and isinstance(ev.get("args"), dict)):
                ev["args"] = dict(ev["args"])
                ev["args"]["name"] = (
                    f"rank{rank}: {ev['args'].get('name', '')}"
                )
            merged.append(ev)
        for k, v in data.items():
            if k != "traceEvents":
                meta.setdefault(k, v)
    if not found:
        return None
    if len(set(sessions_used.values())) > 1:
        import warnings

        warnings.warn(
            "merge_group_profile: ranks resolved different capture "
            f"sessions {sessions_used} — the merged timeline may mix "
            "sessions (a rank's export failed, or stale session dirs "
            "persist under this profile name)",
            stacklevel=2,
        )
    out_path = os.path.join(root, "merged.trace.json.gz")
    with gzip.open(out_path, "wt") as f:
        json.dump({**meta, "traceEvents": merged}, f)
    return out_path


@contextlib.contextmanager
def group_profile(
    name: str | None = None,
    do_prof: bool = True,
    out_dir: str = "prof",
    merge: bool = True,
):
    """Context manager capturing a jax.profiler trace for all processes,
    merged to one timeline on exit.

    Usage parity with the reference (``test_ag_gemm.py:109``):

        with group_profile("ag_gemm", do_prof=args.profile):
            run_the_kernel()

    On exit, process 0 merges every rank's chrome trace it can see into
    ``<out_dir>/<name>/merged.trace.json.gz`` (ranks write to a shared
    filesystem in the torchrun-style launches this mirrors; without one,
    gather the ``rank*`` dirs and call :func:`merge_group_profile`
    post-hoc)."""
    if not do_prof or name is None:
        yield
        return
    path = os.path.join(out_dir, name, f"rank{jax.process_index()}")
    os.makedirs(path, exist_ok=True)
    jax.profiler.start_trace(path)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
        if merge:
            try:
                if jax.process_count() > 1:
                    # EVERY process joins the sync (it is a collective —
                    # rank-0-only would deadlock); it fences the other
                    # ranks' trace export before rank 0 reads their
                    # files (the reference gathers over the process
                    # group at the same point).
                    from jax.experimental import multihost_utils

                    multihost_utils.sync_global_devices(
                        f"group_profile:{name}"
                    )
                if jax.process_index() == 0:
                    merge_group_profile(name, out_dir)
            except Exception:
                # A failed merge must never sink the profiled run; the
                # per-rank traces are still on disk.
                pass
