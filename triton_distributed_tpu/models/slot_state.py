"""Portable in-flight request state: export/import one engine slot.

PR 9 made replica death survivable, but recovery was replay-from-prompt
— a drained or SIGKILL'd replica's in-flight KV was simply lost. This
module makes a live request's full generation state a first-class,
portable object (docs/scale-out.md "Slot migration & handoff"): a
:class:`SlotSnapshot` captures everything one
:class:`~triton_distributed_tpu.models.continuous.ContinuousEngine`
slot needs to continue on a *different* engine bit-exactly —

- the gathered KV pages (bf16, or int8 codes **plus** their per-page
  ``k_scale``/``v_scale`` — codes and scale travel as a pair, so the
  dequantized values are byte-identical on the target),
- the page-table geometry (``kv_len``, page size, kv dtype),
- the prompt and every generated token so far,
- the per-request sampling knobs AND the per-request PRNG key + draw
  counter (``Request.key``/``key_step`` — seeded-sampled continuations
  replay the exact draws the un-migrated run would have made),
- the speculative accept ledger (``SpecState`` counters + adaptive K;
  the n-gram drafter rebuilds from the token history, which the
  snapshot already carries),
- the deadline budget and ``trace_id``.

**Prefix delta** (the DistServe/Splitwise-style disaggregation seed):
``export_slot(..., target_digest=...)`` scores the snapshot's cached
token chain against the *target's* radix digest
(``prefix_cache.digest_match_len``) and omits the payload of fully
covered pages — only the non-shared page suffix ships. Import then
pins exactly those pages out of the target's tree (refcounted, COW
discipline untouched); if the target evicted them in the meantime,
import raises :class:`SnapshotStaleError` and the engine falls back to
a full replay from the prompt (correct, just slower).

Everything serializes to line-JSON (:meth:`SlotSnapshot.to_wire`)
because snapshots ride the existing wire protocol: the server's
``export_slots`` verb, the ``snapshots`` key of a ``requests``
payload, and the supervisor's periodic snapshot pulls all speak it.

Fault seams (``runtime/faults.py``): ``migrate.export`` fires before
any state is read, ``migrate.import`` before any page is allocated —
a kill at either end leaves both engines' pool/radix audits clean
(export is a pure read; import tears down via the engine's crash-safe
``_admit_failure``/fallback path).
"""

from __future__ import annotations

import base64
import dataclasses
import time

import jax
import numpy as np

from triton_distributed_tpu.models.paged_kv_cache import (
    gather_pages,
    write_page,
)
from triton_distributed_tpu.runtime.faults import fault_point

SNAPSHOT_VERSION = 1


class SnapshotError(RuntimeError):
    """A snapshot cannot be imported into this engine (geometry or
    dtype mismatch, malformed payload). The admission path falls back
    to a full replay from the prompt."""


class SnapshotStaleError(SnapshotError):
    """A prefix-delta snapshot's omitted pages are no longer covered by
    the target's radix tree (evicted between export and import) — the
    payload that was never shipped cannot be reconstructed. Fallback:
    full replay (or re-export without ``target_digest``)."""


@dataclasses.dataclass
class SlotSnapshot:
    """One slot's portable generation state (see module docstring)."""

    prompt: np.ndarray            # [S] int32
    out: list[int]                # tokens generated so far (out[-1] is
    gen_len: int                  # the pending, not-yet-appended token)
    kv_len: int                   # valid KV rows: kv_len == S+len(out)-1
    page_size: int
    kv_dtype: str | None
    # Page payloads for pages [from_prefix_pages, ceil(kv_len/page)):
    # [L, n_ship, Hkv, page, hd] pools, [L, n_ship, Hkv] scales.
    k_pages: np.ndarray | None = None
    v_pages: np.ndarray | None = None
    k_scale: np.ndarray | None = None
    v_scale: np.ndarray | None = None
    # Leading fully-cached pages whose payload was omitted because the
    # target's digest already covered them (prefix delta).
    from_prefix_pages: int = 0
    # Per-request sampling state.
    temperature: float | None = None
    top_p: float | None = None
    top_k: int | None = None
    key_data: np.ndarray | None = None  # jax.random.key_data raw words
    key_step: int = 0
    # Speculative accept ledger (drafter rebuilds from prompt+out).
    spec: dict | None = None
    deadline_s: float | None = None
    trace_id: str | None = None
    exported_at: float = 0.0      # wall clock (time.time) at export
    version: int = SNAPSHOT_VERSION

    @property
    def chain(self) -> list[int]:
        """The token chain whose KV the snapshot covers: positions
        ``[0, kv_len)`` — the prompt plus every generated token already
        fed back (the pending ``out[-1]`` has no KV row yet)."""
        toks = [int(t) for t in self.prompt]
        toks += [int(t) for t in self.out[: self.kv_len - len(toks)]]
        return toks

    @property
    def valid_pages(self) -> int:
        return -(-int(self.kv_len) // int(self.page_size))

    def payload_bytes(self) -> int:
        """Bytes of page payload this snapshot ships (the quantity the
        ``tdt_migration_bytes`` histogram observes; prefix-delta
        exports ship strictly less)."""
        total = 0
        for arr in (self.k_pages, self.v_pages, self.k_scale,
                    self.v_scale):
            if arr is not None:
                total += arr.nbytes
        return total

    # -- wire codec -------------------------------------------------------

    def to_wire(self) -> dict:
        """Line-JSON-safe dict (arrays ride base64 with dtype+shape)."""
        d = {
            "version": self.version,
            "prompt": [int(t) for t in self.prompt],
            "out": [int(t) for t in self.out],
            "gen_len": int(self.gen_len),
            "kv_len": int(self.kv_len),
            "page_size": int(self.page_size),
            "kv_dtype": self.kv_dtype,
            "from_prefix_pages": int(self.from_prefix_pages),
            "temperature": self.temperature,
            "top_p": self.top_p,
            "top_k": self.top_k,
            "key_step": int(self.key_step),
            "spec": self.spec,
            "deadline_s": self.deadline_s,
            "trace_id": self.trace_id,
            "exported_at": float(self.exported_at),
        }
        for name in ("k_pages", "v_pages", "k_scale", "v_scale",
                     "key_data"):
            d[name] = _arr_to_wire(getattr(self, name))
        return d

    @classmethod
    def from_wire(cls, d: dict) -> "SlotSnapshot":
        try:
            return cls(
                prompt=np.asarray(d["prompt"], np.int32),
                out=[int(t) for t in d["out"]],
                gen_len=int(d["gen_len"]),
                kv_len=int(d["kv_len"]),
                page_size=int(d["page_size"]),
                kv_dtype=d.get("kv_dtype"),
                k_pages=_arr_from_wire(d.get("k_pages")),
                v_pages=_arr_from_wire(d.get("v_pages")),
                k_scale=_arr_from_wire(d.get("k_scale")),
                v_scale=_arr_from_wire(d.get("v_scale")),
                from_prefix_pages=int(d.get("from_prefix_pages", 0)),
                temperature=d.get("temperature"),
                top_p=d.get("top_p"),
                top_k=d.get("top_k"),
                key_data=_arr_from_wire(d.get("key_data")),
                key_step=int(d.get("key_step", 0)),
                spec=d.get("spec"),
                deadline_s=d.get("deadline_s"),
                trace_id=d.get("trace_id"),
                exported_at=float(d.get("exported_at", 0.0)),
                version=int(d.get("version", SNAPSHOT_VERSION)),
            )
        except (KeyError, TypeError, ValueError) as e:
            raise SnapshotError(
                f"malformed snapshot: {type(e).__name__}: {e}"
            ) from e


def _arr_to_wire(arr: np.ndarray | None) -> dict | None:
    if arr is None:
        return None
    return {
        "dtype": str(arr.dtype),
        "shape": list(arr.shape),
        "b64": base64.b64encode(np.ascontiguousarray(arr).tobytes())
        .decode("ascii"),
    }


def _arr_from_wire(d: dict | None) -> np.ndarray | None:
    if d is None:
        return None
    try:
        # bfloat16 resolves through ml_dtypes, which numpy picks up via
        # jax's registration of the extended dtypes.
        import ml_dtypes  # noqa: F401

        dtype = np.dtype(d["dtype"])
        raw = base64.b64decode(d["b64"])
        return np.frombuffer(raw, dtype=dtype).reshape(d["shape"]).copy()
    except (KeyError, TypeError, ValueError) as e:
        raise SnapshotError(
            f"malformed snapshot array: {type(e).__name__}: {e}"
        ) from e


# -- export ---------------------------------------------------------------


def _export_plan(engine, slot: int, target_digest):
    """The read-only half of an export that decides WHAT ships:
    ``(req, kv_len, skip, ship_ids)`` — the live request, its valid KV
    rows, the prefix-delta page count, and the pool page ids whose
    payload must travel."""
    req = engine._slots[slot]
    if req is None:
        raise SnapshotError(f"slot {slot} has no active request")
    kv_len = int(engine._kv_len[slot])
    page = int(engine.page_size)
    valid = -(-kv_len // page)
    skip = 0
    if target_digest:
        from triton_distributed_tpu.models.prefix_cache import (
            digest_match_len,
        )

        chain = [int(t) for t in req.prompt]
        chain += [int(t) for t in req.out[: kv_len - len(chain)]]
        matched = digest_match_len(target_digest, chain)
        # Only FULLY covered, fully cached pages may be omitted — the
        # import pins full tree pages, never a partial (COW) match.
        skip = min(matched // page, valid)
    ship_ids = [int(p) for p in req.pages[skip:valid]]
    return req, kv_len, skip, ship_ids


def _build_snapshot(engine, req, kv_len: int, skip: int,
                    k, v, ks, vs) -> SlotSnapshot:
    """Assemble one :class:`SlotSnapshot` from a plan plus its gathered
    page payloads (``k``/``v``/``ks``/``vs`` may be views into a larger
    batched gather — ``to_wire`` re-contiguifies)."""
    spec = None
    if req.spec is not None:
        spec = {
            "k_max": req.spec.k_max, "k_min": req.spec.k_min,
            "k": req.spec.k, "proposed": req.spec.proposed,
            "accepted": req.spec.accepted,
            # Tree-speculation width ledger (absent on pre-tree
            # snapshots; import defaults cover it).
            "width": req.spec.width, "w_max": req.spec.w_max,
        }
    key_data = None
    if req.key is not None:
        key_data = np.asarray(jax.random.key_data(req.key))
    return SlotSnapshot(
        prompt=np.asarray(req.prompt, np.int32),
        out=[int(t) for t in req.out],
        gen_len=int(req.gen_len),
        kv_len=kv_len,
        page_size=int(engine.page_size),
        kv_dtype=engine.kv_dtype,
        k_pages=k, v_pages=v, k_scale=ks, v_scale=vs,
        from_prefix_pages=skip,
        temperature=req.temperature, top_p=req.top_p, top_k=req.top_k,
        key_data=key_data, key_step=int(req.key_step),
        spec=spec,
        deadline_s=req.deadline_s,
        trace_id=req.trace_id,
        exported_at=time.time(),
    )


def export_slot(engine, slot: int, *, target_digest=None) -> SlotSnapshot:
    """Snapshot ``slot``'s live request from ``engine`` (pure read — the
    slot keeps decoding; teardown is the caller's decision). Call at a
    scheduling-round boundary on the engine's own thread: that is where
    host tables, ``out``, and the device cache agree.

    ``target_digest`` (a :meth:`PrefixCache.prefix_digest` forest from
    the intended target) turns on the prefix delta: payload for leading
    pages fully covered by the digest is omitted and
    ``from_prefix_pages`` records how many the import must instead pin
    from its own tree."""
    fault_point("migrate.export", slot=slot)
    req, kv_len, skip, ship_ids = _export_plan(engine, slot,
                                               target_digest)
    if ship_ids:
        k, v, ks, vs = gather_pages(engine.cache, ship_ids)
    else:
        k = v = ks = vs = None
    return _build_snapshot(engine, req, kv_len, skip, k, v, ks, vs)


def export_slots_batch(engine, slots, *,
                       target_digest=None) -> dict:
    """Snapshot several active slots in ONE device gather: slot →
    :class:`SlotSnapshot`, bit-identical to per-slot
    :func:`export_slot` calls (the gather is per-page, so splitting a
    concatenated gather per slot reproduces each slot's pages exactly).

    What changes is the cost shape: a drain sweep or a prefill burst
    exporting ``n`` slots pays one ``jnp.take`` launch + one
    device→host fetch over the concatenated ship lists instead of
    ``n`` serial round trips (docs/scale-out.md "Disaggregated pools &
    autoscaling"; ``perf/pools_bench.py`` measures the delta). A slot
    with no live request raises :class:`SnapshotError`, same as the
    serial path — filter first when sweeping."""
    plans = []
    out: dict = {}
    for slot in slots:
        fault_point("migrate.export", slot=slot)
        plans.append((slot, *_export_plan(engine, slot, target_digest)))
    all_ids: list[int] = []
    for _slot, _req, _kv_len, _skip, ship_ids in plans:
        all_ids.extend(ship_ids)
    if all_ids:
        k_all, v_all, ks_all, vs_all = gather_pages(engine.cache,
                                                    all_ids)
    else:
        k_all = v_all = ks_all = vs_all = None
    off = 0
    for slot, req, kv_len, skip, ship_ids in plans:
        n = len(ship_ids)
        if n:
            sl = slice(off, off + n)
            k, v = k_all[:, sl], v_all[:, sl]
            ks = None if ks_all is None else ks_all[:, sl]
            vs = None if vs_all is None else vs_all[:, sl]
        else:
            k = v = ks = vs = None
        off += n
        out[slot] = _build_snapshot(engine, req, kv_len, skip,
                                    k, v, ks, vs)
    return out


def prefix_delta(snap: SlotSnapshot, target_digest) -> SlotSnapshot:
    """Shrink ``snap`` against a target's radix digest: payload for
    leading pages the digest fully covers is dropped and
    ``from_prefix_pages`` grows to match — the import pins those pages
    from the target's own tree. Returns ``snap`` unchanged when the
    digest covers nothing new. This is the transfer-time half of the
    prefix delta (``export_slot(target_digest=...)`` is the
    export-time half): a snapshot exported in full can still ship
    thin once the target is known."""
    from triton_distributed_tpu.models.prefix_cache import (
        digest_match_len,
    )

    matched = digest_match_len(target_digest, snap.chain)
    skip = min(matched // snap.page_size, snap.valid_pages)
    if skip <= snap.from_prefix_pages:
        return snap
    drop = skip - snap.from_prefix_pages
    return dataclasses.replace(
        snap,
        from_prefix_pages=skip,
        k_pages=None if snap.k_pages is None else snap.k_pages[:, drop:],
        v_pages=None if snap.v_pages is None else snap.v_pages[:, drop:],
        k_scale=None if snap.k_scale is None else snap.k_scale[:, drop:],
        v_scale=None if snap.v_scale is None else snap.v_scale[:, drop:],
    )


# -- import ---------------------------------------------------------------


def import_slot(engine, req, snap: SlotSnapshot, slot: int) -> None:
    """Restore ``snap`` into ``slot`` of ``engine``, resuming ``req``
    mid-generation: pin prefix-delta pages from the target's tree,
    allocate the rest (gen-headroom included), write the shipped page
    payloads verbatim, and register the slot so the next scheduling
    round continues decoding exactly where the source stopped.

    Raises :class:`SnapshotError` (geometry/dtype mismatch, malformed
    payload) or :class:`SnapshotStaleError` (prefix delta no longer
    covered); the engine's admission path catches these and falls back
    to a full replay from the prompt. On ANY failure after allocation,
    ``req.slot``/``req.pages``/``req.shared_nodes`` are already set, so
    the standard crash-safe teardown releases everything."""
    fault_point("migrate.import", slot=slot)
    if int(snap.page_size) != int(engine.page_size):
        raise SnapshotError(
            f"page_size mismatch: snapshot {snap.page_size}, "
            f"engine {engine.page_size}"
        )
    if snap.kv_dtype != engine.kv_dtype:
        raise SnapshotError(
            f"kv_dtype mismatch: snapshot {snap.kv_dtype!r}, "
            f"engine {engine.kv_dtype!r}"
        )
    s = len(snap.prompt)
    if not snap.out or snap.kv_len != s + len(snap.out) - 1:
        raise SnapshotError(
            f"inconsistent snapshot: kv_len={snap.kv_len}, "
            f"prompt={s}, out={len(snap.out)}"
        )
    if len(snap.out) >= int(snap.gen_len):
        raise SnapshotError("snapshot is already complete")
    page = int(engine.page_size)
    valid = snap.valid_pages
    skip = int(snap.from_prefix_pages)
    n_ship = valid - skip
    for arr in (snap.k_pages, snap.v_pages):
        got = 0 if arr is None else int(arr.shape[1])
        if got != n_ship:
            raise SnapshotError(
                f"snapshot ships {got} pages; geometry needs {n_ship}"
            )
    total = engine._needed_pages(s, int(snap.gen_len))

    # Prefix-delta pages come from the TARGET's own tree, pinned with
    # the exact discipline _admit_prefix uses (full pages only).
    shared_nodes: list = []
    m = None
    if skip:
        if engine.prefix is None:
            raise SnapshotStaleError(
                "snapshot omits prefix pages but the engine has no "
                "prefix cache"
            )
        # match() caps at len(tokens)-1 (admission must keep one
        # suffix token to prefill); an import restores the WHOLE chain,
        # so a sentinel lifts the cap — it can never match a cached
        # chunk (token ids are non-negative).
        m = engine.prefix.match(snap.chain + [-1])
        if len(m.nodes) < skip:
            engine.prefix.release_match(m)
            raise SnapshotStaleError(
                f"target tree covers {len(m.nodes)} pages; snapshot "
                f"omitted {skip}"
            )
        shared_nodes = m.nodes[:skip]
        # Pins beyond what the delta needs (and any COW pin) go back —
        # the shipped payload is the source of truth for those pages.
        for node in m.nodes[skip:]:
            engine.prefix.release_node(node)
        if m.cow_node is not None:
            engine.prefix.release_node(m.cow_node)
            m.cow_node = None
        m.nodes = []
    try:
        n_new = total - skip
        if engine.prefix is not None:
            new_pages = engine.prefix.allocate(n_new)
            if new_pages is None:
                raise SnapshotError(
                    f"pool cannot cover {n_new} pages for import"
                )
        else:
            new_pages = engine.pool.allocate(n_new)
    except Exception:
        for node in shared_nodes:
            engine.prefix.release_node(node)
        raise
    # From here on the request owns its state: any failure unwinds
    # through the engine's standard slot teardown (pages + pins).
    req.slot = slot
    req.pages = [n.page for n in shared_nodes] + new_pages
    req.shared_nodes = shared_nodes
    for j in range(n_ship):
        engine.cache = write_page(
            engine.cache, req.pages[skip + j],
            snap.k_pages[:, j], snap.v_pages[:, j],
            None if snap.k_scale is None else snap.k_scale[:, j],
            None if snap.v_scale is None else snap.v_scale[:, j],
        )
    engine._table[slot] = 0
    engine._table[slot, : len(req.pages)] = req.pages
    engine._kv_len[slot] = int(snap.kv_len)
    req.out = [int(t) for t in snap.out]
    engine._tok[slot] = req.out[-1]
    if snap.key_data is not None:
        req.key = jax.random.wrap_key_data(
            jax.numpy.asarray(snap.key_data)
        )
    req.key_step = int(snap.key_step)
    if snap.trace_id and req.trace_id is None:
        req.trace_id = snap.trace_id
    if engine.speculative:
        from triton_distributed_tpu.models.speculative import SpecState

        if hasattr(engine, "_new_spec_state"):
            st = engine._new_spec_state()
        else:
            st = SpecState(engine.speculative)
        sp = snap.spec or {}
        st.k = int(sp.get("k", st.k))
        st.proposed = int(sp.get("proposed", 0))
        st.accepted = int(sp.get("accepted", 0))
        # Width rides the snapshot (pre-tree snapshots omit it);
        # clamped to THIS engine's ceiling — a full-width exporter's
        # wide ledger must not make a quantized importer draft trees.
        st.width = max(min(int(sp.get("width", st.width)), st.w_max), 1)
        st.observe(req.prompt)
        st.observe(req.out)
        req.spec = st
    engine._slots[slot] = req
