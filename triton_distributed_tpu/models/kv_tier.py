"""Durable KV tier: host-RAM/disk page store behind the radix tree.

At production scale the shared-prefix population (system prompts,
few-shot templates, long documents) is far bigger than any HBM pool;
until this tier existed, ``PrefixCache.evict_until`` dropped cold pages
to nothing (evicted prefixes silently re-prefilled) and the crash
recovery state of PRs 9–10 (``ContinuousEngine._snapshots``,
``FleetSupervisor._snaps``) lived only in process memory — a supervisor
restart forfeited every in-flight request's snapshot. This module is
the capacity-bounded store both problems spill into
(docs/serving.md "Tiered KV", docs/scale-out.md "Durable snapshots"):

- **Host-RAM tier**: an LRU of encoded entries bounded by
  ``capacity_bytes``. Entries are stored as their WIRE bytes (header +
  checksummed body), so RAM corruption is as detectable as disk
  corruption and the fault seams mutate one representation.
- **Optional disk tier** (``dir=``): write-through, one file per entry,
  atomic write-then-rename (a crash mid-write can only leave a ``.tmp``
  sibling, never a half entry). Entries evicted from the RAM LRU stay
  readable from disk; a fresh process over the same ``dir`` sees every
  durable entry (the supervisor-restart path).
- **Two entry kinds**: ``prefix`` pages keyed by token-chain digest
  (:func:`chain_digest`) carrying one radix page's KV payload
  (``gather_pages``/``write_page`` byte-exact, int8 codes + per-page
  scales as a pair), and ``snap`` slot snapshots keyed by ticket id
  carrying the ``models/slot_state.py`` wire dict.
- **Integrity-checked fault-back**: every entry rides a version header
  + CRC32 over the body. A checksum mismatch, truncated file, wrong
  magic, or key mismatch NEVER yields wrong bits — the entry is
  dropped (counter + ``tier_drop`` event) and :meth:`PageStore.get`
  returns None, so the caller degrades to re-prefill / replay.
- **Fault seams** (``runtime/faults.py``): ``tier.put`` / ``tier.get``
  can refuse (raise-style), corrupt (mutate-style — caught by the
  checksum), or slow (delay rule). Both are containment boundaries:
  an injected failure degrades the tier, never the request.

Everything here is host-side and zero-jax: payload arrays ride the
``models/slot_state.py`` base64 wire codec, so tier entries are the
same line-JSON-safe dicts the migration wire already speaks.
"""

from __future__ import annotations

import base64
import hashlib
import json
import os
import socket
import threading
import time
import zlib
from collections import OrderedDict

import numpy as np

from triton_distributed_tpu.obs import events as obs_events
from triton_distributed_tpu.obs import metrics as obs_metrics
from triton_distributed_tpu.runtime.faults import mutate_point

TIER_VERSION = 1
_MAGIC = b"TDT1"

PREFIX_KIND = "prefix"
SNAP_KIND = "snap"


class TierIntegrityError(RuntimeError):
    """An entry's bytes failed the header/checksum validation — the
    payload cannot be trusted and must be dropped, never decoded into
    KV bits."""


def chain_digest(tokens) -> str:
    """Stable digest of an exact token chain — the ``prefix`` entry
    key. One digest per chain: a spilled radix page is keyed by the
    FULL chain from the root through its own chunk, so fault-back can
    probe page-by-page while walking a new prompt."""
    return hashlib.sha1(
        np.asarray([int(t) for t in tokens], np.int64).tobytes()
    ).hexdigest()


def request_digest(prompt, gen_len: int) -> str:
    """Digest identifying a request's (prompt, gen_len) — how the
    supervisor's restart-resume store matches a re-submitted request to
    a crash-leftover snapshot (ticket ids do not survive a restart)."""
    h = hashlib.sha1()
    h.update(f"g{int(gen_len)}:".encode())
    h.update(np.asarray([int(t) for t in prompt], np.int64).tobytes())
    return h.hexdigest()


# -- prefix-page payload codec --------------------------------------------
#
# One radix page's content as a line-JSON-safe dict, arrays riding the
# slot_state base64 codec (the SAME codec migration snapshots use — one
# array wire format in the repo). ``chain`` is the page's full token
# chain (a page_size multiple; the page holds chain[-page_size:]), kept
# IN the payload so fault-back can verify the digest didn't collide and
# the audit can cross-check key ↔ chain consistency.


def prefix_payload(chain, page_size: int, kv_dtype: str | None,
                   k_page, v_page, k_scale=None, v_scale=None) -> dict:
    from triton_distributed_tpu.models.slot_state import _arr_to_wire

    return {
        "chain": [int(t) for t in chain],
        "page_size": int(page_size),
        "kv_dtype": kv_dtype,
        "k": _arr_to_wire(np.asarray(k_page)),
        "v": _arr_to_wire(np.asarray(v_page)),
        "ks": None if k_scale is None else _arr_to_wire(np.asarray(k_scale)),
        "vs": None if v_scale is None else _arr_to_wire(np.asarray(v_scale)),
    }


def decode_prefix_payload(payload: dict):
    """``(chain, page_size, kv_dtype, k, v, ks, vs)`` from a ``prefix``
    entry; raises :class:`TierIntegrityError` on any malformed field
    (the caller drops the entry and re-prefills)."""
    from triton_distributed_tpu.models.slot_state import (
        SnapshotError,
        _arr_from_wire,
    )

    try:
        chain = [int(t) for t in payload["chain"]]
        page_size = int(payload["page_size"])
        kv_dtype = payload.get("kv_dtype")
        k = _arr_from_wire(payload["k"])
        v = _arr_from_wire(payload["v"])
        ks = _arr_from_wire(payload.get("ks"))
        vs = _arr_from_wire(payload.get("vs"))
    except (KeyError, TypeError, ValueError, SnapshotError) as e:
        raise TierIntegrityError(
            f"malformed prefix payload: {type(e).__name__}: {e}"
        ) from e
    if k is None or v is None:
        raise TierIntegrityError("prefix payload missing page arrays")
    return chain, page_size, kv_dtype, k, v, ks, vs


def payload_nbytes(payload: dict) -> int:
    """Approximate payload size (the base64 blobs dominate) — what the
    engine's ``tier_bytes`` counter accumulates per fault-back."""
    total = 0
    for v in payload.values():
        if isinstance(v, dict) and "b64" in v:
            total += len(v["b64"])
    return total


# -- entry wire format ----------------------------------------------------


def _encode(kind: str, key: str, payload: dict) -> bytes:
    body = json.dumps(payload, separators=(",", ":")).encode()
    head = json.dumps({
        "v": TIER_VERSION, "kind": kind, "key": key,
        "len": len(body), "crc": zlib.crc32(body),
    }, separators=(",", ":")).encode()
    return _MAGIC + head + b"\n" + body


def _decode(kind: str, key: str, blob: bytes) -> dict:
    """Validate + decode one entry blob; raises
    :class:`TierIntegrityError` on wrong magic, unparseable or
    mismatched header, truncation, or a CRC mismatch."""
    if not blob.startswith(_MAGIC):
        raise TierIntegrityError("bad magic (not a tier entry)")
    head_raw, sep, body = blob[len(_MAGIC):].partition(b"\n")
    if not sep:
        raise TierIntegrityError("truncated entry (no header terminator)")
    try:
        head = json.loads(head_raw)
    except ValueError as e:
        raise TierIntegrityError(f"unparseable header: {e}") from e
    if head.get("v") != TIER_VERSION:
        raise TierIntegrityError(f"version mismatch: {head.get('v')!r}")
    if head.get("kind") != kind or head.get("key") != key:
        raise TierIntegrityError(
            f"entry is ({head.get('kind')!r}, {head.get('key')!r}), "
            f"expected ({kind!r}, {key!r})"
        )
    if len(body) != head.get("len"):
        raise TierIntegrityError(
            f"truncated body: {len(body)} != {head.get('len')}"
        )
    if zlib.crc32(body) != head.get("crc"):
        raise TierIntegrityError("checksum mismatch")
    try:
        return json.loads(body)
    except ValueError as e:  # crc passed but json broke: still contained
        raise TierIntegrityError(f"unparseable body: {e}") from e


class PageStore:
    """Capacity-bounded host-RAM tier with an optional write-through
    disk tier (see module docstring). Thread-safe: the engine's
    admission path, the supervisor's monitor thread, and router worker
    threads all touch one store."""

    def __init__(self, capacity_bytes: int = 64 << 20,
                 dir: str | None = None,  # noqa: A002 — the public knob name
                 disk_capacity_bytes: int | None = None,
                 fsync: bool = True):
        self.capacity_bytes = int(capacity_bytes)
        self.dir = dir
        # fsync=False trades power-loss durability for write latency:
        # the atomic rename still makes every entry visible whole to a
        # RESTARTED process (page cache survives a process crash), and
        # an OS crash can only tear an entry the CRC then drops —
        # degrade to re-prefill/replay, never wrong bits. The engine's
        # snapshot write-through runs on the scheduling loop and picks
        # this; the supervisor's resume store keeps the default.
        self.fsync = bool(fsync)
        self.disk_capacity_bytes = (
            None if disk_capacity_bytes is None else int(disk_capacity_bytes)
        )
        if dir:
            os.makedirs(dir, exist_ok=True)
        self._ram: "OrderedDict[tuple[str, str], bytes]" = OrderedDict()
        self._ram_bytes = 0
        self._lock = threading.RLock()
        # Memo for :meth:`resident_chains`: (mutation counter, chains).
        # Every RAM-membership change bumps ``_mut``, invalidating it —
        # the tree-speculation drafter calls resident_chains once per
        # verify round, and re-decoding every header each time would
        # put a JSON parse loop on the decode path.
        self._mut = 0
        self._chain_memo: tuple[int, list[list[int]]] | None = None
        self._digest_memo: tuple[int, dict] | None = None
        # Monotone per-kind non-emptiness flags (see :meth:`may_contain`):
        # one listdir at construction counts entries a PRIOR process
        # left on disk; every successful put flips the flag for good.
        self._kind_seen: dict[str, bool] = {
            PREFIX_KIND: False, SNAP_KIND: False,
        }
        if dir:
            for kd in (PREFIX_KIND, SNAP_KIND):
                try:
                    self._kind_seen[kd] = any(
                        n.endswith(".tier")
                        for n in os.listdir(os.path.join(dir, kd))
                    )
                except OSError:
                    pass
        self.stats = {
            "puts": 0,
            "put_bytes": 0,
            "hits": 0,
            "disk_hits": 0,
            "misses": 0,
            "evictions": 0,       # RAM LRU evictions (disk copy survives)
            "disk_evictions": 0,  # disk-bound prunes — permanent deletions
            "drops": 0,       # integrity failures — entry removed
            "refused": 0,     # puts refused (fault seam / oversized)
            "errors": 0,      # I/O or injected get errors (degraded)
        }
        # Resolved ONCE (the engine `_metric_handles` convention).
        self._m_drops = obs_metrics.counter(
            "tdt_tier_drops_total",
            "Tier entries dropped on integrity failure (checksum / "
            "truncation / header mismatch) — degraded to re-prefill "
            "or replay, never wrong bits.",
        )
        self._m_evictions = obs_metrics.counter(
            "tdt_tier_store_evictions_total",
            "Entries LRU-evicted from the tier's RAM capacity (the "
            "disk copy, when a disk tier is attached, survives).",
        )
        self._m_disk_evictions = obs_metrics.counter(
            "tdt_tier_disk_evictions_total",
            "Entries pruned from the disk tier's byte bound — "
            "PERMANENT deletions, unlike RAM evictions.",
        )
        # Last-write-wins and UNLABELED like tdt_engine_free_pages
        # (one store per serving process is the deployment shape).
        self._g_bytes = obs_metrics.gauge(
            "tdt_tier_ram_bytes", "Bytes held by the tier's RAM LRU.",
        )

    # -- paths -------------------------------------------------------------

    def _path(self, kind: str, key: str) -> str:
        # Filenames are key digests (keys may hold '/'); the header's
        # embedded key is what guards against digest collisions.
        name = hashlib.sha1(key.encode()).hexdigest() + ".tier"
        return os.path.join(self.dir, kind, name)

    # -- write -------------------------------------------------------------

    def put(self, kind: str, key: str, payload: dict) -> bool:
        """Store one entry; returns False when refused (injected fault,
        payload larger than the whole RAM capacity) — the caller treats
        a refused spill exactly like the pre-tier drop-to-nothing."""
        try:
            blob = _encode(kind, key, payload)
            blob = mutate_point("tier.put", blob, kind=kind, key=key)
        except Exception:  # noqa: BLE001 — containment boundary
            with self._lock:
                self.stats["refused"] += 1
            return False
        if len(blob) > self.capacity_bytes:
            with self._lock:
                self.stats["refused"] += 1
            return False
        with self._lock:
            self._ram_insert(kind, key, blob)
            self.stats["puts"] += 1
            self.stats["put_bytes"] += len(blob)
            self._kind_seen[kind] = True
        if self.dir:
            self._disk_write(kind, key, blob)
        return True

    def _ram_insert(self, kind: str, key: str, blob: bytes) -> None:
        """Insert into the RAM LRU and evict down to capacity. Caller
        holds ``_lock``. Pops any entry a concurrent promote/put landed
        first — blind insertion would double-count its bytes in the
        ledger (the store may be SHARED across replicas)."""
        old = self._ram.pop((kind, key), None)
        if old is not None:
            self._ram_bytes -= len(old)
        self._ram[(kind, key)] = blob
        self._ram_bytes += len(blob)
        self._mut += 1
        while self._ram_bytes > self.capacity_bytes and len(self._ram) > 1:
            _, evicted = self._ram.popitem(last=False)
            self._ram_bytes -= len(evicted)
            self._mut += 1
            self.stats["evictions"] += 1
            self._m_evictions.inc()
        self._g_bytes.set(self._ram_bytes)

    def _disk_write(self, kind: str, key: str, blob: bytes) -> None:
        path = self._path(kind, key)
        tmp = f"{path}.tmp.{os.getpid()}.{threading.get_ident()}"
        try:
            os.makedirs(os.path.dirname(path), exist_ok=True)
            with open(tmp, "wb") as f:
                f.write(blob)
                f.flush()
                if self.fsync:
                    os.fsync(f.fileno())
            os.replace(tmp, path)  # atomic: readers see old or new, never half
        except OSError:
            with self._lock:
                self.stats["errors"] += 1
            try:
                os.unlink(tmp)
            except OSError:
                pass
            return
        if self.disk_capacity_bytes is not None:
            self._disk_prune()

    def _disk_prune(self) -> None:
        """LRU-by-mtime prune of the disk tier to its byte bound."""
        entries = []
        total = 0
        for kind in (PREFIX_KIND, SNAP_KIND):
            d = os.path.join(self.dir, kind)
            if not os.path.isdir(d):
                continue
            for name in os.listdir(d):
                if not name.endswith(".tier"):
                    continue
                p = os.path.join(d, name)
                try:
                    st = os.stat(p)
                except OSError:
                    continue
                entries.append((st.st_mtime, st.st_size, p))
                total += st.st_size
        entries.sort()
        for _, size, p in entries:
            if total <= self.disk_capacity_bytes:
                break
            try:
                os.unlink(p)
                total -= size
                with self._lock:
                    self.stats["disk_evictions"] += 1
                self._m_disk_evictions.inc()
            except OSError:
                pass

    # -- read --------------------------------------------------------------

    def get(self, kind: str, key: str) -> dict | None:
        """Fetch + integrity-check one entry. None on miss, on an
        injected/real read error, or on ANY integrity failure (the
        entry is then dropped everywhere and counted) — wrong bits can
        never come out of this method."""
        src = "ram"
        with self._lock:
            blob = self._ram.get((kind, key))
            if blob is not None:
                self._ram.move_to_end((kind, key))
        if blob is None and self.dir:
            src = "disk"
            try:
                with open(self._path(kind, key), "rb") as f:
                    blob = f.read()
            except FileNotFoundError:
                blob = None
            except OSError:
                with self._lock:
                    self.stats["errors"] += 1
                return None
        if blob is None:
            with self._lock:
                self.stats["misses"] += 1
            return None
        try:
            blob = mutate_point("tier.get", blob, kind=kind, key=key)
        except Exception:  # noqa: BLE001 — injected refusal: the entry
            with self._lock:  # itself is fine, degrade as a transient miss
                self.stats["errors"] += 1
            return None
        try:
            payload = _decode(kind, key, blob)
        except Exception as e:  # noqa: BLE001 — containment boundary
            self._drop(kind, key, f"{type(e).__name__}: {e}")
            return None
        with self._lock:
            self.stats["hits"] += 1
            if src == "disk":
                self.stats["disk_hits"] += 1
                # Promote: the RAM front absorbs the next lookup.
                self._ram_insert(kind, key, blob)
        return payload

    def peek(self, kind: str, key: str) -> dict | None:
        """Decode an entry WITHOUT stats, LRU movement, fault seams, or
        drop-on-failure — the audit's read path. None when absent or
        unreadable."""
        with self._lock:
            blob = self._ram.get((kind, key))
        if blob is None and self.dir:
            try:
                with open(self._path(kind, key), "rb") as f:
                    blob = f.read()
            except OSError:
                return None
        if blob is None:
            return None
        try:
            return _decode(kind, key, blob)
        except TierIntegrityError:
            return None

    def contains(self, kind: str, key: str) -> bool:
        """Membership WITHOUT decode, stats, or LRU movement — the
        fabric's ``tier_probe`` answer. A True here is advisory (the
        entry may still fail its checksum at pull time); a False is
        authoritative for this instant."""
        with self._lock:
            if (kind, key) in self._ram:
                return True
        if self.dir:
            return os.path.exists(self._path(kind, key))
        return False

    def get_blob(self, kind: str, key: str) -> bytes | None:
        """One entry's raw WIRE bytes (header + checksummed body),
        verbatim — the fabric's ``tier_get`` serve side. No decode, no
        stats, no LRU movement, no fault seams: validation is the
        PULLER's job (:func:`_decode` at the far end), so the PR 12
        codec is the transport and a garbled blob CRC-drops there
        exactly like a corrupt local entry."""
        with self._lock:
            blob = self._ram.get((kind, key))
            if blob is not None:
                return blob
        if self.dir:
            try:
                with open(self._path(kind, key), "rb") as f:
                    return f.read()
            except OSError:
                return None
        return None

    def _drop(self, kind: str, key: str, reason: str) -> None:
        """Remove a failed entry from BOTH tiers: the bits are suspect
        wherever they live, and leaving them would re-fail every later
        lookup of a key the caller now believes absent."""
        with self._lock:
            blob = self._ram.pop((kind, key), None)
            if blob is not None:
                self._ram_bytes -= len(blob)
                self._mut += 1
                self._g_bytes.set(self._ram_bytes)
            self.stats["drops"] += 1
        self._m_drops.inc()
        if self.dir:
            try:
                os.unlink(self._path(kind, key))
            except OSError:
                pass
        obs_events.emit(
            "tier_drop", tier_kind=kind, key=str(key)[:64],
            reason=str(reason)[:160],
        )

    # -- management --------------------------------------------------------

    def delete(self, kind: str, key: str) -> None:
        with self._lock:
            blob = self._ram.pop((kind, key), None)
            if blob is not None:
                self._ram_bytes -= len(blob)
                self._mut += 1
                self._g_bytes.set(self._ram_bytes)
        if self.dir:
            try:
                os.unlink(self._path(kind, key))
            except OSError:
                pass

    def clear(self, kind: str | None = None) -> int:
        """Drop every entry (of ``kind``, or all) from both tiers — the
        supervisor's clean-shutdown path (a drained fleet has no
        in-flight snapshots worth resuming). Returns entries removed."""
        removed = 0
        with self._lock:
            for k in [k for k in self._ram if kind is None or k[0] == kind]:
                self._ram_bytes -= len(self._ram.pop(k))
                removed += 1
            if removed:
                self._mut += 1
            self._g_bytes.set(self._ram_bytes)
        if self.dir:
            for kd in (PREFIX_KIND, SNAP_KIND):
                if kind is not None and kd != kind:
                    continue
                d = os.path.join(self.dir, kd)
                if not os.path.isdir(d):
                    continue
                for name in os.listdir(d):
                    if name.endswith(".tier"):
                        try:
                            os.unlink(os.path.join(d, name))
                            removed += 1
                        except OSError:
                            pass
        return removed

    def may_contain(self, kind: str) -> bool:
        """Cheap monotone emptiness guard: False only while the store
        has NEVER held an entry of ``kind`` — neither this process nor
        (with a disk tier) a prior one over the same dir. Hot paths use
        it to skip per-request digest hashing against a provably-empty
        tier (the admission loop's ``_tier_fill``). Deletes never reset
        it: conservative — may over-probe, never under-probes."""
        return self._kind_seen.get(kind, True)

    def keys(self, kind: str) -> list[str]:
        """Every live key of ``kind`` (RAM ∪ disk). Disk filenames are
        key digests, so the key is read from each entry's header —
        unreadable files are skipped (a later ``get`` would drop them)."""
        out = {k for (kd, k) in self._ram if kd == kind}
        if self.dir:
            d = os.path.join(self.dir, kind)
            if os.path.isdir(d):
                for name in os.listdir(d):
                    if not name.endswith(".tier"):
                        continue
                    try:
                        with open(os.path.join(d, name), "rb") as f:
                            blob = f.read()
                        head_raw, sep, _ = blob[len(_MAGIC):].partition(b"\n")
                        if not blob.startswith(_MAGIC) or not sep:
                            continue
                        key = json.loads(head_raw).get("key")
                        if isinstance(key, str):
                            out.add(key)
                    except (OSError, ValueError):
                        continue
        return sorted(out)

    def resident_chains(self) -> list[list[int]]:
        """Token chains of the RAM-resident ``prefix`` entries — the
        population the tree-speculation drafter scans for continuations
        of a slot's history whose KV was evicted from the radix tree
        but survives in this tier (``PrefixCache.propose_continuations``
        ``tier_chains=``). Header-only decode: the payload arrays stay
        base64; only the chain list is parsed. Memoized until the RAM
        membership mutates, no stats / LRU movement / fault seams (a
        draft read must never perturb the tier), disk-only entries are
        deliberately out of scope (scanning a directory per verify
        round is not a decode-path cost)."""
        with self._lock:
            memo = self._chain_memo
            if memo is not None and memo[0] == self._mut:
                return memo[1]
            mut = self._mut
            blobs = [
                blob for (kd, _), blob in self._ram.items()
                if kd == PREFIX_KIND
            ]
        chains: list[list[int]] = []
        for blob in blobs:
            try:
                _, sep, body = blob[len(_MAGIC):].partition(b"\n")
                if not blob.startswith(_MAGIC) or not sep:
                    continue
                chain = json.loads(body).get("chain")
            except ValueError:
                continue  # a later get() integrity-drops it
            if isinstance(chain, list) and chain:
                chains.append([int(t) for t in chain])
        with self._lock:
            if self._mut == mut:
                self._chain_memo = (mut, chains)
        return chains

    def digest(self) -> dict:
        """Compact content summary for fleet placement and peer
        fault-back: ``{"hash", "counts", "chains"}`` where ``chains``
        is the sorted 16-hex truncations of the RAM-resident ``prefix``
        chain digests (the keys ARE chain digests), ``counts`` the
        per-kind RAM entry counts, and ``hash`` a digest of the chain
        set (cheap change detection for publishers). Memoized on the
        same mutation counter as :meth:`resident_chains` — replicas
        publish it at every batch boundary without re-scanning. RAM
        scope only: disk-resident entries still answer ``tier_probe``,
        but a directory walk per publish is not a batch-boundary cost.
        Truncated digests can collide harmlessly — placement scores and
        probe gating degrade to an extra probe; actual pulls re-key on
        FULL digests and re-validate the payload chain."""
        with self._lock:
            memo = self._digest_memo
            if memo is not None and memo[0] == self._mut:
                return memo[1]
            counts: dict[str, int] = {}
            chains: list[str] = []
            for (kd, key) in self._ram:
                counts[kd] = counts.get(kd, 0) + 1
                if kd == PREFIX_KIND:
                    chains.append(key[:16])
            chains.sort()
            out = {
                "hash": hashlib.sha1(
                    "\n".join(chains).encode()
                ).hexdigest()[:16],
                "counts": counts,
                "chains": chains,
            }
            self._digest_memo = (self._mut, out)
            return out

    @property
    def ram_bytes(self) -> int:
        with self._lock:
            return self._ram_bytes

    def snapshot(self) -> dict:
        """Counters + occupancy for ``last_stats["tier"]`` and the
        bench."""
        with self._lock:
            out = dict(self.stats)
            out["ram_bytes"] = self._ram_bytes
            out["ram_entries"] = len(self._ram)
        out["capacity_bytes"] = self.capacity_bytes
        out["dir"] = self.dir
        return out

    def audit(self) -> list[str]:
        """Structural invariants over the RAM tier (disk entries are
        verified on every ``get``): every blob decodes under its own
        (kind, key), prefix entries' chain matches their digest key,
        and the byte ledger matches the blobs held. Returns violation
        strings (empty == clean)."""
        problems: list[str] = []
        with self._lock:
            items = list(self._ram.items())
            ram_bytes = self._ram_bytes
        total = 0
        for (kind, key), blob in items:
            total += len(blob)
            try:
                payload = _decode(kind, key, blob)
            except TierIntegrityError as e:
                problems.append(f"entry ({kind}, {key[:16]}…): {e}")
                continue
            if kind == PREFIX_KIND:
                chain = payload.get("chain")
                if not isinstance(chain, list) or chain_digest(chain) != key:
                    problems.append(
                        f"prefix entry {key[:16]}…: digest key does not "
                        "match its payload token chain"
                    )
        if total != ram_bytes:
            problems.append(
                f"RAM byte ledger {ram_bytes} != {total} held"
            )
        return problems


# -- KV fabric ------------------------------------------------------------
#
# Cross-replica prefix exchange (docs/scale-out.md "KV fabric"): every
# replica's tier entries become pullable by peers, so N replicas hold
# ONE N-sized cache instead of N small ones. The reference stack's move
# is making remote memory a first-class directly-addressable tier
# (NVSHMEM symmetric gets, Triton-distributed's remote-pull
# primitives); this applies it one level up — tier entries travel as
# their checksummed wire bytes, so the PR 12 codec IS the transport and
# a garbled remote entry CRC-drops to re-prefill exactly like a
# corrupt local one.

# One wire line bound for fabric responses; mirrors the server's
# MAX_LINE_BYTES (models/ cannot import serving/ — layering).
_MAX_WIRE_LINE = 1 << 20


def tier_digest_match_len(digest, tokens) -> int:
    """Whole-page match length of ``tokens`` against a published tier
    digest (:meth:`PageStore.digest` wrapped with the engine's
    ``"ps"``): contiguous pages from the root whose truncated chain
    digests appear in the digest's chain set, capped so at least one
    token is left to prefill (the engine's fault-back walk does the
    same). 0 on a missing/foreign digest — placement then falls back
    to radix affinity alone."""
    if not isinstance(digest, dict):
        return 0
    try:
        ps = int(digest.get("ps") or 0)
    except (TypeError, ValueError):
        return 0
    chains = digest.get("chains")
    if ps <= 0 or not chains or not isinstance(chains, (list, set)):
        return 0
    have = set(chains)
    n = len(tokens)
    matched = 0
    i = ps
    while i < n:
        if chain_digest(tokens[:i])[:16] not in have:
            break
        matched = i
        i += ps
    return matched


class LocalFabricPeer:
    """In-process peer: a direct reference to another replica's store
    (the ``--replicas`` threaded-fleet shape). Pulls still return the
    encoded wire blob, so the client decodes/validates identically to
    a wire pull — one transport semantics, two carriers."""

    def __init__(self, name: str, store: PageStore):
        self.name = str(name)
        self._store = store

    def probe(self, kind: str, key: str) -> bool:
        return self._store.contains(kind, key)

    def get(self, kind: str, key: str) -> bytes | None:
        return self._store.get_blob(kind, key)


class WireFabricPeer:
    """Wire peer: ``tier_probe``/``tier_get`` line-JSON verbs against
    another replica's :class:`~serving.server.ModelServer` (the
    ``--fleet`` process shape). One short-lived connection per call —
    the verbs are engine-lock-free probe traffic, same as
    ``metrics``/``healthz``."""

    def __init__(self, name: str, host: str, port: int, *,
                 connect_timeout_s: float = 0.25):
        self.name = str(name)
        self.host = str(host)
        self.port = int(port)
        self.connect_timeout_s = float(connect_timeout_s)

    def _call(self, payload: dict, timeout_s: float) -> dict:
        with socket.create_connection(
            (self.host, self.port),
            timeout=max(self.connect_timeout_s, 0.05),
        ) as sock:
            sock.settimeout(max(timeout_s, 0.05))
            sock.sendall(
                json.dumps(payload, separators=(",", ":")).encode() + b"\n"
            )
            buf = b""
            while not buf.endswith(b"\n"):
                chunk = sock.recv(65536)
                if not chunk:
                    break
                buf += chunk
                if len(buf) > _MAX_WIRE_LINE:
                    raise TierIntegrityError("fabric response over line bound")
        resp = json.loads(buf.decode())
        if not isinstance(resp, dict) or resp.get("error"):
            raise TierIntegrityError(
                f"fabric peer error: {resp.get('error') if isinstance(resp, dict) else resp!r}"
            )
        return resp

    def probe(self, kind: str, key: str, timeout_s: float = 0.25) -> bool:
        resp = self._call(
            {"cmd": "tier_probe", "kind": kind, "keys": [key]}, timeout_s
        )
        have = resp.get("have")
        return bool(isinstance(have, list) and have and have[0])

    def get(self, kind: str, key: str,
            timeout_s: float = 0.5) -> bytes | None:
        resp = self._call(
            {"cmd": "tier_get", "kind": kind, "key": key}, timeout_s
        )
        if not resp.get("found"):
            return None
        try:
            return base64.b64decode(resp["blob"], validate=True)
        except (KeyError, TypeError, ValueError) as e:
            raise TierIntegrityError(f"undecodable fabric blob: {e}") from e


class FabricClient:
    """Bounded, deadline-checked peer fault-back for one engine's tier.

    ``fetch`` is consulted by ``ContinuousEngine._tier_fill`` on a
    LOCAL tier miss: probe peers for the chain digest, pull the entry's
    wire bytes, validate them through :func:`_decode` (CRC + header —
    the same containment boundary local reads cross), and hand back the
    decoded payload for the engine's unchanged geometry/fingerprint
    validation. Every failure — refused connect, hung peer past the
    deadline, garbled bytes, over-bound response — degrades to None
    (re-prefill) and never blocks admission: pulls are capped by
    ``max_inflight`` across threads and by ``pull_timeout_s`` of total
    wall clock per fetch, and a failing peer cools down for
    ``cooldown_s`` so a dead neighbor costs one timeout, not one per
    request."""

    def __init__(self, *, pull_timeout_s: float = 0.5,
                 max_inflight: int = 2, cooldown_s: float = 5.0,
                 connect_timeout_s: float = 0.25):
        self.pull_timeout_s = float(pull_timeout_s)
        self.cooldown_s = float(cooldown_s)
        # Dial bound for wire peers (docs/scale-out.md "Multi-host
        # fleet"): an unroutable peer host must fail the probe on
        # THIS deadline, not the OS connect default — cross-host peer
        # lists make black-holed addresses a normal failure mode.
        self.connect_timeout_s = float(connect_timeout_s)
        self._sem = threading.BoundedSemaphore(max(1, int(max_inflight)))
        self._lock = threading.Lock()
        self._peers: list = []
        self._cool: dict[str, float] = {}
        self.stats = {
            "probes": 0,
            "pulls": 0,
            "pull_bytes": 0,
            "pull_failures": 0,
            "remote_hits": 0,
        }
        # Resolved once and pre-touched at 0: a cold scrape must show
        # the full fabric series (PR 15 convention).
        self._m_probes = obs_metrics.counter(
            "tdt_fabric_probes_total",
            "Fabric peer probes issued (tier_probe) on local tier "
            "misses.",
        )
        self._m_pulls = obs_metrics.counter(
            "tdt_fabric_pulls_total",
            "Fabric entry pulls attempted (tier_get) after a positive "
            "peer probe.",
        )
        self._m_pull_bytes = obs_metrics.counter(
            "tdt_fabric_pull_bytes_total",
            "Wire bytes of fabric entries pulled and validated.",
        )
        self._m_pull_failures = obs_metrics.counter(
            "tdt_fabric_pull_failures_total",
            "Fabric probe/pull failures (dead peer, deadline, garbled "
            "or over-bound entry) — each degraded to re-prefill, never "
            "wrong bits or a blocked admission.",
        )
        self._m_remote_hits = obs_metrics.counter(
            "tdt_fabric_remote_hits_total",
            "Fabric pulls that validated and served a peer's tier "
            "entry.",
        )
        for m in (self._m_probes, self._m_pulls, self._m_pull_bytes,
                  self._m_pull_failures, self._m_remote_hits):
            m.inc(0)

    # -- peer wiring -------------------------------------------------------

    @property
    def peers(self) -> list:
        with self._lock:
            return list(self._peers)

    def set_peers(self, peers) -> None:
        """Replace the peer set (any objects with ``name``/``probe``/
        ``get``) — the in-process wiring path."""
        with self._lock:
            self._peers = list(peers)
            self._cool.clear()

    def set_wire_peers(self, peers) -> None:
        """Replace the peer set from ``tier_peers`` wire dicts
        (``{"name", "host", "port"}``) — the supervisor broadcast
        path. Malformed rows are skipped, not fatal: a stale broadcast
        must never wedge the serving loop."""
        built = []
        for p in peers or ():
            try:
                built.append(WireFabricPeer(
                    str(p["name"]), str(p["host"]), int(p["port"]),
                    connect_timeout_s=self.connect_timeout_s,
                ))
            except (KeyError, TypeError, ValueError):
                continue
        self.set_peers(built)

    # -- pull --------------------------------------------------------------

    def _fail(self, peer_name: str, kind: str, key: str,
              reason: str) -> None:
        with self._lock:
            self.stats["pull_failures"] += 1
        self._m_pull_failures.inc()
        obs_events.emit(
            "fabric_pull_failed", peer=str(peer_name)[:64],
            tier_kind=kind, key=str(key)[:16], reason=str(reason)[:160],
        )

    def fetch(self, kind: str, key: str) -> dict | None:
        """Probe peers for ``(kind, key)`` and pull + validate the
        first hit. Returns the DECODED payload dict (what
        ``PageStore.get`` returns) or None — the caller cannot tell a
        dead fabric from a fleet-wide miss, which is the point: both
        degrade to re-prefill."""
        deadline = time.monotonic() + self.pull_timeout_s
        if not self._sem.acquire(blocking=False):
            # At the in-flight bound: skip rather than queue — peer
            # fault-back is an optimization, admission latency is not.
            self._fail("*", kind, key, "inflight bound")
            return None
        try:
            return self._fetch_locked(kind, key, deadline)
        finally:
            self._sem.release()

    def _fetch_locked(self, kind: str, key: str,
                      deadline: float) -> dict | None:
        now = time.monotonic()
        for peer in self.peers:
            if now >= deadline:
                self._fail(peer.name, kind, key, "deadline")
                return None
            with self._lock:
                until = self._cool.get(peer.name, 0.0)
            if now < until:
                continue
            try:
                with self._lock:
                    self.stats["probes"] += 1
                self._m_probes.inc()
                probe_key = mutate_point(
                    "fabric.probe", key, peer=peer.name, kind=kind,
                )
                have = peer.probe(kind, probe_key)
            except Exception as e:  # noqa: BLE001 — containment boundary
                self._cool_peer(peer.name)
                self._fail(peer.name, kind, key,
                           f"probe: {type(e).__name__}: {e}")
                now = time.monotonic()
                continue
            now = time.monotonic()
            if not have:
                continue
            if now >= deadline:
                self._fail(peer.name, kind, key, "deadline")
                return None
            try:
                with self._lock:
                    self.stats["pulls"] += 1
                self._m_pulls.inc()
                blob = peer.get(kind, key)
                blob = mutate_point(
                    "fabric.get", blob, peer=peer.name, kind=kind,
                    key=key,
                )
            except Exception as e:  # noqa: BLE001 — containment boundary
                self._cool_peer(peer.name)
                self._fail(peer.name, kind, key,
                           f"pull: {type(e).__name__}: {e}")
                now = time.monotonic()
                continue
            now = time.monotonic()
            if blob is None:
                continue  # raced away between probe and pull
            if now > deadline:
                # The bytes arrived late (hung peer): honoring them
                # would make the timeout advisory. Drop, re-prefill.
                self._fail(peer.name, kind, key, "deadline")
                return None
            try:
                payload = _decode(kind, key, bytes(blob))
            except Exception as e:  # noqa: BLE001 — the codec IS the
                # transport: a garbled remote entry dies HERE, exactly
                # where a corrupt local one does.
                self._fail(peer.name, kind, key,
                           f"integrity: {type(e).__name__}: {e}")
                continue
            with self._lock:
                self.stats["remote_hits"] += 1
                self.stats["pull_bytes"] += len(blob)
            self._m_remote_hits.inc()
            self._m_pull_bytes.inc(len(blob))
            obs_events.emit(
                "fabric_pull", peer=str(peer.name)[:64], tier_kind=kind,
                key=str(key)[:16], nbytes=len(blob),
            )
            return payload
        return None

    def _cool_peer(self, name: str) -> None:
        with self._lock:
            self._cool[name] = time.monotonic() + self.cooldown_s

    def snapshot(self) -> dict:
        with self._lock:
            out = dict(self.stats)
            out["peers"] = [str(getattr(p, "name", "?"))
                            for p in self._peers]
        return out
