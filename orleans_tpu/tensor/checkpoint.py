"""Durable state plane: columnar checkpoints, device journal, recovery.

Why this exists (ROADMAP item 5): every plane so far makes the cluster
faster or more observable, but a silo that dies still loses everything
not already evicted — storage is per-grain write-back on eviction
(tensor/persistence.py), whole-silo recovery is untested, and replaying
the world through the ~9.9k rpc/s host path would take hours at 4M
grains.  This module is durability done the columnar way, three device
structures + one recovery path:

* **whole-arena columnar checkpoints** — a recovery point is a
  CONSISTENT CUT pinned at a tick boundary (ticks are natural barriers:
  between ticks no message is half-applied), realized as one compiled
  device-side copy per arena (the autofuse ``_pin_copy`` discipline)
  whose chunks then drain device→host BETWEEN ticks under a pause
  budget — live ticking continues against the real columns while the
  pin streams out, the asynchronous-snapshot discipline (Chandy-Lamport
  / Flink's asynchronous barrier snapshotting; see PAPERS.md).  The
  payload includes the arena's full identity metadata — key→row map,
  free-list high-water marks, generation, eviction epoch, both use
  clocks — so a restore reconstructs ROW IDENTITY exactly, not just
  per-key state.
* **attribution-driven incremental deltas** — between full snapshots
  only rows whose PR 10 traffic counts moved re-checkpoint (the first
  in-repo consumer of the attribution signal); cold rows ride the last
  full.  Rows are additionally compared by key against the pinned
  key→row map, so an evict + slot-reuse between checkpoints can never
  alias a clean row (the counts column retires per key on eviction —
  a reused slot's count could coincidentally match the pin).  When the
  attribution plane is live-disabled the dirty predicate degrades to
  the merged use clocks (a superset — touched ⊇ counted).
* **a device journal** — ``JournaledGrain`` re-imagined the way
  dispatch was (the host path's event_sourcing.py commits one storage
  write PER EVENT): registered ingress sites append each tick's batch
  to a per-site append log whose DEVICE leaves are held by reference —
  device arrays are immutable, so the reference IS the log entry: zero
  kernels, zero copies, zero transfers per tick, and a steady injector
  re-presenting the same slab pins exactly one buffer.  Host numpy
  leaves intern by identity + content (the PR 9 staging-memo lesson)
  so a steady loader's static payload is stored once per segment, and
  scalars ride per-entry metadata.  The d2h happens ONCE per segment
  seal as one batched ``jax.device_get``.  A segment becomes DURABLE
  (acknowledged) when its blob + manifest commit lands; buffered lanes
  beyond the committed horizon are explicitly the documented loss
  window of a hard kill.
* **crash recovery** — ``recover()`` rebuilds every arena from the
  latest committed recovery point (full + deltas applied in order),
  then FOLD-REPLAYS the journal tail: entries group by their original
  tick and re-inject as whole batches — one engine tick per journaled
  tick, never per-event Python — through the same handlers, so emits,
  fan-outs and subscriptions re-fire deterministically and the restored
  state is bit-exact for integer workloads (samples/banking.py is the
  oracle workload).  Recovery ends by committing a fresh full snapshot,
  re-anchoring the chain so a second crash recovers from the new point.

Commit protocol (the zero-acknowledged-loss contract the chaos
invariant ``check_durability_accounting`` pins): blobs first, manifest
last, manifest replaced atomically (tmp + fsync + rename) — a kill at
any byte offset leaves either the old recovery point or the new one,
never a torn mix.  ``durable_horizon()`` names what is acknowledged.
"""

from __future__ import annotations

import json
import os
import time
import weakref
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from orleans_tpu.tensor.arena import _pow2_pad
from orleans_tpu.tensor.attribution import pow2ceil
from orleans_tpu.tensor.persistence import fsync_write
from orleans_tpu.tensor.profiler import host_read


@jax.jit
def _pin_tree(tree):
    """One compiled device-side copy of an arena's state tree — the
    consistent-cut pin.  Async dispatch, never an eager per-column copy
    (the autofuse ``_pin_copy`` lesson: eager copies measured ruinously
    slow on the pre-PR-1 chip rig)."""
    return jax.tree_util.tree_map(jnp.copy, tree)


@jax.jit
def _gather_tree(tree, idx):
    """Gather one chunk of rows from a pinned state tree (all fields in
    one dispatch; the caller fetches the result with ONE device_get)."""
    return jax.tree_util.tree_map(lambda col: col[idx], tree)


@jax.jit
def _dirty_mask_kernel(counts, pinned_counts, clock_dev, clock_host,
                       live, cutoff):
    """Delta dirtiness ON DEVICE: a live row is dirty when its traffic
    count moved since the pin OR either use clock advanced past the pin
    tick (the clock term covers folds the attribution plane buffered or
    retired between pins).  Only the bool mask crosses d2h."""
    moved = counts != pinned_counts
    touched = jnp.maximum(clock_dev, clock_host) >= cutoff
    return live & (moved | touched)


@jax.jit
def _touched_mask_kernel(clock_dev, clock_host, live, cutoff):
    """Clock-only dirtiness (attribution plane disabled): touched since
    the pin tick — a superset of 'state changed'."""
    return live & (jnp.maximum(clock_dev, clock_host) >= cutoff)


# ---------------------------------------------------------------------------
# snapshot stores
# ---------------------------------------------------------------------------

class SnapshotStore:
    """Blob + manifest contract of the durable state plane.  Blobs are
    named dicts of numpy arrays with a small JSON meta; the MANIFEST is
    the single atomic commit pointer — a recovery point exists exactly
    when the manifest referencing it is readable."""

    def put_blob(self, name: str, arrays: Dict[str, np.ndarray],
                 meta: Optional[Dict[str, Any]] = None) -> int:
        """Write a blob durably; returns approximate bytes written."""
        raise NotImplementedError

    def get_blob(self, name: str
                 ) -> Optional[Tuple[Dict[str, np.ndarray],
                                     Dict[str, Any]]]:
        raise NotImplementedError

    def delete_blob(self, name: str) -> None:
        raise NotImplementedError

    def read_manifest(self) -> Optional[Dict[str, Any]]:
        raise NotImplementedError

    def commit_manifest(self, manifest: Dict[str, Any]) -> None:
        """ATOMIC replace — the durability acknowledgement point."""
        raise NotImplementedError


class MemorySnapshotStore(SnapshotStore):
    """In-process store; share ``backing`` across engines to model a
    durable medium surviving a hard kill (the MemoryVectorStore
    pattern).  Arrays are copied on write so a donated/reused buffer
    can never mutate a committed snapshot."""

    def __init__(self, backing: Optional[Dict] = None) -> None:
        self._b = backing if backing is not None else {}
        self._b.setdefault("blobs", {})

    @staticmethod
    def shared_backing() -> Dict:
        return {}

    def put_blob(self, name, arrays, meta=None):
        copied = {k: np.asarray(v).copy() for k, v in arrays.items()}
        self._b["blobs"][name] = (copied, dict(meta or {}))
        return int(sum(a.nbytes for a in copied.values()))

    def get_blob(self, name):
        ent = self._b["blobs"].get(name)
        if ent is None:
            return None
        arrays, meta = ent
        return ({k: v.copy() for k, v in arrays.items()}, dict(meta))

    def delete_blob(self, name):
        self._b["blobs"].pop(name, None)

    def read_manifest(self):
        m = self._b.get("manifest")
        return json.loads(m) if m is not None else None

    def commit_manifest(self, manifest):
        # serialize through JSON: the manifest must stay plain data (the
        # file store round-trips it), and assignment is atomic
        self._b["manifest"] = json.dumps(manifest)


class FileSnapshotStore(SnapshotStore):
    """On-disk store: one ``.npz`` per blob under ``root``, manifest as
    ``MANIFEST.json``.  Every write is tmp + fsync + atomic rename
    (persistence.fsync_write), and blobs land BEFORE the manifest that
    references them, so a kill at any point leaves a readable store."""

    def __init__(self, root: str) -> None:
        self.root = root
        os.makedirs(root, exist_ok=True)

    def _path(self, name: str) -> str:
        if "/" in name or "\\" in name or name.startswith("."):
            raise ValueError(f"bad blob name {name!r}")
        return os.path.join(self.root, name + ".npz")

    def put_blob(self, name, arrays, meta=None):
        payload = {k: np.asarray(v) for k, v in arrays.items()}
        payload["__meta__"] = np.frombuffer(
            json.dumps(meta or {}).encode(), dtype=np.uint8)
        path = self._path(name)
        fsync_write(path, lambda f: np.savez(f, **payload))
        return int(os.path.getsize(path))

    def get_blob(self, name):
        path = self._path(name)
        if not os.path.exists(path):
            return None
        with np.load(path) as z:
            arrays = {k: z[k] for k in z.files if k != "__meta__"}
            meta = json.loads(z["__meta__"].tobytes().decode()) \
                if "__meta__" in z.files else {}
        return arrays, meta

    def delete_blob(self, name):
        try:
            os.remove(self._path(name))
        except FileNotFoundError:
            pass

    def read_manifest(self):
        path = os.path.join(self.root, "MANIFEST.json")
        try:
            with open(path, "r") as f:
                return json.load(f)
        except FileNotFoundError:
            return None
        except json.JSONDecodeError:
            # an atomically-renamed manifest is never torn; a torn file
            # here means the medium predates fsync_write — treat as no
            # recovery point rather than crashing the restore path
            return None

    def commit_manifest(self, manifest):
        fsync_write(os.path.join(self.root, "MANIFEST.json"),
                    lambda f: f.write(json.dumps(manifest, indent=1)
                                      .encode()),
                    binary=True)


# ---------------------------------------------------------------------------
# the device journal
# ---------------------------------------------------------------------------

def _tree_skeleton(obj):
    """JSON-able skeleton of an args pytree (dict/list/tuple nesting);
    leaves become integer slots in flatten order.  The journal needs a
    SERIALIZABLE tree structure (jax treedefs are not), and every
    workload in this repo passes plain-container args."""
    slot = [0]

    def walk(o):
        if isinstance(o, dict):
            return {"t": "d", "k": {k: walk(o[k]) for k in sorted(o)}}
        if isinstance(o, (list, tuple)):
            return {"t": "l" if isinstance(o, list) else "u",
                    "c": [walk(c) for c in o]}
        i = slot[0]
        slot[0] += 1
        return {"t": "x", "i": i}

    return walk(obj), slot[0]


def _skeleton_flatten(obj, out: List[Any]) -> None:
    if isinstance(obj, dict):
        for k in sorted(obj):
            _skeleton_flatten(obj[k], out)
    elif isinstance(obj, (list, tuple)):
        for c in obj:
            _skeleton_flatten(c, out)
    else:
        out.append(obj)


def _skeleton_unflatten(skel, leaves: List[Any]):
    t = skel["t"]
    if t == "d":
        return {k: _skeleton_unflatten(v, leaves)
                for k, v in skel["k"].items()}
    if t in ("l", "u"):
        seq = [_skeleton_unflatten(c, leaves) for c in skel["c"]]
        return seq if t == "l" else tuple(seq)
    return leaves[skel["i"]]


class _JournalSite:
    """One journaled ingress (type, method): the open segment's state."""

    __slots__ = ("type_name", "method", "key", "skeleton", "n_slots",
                 "capacity", "entries", "dev_arrays", "dev_index",
                 "host_arrays", "host_index", "seq", "committed_lanes",
                 "committed_tick", "appended_lanes", "segment_lanes")

    def __init__(self, type_name: str, method: str, capacity: int) -> None:
        self.type_name = type_name
        self.method = method
        self.key = f"{type_name}.{method}"
        self.skeleton = None          # args tree skeleton (JSON-able)
        self.n_slots = 0
        self.capacity = capacity      # buffered-lane bound → forced seal
        self.entries: List[Dict[str, Any]] = []
        # DEVICE leaves of the open segment, held BY REFERENCE: device
        # arrays are immutable, so a reference IS the append — zero
        # device work per tick, and a steady injector re-presenting the
        # same buffer pins exactly one buffer regardless of entry count
        self.dev_arrays: List[Any] = []
        # id() → index into dev_arrays.  Every indexed array is HELD
        # (alive) until the seal, so an id can never be reused by a
        # different live array — the `is` check below is belt-and-braces
        self.dev_index: Dict[int, int] = {}
        # identity+content-interned host numpy leaves for the OPEN
        # segment: a steady loader re-presenting the same payload array
        # stores it once per segment (the PR 9 staging-memo discipline)
        self.host_arrays: List[np.ndarray] = []
        self.host_index: Dict[int, Tuple[Any, int]] = {}
        self.seq = 0                  # next segment seq
        self.committed_lanes = 0      # lanes sealed into durable segments
        self.committed_tick = -1
        self.appended_lanes = 0
        self.segment_lanes = 0        # lanes in the OPEN (not yet durable)


class DeviceJournal:
    """Per-site device append logs + the segment seal/replay machinery.

    Append cost model — the whole point of the device tier: a DEVICE
    leaf appends by reference (device arrays are immutable, so holding
    the reference IS the log entry — no kernel, no copy, no transfer;
    an injector re-presenting the same slab every tick pins ONE
    buffer), a host numpy leaf pays an identity-memo lookup (content
    memcmp only on identity hits — the PR 9 staging-memo lesson: hosts
    mutate buffers in place), scalars ride per-entry metadata.  The
    d2h for every buffered device leaf happens ONCE per segment seal,
    as one batched ``jax.device_get`` — never per event, never per
    tick.  ``event_sourcing.py`` pays one storage commit per event;
    this pays one durable commit per segment."""

    def __init__(self, engine, plane: "CheckpointPlane") -> None:
        self._engine = weakref.ref(engine)
        self.plane = plane
        self.sites: Dict[Tuple[str, str], _JournalSite] = {}
        self._order = 0               # global append order stamp
        self.ring_overflows = 0
        self.segments_committed = 0
        self.flush_seconds = 0.0
        self.replayed_lanes = 0

    # -- registration -------------------------------------------------------

    def register(self, type_name: str, method: str) -> _JournalSite:
        key = (type_name, method)
        site = self.sites.get(key)
        if site is None:
            cap = pow2ceil(self.plane.config().journal_ring_lanes)
            site = _JournalSite(type_name, method, cap)
            self.sites[key] = site
        return site

    # -- append -------------------------------------------------------------

    def _intern_host(self, site: _JournalSite, a: np.ndarray) -> int:
        """Identity + content interning of a host leaf (a loader may
        mutate the same buffer in place between ticks — identity alone
        was the PR 9 staging bug)."""
        ent = site.host_index.get(id(a))
        if ent is not None:
            ref, idx = ent
            if ref() is a and np.array_equal(a, site.host_arrays[idx]):
                return idx
        idx = len(site.host_arrays)
        site.host_arrays.append(np.asarray(a).copy())
        try:
            site.host_index[id(a)] = (weakref.ref(a), idx)
        except TypeError:
            pass  # non-weakrefable: stored, just never deduped
        return idx

    def _intern_dev(self, site: _JournalSite, a) -> int:
        """Append-by-reference of an immutable device leaf; identical
        re-presented buffers (the steady injector) dedupe by identity —
        no content compare needed, device arrays never mutate.  O(1):
        a linear scan over the open segment would make the write-ahead
        hook quadratic for workloads presenting fresh arrays per tick."""
        idx = site.dev_index.get(id(a))
        if idx is not None and site.dev_arrays[idx] is a:
            return idx
        site.dev_arrays.append(a)
        idx = len(site.dev_arrays) - 1
        site.dev_index[id(a)] = idx
        return idx

    def append(self, type_name: str, method: str, batch) -> None:
        """Journal one ingress batch (engine enqueue / injector inject).
        Appends never raise into the hot path on a full buffer — the
        open segment seals first (counted as a ring_overflow)."""
        site = self.sites.get((type_name, method))
        if site is None:
            return
        args = batch.args
        skel, n_slots = _tree_skeleton(args)
        if site.skeleton is None:
            site.skeleton = skel
            site.n_slots = n_slots
        elif skel != site.skeleton:
            # a site changing its args structure is pathological but
            # legal — seal the open segment under the old skeleton and
            # re-spec
            self.flush(site)
            site.skeleton = skel
            site.n_slots = n_slots
        leaves: List[Any] = []
        _skeleton_flatten(args, leaves)
        keys_host = batch.keys_host
        keys_dev = batch.keys_dev if keys_host is None else None
        if keys_host is None and keys_dev is None:
            raise ValueError(
                f"journal site {site.key}: ingress batch carries neither "
                f"host nor device keys (wide-key ingress is not "
                f"journalable — hash identities into the int domain)")
        lanes = len(keys_host) if keys_host is not None else len(keys_dev)
        if site.segment_lanes + lanes > site.capacity and site.entries:
            self.ring_overflows += 1
            self.flush(site)
        entry: Dict[str, Any] = {
            "tick": int(batch.inject_tick),
            "order": self._order,
            "lanes": int(lanes),
            "refs": [],
        }
        self._order += 1
        for leaf in leaves:
            if isinstance(leaf, jnp.ndarray) and leaf.ndim >= 1:
                # any-width device leaf: lane-aligned payloads AND
                # per-batch device constants (lookup tables) both append
                # by reference — replay re-presents the exact bytes
                entry["refs"].append(
                    {"k": "d", "i": self._intern_dev(site, leaf)})
            elif isinstance(leaf, np.ndarray) and leaf.ndim >= 1:
                entry["refs"].append(
                    {"k": "h", "i": self._intern_host(site, leaf)})
            else:
                # scalar / 0-d leaf: host meta (np scalars are free;
                # a 0-d DEVICE leaf pays one d2h — rare by construction)
                entry["refs"].append(
                    {"k": "s", "v": np.asarray(leaf).item(),
                     "d": str(np.asarray(leaf).dtype)})
        if keys_host is not None:
            entry["keys"] = {"k": "h",
                             "i": self._intern_host(site, keys_host)}
        else:
            entry["keys"] = {"k": "d",
                             "i": self._intern_dev(site, keys_dev)}
        site.entries.append(entry)
        site.appended_lanes += lanes
        site.segment_lanes += lanes

    # -- seal / durability --------------------------------------------------

    def pending_lanes(self) -> int:
        return sum(s.segment_lanes for s in self.sites.values())

    def flush(self, site: Optional[_JournalSite] = None) -> int:
        """Seal the open segment(s) durable: ONE batched d2h for every
        buffered device leaf, all segment BLOBS first, then ONE
        manifest commit covering every sealed site (the blobs-first/
        manifest-last contract at one fsync per flush, not one per
        site).  Returns segments committed — this is the
        acknowledgement point: everything in a sealed segment survives
        a hard kill, everything still buffered does not."""
        t0 = time.perf_counter()
        sites = [site] if site is not None else list(self.sites.values())
        sealed: List[Tuple[_JournalSite, str, Dict[str, Any]]] = []
        for s in sites:
            if not s.entries:
                continue
            arrays: Dict[str, np.ndarray] = {}
            host_dev = host_read("checkpoint", s.dev_arrays) \
                if s.dev_arrays else []
            for i, a in enumerate(host_dev):
                arrays[f"d{i}"] = np.asarray(a)
            for i, a in enumerate(s.host_arrays):
                arrays[f"h{i}"] = a
            ticks = [e["tick"] for e in s.entries]
            meta = {
                "site": [s.type_name, s.method],
                "seq": s.seq,
                "skeleton": s.skeleton,
                "entries": s.entries,
                "lanes": s.segment_lanes,
                "tick_min": min(ticks),
                "tick_max": max(ticks),
            }
            blob = f"journal-{s.key}-{s.seq:08d}"
            self.plane.store.put_blob(blob, arrays, meta)
            sealed.append((s, blob, meta))
        if sealed:
            self.plane._journal_commit(sealed)
            for s, _blob, meta in sealed:
                s.seq += 1
                s.committed_lanes += s.segment_lanes
                s.committed_tick = meta["tick_max"]
                s.entries = []
                s.dev_arrays = []
                s.dev_index = {}
                s.host_arrays = []
                s.host_index = {}
                s.segment_lanes = 0
                self.segments_committed += 1
        self.flush_seconds += time.perf_counter() - t0
        return len(sealed)

    # -- replay -------------------------------------------------------------

    @staticmethod
    def decode_segment(arrays: Dict[str, np.ndarray],
                       meta: Dict[str, Any]) -> List[Dict[str, Any]]:
        """Segment blob → list of replayable entries: each is
        ``{tick, order, keys, args}`` with numpy leaves."""
        def resolve(ref):
            if ref["k"] == "d":
                return arrays[f"d{ref['i']}"]
            if ref["k"] == "h":
                return arrays[f"h{ref['i']}"]
            return np.dtype(ref["d"]).type(ref["v"])

        out = []
        skel = meta["skeleton"]
        for e in meta["entries"]:
            leaves = [resolve(ref) for ref in e["refs"]]
            out.append({"tick": e["tick"], "order": e["order"],
                        "keys": np.asarray(resolve(e["keys"])),
                        "args": _skeleton_unflatten(skel, leaves)})
        return out

    def snapshot(self) -> Dict[str, Any]:
        return {
            "sites": {s.key: {"appended_lanes": s.appended_lanes,
                              "committed_lanes": s.committed_lanes,
                              "pending_lanes": s.segment_lanes,
                              "segments": s.seq,
                              "committed_tick": s.committed_tick}
                      for s in self.sites.values()},
            "segments_committed": self.segments_committed,
            "ring_overflows": self.ring_overflows,
            "pending_lanes": self.pending_lanes(),
            "flush_seconds": round(self.flush_seconds, 6),
            "replayed_lanes": self.replayed_lanes,
        }


# ---------------------------------------------------------------------------
# the checkpoint plane
# ---------------------------------------------------------------------------

class _ActiveSnapshot:
    """One in-progress (pinned, draining) snapshot."""

    __slots__ = ("kind", "tick", "seq", "arenas", "queue", "bytes",
                 "rows", "parts", "started", "timers")

    def __init__(self, kind: str, tick: int, seq: int) -> None:
        self.kind = kind              # "full" | "delta"
        self.tick = tick              # the consistent-cut tick
        self.seq = seq
        self.arenas: Dict[str, Dict[str, Any]] = {}
        self.queue: List[Tuple[str, int]] = []  # (type, chunk index)
        self.bytes = 0
        self.rows = 0
        self.parts: Dict[str, List[str]] = {}
        self.started = time.perf_counter()
        # timers-plane export pinned with the cut: (arrays, meta) for
        # one blob, or None when nothing is armed/logged
        self.timers: Any = None


class FencedError(RuntimeError):
    """The snapshot store's manifest carries a newer promotion-fence
    epoch than this plane holds: a promoted standby has claimed the
    store (and with it, this silo's ring range).  Every commit path
    raises this instead of acknowledging — the old primary, even if
    merely partitioned rather than dead, can never serve a durable
    write after its range was claimed."""


class CheckpointPlane:
    """The engine's durable state plane (attach a SnapshotStore to
    engage).  All public entry points are host-synchronous and run
    between ticks — ``on_tick`` is the engine hook, ``checkpoint_full``
    / ``checkpoint_delta`` drive a snapshot to completion for explicit
    callers, ``recover`` is the silo-startup restore path."""

    def __init__(self, engine, store: Optional[SnapshotStore] = None
                 ) -> None:
        self._engine = weakref.ref(engine)
        self.store = store
        self.journal = DeviceJournal(engine, self)
        self._active: Optional[_ActiveSnapshot] = None
        self._manifest: Optional[Dict[str, Any]] = None
        self._seq = 0
        self._last_full_tick = -1
        self._last_ckpt_tick = -1     # last committed recovery point
        self._last_journal_flush_tick = 0
        # per-arena pin for delta dirtiness: (generation, host key map,
        # device counts copy | None, pin tick)
        self._delta_pin: Dict[str, Tuple] = {}
        self._replaying = False
        # emit-destination pre-activation hints per journaled site:
        # arg leaf names whose values are emit-target KEYS of the
        # site's own type (register_journal(..., emit_key_args=...)).
        # Recovery resolves their union BEFORE fused replay so a fused
        # window never misses on a cold emit destination (activation
        # is field-inits only — state exactness is unaffected).
        self._emit_key_args: Dict[Tuple[str, str], Tuple[str, ...]] = {}
        # promotion fence: the manifest's fence epoch this plane holds.
        # A standby promotion bumps the store's epoch; every commit
        # path re-reads it first and refuses (FencedError) when the
        # store has moved past us — a partitioned old primary can
        # never acknowledge a write after its range was claimed.
        self.fence_epoch = 0
        self._fence_owner = ""
        self.fenced = False
        self.on_fenced: Optional[Any] = None  # silo kill hook
        # counters (silo.collect_metrics mirrors these into ckpt.*)
        self.full_snapshots = 0
        self.delta_snapshots = 0
        self.rows_written = 0
        self.bytes_written = 0
        self.restored_rows = 0
        self.last_restore_s = 0.0
        self.last_dirty_rows = 0
        self.pauses: List[float] = []
        self.max_pause_s = 0.0
        # recovery observability (silo mirrors into recovery.*)
        self.replay_fused_windows = 0
        self.replay_fused_lanes = 0
        self.promotions = 0
        self.last_rto_s = 0.0
        if store is not None:
            m = store.read_manifest()
            if m is not None:
                self._manifest = m
                self._seq = int(m.get("seq", 0)) + 1
                rec = m.get("recovery") or {}
                self._last_ckpt_tick = int(rec.get("tick", -1))
                self.fence_epoch = int(
                    (m.get("fence") or {}).get("epoch", 0))

    # -- plumbing -----------------------------------------------------------

    @property
    def enabled(self) -> bool:
        return self.store is not None

    def engine(self):
        return self._engine()

    def config(self):
        return self.engine().config

    def _rec(self):
        """The owning silo's SpanRecorder (timeline plane spans) or
        None — same single-check gate every engine hook uses."""
        eng = self.engine()
        return None if eng is None else eng._span_recorder()

    def attach_store(self, store: SnapshotStore) -> None:
        """Late binding (tests / silo setup hooks / standby promotion):
        engage the plane on a running engine."""
        self.store = store
        m = store.read_manifest()
        if m is not None:
            self._manifest = m
            self._seq = int(m.get("seq", 0)) + 1
            self._last_ckpt_tick = int(
                (m.get("recovery") or {}).get("tick", -1))
            self.fence_epoch = int(
                (m.get("fence") or {}).get("epoch", 0))

    def register_journal(self, interface, method: str,
                         emit_key_args: Tuple[str, ...] = ()) -> None:
        """``emit_key_args``: names of arg leaves whose values are emit
        DESTINATION keys of this same grain type (e.g. banking
        transfer's ``dst``) — recovery pre-activates their union so
        fused fold-replay windows never roll back on cold emit
        targets."""
        eng = self.engine()
        type_name = eng._type_name(interface)
        self.journal.register(type_name, method)
        # mark the fast-path set the ingress hook checks
        eng._journal_sites.add((type_name, method))
        if emit_key_args:
            self._emit_key_args[(type_name, method)] = \
                tuple(emit_key_args)

    def journal_ingress(self, type_name: str, method: str, batch) -> None:
        if self._replaying or not self.enabled:
            return
        self.journal.append(type_name, method, batch)

    def durable_horizon(self) -> Dict[str, Any]:
        """What is ACKNOWLEDGED durable right now: the committed
        recovery-point tick plus each journal site's committed lane
        count/tick.  Ring lanes past this horizon are the documented
        loss window of a hard kill."""
        return {
            "recovery_tick": self._last_ckpt_tick,
            "journal": {s.key: {"committed_lanes": s.committed_lanes,
                                "committed_tick": s.committed_tick}
                        for s in self.journal.sites.values()},
        }

    # -- promotion fence ----------------------------------------------------

    def _check_fence(self) -> None:
        """Re-read the store's fence epoch before a commit.  A newer
        epoch means a standby promoted over this store — refuse
        (FencedError) rather than acknowledge a write the promoted
        silo will never see."""
        m = self.store.read_manifest()
        cur = int(((m or {}).get("fence") or {}).get("epoch", 0))
        if cur > self.fence_epoch:
            self.fenced = True
            raise FencedError(
                f"snapshot store fenced at epoch {cur} (this plane "
                f"holds {self.fence_epoch}) — a standby promoted over "
                f"this store; refusing to commit")

    def acquire_fence(self, owner: str = "") -> int:
        """Claim the store: bump the manifest's fence epoch with one
        commit.  From this commit on, every OTHER plane attached to the
        store (the old primary) fails its next commit with
        FencedError.  Returns the new epoch."""
        m = self.store.read_manifest() or {}
        epoch = int((m.get("fence") or {}).get("epoch", 0)) + 1
        m["fence"] = {"epoch": epoch, "owner": owner}
        m["seq"] = self._seq
        self._seq += 1
        self.store.commit_manifest(m)
        self._manifest = m
        self.fence_epoch = epoch
        self._fence_owner = owner
        return epoch

    # -- cadence / engine hook ----------------------------------------------

    def _quiescent_for_pin(self) -> bool:
        """A pin is a consistent cut only when nothing is half-delivered:
        no parked optimistic/exchange/fan-out checks, no fence-deferred
        batches, no queued work (the maybe_periodic_checkpoint
        discipline — the common steady state passes, continuous traffic
        cannot starve the cadence because queues drain every tick)."""
        eng = self.engine()
        return not (eng._pending_checks or eng._exchange_checks
                    or eng._fanout_checks or eng._fence_deferred
                    or any(eng.queues.values()))

    def on_tick(self) -> float:
        """The run_tick hook: start a due snapshot, drain one
        pause-budgeted slice, keep the journal cadence.  Returns host
        seconds spent (the 'checkpoint' stage)."""
        if not self.enabled or self._replaying:
            return 0.0
        eng = self.engine()
        cfg = eng.config
        t0 = time.perf_counter()
        did = False
        if self._active is None:
            full_due = cfg.ckpt_full_every_ticks > 0 and \
                eng.tick_number - max(self._last_full_tick, 0) \
                >= cfg.ckpt_full_every_ticks
            delta_due = cfg.ckpt_delta_every_ticks > 0 and \
                self._last_full_tick >= 0 and \
                eng.tick_number - max(self._last_ckpt_tick, 0) \
                >= cfg.ckpt_delta_every_ticks
            if (full_due or delta_due) and not self._quiescent_for_pin():
                # parked optimistic checks under steady emit traffic
                # would starve the cadence forever — force the (already
                # cap-bounded) synchronizing drain.  If it re-queued
                # redeliveries, the pin defers ONE tick (their stamps
                # predate the cut, so pinning over them would lose
                # their effects to the replay filter).
                eng._drain_checks()
                did = True
            if (full_due or delta_due) and self._quiescent_for_pin():
                self.begin("full" if full_due else "delta")
                did = True
        try:
            if self._active is not None:
                self.run_slice(cfg.ckpt_pause_budget_s)
                did = True
            if cfg.journal_flush_every_ticks > 0 and \
                    eng.tick_number - self._last_journal_flush_tick \
                    >= cfg.journal_flush_every_ticks:
                self._last_journal_flush_tick = eng.tick_number
                if self.journal.pending_lanes():
                    self.journal.flush()
                    did = True
        except FencedError:
            # a standby promoted over this store: the plane is dead
            # from here — drop the in-flight snapshot, stop journaling
            # (nothing further can be acknowledged) and hand control to
            # the silo hook, which kills the silo (a fenced primary
            # must not keep serving a range another silo now owns)
            self._active = None
            self.store = None
            cb, self.on_fenced = self.on_fenced, None
            if cb is not None:
                cb()
            return time.perf_counter() - t0
        if not did:
            return 0.0
        dt = time.perf_counter() - t0
        self.pauses.append(dt)
        if len(self.pauses) > 1024:
            del self.pauses[:512]
        self.max_pause_s = max(self.max_pause_s, dt)
        return dt

    # -- snapshot lifecycle -------------------------------------------------

    def begin(self, kind: str) -> None:
        """Pin the consistent cut: settle the fused chain, seal the
        journal at the cut, take one compiled device copy per arena +
        host metadata copies.  O(live arenas) host work — the drain
        happens in later slices."""
        if self._active is not None:
            raise RuntimeError("snapshot already in progress")
        t_pin0 = time.perf_counter()
        eng = self.engine()
        fuser = getattr(eng, "autofuser", None)
        if fuser is not None and fuser._unverified:
            # the pin must capture VERIFIED state: an unverified window
            # chain either proves exact or rolls back + replays NOW
            fuser._settle_chain()
        # NOTE: the attribution plane's buffered folds are deliberately
        # NOT flushed here.  Stale counts can only under-report "moved"
        # (a fold buffered across BOTH pins shows no diff), and every
        # such row's use clock advanced past the pin tick — the dirty
        # predicate's clock term catches it.  Forcing a flush at the
        # pin's arbitrary buffer depth was measured paying that plane's
        # coalesced-kernel arity compiles (~0.3s) inside checkpoint
        # pauses.
        pin_tick = eng.tick_number
        # journal horizon: everything <= the cut seals durable with the
        # snapshot; replay after restore starts at tick >= pin_tick
        self.journal.flush()
        snap = _ActiveSnapshot(kind, pin_tick, self._seq)
        self._seq += 1
        chunk = max(1, int(eng.config.ckpt_chunk_rows))
        for name, arena in eng.arenas.items():
            live_rows = np.nonzero(arena._key_of_row >= 0)[0]
            part_kind = kind
            if kind == "delta":
                pin = self._delta_pin.get(name)
                if pin is None or pin[0] != arena.generation:
                    # no pin yet, or rows moved since (growth/compaction
                    # /reshard re-home rows): delta row ids would lie —
                    # promote this arena's part to a full
                    part_kind = "full"
                else:
                    live_rows = self._dirty_rows(arena, pin, live_rows)
            if part_kind == "full" and kind == "delta":
                snap.kind = "full"  # an all-full delta IS a full
            pinned = _pin_tree({**arena.state,
                                "__last_use_dev": arena.last_use_dev})
            meta = arena.export_layout()
            meta["tick"] = pin_tick
            meta["kind"] = part_kind
            snap.arenas[name] = {
                "pin": pinned,
                "meta": meta,
                "rows": live_rows.astype(np.int64),
                "chunk": chunk,
                "n_chunks": -(-len(live_rows) // chunk)
                if len(live_rows) else 0,
            }
            snap.parts[name] = []
            for c in range(snap.arenas[name]["n_chunks"]):
                snap.queue.append((name, c))
        # promoting any arena to full promotes the SNAPSHOT: a recovery
        # point must be self-consistent (all-arena cut at one tick)
        if snap.kind == "full":
            for name, a in snap.arenas.items():
                if a["meta"]["kind"] == "delta":
                    arena = eng.arenas[name]
                    a["rows"] = np.nonzero(
                        arena._key_of_row >= 0)[0].astype(np.int64)
                    a["meta"]["kind"] = "full"
                    a["n_chunks"] = -(-len(a["rows"]) // a["chunk"]) \
                        if len(a["rows"]) else 0
            snap.queue = [(n, c) for n, a in snap.arenas.items()
                          for c in range(a["n_chunks"])]
        self.last_dirty_rows = sum(
            len(a["rows"]) for a in snap.arenas.values()
            if a["meta"]["kind"] == "delta")
        # the timers plane rides the same cut (AFTER any full
        # promotion above — its export kind must match the snapshot's):
        # full = compact live slots at absolute dues, delta = the
        # arm/cancel op log since the previous cut
        snap.timers = eng.timers.export_cut(snap.kind)
        self._active = snap
        rec = self._rec()
        if rec is not None:
            rec.plane_span("checkpoint", f"pin {snap.kind}",
                           duration=time.perf_counter() - t_pin0,
                           tick=pin_tick, seq=snap.seq,
                           arenas=len(snap.arenas),
                           dirty_rows=self.last_dirty_rows)

    def _dirty_rows(self, arena, pin, live_rows: np.ndarray) -> np.ndarray:
        """Attribution-driven delta predicate: rows whose traffic count
        moved since the pin, union rows either use clock touched past
        the pin tick, union rows whose KEY changed (evict + slot reuse
        could alias both of the above)."""
        gen, pinned_keys, pinned_counts, pin_tick = pin
        live = arena._key_of_row >= 0
        cutoff = int(np.clip(pin_tick, -2**31 + 1, 2**31 - 1))
        host_clock = np.clip(arena.last_use_tick, 0, 2**31 - 1) \
            .astype(np.int32)
        eng = self.engine()
        att = eng.attribution
        if pinned_counts is not None and att is not None \
                and att.has_state(arena.info.name):
            counts = att.counts_for(arena.info.name)
            if counts.shape == pinned_counts.shape:
                mask = _dirty_mask_kernel(
                    counts, pinned_counts, arena.last_use_dev,
                    jnp.asarray(host_clock), jnp.asarray(live),
                    jnp.int32(cutoff))
            else:
                mask = _touched_mask_kernel(
                    arena.last_use_dev, jnp.asarray(host_clock),
                    jnp.asarray(live), jnp.int32(cutoff))
        else:
            mask = _touched_mask_kernel(
                arena.last_use_dev, jnp.asarray(host_clock),
                jnp.asarray(live), jnp.int32(cutoff))
        dirty = np.asarray(mask).copy()
        if arena._replicas:
            # replica groups are always dirty: the lane-hash spread
            # lands commutative contributions on secondary rows without
            # advancing the clocks the predicate reads, so a delta that
            # skipped them would lose acknowledged writes at the cut.
            # Hot grains only — a handful of rows per delta.
            for r in arena._replicas.values():
                dirty[r] = True
        # key churn: rows reused by a different grain since the pin (the
        # pinned map is capacity-aligned only while capacity matched)
        n = min(len(pinned_keys), len(arena._key_of_row))
        changed = arena._key_of_row[:n] != pinned_keys[:n]
        dirty[:n] |= changed & live[:n]
        if len(arena._key_of_row) > n:
            dirty[n:] |= live[n:]
        return np.flatnonzero(dirty).astype(np.int64)

    def run_slice(self, budget_s: float) -> int:
        """Drain chunks of the pinned snapshot until the pause budget is
        spent (<= 0 drains everything — the synchronous baseline).  The
        commit (meta blobs + manifest) rides the final slice.  Returns
        chunks drained."""
        snap = self._active
        if snap is None:
            return 0
        t0 = time.perf_counter()
        drained = 0
        while snap.queue:
            name, c = snap.queue.pop(0)
            a = snap.arenas[name]
            rows = a["rows"][c * a["chunk"]:(c + 1) * a["chunk"]]
            # fixed-size pow2 pad: one compiled gather per (arena
            # layout, chunk) instead of per data-dependent length
            idx = jnp.asarray(_pow2_pad(rows.astype(np.int32), 0))
            host = host_read("checkpoint", _gather_tree(a["pin"], idx))
            arrays = {k: np.asarray(v)[:len(rows)]
                      for k, v in host.items()}
            arrays["__rows"] = rows
            arrays["__keys"] = a["meta"]["key_of_row"][rows]
            blob = f"ckpt-{snap.seq:08d}-{name}-{c:06d}"
            snap.bytes += self.store.put_blob(
                blob, arrays, {"arena": name, "chunk": c})
            snap.parts[name].append(blob)
            snap.rows += len(rows)
            drained += 1
            if budget_s > 0 and time.perf_counter() - t0 >= budget_s:
                break
        if drained:
            rec = self._rec()
            if rec is not None:
                rec.plane_span("checkpoint", "drain slice",
                               duration=time.perf_counter() - t0,
                               chunks=drained, seq=snap.seq,
                               remaining=len(snap.queue))
        if not snap.queue:
            self._commit_snapshot(snap)
        return drained

    def _commit_snapshot(self, snap: _ActiveSnapshot) -> None:
        self._check_fence()
        eng = self.engine()
        arenas_ref: Dict[str, Any] = {}
        for name, a in snap.arenas.items():
            meta = dict(a["meta"])
            key_of_row = meta.pop("key_of_row")
            last_use = meta.pop("last_use_tick")
            meta_blob = f"ckpt-{snap.seq:08d}-{name}-meta"
            self.store.put_blob(
                meta_blob,
                {"key_of_row": key_of_row, "last_use_tick": last_use,
                 "shard_next": np.asarray(meta.pop("shard_next"),
                                          np.int64),
                 "live_keys": key_of_row[key_of_row >= 0]},
                meta)
            arenas_ref[name] = {"meta": meta_blob,
                                "parts": snap.parts[name],
                                "kind": a["meta"]["kind"]}
        manifest = dict(self._manifest or {})
        rec = dict(manifest.get("recovery") or
                   {"full": None, "deltas": []})
        entry = {"seq": snap.seq, "tick": snap.tick,
                 "arenas": arenas_ref}
        if snap.timers is not None:
            arrays, tmeta = snap.timers
            timers_blob = f"ckpt-{snap.seq:08d}-__timers"
            snap.bytes += self.store.put_blob(timers_blob, arrays, tmeta)
            entry["timers"] = timers_blob
        old_blobs: List[str] = []
        if snap.kind == "full":
            for prev in ([rec.get("full")] if rec.get("full") else []) \
                    + list(rec.get("deltas") or []):
                for ref in prev["arenas"].values():
                    old_blobs.extend(ref["parts"])
                    old_blobs.append(ref["meta"])
                if prev.get("timers"):
                    old_blobs.append(prev["timers"])
            rec = {"full": entry, "deltas": [], "tick": snap.tick}
            self._last_full_tick = snap.tick
        else:
            rec["deltas"] = list(rec.get("deltas") or []) + [entry]
            rec["tick"] = snap.tick
        manifest["seq"] = snap.seq
        manifest["recovery"] = rec
        manifest["engine"] = {"tick_number": eng.tick_number}
        journal = dict(manifest.get("journal") or {})
        if snap.kind == "full":
            # journal segments wholly before the new full are dead
            for key, j in list(journal.items()):
                keep = [s for s in j["segments"]
                        if s["tick_max"] >= snap.tick]
                for s in j["segments"]:
                    if s not in keep:
                        old_blobs.append(s["blob"])
                journal[key] = {"segments": keep}
        manifest["journal"] = journal
        if self.fence_epoch:
            manifest["fence"] = {"epoch": self.fence_epoch,
                                 "owner": self._fence_owner}
        self.store.commit_manifest(manifest)
        self._manifest = manifest
        for blob in old_blobs:
            self.store.delete_blob(blob)
        self._last_ckpt_tick = snap.tick
        # re-pin the delta baseline against the committed cut
        att = eng.attribution
        for name, arena in eng.arenas.items():
            counts = None
            if att is not None and att.enabled \
                    and att.has_state(name):
                counts = _pin_tree(att.counts_for(name))
            self._delta_pin[name] = (arena.generation,
                                     arena._key_of_row.copy(),
                                     counts, snap.tick)
        if snap.kind == "full":
            self.full_snapshots += 1
        else:
            self.delta_snapshots += 1
        self.rows_written += snap.rows
        self.bytes_written += snap.bytes
        self._active = None
        rec = self._rec()
        if rec is not None:
            rec.plane_span("checkpoint", f"seal {snap.kind}",
                           tick=snap.tick, seq=snap.seq,
                           rows=snap.rows, bytes=snap.bytes)

    def _journal_commit(self, sealed: List[Tuple[Any, str,
                                                 Dict[str, Any]]]) -> None:
        """Acknowledge freshly written journal segment blobs with ONE
        manifest commit (blobs are already durable — the caller wrote
        them first; the commit order every store write in this plane
        follows)."""
        self._check_fence()
        manifest = dict(self._manifest or {})
        journal = dict(manifest.get("journal") or {})
        for site, blob, meta in sealed:
            j = dict(journal.get(site.key) or {"segments": []})
            j["segments"] = list(j["segments"]) + [{
                "seq": site.seq, "blob": blob, "lanes": meta["lanes"],
                "tick_min": meta["tick_min"],
                "tick_max": meta["tick_max"],
            }]
            journal[site.key] = j
        manifest["journal"] = journal
        manifest["seq"] = self._seq
        self._seq += 1
        eng = self.engine()
        manifest["engine"] = {"tick_number": eng.tick_number}
        manifest.setdefault("recovery",
                            {"full": None, "deltas": [], "tick": -1})
        if self.fence_epoch:
            manifest["fence"] = {"epoch": self.fence_epoch,
                                 "owner": self._fence_owner}
        self.store.commit_manifest(manifest)
        self._manifest = manifest
        rec = self._rec()
        if rec is not None:
            rec.plane_span("journal", "segment seal",
                           segments=len(sealed),
                           lanes=sum(int(m["lanes"])
                                     for _, _, m in sealed))

    # -- explicit sync entry points -----------------------------------------

    def checkpoint_full(self) -> Dict[str, Any]:
        """Pin + drain a full snapshot to durable commit, synchronously
        (explicit callers: graceful stop, benches, tests).  The pause
        budget does not apply — the caller asked for completion."""
        return self._checkpoint_sync("full")

    def checkpoint_delta(self) -> Dict[str, Any]:
        return self._checkpoint_sync("delta")

    def _checkpoint_sync(self, kind: str) -> Dict[str, Any]:
        if not self.enabled:
            raise RuntimeError("checkpoint plane has no snapshot store")
        if self._active is not None:
            self.run_slice(0.0)  # finish the in-flight one first
        if kind == "delta" and self._last_full_tick < 0:
            kind = "full"  # a delta needs a base
        t0 = time.perf_counter()
        self.begin(kind)
        snap = self._active
        self.run_slice(0.0)
        assert self._active is None
        return {"kind": snap.kind, "tick": snap.tick,
                "rows": snap.rows, "bytes": snap.bytes,
                "seconds": round(time.perf_counter() - t0, 6)}

    # -- recovery -----------------------------------------------------------

    async def recover(self) -> Dict[str, Any]:
        """Crash recovery: rebuild every arena from the latest committed
        recovery point (host-assembled full columns adopted in one
        transfer each, deltas as one batched scatter per column),
        fold-replay the journal tail (fused windows of consecutive
        journaled ticks where the signature allows; per-tick engine
        calls otherwise), then re-anchor.  Re-anchoring follows
        ``config.recover_reanchor``: "sync" writes a fresh full inside
        recover (the old behavior — restore time then includes a full
        snapshot drain); "defer" leaves the old recovery point and lets
        the periodic cadence re-anchor — a second crash replays the
        same journal tail idempotently from the old cut.  Idempotent
        when the store holds no manifest (fresh deployment)."""
        if not self.enabled:
            return {"recovered": False, "reason": "no snapshot store"}
        manifest = self.store.read_manifest()
        if manifest is None:
            return {"recovered": False, "reason": "no manifest"}
        eng = self.engine()
        t0 = time.perf_counter()
        self._manifest = manifest
        self._seq = int(manifest.get("seq", 0)) + 1
        self.fence_epoch = int(
            (manifest.get("fence") or {}).get("epoch", 0))
        rec = manifest.get("recovery") or {}
        restored_rows = 0
        recovery_tick = int(rec.get("tick", -1))
        entries = [rec["full"]] if rec.get("full") else []
        entries += list(rec.get("deltas") or [])
        for entry in entries:
            for name, ref in entry["arenas"].items():
                restored_rows += self._restore_arena_part(
                    name, ref, base=(entry is entries[0]))
            if entry.get("timers"):
                got = self.store.get_blob(entry["timers"])
                if got is None:
                    raise RuntimeError(
                        f"manifest references missing timers blob "
                        f"{entry['timers']} (commit-order contract "
                        f"broken)")
                eng.timers.restore_entry(got[0], got[1])
        if entries:
            # silent catch-up BEFORE journal fold-replay: fires
            # acknowledged at/before the cut are retired (their effects
            # are in the recovered state), everything due after the cut
            # re-fires during replay exactly once
            eng.timers.finish_restore(recovery_tick)
        # a mesh-shape mismatch between the recording and recovering
        # engines: the snapshot restored at the RECORDED layout — re-lay
        # onto the live mesh now (identity necessarily changes with it)
        for arena in eng.arenas.values():
            if arena.n_shards != eng.n_shards:
                arena.reshard(eng.n_shards, eng.sharding)
        replay = self._load_replay_tail(manifest, recovery_tick)
        self._replaying = True
        try:
            if recovery_tick >= 0:
                eng.tick_number = max(eng.tick_number, recovery_tick)
            replayed, fused_windows, fused_lanes = \
                self._fold_replay(replay)
            await eng.flush()
        finally:
            self._replaying = False
        self.journal.replayed_lanes += replayed
        mt = (manifest.get("engine") or {}).get("tick_number")
        if mt is not None:
            eng.tick_number = max(eng.tick_number, int(mt))
        if str(getattr(eng.config, "recover_reanchor", "sync")) \
                == "defer":
            # no terminal full here: the OLD recovery point stays the
            # anchor and the next cadence full re-anchors outside the
            # outage window.  The tick bump keeps the global
            # (tick, order) replay sort unambiguous across restarts:
            # per-process journal order counters restart at 0, so new
            # appends must land at a strictly later tick than anything
            # replayed above.
            eng.tick_number += 1
            anchor = None
        else:
            # re-anchor synchronously: a second crash recovers from
            # HERE, and the replayed segments are pruned so replay is
            # never applied twice
            anchor = self.checkpoint_full()
        self.restored_rows += restored_rows
        self.last_restore_s = time.perf_counter() - t0
        return {"recovered": True,
                "recovery_tick": recovery_tick,
                "restored_rows": restored_rows,
                "replayed_lanes": replayed,
                "replayed_ticks": len({e['tick'] for e in replay}),
                "fused_windows": fused_windows,
                "fused_lanes": fused_lanes,
                "re_anchor": anchor,
                "seconds": round(self.last_restore_s, 6)}

    def _load_replay_tail(self, manifest: Dict[str, Any],
                          recovery_tick: int,
                          cache: Optional[Dict[str, Any]] = None
                          ) -> List[Dict[str, Any]]:
        """Decode every committed journal entry at/after the cut into
        the global (tick, order) replay order, rebuilding each site's
        seq/committed counters so new segments continue the chain.
        ``cache`` maps blob name → (arrays, meta) for segments already
        staged host-side (the warm-standby tailer)."""
        eng = self.engine()
        replay: List[Dict[str, Any]] = []
        for key, j in (manifest.get("journal") or {}).items():
            for seg in j["segments"]:
                got = (cache or {}).get(seg["blob"]) \
                    or self.store.get_blob(seg["blob"])
                if got is None:
                    raise RuntimeError(
                        f"manifest references missing journal blob "
                        f"{seg['blob']} (commit-order contract broken)")
                arrays, meta = got
                type_name, method = meta["site"]
                for e in DeviceJournal.decode_segment(arrays, meta):
                    if e["tick"] >= recovery_tick:
                        e["type"] = type_name
                        e["method"] = method
                        replay.append(e)
                # rebuild the site's seq/committed counters so new
                # segments continue the chain
                site = self.journal.register(type_name, method)
                site.seq = max(site.seq, seg["seq"] + 1)
                site.committed_lanes += seg["lanes"]
                # the recovered site's append history IS its committed
                # history (ring lanes died with the killed process) —
                # keeps appended == committed + pending true across
                # restarts for the chaos accounting invariant
                site.appended_lanes += seg["lanes"]
                site.committed_tick = max(site.committed_tick,
                                          seg["tick_max"])
                eng._journal_sites.add((type_name, method))
        replay.sort(key=lambda e: (e["tick"], e["order"]))
        return replay

    def _fold_replay(self, replay: List[Dict[str, Any]]
                     ) -> Tuple[int, int, int]:
        """Replay the sorted journal tail.  Runs of consecutive ticks
        with a fusable per-site signature execute as ONE stacked-rows
        fused window (``FusedTickProgram.replay``) instead of a
        per-tick engine call each — preserving original stamps and the
        acknowledged-prefix contract bit-exactly (a window that misses
        rolls back and replays per-tick, the autofuse discipline).
        Returns (replayed_lanes, fused_windows, fused_lanes).  The
        caller holds ``_replaying``."""
        eng = self.engine()
        # group entries by original tick, in order
        ticks: List[Tuple[int, List[Dict[str, Any]]]] = []
        for e in replay:
            if ticks and ticks[-1][0] == e["tick"]:
                ticks[-1][1].append(e)
            else:
                ticks.append((e["tick"], [e]))
        cap = int(getattr(eng.config, "recover_fused_window", 0) or 0)
        can_fuse = (cap > 1 and eng.router is None
                    and not getattr(eng, "_stream_routes", {})
                    and eng.timers.armed_total == 0)
        if can_fuse:
            # emit-destination pre-activation (register_journal's
            # emit_key_args hints): activate the union of hinted key
            # leaves up front so fused windows never roll back on cold
            # emit targets.  Activation is field-inits only — state
            # exactness is unaffected.  Gated on can_fuse so the pure
            # per-tick path keeps its byte-identical row-identity
            # behavior.
            buckets: Dict[str, List[np.ndarray]] = {}
            for e in replay:
                names = self._emit_key_args.get((e["type"], e["method"]))
                if not names or not isinstance(e["args"], dict):
                    continue
                for nm in names:
                    leaf = e["args"].get(nm)
                    if leaf is not None:
                        buckets.setdefault(e["type"], []).append(
                            np.asarray(leaf).reshape(-1))
            for type_name, chunks in buckets.items():
                keys = np.unique(np.concatenate(chunks)
                                 .astype(np.int64))
                eng.arena_for(type_name).resolve_rows(keys)
        replayed = 0
        fused_windows = 0
        fused_lanes = 0
        # compiled-window reuse across the tail: windows with the same
        # (T, site order, lane widths, args skeleton) re-run ONE
        # program with swapped injections instead of re-tracing — on a
        # long tail the trace/compile cost is paid once, not per
        # window (rows/masks ride as runtime inputs, so the trace
        # never baked the keys; arena growth still re-traces via the
        # generation discipline in prepare())
        prog_cache: Dict[Tuple, Any] = {}
        i = 0
        while i < len(ticks):
            j = self._fused_run_end(ticks, i, cap) if can_fuse else i
            if j - i > 1:
                lanes, was_fused = self._replay_window(ticks[i:j],
                                                       prog_cache)
                replayed += lanes
                if was_fused:
                    fused_windows += 1
                    fused_lanes += lanes
                i = j
                continue
            tick, entries = ticks[i]
            eng.tick_number = tick  # stamps match the original run
            for e in entries:
                eng.enqueue_local_batch(e["type"], e["method"],
                                        e["keys"], e["args"])
                replayed += len(e["keys"])
            eng.run_tick()
            i += 1
        self.replay_fused_windows += fused_windows
        self.replay_fused_lanes += fused_lanes
        return replayed, fused_windows, fused_lanes

    @staticmethod
    def _entry_sig(e: Dict[str, Any]) -> Tuple:
        leaves, treedef = jax.tree_util.tree_flatten(e["args"])
        return (len(e["keys"]), treedef,
                tuple((np.shape(lf), np.asarray(lf).dtype.str)
                      for lf in leaves))

    def _fused_run_end(self, ticks, i: int, cap: int) -> int:
        """Longest run [i, j) of CONSECUTIVE ticks a single stacked
        window can replay: per-site lane width and args skeleton stay
        constant wherever the site appears, at most one entry per
        (site, tick), intra-tick site order embeds into one canonical
        order, and no touched source arena holds replica groups (their
        lane-hash spread is per-batch — per-tick replay keeps it
        exact)."""
        eng = self.engine()
        sigs: Dict[Tuple[str, str], Tuple] = {}
        canonical: List[Tuple[str, str]] = []
        j = i
        while j < len(ticks) and j - i < cap:
            tick, entries = ticks[j]
            if j > i and tick != ticks[j - 1][0] + 1:
                break
            seen = set()
            pos = -1
            ok = True
            for e in entries:
                site = (e["type"], e["method"])
                if site in seen:
                    ok = False
                    break
                seen.add(site)
                sig = self._entry_sig(e)
                if sigs.setdefault(site, sig) != sig:
                    ok = False
                    break
                if site in canonical:
                    p = canonical.index(site)
                    if p <= pos:
                        ok = False
                        break
                    pos = p
                else:
                    try:
                        arena = eng.arena_for(e["type"])
                    except Exception:
                        ok = False
                        break
                    if arena._replicas:
                        ok = False
                        break
                    canonical.insert(pos + 1, site)
                    pos += 1
            if not ok:
                break
            j += 1
        return max(j, i)

    def _replay_window(self, group,
                       prog_cache: "Optional[Dict[Tuple, Any]]" = None
                       ) -> Tuple[int, bool]:
        """One stacked-rows fused window over consecutive journaled
        ticks.  Exactness contract: snapshot (plain references —
        undonated) after prepare, run, verify; a nonzero miss count
        rolls everything back (state, counters, ledger, attribution)
        and replays the window per-tick unfused.  ``prog_cache`` maps
        window signatures to built programs so same-shaped windows
        later in the tail skip the trace/compile.  Returns
        (replayed_lanes, ran_fused)."""
        from orleans_tpu.tensor.fused import FusedTickProgram
        eng = self.engine()
        first_tick = group[0][0]
        T = len(group)
        by_site: Dict[Tuple[str, str], Dict[int, Dict]] = {}
        order: List[Tuple[str, str]] = []
        lanes_total = 0
        for t, (tick, entries) in enumerate(group):
            pos = -1
            for e in entries:
                site = (e["type"], e["method"])
                if site not in by_site:
                    by_site[site] = {}
                    order.insert(pos + 1, site)
                    pos += 1
                else:
                    pos = order.index(site)
                by_site[site][t] = e
                lanes_total += len(e["keys"])
        if all(len(entries) <= 1 for _, entries in group):
            # no tick sequences two sites, so the order list carries no
            # intra-tick constraint — sort it canonically so windows
            # that merely ENCOUNTER sites in a different order share a
            # cache signature (and a compiled program)
            order.sort()
        sites = []
        stackeds = []
        for site in order:
            per_tick = by_site[site]
            example = next(iter(per_tick.values()))
            m = len(example["keys"])
            keys2d = np.full((T, m), -1, dtype=np.int64)
            mask2d = np.zeros((T, m), dtype=bool)
            for t, e in per_tick.items():
                keys2d[t] = np.asarray(e["keys"], np.int64)
                mask2d[t] = True
            ex_leaves, treedef = jax.tree_util.tree_flatten(
                example["args"])
            stacked_leaves = []
            for li, ex in enumerate(ex_leaves):
                ex = np.asarray(ex)
                buf = np.zeros((T, *ex.shape), dtype=ex.dtype)
                for t, e in per_tick.items():
                    buf[t] = np.asarray(
                        jax.tree_util.tree_leaves(e["args"])[li])
                stacked_leaves.append(buf)
            args_stacked = jax.tree_util.tree_unflatten(
                treedef, stacked_leaves)
            if not isinstance(args_stacked, dict):
                # reserved leaves ride a dict — non-dict arg trees fall
                # back to per-tick replay
                return self._replay_group_per_tick(group), False
            sites.append((site[0], site[1], keys2d, mask2d))
            stackeds.append(dict(args_stacked))
        sig = (T, tuple(
            (tn, m, k2.shape[1],
             tuple(sorted((name, np.shape(lf), np.asarray(lf).dtype.str)
                          for name, lf in st.items())))
            for (tn, m, k2, _mk), st in zip(sites, stackeds)))
        prog = prog_cache.get(sig) if prog_cache is not None else None
        if prog is not None:
            # same window shape as an earlier one: swap the injections
            # into the cached program's sources and re-resolve — rows
            # and masks are runtime inputs, so the compiled trace is
            # reusable as-is (prepare() still re-traces if the resolve
            # grew an arena, the generation discipline)
            for src, (_tn, _m, k2, mk) in zip(prog.sources, sites):
                src.keys2d = np.asarray(k2, dtype=np.int64)
                src.mask2d = np.asarray(mk, dtype=bool)
                src.keys = (np.unique(src.keys2d[src.mask2d])
                            if src.mask2d.any()
                            else np.empty(0, dtype=np.int64))
                src.refresh_rows()
        else:
            try:
                prog = FusedTickProgram.replay(eng, sites)
            except KeyError:
                return self._replay_group_per_tick(group), False
            # undonated: rollback snapshots stay plain references
            prog.donate = False
            if prog_cache is not None:
                prog_cache[sig] = prog
        statics = [{} for _ in sites]
        for si, s in enumerate(prog.sources):
            stackeds[si]["__rows__"] = jnp.asarray(s.rows2d)
            stackeds[si]["__mask__"] = jnp.asarray(s.mask2d)
        multi = len(sites) > 1
        stacked_arg = stackeds if multi else stackeds[0]
        static_arg = statics if multi else statics[0]
        # prepare BEFORE the snapshot: source resolution/discovery can
        # activate keys and GROW an arena — a post-snapshot grow would
        # make the snapshot unrestorable (the autofuse discipline)
        prog.prepare(stacked_arg, static_arg)
        for si, s in enumerate(prog.sources):
            stackeds[si]["__rows__"] = jnp.asarray(s.rows2d)
            stackeds[si]["__mask__"] = jnp.asarray(s.mask2d)
        snapshot = {n: dict(eng.arena_for(n).state)
                    for n in prog._touched}
        counters = (eng.tick_number, eng.ticks_run,
                    eng.messages_processed)
        ledger_state = eng.ledger.snapshot_state()
        attr_state = eng.attribution.snapshot_state()
        eng.tick_number = first_tick  # stamps match the original run
        prog.run(stacked_arg, static_arg)
        if prog.verify() == 0:
            return lanes_total, True
        # non-exact window (cold emit destination the hints didn't
        # cover, fan-out overflow): roll back and replay per-tick —
        # the slow path that keeps transparency exact
        for n, cols in snapshot.items():
            eng.arena_for(n).adopt_state(cols)
        (eng.tick_number, eng.ticks_run,
         eng.messages_processed) = counters
        if ledger_state is not None:
            eng.ledger.restore_state(ledger_state)
        if attr_state is not None:
            eng.attribution.restore_state(attr_state)
        return self._replay_group_per_tick(group), False

    def _replay_group_per_tick(self, group) -> int:
        eng = self.engine()
        lanes = 0
        for tick, entries in group:
            eng.tick_number = tick
            for e in entries:
                eng.enqueue_local_batch(e["type"], e["method"],
                                        e["keys"], e["args"])
                lanes += len(e["keys"])
            eng.run_tick()
        return lanes

    def _restore_arena_part(self, name: str, ref: Dict[str, Any],
                            base: bool, store: Optional[Any] = None,
                            replace: bool = False) -> int:
        """Land one manifest entry's arena part.  FULL entries take the
        fast device path: every state column is assembled at full
        capacity in vectorized numpy (field init + one fancy-index
        placement per part) and adopted with ONE ``device_put`` per
        column (``arena.adopt_columns``) — no per-chunk scatters, no
        wasted init allocation (``adopt_layout(init_columns=False)``).
        DELTA entries concatenate all parts and land as ONE batched
        scatter per column.  ``store`` overrides the plane's own store
        (warm-standby tailing); ``replace`` permits full adoption over
        a non-empty arena (standby re-base onto a newer full)."""
        store = store if store is not None else self.store
        got = store.get_blob(ref["meta"])
        if got is None:
            raise RuntimeError(
                f"manifest references missing snapshot blob "
                f"{ref['meta']} (commit-order contract broken)")
        meta_arrays, meta = got
        eng = self.engine()
        arena = eng.arena_for(name)
        parts = []
        for blob in ref["parts"]:
            got = store.get_blob(blob)
            if got is None:
                raise RuntimeError(
                    f"manifest references missing snapshot blob {blob}")
            parts.append(got[0])
        restored = 0
        if base or ref.get("kind") == "full":
            arena.adopt_layout(meta, meta_arrays["key_of_row"],
                               meta_arrays["last_use_tick"],
                               meta_arrays["shard_next"],
                               init_columns=False, replace=replace)
            capacity = arena.capacity
            part_rows = [np.asarray(p["__rows"], np.int64)
                         for p in parts]
            restored = sum(len(r) for r in part_rows)
            columns: Dict[str, np.ndarray] = {}
            for fname, f in arena.info.state_fields.items():
                col = np.full((capacity, *f.shape), f.init,
                              dtype=f.dtype)
                for p, rows in zip(parts, part_rows):
                    col[rows] = np.asarray(p[fname], dtype=f.dtype)
                columns[fname] = col
            last_dev = np.zeros(capacity, dtype=np.int32)
            for p, rows in zip(parts, part_rows):
                last_dev[rows] = np.asarray(p["__last_use_dev"],
                                            np.int32)
            arena.adopt_columns(columns, last_dev)
        else:
            # deltas within one generation: rows never moved, so the
            # recorded row ids land EXACTLY (evict + slot-reuse between
            # base and delta included) — free dead keys, re-home moved
            # ones, place the dirty set at its recorded rows, then ONE
            # batched scatter per column over the concatenated parts
            all_rows = np.concatenate(
                [p["__rows"] for p in parts]) if parts \
                else np.empty(0, np.int64)
            all_keys = np.concatenate(
                [p["__keys"] for p in parts]) if parts \
                else np.empty(0, np.int64)
            arena.adopt_delta(meta, all_rows, all_keys,
                              meta_arrays["live_keys"],
                              meta_arrays["shard_next"],
                              meta_arrays["last_use_tick"])
            if parts:
                columns = {
                    fname: np.concatenate(
                        [np.asarray(p[fname]) for p in parts])
                    for fname in arena.info.state_fields}
                last_dev = np.concatenate(
                    [np.asarray(p["__last_use_dev"]) for p in parts])
                arena.scatter_restore(all_rows, columns, last_dev)
                restored = len(all_rows)
        return restored

    # -- observability ------------------------------------------------------

    def pause_p99_s(self) -> float:
        if not self.pauses:
            return 0.0
        return float(np.percentile(np.asarray(self.pauses), 99))

    def age_ticks(self) -> int:
        """Ticks since the last committed recovery point — the live
        loss-window gauge (ckpt.age_ticks)."""
        if not self.enabled or self._last_ckpt_tick < 0:
            return -1
        return int(self.engine().tick_number - self._last_ckpt_tick)

    def snapshot(self) -> Dict[str, Any]:
        return {
            "enabled": self.enabled,
            "full_snapshots": self.full_snapshots,
            "delta_snapshots": self.delta_snapshots,
            "rows_written": self.rows_written,
            "bytes_written": self.bytes_written,
            "restored_rows": self.restored_rows,
            "last_restore_s": round(self.last_restore_s, 6),
            "last_dirty_rows": self.last_dirty_rows,
            "age_ticks": self.age_ticks(),
            "pause_p99_s": round(self.pause_p99_s(), 6),
            "max_pause_s": round(self.max_pause_s, 6),
            "in_progress": self._active.kind
            if self._active is not None else None,
            "replay_fused_windows": self.replay_fused_windows,
            "replay_fused_lanes": self.replay_fused_lanes,
            "promotions": self.promotions,
            "last_rto_s": round(self.last_rto_s, 6),
            "journal": self.journal.snapshot(),
        }


class StandbyTailer:
    """Warm-standby log shipping over the primary's ``SnapshotStore``.

    A standby engine tails the primary's committed recovery entries
    (fulls + deltas, adopted straight into its arenas) and sealed
    journal segments (staged host-side only — a delta records absolute
    values at its cut, so applying journaled ticks the next delta
    already covers would double-count).  The standby therefore holds
    an adopted-but-not-serving arena within one seal of the durable
    horizon, and ``promote()`` only has to fence the store and replay
    the staged tail — no full restore inside the outage window.

    Contract with the primary: everything flows through the existing
    blobs-first / manifest-last commit order, so every blob a manifest
    names is readable by the time the tailer sees the manifest.  The
    only race is PRUNING (the primary deletes superseded blobs after
    committing a new full); a missing blob mid-poll just resets the
    tailer, and the next poll re-bases onto the newer full.
    """

    def __init__(self, engine, store: SnapshotStore) -> None:
        self._engine = weakref.ref(engine)
        self.store = store
        self._manifest: Optional[Dict[str, Any]] = None
        self._adopted_seqs: set = set()
        self._adopted_tick = -1
        self._full_seq = -1
        # blob name -> (arrays, meta): sealed journal segments staged
        # host-side, handed to _load_replay_tail as its cache at
        # promotion time
        self._staged: Dict[str, Any] = {}
        self._staged_tick = -1
        self._staged_timers: List[Tuple[Any, Any]] = []
        self.polls = 0
        self.adopted_rows = 0
        self.adopted_entries = 0
        self.resets = 0
        self.promoted = False
        self.last_promote_s = 0.0

    def _reset(self) -> None:
        self._adopted_seqs.clear()
        self._staged_timers = []
        self._adopted_tick = -1
        self._full_seq = -1

    def poll(self) -> Dict[str, Any]:
        """One tailing step: adopt any recovery entries newer than what
        this standby holds, stage any newly sealed journal segments.
        Cheap no-op when nothing changed."""
        self.polls += 1
        manifest = self.store.read_manifest()
        if manifest is None:
            return {"adopted_entries": 0, "staged_segments": 0}
        self._manifest = manifest
        plane = self._engine().checkpointer
        rec = manifest.get("recovery") or {}
        entries = [rec["full"]] if rec.get("full") else []
        entries += list(rec.get("deltas") or [])
        adopted = 0
        try:
            if entries and int(entries[0]["seq"]) != self._full_seq:
                # a newer full supersedes everything adopted so far:
                # re-base (replace=True full adoption over the live
                # arena) and re-stage its timers chain from scratch
                self._reset()
                self._full_seq = int(entries[0]["seq"])
            for entry in entries:
                seq = int(entry["seq"])
                if seq in self._adopted_seqs:
                    continue
                is_base = entry is entries[0]
                for name, ref in entry["arenas"].items():
                    self.adopted_rows += plane._restore_arena_part(
                        name, ref, base=is_base, store=self.store,
                        replace=is_base)
                if entry.get("timers"):
                    got = self.store.get_blob(entry["timers"])
                    if got is None:
                        raise RuntimeError(
                            f"standby: timers blob {entry['timers']} "
                            f"pruned mid-poll")
                    self._staged_timers.append(got)
                self._adopted_seqs.add(seq)
                self._adopted_tick = max(self._adopted_tick,
                                         int(entry["tick"]))
                self.adopted_entries += 1
                adopted += 1
        except RuntimeError:
            # prune race: the primary committed a new full and deleted
            # the blobs under us — drop everything, next poll re-bases
            self._reset()
            self.resets += 1
            return {"adopted_entries": 0, "staged_segments": 0,
                    "reset": True}
        staged = 0
        live_blobs = set()
        for key, j in (manifest.get("journal") or {}).items():
            for seg in j["segments"]:
                live_blobs.add(seg["blob"])
                if seg["blob"] in self._staged:
                    continue
                got = self.store.get_blob(seg["blob"])
                if got is None:
                    continue  # pruned already; harmless, skip
                self._staged[seg["blob"]] = got
                self._staged_tick = max(self._staged_tick,
                                        int(seg["tick_max"]))
                staged += 1
        # drop staged segments a new full made dead
        for blob in list(self._staged):
            if blob not in live_blobs:
                del self._staged[blob]
        if adopted or staged:
            eng = self._engine()
            rec = None if eng is None else eng._span_recorder()
            if rec is not None:
                rec.plane_span("standby", "tail poll",
                               adopted_entries=adopted,
                               staged_segments=staged,
                               lag_ticks=self.lag_ticks())
        return {"adopted_entries": adopted, "staged_segments": staged}

    def lag_ticks(self) -> int:
        """How far this standby trails the durable horizon, in ticks:
        (latest committed recovery/segment tick) - (latest tick this
        standby has adopted or staged).  ``-1`` until the first
        manifest is seen (no primary to trail yet)."""
        if self._manifest is None:
            return -1
        rec = self._manifest.get("recovery") or {}
        durable = int(rec.get("tick", -1))
        for key, j in (self._manifest.get("journal") or {}).items():
            for seg in j["segments"]:
                durable = max(durable, int(seg["tick_max"]))
        held = max(self._adopted_tick, self._staged_tick)
        if durable < 0:
            return 0
        return max(0, durable - held)

    async def promote(self, owner: str = "") -> Dict[str, Any]:
        """Take over the primary's range: fence the store (the old
        primary's next commit fails with FencedError), catch up the
        last committed entries, restore staged timers, fold-replay only
        the un-adopted journal tail, and leave the engine serving at
        the durable horizon.  Deliberately does NOT write a terminal
        full — the periodic cadence re-anchors outside the outage
        window, which is what keeps RTO at tail-replay cost."""
        eng = self._engine()
        plane = eng.checkpointer
        t0 = time.perf_counter()
        plane.attach_store(self.store)
        epoch = plane.acquire_fence(owner or "standby")
        # final catch-up under the fence: anything the old primary
        # committed before the fence landed is adopted/staged here;
        # anything after it could never commit
        self.poll()
        manifest = plane._manifest
        if self._staged_timers:
            for arrays, tmeta in self._staged_timers:
                eng.timers.restore_entry(arrays, tmeta)
            eng.timers.finish_restore(self._adopted_tick)
        for arena in eng.arenas.values():
            if arena.n_shards != eng.n_shards:
                arena.reshard(eng.n_shards, eng.sharding)
        replay = plane._load_replay_tail(
            manifest, self._adopted_tick, cache=self._staged)
        plane._replaying = True
        try:
            if self._adopted_tick >= 0:
                eng.tick_number = max(eng.tick_number,
                                      self._adopted_tick)
            replayed, fused_windows, fused_lanes = \
                plane._fold_replay(replay)
            await eng.flush()
        finally:
            plane._replaying = False
        plane.journal.replayed_lanes += replayed
        mt = (manifest.get("engine") or {}).get("tick_number")
        if mt is not None:
            eng.tick_number = max(eng.tick_number, int(mt))
        # same defer-re-anchor tick bump as recover(): per-process
        # journal order counters restart at 0, so post-promotion
        # appends must land strictly after everything replayed
        eng.tick_number += 1
        plane.restored_rows += self.adopted_rows
        plane.promotions += 1
        self.promoted = True
        self.last_promote_s = time.perf_counter() - t0
        plane.last_rto_s = self.last_promote_s
        rec = eng._span_recorder()
        if rec is not None:
            rec.plane_span("standby", "promote",
                           duration=self.last_promote_s,
                           fence_epoch=epoch,
                           adopted_rows=self.adopted_rows,
                           replayed_lanes=replayed)
        return {"promoted": True,
                "fence_epoch": epoch,
                "adopted_tick": self._adopted_tick,
                "adopted_rows": self.adopted_rows,
                "replayed_lanes": replayed,
                "fused_windows": fused_windows,
                "fused_lanes": fused_lanes,
                "seconds": round(self.last_promote_s, 6)}

    def snapshot(self) -> Dict[str, Any]:
        return {"polls": self.polls,
                "adopted_entries": self.adopted_entries,
                "adopted_rows": self.adopted_rows,
                "adopted_tick": self._adopted_tick,
                "staged_segments": len(self._staged),
                "lag_ticks": self.lag_ticks(),
                "resets": self.resets,
                "promoted": self.promoted,
                "last_promote_s": round(self.last_promote_s, 6)}
